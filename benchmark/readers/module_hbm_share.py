"""Share of the HBM roofline one paged decode step of a sparse-expert LM
reaches, in percent: the bytes the step streams (``costs_moe.
decode_step_bytes``, from shapes alone) at the peak bandwidth, over the
median device time of one execution of the decode program.  A capture's
device plane lists program executions on its ``XLA Modules`` line by the
jitted function's name, ``jit_<module>(<fingerprint>)``; the chunk program
runs between decode steps under another name and is left out.  No such line
or no such program (an older tree): nothing to read."""

import re

from benchmark import costs_moe, spans, stats, xplane

MODULES_LINE = "XLA Modules"


def module_durations(data, name: str):
    pat = re.compile(r"^jit_" + re.escape(name) + r"(\(|$)")
    planes = sorted((p for p in data.planes
                     if xplane.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    if not planes:
        return []
    return [ev.duration_ns * 1e-9 for line in planes[0].lines
            if line.name == MODULES_LINE
            for ev in line.events if pat.match(ev.name)]


def read(rc, module):
    if rc.trace is None or rc.peak is None:
        return None
    if "num_slots" not in rc.facts or "num_experts" not in rc.cfg:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    from jax.profiler import ProfileData

    xs = module_durations(ProfileData.from_file(path), module)
    if not xs:
        return None
    need = costs_moe.decode_step_bytes(
        rc.cfg, rc.facts["num_slots"], rc.facts["slot_len"])
    floor_s = need["total_bytes"] / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / stats.percentile(xs, 0.5)
