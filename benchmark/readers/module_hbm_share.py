"""Share of the HBM roofline one paged decode step of a sparse-expert LM
reaches, in percent: the bytes the step MUST move for what it had live
(``costs_moe.live_step_bytes``: the weights from shapes alone, K/V at the
cached positions the captured steps' rows held) at the peak bandwidth, over
the MEAN device time of one execution of the decode program.  A capture's
device plane lists program executions on its ``XLA Modules`` line by the
jitted function's name, ``jit_<module>(<fingerprint>)``; the chunk program
and the mixed step run between decode steps under other names and are left
out, and the first and the last execution, which the capture's edges may
cut, too.

The live count is the kind's fact ``lm_kv_positions_per_step[module]``: the
replica's ``worker_hooks.ReadWatch`` over the profiler's own window (every
step of the program READ between the capture's start and its stop: the sum
over its rows of ``pos + 1``, exact, over those steps).  A MEAN count over
the MEAN time of the same executions is their total bytes over their total
time, which cannot pass the peak; a mean count over a MEDIAN time can, where
a program's steps differ (``step_program_roofline`` has the case, PERF.md PR
58).  No such line, no such program, or no count (an untraced run, a tree
whose engine the watch does not fit): nothing to read."""

import re

from benchmark import costs_moe, scopes, spans, xplane

MODULES_LINE = "XLA Modules"
#: bytes an element of the traffic file's ``dtype``
BYTES_EL = {"bfloat16": 2, "float32": 4}


def whole_executions(plane, module):
    """Device seconds of each execution of ``jit_<module>`` on a
    ``scopes.DevicePlane``, the first and the last (which the capture's
    edges may cut) left out; empty without such a program."""
    program = plane.program_id(module)
    if program is None:
        return []
    name = f"jit_{module}({program})"
    runs = [end - start for ident, start, end in sorted(
        plane.modules, key=lambda ev: ev[1])
        if plane.metadata[ident]["name"] == name]
    return runs[1:-1] or runs


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
    if rc.trace is None or rc.peak is None or "num_experts" not in rc.cfg:
        return None
    live = (rc.facts.get("lm_kv_positions_per_step") or {}).get(module)
    path = spans.newest_xplane()
    if live is None or path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    whole = whole_executions(planes[min(planes)], module)
    if not whole:
        return None
    need = costs_moe.live_step_bytes(
        rc.cfg, live, BYTES_EL.get(rc.traffic.get("dtype"), 2))["total_bytes"]
    return (100.0 * need / rc.peak["hbm_bytes_per_s"]
            / (sum(whole) / len(whole)))
