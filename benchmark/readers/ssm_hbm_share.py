"""Share of the HBM roofline one paged decode step of a hybrid state-space /
attention LM reaches, in percent: the bytes the step streams
(``costs_ssm.decode_step_bytes``, from the published shapes alone: every
weight once, the recurrent state of every slot twice, the attention layers'
K/V at the full ``slot_len``) at the peak bandwidth, over the median device
time of one execution of the decode program (``module``, found on the
capture's ``XLA Modules`` line as ``module_hbm_share`` finds it).  Not this
family's configuration, no such line or no such program (an older tree):
nothing to read.

NO METRIC NAMES THIS READER since PR 61: it prices every slot's state and
K/V at ``slot_len`` over a MEDIAN time, where ``ssm_roofline`` (part
``step``) reads what the captured steps had live, mean over mean.
It stays because ``tests/test_benchmark_ssm.py`` (tier-1, not a benchmark
PR's to edit) pins it: a PR that may edit that file moves the test, and the
next benchmark PR deletes this file (PERF.md 7)."""

from benchmark import costs_ssm, spans, stats
from benchmark.readers.module_hbm_share import module_durations


def read(rc, module):
    if rc.trace is None or rc.peak is None:
        return None
    if "num_slots" not in rc.facts or "mamba_d_state" not in rc.cfg:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    from jax.profiler import ProfileData

    xs = module_durations(ProfileData.from_file(path), module)
    if not xs:
        return None
    need = costs_ssm.decode_step_bytes(
        rc.cfg, rc.facts["num_slots"], rc.facts["slot_len"])
    floor_s = need["total_bytes"] / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / stats.percentile(xs, 0.5)
