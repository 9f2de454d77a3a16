"""Share of the HBM roofline the grouped expert product reaches, in percent:
the bytes one call of the kernel streams (one matrix of every expert the
step touched: ``costs_moe.expert_matrix_bytes`` times the experts a layer
streamed a step, which the engine counts) at the peak bandwidth, over the
median device time of the kernel's calls in the traced window.  The kernel
is found by its name in the device trace (``kernel``: a regular expression
over operation names).  No such operation: nothing to read."""

import re

from benchmark import costs_moe, stats


def read(rc, kernel):
    if rc.trace is None or rc.peak is None:
        return None
    touched = rc.facts.get("moe_experts_streamed_per_layer_step")
    if not touched or "num_experts" not in rc.cfg:
        return None
    pat = re.compile(kernel)
    dev = rc.trace.devices[min(rc.trace.devices)]
    xs = [e - s for n, s, e in dev.ops if pat.match(n)]
    if not xs:
        return None
    floor_s = (touched * costs_moe.expert_matrix_bytes(rc.cfg)
               / rc.peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / stats.percentile(xs, 0.5)
