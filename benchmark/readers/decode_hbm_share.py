"""Share of the HBM roofline one cached decode iteration reaches, in percent:
the bytes the MEAN step of a call must stream (``costs.decode_step_bytes``
with the self slabs at their mean written length) at the peak bandwidth,
over the device time of one iteration.  The iteration time comes from the
trace: the decode loop is the longest ``while`` container on the device, and
it runs ``max_new_tokens`` iterations (random weights: no row ends early):
a mean time.  Iteration ``i`` of ``n`` has ``i`` positions of the self slabs
written and writes its own, so it must read ``i + 1``; the mean over ``i =
0 .. n - 1`` is ``(n + 1) / 2``: 64.5 of a slab of 129 at 128 new tokens."""

from benchmark import costs


def read(rc):
    f = rc.facts
    if rc.trace is None or rc.peak is None:
        return None
    loop_s = rc.trace.longest_container("while")
    if not loop_s:
        return None
    steps = f["max_new_tokens"]
    need = costs.decode_step_bytes(
        rc.cfg, f["rows_per_block"], f["encoder_len"], (steps + 1) / 2)
    floor_s = need["total_bytes"] / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / (loop_s / steps)
