"""Share of the HBM roofline one cached decode iteration reaches, in percent:
the bytes the step must stream (``costs.decode_step_bytes``) at the peak
bandwidth, over the device time of one iteration.  The iteration time comes
from the trace: the decode loop is the longest ``while`` container on the
device, and it runs ``max_new_tokens`` iterations (random weights: no row
ends early)."""

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
        rc.cfg, f["rows_per_block"], f["encoder_len"], steps + 1)
    floor_s = need["total_bytes"] / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / (loop_s / steps)
