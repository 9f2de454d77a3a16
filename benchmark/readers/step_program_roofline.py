"""Share of the HBM roofline a PART of ONE of the step's programs reaches
over a capture, in percent: the bytes the part had to move in an execution
of ``module`` (``part``, below, priced for the program's OWN count a step:
the kind's fact by program, the engine's counter over the profiler's own
window divided by that program's steps in it) at the peak bandwidth, over
the MEAN device time, an execution of that program, of the operations traced
under ``scope`` (``mla_scope_roofline.time_per_execution``: the union of
their intervals inside each whole execution).  A mean count over a mean
time, both of the same executions, is their total bytes over their total
time, which cannot pass the peak; a mean count over a MEDIAN time can,
where the steps of a program differ (a decode step with one row live or
with fourteen), and a capture placed in a lull read so (PERF.md, PR 58).
Each program is its own metric: what the capture holds most of decides
nothing.

* ``part = "mhc_streams"``: ``costs_mhc.stream_bytes`` for the rows that
  held a token a step (fact ``mhc_stream_rows_per_step``);
* ``part = "held_experts"``: three matrices of each held expert the
  program's steps touched (fact ``moe_held_experts_streamed_per_step``,
  ``costs_mla.held_expert_bytes``).

Not this family's configuration (no ``hc_mult``), no capture, no execution
of the program, no operation in the scope or no count for the program (a
tree from before PR 58 has neither the cell nor the counter): nothing to
read."""

from benchmark import costs_mhc, costs_mla, scopes, spans
from benchmark.readers.mla_scope_roofline import time_per_execution

_BYTES = {"bfloat16": 2, "float32": 4}
_FACT = {"mhc_streams": "mhc_stream_rows_per_step",
         "held_experts": "moe_held_experts_streamed_per_step"}


def read(rc, part, scope, module):
    if rc.trace is None or rc.peak is None or rc.cfg.get("hc_mult", 1) < 2:
        return None
    count = (rc.facts.get(_FACT[part]) or {}).get(module)
    path = spans.newest_xplane()
    if count is None or path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    whole = time_per_execution(planes[min(planes)], module, scope)
    if not whole:
        return None
    bytes_el = _BYTES.get(rc.traffic.get("dtype"), 2)
    need = (costs_mhc.stream_bytes(rc.cfg, count, bytes_el)
            if part == "mhc_streams"
            else costs_mla.held_expert_bytes(rc.cfg, count, bytes_el))
    return (100.0 * need / rc.peak["hbm_bytes_per_s"]
            / (sum(whole) / len(whole)))
