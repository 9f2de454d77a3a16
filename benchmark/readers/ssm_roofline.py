"""Share of the HBM roofline a decode step of a hybrid state-space /
attention LM (``model_type: jamba``), or its state update, reaches over a
capture, in percent: the bytes ``part`` MUST move for what the captured
steps had live (``benchmark/costs_ssm.py``) at the peak bandwidth, over the
MEAN device time of an execution of the program ``module``.

The live counts are the ENGINE'S own over the profiler's window (the kind's
facts ``ssm_rows_live_per_step`` / ``ssm_positions_live_per_step``:
``stats()['ssd_rows_live']`` and ``['ssd_positions_live']``, the rows whose
state a step advanced and the cached positions they held, over
``['steps_issued']``, each read once the capture has started and before it
is stopped).  The engine counts them as a step is issued and does not split
them by program: the mean is over the decode steps and the mixed steps
together, whose rows are the same population (a mixed step is a decode step
that carries a chunk beside its rows).  A MEAN count over the MEAN time of
the same executions is their total bytes over their total time, which cannot
pass the peak; a mean count over a MEDIAN time can
(``step_program_roofline`` has why).

* ``part = "step"`` (``scope`` None): ``costs_ssm.live_step_bytes``: every
  weight once, the state and convolution tail of the live rows twice, the
  attention layers' K/V at the live positions; over whole executions of the
  program on the capture's ``XLA Modules`` line (the first and the last,
  which the capture's edges may cut, left out): the share of the WHOLE step.
* ``part = "state_update"`` (with ``scope``): 2 x the float32 state of the
  live rows (``costs_ssm.state_bytes(..., tail_el=0)``: the bf16 convolution
  tail moves under ``ssm_conv``, outside the update's scope, and is left
  out) over the time, an execution, of the operations traced under ``scope``
  (``mla_scope_roofline.time_per_execution``: by scope and not by kernel
  name, so it reads the same work whatever implements it).

Not this family's configuration, no capture, no execution of the program, no
operation in the scope or no counts (an untraced run, an older tree):
nothing to read."""

from benchmark import costs_ssm, scopes, spans
from benchmark.readers.mla_scope_roofline import time_per_execution
from benchmark.readers.module_hbm_share import BYTES_EL, whole_executions


def need_bytes(part, cfg, rows, positions, bytes_el):
    if part == "step":
        return costs_ssm.live_step_bytes(cfg, rows, positions,
                                         bytes_el)["total_bytes"]
    if part == "state_update":
        return 2 * costs_ssm.state_bytes(cfg, rows, tail_el=0)
    raise ValueError(f"part {part!r}")


def read(rc, part, module, scope=None):
    if (rc.trace is None or rc.peak is None
            or "mamba_d_state" not in rc.cfg):
        return None
    rows = rc.facts.get("ssm_rows_live_per_step")
    positions = rc.facts.get("ssm_positions_live_per_step")
    path = spans.newest_xplane()
    if rows is None or positions is None or path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    plane = planes[min(planes)]
    whole = (whole_executions(plane, module) if scope is None
             else time_per_execution(plane, module, scope))
    if not whole:
        return None
    need = need_bytes(part, rc.cfg, rows, positions,
                      BYTES_EL.get(rc.traffic.get("dtype"), 2))
    if not need:
        return None
    return (100.0 * need / rc.peak["hbm_bytes_per_s"]
            / (sum(whole) / len(whole)))
