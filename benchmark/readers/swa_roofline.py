"""Share of the HBM roofline a PART of ONE of the step's programs reaches
over a capture of a ``laguna`` cell, in percent: the bytes the part had to
move in an execution of ``module`` (``part``, below, priced by
``benchmark/costs_swa.py`` for the program's OWN count a step: the kind's
fact by program, the engine's counter over the profiler's own window divided
by that program's steps in it) at the peak bandwidth, over the MEAN device
time of an execution of that program: of the whole execution (``scope``
None: the program's events on the capture's ``XLA Modules`` line), or of the
operations traced under ``scope`` (``mla_scope_roofline.time_per_execution``:
the union of their intervals inside each whole execution).  A mean count over
a mean time, both of the same executions, is their total bytes over their
total time, which cannot pass the peak (``step_program_roofline`` has why
not a median).

* ``part = "step"``: ``costs_swa.step_bytes``: the weights but the
  embedding, the experts streamed, the live ring and page positions;
* ``part = "experts"``: three matrices of each expert the program's steps
  touched (fact ``swa_experts_streamed_per_step``);
* ``part = "window_read"`` / ``"full_read"``: K and V at the LIVE positions x
  layers of the kind (facts ``swa_ring_positions_per_step`` /
  ``swa_page_positions_per_step``).

Not this family's configuration (``model_type`` is not ``laguna``), no
capture, no execution of the program, no operation in the scope or no count
for the program (a tree from before PR 60 has neither the cell nor the
counters): nothing to read."""

from benchmark import costs_swa, scopes, spans
from benchmark.readers.mla_scope_roofline import time_per_execution

_BYTES = {"bfloat16": 2, "float32": 4}
_FACTS = ("swa_experts_streamed_per_step", "swa_ring_positions_per_step",
          "swa_page_positions_per_step")


def need_bytes(part, cfg, experts, ring, pages, bytes_el):
    if part == "step":
        return costs_swa.step_bytes(cfg, experts, ring, pages,
                                    bytes_el)["total_bytes"]
    if part == "experts":
        return costs_swa.expert_bytes(cfg, experts, bytes_el)
    if part == "window_read":
        return costs_swa.cached_read_bytes(cfg, ring, bytes_el)
    if part == "full_read":
        return costs_swa.cached_read_bytes(cfg, pages, bytes_el)
    raise ValueError(f"part {part!r}")


def read(rc, part, module, scope=None):
    if (rc.trace is None or rc.peak is None
            or rc.cfg.get("model_type") != "laguna"):
        return None
    counts = [(rc.facts.get(key) or {}).get(module) for key in _FACTS]
    path = spans.newest_xplane()
    if None in counts or path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    plane = planes[min(planes)]
    if scope is None:
        program = plane.program_id(module)
        runs = [] if program is None else plane.programs()[program][1]
        # the first and the last execution may be cut by the capture's edges
        whole = runs[1:-1] or runs
    else:
        whole = time_per_execution(plane, module, scope)
    if not whole:
        return None
    need = need_bytes(part, rc.cfg, *counts,
                      _BYTES.get(rc.traffic.get("dtype"), 2))
    if not need:
        return None
    return (100.0 * need / rc.peak["hbm_bytes_per_s"]
            / (sum(whole) / len(whole)))
