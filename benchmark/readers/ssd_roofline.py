"""Share of the HBM roofline a decode step of a Mamba-2 / latent
sparse-expert rank (``model_type: nemotron_h``), or a PART of it, reaches, in
percent: the bytes of ``part`` (below, ``benchmark/costs_ssd.py``) at the peak
bandwidth over device time.  Every count is the engine's own over the
profiler's window (the kind's facts): rows whose state a step advanced, the
positions they hold, the held experts the program's steps touched.

``modules`` names the programs a step can be (the decode program alone, the
mixed step that carries a prefill chunk beside it): the one the capture holds
MOST executions of is read (``mla_hbm_share.most_run``), with the experts ITS
steps streamed.

* ``part = "step"``: ``costs_ssd.decode_step_bytes`` over the median device
  time of one execution of the program (the ``XLA Modules`` line): the share
  of the WHOLE step.  The bytes are a floor for the mixed step too.
* ``part = "state_update"`` (with ``scope``): 2 x the float32 state of the
  rows a step advanced, over the time of the operations traced under
  ``scope`` in an execution (by scope, not by kernel name).
* ``part = "experts"`` (with ``scope``): two matrices of each held expert the
  program's steps touched, over the scope's time likewise.

Not this family's configuration, none of the programs, no operation in the
scope or no counts (an older tree): nothing to read."""

from benchmark import costs_ssd, scopes, spans, stats
from benchmark.readers.mla_hbm_share import most_run
from benchmark.readers.mla_scope_roofline import time_per_execution
from benchmark.readers.module_hbm_share import module_durations


def needed_bytes(part, cfg, facts, module):
    rows = facts.get("ssd_rows_live_per_step")
    streamed = (facts.get("moe_held_experts_streamed_per_step")
                or {}).get(module)
    if part == "state_update":
        return None if rows is None else 2 * costs_ssd.state_bytes(
            cfg, rows, tail_el=0)
    if streamed is None:
        return None
    if part == "experts":
        return costs_ssd.held_expert_bytes(cfg, streamed)
    if part == "step":
        positions = facts.get("ssd_positions_live_per_step")
        if rows is None or positions is None:
            return None
        return costs_ssd.decode_step_bytes(
            cfg, rows, positions, streamed)["total_bytes"]
    raise ValueError(f"part {part!r}")


def read(rc, part, modules, scope=None):
    if (rc.trace is None or rc.peak is None
            or "mamba_num_heads" not in rc.cfg):
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    if scope is None:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        runs = {m: module_durations(data, m) for m in modules}
    else:
        planes = scopes.read(path)
        if not planes:
            return None
        plane = planes[min(planes)]
        runs = {m: time_per_execution(plane, m, scope) or []
                for m in modules}
    module, times = most_run(runs)
    if module is None:
        return None
    need = needed_bytes(part, rc.cfg, rc.facts, module)
    if need is None:
        return None
    return (100.0 * need / rc.peak["hbm_bytes_per_s"]
            / stats.percentile(times, 0.5))
