"""Share of its roofline a PART of the decode step reaches, in percent,
by scope and not by kernel name (the same work whatever implements it): the
floor of ``part`` (below) over the device time, an execution of the step's
program, of the operations traced under ``scope`` (and, with ``under``,
under another component matching that: ``benchmark/scopes.py`` reads the
``jax.named_scope`` / flax module path from the capture's event metadata).
An execution's time is the union of the intervals of its operations in the
scope; the median over the executions that lie whole inside the capture is
taken.  ``modules`` names the programs a step can be (the decode program
alone, the mixed step that carries a prefill chunk beside it): the one the
capture holds most executions of is read, with ITS steps' counts
(``mla_hbm_share.most_run``); the scopes mark the same work in either.

* ``part = "attention"``: the absorbed read of the latent (scopes
  ``kv_gather`` and ``decode_attention`` under the latent module ``attn``):
  the larger of the latent's bytes at the positions the engine counted live
  a step (``costs_mla.latent_bytes``) at the HBM peak and the operations the
  read needs there (``costs_mla.absorbed_attention_flops``) at the matmul
  peak.  At the published widths the bytes are the larger bound (121
  operations a byte against the chip's 240).
* ``part = "held_experts"``: scope ``moe_experts``: three matrices of each
  held expert the program's steps touched (the engine's count a step; in a
  mixed step the chunk's rows go through the same products) at the HBM peak.

Not this family's configuration, none of the programs, no operation in the
scope or no counts (an older tree): nothing to read."""

import re

from benchmark import costs_mla, scopes, spans, stats, xplane
from benchmark.readers.mla_hbm_share import most_run
from benchmark.readers.scope_share import in_scope


def time_per_execution(plane, module, scope, under=None):
    """Device seconds the operations in the scope take in each whole
    execution of ``module`` on ``plane``; None without such a program."""
    program = plane.program_id(module)
    if program is None:
        return None
    scope, under = re.compile(scope), under and re.compile(under)
    ops = sorted((s, e) for i, s, e in plane.of_program(program)
                 if in_scope(plane.parts(i), scope, under))
    if not ops:
        return None
    runs = sorted((s, e) for i, s, e in plane.modules
                  if plane.metadata[i]["name"].startswith(
                      f"jit_{module}({program})"))
    per_run, k = [], 0
    for start, end in runs:
        while k < len(ops) and ops[k][0] < start:
            k += 1
        inside = []
        while k < len(ops) and ops[k][0] < end:
            inside.append(ops[k])
            k += 1
        if inside:
            per_run.append(xplane.total(xplane.union(inside)))
    # the first and the last execution may be cut by the capture's edges
    return per_run[1:-1] or per_run


def floor_seconds(part, cfg, facts, peak, module):
    if part == "attention":
        live = facts.get("latent_positions_live_per_step")
        if live is None:
            return None
        return max(
            costs_mla.latent_bytes(cfg, live) / peak["hbm_bytes_per_s"],
            costs_mla.absorbed_attention_flops(cfg, live)
            / peak["bf16_flops_per_s"])
    if part == "held_experts":
        streamed = (facts.get("moe_held_experts_streamed_per_step")
                    or {}).get(module)
        if streamed is None:
            return None
        return (costs_mla.held_expert_bytes(cfg, streamed)
                / peak["hbm_bytes_per_s"])
    raise ValueError(f"part {part!r}")


def read(rc, part, scope, modules, under=None):
    if rc.trace is None or rc.peak is None or "kv_lora_rank" not in rc.cfg:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    plane = planes[min(planes)]
    module, whole = most_run({
        m: time_per_execution(plane, m, scope, under) or [] for m in modules})
    if module is None:
        return None
    floor_s = floor_seconds(part, rc.cfg, rc.facts, rc.peak, module)
    if floor_s is None:
        return None
    return 100.0 * floor_s / stats.percentile(whole, 0.5)
