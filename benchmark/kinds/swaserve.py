"""Kind ``swaserve``: one ``InferenceEngine`` replica over a published
``laguna`` causal LM (window layers that keep a ring of positions a slot
beside full layers that keep K/V pages, query heads counted by layer kind, a
gate a head, a sigmoid router over 256 small experts all held, a shared
expert, a leading dense layer) with EVERY expert and the whole vocabulary on
the chip, behind ``serve.run``, open-loop load over HTTP at the traffic
file's fixed rate.

The load, its client and the client-side series are ``kinds/serve.py``'s
(``offer_load``, ``summarize``); the seed rule is ``kinds/mlaserve.py``'s;
the pick of the requests the check is made on is ``kinds/mhcserve.py``'s
(``reference_verdicts``: prompts that cross a chunk boundary, end in a padded
chunk and are longer than ``check_prompt_over``, here the ring: such a
request has wrapped it).  What is this kind's own: the window's counts
(:func:`hold_window`: assignments, ring and page positions that fit the
steps), the verdicts against the traffic file's limits with this family's
controls (:func:`hold_reference`), the facts the new readers take
(:func:`step_facts`).  ``tools/sweep.py`` drives it through ``deploy`` /
``offer_load`` / ``summarize`` like any serving kind.
"""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import stats
from benchmark.kinds.mhcserve import reference_verdicts
from benchmark.kinds.mlaserve import _weights_seed
from benchmark.kinds.serve import (_free_port, _post, offer_load,
                                   read_per_step, summarize)

__all__ = ["deploy", "offer_load", "summarize", "run"]

# ``--rehearse`` hands every kind T5Config.tiny(); this kind runs its own
# tiny configuration of the published family instead (control flow only):
# F-dense S S S F, a window of 8, 6 / 8 query heads on 2 K/V heads, half a
# head turning under yarn on the full kind, 16 experts top-4 and a shared one
TINY = {
    "model_type": "laguna", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 8, "attention_factor": 0.1 * math.log(4) + 1,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "assumed": {
        "eos_token_id": None, "pad_token_id": 0, "initializer_range": 0.08,
        "gate_initializer_range": 0.25,
        "tensor_names": {
            "embed": "model.embed_tokens.weight", "head": "lm_head.weight",
            "final_norm": "model.norm.weight",
            "attn_norm": "model.layers.{i}.input_layernorm.weight",
            "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
            "q": "model.layers.{i}.self_attn.q_proj.weight",
            "k": "model.layers.{i}.self_attn.k_proj.weight",
            "v": "model.layers.{i}.self_attn.v_proj.weight",
            "o": "model.layers.{i}.self_attn.o_proj.weight",
            "g": "model.layers.{i}.self_attn.g_proj.weight",
            "dense": "model.layers.{i}.mlp.{m}_proj.weight",
            "router": "model.layers.{i}.mlp.gate.weight",
            "router_bias":
                "model.layers.{i}.mlp.gate.e_score_correction_bias",
            "expert": "model.layers.{i}.mlp.experts.{e}.{m}_proj.weight",
            "shared": "model.layers.{i}.mlp.shared_expert.{m}_proj.weight"}},
}

#: each control is the reference computed as a system at fault would
CONTROLS = {
    "lowprec_err": "at {check_lowprec_bits} mantissa bits",
    "nowindow_err": "with a sliding layer that sees every earlier position",
    "nogate_err": "without the output gate",
    "wholerope_err": "with rope on the whole head of a full layer",
    "noyarn_err": "without yarn's factor on cos and sin",
    "otherring_err": "with another slot's ring under the sliding layers",
}

#: the engine's counters the per-step facts are made of
STEP_COUNTERS = ("moe_steps", "moe_steps_alone", "moe_experts_streamed",
                 "moe_experts_streamed_alone", "window_positions_live",
                 "window_positions_live_alone", "kv_page_positions_live",
                 "kv_page_positions_live_alone", "steps_issued",
                 "mixed_steps")


def _config(ctx) -> Dict[str, Any]:
    if ctx.rehearse:
        ctx.cfg = dict(TINY)
    return ctx.cfg


def deploy(ctx, parts: Optional[Dict[str, float]] = None):
    """Checkpoint from the seed, ``serve.run``, the engine's programs warm.
    Returns (handle, port); ``parts`` takes the seconds each of the three
    took."""
    from benchmark.harness import RunFailure

    try:
        from tpu_air.models.lm import hf_import
        hf_import.LAGUNA_NAMES
    except (ImportError, AttributeError):
        # a tree from before PR 60: say so in one line and exit 2
        raise RunFailure("this tree's CausalLM has no window layer (a ring "
                         "of positions a slot beside the pages), no head "
                         "count by layer kind, no output gate and no "
                         "importer for the published laguna configuration "
                         "(tpu_air/models/lm/hf_import.py)") from None
    from tpu_air import serve
    from tpu_air.engine import EngineConfig

    from benchmark import weights_swa
    from benchmark.worker_hooks_swa import ObservedSWAEngineDeployment

    t, cfg = ctx.traffic, _config(ctx)
    parts = {} if parts is None else parts
    t0 = time.monotonic()
    ckpt = weights_swa.write_checkpoint(
        cfg, _weights_seed(ctx), t["dtype"],
        os.path.join(ctx.scratch, "checkpoint"),
        max_seq_len=int(t["slot_len"]))
    parts["checkpoint_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    port = _free_port()
    handle = serve.run(
        ObservedSWAEngineDeployment.options(num_replicas=1, num_chips=1).bind(
            ckpt,
            EngineConfig(num_slots=int(t["num_slots"]),
                         slot_len=int(t["slot_len"]),
                         page_len=int(t["page_len"]),
                         max_new_tokens=int(t["max_new_tokens"]),
                         eos_token_id=cfg.get("assumed", {}).get(
                             "eos_token_id")),
            dtype=t["dtype"]),
        port=port)
    parts["serve_run_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    # the engine builds on the first request (the mixed step compiles with
    # it); a prompt longer than a page and three tokens run the chunk
    # program twice and the step (no prefix cache for this model: the second
    # request runs them again, warm)
    warm = [2 + x % 300 for x in range(5, 5 + int(t["page_len"]) + 3)]
    for _ in range(2):
        _post(port, {"prompt": warm, "max_new_tokens": 3})
    parts["load_and_warm_s"] = time.monotonic() - t0
    return handle, port


def hold_window(ctx, cfg, rows, schedule, summary, before, after) -> Dict:
    """What the window must have done, from the client's rows and the
    engine's counters ``before`` and ``after`` it (``stats()``): every
    answer its budget long, every request the client saw done in the engine,
    every decoded token's assignments at the experts the chip holds, ring
    and page positions that fit the steps, the prefix cache off by the
    model, the rings a share of what pages at ``slot_len`` would be.
    Returns the window's counts."""
    from tpu_air.serve.admission import AdmissionPolicy

    t, trim = ctx.traffic, AdmissionPolicy().clamp_budget
    ctx.attempted, ctx.failed = summary["attempted"], summary["failed"]
    for r, s in zip(rows, schedule):
        if r["outcome"] == "ok":
            want = trim(s["priority"], s["max_new_tokens"])
            ctx.check(len(r["tokens"]) == want and all(
                0 <= x < cfg["vocab_size"] for x in r["tokens"]),
                f"request due at {r['due_s']:.3f}s answered "
                f"{len(r['tokens'])} tokens for a budget of {want} "
                "(no EOS: budgets end requests)")
    delta = lambda key: after.get(key, 0) - before.get(key, 0)  # noqa: E731
    done = delta("requests_completed")
    ctx.check(summary["completed"] <= done <= summary["attempted"],
              f"engine completed {done}, the client saw "
              f"{summary['completed']} of {summary['attempted']}")
    experts = cfg["num_experts"]
    sparse = cfg["mlp_layer_types"].count("sparse")
    per_expert = (np.array(after["moe_expert_load"])
                  - np.array(before.get("moe_expert_load") or [0] * experts))
    ctx.check(len(per_expert) == experts
              and not after.get("moe_assignments_elsewhere"),
              f"the engine counts {len(per_expert)} experts, the chip holds "
              f"all {experts}")
    assigned, steps = delta("moe_assignments"), delta("moe_steps")
    decoded = delta("tokens_emitted") - done
    expect = decoded * cfg["num_experts_per_tok"] * sparse
    in_flight = int(t["num_slots"]) * cfg["num_experts_per_tok"] * sparse * 2
    ctx.check(abs(assigned - expect) <= in_flight,
              f"{assigned} assignments for {decoded} decoded tokens (want "
              f"{expect} within {in_flight})")
    window = cfg["layer_types"].count("sliding_attention")
    full = len(cfg["layer_types"]) - window
    ring, pages = delta("window_positions_live"), delta(
        "kv_page_positions_live")
    ctx.check(0 < ring <= window * cfg["sliding_window"] * int(
        t["num_slots"]) * max(steps, 1) and ring * full <= pages * window,
        f"{ring} ring positions x layers and {pages} page positions x "
        f"layers live over {steps} steps: a row reads at most "
        f"{cfg['sliding_window']} positions of each of {window} rings and "
        f"all it holds of each of {full} page layers")
    held = 100.0 * after.get("window_ring_bytes", 0) / max(
        after.get("window_ring_bytes_as_pages", 0), 1)
    ctx.check(after.get("prefix_cache_disabled_by_model") is True
              and 0 < held < 100,
              f"the rings hold {after.get('window_ring_bytes')} bytes, "
              f"{held:.1f} % of what the same layers would hold as pages at "
              f"slot_len, beside {after.get('kv_page_bytes')} bytes of "
              "pages; the prefix cache is off by the model")
    return {"delta": delta, "per_expert": per_expert, "steps": steps,
            "assigned": assigned, "ring": ring, "pages": pages,
            "held_share": held}


def hold_reference(ctx, cfg, verdicts: List[Dict[str, Any]]) -> Dict:
    """The verdicts against the traffic file's limits (its ``check_why`` has
    every reading), as ``mhcserve.hold_reference`` holds its own: ``err``,
    ``margin`` and ``gap`` a position are ``bench_reference_check``'s.  Away
    from a routing tie (``gap >= check_tie_eps``) a position is held to
    ``check_logit_tol`` and ``check_margin``; a request's MEDIAN err to
    ``check_logit_tol`` near a tie or not; of the tokens the window streamed
    at least ``check_kept_share`` are the reference's own choice, which the
    reference in the low precision keeps fewer of.  Near a tie, where the
    bf16 system may take another expert than the float32 reference, a
    position is held UNDER what a planted fault reads there
    (``check_tie_tol``, ``check_tie_margin``), both plants held as controls.
    Each control of :data:`CONTROLS` must read over ``check_logit_tol`` in
    the median: the limit would otherwise pass a system computing so.
    Returns the readings."""
    t = ctx.traffic
    tol, margin = float(t["check_logit_tol"]), float(t["check_margin"])
    eps = float(t["check_tie_eps"])
    tie_err, tie_margin = float(t["check_tie_tol"]), float(
        t["check_tie_margin"])
    kept_least = t.get("check_kept_share")      # None: not held (--rehearse)
    cat = lambda key, n=None: np.concatenate(  # noqa: E731
        [np.asarray(v[key], float)[:n and len(v["err"])] for v in verdicts])
    err, held, gap, err_gap = (cat("err"), cat("margin"), cat("gap"),
                               cat("gap", True))
    tied, err_tied = gap < eps, err_gap < eps
    worst = lambda x: float(x.max()) if x.size else 0.0  # noqa: E731
    mid = lambda x: float(np.median(x)) if x.size else None  # noqa: E731
    ctx.check(bool((err[~err_tied] <= tol).all()),
              f"system logits differ from the reference by up to "
              f"{worst(err[~err_tied]):.4f} of the row's top-to-median "
              f"distance (limit {tol}) at positions whose routing is not "
              "near a tie")
    medians = [float(np.median(v["err"])) for v in verdicts]
    ctx.check(max(medians) <= tol,
              f"the median of that difference over a request's positions is "
              f"{max(medians):.4f} for one of the {len(medians)} requests "
              f"(limit {tol}): near a tie or not, most positions route as "
              "the reference does")
    ctx.check(bool((err[err_tied] <= tie_err).all()),
              f"at {int(err_tied.sum())} near-tied positions (reference gap "
              f"< {eps}) the logits differ by up to "
              f"{worst(err[err_tied]):.4f} (limit {tie_err})")
    ctx.check(bool((held[~tied] <= margin).all())
              and bool((held[tied] <= tie_margin).all()),
              f"a token streamed in the window has its reference logit "
              f"{worst(held[~tied]):.4f} under the largest (limit {margin}), "
              f"near a tie {worst(held[tied]):.4f} (limit {tie_margin})")
    kept = sum(v["exact"] for v in verdicts) / max(len(held), 1)
    shortest = next(v for v in verdicts if "lowprec_err" in v)
    if kept_least is not None:
        low = CONTROLS["lowprec_err"].format(**{**cfg, **t})
        low_kept = (shortest["control_kept"]["lowprec_err"]
                    / shortest["tokens"])
        ctx.check(low_kept < kept_least <= kept,
                  f"{100 * kept:.1f} % of the {len(held)} tokens the window "
                  f"streamed are the reference's own choice (at least "
                  f"{100 * kept_least:g} %); the reference {low} keeps "
                  f"{100 * low_kept:.1f} %")
    # the tier near a tie against what it must catch: planted on the same
    # positions, another slot's token and another row's logits
    planted = {}
    if "planted_margin" in verdicts[0]:
        planted = {"margin": cat("planted_margin")[tied],
                   "err": cat("planted_err")[err_tied]}
        for key, limit, what in (
                ("margin", tie_margin, "another request's token"),
                ("err", tie_err, "another request's logits")):
            x = planted[key]
            ctx.check(x.size == 0 or mid(x) > limit,
                      f"{what} planted at the {x.size} near-tied positions "
                      f"reads a median {mid(x) or 0:.4f} and is over the "
                      f"limit {limit} at {int((x > limit).sum())} of them: "
                      "the limit near a tie catches it")
    medians_of = {}
    for key, what in CONTROLS.items():
        medians_of[key] = float(np.median(shortest[key]))
        ctx.check(medians_of[key] > tol,
                  f"the reference {what.format(**{**cfg, **t})} differs by "
                  f"a median {medians_of[key]:.4f}: the limit {tol} on a "
                  "request's median would pass a system computing so")
    return dict(
        check_exact_tokens=[v["exact"] for v in verdicts],
        check_kept_share=kept,
        check_reference_on=verdicts[0]["reference_on"],
        check_err_max=worst(err), check_err_max_untied=worst(err[~err_tied]),
        check_err_p50_by_request=medians,
        check_err_over_tol_share=float((err > tol).mean()),
        check_margin_max=worst(held),
        check_margin_max_untied=worst(held[~tied]),
        check_near_tied=int(tied.sum()), check_positions=int(len(err)),
        check_window_tokens=int(len(held)),
        # the largest reading among the positions a threshold leaves untied:
        # what check_tie_eps was chosen from
        check_by_eps={str(e): {
            "tied": int((gap < e).sum()),
            "err_untied": worst(err[err_gap >= e]),
            "margin_untied": worst(held[gap >= e])}
            for e in (0.0, 2e-3, 5e-3, 7.5e-3, 1e-2, 1.5e-2, 2e-2)},
        # what the limits near a tie lie between: the system's largest and
        # the planted faults' readings on the same positions
        check_planted={k: {"p50": mid(x), "p10": float(np.quantile(x, 0.1)),
                           "min": float(x.min())}
                       for k, x in planted.items() if x.size},
        check_control_medians=medians_of,
        check_control_kept={k: n / shortest["tokens"]
                            for k, n in shortest["control_kept"].items()},
        check_seconds=verdicts[0].get("seconds"))


def step_facts(ctx, call, delta) -> Dict[str, Any]:
    """What one step streamed and had live, by the program that ran it (the
    decode program alone, or the mixed step whose chunk's rows touch experts
    of their own): over the profiler's window where there was one (the
    readers divide the CAPTURED programs' time by it), else over the window
    and its drain."""
    seen = call("bench_traced_counts") if ctx.trace else {}
    over = "the profiler's window" if seen.get("moe_steps") else "the window"
    if not seen.get("moe_steps"):
        seen = {k: delta(k) for k in STEP_COUNTERS}
    by_program = lambda key, n, n_alone: {  # noqa: E731
        program: value / count for program, (count, value) in (
            ("lm_paged_decode_step", (seen[n_alone], seen[key + "_alone"])),
            ("lm_paged_mixed_step", (seen[n] - seen[n_alone],
                                     seen[key] - seen[key + "_alone"])))
        if count}
    return {
        "counts_of": seen, "counts_over": over,
        # the replica's ReadWatch beside the engine's own count of the page
        # positions (x the full layers): for the record, read by no metric
        "watched_kv_positions_per_step": read_per_step(
            seen, "kv_positions_read"),
        "swa_experts_streamed_per_step": by_program(
            "moe_experts_streamed", "moe_steps", "moe_steps_alone"),
        # counted as a step is READ, as the experts are: the same steps
        "swa_ring_positions_per_step": by_program(
            "window_positions_live", "moe_steps", "moe_steps_alone"),
        "swa_page_positions_per_step": by_program(
            "kv_page_positions_live", "moe_steps", "moe_steps_alone"),
    }


def run(ctx) -> None:
    import tpu_air

    t, cfg = ctx.traffic, _config(ctx)
    parts: Dict[str, float] = {}
    handle, port = deploy(ctx, parts)
    call = lambda name, *a: tpu_air.get(  # noqa: E731
        handle.method(name)(*a))
    facts0, stats0 = call("bench_facts"), call("stats")
    t0 = time.monotonic()
    load = offer_load(ctx, handle, port, t, ctx.seed, ctx.seconds)
    parts["lead_and_drain_s"] = time.monotonic() - t0 - ctx.seconds
    stats1, facts1 = call("stats"), call("bench_facts")
    rows, schedule = load["rows"], load["schedule"]
    summary = summarize(rows, ctx.seconds, float(t["drain_s"]))
    ctx.check(facts1["cold_compiles"] == facts0["cold_compiles"],
              "cold compiles inside the window")
    window = hold_window(ctx, cfg, rows, schedule, summary, stats0, stats1)
    t0 = time.monotonic()
    checked = reference_verdicts(ctx, cfg, call, rows, schedule)
    parts["check_s"] = time.monotonic() - t0
    readings = hold_reference(ctx, cfg, checked.pop("verdicts"))
    facts2 = call("bench_facts")
    steps_by = step_facts(ctx, call, window["delta"])

    late95 = stats.percentile(summary["client_late_ms"], 0.95)
    if late95 is not None and late95 > float(t["poll_ms"]):
        print(f"benchmark: WARNING the load generator ran late: p95 "
              f"{late95:.1f} ms against a poll interval of {t['poll_ms']} "
              "ms — not a fast server", file=sys.stderr)
    half = [q for q in load["queue"] if q["t"] <= ctx.seconds / 2]
    occupancy = [q["slot_occupancy"] for q in load["queue"]]
    delta, steps, per_expert = (window["delta"], window["steps"],
                                window["per_expert"])
    ctx.window_s = ctx.seconds
    ctx.window_start = load["started_at"]
    ctx.facts.update({
        "window_s": ctx.window_s,
        "client_ttft_ms": summary["client_ttft_ms"],
        "client_tpot_ms": summary["client_tpot_ms"],
        "client_late_ms": summary["client_late_ms"],
        "client_poll_late_ms": load["poll_late_ms"],
        "serve_tokens": summary["tokens"],
        "serve_completed": summary["completed"],
        "engine_step_ms_p50": 1000.0 * stats1["step_latency_s"]["p50"],
        "engine_ttft_ms_p50": 1000.0 * stats1["ttft_s"]["p50"],
        "engine_steps": steps,
        "engine_tokens": delta("tokens_emitted"),
        "queue_depth_half": half[-1]["queue_depth"] if half else None,
        "queue_depth_end": (load["queue"][-1]["queue_depth"]
                            if load["queue"] else None),
        "num_slots": int(t["num_slots"]), "slot_len": int(t["slot_len"]),
        "page_len": int(t["page_len"]),
        "moe_load_max_over_mean": float(per_expert.max()
                                        / max(per_expert.mean(), 1e-9)),
        "swa_experts_streamed_per_step": steps_by[
            "swa_experts_streamed_per_step"],
        "swa_ring_positions_per_step": steps_by[
            "swa_ring_positions_per_step"],
        "swa_page_positions_per_step": steps_by[
            "swa_page_positions_per_step"],
        "swa_window_held_share": window["held_share"],
        "memory_peak_bytes": facts2.get("memory_peak_bytes"),
        "worker_compile_s": facts2["compile_s"],
        "worker_cold_compiles": facts2["cold_compiles"],
        "worker_cache_hits": facts2["cache_hits"],
    })
    ctx.notes.update(
        requests=summary["attempted"], outcomes=summary["outcomes"],
        engine_step_ms=stats1["step_latency_s"],
        engine_steps_by_program=stats1.get("step_latency_by_program_s"),
        engine_ttft_ms_p50=1000.0 * stats1["ttft_s"]["p50"],
        engine_steps=steps,
        serve_ttft_p95_ms=stats.percentile(summary["client_ttft_ms"], 0.95),
        serve_ttft_p50_ms=stats.percentile(summary["client_ttft_ms"], 0.5),
        serve_tokens_per_s=summary["tokens"] / ctx.seconds,
        occupancy_by_second=occupancy,
        polls=summary["polls"], loadgen_late_ms_p95=late95,
        loadgen_poll_late_ms_p95=stats.percentile(load["poll_late_ms"], 0.95),
        poll_interval_ms_p50=stats.percentile(
            summary["poll_interval_ms"], 0.5),
        slot_occupancy_mean=(sum(occupancy) / len(occupancy)
                             if occupancy else None),
        prefill_chunks=delta("prefill_chunks"),
        chunks_fused=delta("chunks_fused"),
        kvpool=stats1.get("kvpool"),
        moe_assignments=window["assigned"],
        moe_expert_load=per_expert.tolist(),
        window_ring_bytes=stats1.get("window_ring_bytes"),
        kv_page_bytes=stats1.get("kv_page_bytes"),
        window_positions_live=window["ring"],
        kv_page_positions_live=window["pages"],
        steps_dropped=stats1.get("steps_dropped"),
        # the fullest device as the window ended, before the reference ran
        # beside the engine (memory_peak_bytes is read after it)
        memory_peak_bytes_before_check=facts1.get("memory_peak_bytes"),
        check_prompt_lens=checked["prompt_lens"],
        check_answer_lens=checked["answer_lens"],
        check_slots=checked["slots"],
        # where the run's time outside the window went, as this process saw it
        setup_parts=parts,
        traced=load["traced"], **steps_by, **readings)
