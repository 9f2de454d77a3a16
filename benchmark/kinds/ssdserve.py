"""Kind ``ssdserve``: one ``InferenceEngine`` replica over ONE expert-parallel
rank's share of a published Mamba-2 / latent sparse-expert causal LM
(``model_type: nemotron_h``: layers that are a Mamba-2 mixer, attention or
routed experts alone; a 4 MB float32 state a slot a Mamba-2 layer beside the
K/V pages of the few attention layers; a router over all the experts of which
the chip holds some, in a latent) behind ``serve.run``, open-loop load over
HTTP at the traffic file's fixed rate.

The load, its client and the client-side series are ``kinds/serve.py``'s
(``offer_load``, ``summarize``); the deployment (``EngineConfig`` from the
traffic file, a checkpoint of the share through
``benchmark/weights_nemotron.py``) and the check against the reference are
this kind's own.  ``tools/sweep.py`` drives it through ``deploy`` /
``offer_load`` / ``summarize`` like any serving kind.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

from benchmark import stats
from benchmark.kinds.serve import _free_port, _post, offer_load, summarize

__all__ = ["deploy", "offer_load", "summarize", "run"]

# ``--rehearse`` hands every kind T5Config.tiny(); this kind runs its own
# tiny configuration of the published family instead (control flow only):
# all three kinds of layer, two groups of three heads, rank 1 of 2
TINY = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "ME*ME", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 48,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 5, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
    "mamba_num_heads": 6, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 4, "expand": 2,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "layer_norm_epsilon": 1e-05, "vocab_size": 384,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
    "attention_bias": False, "mamba_proj_bias": False,
    "num_nextn_predict_layers": 1,
    "deployment": {"expert_parallel": 2, "expert_rank": 1,
                   "router_width": 16},
    "assumed": {"eos_token_id": None, "pad_token_id": 0,
                "initializer_range": 0.08},
}


def _config(ctx) -> Dict[str, Any]:
    if ctx.rehearse:
        ctx.cfg = dict(TINY)
    return ctx.cfg


def deploy(ctx, parts: Optional[Dict[str, float]] = None):
    """Checkpoint of the share from the seed, ``serve.run``, the engine's
    programs warm.  Returns (handle, port); ``parts`` takes the seconds each
    of the three took."""
    from benchmark.harness import RunFailure

    try:
        from tpu_air.models.lm import hf_import
        hf_import.convert_nemotron_h_state_dict
    except (ImportError, AttributeError):
        # a tree from before PR 47: say so in one line and exit 2
        raise RunFailure("this tree's CausalLM has no Mamba-2 mixer, no "
                         "layer pattern and no importer for the published "
                         "nemotron_h configuration "
                         "(tpu_air/models/lm/hf_import.py)") from None
    from tpu_air import serve
    from tpu_air.engine import EngineConfig

    from benchmark import weights_nemotron
    from benchmark.worker_hooks_ssd import ObservedSSDEngineDeployment

    t, cfg = ctx.traffic, _config(ctx)
    parts = {} if parts is None else parts
    t0 = time.monotonic()
    ckpt = weights_nemotron.write_checkpoint(
        cfg, ctx.seed, t["dtype"], os.path.join(ctx.scratch, "checkpoint"),
        max_seq_len=int(t["slot_len"]))
    parts["checkpoint_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    port = _free_port()
    handle = serve.run(
        ObservedSSDEngineDeployment.options(num_replicas=1, num_chips=1).bind(
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
    # program twice and the step
    warm = [2 + x % 300 for x in range(5, 5 + int(t["page_len"]) + 3)]
    _post(port, {"prompt": warm, "max_new_tokens": 3})
    parts["load_and_warm_s"] = time.monotonic() - t0
    return handle, port


def run(ctx) -> None:
    import tpu_air

    from benchmark.weights_mla import held as held_range

    t, cfg = ctx.traffic, _config(ctx)
    vocab = cfg["vocab_size"]
    parts: Dict[str, float] = {}
    handle, port = deploy(ctx, parts)
    facts0 = tpu_air.get(handle.method("bench_facts")())
    stats0 = tpu_air.get(handle.method("stats")())

    t0 = time.monotonic()
    load = offer_load(ctx, handle, port, t, ctx.seed, ctx.seconds)
    parts["lead_and_drain_s"] = time.monotonic() - t0 - ctx.seconds
    stats1 = tpu_air.get(handle.method("stats")())
    facts1 = tpu_air.get(handle.method("bench_facts")())
    rows, schedule = load["rows"], load["schedule"]
    summary = summarize(rows, ctx.seconds, float(t["drain_s"]))

    # the traffic's class holds every budget untrimmed; the check is made
    # against what the default admission would leave all the same
    from tpu_air.serve.admission import AdmissionPolicy

    trim = AdmissionPolicy().clamp_budget
    ctx.attempted, ctx.failed = summary["attempted"], summary["failed"]
    trimmed = 0
    for r, s in zip(rows, schedule):
        if r["outcome"] == "ok":
            toks = r["tokens"]
            want = trim(s["priority"], s["max_new_tokens"])
            trimmed += want < s["max_new_tokens"]
            ctx.check(len(toks) == want
                      and all(0 <= x < vocab for x in toks),
                      f"request due at {r['due_s']:.3f}s answered "
                      f"{len(toks)} tokens for a budget of {want} "
                      "(no EOS: budgets end requests)")
    done_in_engine = (stats1["requests_completed"]
                      - stats0["requests_completed"])
    ctx.check(summary["completed"] <= done_in_engine
              <= summary["attempted"],
              f"engine completed {done_in_engine}, the client saw "
              f"{summary['completed']} of {summary['attempted']}")
    ctx.check(facts1["cold_compiles"] == facts0["cold_compiles"],
              "cold compiles inside the window")
    ctx.check(bool(stats1.get("prefix_cache_disabled_by_model")),
              "the engine left prefix sharing on for a model with "
              "recurrent state")

    # routing over the window: every assignment a decoding row made, at one
    # of the held experts or sent elsewhere, none dropped
    delta = lambda key: stats1.get(key, 0) - stats0.get(key, 0)  # noqa: E731
    held_n = held_range(cfg)[1]
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    sparse, mamba = pattern.count("E"), pattern.count("M")
    per_expert = (np.array(stats1["moe_expert_load"])
                  - np.array(stats0.get("moe_expert_load") or [0] * held_n))
    ctx.check(len(per_expert) == held_n,
              f"the engine counts {len(per_expert)} experts, the chip holds "
              f"{held_n}")
    held_a, away = delta("moe_assignments"), delta("moe_assignments_elsewhere")
    steps = delta("moe_steps")
    decoded = delta("tokens_emitted") - done_in_engine
    expect = decoded * cfg["num_experts_per_tok"] * sparse
    in_flight = int(t["num_slots"]) * cfg["num_experts_per_tok"] * sparse * 2
    ctx.check(abs(held_a + away - expect) <= in_flight,
              f"{held_a} assignments here and {away} elsewhere for "
              f"{decoded} decoded tokens (want {expect} within {in_flight})")
    rows_live = delta("ssd_rows_live")
    ctx.check(abs(rows_live - decoded) <= 2 * int(t["num_slots"]),
              f"the steps advanced {rows_live} rows' state for {decoded} "
              "decoded tokens")

    # outside the window, on what the window finished: some of its requests
    # by the seed, the tokens the engine streamed for them under load held
    # against the reference inside the replica.  Every one has a prompt that
    # crosses a chunk boundary and ends in a padded chunk; one is the request
    # whose prompt ends soonest after the first boundary (its first streamed
    # tokens are the first a state lost there would spoil: the dropped-state
    # reading is taken on it)
    rng = np.random.default_rng([ctx.seed, 3])
    page = int(t["page_len"])
    done = [i for i, r in enumerate(rows) if r["outcome"] == "ok"
            and len(schedule[i]["prompt"]) > page
            and len(schedule[i]["prompt"]) % page]
    want_n = int(t["check_requests"])
    picked = [done[j] for j in rng.permutation(len(done))[:want_n - 1]]
    near = [i for i in sorted(done, key=lambda i: len(schedule[i]["prompt"]))
            if i not in picked][:want_n - len(picked)]
    picked = near + picked
    ctx.check(len(picked) == want_n,
              f"the window finished {len(done)} requests whose prompt "
              f"crosses a chunk boundary; the check wants {want_n}")
    if not picked:
        from benchmark.harness import RunFailure

        raise RunFailure("the window finished no request the check can "
                         "be made on")
    prompts = [schedule[i]["prompt"] for i in picked]
    answers = [rows[i]["tokens"] for i in picked]
    slots = rng.choice(int(t["num_slots"]), max(1, len(picked) - 1),
                       replace=False).tolist()
    t0 = time.monotonic()
    verdicts = tpu_air.get(handle.method("bench_reference_check")(
        dict(cfg), ctx.seed, t["dtype"], prompts, answers, slots,
        int(t["check_new_tokens"]),
        int(t["prompt_len"]["max"]) + int(t["output_len"]["max"]),
        int(t["output_len"]["max"]), int(t["check_lowprec_bits"]),
        int(t["check_drop_state_at"]), int(t["check_state_bits"]),
        int(t["check_state_heads"])))
    parts["check_s"] = time.monotonic() - t0
    tol, margin = float(t["check_logit_tol"]), float(t["check_margin"])
    eps, loose = float(t["check_tie_eps"]), float(t["check_tie_tol"])
    err = np.concatenate([v["err"] for v in verdicts])
    under = np.concatenate([v["margin"] for v in verdicts])
    gap = np.concatenate([v["gap"] for v in verdicts])
    err_gap = np.concatenate([v["gap"][:len(v["err"])] for v in verdicts])
    tied, err_tied = gap < eps, err_gap < eps
    worst = lambda x: float(x.max()) if x.size else 0.0  # noqa: E731
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
    ctx.check(bool((err[err_tied] <= loose).all()),
              f"at {int(err_tied.sum())} near-tied positions (reference gap "
              f"< {eps}) the logits differ by up to "
              f"{worst(err[err_tied]):.4f} (limit {loose})")
    ctx.check(bool((under[~tied] <= margin).all())
              and bool((under[tied] <= loose).all()),
              f"a token streamed in the window has its reference logit "
              f"{worst(under[~tied]):.4f} under the largest (limits "
              f"{margin}, near a tie {loose})")
    shortest = next(v for v in verdicts if "lowprec_err" in v)
    low = np.array(shortest["lowprec_err"])
    lost = np.array(shortest["dropstate_err"])
    state_kept = shortest["state_kept"]
    state, state_tol = [v["state"] for v in verdicts], float(
        t["check_state_tol"])
    ctx.check(max(state) <= state_tol,
              f"the first Mamba-2 layer's carried state lies {max(state):.5f} "
              f"from the reference's (over its {t['check_state_heads']} slowest "
              f"heads; limit "
              f"{state_tol})")
    ctx.check(bool(np.median(low) > tol),
              f"the reference at {t['check_lowprec_bits']} mantissa bits "
              f"differs by a median {np.median(low):.4f}: the limit {tol} "
              "would pass a system computing in that precision")
    ctx.check(bool(np.median(lost) > tol),
              f"the reference with its state dropped at position "
              f"{t['check_drop_state_at']} differs by a median "
              f"{np.median(lost):.4f}: the limit {tol} would pass a system "
              "that loses the carried state between chunks")
    ctx.check(state_kept > state_tol,
              f"the reference with its carried state at "
              f"{t['check_state_bits']} mantissa bits carries a state "
              f"{state_kept:.5f} from its own: the limit {state_tol} would "
              "pass a system that keeps the state-space state in that "
              "precision")
    facts2 = tpu_air.get(handle.method("bench_facts")())
    # what one step streamed and had live, by the program that ran it (the
    # decode program alone, or the mixed step whose chunk's rows touch
    # experts of their own): over the profiler's window where there was one
    # (the readers divide the CAPTURED programs' time by it), else over the
    # window and its drain
    counters = ("moe_steps", "moe_steps_alone", "moe_experts_streamed",
                "moe_experts_streamed_alone", "ssd_rows_live",
                "ssd_positions_live")
    seen = (tpu_air.get(handle.method("bench_traced_counts")())
            if ctx.trace else {})
    if not seen.get("moe_steps"):
        seen = {k: delta(k) for k in counters}
    alone = (seen["moe_steps_alone"], seen["moe_experts_streamed_alone"])
    mixed = (seen["moe_steps"] - alone[0],
             seen["moe_experts_streamed"] - alone[1])
    streamed_per_step = {
        program: streamed / n for program, (n, streamed) in (
            ("lm_paged_decode_step", alone), ("lm_paged_mixed_step", mixed))
        if n}
    per_step = lambda key: seen[key] / max(seen["moe_steps"], 1)  # noqa: E731

    # each finished request's own gap, by when it arrived: the window begins
    # on an empty engine, so its first requests live among fewer rows
    gaps = [(r["due_s"], 1000.0 * r["first_to_done_s"] / (len(r["tokens"]) - 1))
            for r in rows if r["outcome"] == "ok" and len(r["tokens"]) > 1]
    third = lambda k: stats.percentile(  # noqa: E731
        [g for due, g in gaps
         if k * ctx.seconds / 3 <= due < (k + 1) * ctx.seconds / 3], 0.5)
    late95 = stats.percentile(summary["client_late_ms"], 0.95)
    if late95 is not None and late95 > float(t["poll_ms"]):
        print(f"benchmark: WARNING the load generator ran late: p95 "
              f"{late95:.1f} ms against a poll interval of {t['poll_ms']} "
              "ms — not a fast server", file=sys.stderr)
    poll_late95 = stats.percentile(load["poll_late_ms"], 0.95)
    poll_every = stats.percentile(summary["poll_interval_ms"], 0.5)
    half = [q for q in load["queue"] if q["t"] <= ctx.seconds / 2]
    occupancy = [q["slot_occupancy"] for q in load["queue"]]
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
        "moe_held_experts_streamed_per_step": streamed_per_step,
        "ssd_rows_live_per_step": per_step("ssd_rows_live"),
        "ssd_positions_live_per_step": per_step("ssd_positions_live"),
        "ssm_state_bytes": stats1.get("ssm_state_bytes"),
        "memory_peak_bytes": facts2.get("memory_peak_bytes"),
        "worker_compile_s": facts2["compile_s"],
        "worker_cold_compiles": facts2["cold_compiles"],
        "worker_cache_hits": facts2["cache_hits"],
    })
    ctx.notes.update(
        requests=summary["attempted"], outcomes=summary["outcomes"],
        budgets_trimmed_by_admission=int(trimmed),
        engine_step_ms=stats1["step_latency_s"],
        engine_ttft_ms_p50=1000.0 * stats1["ttft_s"]["p50"],
        engine_steps=steps,
        serve_ttft_p95_ms=stats.percentile(summary["client_ttft_ms"], 0.95),
        serve_ttft_p50_ms=stats.percentile(summary["client_ttft_ms"], 0.5),
        serve_tokens_per_s=summary["tokens"] / ctx.seconds,
        client_tpot_ms_quartiles=[stats.percentile(
            [g for _, g in gaps], q) for q in (0.25, 0.5, 0.75)],
        client_tpot_ms_p50_by_arrival_third=[third(k) for k in range(3)],
        occupancy_by_second=occupancy,
        polls=summary["polls"], loadgen_late_ms_p95=late95,
        loadgen_poll_late_ms_p95=poll_late95,
        poll_interval_ms_p50=poll_every,
        slot_occupancy_mean=(sum(occupancy) / len(occupancy)
                             if occupancy else None),
        prefill_chunks=delta("prefill_chunks"),
        chunks_fused=delta("chunks_fused"),
        kvpool=stats1.get("kvpool"),
        moe_assignments_held=held_a, moe_assignments_elsewhere=away,
        moe_expert_load=per_expert.tolist(),
        moe_held_experts_streamed_per_step=streamed_per_step,
        ssd_rows_live_per_step=ctx.facts["ssd_rows_live_per_step"],
        ssd_positions_live_per_step=ctx.facts["ssd_positions_live_per_step"],
        ssm_state_bytes=stats1.get("ssm_state_bytes"),
        ssm_state_resets=delta("ssm_state_resets"),
        ssm_rows_held=delta("ssm_rows_held"),
        mamba_layers=mamba,
        counts_of=seen,
        steps_dropped=stats1.get("steps_dropped"),
        # the fullest device as the window ended, before the reference ran
        # beside the engine (memory_peak_bytes is read after it)
        memory_peak_bytes_before_check=facts1.get("memory_peak_bytes"),
        check_exact_tokens=[v["exact"] for v in verdicts],
        check_reference_on=verdicts[0]["reference_on"],
        check_err_max=float(err.max()),
        check_err_max_untied=worst(err[~err_tied]),
        check_err_p50=float(np.median(err)),
        check_err_p50_by_request=medians,
        check_err_p90=float(np.quantile(err, 0.9)),
        check_err_over_tol_share=float((err > tol).mean()),
        check_margin_max=float(under.max()),
        check_margin_max_untied=worst(under[~tied]),
        check_near_tied=int(tied.sum()), check_positions=int(len(err)),
        check_gap_p10=float(np.quantile(gap, 0.1)),
        # the largest reading among the positions a threshold leaves untied:
        # what check_tie_eps was chosen from
        check_by_eps={str(e): {
            "tied": int((gap < e).sum()),
            "err_untied": worst(err[err_gap >= e]),
            "margin_untied": worst(under[gap >= e])}
            for e in (0.0, 1e-3, 2e-3, 3e-3, 5e-3, 7e-3, 1e-2, 2e-2)},
        check_gap_min=float(gap.min()),
        check_lowprec_err_p50=float(np.median(low)),
        check_lowprec_err_min=float(low.min()),
        check_dropstate_err_p50=float(np.median(lost)),
        check_dropstate_err_min=float(lost.min()),
        check_state=state,
        check_state_kept=state_kept,
        # a head, slowest first: what the share of slow heads was chosen from
        check_state_by_head=[v["state_by_head"] for v in verdicts],
        check_state_kept_by_head=shortest["state_kept_by_head"],
        check_window_tokens=int(len(under)),
        check_prompt_lens=[len(p) for p in prompts],
        check_answer_lens=[len(a) for a in answers],
        check_slots=slots,
        check_seconds=verdicts[0].get("seconds"),
        # where the run's time outside the window went, as this process saw it
        setup_parts=parts,
        traced=load["traced"])
