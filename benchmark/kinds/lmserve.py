"""Kind ``lmserve``: one ``InferenceEngine`` replica (paged KV pool, chunked
prefill, continuous batching) over a published causal LM behind
``serve.run``, open-loop load over HTTP at the traffic file's fixed rate.

The load, its client and the client-side series are ``kinds/serve.py``'s
(``offer_load``, ``summarize``); the deployment (``EngineConfig`` from the
traffic file, a checkpoint through ``benchmark/weights_lm.py``) and the
check against the reference are this kind's own.  ``tools/sweep.py`` drives
it through ``deploy`` / ``offer_load`` / ``summarize`` like any serving kind.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

import numpy as np

from benchmark import stats, weights_lm
from benchmark.kinds.serve import (_free_port, _post, offer_load,
                                   read_per_step, summarize)

__all__ = ["deploy", "offer_load", "summarize", "run"]

# ``--rehearse`` hands every kind T5Config.tiny(); this kind runs its own
# tiny configuration of the published family instead (control flow only)
TINY = {
    "model_type": "olmoe", "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "vocab_size": 384, "max_position_embeddings": 512, "rope_theta": 10000,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "norm_topk_prob": False, "clip_qkv": None, "attention_bias": False,
    "rope_scaling": None, "hidden_act": "silu",
    "assumed": {"eos_token_id": None, "initializer_range": 0.02},
}


def _config(ctx) -> Dict[str, Any]:
    if ctx.rehearse:
        ctx.cfg = dict(TINY)
    return ctx.cfg


def deploy(ctx):
    """Checkpoint from the seed, ``serve.run``, both engine programs warm.
    Returns (handle, port)."""
    from benchmark.harness import RunFailure

    try:
        from tpu_air.models.lm import hf_import  # noqa: F401
    except ImportError:
        # a tree from before PR 27: say so in one line and exit 2
        raise RunFailure("this tree's CausalLM has no sparse-expert layer "
                         "and no importer for the published configuration "
                         "(tpu_air/models/lm/hf_import.py)") from None
    from tpu_air import serve
    from tpu_air.engine import EngineConfig

    from benchmark.worker_hooks_lm import ObservedLMEngineDeployment

    t, cfg = ctx.traffic, _config(ctx)
    ckpt = weights_lm.write_checkpoint(
        cfg, ctx.seed, t["dtype"], os.path.join(ctx.scratch, "checkpoint"),
        max_seq_len=int(t["slot_len"]))
    port = _free_port()
    handle = serve.run(
        ObservedLMEngineDeployment.options(num_replicas=1, num_chips=1).bind(
            ckpt,
            EngineConfig(num_slots=int(t["num_slots"]),
                         slot_len=int(t["slot_len"]),
                         page_len=int(t["page_len"]),
                         max_new_tokens=int(t["max_new_tokens"]),
                         eos_token_id=cfg.get("assumed", {}).get(
                             "eos_token_id")),
            dtype=t["dtype"]),
        port=port)
    # the engine builds on the first request; a prompt longer than a page
    # and three tokens run the chunk program twice, the copy and the step
    warm = list(range(5, 5 + int(t["page_len"]) + 3))
    _post(port, {"prompt": [2 + x % 300 for x in warm], "max_new_tokens": 3})
    return handle, port


def run(ctx) -> None:
    import tpu_air

    t, cfg = ctx.traffic, _config(ctx)
    vocab = cfg["vocab_size"]
    handle, port = deploy(ctx)
    facts0 = tpu_air.get(handle.method("bench_facts")())
    stats0 = tpu_air.get(handle.method("stats")())

    load = offer_load(ctx, handle, port, t, ctx.seed, ctx.seconds)
    stats1 = tpu_air.get(handle.method("stats")())
    facts1 = tpu_air.get(handle.method("bench_facts")())
    rows, schedule = load["rows"], load["schedule"]
    summary = summarize(rows, ctx.seconds, float(t["drain_s"]))

    # serve.run's default admission trims an interactive request's budget
    # (to 256 tokens) and does not refuse it: the stream ends there
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

    # routing over the window: every assignment a decoding row made
    load0 = np.array(stats0.get("moe_expert_load")
                     or [0] * cfg["num_experts"])
    per_expert = np.array(stats1["moe_expert_load"]) - load0
    assigned = stats1["moe_assignments"] - stats0.get("moe_assignments", 0)
    steps = stats1["moe_steps"] - stats0.get("moe_steps", 0)
    # each token decoded in a step (all but a request's first) makes
    # top-k assignments in every layer: none dropped, none added
    decoded = (stats1["tokens_emitted"] - stats0["tokens_emitted"]
               - done_in_engine)
    expect = (decoded * cfg["num_experts_per_tok"]
              * cfg["num_hidden_layers"])
    in_flight = int(t["num_slots"]) * cfg["num_experts_per_tok"] \
        * cfg["num_hidden_layers"] * 2
    ctx.check(abs(assigned - expect) <= in_flight,
              f"{assigned} assignments for {decoded} decoded tokens "
              f"(want {expect} within {in_flight})")

    # outside the window: prompts sent alone, the system's logits and its
    # streamed tokens held against the reference inside the replica
    rng = np.random.default_rng([ctx.seed, 3])
    n, budget = int(t["check_prompts"]), int(t["check_new_tokens"])
    lens = rng.integers(t["check_prompt_len"]["min"],
                        t["check_prompt_len"]["max"] + 1, n)
    prompts = [rng.integers(2, vocab, int(k)).tolist() for k in lens]
    answers = [_post(port, {"prompt": p, "max_new_tokens": budget})
               ["results"][0]["tokens"] for p in prompts]
    verdicts = tpu_air.get(handle.method("bench_reference_check")(
        dict(cfg), ctx.seed, t["dtype"], prompts,
        answers, int(t["check_lowprec_bits"])))
    tol, margin = float(t["check_logit_tol"]), float(t["check_margin"])
    eps, loose = float(t["check_tie_eps"]), float(t["check_tie_tol"])
    err = np.concatenate([v["err"] for v in verdicts])
    gap = np.concatenate([v["gap"] for v in verdicts])
    held = np.concatenate([v["margin"] for v in verdicts])
    tied = gap < eps
    ctx.check(all(v["tokens"] == budget for v in verdicts),
              f"check prompts answered {[v['tokens'] for v in verdicts]} "
              f"tokens, wanted {budget} each")
    ctx.check(bool((err[~tied] <= tol).all()),
              f"system logits differ from the reference by up to "
              f"{err[~tied].max() if (~tied).any() else 0:.4f} of the row's "
              f"top-to-median distance (limit {tol}) at positions whose "
              f"routing is not near a tie")
    ctx.check(bool((err[tied] <= loose).all()),
              f"at {int(tied.sum())} near-tied positions (reference gap < "
              f"{eps}) the logits differ by up to "
              f"{err[tied].max() if tied.any() else 0:.4f} (limit {loose})")
    ctx.check(bool((held[~tied] <= margin).all())
              and bool((held[tied] <= loose).all()),
              f"a streamed token's reference logit lies {held.max():.4f} "
              f"under the largest (limits {margin}, near a tie {loose})")
    low = np.array(next(v["lowprec_err"] for v in verdicts
                        if "lowprec_err" in v))
    ctx.check(bool(np.median(low) > tol),
              f"the reference at {t['check_lowprec_bits']} mantissa bits "
              f"differs by a median {np.median(low):.4f}: the limit {tol} "
              "would pass a system computing in that precision")
    facts2 = tpu_air.get(handle.method("bench_facts")())
    # what the CAPTURED steps had live, by the program that ran them: the
    # replica's count over the profiler's own window (a traced run alone
    # asks for it, and alone has a watch to ask)
    seen = (tpu_air.get(handle.method("bench_traced_counts")())
            if ctx.trace else {})
    kv_per_step = read_per_step(seen, "kv_positions_read")

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
        "engine_tokens": stats1["tokens_emitted"] - stats0["tokens_emitted"],
        "queue_depth_half": half[-1]["queue_depth"] if half else None,
        "queue_depth_end": (load["queue"][-1]["queue_depth"]
                            if load["queue"] else None),
        "num_slots": int(t["num_slots"]), "slot_len": int(t["slot_len"]),
        "page_len": int(t["page_len"]),
        "moe_load_max_over_mean": float(per_expert.max()
                                        / per_expert.mean()),
        "lm_kv_positions_per_step": kv_per_step,
        "moe_experts_streamed_per_layer_step": (
            (stats1["moe_experts_streamed"]
             - stats0.get("moe_experts_streamed", 0))
            / max(steps, 1) / cfg["num_hidden_layers"]),
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
        ttft_over_1s=[[round(r["due_s"], 2), round(r["ttft_s"], 2)]
                      for r in rows if (r["ttft_s"] or 0) > 1.0][:40],
        occupancy_by_second=occupancy,
        ttft_samples_beyond_p95=stats.samples_beyond(
            summary["attempted"], 0.95),
        polls=summary["polls"], loadgen_late_ms_p95=late95,
        loadgen_poll_late_ms_p95=poll_late95,
        poll_interval_ms_p50=poll_every,
        slot_occupancy_mean=(sum(occupancy) / len(occupancy)
                             if occupancy else None),
        prefill_chunks=(stats1.get("prefill_chunks", 0)
                        - stats0.get("prefill_chunks", 0)),
        kvpool=stats1.get("kvpool"),
        moe_assignments=assigned, moe_expert_load=per_expert.tolist(),
        check_exact_tokens=[v["exact"] for v in verdicts],
        check_reference_on=verdicts[0]["reference_on"],
        check_err_max=float(err.max()),
        check_err_max_untied=float(err[~tied].max()) if (~tied).any() else None,
        check_err_p50=float(np.median(err)),
        check_margin_max=float(held.max()),
        check_near_tied=int(tied.sum()), check_positions=int(len(err)),
        check_gap_p10=float(np.quantile(gap, 0.1)),
        check_lowprec_err_p50=float(np.median(low)),
        check_lowprec_err_min=float(low.min()),
        counts_of=seen,
        lm_kv_positions_per_step=kv_per_step,
        lm_rows_per_step=read_per_step(seen, "rows_read"),
        traced=load["traced"])
