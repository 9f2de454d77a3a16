"""Kind ``ssmserve``: one ``InferenceEngine`` replica over a published hybrid
state-space / attention causal LM (``model_type: jamba``: Mamba layers whose
per-slot state lives beside the K/V pages of a few attention layers) behind
``serve.run``, open-loop load over HTTP at the traffic file's fixed rate.

``kinds/lmserve.py`` without its routing checks: the load, its client and
the client-side series are ``kinds/serve.py``'s (``offer_load``,
``summarize``); the deployment (``EngineConfig`` from the traffic file, a
checkpoint through ``benchmark/weights_ssm.py``) and the check against the
reference are this kind's own.  ``tools/sweep.py`` drives it through
``deploy`` / ``offer_load`` / ``summarize`` like any serving kind.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

import numpy as np

from benchmark import stats
from benchmark.kinds.serve import (_free_port, _post, offer_load,
                                   read_per_step, summarize)

__all__ = ["deploy", "offer_load", "summarize", "run"]

# ``--rehearse`` hands every kind T5Config.tiny(); this kind runs its own
# tiny configuration of the published family instead (control flow only)
TINY = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "attn_layer_period": 4, "attn_layer_offset": 2,
    "num_experts": 1, "num_experts_per_tok": 1, "mamba_expand": 2,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 6,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "rms_norm_eps": 1e-06,
    "vocab_size": 384, "max_position_embeddings": 512,
    "tie_word_embeddings": True, "hidden_act": "silu", "sliding_window": None,
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
        from tpu_air.models.lm import hf_import
        hf_import.convert_jamba_state_dict
    except (ImportError, AttributeError):
        # a tree from before PR 41: say so in one line and exit 2
        raise RunFailure("this tree's CausalLM has no state-space layer and "
                         "no importer for the published jamba configuration "
                         "(tpu_air/models/lm/hf_import.py)") from None
    from tpu_air import serve
    from tpu_air.engine import EngineConfig

    from benchmark import weights_ssm
    from benchmark.worker_hooks_ssm import ObservedSSMEngineDeployment

    t, cfg = ctx.traffic, _config(ctx)
    ckpt = weights_ssm.write_checkpoint(
        cfg, ctx.seed, t["dtype"], os.path.join(ctx.scratch, "checkpoint"),
        max_seq_len=int(t["slot_len"]))
    port = _free_port()
    handle = serve.run(
        ObservedSSMEngineDeployment.options(num_replicas=1, num_chips=1).bind(
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
    # and three tokens run the chunk program twice and the step
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
    steps = stats1.get("steps_issued", 0) - stats0.get("steps_issued", 0)

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
    verdicts = tpu_air.get(handle.method("bench_reference_check")(
        dict(cfg), ctx.seed, t["dtype"], prompts, answers, slots,
        int(t["check_new_tokens"]),
        int(t["prompt_len"]["max"]) + int(t["output_len"]["max"]),
        int(t["check_lowprec_bits"]), int(t["check_drop_state_at"]),
        int(t["check_state_bits"])))
    tol, margin = float(t["check_logit_tol"]), float(t["check_margin"])
    err = np.concatenate([v["err"] for v in verdicts])
    held = np.concatenate([v["margin"] for v in verdicts])
    ctx.check(bool((err <= tol).all()),
              f"system logits differ from the reference by up to "
              f"{err.max():.4f} of the row's top-to-median distance "
              f"(limit {tol})")
    ctx.check(bool((held <= margin).all()),
              f"a token streamed in the window has its reference logit "
              f"{held.max():.4f} under the largest (limit {margin})")
    shortest = next(v for v in verdicts if "lowprec_err" in v)
    low = np.array(shortest["lowprec_err"])
    lost = np.array(shortest["dropstate_err"])
    longest = next(v for v in verdicts if "state_err" in v)
    kept, state_kept = np.array(longest["state_err"]), longest["state_kept"]
    state, state_tol = [v["state"] for v in verdicts], float(
        t["check_state_tol"])
    ctx.check(max(state) <= state_tol,
              f"the first Mamba layer's carried state lies {max(state):.5f} "
              f"from the reference's (over its slow channels; limit "
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
    # what a CAPTURED step had live: the engine's own counters over the
    # profiler's window (rows whose state it advanced, the positions they
    # held, as issued: not split by program), a traced run alone
    seen = (tpu_air.get(handle.method("bench_traced_counts")())
            if ctx.trace else {})
    issued = seen.get("steps_issued", 0)
    live = ({"ssm_rows_live_per_step": seen["ssd_rows_live"] / issued,
             "ssm_positions_live_per_step":
                 seen["ssd_positions_live"] / issued} if issued else {})

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
        "ssm_state_bytes": stats1.get("ssm_state_bytes"),
        **live,
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
        occupancy_by_second=occupancy,
        polls=summary["polls"], loadgen_late_ms_p95=late95,
        loadgen_poll_late_ms_p95=poll_late95,
        poll_interval_ms_p50=poll_every,
        slot_occupancy_mean=(sum(occupancy) / len(occupancy)
                             if occupancy else None),
        prefill_chunks=(stats1.get("prefill_chunks", 0)
                        - stats0.get("prefill_chunks", 0)),
        kvpool=stats1.get("kvpool"),
        ssm_state_bytes=stats1.get("ssm_state_bytes"),
        ssm_state_resets=(stats1.get("ssm_state_resets", 0)
                          - stats0.get("ssm_state_resets", 0)),
        ssm_rows_held=(stats1.get("ssm_rows_held", 0)
                       - stats0.get("ssm_rows_held", 0)),
        steps_dropped=stats1.get("steps_dropped"),
        check_exact_tokens=[v["exact"] for v in verdicts],
        check_reference_on=verdicts[0]["reference_on"],
        check_err_max=float(err.max()),
        check_err_p50=float(np.median(err)),
        check_margin_max=float(held.max()),
        check_positions=int(len(err)),
        check_lowprec_err_p50=float(np.median(low)),
        check_lowprec_err_min=float(low.min()),
        check_dropstate_err_p50=float(np.median(lost)),
        check_dropstate_err_min=float(lost.min()),
        check_state=state,
        check_state_kept=state_kept,
        check_state_err_p50=float(np.median(kept)),
        check_state_err_max=float(kept.max()),
        check_window_tokens=int(len(held)),
        check_prompt_lens=[len(p) for p in prompts],
        check_answer_lens=[len(a) for a in answers],
        check_slots=slots,
        check_seconds=verdicts[0].get("seconds"),
        # the replica's own watch beside the engine's counters: the same
        # steps as they were READ, by program
        counts_of=seen, **live,
        watched_rows_per_step=read_per_step(seen, "rows_read"),
        watched_kv_positions_per_step=read_per_step(
            seen, "kv_positions_read"),
        traced=load["traced"])
