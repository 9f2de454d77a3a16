"""Kind ``mhcserve``: one ``InferenceEngine`` replica over a published
``xing4_0`` causal LM (the ``deepseek_v3`` layer: a latent page pool read by
an absorbed decode step, a sigmoid router, a shared expert, a leading dense
layer; inside a residual path of four streams, manifold-constrained
hyper-connections) with EVERY expert and the whole vocabulary on the chip,
behind ``serve.run``, open-loop load over HTTP at the traffic file's fixed
rate.

The load, its client and the client-side series are ``kinds/serve.py``'s
(``offer_load``, ``summarize``); the seed rule and the tiny configuration's
``deepseek_v3`` keys are ``kinds/mlaserve.py``'s.  ``mlaserve.run`` is one
function and takes no part from outside, so what this kind does as that one
does (the window's counts, the facts a reader takes) is written again here,
in pieces a later kind can import: :func:`hold_window`,
:func:`reference_verdicts`, :func:`hold_reference`, :func:`step_facts`.
What differs: the requests the check is made on cross the rope's original
length, near a routing tie a position is held under what a planted fault
reads (``hold_reference``), six controls in place of two, and the residual
streams' rows.  ``tools/sweep.py`` drives it through ``deploy`` /
``offer_load`` / ``summarize`` like any serving kind.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import stats
from benchmark.kinds import mlaserve
from benchmark.kinds.mlaserve import _weights_seed
from benchmark.kinds.serve import _free_port, _post, offer_load, summarize

__all__ = ["deploy", "offer_load", "summarize", "run"]

# ``--rehearse`` hands every kind T5Config.tiny(); this kind runs its own
# tiny configuration of the published family instead (control flow only):
# ``mlaserve``'s, every expert held, one routing group, four streams
TINY = {
    **{k: v for k, v in mlaserve.TINY.items() if k != "deployment"},
    "model_type": "xing4_0", "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2,
    "rope_scaling": {
        "type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "assumed": {**mlaserve.TINY["assumed"],
                "mhc_tensor_names": {
                    "phi": "model.layers.{layer}.{sublayer}_hc.phi.weight",
                    "b": "model.layers.{layer}.{sublayer}_hc.bias",
                    "alpha": "model.layers.{layer}.{sublayer}_hc.alpha"}},
}

#: each control is the reference computed as a system at fault would
CONTROLS = {
    "lowprec_err": "at {check_lowprec_bits} mantissa bits",
    "noyarn_err": "without yarn's factor in the softmax scale",
    "sinkhorn1_err": "at 1 Sinkhorn round instead of {hc_sinkhorn_iters}",
    "identity_err": "with H_res = I (streams that never mix)",
}


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
        hf_import.XING_MHC_NAMES
    except (ImportError, AttributeError):
        # a tree from before PR 58: say so in one line and exit 2
        raise RunFailure("this tree's CausalLM has no residual path of "
                         "several streams (LMConfig.hc_mult) and no importer "
                         "for the published xing4_0 configuration "
                         "(tpu_air/models/lm/hf_import.py)") from None
    from tpu_air import serve
    from tpu_air.engine import EngineConfig

    from benchmark import weights_xing
    from benchmark.worker_hooks_mhc import ObservedMHCEngineDeployment

    t, cfg = ctx.traffic, _config(ctx)
    parts = {} if parts is None else parts
    t0 = time.monotonic()
    ckpt = weights_xing.write_checkpoint(
        cfg, _weights_seed(ctx), t["dtype"],
        os.path.join(ctx.scratch, "checkpoint"),
        max_seq_len=int(t["slot_len"]))
    parts["checkpoint_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    port = _free_port()
    handle = serve.run(
        ObservedMHCEngineDeployment.options(num_replicas=1, num_chips=1).bind(
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
    # program twice and the step, and the same prompt again meets the prefix
    # cache and the page copy
    warm = [2 + x % 300 for x in range(5, 5 + int(t["page_len"]) + 3)]
    for _ in range(2):
        _post(port, {"prompt": warm, "max_new_tokens": 3})
    parts["load_and_warm_s"] = time.monotonic() - t0
    return handle, port


def hold_window(ctx, cfg, rows, schedule, summary, before, after) -> Dict:
    """What the window must have done, from the client's rows and the
    engine's counters ``before`` and ``after`` it (``stats()``): every
    answer its budget long, every request the client saw done in the engine,
    every decoded token's assignments at the experts the chip holds, latent
    positions and stream rows that fit the steps.  Returns the window's
    counts."""
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
    experts = cfg["n_routed_experts"]
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
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
    live, pool = delta("latent_positions_live"), after.get(
        "latent_positions_pool")
    ctx.check(bool(pool) and 0 < live <= pool * max(steps, 1),
              f"latent positions: {live} live over {steps} steps of a pool "
              f"of {pool}")
    ctx.check(after.get("mhc_streams") == cfg["hc_mult"]
              and delta("mhc_rows_live") >= decoded,
              f"{after.get('mhc_streams')} residual streams, "
              f"{delta('mhc_rows_live')} rows x steps held a token for "
              f"{decoded} decoded tokens")
    return {"delta": delta, "per_expert": per_expert, "steps": steps,
            "assigned": assigned, "live": live, "pool": pool}


def reference_verdicts(ctx, cfg, call, rows, schedule) -> Dict[str, Any]:
    """Outside the window, on what the window finished: some of its requests
    by the seed, each with a prompt that crosses a chunk boundary, ends in a
    padded chunk AND crosses the rope's original length
    (``check_prompt_over``); the tokens the engine streamed for them under
    load held against the reference inside the replica
    (``bench_reference_check``), at fixed lengths."""
    from benchmark.harness import RunFailure

    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 3])
    page, cross = int(t["page_len"]), int(t["check_prompt_over"])
    done = [i for i, r in enumerate(rows) if r["outcome"] == "ok"
            and len(schedule[i]["prompt"]) > max(page, cross)
            and len(schedule[i]["prompt"]) % page]
    want_n = int(t["check_requests"])
    picked = [done[j] for j in rng.permutation(len(done))[:want_n]]
    ctx.check(len(picked) == want_n,
              f"the window finished {len(done)} requests whose prompt "
              f"crosses a chunk boundary and position {cross}; the check "
              f"wants {want_n}")
    if not picked:
        raise RunFailure("the window finished no request the check can "
                         "be made on")
    prompts = [schedule[i]["prompt"] for i in picked]
    answers = [rows[i]["tokens"] for i in picked]
    slots = rng.choice(int(t["num_slots"]), max(1, len(picked) - 1),
                       replace=False).tolist()
    verdicts = call(
        "bench_reference_check", dict(cfg), _weights_seed(ctx), t["dtype"],
        prompts, answers, slots, int(t["check_new_tokens"]),
        int(t["prompt_len"]["max"]) + int(t["output_len"]["max"]),
        int(t["output_len"]["max"]), int(t["check_lowprec_bits"]))
    return {"verdicts": verdicts, "slots": slots,
            "prompt_lens": [len(p) for p in prompts],
            "answer_lens": [len(a) for a in answers]}


def hold_reference(ctx, cfg, verdicts: List[Dict[str, Any]]) -> Dict:
    """The verdicts against the traffic file's limits (its ``check_why`` has
    every reading).  ``err``, ``margin`` and ``gap`` a position are
    ``bench_reference_check``'s.  Away from a routing tie (``gap >=
    check_tie_eps``) a position is held to ``check_logit_tol`` and
    ``check_margin``; a request's MEDIAN err to ``check_logit_tol`` near a
    tie or not; of the tokens the window streamed at least
    ``check_kept_share`` are the reference's own choice, which the reference
    in the low precision keeps fewer of.  Near a tie, where
    the bf16 system may take another expert than the float32 reference, a
    position is held UNDER what a planted fault reads there:
    ``check_tie_tol`` (err) under another row's logits, ``check_tie_margin``
    (margin) under another slot's token, both planted on the same positions
    and held as controls.  Each control must fail the limit that would
    otherwise pass the system computing so.  Returns the readings."""
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
            for e in (0.0, 3e-3, 5e-3, 7e-3, 1e-2, 2e-2)},
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
    """What one step streamed, had live and moved through its residual
    streams, by the program that ran it (the decode program alone, or the
    mixed step whose chunk's rows touch experts of their own): over the
    profiler's window where there was one (the readers divide the CAPTURED
    programs' time by it), else over the window and its drain."""
    seen = call("bench_traced_counts") if ctx.trace else {}
    over = "the profiler's window" if seen.get("moe_steps") else "the window"
    if not seen.get("moe_steps"):
        seen = {k: delta(k) for k in (
            "moe_steps", "moe_steps_alone", "moe_experts_streamed",
            "moe_experts_streamed_alone", "latent_positions_live",
            "mhc_rows_live", "mhc_rows_live_alone", "steps_issued",
            "mixed_steps")}
    by_program = lambda total, alone, n, n_alone: {  # noqa: E731
        program: value / count for program, (count, value) in (
            ("lm_paged_decode_step", (n_alone, alone)),
            ("lm_paged_mixed_step", (n - n_alone, total - alone)))
        if count}
    return {
        "counts_of": seen, "counts_over": over,
        "moe_held_experts_streamed_per_step": by_program(
            seen["moe_experts_streamed"], seen["moe_experts_streamed_alone"],
            seen["moe_steps"], seen["moe_steps_alone"]),
        "mhc_stream_rows_per_step": by_program(
            seen["mhc_rows_live"], seen["mhc_rows_live_alone"],
            seen["steps_issued"], seen["steps_issued"] - seen["mixed_steps"]),
        "latent_positions_live_per_step": (
            seen["latent_positions_live"] / max(seen["moe_steps"], 1)),
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
        "moe_held_experts_streamed_per_step": steps_by[
            "moe_held_experts_streamed_per_step"],
        "latent_positions_live_per_step": steps_by[
            "latent_positions_live_per_step"],
        "latent_live_share": (100.0 * window["live"]
                              / max(window["pool"] * max(steps, 1), 1)),
        "mhc_stream_rows_per_step": steps_by["mhc_stream_rows_per_step"],
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
        latent_positions_pool=window["pool"],
        mhc_rows_live=delta("mhc_rows_live"),
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
