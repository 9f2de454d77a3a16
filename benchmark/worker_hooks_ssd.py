"""The replica of the ``ssdserve`` kind: ``worker_hooks.ObservedEngineServer``
(facts, a profiler window) plus the comparison of a served Mamba-2 / latent
sparse-expert ``CausalLM`` (one expert-parallel rank's share of a published
``nemotron_h`` model) with the benchmark's own reference, made INSIDE the
replica (the only process that holds the chip and the parameters; never a
process of its own) and OUTSIDE the measured window, ON requests the window
finished.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_nemotron
from benchmark.reference import nemotron_h
from benchmark.weights_mla import held, published_view
from benchmark.worker_hooks import ObservedEngineServer
from benchmark.worker_hooks_mla import round_mantissa


def replayed_logits(engine, prompts: List[List[int]],
                    answers: List[List[int]], slots: List[int]
                    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """``worker_hooks_ssm.replayed_logits`` for a model whose rows are
    Mamba-2's: the logits the SYSTEM computes for each token of ``answers``
    (the answer teacher-forced) through the ENGINE'S OWN pool and state rows
    at its own geometry and in its own order of work (at most one prefill
    chunk, then one decode step over every row past its prompt; fewer
    ``slots`` than sequences, so a row mid-prefill rides the steps between
    its chunks with its state held, the other rows ride every step, and the
    last sequence takes a slot an earlier one left, whose first chunk starts
    the state from zeros), with the engine bodies' text up to the head.  The
    engine must be idle; its cache is taken under its step lock and handed
    back.

    Returns the logits a sequence ``[len(answer), V]`` and, a sequence, the
    state ``[H, P, N]`` the first Mamba-2 layer holds in the sequence's row
    once its last token has gone in."""
    import time

    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm.generate import (
        make_paged_decode_logits_body, make_prefill_chunk_logits_body)

    cfg, model = engine.config, engine.model
    c, n_slots, pps = cfg.page_len, cfg.num_slots, cfg.pages_per_slot()
    chunk_body = make_prefill_chunk_logits_body(model, c, cfg.slot_len)
    step_body = make_paged_decode_logits_body(model, cfg.slot_len)
    chunk = jax.jit(lambda *a, slot: chunk_body(*a, slot=slot)[::2],
                    donate_argnums=(1,))
    step = jax.jit(lambda *a: step_body(*a)[:3:2], donate_argnums=(1,))
    # nothing is live in an idle engine: a slot's pages are its own run
    table = 1 + np.arange(n_slots * pps, dtype=np.int32).reshape(n_slots, pps)
    pad = model.config.pad_token_id
    out = [np.zeros((len(a), model.config.vocab_size), np.float32)
           for a in answers]
    first = f"layer_{model.config.layer_kinds().index('mamba2')}"
    states = [None] * len(prompts)

    def leave(i, s):
        states[i] = np.asarray(cache[first]["mamba"]["ssm_state"][s])
        free.append(s)

    waiting, free = list(range(len(prompts))), list(slots)
    filling, decoding = None, {}     # [sequence, slot, p0]; slot -> (seq, j)
    patience = time.monotonic() + 60.0
    while not engine.idle() and time.monotonic() < patience:
        time.sleep(0.05)
    with engine._step_lock:
        if not engine.idle():
            raise RuntimeError("the check replays through the engine's own "
                               "pool: the engine must be idle")
        cache = engine.cache
        try:
            while waiting or filling or decoding:
                if filling is None and waiting and free:
                    filling = [waiting.pop(0), free.pop(0), 0]
                if filling:
                    i, s, p0 = filling
                    piece = prompts[i][p0:p0 + c]
                    ids = np.full((1, c), pad, np.int32)
                    ids[0, :len(piece)] = piece
                    cache, logits = chunk(
                        engine.params, cache, jnp.asarray(ids),
                        jnp.int32(p0), jnp.int32(len(piece) - 1),
                        jnp.asarray(table[s]), slot=jnp.int32(s))
                    filling[2] = p0 + c
                    if p0 + c >= len(prompts[i]):
                        out[i][0] = np.asarray(logits)
                        filling = None
                        if len(answers[i]) > 1:
                            decoding[s] = (i, 1)
                        else:
                            leave(i, s)
                if decoding:
                    tok = np.zeros((n_slots,), np.int32)
                    pos = np.zeros((n_slots,), np.int32)
                    tbl = np.zeros((n_slots, pps), np.int32)
                    for s, (i, j) in decoding.items():
                        tok[s] = answers[i][j - 1]
                        pos[s] = len(prompts[i]) - 1 + j
                        tbl[s] = table[s]
                    cache, logits = step(
                        engine.params, cache, jnp.asarray(tok),
                        jnp.asarray(pos), jnp.asarray(tbl))
                    for s, (i, j) in list(decoding.items()):
                        out[i][j] = np.asarray(logits[s])
                        if j + 1 < len(answers[i]):
                            decoding[s] = (i, j + 1)
                        else:
                            del decoding[s]
                            leave(i, s)
        finally:
            engine.cache = cache
    return out, states


#: ``stats()`` counters whose change over the profiler's window says what
#: the CAPTURED steps did (the window's own, not the run's average)
TRACED_COUNTERS = ("moe_steps", "moe_steps_alone", "moe_experts_streamed",
                   "moe_experts_streamed_alone", "ssd_rows_live",
                   "ssd_positions_live", "steps_issued")


def slow_heads(pub: "weights_nemotron.Published", layer: int) -> np.ndarray:
    """The layer's Mamba-2 heads, the one that forgets slowest first, by the
    published scalars alone (``softplus(dt_bias) * exp(A_log)``, smallest
    first: time constants of a thousand positions down to under one)."""
    m = f"backbone.layers.{layer}.mixer."
    f32 = lambda name: np.asarray(pub.tensor(m + name)).astype(  # noqa: E731
        np.float32)
    rate = np.log1p(np.exp(f32("dt_bias"))) * np.exp(f32("A_log"))
    return np.argsort(rate, kind="stable")


class ObservedSSDEngineServer(ObservedEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``)
    TRACED_COUNTERS = TRACED_COUNTERS

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]], slots: List[int],
                              n_err: int, pad_to: int, rows_to: int,
                              lowprec_bits: int, drop_state_at: int,
                              state_bits: int, state_heads: int
                              ) -> List[Dict[str, Any]]:
        """Hold the system to the reference on requests the WINDOW finished:
        ``prompts`` and the whole ``answers`` the engine streamed for them
        under load.  Per request, the reference teacher-forced on prompt plus
        answer (the rank's share: the routed experts it holds, the shared
        one, its slice of the vocabulary), per streamed token ``j``:

        * ``margin`` (every token): how far the streamed token's REFERENCE
          logit lies under the reference's largest, over the reference row's
          top-to-median distance: the engine's own compiled programs, with
          every slot in them and most of them live;
        * ``err`` (the first ``n_err`` tokens): max over the slice of
          |system logit - reference logit| on the same scale, the system's
          logits being :func:`replayed_logits`;
        * ``gap`` (every token): how close the reference's routing at that
          position is to a tie this rank can see, over layers
          (``nemotron_h.route``);
        * ``state`` (once, after those ``n_err`` tokens): the state the
          system carries in the first Mamba-2 layer (whose inputs are the
          embeddings alone) against the reference's at the same position,
          over the ``state_heads`` SLOWEST heads (:func:`slow_heads`): the
          root mean square over those heads of |state - reference| /
          |reference| a head.  The logits cannot tell
          a state kept in fewer bits than the configuration states (PR 41);
          in a slow head the rounding of the state after every position
          adds up where the inputs' rounding averages out.

        The reference reads the same seeded tensors the checkpoint was made
        from, in the published layout, raised to float32 a few at a time on
        the replica's device beside the engine.  It is causal, so every
        sequence is padded to ``pad_to`` positions and its rows to
        ``rows_to``: the reference, compiled part by part, meets one shape
        in every run.  Three more readings of the reference against itself
        on the request with the shortest prompt, each what a system at fault
        would read: matrix inputs rounded to ``lowprec_bits`` mantissa bits
        (``lowprec_err``), every Mamba-2 layer forgetting at position
        ``drop_state_at`` (``dropstate_err``), and the carried state rounded
        to ``state_bits`` mantissa bits after every position (``state_kept``:
        what such a system would read as ``state``)."""
        import time

        import jax
        import jax.numpy as jnp

        engine = self._ensure_engine()
        pub = weights_nemotron.Published(cfg, seed, dtype)
        view, share = published_view(cfg), held(cfg)
        pad = cfg.get("assumed", {}).get("pad_token_id") or 0
        t0 = time.time()
        system, carried = replayed_logits(
            engine, prompts, [a[:n_err] for a in answers], slots)
        seconds = {"system": time.time() - t0}
        shortest = min(range(len(prompts)), key=lambda i: len(prompts[i]))

        def tensor(name):
            # a column-major matrix goes up as the buffer lies and is turned
            # on the device: the host would turn it element by element
            a = pub.tensor(name)
            if a.ndim == 2 and not a.flags.c_contiguous:
                return jnp.asarray(a.T).T
            return a

        def job(i, **how):
            p, a = prompts[i], answers[i]
            ids = list(p) + list(a[:-1])
            rows = list(range(len(p) - 1, len(ids)))
            rows += [rows[-1]] * (rows_to - len(rows))
            ids += [pad] * (pad_to - len(ids))
            seen = len(p) + min(n_err, len(a)) - 1     # the replay's end
            return {"ids": ids, "rows": rows, "state_after": seen, **how}

        jobs = [job(i) for i in range(len(prompts))] + [
            # 3 bits multiply exactly in one bfloat16 pass (float32 sums)
            job(shortest, round_inputs=round_mantissa(lowprec_bits),
                rounded_precision="default" if lowprec_bits <= 7 else None),
            job(shortest, drop_state_at=drop_state_at),
            job(shortest, round_state=round_mantissa(state_bits))]
        got = []
        for k, one in enumerate(jobs):
            t0 = time.time()
            got += nemotron_h.forward_each(tensor, view, [one], held=share)
            seconds[f"reference_{k}"] = time.time() - t0

        first = view["hybrid_override_pattern"].index("M")

        def by_head(state, ref):
            """|state - ref| / |ref| a head, the slowest head first."""
            order = slow_heads(pub, first)
            return (np.linalg.norm((state - ref)[order], axis=(1, 2))
                    / np.linalg.norm(ref[order], axis=(1, 2)))

        def apart(state, ref):
            """The root mean square of that over the ``state_heads`` slowest
            heads: each head counts alike, whatever its state's norm (taken
            over all their states together, the head with the largest state
            decided the reading)."""
            return float(np.sqrt(np.mean(
                by_head(state, ref)[:state_heads] ** 2)))

        out = []
        for i, (a, p, ours) in enumerate(zip(answers, prompts, system)):
            want = got[i]["logits"][:len(a)]
            gap = got[i]["router_gap"][len(p) - 1:len(p) - 1 + len(a)]
            scale = want.max(-1) - np.median(want, -1)
            chosen = want[np.arange(len(a)), np.asarray(a)]
            k = len(ours)
            v = {"tokens": len(a),
                 "err": (np.abs(ours - want[:k]).max(-1) / scale[:k]).tolist(),
                 "margin": ((want.max(-1) - chosen) / scale).tolist(),
                 "gap": gap.tolist(),
                 "state": apart(carried[i], got[i]["states"][0]),
                 "state_by_head": by_head(carried[i],
                                          got[i]["states"][0]).tolist(),
                 "exact": int((want.argmax(-1) == np.asarray(a)).sum()),
                 "reference_on": jax.devices()[0].platform}
            if i == shortest:
                against = lambda other: (np.abs(  # noqa: E731
                    other["logits"][:len(a)] - want).max(-1) / scale
                    )[:k].tolist()
                v["lowprec_err"] = against(got[-3])
                v["dropstate_err"] = against(got[-2])
                v["state_kept"] = apart(got[-1]["states"][0],
                                        got[i]["states"][0])
                v["state_kept_by_head"] = by_head(
                    got[-1]["states"][0], got[i]["states"][0]).tolist()
            out.append(v)
        out[0]["seconds"] = seconds     # where the check's time went
        return out


ObservedSSDEngineDeployment = Deployment(
    func_or_class=ObservedSSDEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
