"""The replica of the ``ssmserve`` kind: ``worker_hooks.ObservedEngineServer``
(facts, a profiler window) plus the comparison of a served hybrid
state-space / attention ``CausalLM`` with the benchmark's own reference,
made INSIDE the replica (the only process that holds the chip and the
parameters; never a process of its own) and OUTSIDE the measured window,
ON requests the window finished.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_ssm
from benchmark.reference import jamba
from benchmark.worker_hooks import ObservedEngineServer
from benchmark.worker_hooks_lm import round_mantissa


def replayed_logits(engine, prompts: List[List[int]],
                    answers: List[List[int]], slots: List[int]
                    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """The logits the SYSTEM computes for each token of ``answers`` (the
    answer teacher-forced), through the ENGINE'S OWN pool and state rows, at
    its own geometry (every slot in the decode program, the same pages a
    slot and slot length), and in the engine's own order of work.  The two
    programs are the engine bodies' text up to the head
    (``make_prefill_chunk_logits_body``, ``make_paged_decode_logits_body``):
    the engine's compiled programs hand out tokens, not logits; what they
    streamed under load is held by ``margin`` (below).

    One iteration is the engine's: at most one prefill chunk, then one
    decode step over every row past its prompt.  ``slots`` are fewer than
    the sequences, so: a row mid-prefill rides the decode steps issued
    between its chunks, at position 0 with the null table row, its state
    held; the other slots of the pool ride every step the same way; and the
    last sequence takes a slot an earlier one left, whose first chunk starts
    the state from zeros.  The engine must be idle (the window's requests
    have ended): its cache is taken under its step lock and handed back.

    Returns the logits a sequence ``[len(answer), V]`` and, a sequence, the
    state ``[d_inner, d_state]`` the first Mamba layer holds in the
    sequence's row once its last token has gone in (``len(prompt) +
    len(answer) - 1`` positions)."""
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
    first = f"layer_{model.config.layer_kinds().index('mamba')}"

    states = [None] * len(prompts)

    def leave(i, s):
        states[i] = np.asarray(cache[first]["mamba"]["ssm_state"][s]).T
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


class ObservedSSMEngineServer(ObservedEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``): the rows
    #: whose state a step advanced and the positions they held, as issued
    TRACED_COUNTERS = ("steps_issued", "mixed_steps", "ssd_rows_live",
                       "ssd_positions_live", "ssm_rows_held")

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]], slots: List[int],
                              n_err: int, pad_to: int, lowprec_bits: int,
                              drop_state_at: int, state_bits: int
                              ) -> List[Dict[str, Any]]:
        """Hold the system to the reference on requests the WINDOW finished:
        ``prompts`` and the whole ``answers`` the engine streamed for them
        under load.  Per request, the reference teacher-forced on prompt
        plus answer, per streamed token ``j``:

        * ``margin`` (every token): how far the streamed token's REFERENCE
          logit lies under the reference's largest, over the reference
          row's top-to-median distance: the engine's own compiled programs,
          with every slot in them and most of them live;
        * ``err`` (the first ``n_err`` tokens): max over the vocabulary of
          |system logit - reference logit| on the same scale, the system's
          logits being :func:`replayed_logits`;
        * ``state`` (once, after those ``n_err`` tokens): the state-space
          state the system carries in the first Mamba layer (whose inputs
          are the embeddings alone) against the reference's at the same
          position, over the SLOW channels (the eighth with the smallest
          published step-size bias: time constants of 500 to 1,000
          positions): |state - reference| / |reference| of all their
          states together.  The logits cannot tell a state kept in fewer
          bits than the configuration states (read: 0.02 where the
          system's own ``err`` is 0.04), and over all channels the state
          hardly can (the system's bf16 inputs read 0.002 to 0.004, a bf16
          state 0.006 to 0.007).  In a slow channel the inputs' rounding
          averages out over the hundreds of positions it sums, and the
          rounding of the state after every position adds up.

        The reference reads the same seeded tensors the checkpoint was made
        from, in the published layout, raised to float32 one at a time on
        the replica's device beside the engine.  It is causal, so every
        sequence is padded to ``pad_to`` positions and its rows to the
        longest answer: the reference, compiled operation by operation,
        meets one shape in every run.  Three more readings of the reference
        against itself, each what a system at fault would read as ``err``:
        matrix inputs rounded to ``lowprec_bits`` mantissa bits and every
        Mamba layer forgetting at position ``drop_state_at`` (the request
        with the shortest prompt, its first ``n_err`` tokens: the first such
        a loss would spoil), and the carried state rounded to ``state_bits``
        mantissa bits after every position (the longest sequence: every
        token's ``state_err``, and ``state_kept`` what such a system would
        read as ``state``)."""
        import time

        import jax

        engine = self._ensure_engine()
        pub = weights_ssm.Published(cfg, seed, dtype)
        t0 = time.time()
        system, carried = replayed_logits(
            engine, prompts, [a[:n_err] for a in answers], slots)
        seconds = {"system": time.time() - t0}
        most = max(len(a) for a in answers)
        shortest = min(range(len(prompts)), key=lambda i: len(prompts[i]))
        longest = max(range(len(prompts)),
                      key=lambda i: len(prompts[i]) + len(answers[i]))

        def reference(i, **how):
            p, a = prompts[i], answers[i]
            ids = list(p) + list(a[:-1])
            rows = list(range(len(p) - 1, len(ids)))
            rows += [rows[-1]] * (most - len(rows))
            ids += [cfg.get("assumed", {}).get("pad_token_id") or 0] * (
                pad_to - len(ids))
            seen = len(p) + min(n_err, len(a)) - 1     # the replay's end
            got = jamba.forward(pub.tensor, cfg, ids, rows,
                                state_after=seen, **how)
            return got["logits"][:len(a)], got["states"][0]

        first = next(i for i in range(cfg["num_hidden_layers"])
                     if not jamba.layer_is_attention(cfg, i))
        bias = np.asarray(pub.tensor(
            f"model.layers.{first}.mamba.dt_proj.bias")).astype(np.float32)
        slow = np.argsort(bias, kind="stable")[:len(bias) // 8]

        def apart(state, ref):
            """|state - ref| / |ref| over the slow channels' states."""
            return float(np.linalg.norm(state[slow] - ref[slow])
                         / np.linalg.norm(ref[slow]))

        out = []
        for i, (a, got) in enumerate(zip(answers, system)):
            t0 = time.time()
            want, state = reference(i)
            seconds[f"reference_{i}"] = time.time() - t0
            scale = want.max(-1) - np.median(want, -1)
            chosen = want[np.arange(len(a)), np.asarray(a)]
            k = len(got)
            v = {"tokens": len(a),
                 "err": (np.abs(got - want[:k]).max(-1) / scale[:k]).tolist(),
                 "margin": ((want.max(-1) - chosen) / scale).tolist(),
                 "state": apart(carried[i], state),
                 "exact": int((want.argmax(-1) == np.asarray(a)).sum()),
                 "reference_on": jax.devices()[0].platform}

            def against(**how):
                logits, kept = reference(i, **how)
                return ((np.abs(logits - want).max(-1) / scale).tolist(),
                        apart(kept, state))

            if i == shortest:
                v["lowprec_err"] = against(
                    round_inputs=round_mantissa(lowprec_bits))[0][:k]
                v["dropstate_err"] = against(
                    drop_state_at=drop_state_at)[0][:k]
            if i == longest:
                v["state_err"], v["state_kept"] = against(
                    round_state=round_mantissa(state_bits))
            out.append(v)
        out[0]["seconds"] = seconds     # where the check's time went
        return out


ObservedSSMEngineDeployment = Deployment(
    func_or_class=ObservedSSMEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
