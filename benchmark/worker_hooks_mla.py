"""The replica of the ``mlaserve`` kind: ``worker_hooks.ObservedEngineServer``
(facts, a profiler window) plus the comparison of a served latent-attention
sparse-expert ``CausalLM`` (one expert-parallel rank's share of a published
``deepseek_v3`` model) with the benchmark's own reference, made INSIDE the
replica (the only process that holds the chip and the parameters; never a
process of its own) and OUTSIDE the measured window, ON requests the window
finished.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_mla
from benchmark.reference import deepseek
from benchmark.worker_hooks import ObservedEngineServer


def round_mantissa(bits: int):
    """``f(x)``: float32 ``x`` rounded to ``bits`` explicit mantissa bits,
    ties to even, exponent range untouched: what
    ``worker_hooks_lm.round_mantissa`` computes, on the bit pattern (add half
    of the last kept bit, clear what lies under it): ``frexp`` and ``ldexp``
    fused into every product of the reference took the chip's compiler 110 s
    a check."""
    import jax
    import jax.numpy as jnp

    shift = 23 - bits
    half, low = (1 << (shift - 1)) - 1, (1 << shift) - 1

    def f(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        u = (u + (half + ((u >> shift) & 1))) & jnp.uint32(0xFFFFFFFF ^ low)
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    return f


def replayed_logits(engine, prompts: List[List[int]],
                    answers: List[List[int]], slots: List[int]
                    ) -> List[np.ndarray]:
    """The logits the SYSTEM computes for each token of ``answers`` (the
    answer teacher-forced), through the ENGINE'S OWN latent page pool at its
    own geometry (every slot in the decode program, the same pages a slot and
    slot length), and in the engine's own order of work.  The two programs
    are the engine bodies' text up to the head
    (``make_prefill_chunk_logits_body``: expanded attention over the slot's
    gathered latent; ``make_paged_decode_logits_body``: the absorbed read):
    the engine's compiled programs hand out tokens, not logits; what they
    streamed under load is held by ``margin`` (``bench_reference_check``).

    One iteration is the engine's: at most one prefill chunk, then one
    decode step over every row past its prompt.  ``slots`` are fewer than the
    sequences, so: a row mid-prefill rides the decode steps issued between
    its chunks, at position 0 with the null table row; the other slots of
    the pool ride every step the same way; and the last sequence takes a slot
    an earlier one left.  The engine must be idle (the window's requests have
    ended): its cache is taken under its step lock and handed back.  The
    replay writes the pool's pages by its own table, so the prefix cache
    (whose pages an idle engine holds alone) is emptied first: what is served
    afterwards finds no page whose content the replay changed.

    Returns the logits a sequence ``[len(answer), V]``."""
    import time

    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm.generate import (
        make_paged_decode_logits_body, make_prefill_chunk_logits_body)

    cfg, model = engine.config, engine.model
    c, n_slots, pps = cfg.page_len, cfg.num_slots, cfg.pages_per_slot()
    chunk_body = make_prefill_chunk_logits_body(model, c, cfg.slot_len)
    step_body = make_paged_decode_logits_body(model, cfg.slot_len)
    chunk = jax.jit(lambda *a: chunk_body(*a)[::2], donate_argnums=(1,))
    step = jax.jit(lambda *a: step_body(*a)[:3:2], donate_argnums=(1,))
    # nothing is live in an idle engine: a slot's pages are its own run
    table = 1 + np.arange(n_slots * pps, dtype=np.int32).reshape(n_slots, pps)
    pad = model.config.pad_token_id
    out = [np.zeros((len(a), model.config.vocab_size), np.float32)
           for a in answers]
    waiting, free = list(range(len(prompts))), list(slots)
    filling, decoding = None, {}     # [sequence, slot, p0]; slot -> (seq, j)
    patience = time.monotonic() + 60.0
    while not engine.idle() and time.monotonic() < patience:
        time.sleep(0.05)
    with engine._step_lock:
        if not engine.idle():
            raise RuntimeError("the check replays through the engine's own "
                               "pool: the engine must be idle")
        prefix = engine.pool.prefix
        if prefix is not None:
            prefix.evict(prefix.resident_pages())
            if prefix.resident_pages():
                raise RuntimeError("an idle engine's prefix cache holds "
                                   "pages it cannot give up")
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
                        jnp.asarray(table[s]))
                    filling[2] = p0 + c
                    if p0 + c >= len(prompts[i]):
                        out[i][0] = np.asarray(logits)
                        filling = None
                        if len(answers[i]) > 1:
                            decoding[s] = (i, 1)
                        else:
                            free.append(s)
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
                            free.append(s)
        finally:
            engine.cache = cache
    return out


#: sequences the reference computes side by side in the check (the four
#: requests and two readings more, in two passes): each keeps three float32
#: copies of its rows on the device, 0.35 GB at 4096 positions
REFERENCE_TOGETHER = 3

#: ``stats()`` counters whose change over the profiler's window says what
#: the CAPTURED steps did (the window's own, not the run's average)
TRACED_COUNTERS = ("moe_steps", "moe_steps_alone", "moe_experts_streamed",
                   "moe_experts_streamed_alone", "latent_positions_live")


class ObservedMLAEngineServer(ObservedEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``: starting
    #: and stopping the profiler take seconds in which the engine steps on,
    #: and counts over those would be another stretch's)
    TRACED_COUNTERS = TRACED_COUNTERS

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]], slots: List[int],
                              n_err: int, pad_to: int, rows_to: int,
                              lowprec_bits: int) -> List[Dict[str, Any]]:
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
          position is to a tie, over layers (``deepseek.route``): where it
          is tiny the bf16 system may choose another expert or group.

        The reference reads the same seeded tensors the checkpoint was made
        from, in the published layout, raised to float32 a few at a time (one
        layer's attention, one expert) on the replica's device beside the
        engine, ``REFERENCE_TOGETHER`` sequences sharing each fetch
        (``deepseek.forward_each``).  It is causal, so every
        sequence is padded to ``pad_to`` positions and its rows to
        ``rows_to``: the reference, compiled part by part, meets one
        shape in every run.  Two more readings of the reference against
        itself on the request with the shortest prompt, each what a system
        at fault would read as ``err``: matrix inputs rounded to
        ``lowprec_bits`` mantissa bits (``lowprec_err``), and the softmax
        scale without yarn's ``(0.1 ln factor + 1)^2`` (``noyarn_err``)."""
        import time

        import jax
        import jax.numpy as jnp

        engine = self._ensure_engine()
        pub = weights_mla.Published(cfg, seed, dtype)
        view, share = weights_mla.published_view(cfg), weights_mla.held(cfg)
        pad = cfg.get("assumed", {}).get("pad_token_id") or 0
        t0 = time.time()
        system = replayed_logits(
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
            return {"ids": ids, "rows": rows, **how}

        # every request, and the shortest twice more as a system at fault
        # would compute it; REFERENCE_TOGETHER at a time share the fetch of
        # each tensor (a sequence's float32 rows stay on the device meanwhile)
        jobs = [job(i) for i in range(len(prompts))] + [
            # 3 bits and all that a bfloat16 holds multiply exactly in one of
            # its passes (float32 sums): no need of the six of "highest"
            job(shortest, round_inputs=round_mantissa(lowprec_bits),
                rounded_precision="default" if lowprec_bits <= 7 else None),
            job(shortest, yarn_softmax_scale=False)]
        got = []
        for k in range(0, len(jobs), REFERENCE_TOGETHER):
            t0 = time.time()
            got += deepseek.forward_each(
                tensor, view, jobs[k:k + REFERENCE_TOGETHER], held=share)
            seconds[f"reference_{k}"] = time.time() - t0

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
                 "exact": int((want.argmax(-1) == np.asarray(a)).sum()),
                 "reference_on": jax.devices()[0].platform}
            if i == shortest:
                against = lambda other: (np.abs(  # noqa: E731
                    other["logits"][:len(a)] - want).max(-1) / scale
                    )[:k].tolist()
                v["lowprec_err"] = against(got[-2])
                v["noyarn_err"] = against(got[-1])
            out.append(v)
        out[0]["seconds"] = seconds     # where the check's time went
        return out


ObservedMLAEngineDeployment = Deployment(
    func_or_class=ObservedMLAEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
