"""The replica of the ``swaserve`` kind: ``worker_hooks.ObservedEngineServer``
(facts, a profiler window) with the engine's counters read inside the
profiler's window, the system's logits replayed through the engine's own
page pool AND rings, and the comparison of a served ``laguna`` ``CausalLM``
(window layers that keep a ring a slot beside full layers that keep pages)
against the benchmark's own reference (``benchmark/reference/laguna.py``),
made INSIDE the replica and OUTSIDE the measured window, ON requests the
window finished.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_swa
from benchmark.reference import laguna
from benchmark.worker_hooks import ObservedEngineServer
from benchmark.worker_hooks_mla import round_mantissa

#: sequences the reference computes side by side (they share the fetch of
#: each tensor): each keeps its float32 rows and a layer's q, k, v on the
#: device, 0.25 GB at 4,096 positions, beside an engine that holds 11.6 GB
REFERENCE_TOGETHER = 3


def replayed_logits(engine, prompts: List[List[int]],
                    answers: List[List[int]], slots: List[int]
                    ) -> List[np.ndarray]:
    """The logits the SYSTEM computes for each token of ``answers`` (the
    answer teacher-forced), through the ENGINE'S OWN page pool and rings at
    its own geometry (every slot in the decode program, the same pages a
    slot, the same ring length) and in the engine's own order of work, with
    the engine bodies' text up to the head
    (``make_prefill_chunk_logits_body``, ``make_paged_decode_logits_body``):
    the engine's compiled programs hand out tokens, not logits; what they
    streamed under load is held by ``margin`` (``bench_reference_check``).

    One iteration is the engine's: at most one prefill chunk, then one
    decode step over every row past its prompt.  ``slots`` are fewer than the
    sequences, so: a row mid-prefill rides the decode steps issued between
    its chunks, at position 0 with the null table row and its ring write
    dropped; the other slots of the pool ride every step the same way; and
    the last sequence takes a slot an earlier one left, whose ring still
    holds that tenant's positions.  The engine must be idle; its cache is
    taken under its step lock and handed back (no prefix cache to empty: the
    model turns it off).

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
    chunk = jax.jit(lambda *a, slot: chunk_body(*a, slot=slot)[::2],
                    donate_argnums=(1,))
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


class ObservedSWAEngineServer(ObservedEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``): the
    #: per-step counts the roofline readers divide the CAPTURED programs'
    #: time by; its ``ReadWatch`` counts the page positions a second time,
    #: for the run's notes to set beside the engine's own
    TRACED_COUNTERS = ("moe_steps", "moe_steps_alone",
                       "moe_experts_streamed", "moe_experts_streamed_alone",
                       "window_positions_live", "window_positions_live_alone",
                       "kv_page_positions_live",
                       "kv_page_positions_live_alone", "steps_issued",
                       "mixed_steps")

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]], slots: List[int],
                              n_err: int, pad_to: int, rows_to: int,
                              lowprec_bits: int) -> List[Dict[str, Any]]:
        """Hold the system to the reference on requests the WINDOW finished:
        ``prompts`` and the whole ``answers`` the engine streamed for them
        under load.  Per request, the reference teacher-forced on prompt plus
        answer, per streamed token ``j``:

        * ``margin`` (every token): how far the streamed token's REFERENCE
          logit lies under the reference's largest, over the reference row's
          top-to-median distance: the engine's own compiled programs, with
          every slot in them and most of them live;
        * ``err`` (the first ``n_err`` tokens): max over the vocabulary of
          |system logit - reference logit| on the same scale, the system's
          logits being :func:`replayed_logits`;
        * ``gap`` (every token): how close the reference's routing at that
          position is to a tie, the smallest over the sparse layers
          (``laguna.route``): where it is tiny the bf16 system may choose
          another expert.

        The reference reads the same seeded tensors the checkpoint was made
        from, raised to float32 a few at a time on the replica's device
        beside the engine, ``REFERENCE_TOGETHER`` sequences sharing each
        fetch.  It is causal, so every sequence is padded to ``pad_to``
        positions and its rows to ``rows_to``: the reference, compiled part
        by part, meets one shape in every run.

        Six more readings of the reference against itself on the request
        with the shortest prompt, each what a system at fault would read as
        ``err``: matrix inputs at ``lowprec_bits`` mantissa bits
        (``lowprec_err``), the window mask left out (``nowindow_err``), the
        gate left out (``nogate_err``), rope on the whole head of a full
        layer (``wholerope_err``), yarn's factor off cos and sin
        (``noyarn_err``) and the NEXT request's K and V under the sliding
        layers (``otherring_err``: another slot's ring); ``control_kept``
        says at how many of that request's streamed positions each still
        chooses the reference's token.  And what a system that served
        ANOTHER request's work would read on every request, from the arrays
        in hand: the next request's streamed tokens held to this one's
        reference rows (``planted_margin``) and its replayed logits held to
        them (``planted_err``)."""
        import time

        import jax
        import jax.numpy as jnp

        engine = self._ensure_engine()
        pub = weights_swa.Published(cfg, seed, dtype)
        names = weights_swa.names(cfg)
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

        controls = {
            # 3 bits and all that a bfloat16 holds multiply exactly in one of
            # its passes (float32 sums): no need of the six of "highest"
            "lowprec_err": dict(
                round_inputs=round_mantissa(lowprec_bits),
                rounded_precision="default" if lowprec_bits <= 7 else None),
            "nowindow_err": dict(window_mask=False),
            "nogate_err": dict(gate=False),
            "wholerope_err": dict(rope_whole_head=True),
            "noyarn_err": dict(attention_factor=False),
        }
        # every request, and beside them (they share the call, so the fetch)
        # the shortest once more over ANOTHER slot's ring: its sliding layers
        # read the K and V of the request after it
        other = (shortest + 1) % len(prompts)
        t0 = time.time()
        got = laguna.forward_each(
            tensor, cfg, [job(i) for i in range(len(prompts))]
            + [job(shortest, ring_of=other)], names=names)
        ring = got.pop()
        seconds["reference_requests"] = time.time() - t0
        jobs = [job(shortest, **how) for how in controls.values()]
        for k in range(0, len(jobs), REFERENCE_TOGETHER):
            t0 = time.time()
            got += laguna.forward_each(
                tensor, cfg, jobs[k:k + REFERENCE_TOGETHER], names=names)
            seconds[f"reference_controls_{k}"] = time.time() - t0
        got.append(ring)
        controls["otherring_err"] = None

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
                v["control_kept"] = {}
                for j, key in enumerate(controls):
                    other_logits = got[len(prompts) + j]["logits"][:len(a)]
                    v[key] = (np.abs(other_logits - want).max(-1) / scale
                              )[:k].tolist()
                    v["control_kept"][key] = int(
                        (other_logits.argmax(-1) == want.argmax(-1)).sum())
            if len(prompts) > 1:
                nxt = (i + 1) % len(prompts)
                theirs = np.resize(np.asarray(answers[nxt]), len(a))
                v["planted_margin"] = ((
                    want.max(-1) - want[np.arange(len(a)), theirs]) / scale
                    ).tolist()
                rows = np.arange(k) % len(system[nxt])
                v["planted_err"] = (np.abs(system[nxt][rows] - want[:k]
                                           ).max(-1) / scale[:k]).tolist()
            out.append(v)
        out[0]["seconds"] = seconds     # where the check's time went
        return out


ObservedSWAEngineDeployment = Deployment(
    func_or_class=ObservedSWAEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
