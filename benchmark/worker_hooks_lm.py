"""The replica of the ``lmserve`` kind: ``worker_hooks.ObservedEngineServer``
(facts, a profiler window) plus the comparison of a served sparse-expert
``CausalLM`` with the benchmark's own reference, made INSIDE the replica
(the only process that holds the chip and the parameters) and OUTSIDE the
measured window.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_lm
from benchmark.reference import olmoe
from benchmark.worker_hooks import ObservedEngineServer


def round_mantissa(bits: int):
    """``f(x)``: x rounded to ``bits`` explicit mantissa bits (bf16 has 7,
    float8 e4m3 has 3), exponent range untouched: the reference's matrix
    inputs in a LOWER precision than the configuration states."""
    import jax.numpy as jnp

    def f(x):
        m, e = jnp.frexp(x)
        return jnp.ldexp(jnp.round(m * 2.0 ** (bits + 1))
                         / 2.0 ** (bits + 1), e)

    return f


def paged_logits(model, params, page_len: int, prompts: List[List[int]],
                 answers: List[List[int]]) -> List[np.ndarray]:
    """For each prompt and the tokens streamed for it: the logits the SYSTEM
    computes for those positions through its own chunked prefill and paged
    single-token decode, the answer teacher-forced.  The two programs are
    the engine bodies' own text up to the head (``make_prefill_chunk_logits_
    body``, ``make_paged_decode_logits_body``), over a private pool of one
    slot a prompt.  Row ``j`` of a result is the distribution the ``j``-th
    streamed token was drawn from."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm.generate import (
        init_paged_cache, make_paged_decode_logits_body,
        make_prefill_chunk_logits_body)

    n_seq, c = len(prompts), page_len
    longest = max(len(p) + len(a) for p, a in zip(prompts, answers))
    pps = -(-longest // c)
    slot_len = pps * c
    cache = init_paged_cache(model, n_seq, n_seq * pps + 1, c, pps)
    table = 1 + np.arange(n_seq * pps, dtype=np.int32).reshape(n_seq, pps)
    chunk = jax.jit(make_prefill_chunk_logits_body(model, c, slot_len),
                    donate_argnums=(1,))
    step = jax.jit(make_paged_decode_logits_body(model, slot_len),
                   donate_argnums=(1,))
    pad = model.config.pad_token_id
    out = [np.zeros((len(a), model.config.vocab_size), np.float32)
           for a in answers]
    for s, p in enumerate(prompts):
        for p0 in range(0, len(p), c):
            ids = np.full((1, c), pad, np.int32)
            piece = p[p0:p0 + c]
            ids[0, :len(piece)] = piece
            cache, _, logits = chunk(
                params, cache, jnp.asarray(ids), jnp.int32(p0),
                jnp.int32(len(piece) - 1), jnp.asarray(table[s]))
        out[s][0] = np.asarray(logits)
    steps = max(len(a) for a in answers) - 1
    for j in range(1, steps + 1):
        # a sequence whose answer is shorter rides along on the null page
        live = [j < len(a) for a in answers]
        tok = np.array([a[j - 1] if ok else 0
                        for a, ok in zip(answers, live)], np.int32)
        pos = np.array([len(p) - 1 + j if ok else 0
                        for p, ok in zip(prompts, live)], np.int32)
        tbl = np.where(np.array(live)[:, None], table, 0).astype(np.int32)
        cache, _, logits, _ = step(params, cache, jnp.asarray(tok),
                                   jnp.asarray(pos), jnp.asarray(tbl))
        logits = np.asarray(logits)
        for s, ok in enumerate(live):
            if ok:
                out[s][j] = logits[s]
    return out


class ObservedLMEngineServer(ObservedEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``), beside the
    #: K/V positions the captured steps had live (its ``ReadWatch``: the
    #: engine's ``stats()`` hold no such count for a model that keeps
    #: neither rings nor a latent pool)
    TRACED_COUNTERS = ("moe_steps", "moe_steps_alone",
                       "moe_experts_streamed", "moe_experts_streamed_alone",
                       "steps_issued", "mixed_steps")

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]],
                              lowprec_bits: int) -> List[Dict[str, Any]]:
        """Hold the system to the reference on ``prompts`` and the tokens
        the engine streamed for them.  Per prompt, per streamed token ``j``:

        * ``err``: max over the vocabulary of |system logit - reference
          logit| over the reference row's top-to-median distance, the
          system's logits being :func:`paged_logits`;
        * ``margin``: how far the streamed token's REFERENCE logit lies
          under the reference's largest, on the same scale;
        * ``gap``: the reference's smallest distance (log probability)
          between its k-th and (k+1)-th expert at that position, over
          layers: where it is tiny the bf16 system may route differently.

        The reference reads the same seeded tensors the checkpoint was made
        from, in the published layout, and raises them to float32 one at a
        time on the replica's device beside the engine (matrix products at
        the highest precision).  For the shortest prompt the reference is
        computed once more with its matrix inputs rounded to
        ``lowprec_bits`` mantissa bits: ``lowprec_err`` is what a system
        computing in that precision would read as ``err``."""
        import jax

        engine = self._ensure_engine()
        pub = weights_lm.Published(cfg, seed, dtype)
        system = paged_logits(engine.model, engine.params,
                              engine.config.page_len, prompts, answers)
        shortest = min(range(len(prompts)), key=lambda i: len(prompts[i]))
        out = []
        for i, (p, a, got) in enumerate(zip(prompts, answers, system)):
            ids = list(p) + list(a[:-1])
            rows = range(len(p) - 1, len(ids))
            ref = olmoe.forward(pub.tensor, cfg, ids, rows)
            want = ref["logits"]
            scale = want.max(-1) - np.median(want, -1)
            err = np.abs(got - want).max(-1) / scale
            chosen = want[np.arange(len(a)), np.asarray(a)]
            v = {"tokens": len(a), "err": err.tolist(),
                 "margin": ((want.max(-1) - chosen) / scale).tolist(),
                 "gap": ref["router_gap"][len(p) - 1:].tolist(),
                 "exact": int((want.argmax(-1) == np.asarray(a)).sum()),
                 "reference_on": jax.devices()[0].platform}
            if i == shortest:
                low = olmoe.forward(pub.tensor, cfg, ids, rows,
                                    round_mantissa(lowprec_bits))
                v["lowprec_err"] = (np.abs(low["logits"] - want).max(-1)
                                    / scale).tolist()
            out.append(v)
        return out


ObservedLMEngineDeployment = Deployment(
    func_or_class=ObservedLMEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
