"""The replica of the ``mhcserve`` kind: ``worker_hooks_mla``'s replica
(facts, a profiler window with the engine's counters read inside it, the
system's logits replayed through the engine's own latent pool) with the
comparison of a served ``xing4_0`` ``CausalLM`` (the ``deepseek_v3`` layer
inside four residual streams) against the benchmark's own reference
(``benchmark/reference/xing.py``), made INSIDE the replica and OUTSIDE the
measured window, ON requests the window finished.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from tpu_air.serve.deployment import Deployment

from benchmark import weights_xing
from benchmark.reference import xing
from benchmark.worker_hooks_mla import (ObservedMLAEngineServer,
                                        replayed_logits, round_mantissa)

#: sequences the reference computes side by side (they share the fetch of
#: each tensor): each keeps its four float32 streams on the device, 0.46 GB
#: at 7,936 positions, beside an engine that holds 11.5 GB of the 15.75
REFERENCE_TOGETHER = 2

#: ``stats()`` counters whose change over the profiler's window says what
#: the CAPTURED steps did: ``worker_hooks_mla``'s and the residual streams'
TRACED_COUNTERS = ("moe_steps", "moe_steps_alone", "moe_experts_streamed",
                   "moe_experts_streamed_alone", "latent_positions_live",
                   "mhc_rows_live", "mhc_rows_live_alone", "steps_issued",
                   "mixed_steps")


class ObservedMHCEngineServer(ObservedMLAEngineServer):
    #: read inside the profiler's window (``_trace_with_counts``, which keeps
    #: them as soon as they are read and not behind ``stop_trace``: two
    #: traced runs of this cell (PR 58) ended with the capture written and
    #: no counts, and the kind then pairs the WHOLE window's counts with the
    #: capture's times: a decode step's experts read 104.8 % and 123 % so)
    TRACED_COUNTERS = TRACED_COUNTERS

    def bench_reference_check(self, cfg: Dict[str, Any], seed: int,
                              dtype: str, prompts: List[List[int]],
                              answers: List[List[int]], slots: List[int],
                              n_err: int, pad_to: int, rows_to: int,
                              lowprec_bits: int) -> List[Dict[str, Any]]:
        """Hold the system to the reference on requests the WINDOW finished,
        as ``ObservedMLAEngineServer.bench_reference_check`` does (``margin``
        on every streamed token, ``err`` on the first ``n_err`` through
        :func:`replayed_logits`, ``gap`` the reference's routing margin),
        with the whole model on the chip (every expert, the whole
        vocabulary) and the reference teacher-forced in blocks: a sequence
        is padded to ``pad_to`` positions, its logits are taken at the
        ``rows_to`` streamed positions alone (a 7,680-token prompt's logits
        over 131,072 ids are 4 GB), and ``REFERENCE_TOGETHER`` sequences
        share each tensor's fetch.

        Four more readings of the reference against itself on the request
        with the shortest prompt, each what a system at fault would read as
        ``err``: matrix inputs at ``lowprec_bits`` mantissa bits
        (``lowprec_err``), the softmax scale without yarn's factor
        (``noyarn_err``), ONE Sinkhorn round instead of the configuration's
        twenty (``sinkhorn1_err``) and ``H_res = I``, streams that never mix
        (``identity_err``); ``control_kept`` says at how many of that
        request's streamed positions each still chooses the reference's
        token.  And what a system that served ANOTHER request's work would
        read on every request, from the arrays in hand: the next request's
        streamed tokens held to this one's reference rows
        (``planted_margin``: another slot's token) and its replayed logits
        held to them (``planted_err``: another row's logits)."""
        import time

        import jax
        import jax.numpy as jnp

        engine = self._ensure_engine()
        pub = weights_xing.Published(cfg, seed, dtype)
        names = weights_xing.mhc_names(cfg)
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
            "noyarn_err": dict(yarn_softmax_scale=False),
            "sinkhorn1_err": dict(sinkhorn_iters=1),
            "identity_err": dict(identity_res=True),
        }
        jobs = [job(i) for i in range(len(prompts))] + [
            job(shortest, **how) for how in controls.values()]
        got = []
        for k in range(0, len(jobs), REFERENCE_TOGETHER):
            t0 = time.time()
            got += xing.forward_each(
                tensor, cfg, jobs[k:k + REFERENCE_TOGETHER], names=names)
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
                v["control_kept"] = {}
                for j, key in enumerate(controls):
                    other = got[len(prompts) + j]["logits"][:len(a)]
                    v[key] = (np.abs(other - want).max(-1) / scale
                              )[:k].tolist()
                    v["control_kept"][key] = int(
                        (other.argmax(-1) == want.argmax(-1)).sum())
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


ObservedMHCEngineDeployment = Deployment(
    func_or_class=ObservedMHCEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
