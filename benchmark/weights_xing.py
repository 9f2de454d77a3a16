"""Seeded weights of the published ``xing4_0`` configuration
(Xing4.0-29B-A4B), in the PUBLISHED layout and names, and a checkpoint of
them that the replica loads the normal way.

Everything a ``deepseek_v3`` layer has is ``benchmark/weights_mla.py``'s, value
for value (``Published`` here is its class with more names: the pool of
seeded normal values, the router's gains and selection bias, norm weights of
ones); the chip holds every expert and the whole vocabulary, so
``weights_mla.held`` is all 64 and ``published_view`` the file as it is.  What
is added is a sublayer's hyper-connection, under the names the configuration
file lists (``assumed.mhc_tensor_names``), float32 whatever the model's
dtype, made as the file's ``assumed.mhc_init`` says:

* ``phi [n*n + 2n, n*C]``: normal, standard deviation ``(n*C)^(-1/2)``, so
  each number of ``m = RMSNorm(vec(X)) phi`` has unit variance over tokens;
* ``alpha``: ``(1, 1, 0.5)`` times a seeded factor in 0.9 to 1.1;
* ``b``: every part carries an OFFSET a stream, the four values (1, 1/3,
  -1/3, -1) dealt to the streams by the seed (streams that are read from and
  written to unequally, as a trained model's are): ``H~pre`` 1.5 times the
  offsets + normal 0.5 (``H_pre`` from 0.18 to 0.82), ``H~post`` the offsets
  + normal 0.5, ``H~res`` ``2 I`` + 1.5 times a column deal + 1.5 times a row
  deal + normal 0.25.

With those the maps move by token and the streams mix (``H_res``'s diagonal
averages 0.3 to 0.9), and ``exp(H~res)`` is far from doubly stochastic
before the projection: a program that dropped ``v phi``, ran another number of
Sinkhorn rounds or left the streams unmixed computes other logits, and the
check's controls read how far.  With exchangeable streams (``b`` normal 0.5
and ``1.5 I`` + normal 0.25) one round already lands within 0.02 of twenty
and the control at one round read 0.0196 where the system reads 0.011; and
the sum of the streams on exit does not depend on ``H_res`` at all (its
columns sum to 1), which reaches the logits only through a later sublayer's
unequal ``H_pre``: with ``H_pre`` near a half throughout the control at
``H_res = I`` read as low as 0.056 (PERF.md, PR 58).
"""

from __future__ import annotations

import os
import re
import zlib
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import weights_mla
from benchmark.weights_lm import _RAW

#: every expert and the whole vocabulary are held: ``weights_mla``'s mapping
lm_config = weights_mla.lm_config


def mhc_names(cfg: Dict[str, Any]) -> Dict[str, str]:
    """The published names of a hyper-connection's tensors: what the
    configuration file assumes (the catalog gives none)."""
    return dict(cfg["assumed"]["mhc_tensor_names"])


class Published(weights_mla.Published):
    def __init__(self, cfg: Dict[str, Any], seed: int, dtype: str):
        super().__init__(cfg, seed, dtype)
        self._hc = {
            kind: re.compile(re.escape(pattern).replace(
                r"\{layer\}", r"\d+").replace(r"\{sublayer\}", "(attn|mlp)"))
            for kind, pattern in mhc_names(cfg).items()}

    def _hc_kind(self, name: str):
        """``phi`` / ``b`` / ``alpha`` where ``name`` is a hyper-connection's
        tensor (of any layer and sublayer), else None."""
        return next((kind for kind, rx in self._hc.items()
                     if rx.fullmatch(name)), None)

    def shape(self, name: str) -> Tuple[int, ...]:
        kind = self._hc_kind(name)
        if kind is None:
            return super().shape(name)
        n = self.cfg["hc_mult"]
        maps = n * n + 2 * n
        return {"phi": (maps, n * self.cfg["hidden_size"]), "b": (maps,),
                "alpha": (3,)}[kind]

    def tensor(self, name: str) -> np.ndarray:
        kind = self._hc_kind(name)
        if kind is None:
            return super().tensor(name)
        rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
        n = self.cfg["hc_mult"]
        if kind == "phi":
            rows, cols = self.shape(name)
            # column-major, as every matrix the program keeps transposed
            return (rng.standard_normal((cols, rows), dtype=np.float32)
                    * np.float32(cols ** -0.5)).T
        if kind == "alpha":
            return (np.array([1.0, 1.0, 0.5])
                    * rng.uniform(0.9, 1.1, 3)).astype(np.float32)
        dealt = lambda scale: scale * rng.permutation(  # noqa: E731
            np.linspace(1.0, -1.0, n))
        res = (2.0 * np.eye(n) + dealt(1.5)[None, :] + dealt(1.5)[:, None]
               + 0.25 * rng.standard_normal((n, n)))
        pre = dealt(1.5) + 0.5 * rng.standard_normal(n)
        post = dealt(1.0) + 0.5 * rng.standard_normal(n)
        return np.concatenate([pre, post, res.ravel()]).astype(np.float32)


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str,
                     max_seq_len: int):
    """A ``Checkpoint`` directory at ``path``: the ``LMConfig`` the published
    keys map to and the seeded tensors through the program's importer, the
    hyper-connections float32 among the weights of ``dtype``."""
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    config = lm_config(cfg, dtype, max_seq_len)
    pub = Published(cfg, seed, dtype)
    params = hf_import.convert_deepseek_v3_state_dict(
        pub.raw, config, names=mhc_names(cfg))
    width = np.dtype(_RAW[np.dtype(pub.dtype).itemsize])
    params = jax.tree_util.tree_map(
        lambda a: a.view(pub.dtype if a.dtype == width else np.float32),
        params)
    os.makedirs(path, exist_ok=True)
    ckpt = Checkpoint.from_model(model_config=config, path=path)
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        weights_mla.write_params(params, f)
    return ckpt
