"""Seeded weights of a published ``jamba`` configuration, in the PUBLISHED
layout and names (``model.layers.3.mamba.x_proj.weight`` ...), and a
checkpoint of them the replica loads the normal way.

``Published(cfg, seed, dtype).tensor(name)`` is a function of its arguments
alone, so the driver (which writes the checkpoint through the program's
importer, ``tpu_air.models.lm.hf_import``) and the replica's correctness
check (which hands the same tensors to the benchmark's own reference, one at
a time) see the same values without either holding a second copy of 6 GB.

Values: a matrix is a window into a pool of seeded normal values at a seeded
offset, wrapped (the pool's length is prime), at the published
``initializer_range`` (0.02), as ``benchmark/weights_lm.py`` makes them.
Norm weights are ones.  The Mamba scalars get Mamba's own init (the
configuration file's ``assumed.mamba_init`` says why): ``A_log[c, n] =
log(n + 1)``, ``D = 1``, ``dt_proj.bias = softplus^-1(dt0)`` with ``dt0``
log-uniform in [1e-3, 1e-1], ``dt_proj.weight`` uniform in ``+-dt_rank^-0.5``,
the convolution's weight and bias uniform in ``+-d_conv^-0.5``.
"""

from __future__ import annotations

import os
import re
import zlib
from typing import Any, Dict, Tuple

import numpy as np

from benchmark.weights_lm import POOL, _RAW, lm_config, np_dtype


class Published:
    def __init__(self, cfg: Dict[str, Any], seed: int, dtype: str):
        self.cfg, self.seed, self.dtype = cfg, int(seed), np_dtype(dtype)
        self.std = float(cfg.get("assumed", {}).get("initializer_range", 0.02))
        rng = np.random.default_rng([self.seed, 0xC0FFEE])
        scaled = (rng.standard_normal(POOL, dtype=np.float32)
                  * self.std).astype(self.dtype)
        # as plain integers: numpy moves a custom dtype element by element
        self._pool = scaled.view(_RAW[scaled.itemsize])

    def shape(self, name: str) -> Tuple[int, ...]:
        c = self.cfg
        d, f = c["hidden_size"], c["intermediate_size"]
        inner = c["mamba_expand"] * d
        n, k, r = c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
        kv = c["num_key_value_heads"] * (d // c["num_attention_heads"])
        tail = name.split(".", 3)[-1] if name.startswith(
            "model.layers.") else name
        shapes = {
            "model.embed_tokens.weight": (c["vocab_size"], d),
            "model.final_layernorm.weight": (d,),
            "input_layernorm.weight": (d,),
            "pre_ff_layernorm.weight": (d,),
            "feed_forward.gate_proj.weight": (f, d),
            "feed_forward.up_proj.weight": (f, d),
            "feed_forward.down_proj.weight": (d, f),
            "self_attn.q_proj.weight": (d, d),
            "self_attn.k_proj.weight": (kv, d),
            "self_attn.v_proj.weight": (kv, d),
            "self_attn.o_proj.weight": (d, d),
            "mamba.in_proj.weight": (2 * inner, d),
            "mamba.conv1d.weight": (inner, 1, k),
            "mamba.conv1d.bias": (inner,),
            "mamba.x_proj.weight": (r + 2 * n, inner),
            "mamba.dt_proj.weight": (inner, r),
            "mamba.dt_proj.bias": (inner,),
            "mamba.A_log": (inner, n),
            "mamba.D": (inner,),
            "mamba.out_proj.weight": (d, inner),
            "mamba.dt_layernorm.weight": (r,),
            "mamba.b_layernorm.weight": (n,),
            "mamba.c_layernorm.weight": (n,),
        }
        if tail not in shapes:
            raise KeyError(name)
        return shapes[tail]

    def _uniform(self, name: str, shape, bound: float) -> np.ndarray:
        rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
        return rng.uniform(-bound, bound, shape).astype(np.float32)

    def tensor(self, name: str) -> np.ndarray:
        shape = self.shape(name)
        c = self.cfg
        if name.endswith("layernorm.weight") or name.endswith("mamba.D"):
            return np.ones(shape, self.dtype)
        if name.endswith("mamba.A_log"):
            return np.log(np.broadcast_to(np.arange(
                1, shape[1] + 1, dtype=np.float32), shape)).astype(self.dtype)
        if name.endswith("dt_proj.bias"):
            rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
            dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            return (dt0 + np.log(-np.expm1(-dt0))).astype(
                np.float32).astype(self.dtype)
        if name.endswith("dt_proj.weight"):
            return self._uniform(name, shape, c["mamba_dt_rank"] ** -0.5
                                 ).astype(self.dtype)
        if re.search(r"conv1d\.(weight|bias)$", name):
            return self._uniform(name, shape, c["mamba_d_conv"] ** -0.5
                                 ).astype(self.dtype)
        start = int(np.random.default_rng(
            [self.seed, zlib.crc32(name.encode())]).integers(0, POOL))
        n = int(np.prod(shape))
        out = np.empty(n, self._pool.dtype)
        done = 0
        while done < n:
            take = min(n - done, POOL - start)
            out[done:done + take] = self._pool[start:start + take]
            done, start = done + take, 0
        return out.view(self.dtype).reshape(shape)

    def raw(self, name: str) -> np.ndarray:
        """The tensor as plain integers of its width (for moving it)."""
        t = self.tensor(name)
        return t.view(_RAW[t.itemsize])


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str,
                     max_seq_len: int):
    """A ``Checkpoint`` directory at ``path``: the ``LMConfig`` the published
    keys map to and the seeded tensors, through the program's importer."""
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    config = lm_config(cfg, dtype, max_seq_len)
    pub = Published(cfg, seed, dtype)
    params = hf_import.convert_jamba_state_dict(pub.raw, config)
    params = jax.tree_util.tree_map(lambda a: a.view(pub.dtype), params)
    os.makedirs(path, exist_ok=True)
    return Checkpoint.from_model(model_config=config, params=params, path=path)
