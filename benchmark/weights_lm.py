"""Seeded weights of a published ``olmoe`` configuration, in the PUBLISHED
layout and names (``model.layers.3.mlp.experts.17.up_proj.weight`` ...), and
a checkpoint of them the replica loads the normal way.

``Published(cfg, seed, dtype).tensor(name)`` is a function of its arguments
alone, so the driver (which writes the checkpoint through the program's
importer, ``tpu_air.models.lm.hf_import``) and the replica's correctness
check (which hands the same tensors to the benchmark's own reference, one
at a time) see the same values without either holding a second copy of 7 GB.

Values, as in ``benchmark/weights.py``: a matrix is a window into a pool of
seeded normal values at a seeded offset, wrapped (the pool's length is
prime).  Every matrix has the published ``initializer_range`` (0.02; the
query projection carries no extra scale: the 1/sqrt(head_dim) is the
program's).  Norm weights are ones.  Rows of the router are widened by a
seeded per-expert gain (the configuration file's ``router_init``).
"""

from __future__ import annotations

import os
import re
import zlib
from statistics import NormalDist
from typing import Any, Dict, Tuple

import numpy as np

POOL = 8_388_593  # prime
ROUTER_SIGMA = 0.25
_RAW = {2: np.uint16, 4: np.uint32}


def np_dtype(dtype: str):
    import ml_dtypes

    return {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}[dtype]


class Published:
    def __init__(self, cfg: Dict[str, Any], seed: int, dtype: str):
        self.cfg, self.seed, self.dtype = cfg, int(seed), np_dtype(dtype)
        self.std = float(cfg.get("assumed", {}).get("initializer_range", 0.02))
        rng = np.random.default_rng([self.seed, 0xC0FFEE])
        unit = rng.standard_normal(POOL, dtype=np.float32)
        # as plain integers: numpy moves a custom dtype element by element
        scaled = (unit * self.std).astype(self.dtype)
        self._pool = scaled.view(_RAW[scaled.itemsize])
        n = cfg["num_experts"]
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        self.router_gain = np.exp(ROUTER_SIGMA * z)
        np.random.default_rng([self.seed, 0x6A1]).shuffle(self.router_gain)
        self._unit = unit

    def shape(self, name: str) -> Tuple[int, ...]:
        c = self.cfg
        d, f = c["hidden_size"], c["intermediate_size"]
        if name in ("model.embed_tokens.weight", "lm_head.weight"):
            return (c["vocab_size"], d)
        if name.endswith("norm.weight") or name.endswith("layernorm.weight"):
            return (d,)
        if name.endswith("mlp.gate.weight"):
            return (c["num_experts"], d)
        if re.search(r"self_attn\.[qkvo]_proj\.weight$", name):
            return (d, d)
        if name.endswith("down_proj.weight"):
            return (d, f)
        if name.endswith(("gate_proj.weight", "up_proj.weight")):
            return (f, d)
        raise KeyError(name)

    def tensor(self, name: str) -> np.ndarray:
        shape = self.shape(name)
        if len(shape) == 1:
            return np.ones(shape, self.dtype)
        start = int(np.random.default_rng(
            [self.seed, zlib.crc32(name.encode())]).integers(0, POOL))
        n = int(np.prod(shape))
        if name.endswith("mlp.gate.weight"):
            idx = (start + np.arange(n)) % POOL
            rows = self._unit[idx].reshape(shape) * self.std
            return (rows * self.router_gain[:, None]).astype(self.dtype)
        out = np.empty(n, self._pool.dtype)
        done = 0
        while done < n:
            take = min(n - done, POOL - start)
            out[done:done + take] = self._pool[start:start + take]
            done, start = done + take, 0
        return out.view(self.dtype).reshape(shape)

    def tensor_f32(self, name: str) -> np.ndarray:
        """The tensor's values as float32 (bf16 widened by a shift: numpy
        converts a custom dtype element by element)."""
        t = self.tensor(name)
        if t.dtype == np.float32:
            return t
        return (t.view(np.uint16).astype(np.uint32) << 16).view(np.float32)

    def raw(self, name: str) -> np.ndarray:
        """The tensor as plain integers of its width (for moving it)."""
        t = self.tensor(name)
        return t.view(_RAW[t.itemsize])


def lm_config(cfg: Dict[str, Any], dtype: str, max_seq_len: int):
    from tpu_air.models.lm import hf_import

    return hf_import.lm_config_from_hf(
        cfg, dtype=dtype, max_seq_len=max_seq_len,
        eos_token_id=cfg.get("assumed", {}).get("eos_token_id"),
        pad_token_id=cfg.get("assumed", {}).get("pad_token_id", 0))


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str,
                     max_seq_len: int):
    """A ``Checkpoint`` directory at ``path``: the ``LMConfig`` the published
    keys map to and the seeded tensors, through the program's importer."""
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    config = lm_config(cfg, dtype, max_seq_len)
    pub = Published(cfg, seed, dtype)
    params = hf_import.convert_olmoe_state_dict(pub.raw, config)
    params = jax.tree_util.tree_map(lambda a: a.view(pub.dtype), params)
    os.makedirs(path, exist_ok=True)
    return Checkpoint.from_model(model_config=config, params=params, path=path)
