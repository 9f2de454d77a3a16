"""Inference weights from ``--seed``, written by the driver with numpy as a
checkpoint directory the workers load the normal way.  The driver never
starts a JAX backend for this: the parameter tree's shapes come from
``jax.eval_shape`` (abstract), the values from numpy.

Values: every matrix leaf is a window into a pool of seeded normal values
scaled to the leaf's width (``leaf_std``), taken at a seeded offset and
wrapped; the pool's length is prime, so no two rows or leaves line up.  Norm
weights are ones.
Drawing 783 M independent normals takes the host tens of seconds and serves
no request; a checkpoint's speed does not depend on its values.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

POOL = 8_388_593  # prime


def t5_config(cfg: Dict[str, Any], dtype: str):
    from tpu_air.models.t5 import T5Config

    config = T5Config.from_dict(cfg)
    config.dtype = dtype
    return config


def param_shapes(config):
    import jax
    import jax.numpy as jnp

    from tpu_air.models.t5 import T5ForConditionalGeneration

    model = T5ForConditionalGeneration(config)
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    return jax.eval_shape(
        lambda k, a, b, c: model.init(k, a, b, c),
        jax.ShapeDtypeStruct((2,), jnp.uint32), ids, ids,
        jax.ShapeDtypeStruct((1, 4), jnp.int32))["params"]


def leaf_std(names, cfg) -> float:
    """Standard deviation of a matrix leaf, by the published T5 convention
    (Mesh TensorFlow): T5 does not scale attention scores, so the query
    projection carries the 1/sqrt(d_kv).  (The program's own ``model.init``
    gives q the same width as k and v; its scores then have a deviation of
    about 8, attention is close to one-hot, and bf16 rounding decides which
    key wins — no two programs agree on a logit.  With this convention the
    window decode and a full forward pass can be compared.)"""
    d, ff = cfg.d_model, cfg.d_ff
    if names[-1] == "embedding":
        return 1.0 if names[0] == "shared" else d ** -0.5
    which = names[-2]
    if which == "q":
        return (d * cfg.d_kv) ** -0.5
    if which == "wo":
        return ff ** -0.5
    if which == "o":
        return (cfg.num_heads * cfg.d_kv) ** -0.5
    return d ** -0.5  # k, v, wi_0, wi_1, lm_head


def seeded_params(shapes, seed: int, dtype: str, config):
    import jax
    import ml_dtypes

    np_dtype = {"bfloat16": ml_dtypes.bfloat16,
                "float32": np.float32}[dtype]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    unit = rng.standard_normal(POOL, dtype=np.float32)
    pools: Dict[float, np.ndarray] = {}

    def pool(std: float) -> np.ndarray:
        # one pool for each width; copied as plain integers, because numpy
        # moves a custom dtype element by element
        if std not in pools:
            scaled = (unit * std).astype(np_dtype)
            pools[std] = scaled.view(
                {2: np.uint16, 4: np.uint32}[scaled.itemsize])
        return pools[std]

    def leaf(path, s):
        names = [str(p.key) for p in path]
        n = int(np.prod(s.shape))
        if names[-1] == "weight":  # RMSNorm scale
            return np.ones(s.shape, np_dtype)
        raw = pool(leaf_std(names, config))
        start = int(rng.integers(0, POOL))
        out = np.empty(n, raw.dtype)
        done = 0
        while done < n:
            take = min(n - done, POOL - start)
            out[done:done + take] = raw[start:start + take]
            done, start = done + take, 0
        return out.view(np_dtype).reshape(s.shape)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str):
    """A ``Checkpoint`` directory at ``path`` holding the configuration and
    seeded parameters in ``dtype``; returns the Checkpoint."""
    from tpu_air.train.checkpoint import Checkpoint

    config = t5_config(cfg, dtype)
    params = seeded_params(param_shapes(config), seed, dtype, config)
    os.makedirs(path, exist_ok=True)
    return Checkpoint.from_model(model_config=config, params=params, path=path)
