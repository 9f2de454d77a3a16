"""Seeded weights of the published ``laguna`` configuration (Laguna-XS.2:
window layers beside full ones, query heads counted by layer kind, a gate a
head, 256 small experts all held), in the PUBLISHED layout under the names
the configuration file assumes (``assumed.tensor_names``; the catalog gives
none), and a checkpoint of them that the replica loads the normal way.

``Published(cfg, seed, dtype).tensor(name)`` is a function of its arguments
alone, so the driver (which writes the checkpoint through the program's
importer) and the replica's correctness check (which hands the same tensors
to the benchmark's own reference, one at a time) see the same values without
either holding a second copy of 7.7 GB.

Values, as ``benchmark/weights_mla.py`` makes them: a matrix is a window into
a pool of seeded normal values at a seeded offset, wrapped (the pool's length
is prime), at the assumed ``initializer_range`` (0.02), COLUMN-major where
the program keeps it transposed (every matrix but the embedding and the
router).  Norm weights are ones.  Rows of the router are widened by a
per-expert gain ``exp(0.25 z_e)`` over the 256 normal quantiles, and the
selection bias is those quantiles at 0.1 of the spread of the sigmoid
scores; both are dealt to the experts by the seed, anew for each layer (every
expert is held, so the deal is a plain shuffle).  The gate's matrix
``g_proj`` is drawn WIDER (``assumed.gate_init``): at 0.02 its logits have a
spread of 0.9 and every head's gate sits near a half, a constant the output
projection could absorb; at 0.06 the gates of a token run from 0.05 to 0.95
and a program that dropped the gate computes other logits.
"""

from __future__ import annotations

import os
import re
import zlib
from statistics import NormalDist
from typing import Any, Dict, Tuple

import numpy as np

from benchmark.weights_lm import POOL, ROUTER_SIGMA, _RAW, np_dtype
from benchmark.weights_mla import BIAS_SHARE, write_params

#: matrices the importer reads as they are stored (rows gathered): row-major
#: like every vector; all others are column-major
ROW_MAJOR = ("embed_tokens.weight", "mlp.gate.weight")


def names(cfg: Dict[str, Any]) -> Dict[str, str]:
    """The tensor names the configuration file assumes."""
    return dict(cfg["assumed"]["tensor_names"])


class Published:
    def __init__(self, cfg: Dict[str, Any], seed: int, dtype: str):
        self.cfg, self.seed, self.dtype = cfg, int(seed), np_dtype(dtype)
        assumed = cfg.get("assumed", {})
        self.std = float(assumed.get("initializer_range", 0.02))
        self.gate_std = float(assumed.get("gate_initializer_range", self.std))
        rng = np.random.default_rng([self.seed, 0xC0FFEE])
        unit = rng.standard_normal(POOL, dtype=np.float32)
        # as plain integers: numpy moves a custom dtype element by element
        scaled = (unit * self.std).astype(self.dtype)
        self._pool = scaled.view(_RAW[scaled.itemsize])
        self._unit = unit
        n = cfg["num_experts"]
        self._z = np.array([NormalDist().inv_cdf((i + 0.5) / n)
                            for i in range(n)])
        # the spread of s = sigmoid(router row . h) over experts and tokens,
        # h of unit RMS (it leaves an RMSNorm whose weight is ones)
        logit = (np.random.default_rng([self.seed, 0x51D]).standard_normal(
            (256, n)) * self.std * np.sqrt(cfg["hidden_size"])
            * np.exp(ROUTER_SIGMA * self._z))
        self.bias_std = BIAS_SHARE * float(np.std(1 / (1 + np.exp(-logit))))
        rx = lambda pattern: re.compile(  # noqa: E731
            re.escape(pattern).replace(r"\{i\}", r"(?P<i>\d+)").replace(
                r"\{e\}", r"(?P<e>\d+)").replace(r"\{m\}", r"(?P<m>\w+)"))
        self._names = {key: rx(p) for key, p in names(cfg).items()}

    def _which(self, name: str):
        """``(key, layer, matrix)`` of a tensor's name under the assumed
        names (``layer`` and ``matrix``: None where the name has none)."""
        for key, rx in self._names.items():
            m = rx.fullmatch(name)
            if m:
                got = m.groupdict()
                return (key, None if got.get("i") is None else int(got["i"]),
                        got.get("m"))
        raise KeyError(name)

    def dealt(self, layer: int, what: int) -> np.ndarray:
        """The router's normal quantiles dealt to its experts for ``layer``
        (``what``: 0 the gains' deal, 1 the selection bias's)."""
        return np.random.default_rng(
            [self.seed, 0x6A1, layer, what]).permutation(self._z)

    def shape(self, name: str) -> Tuple[int, ...]:
        c = self.cfg
        key, i, m = self._which(name)
        d, hd, g = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
        if key in ("embed", "head"):
            return (c["vocab_size"], d)
        if key in ("final_norm", "attn_norm", "mlp_norm"):
            return (d,)
        h = c["num_attention_heads_per_layer"][i]
        if key in ("dense", "expert", "shared"):
            f = {"dense": c["intermediate_size"],
                 "expert": c["moe_intermediate_size"],
                 "shared": c["shared_expert_intermediate_size"]}[key]
            return (d, f) if m == "down" else (f, d)
        return {"q": (h * hd, d), "k": (g * hd, d), "v": (g * hd, d),
                "o": (d, h * hd), "g": (h, d),
                "router": (c["num_experts"], d),
                "router_bias": (c["num_experts"],)}[key]

    def tensor(self, name: str) -> np.ndarray:
        shape = self.shape(name)
        key, layer, _ = self._which(name)
        rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
        if key == "router_bias":
            return (self.dealt(layer, 1) * self.bias_std
                    ).astype(np.float32).astype(self.dtype)
        if len(shape) == 1:
            return np.ones(shape, self.dtype)
        start = int(rng.integers(0, POOL))
        n = int(np.prod(shape))
        if key in ("router", "g"):
            idx = (start + np.arange(n)) % POOL
            if key == "g":      # column-major, like every projection
                return (self._unit[idx].reshape(shape[::-1])
                        * self.gate_std).astype(self.dtype).T
            gain = np.exp(ROUTER_SIGMA * self.dealt(layer, 0))
            return (self._unit[idx].reshape(shape) * self.std
                    * gain[:, None]).astype(self.dtype)
        out = np.empty(n, self._pool.dtype)
        done = 0
        while done < n:
            take = min(n - done, POOL - start)
            out[done:done + take] = self._pool[start:start + take]
            done, start = done + take, 0
        if name.endswith(ROW_MAJOR):
            return out.view(self.dtype).reshape(shape)
        return out.view(self.dtype).reshape(shape[::-1]).T

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
    params = hf_import.convert_laguna_state_dict(pub.raw, config,
                                                 names=names(cfg))
    params = jax.tree_util.tree_map(lambda a: a.view(pub.dtype), params)
    os.makedirs(path, exist_ok=True)
    ckpt = Checkpoint.from_model(model_config=config, path=path)
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        write_params(params, f)
    return ckpt
