"""Seeded weights of a published ``nemotron_h`` configuration
(NVIDIA-Nemotron-3-Super-120B-A12B), in the PUBLISHED layout and names
(``backbone.layers.0.mixer.in_proj.weight``,
``backbone.layers.1.mixer.experts.7.up_proj.weight`` ...), and a checkpoint of
ONE expert-parallel rank's share of them that the replica loads the normal
way.

``Published(cfg, seed, dtype).tensor(name)`` is a function of its arguments
alone, so the driver (which writes the checkpoint through the program's
importer, ``tpu_air.models.lm.hf_import``) and the replica's correctness
check (which hands the same tensors to the benchmark's own reference, one at
a time) see the same values without either holding a second copy of 9.3 GB.
``cfg`` is the configuration FILE's dict, read as ``weights_mla`` reads its
own: ``n_routed_experts`` there is what the chip holds, ``deployment`` says
what the router scores (``router_width``) and which rank this is
(:func:`weights_mla.published_view`, :func:`weights_mla.held`).

Values, as ``benchmark/weights_mla.py`` makes them: a matrix is a window into
a pool of seeded normal values at the assumed ``initializer_range`` (0.02),
column-major where the importer transposes it; norm weights are ones; the
router's rows carry a per-expert gain and the selection bias the normal
quantiles at 0.1 of the spread of the sigmoid scores, both DEALT over the
ranks (:meth:`Published.dealt`).  The Mamba-2 scalars get Mamba-2's own
initialisation (the configuration file's ``assumed.mamba_init`` says why):
``A_log = log`` of uniform [1, 16] a head, ``D = 1``, ``dt_bias =
softplus^-1`` of log-uniform [``time_step_min``, ``time_step_max``] floored
at ``time_step_floor``, the convolution's weight and bias uniform in
``+-conv_kernel^-0.5``.
"""

from __future__ import annotations

import os
import re
import zlib
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import weights_mla
from benchmark.weights_lm import POOL, ROUTER_SIGMA
from benchmark.weights_mla import (held, lm_config, published_view,
                                   write_params)

__all__ = ["Published", "held", "lm_config", "published_view",
           "write_checkpoint"]

#: matrices the importer reads as they are stored (rows gathered, or made
#: row by row): row-major like every vector; all others are column-major
ROW_MAJOR = ("backbone.embeddings.weight", "mixer.gate.weight")


class Published(weights_mla.Published):
    """``weights_mla.Published`` (the pool, the router's quantiles and the
    spread of its scores) with this family's shapes, scalars and deal."""

    def dealt(self, layer: int, what: int) -> np.ndarray:
        """The router's normal quantiles dealt to its experts for ``layer``
        (``what``: 0 the gains' deal, 1 the selection bias's): every RANK'S
        run of consecutive experts takes one quantile from each stratum of
        as many neighbouring quantiles as there are ranks, in a seeded
        order, so that what ONE rank holds (how many of its experts a step
        touches: the bytes it streams) is not a matter of the seed."""
        n = len(self._z)
        rank = held(self.cfg)[1]
        rng = np.random.default_rng([self.seed, 0x6A1, layer, what])
        if n % rank:
            return rng.permutation(self._z)
        ranks = n // rank
        out = np.empty((ranks, rank))
        for j, stratum in enumerate(self._z.reshape(rank, ranks)):
            out[:, j] = rng.permutation(stratum)
        for block in out:
            rng.shuffle(block)
        return out.reshape(-1)

    def shape(self, name: str) -> Tuple[int, ...]:
        c = self.cfg
        d, f, lat = (c["hidden_size"], c["moe_intermediate_size"],
                     c["moe_latent_size"])
        H, P = c["mamba_num_heads"], c["mamba_head_dim"]
        G, N, K = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
        inner, conv = H * P, H * P + 2 * G * N
        hd = c["head_dim"]
        q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
        e = published_view(c)["n_routed_experts"]
        fs = c["moe_shared_expert_intermediate_size"]
        if name in ("backbone.embeddings.weight", "lm_head.weight"):
            return (c["vocab_size"], d)
        if name == "backbone.norm_f.weight":
            return (d,)
        tail = name.split(".", 3)[-1]
        expert = re.match(r"mixer\.experts\.(\d+)\.(up|down)_proj\.weight$", tail)
        if expert:
            if not held(c)[0] <= int(expert.group(1)) < sum(held(c)):
                raise KeyError(f"{name}: this rank holds experts "
                               f"{held(c)[0]}..+{held(c)[1]}")
            return (lat, f) if expert.group(2) == "down" else (f, lat)
        shapes = {
            "norm.weight": (d,),
            "mixer.in_proj.weight": (inner + conv + H, d),
            "mixer.conv1d.weight": (conv, 1, K),
            "mixer.conv1d.bias": (conv,),
            "mixer.dt_bias": (H,), "mixer.A_log": (H,), "mixer.D": (H,),
            "mixer.norm.weight": (inner,),
            "mixer.out_proj.weight": (d, inner),
            "mixer.q_proj.weight": (q, d), "mixer.k_proj.weight": (kv, d),
            "mixer.v_proj.weight": (kv, d), "mixer.o_proj.weight": (d, q),
            "mixer.gate.weight": (e, d),
            "mixer.gate.e_score_correction_bias": (e,),
            "mixer.fc1_latent_proj.weight": (lat, d),
            "mixer.fc2_latent_proj.weight": (d, lat),
            "mixer.shared_experts.up_proj.weight": (fs, d),
            "mixer.shared_experts.down_proj.weight": (d, fs),
        }
        if tail not in shapes:
            raise KeyError(name)
        return shapes[tail]

    def tensor(self, name: str) -> np.ndarray:
        shape = self.shape(name)
        c = self.cfg
        rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
        layer = re.match(r"backbone\.layers\.(\d+)\.", name)
        f32 = lambda a: np.asarray(a, np.float32).astype(  # noqa: E731
            self.dtype)
        if name.endswith("e_score_correction_bias"):
            return f32(self.dealt(int(layer.group(1)), 1) * self.bias_std)
        if name.endswith("mixer.A_log"):
            return f32(np.log(rng.uniform(1.0, 16.0, shape)))
        if name.endswith("mixer.dt_bias"):
            dt0 = np.maximum(np.exp(rng.uniform(
                np.log(c["time_step_min"]), np.log(c["time_step_max"]),
                shape)), c["time_step_floor"])
            return f32(dt0 + np.log(-np.expm1(-dt0)))
        if re.search(r"conv1d\.(weight|bias)$", name):
            bound = c["conv_kernel"] ** -0.5
            return f32(rng.uniform(-bound, bound, shape))
        if len(shape) == 1:
            return np.ones(shape, self.dtype)    # norms, and D
        start = int(rng.integers(0, POOL))
        n = int(np.prod(shape))
        if name.endswith("mixer.gate.weight"):
            idx = (start + np.arange(n)) % POOL
            rows = self._unit[idx].reshape(shape) * self.std
            gain = np.exp(ROUTER_SIGMA * self.dealt(int(layer.group(1)), 0))
            return (rows * gain[:, None]).astype(self.dtype)
        out = np.empty(n, self._pool.dtype)
        done = 0
        while done < n:
            take = min(n - done, POOL - start)
            out[done:done + take] = self._pool[start:start + take]
            done, start = done + take, 0
        if name.endswith(ROW_MAJOR):
            return out.view(self.dtype).reshape(shape)
        return out.view(self.dtype).reshape(shape[::-1]).T


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str,
                     max_seq_len: int):
    """A ``Checkpoint`` directory at ``path``: the ``LMConfig`` the published
    keys map to and the rank's share of the seeded tensors, through the
    program's importer, streamed to ``params.msgpack`` leaf by leaf
    (``weights_mla.write_params``)."""
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    config = lm_config(cfg, dtype, max_seq_len)
    pub = Published(cfg, seed, dtype)
    params = hf_import.convert_nemotron_h_state_dict(pub.raw, config)
    params = jax.tree_util.tree_map(lambda a: a.view(pub.dtype), params)
    os.makedirs(path, exist_ok=True)
    ckpt = Checkpoint.from_model(model_config=config, path=path)
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        write_params(params, f)
    return ckpt
