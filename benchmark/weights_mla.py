"""Seeded weights of a published ``deepseek_v3`` configuration
(GigaChat3.1-702B-A36B), in the PUBLISHED layout and names
(``model.layers.2.self_attn.kv_a_proj_with_mqa.weight``,
``model.layers.2.mlp.experts.7.up_proj.weight`` ...), and a checkpoint of
ONE expert-parallel rank's share of them that the replica loads the normal
way.

``Published(cfg, seed, dtype).tensor(name)`` is a function of its arguments
alone, so the driver (which writes the checkpoint through the program's
importer, ``tpu_air.models.lm.hf_import``) and the replica's correctness
check (which hands the same tensors to the benchmark's own reference, one at
a time) see the same values without either holding a second copy of 8.6 GB.
``cfg`` is the configuration FILE's dict: ``n_routed_experts`` there is what
the chip holds, and its ``deployment`` group says what the router scores
(``router_width``) and which rank this is; :func:`published_view` is the dict
as the importer and the reference read it (``n_routed_experts``: the router's
width) and :func:`held` the rank's range of expert ids.  Tensors of experts
the rank does not hold are never asked for; the embedding and the head are
the rank's slice of the vocabulary (``vocab_size`` rows).

Values, as ``benchmark/weights_lm.py`` makes them: a matrix is a window into
a pool of seeded normal values at a seeded offset, wrapped (the pool's length
is prime), at the assumed ``initializer_range`` (0.02).  Norm weights are
ones.  Rows of the router are widened by a per-expert gain ``exp(0.25 z_e)``
over the normal quantiles of the router's width, and the selection bias
``e_score_correction_bias`` is those quantiles at 0.1 of the spread of the
sigmoid scores; both are dealt to the experts by the seed, anew for each
layer, so that every rank's experts take one value from each stratum
(:meth:`Published.dealt`; the configuration file's ``assumed`` says why).

Setting the share up is most of a run's time that is not its window, so the
8.6 GB are moved as few times as the path allows.  A matrix the program keeps
transposed (``[in, out]``: every projection but the ones in
:data:`ROW_MAJOR`) is made COLUMN-major, so its transpose in the importer is
the buffer as it lies; and :func:`write_params` streams the tree into the
checkpoint's ``params.msgpack`` leaf by leaf, in the bytes
``Checkpoint.from_model`` would write, without a packed copy of the whole.
"""

from __future__ import annotations

import os
import re
import struct
import zlib
from statistics import NormalDist
from typing import Any, Dict, Tuple

import numpy as np

from benchmark.weights_lm import POOL, ROUTER_SIGMA, _RAW, np_dtype

BIAS_SHARE = 0.1
#: matrices the importer reads as they are stored (rows gathered, or cut by
#: head): row-major like every vector; all others are column-major
ROW_MAJOR = ("model.embed_tokens.weight", "self_attn.kv_b_proj.weight",
             "mlp.gate.weight")


def published_view(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The file's dict with ``n_routed_experts`` as the published model has
    it: the experts the router scores."""
    dep = cfg.get("deployment", {})
    return {**cfg, "n_routed_experts": dep.get("router_width",
                                               cfg["n_routed_experts"])}


def held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """``(first id, count)`` of the routed experts this rank holds."""
    count = cfg["n_routed_experts"]
    return cfg.get("deployment", {}).get("expert_rank", 0) * count, count


class Published:
    def __init__(self, cfg: Dict[str, Any], seed: int, dtype: str):
        self.cfg, self.seed, self.dtype = cfg, int(seed), np_dtype(dtype)
        self.std = float(cfg.get("assumed", {}).get("initializer_range", 0.02))
        rng = np.random.default_rng([self.seed, 0xC0FFEE])
        unit = rng.standard_normal(POOL, dtype=np.float32)
        # as plain integers: numpy moves a custom dtype element by element
        scaled = (unit * self.std).astype(self.dtype)
        self._pool = scaled.view(_RAW[scaled.itemsize])
        self._unit = unit
        n = published_view(cfg)["n_routed_experts"]
        self._z = np.array([NormalDist().inv_cdf((i + 0.5) / n)
                            for i in range(n)])
        # the spread of s = sigmoid(router row . h) over experts and tokens,
        # h of unit RMS (it leaves an RMSNorm whose weight is ones)
        logit = (np.random.default_rng([self.seed, 0x51D]).standard_normal(
            (256, n)) * self.std * np.sqrt(cfg["hidden_size"])
            * np.exp(ROUTER_SIGMA * self._z))
        self.bias_std = BIAS_SHARE * float(np.std(1 / (1 + np.exp(-logit))))

    def dealt(self, layer: int, what: int) -> np.ndarray:
        """The router's normal quantiles dealt to its experts for ``layer``
        (``what``: 0 the gains' deal, 1 the selection bias's): every RANK'S
        run of consecutive experts takes one quantile from each equal
        stratum of them, in a seeded order.  A deployment places experts so
        that its ranks hold a like mix of popular and unpopular ones, and a
        plain shuffle makes what ONE rank holds (how many of its experts a
        step touches: the bytes it streams) a matter of the seed."""
        n = len(self._z)
        rank = held(self.cfg)[1]
        rng = np.random.default_rng([self.seed, 0x6A1, layer, what])
        if n % rank or rank * rank != n:
            return rng.permutation(self._z)
        out = np.empty(n)
        for j, stratum in enumerate(self._z.reshape(rank, rank)):
            out[j::rank] = rng.permutation(stratum)
        for block in out.reshape(n // rank, rank):
            rng.shuffle(block)
        return out

    def shape(self, name: str) -> Tuple[int, ...]:
        c = self.cfg
        d, f, fm = (c["hidden_size"], c["intermediate_size"],
                    c["moe_intermediate_size"])
        h, rq, r = (c["num_attention_heads"], c["q_lora_rank"],
                    c["kv_lora_rank"])
        dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
        e = published_view(c)["n_routed_experts"]
        if name in ("model.embed_tokens.weight", "lm_head.weight"):
            return (c["vocab_size"], d)
        if name == "model.norm.weight":
            return (d,)
        tail = name.split(".", 3)[-1]
        expert = re.match(r"mlp\.(experts\.(\d+)|shared_experts)\.(\w+)_proj"
                          r"\.weight$", tail)
        if expert:
            if expert.group(2) is not None and not (
                    held(c)[0] <= int(expert.group(2)) < sum(held(c))):
                raise KeyError(f"{name}: this rank holds experts "
                               f"{held(c)[0]}..+{held(c)[1]}")
            width = fm * (1 if expert.group(2) is not None
                          else c["n_shared_experts"])
            return (d, width) if expert.group(3) == "down" else (width, d)
        shapes = {
            "input_layernorm.weight": (d,),
            "post_attention_layernorm.weight": (d,),
            "self_attn.q_a_proj.weight": (rq, d),
            "self_attn.q_a_layernorm.weight": (rq,),
            "self_attn.q_b_proj.weight": (h * (dn + dr), rq),
            "self_attn.kv_a_proj_with_mqa.weight": (r + dr, d),
            "self_attn.kv_a_layernorm.weight": (r,),
            "self_attn.kv_b_proj.weight": (h * (dn + dv), r),
            "self_attn.o_proj.weight": (d, h * dv),
            "mlp.gate_proj.weight": (f, d),
            "mlp.up_proj.weight": (f, d),
            "mlp.down_proj.weight": (d, f),
            "mlp.gate.weight": (e, d),
            "mlp.gate.e_score_correction_bias": (e,),
        }
        if tail not in shapes:
            raise KeyError(name)
        return shapes[tail]

    def tensor(self, name: str) -> np.ndarray:
        shape = self.shape(name)
        rng = np.random.default_rng([self.seed, zlib.crc32(name.encode())])
        layer = re.match(r"model\.layers\.(\d+)\.", name)
        if name.endswith("e_score_correction_bias"):
            return (self.dealt(int(layer.group(1)), 1) * self.bias_std
                    ).astype(np.float32).astype(self.dtype)
        if len(shape) == 1:
            return np.ones(shape, self.dtype)
        start = int(rng.integers(0, POOL))
        n = int(np.prod(shape))
        if name.endswith("mlp.gate.weight"):
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

    def raw(self, name: str) -> np.ndarray:
        """The tensor as plain integers of its width (for moving it)."""
        t = self.tensor(name)
        return t.view(_RAW[t.itemsize])


def lm_config(cfg: Dict[str, Any], dtype: str, max_seq_len: int):
    from tpu_air.models.lm import hf_import

    first, count = held(cfg)
    return hf_import.lm_config_from_hf(
        published_view(cfg), dtype=dtype, max_seq_len=max_seq_len,
        experts_first=first, experts_held=count,
        eos_token_id=cfg.get("assumed", {}).get("eos_token_id"),
        pad_token_id=cfg.get("assumed", {}).get("pad_token_id", 0))


def write_params(tree: Dict[str, Any], f) -> None:
    """``flax.serialization.msgpack_serialize(tree)`` written to ``f`` leaf by
    leaf (a nested dict of numpy arrays; an array is msgpack's extension 1
    around ``(shape, dtype name, bytes)``), each array straight from its own
    buffer.  ``tests/test_benchmark_mla.py`` holds the bytes to flax's."""
    import msgpack
    from flax import serialization

    pack = msgpack.Packer(use_bin_type=True, strict_types=True)
    if isinstance(tree, dict):
        f.write(pack.pack_map_header(len(tree)))
        for key, value in sorted(tree.items()):
            f.write(pack.pack(key))
            write_params(value, f)
        return
    arr = np.ascontiguousarray(tree)
    if arr.nbytes > serialization.MAX_CHUNK_SIZE:
        raise ValueError(f"a leaf of {arr.nbytes} bytes: flax would write "
                         "it in chunks, and this writer does not")
    if arr.nbytes < 1 << 16:
        # msgpack picks shorter headers for short payloads: leave it to flax
        f.write(serialization.msgpack_serialize(arr))
        return
    head = (msgpack.packb((arr.shape, arr.dtype.name), use_bin_type=True)
            + b"\xc6" + struct.pack(">I", arr.nbytes))
    head = b"\x93" + head[1:]      # an array of three: the bytes follow
    # ext 32 (0xc9), its length, its type: flax's _MsgpackExtType.ndarray
    f.write(b"\xc9" + struct.pack(">Ib", len(head) + arr.nbytes, 1) + head)
    f.write(arr.view(_RAW.get(arr.itemsize, np.uint8)).data)


def write_checkpoint(cfg: Dict[str, Any], seed: int, dtype: str, path: str,
                     max_seq_len: int):
    """A ``Checkpoint`` directory at ``path``: the ``LMConfig`` the published
    keys map to and the rank's share of the seeded tensors, through the
    program's importer."""
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    config = lm_config(cfg, dtype, max_seq_len)
    pub = Published(cfg, seed, dtype)
    params = hf_import.convert_deepseek_v3_state_dict(pub.raw, config)
    params = jax.tree_util.tree_map(lambda a: a.view(pub.dtype), params)
    os.makedirs(path, exist_ok=True)
    ckpt = Checkpoint.from_model(model_config=config, path=path)
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        write_params(params, f)
    return ckpt
