"""The benchmark's own copy of ``tpu_air/models/lm/reference_xing.py``
(PR 58), kept here so that a later change to the program cannot move the
yardstick; it calls the benchmark's own copy of the ``deepseek_v3`` parts
(``benchmark/reference/deepseek.py``), not the program's.

A plain reference of the published Xing4.0 forward pass (``model_type:
xing4_0``; Xing4.0-29B-A4B): the ``deepseek_v3`` layer (latent attention, a
sigmoid router over its experts, a shared expert, leading dense layers:
``reference_deepseek.py``, whose parts this file calls) inside a residual
path of ``n = hc_mult`` streams, manifold-constrained hyper-connections (Xie
et al., "mHC", arXiv:2512.24880).  Straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: expanded attention, a
Python loop over layers, heads, experts and Sinkhorn rounds, no cache, no
batching, no kernel; the published tensor names and layouts.

A token's state is ``X [n, C]``.  On entry every stream is the token's
embedding row; each SUBLAYER ``f`` (``Attn(RMSNorm(.))`` and
``FF_i(RMSNorm(.))`` of ``reference_deepseek.py``) has ``phi [n*n + 2n,
n*C]`` (stored as a linear layer's weight), ``b [n*n + 2n]`` and ``alpha =
(a_pre, a_post, a_res)`` and computes::

    v = vec(X)                                     n*C numbers
    m = (mean(v^2) + rms_norm_eps)^(-1/2) (phi v)  RMSNorm(v) without a learned scale
    H~pre = a_pre m[0:n] + b[0:n];  H~post = a_post m[n:2n] + b[n:2n]
    H~res = a_res mat(m[2n:]) + mat(b[2n:])        n x n, row-major
    H_pre = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
    M = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    h = sum_i H_pre[i] X[i];   y = f(h);   X'[i] = sum_j M[i, j] X[j] + H_post[i] y

and on exit ``logits = W_head RMSNorm(sum_i X[i])``.

What the published configuration does not say, and is assumed (the
configuration file's ``assumed`` gives each with its source): columns before
rows in a Sinkhorn round (the paper's ``T_r(T_c(.))``), ``hc_eps`` in both
denominators, the clamp before ``exp``, the RMS without a learned scale,
entry by replication and exit by the sum (Zhu et al., "Hyper-Connections",
arXiv:2409.19606), and the NAMES of the mHC tensors (:data:`MHC_NAMES`; the
catalog row has no tensor names).  The departures of ``reference_deepseek``
hold here too ((1)-(3), (5)-(7) there: every expert applied to every
position, excluded groups at ``-inf``, ties to the lower index, ``mscale =
mscale_all_dim``, the multi-token module not run, one sequence at a time).

``weights`` is ``get(published tensor name) -> array``; ``cfg`` the published
``config.json`` as a dict.  A job of :func:`forward_each` may ask for what a
system at fault would compute, so that a caller can see its tolerance tell
them apart: ``round_inputs`` / ``rounded_precision`` / ``yarn_softmax_scale``
as in ``reference_deepseek`` (the product onto the ``n*n + 2n`` numbers is
float32 in the published model whatever the rest computes in, like the
router: it is never rounded), ``sinkhorn_iters`` (another number of rounds
than the configuration's) and ``identity_res`` (``H_res = I``: streams that
never mix).
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek

Array = jax.Array

#: the published names of a sublayer's mHC tensors, ASSUMED (``{layer}`` the
#: layer's number, ``{sublayer}`` ``attn`` or ``mlp``)
MHC_NAMES = {
    "phi": "model.layers.{layer}.{sublayer}_hc.phi.weight",
    "b": "model.layers.{layer}.{sublayer}_hc.bias",
    "alpha": "model.layers.{layer}.{sublayer}_hc.alpha",
}


def sinkhorn(res: Array, iters: int, eps: float, lo: float,
             hi: float) -> Array:
    """``H~res [T, n, n]`` -> the doubly stochastic ``H_res``."""
    m = jnp.exp(jnp.clip(res, lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)    # columns
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)    # rows
    return m


def maps(cfg: Dict[str, Any], x: Array, phi: Array, b: Array, alpha: Array,
         iters: Optional[int] = None, identity_res: bool = False):
    """``x [T, n, C]`` -> ``(H_pre [T, n], H_post [T, n], H_res [T, n,
    n])``."""
    t, n, c = x.shape
    v = x.reshape(t, n * c)
    m = (jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                       + cfg["rms_norm_eps"])
         * jnp.matmul(v, phi.astype(jnp.float32).T))
    b = b.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    res = (alpha[2] * m[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    if identity_res:
        return h_pre, h_post, jnp.broadcast_to(jnp.eye(n), (t, n, n))
    return h_pre, h_post, sinkhorn(
        res, cfg["hc_sinkhorn_iters"] if iters is None else iters,
        cfg["hc_eps"], cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            names: Dict[str, str] = MHC_NAMES, layer_outputs: bool = False,
            **how: Any) -> Dict[str, np.ndarray]:
    """Logits of one sequence: ``{"logits": [len(rows), V], "router_gap":
    [T]}`` as ``reference_deepseek.forward`` gives them and, with
    ``layer_outputs``, ``"streams"``: the ``[T, n, C]`` state behind each
    layer.  ``how``: a job's other keys (:func:`forward_each`)."""
    return forward_each(weights, cfg, [{"ids": ids, "rows": rows, **how}],
                        names=names, layer_outputs=layer_outputs)[0]


def forward_each(weights: Callable[[str], Any], cfg: Dict[str, Any],
                 jobs: Sequence[Dict[str, Any]],
                 names: Dict[str, str] = MHC_NAMES,
                 sinkhorn_iters: Optional[int] = None,
                 identity_res: bool = False,
                 layer_outputs: bool = False) -> List[Dict[str, np.ndarray]]:
    """:func:`forward` of several sequences, each computed alone; what they
    share is the fetch of each published tensor
    (``reference_deepseek.forward_each``).  A job holds ``ids`` and, where
    wanted, ``rows``, ``round_inputs`` (with ``rounded_precision``),
    ``yarn_softmax_scale``, ``sinkhorn_iters`` and ``identity_res``; the last
    two default to this call's."""
    w = lambda name: jnp.asarray(weights(name))  # noqa: E731
    part = deepseek._parts(json.dumps(_deepseek_keys(cfg), sort_keys=True),
                           None)
    hc = _mhc_parts(json.dumps({k: cfg[k] for k in (
        "rms_norm_eps", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max")}, sort_keys=True))
    n = cfg["hc_mult"]
    embedding = np.asarray(weights("model.embed_tokens.weight"))

    class Seq:
        def __init__(self, ids, rows=None, round_inputs=None,
                     yarn_softmax_scale=True, rounded_precision=None,
                     sinkhorn_iters=sinkhorn_iters,
                     identity_res=identity_res):
            self.r = round_inputs and (round_inputs, rounded_precision)
            self.sigma = deepseek.softmax_scale(cfg, yarn_softmax_scale)
            self.iters, self.identity = sinkhorn_iters, bool(identity_res)
            ids = np.asarray(ids, np.int64)
            self.rows = np.asarray(
                np.arange(len(ids)) if rows is None else rows, np.int64)
            # on entry every stream is the token's embedding row
            self.x = hc.expand(
                jnp.asarray(embedding[ids]).astype(jnp.float32), n)
            self.gap = jnp.full((len(ids),), jnp.inf, jnp.float32)
            self.streams = []

    seqs = [Seq(**job) for job in jobs]

    def sublayer(i, which, f):
        """``X <- H_res X + H_post f(H_pre X)`` for every sequence; ``f``
        takes the sequences' inputs together (an expert is fetched once for
        all of them), pops each as it takes it up (113 MB a sequence at the
        published width: nothing else holds it) and gives their outputs."""
        phi, b, alpha = (w(names[k].format(layer=i, sublayer=which))
                         for k in ("phi", "b", "alpha"))
        hs, back = [], []
        for s in seqs:
            h, h_post, h_res = hc.read(s.iters, s.identity, s.x, phi, b,
                                       alpha)
            hs.append(h)
            back.append((h_res, h_post))
            del h
        for s, y, maps_ in zip(seqs, f(hs), back):
            s.x = hc.write(s.x, y, *maps_)

    each = lambda f: lambda hs: [  # noqa: E731
        f(s, hs.pop(0)) for s in seqs]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            norm = w(pre + "input_layernorm.weight")
            latent = [w(f"{pre}self_attn.{name}.weight") for name in (
                "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
                "kv_a_layernorm", "kv_b_proj")]
            o = w(pre + "self_attn.o_proj.weight")

            def attention(s, h):
                q, q_r, k_r, kv = part.latent(s.r, h, norm, *latent)
                att = [part.head(s.r, s.sigma, head, q, q_r, k_r, kv)
                       for head in range(cfg["num_attention_heads"])]
                return part.project(s.r, jnp.concatenate(att, -1), o)

            sublayer(i, "attn", each(attention))
            del latent, o
            norm2 = w(pre + "post_attention_layernorm.weight")

            def dense(s, h):
                n2 = part.norm(h, norm2)
                proj = lambda x, m: part.project(  # noqa: E731
                    s.r, x, w(f"{pre}mlp.{m}_proj.weight"))
                return proj(part.silu_times(proj(n2, "gate"),
                                            proj(n2, "up")), "down")

            def sparse(hs):
                three = lambda at: [w(f"{at}{m}_proj.weight")  # noqa: E731
                                    for m in ("gate", "up", "down")]
                router = w(pre + "mlp.gate.weight")
                bias = w(pre + "mlp.gate.e_score_correction_bias")
                n2s, weights, ys = [], [], []
                for s in seqs:
                    n2s.append(part.norm(hs.pop(0), norm2))
                    weight, s.gap = part.route(n2s[-1], router, bias, s.gap)
                    weights.append(weight)
                    ys.append(jnp.zeros_like(n2s[-1]))
                for e in range(cfg["n_routed_experts"]):
                    expert = three(f"{pre}mlp.experts.{e}.")
                    ys = [part.expert(s.r, y, n2, weight, e, *expert)
                          for s, y, n2, weight in zip(seqs, ys, n2s, weights)]
                    del expert
                if cfg.get("n_shared_experts"):
                    shared = three(pre + "mlp.shared_experts.")
                    ys = [part.add(y, part.swiglu(s.r, n2, *shared))
                          for s, y, n2 in zip(seqs, ys, n2s)]
                return ys

            sublayer(i, "mlp", each(dense)
                     if i < cfg["first_k_dense_replace"] else sparse)
            if layer_outputs:
                for s in seqs:
                    s.streams.append(np.asarray(s.x))
        norm = w("model.norm.weight")
        head = w("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
                 else "lm_head.weight")
        out = []
        for s in seqs:
            # on exit the streams are summed
            logits = part.logits(s.r, hc.reduce(s.x), norm,
                                 jnp.asarray(s.rows), head)
            got = {"logits": np.asarray(logits),
                   "router_gap": np.asarray(s.gap)}
            if layer_outputs:
                got["streams"] = s.streams
            out.append(got)
    return out


def _deepseek_keys(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys ``reference_deepseek``'s parts read, as this configuration
    has them (``rope_scaling``'s kind under ``type``)."""
    return {k: cfg.get(k) for k in (
        "rms_norm_eps", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "rope_scaling", "n_group", "topk_group", "num_experts_per_tok",
        "routed_scaling_factor")}


@functools.lru_cache(maxsize=4)
def _mhc_parts(cfg_json: str):
    """The residual path's steps as :func:`forward_each` calls them, each
    one compiled program (as ``reference_deepseek._parts`` compiles a
    layer's parts): the number of rounds and ``identity_res`` are static."""
    from types import SimpleNamespace

    cfg = json.loads(cfg_json)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def read(iters, identity, x, phi, b, alpha):
        h_pre, h_post, h_res = maps(cfg, x, phi, b, alpha.astype(jnp.float32),
                                    iters, identity)
        return jnp.einsum("ti,tic->tc", h_pre, x), h_post, h_res

    @jax.jit
    def write(x, y, h_res, h_post):
        return (jnp.einsum("tij,tjc->tic", h_res, x)
                + h_post[:, :, None] * y[:, None, :])

    @functools.partial(jax.jit, static_argnums=(1,))
    def expand(x, n):
        return jnp.repeat(x[:, None, :], n, axis=1)

    @jax.jit
    def reduce(x):
        return jnp.sum(x, axis=1)

    return SimpleNamespace(read=read, write=write, expand=expand,
                           reduce=reduce)
