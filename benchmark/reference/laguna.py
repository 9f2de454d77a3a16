"""The benchmark's own copy of ``tpu_air/models/lm/reference_laguna.py``
(PR 60), kept here so that a later change to the program cannot move the
yardstick.

A plain reference of the published Laguna forward pass (``model_type:
laguna``; Laguna-XS.2): straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  The whole sequence at once, a
Python loop over layers, heads and experts, no cache, no ring, no batching, no
kernel; the published tensor layouts under the names the configuration file
assumes.  The parts of a layer are compiled one by one (:func:`_parts`), not
the model as a whole.  What the system computes (``CausalLM`` through chunked
prefill, the paged decode step and the mixed step, a window layer's K and V
carried in a ring a slot and a full layer's in pages) is held to this.

Layer ``i`` (``eps`` = ``rms_norm_eps``, every norm an RMSNorm with a learned
scale)::

    x = x + Attn_i(RMSNorm(x));  x = x + FF_i(RMSNorm(x))

    Attn_i(n):  H_i = num_attention_heads_per_layer[i], G = num_key_value_heads,
                d = head_dim;  q = W_q n [T, H_i, d];  k = W_k n, v = W_v n [T, G, d]
        rope on the first r*d numbers of each head of q and k (rotate-half
        inside them: number j turns with j + r*d/2), the rest pass;  by
        layer_types[i]:  full_attention  r = 0.5, theta 500000, yarn over the
        r*d numbers that turn (factor, original_max_position_embeddings,
        beta_fast, beta_slow), cos and sin times attention_factor;
        sliding_attention  r = 1, theta 10000, plain
        head j reads K/V head j // (H_i / G);  scores q.k / sqrt(d);  softmax
        over the keys p <= t (full) or t - sliding_window < p <= t (sliding:
        the token itself counts, sliding_window keys)
        g = sigmoid(W_g n) [T, H_i];  head j's output times g[:, j];  W_o
    FF_i, mlp_layer_types[i] dense:  W_down(silu(W_gate n) * W_up n)
    FF_i sparse:  Shared(n) + sum over the token's experts e of w_e E_e(n)
        s = sigmoid(W_r n) in float32;  the token's experts: the
        num_experts_per_tok largest s + b (b a selection bias);  w_e =
        moe_routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)

    logits = W_head RMSNorm(x)

What the published ``config.json`` does not say and this file ASSUMES (the
configuration file's ``assumed`` has each with its reason): the gate's form
(``"gating": true``; the sibling Laguna-S-2.1 says ``per-head``), the
router's scoring (sigmoid with a selection bias, renormalised, as
``deepseek_v3``'s with one group), that the window counts the token itself,
the tensor names (:data:`NAMES`).  Departures from a gathered implementation:
every expert is applied to every position and weighted by ``w_e`` or 0 (the
same sum, no shape depends on the routing); ties in the top-k go to the
lower index; one sequence at a time, so no padding mask
(:func:`forward_each` takes several and computes each alone, layer by layer,
so that a tensor is fetched once for all).

``weights`` is ``get(tensor name) -> array``; ``cfg`` the published
``config.json`` as a dict.  A job's other keys exist so that a caller can
compute what a system AT FAULT would give and see that its tolerance tells
them apart: ``round_inputs`` (applied to both inputs of every matrix product;
``rounded_precision`` the precision of those products, default ``highest``),
``window_mask=False`` (a sliding layer that sees every earlier position),
``gate=False`` (no output gate), ``rope_whole_head=True`` (a full layer that
turns the whole head, frequencies over ``d`` numbers), ``attention_factor=
False`` (yarn's factor left off cos and sin), ``ring_of=j`` (the sliding
layers read the K and V of job ``j`` of the same call: another slot's ring).
"""

from __future__ import annotations

import functools
import json
import math
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

#: the tensor names assumed (the catalog row gives none): ``qwen2_moe``'s,
#: whose key names the config carries (``shared_expert_intermediate_size``,
#: ``norm_topk_prob``, ``mlp_only_layers``), the gate as ``g_proj`` and the
#: selection bias under ``deepseek_v3``'s name
NAMES = {
    "embed": "model.embed_tokens.weight",
    "head": "lm_head.weight",
    "final_norm": "model.norm.weight",
    "attn_norm": "model.layers.{i}.input_layernorm.weight",
    "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
    "q": "model.layers.{i}.self_attn.q_proj.weight",
    "k": "model.layers.{i}.self_attn.k_proj.weight",
    "v": "model.layers.{i}.self_attn.v_proj.weight",
    "o": "model.layers.{i}.self_attn.o_proj.weight",
    "g": "model.layers.{i}.self_attn.g_proj.weight",
    "dense": "model.layers.{i}.mlp.{m}_proj.weight",
    "router": "model.layers.{i}.mlp.gate.weight",
    "router_bias": "model.layers.{i}.mlp.gate.e_score_correction_bias",
    "expert": "model.layers.{i}.mlp.experts.{e}.{m}_proj.weight",
    "shared": "model.layers.{i}.mlp.shared_expert.{m}_proj.weight",
}


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope_of(cfg: Dict[str, Any], layer_type: str,
            whole_head: bool = False, attention_factor: bool = True):
    """``(inv_freq [turned / 2] float32, turned, factor)`` of a layer of
    ``layer_type``: the frequencies of the numbers that turn, how many of a
    head's numbers those are, and what multiplies cos and sin."""
    p = cfg["rope_parameters"][layer_type]
    share = 1.0 if whole_head else p.get("partial_rotary_factor", 1.0)
    dim, base = int(cfg["head_dim"] * share), float(p["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if p.get("rope_type", "default") == "default":
        return plain.astype(np.float32), dim, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    orig = p["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = plain / p["factor"] * (1 - keep) + plain * keep
    factor = p.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(p["factor"]) + 1.0
    return inv.astype(np.float32), dim, (factor if attention_factor else 1.0)


def rope_rotate_half(x: Array, positions: Array, inv_freq: Array,
                     factor: float) -> Array:
    """``x [T, H, d]``: the first ``2 * len(inv_freq)`` numbers of a head
    turn, number ``j`` with ``j + len(inv_freq)``; the rest pass."""
    n = 2 * inv_freq.shape[0]
    t, rest = x[..., :n], x[..., n:]
    ang = positions.astype(jnp.float32)[:, None] * inv_freq       # [T, n/2]
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * factor)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * factor)[:, None, :]
    rot = jnp.concatenate([-t[..., n // 2:], t[..., :n // 2]], -1)
    return jnp.concatenate([t * cos + rot * sin, rest], -1)


def route(cfg: Dict[str, Any], logits: Array, bias: Array):
    """``(weights [T, E], gap [T])``: ``weights`` is ``w_e`` for the token's
    experts and 0 elsewhere; ``gap`` how close the choice is to a tie: the
    k-th minus the (k+1)-th selection score."""
    t, e = logits.shape
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits)
    pick = s + bias
    # a stable order: larger first, lower index first among equals
    order = jnp.argsort(-pick, -1, stable=True)
    chosen = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], order[:, :k]].set(True)
    top = jnp.take_along_axis(pick, order, -1)
    w = jnp.where(chosen, s, 0.0)
    w = (cfg.get("moe_routed_scaling_factor", 1.0) * w
         / (w.sum(-1, keepdims=True) + 1e-20))
    return w, top[:, k - 1] - top[:, k]


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            **how: Any) -> Dict[str, np.ndarray]:
    """Logits of one sequence: ``{"logits": [len(rows), V], "router_gap":
    [T]}`` (``rows``: the positions whose logits are wanted, default all;
    ``router_gap[t]``: the smallest :func:`route` gap over the sparse layers
    at position ``t``).  ``how``: a job's other keys (the module doc)."""
    return forward_each(weights, cfg, [{"ids": ids, "rows": rows, **how}])[0]


def forward_each(weights: Callable[[str], Any], cfg: Dict[str, Any],
                 jobs: Sequence[Dict[str, Any]],
                 names: Optional[Dict[str, str]] = None
                 ) -> List[Dict[str, np.ndarray]]:
    """:func:`forward` of several sequences, each what :func:`forward` alone
    gives (``ring_of`` apart: that job's sliding layers read another job's K
    and V, and the two must be equally long).  The sequences share the
    FETCH: a tensor is asked for and moved to the device once for all of
    them.  ``names``: the tensor names, where they are not :data:`NAMES`."""
    names = {**NAMES, **(names or {})}
    # a tensor goes to the device as it is stored, and the part that uses it
    # raises it to float32 there (the whole model in float32 need not fit)
    w = lambda key, **at: jnp.asarray(  # noqa: E731
        weights(names[key].format(**at)))
    part = _parts(json.dumps(cfg, sort_keys=True))
    embedding = np.asarray(weights(names["embed"]))

    class Seq:
        def __init__(self, ids, rows=None, round_inputs=None,
                     rounded_precision=None, window_mask=True, gate=True,
                     rope_whole_head=False, attention_factor=True,
                     ring_of=None):
            self.r = round_inputs and (round_inputs, rounded_precision)
            self.window_mask, self.gate, self.ring_of = (window_mask, gate,
                                                         ring_of)
            self.rope = (rope_whole_head, attention_factor)
            ids = np.asarray(ids, np.int64)
            self.rows = np.asarray(
                np.arange(len(ids)) if rows is None else rows, np.int64)
            self.x = jnp.asarray(embedding[ids]).astype(jnp.float32)
            self.gap = jnp.full((len(ids),), jnp.inf, jnp.float32)

    seqs = [Seq(**job) for job in jobs]
    three = lambda key, **at: [w(key, m=m, **at)  # noqa: E731
                               for m in ("gate", "up", "down")]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg["num_hidden_layers"]):
            kind = cfg["layer_types"][i]
            heads = cfg["num_attention_heads_per_layer"][i]
            sliding = kind == "sliding_attention"
            norm = w("attn_norm", i=i)
            proj = [w(key, i=i) for key in ("q", "k", "v", "g")]
            o = w("o", i=i)
            for s in seqs:
                s.qkvg = part.qkvg(s.r, kind, heads, *s.rope, s.x, norm,
                                   *proj)
            for s in seqs:
                q, k, v, g = s.qkvg
                if sliding and s.ring_of is not None:
                    k, v = seqs[s.ring_of].qkvg[1:3]
                window = (cfg["sliding_window"]
                          if sliding and s.window_mask else 0)
                att = [part.head(s.r, window, heads, h, q, k, v)
                       for h in range(heads)]
                s.x = part.attention_out(s.r, s.gate, s.x, att, g, o)
                del att
            for s in seqs:
                del s.qkvg
            del proj, o
            norm = w("mlp_norm", i=i)
            for s in seqs:
                s.n2 = part.norm(s.x, norm)
            if cfg["mlp_layer_types"][i] == "dense":
                for s in seqs:
                    dense = lambda x, m: part.project(  # noqa: E731
                        s.r, x, w("dense", i=i, m=m))
                    hid = part.silu_times(dense(s.n2, "gate"),
                                          dense(s.n2, "up"))
                    s.x = part.add(s.x, dense(hid, "down"))
                    del hid
                continue
            router, bias = w("router", i=i), w("router_bias", i=i)
            for s in seqs:
                s.weight, s.gap = part.route(s.n2, router, bias, s.gap)
                s.y = jnp.zeros_like(s.x)
            for e in range(cfg["num_experts"]):
                expert = three("expert", i=i, e=e)
                for s in seqs:
                    s.y = part.expert(s.r, s.y, s.n2, s.weight, e, *expert)
                del expert
            shared = three("shared", i=i)
            for s in seqs:
                s.x = part.add(s.x, s.y, part.swiglu(s.r, s.n2, *shared))
            del shared
        norm = w("final_norm")
        head = w("embed" if cfg.get("tie_word_embeddings") else "head")
        return [{"logits": np.asarray(part.logits(
            s.r, s.x, norm, jnp.asarray(s.rows), head)),
            "router_gap": np.asarray(s.gap)} for s in seqs]


@functools.lru_cache(maxsize=4)
def _parts(cfg_json: str):
    """The parts of a layer as :func:`forward_each` calls them, each ONE
    compiled program (``jax.jit``) for a configuration; the loops over
    layers, heads and experts stay in Python, and a head and an expert are
    the same program with another index.  What says HOW a part computes (a
    sequence's ``round_inputs`` with its ``rounded_precision``, the layer's
    kind and head count, the controls) is static: a reading at fault compiles
    its own parts.  Every weight comes in as stored and is raised to float32
    inside."""
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]
    groups, d = cfg["num_key_value_heads"], cfg["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    static = lambda *n: functools.partial(  # noqa: E731
        jax.jit, static_argnums=n)

    def mm(r, a, b):
        if r is None:
            return jnp.matmul(a, b)
        rounded, precision = r
        return jnp.matmul(rounded(a), rounded(b), precision=precision)

    def swiglu(r, n, gate, up, down):
        hid = jax.nn.silu(mm(r, n, f32(gate).T)) * mm(r, n, f32(up).T)
        return mm(r, hid, f32(down).T)

    @static(0, 1, 2, 3, 4)
    def qkvg(r, kind, heads, whole_head, attention_factor, x, norm, wq, wk,
             wv, wg):
        t = x.shape[0]
        pos = jnp.arange(t)
        inv_freq, _, factor = rope_of(cfg, kind, whole_head, attention_factor)
        n1 = rms_norm(x, f32(norm), eps)
        turn = lambda a: rope_rotate_half(  # noqa: E731
            a, pos, jnp.asarray(inv_freq), factor)
        q = turn(mm(r, n1, f32(wq).T).reshape(t, heads, d))
        k = turn(mm(r, n1, f32(wk).T).reshape(t, groups, d))
        v = mm(r, n1, f32(wv).T).reshape(t, groups, d)
        return q, k, v, jax.nn.sigmoid(mm(r, n1, f32(wg).T))

    @static(0, 1, 2)
    def head(r, window, heads, h, q, k, v):
        pos = jnp.arange(q.shape[0])
        seen = pos[:, None] >= pos[None, :]
        if window:
            seen = seen & (pos[:, None] - pos[None, :] < window)
        kv = h // (heads // groups)
        s = mm(r, q[:, h], k[:, kv].T) * d ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        return mm(r, jax.nn.softmax(s, -1), v[:, kv])

    @static(0, 1)
    def attention_out(r, gate, x, att, g, o):
        att = jnp.stack(att, 1)                               # [T, H, d]
        if gate:
            att = att * g[:, :, None]
        return x + mm(r, att.reshape(att.shape[0], -1), f32(o).T)

    @jax.jit
    def route_(n2, router, bias, gap):
        # the router is float32 whatever the precision of the rest: it is
        # never rounded here
        weight, g = route(cfg, jnp.matmul(n2, f32(router).T), f32(bias))
        return weight, jnp.minimum(gap, g)

    @static(0)
    def expert(r, y, n2, weight, e, gate, up, down):
        return y + weight[:, e][:, None] * swiglu(r, n2, gate, up, down)

    @static(0)
    def logits(r, x, norm, rows, head_w):
        return mm(r, rms_norm(x, f32(norm), eps)[rows], f32(head_w).T)

    @jax.jit
    def norm(x, w):
        return rms_norm(x, f32(w), eps)

    @static(0)
    def project(r, x, w):
        return mm(r, x, f32(w).T)

    @jax.jit
    def silu_times(a, b):
        return jax.nn.silu(a) * b

    @jax.jit
    def add(*a):
        return sum(a[1:], a[0])

    return SimpleNamespace(
        qkvg=qkvg, head=head, attention_out=attention_out, norm=norm,
        project=project, silu_times=silu_times, add=add, route=route_,
        expert=expert, swiglu=static(0)(swiglu), logits=logits)
