"""A plain reference of the published DeepSeek-V3 forward pass (``model_type:
deepseek_v3``; GigaChat3.1-702B-A36B): straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``.  EXPANDED latent
attention only, a Python loop over layers, heads and experts, no cache, no
batching, no kernel; the published tensor names and layouts.  The parts of a
layer are compiled one by one (:func:`_parts`), not the model as a whole.
What the system computes (``CausalLM`` through chunked prefill, the absorbed
paged decode step and the mixed step, the latent carried in the engine's page
pool) is held to this.

Layer ``i``, as published (``modeling_deepseek``)::

    x = x + Attn(RMSNorm(x));  x = x + FF_i(RMSNorm(x))

    Attn(h):  c_q = RMSNorm(W_DQ h);  [q_n, q_r] = W_UQ c_q      heads x (dn + dr)
              [c, k_r] = W_DKV h;  c = RMSNorm(c);  q_r, k_r = rope(q_r), rope(k_r)
              [k_n, v] = W_UKV c                                 heads x (dn + dv)
              o = causal softmax(sigma (q_n . k_n + q_r . k_r)) v;  W_O o
       rope: a head's rope dimensions are de-interleaved (0, 2, 4, .. then 1,
       3, 5, ..) and turned by rotate-half, so dimension 2j turns with 2j + 1;
       k_r is ONE vector shared by all heads; frequencies stretched by yarn;
       sigma = (dn + dr)^-1/2 * (0.1 mscale_all_dim ln(factor) + 1)^2
    FF_i, i < first_k_dense_replace:  W_down(silu(W_gate n) * W_up n)
    FF_i otherwise:  Shared(n) + sum over the token's experts e of w_e E_e(n)
       s = sigmoid(W_g n) in float32;  selection scores s + b;  groups of
       consecutive experts ranked by the sum of their two largest s + b, the
       best topk_group stay;  the token's experts: the num_experts_per_tok
       largest s + b among those groups;  w_e = routed_scaling_factor * s_e /
       (sum of the chosen s + 1e-20)

    logits = W_head RMSNorm(x)

Departures from the published code, all of them: (1) every expert is applied
to every position and weighted by ``w_e`` or 0, instead of gathering each
expert's positions: the same sum, and no shape depends on the routing;
(2) experts outside the kept groups are excluded from the top-k (score
``-inf``) where the published code sets their score to 0.0: the same choice
whenever a kept group holds ``num_experts_per_tok`` positive scores, which
sigmoid scores plus a small bias always do; (3) ties in a top-k go to the
lower index; (4) ``held = (first, count)``: only the routed experts ``first
.. first + count`` are computed (the share of ONE expert-parallel rank); an
assignment to any other expert adds nothing, and that partial sum goes on to
the next layer, as the rank's program has it.  Default: all of them;
(5) ``mscale`` = ``mscale_all_dim`` is assumed (cos and sin unscaled), no
bias, ``norm_topk_prob`` true, sigmoid scoring, ``noaux_tc``; nothing else is
implemented; (6) the multi-token module (``num_nextn_predict_layers``) is not
run: the next-token logits do not depend on it; (7) one sequence at a time,
so there is no padding mask (:func:`forward_each` takes several and computes
each alone, layer by layer, so that a tensor is fetched once for all).

``weights`` is ``get(published tensor name) -> array``; ``cfg`` the published
``config.json`` as a dict (``n_routed_experts``: what the router scores).
``round_inputs`` (applied to both inputs of every matrix product) and
``yarn_softmax_scale=False`` (``sigma`` without yarn's factor) exist so that
a caller can compute what a LOWER precision, or a system that forgot the
softmax's yarn factor, would give, and see that its tolerance tells them
apart.  ``rounded_precision`` is the precision of the products of ROUNDED
inputs (default: ``highest`` like the rest): inputs of no more mantissa bits
than a bfloat16 holds multiply exactly in one bfloat16 pass with float32 sums
(``"default"``), and those parts compile in a sixth of the time.
"""

from __future__ import annotations

import functools
import json
import math
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def yarn_inv_freq(cfg: Dict[str, Any]) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``); plain where ``rope_scaling`` is
    null."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return plain.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (plain / sc["factor"] * (1 - mask) + plain * mask).astype(
        np.float32)


def softmax_scale(cfg: Dict[str, Any], yarn: bool = True) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if yarn and sc and sc.get("mscale_all_dim"):
        m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
        scale *= m * m
    return scale


def rope_interleaved(x: Array, positions: Array, inv_freq: Array) -> Array:
    """``x [T, H, d]``: de-interleave, then rotate half."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def route(cfg: Dict[str, Any], logits: Array, bias: Array,
          held: Optional[Tuple[int, int]] = None) -> Tuple[Array, Array]:
    """``(weights [T, E], gap [T])``: ``weights`` is ``w_e`` for the token's
    experts and 0 elsewhere; ``gap`` how close the choice is to a tie: the
    smaller of (k-th minus (k+1)-th selection score among the kept groups'
    experts) and (last kept minus first dropped group score).  With ``held =
    (first, count)`` the first of the two is taken as the rank that holds
    experts ``first .. first + count`` sees it: how far its lowest chosen
    expert lies over the best expert not chosen, and its best expert not
    chosen under the k-th: closer pairs of experts held elsewhere may change
    places and move this rank's sum by the renormalisation alone (their gap
    over a sum near ``k / 2``).  A group boundary counts whichever groups
    meet at it: the kept groups' experts compete for the same ``k`` places."""
    t, e = logits.shape
    g, keep, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits)
    pick = s + bias
    by_group = pick.reshape(t, g, e // g)
    rank = jnp.sort(by_group, -1)[..., -2:].sum(-1)              # [T, g]
    # a stable order: larger first, lower index first among equals
    order = jnp.argsort(-rank, -1, stable=True)
    stays = jnp.zeros((t, g), bool).at[
        jnp.arange(t)[:, None], order[:, :keep]].set(True)
    ranked = jnp.take_along_axis(rank, order, -1)
    gap = (ranked[:, keep - 1] - ranked[:, keep] if keep < g
           else jnp.full((t,), jnp.inf))
    among = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, e)
    order = jnp.argsort(-among, -1, stable=True)
    chosen = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], order[:, :k]].set(True)
    top = jnp.take_along_axis(among, order, -1)
    between = top[:, k - 1] - top[:, k]
    if held is not None:
        own = (jnp.arange(e) >= held[0]) & (jnp.arange(e) < sum(held))
        lowest_in = jnp.where(chosen & own, among, jnp.inf).min(-1)
        best_out = jnp.where(~chosen & own, among, -jnp.inf).max(-1)
        between = jnp.minimum(lowest_in - top[:, k], top[:, k - 1] - best_out)
    gap = jnp.minimum(gap, between)
    w = jnp.where(chosen, s, 0.0)
    w = cfg["routed_scaling_factor"] * w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, gap


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            round_inputs: Optional[Callable[[Array], Array]] = None,
            held: Optional[Tuple[int, int]] = None,
            yarn_softmax_scale: bool = True,
            layer_outputs: bool = False,
            rounded_precision: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Logits of one sequence.  Returns ``{"logits": [len(rows), V],
    "router_gap": [T]}`` (``rows``: the positions whose logits are wanted,
    default all; ``router_gap[t]``: the smallest :func:`route` gap over the
    sparse layers at position ``t``, as the rank ``held`` sees it) and, with
    ``layer_outputs``, ``"routed"`` / ``"shared"``: per sparse layer the
    ``[T, D]`` routed sum over the held experts and the shared expert's
    output."""
    return forward_each(
        weights, cfg,
        [{"ids": ids, "rows": rows, "round_inputs": round_inputs,
          "yarn_softmax_scale": yarn_softmax_scale,
          "rounded_precision": rounded_precision}],
        held=held, layer_outputs=layer_outputs)[0]


def forward_each(weights: Callable[[str], Any], cfg: Dict[str, Any],
                 jobs: Sequence[Dict[str, Any]],
                 held: Optional[Tuple[int, int]] = None,
                 layer_outputs: bool = False) -> List[Dict[str, np.ndarray]]:
    """:func:`forward` of several sequences, each what :func:`forward` alone
    gives: ``jobs`` holds for each its own ``ids`` and, where wanted,
    ``rows``, ``round_inputs`` (with ``rounded_precision``) and
    ``yarn_softmax_scale``.  The sequences do not see one another (a loop
    over them inside each part of a layer); what they share is the FETCH: a
    published tensor is asked for and moved to the device once for all of
    them, where a caller that makes or moves the 8.6 GB anew for every
    sequence would wait for that each time.  The dense feed-forward's three
    matrices (1.6 GB in float32 at the published width) are the exception:
    fetched for each sequence, one at a time."""
    # a tensor goes to the device as it is stored, and the part that uses it
    # raises it to float32 there (the whole model in float32 need not fit)
    w = lambda name: jnp.asarray(weights(name))  # noqa: E731
    held = held and tuple(held)
    part = _parts(json.dumps(cfg, sort_keys=True), held)
    first, count = held or (0, cfg["n_routed_experts"])
    embedding = np.asarray(weights("model.embed_tokens.weight"))

    class Seq:
        def __init__(self, ids, rows=None, round_inputs=None,
                     yarn_softmax_scale=True, rounded_precision=None):
            self.r = round_inputs and (round_inputs, rounded_precision)
            self.sigma = softmax_scale(cfg, yarn_softmax_scale)
            ids = np.asarray(ids, np.int64)
            self.rows = np.asarray(
                np.arange(len(ids)) if rows is None else rows, np.int64)
            self.x = jnp.asarray(embedding[ids]).astype(jnp.float32)
            self.gap = jnp.full((len(ids),), jnp.inf, jnp.float32)
            self.routed, self.shared = [], []

    seqs = [Seq(**job) for job in jobs]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            norm = w(pre + "input_layernorm.weight")
            latent = [w(f"{pre}self_attn.{name}.weight") for name in (
                "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
                "kv_a_layernorm", "kv_b_proj")]
            o = w(pre + "self_attn.o_proj.weight")
            for s in seqs:
                q, q_r, k_r, kv = part.latent(s.r, s.x, norm, *latent)
                att = [part.head(s.r, s.sigma, h, q, q_r, k_r, kv)
                       for h in range(cfg["num_attention_heads"])]
                s.x = part.attention_out(s.r, s.x, att, o)
                del q, q_r, k_r, kv, att
            del latent, o
            norm = w(pre + "post_attention_layernorm.weight")
            for s in seqs:
                s.n2 = part.norm(s.x, norm)
            if i < cfg["first_k_dense_replace"]:
                for s in seqs:
                    dense = lambda x, m: part.project(  # noqa: E731
                        s.r, x, w(f"{pre}mlp.{m}_proj.weight"))
                    hid = part.silu_times(dense(s.n2, "gate"),
                                          dense(s.n2, "up"))
                    s.x = part.add(s.x, dense(hid, "down"))
                    del hid
                continue
            three = lambda pre: [w(f"{pre}{m}_proj.weight")  # noqa: E731
                                 for m in ("gate", "up", "down")]
            router = w(pre + "mlp.gate.weight")
            bias = w(pre + "mlp.gate.e_score_correction_bias")
            for s in seqs:
                s.weight, s.gap = part.route(s.n2, router, bias, s.gap)
                s.y = jnp.zeros_like(s.x)
            for e in range(first, first + count):
                expert = three(f"{pre}mlp.experts.{e}.")
                for s in seqs:
                    s.y = part.expert(s.r, s.y, s.n2, s.weight, e, *expert)
                del expert
            shared = (three(pre + "mlp.shared_experts.")
                      if cfg.get("n_shared_experts") else None)
            for s in seqs:
                also = (part.swiglu(s.r, s.n2, *shared) if shared
                        else jnp.zeros_like(s.x))
                if layer_outputs:
                    s.routed.append(np.asarray(s.y))
                    s.shared.append(np.asarray(also))
                s.x = part.add(s.x, s.y, also)
            del shared
        norm = w("model.norm.weight")
        head = w("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
                 else "lm_head.weight")
        out = []
        for s in seqs:
            logits = part.logits(s.r, s.x, norm, jnp.asarray(s.rows), head)
            got = {"logits": np.asarray(logits),
                   "router_gap": np.asarray(s.gap)}
            if layer_outputs:
                got["routed"], got["shared"] = s.routed, s.shared
            out.append(got)
    return out


@functools.lru_cache(maxsize=4)
def _parts(cfg_json: str, held: Optional[Tuple[int, int]]):
    """The parts of a layer as :func:`forward_each` calls them, each ONE
    compiled program (``jax.jit``) for a configuration: computed operation by
    operation, one sequence at the published widths met some 260 small
    programs, which the chip's compiler took two minutes over on a run's
    first check.  The loops over layers, heads and experts stay in Python; a
    head and an expert are the same program with another index.  ``r`` (a
    sequence's ``round_inputs`` with its ``rounded_precision``, or ``None``)
    and ``sigma`` are static: the two sensitivity readings compile their own
    parts.  Every weight comes in
    as stored and is raised to float32 inside."""
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    inv_freq = yarn_inv_freq(cfg)
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

    @static(0)
    def latent(r, x, norm, q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b):
        t = x.shape[0]
        pos = jnp.arange(t)
        n1 = rms_norm(x, f32(norm), eps)
        cq = rms_norm(mm(r, n1, f32(q_a).T), f32(q_a_norm), eps)
        q = mm(r, cq, f32(q_b).T).reshape(t, heads, dn + dr)
        ckr = mm(r, n1, f32(kv_a).T)
        c = rms_norm(ckr[:, :rank], f32(kv_a_norm), eps)
        q_r = rope_interleaved(q[..., dn:], pos, jnp.asarray(inv_freq))
        k_r = rope_interleaved(ckr[:, None, rank:], pos,
                               jnp.asarray(inv_freq))[:, 0]
        kv = mm(r, c, f32(kv_b).T).reshape(t, heads, dn + dv)
        return q, q_r, k_r, kv

    @static(0, 1)
    def head(r, sigma, h, q, q_r, k_r, kv):
        pos = jnp.arange(q.shape[0])
        causal = pos[:, None] >= pos[None, :]
        s = (mm(r, q[:, h, :dn], kv[:, h, :dn].T)
             + mm(r, q_r[:, h], k_r.T)) * sigma
        s = jnp.where(causal, s, -jnp.inf)
        return mm(r, jax.nn.softmax(s, -1), kv[:, h, dn:])

    @static(0)
    def attention_out(r, x, att, o):
        return x + mm(r, jnp.concatenate(att, -1), f32(o).T)

    @jax.jit
    def route_(n2, router, bias, gap):
        # the router is float32 in the published model whatever the
        # precision of the rest: it is never rounded here
        weight, g = route(cfg, jnp.matmul(n2, f32(router).T), f32(bias), held)
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
        latent=latent, head=head, attention_out=attention_out, norm=norm,
        project=project, silu_times=silu_times, add=add, route=route_,
        expert=expert, swiglu=static(0)(swiglu), logits=logits)
