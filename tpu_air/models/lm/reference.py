"""A plain reference of the published OLMoE forward pass: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.
No cache, no batching, no kernel; a Python loop over layers and over
experts; the published tensor names, rope pairing and norm placement.  What
the system computes (``CausalLM`` through chunked prefill and the paged
decode step) is held to this.

The layer, as published (allenai/OLMoE-1B-7B-0125-Instruct, ``modeling_olmoe``):

    n1 = RMSNorm(x)
    q, k = RMSNorm_q(Wq n1), RMSNorm_k(Wk n1)      over the whole projection
    h  = x + Wo Attn(rope(q), rope(k), Wv n1)      rope pairs i with i + d/2
    n2 = RMSNorm(h)
    p  = softmax(Wr n2) in float32
    y  = h + sum over the top-k experts e of p_e Wdown_e(silu(Wgate_e n2) * Wup_e n2)
    logits = Whead RMSNorm(y)

Departures from the published code, all of them: (1) every expert is applied
to every position and weighted by ``p_e`` or 0, instead of gathering each
expert's positions: the same sum, and no shape depends on the routing;
(2) ``norm_topk_prob`` false, ``clip_qkv`` null, no bias and no rope scaling
are assumed, as the 0125-Instruct configuration has them, and nothing else is
implemented; (3) one sequence at a time, so there is no padding mask.

``weights`` is ``get(published tensor name) -> array``; ``cfg`` the
published ``config.json`` as a dict.  ``round_inputs`` (a function applied
to both inputs of every matrix product, default none) exists so that a
caller can compute the same pass in a LOWER precision and see that its
tolerance tells the two apart.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope_rotate_half(x: Array, positions: Array, theta: float) -> Array:
    """``x [T, H, d]``: dimension ``i`` turns with ``i + d/2``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv        # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            round_inputs: Optional[Callable[[Array], Array]] = None
            ) -> Dict[str, np.ndarray]:
    """Logits of one sequence.  Returns ``{"logits": [len(rows), V],
    "router_gap": [T]}``: ``rows`` are the positions whose logits are wanted
    (default all), and ``router_gap[t]`` is the smallest, over layers, of
    ``log p`` of the k-th minus the (k+1)-th most probable expert at position
    ``t``: how close the routing there is to a tie."""
    r = round_inputs or (lambda a: a)
    mm = lambda a, b: jnp.matmul(r(a), r(b))  # noqa: E731
    # a tensor goes to the device as it is stored and is raised to float32
    # there, one at a time (the whole model in float32 need not fit)
    w = lambda name: jnp.asarray(weights(name)).astype(  # noqa: E731
        jnp.float32)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, k_top = cfg["num_attention_heads"], cfg["num_experts_per_tok"]
    d = cfg["hidden_size"] // heads
    ids = np.asarray(ids, np.int64)
    t = len(ids)
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(
            weights("model.embed_tokens.weight"))[ids]).astype(jnp.float32)
        gap = jnp.full((t,), jnp.inf, jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            n1 = rms_norm(x, w(pre + "input_layernorm.weight"), eps)
            q = rms_norm(mm(n1, w(pre + "self_attn.q_proj.weight").T),
                         w(pre + "self_attn.q_norm.weight"), eps)
            k = rms_norm(mm(n1, w(pre + "self_attn.k_proj.weight").T),
                         w(pre + "self_attn.k_norm.weight"), eps)
            v = mm(n1, w(pre + "self_attn.v_proj.weight").T)
            q = rope_rotate_half(q.reshape(t, heads, d), pos, theta)
            k = rope_rotate_half(k.reshape(t, heads, d), pos, theta)
            v = v.reshape(t, heads, d)
            att = []
            for h in range(heads):
                s = mm(q[:, h], k[:, h].T) / np.sqrt(d)
                s = jnp.where(causal, s, -jnp.inf)
                att.append(mm(jax.nn.softmax(s, -1), v[:, h]))
            x = x + mm(jnp.concatenate(att, -1),
                       w(pre + "self_attn.o_proj.weight").T)
            n2 = rms_norm(x, w(pre + "post_attention_layernorm.weight"), eps)
            # the router is float32 in the published model whatever the
            # precision of the rest: it is never rounded here
            p = jax.nn.softmax(
                jnp.matmul(n2, w(pre + "mlp.gate.weight").T), -1)
            top = jnp.sort(p, -1)[:, ::-1]
            keep = p >= top[:, k_top - 1:k_top]
            gap = jnp.minimum(gap, jnp.log(top[:, k_top - 1])
                              - jnp.log(top[:, k_top]))
            y = jnp.zeros_like(x)
            for e in range(cfg["num_experts"]):
                ex = f"{pre}mlp.experts.{e}."
                hid = (jax.nn.silu(mm(n2, w(ex + "gate_proj.weight").T))
                       * mm(n2, w(ex + "up_proj.weight").T))
                y = y + jnp.where(keep[:, e], p[:, e], 0.0)[:, None] * mm(
                    hid, w(ex + "down_proj.weight").T)
            x = x + y
        x = rms_norm(x, w("model.norm.weight"), eps)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int64))]
        head = ("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
                else "lm_head.weight")
        logits = mm(x, w(head).T)
    return {"logits": np.asarray(logits), "router_gap": np.asarray(gap)}
