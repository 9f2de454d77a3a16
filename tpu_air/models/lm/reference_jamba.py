"""A plain reference of the published Jamba forward pass (``model_type:
jamba``, AI21-Jamba2-3B): straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  No cache, no batching, no
kernel, no chunks; a Python loop over layers and heads and a sequential
``lax.scan`` over positions for the state-space recurrence; the published
tensor names and layouts.  What the system computes (``CausalLM`` through
chunked prefill and the paged decode step, the state carried in the engine's
cache) is held to this.

Layer ``i``, as published (``modeling_jamba``)::

    x = x + mixer_i(RMSNorm(x));  x = x + W_down(silu(W_gate n) * W_up n),  n = RMSNorm(x)

    attention mixer (i % attn_layer_period == attn_layer_offset):
      q = W_q h (heads x d), k = W_k h, v = W_v h (kv heads x d); no bias, NO
      position encoding; causal softmax(q k^T / sqrt(d)); a K/V head serves
      heads / kv_heads query heads; W_o
    Mamba mixer (every other layer):
      [u, z] = W_in h;  u = silu(conv1d_causal_depthwise(u) + b_conv)
      [dt, B, C] = W_x u;  dt, B, C = RMSNorm_dt(dt), RMSNorm_b(B), RMSNorm_c(C)
      delta = softplus(W_dt dt + b_dt);  A = -exp(A_log)            [c, n]
      s_t[c, n] = exp(delta_t[c] A[c, n]) s_{t-1}[c, n] + delta_t[c] B_t[n] u_t[c]
      y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c];  out = W_out(y * silu(z))

    logits = E RMSNorm(x)   (tied embedding)

Departures from the published code, all of them: (1) ``num_experts`` 1, so
every feed-forward is the one SwiGLU and no router runs (``expert_layer_*``
select nothing); nothing else is implemented; (2) one sequence at a time, so
there is no padding mask; (3) the recurrence is the plain sequential one, not
the published fused scan kernel: the same equations; (4) ``mamba_proj_bias``
false and ``mamba_conv_bias`` true are assumed, as the configuration has
them.

``weights`` is ``get(published tensor name) -> array``; ``cfg`` the
published ``config.json`` as a dict.  ``round_inputs`` (applied to both
inputs of every matrix product), ``drop_state_at`` (a position at which
every Mamba layer forgets: state and convolution inputs before it read as
zero) and ``round_state`` (applied to the state every Mamba layer carries,
after every position) exist so that a caller can compute what a LOWER
precision, a system that loses the carried state between two chunks, or one
that keeps the state in fewer bits than the configuration states, would
give, and see that its tolerance tells them apart.  ``state_after`` asks for
the state every Mamba layer carries after that many positions as well
(``"states"``), for a caller that compares the carried state itself.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def layer_is_attention(cfg: Dict[str, Any], i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            round_inputs: Optional[Callable[[Array], Array]] = None,
            drop_state_at: Optional[int] = None,
            round_state: Optional[Callable[[Array], Array]] = None,
            state_after: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Logits of one sequence: ``{"logits": [len(rows), V]}``, ``rows`` the
    positions whose logits are wanted (default all); with ``state_after``
    also ``"states": [Mamba layers, d_inner, d_state]``."""
    r = round_inputs or (lambda a: a)
    kept = round_state or (lambda a: a)
    mm = lambda a, b: jnp.matmul(r(a), r(b))  # noqa: E731
    # a tensor goes to the device as it is stored and is raised to float32
    # there, one at a time (the whole model in float32 need not fit)
    w = lambda name: jnp.asarray(weights(name)).astype(  # noqa: E731
        jnp.float32)
    eps = cfg["rms_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    c = cfg["mamba_expand"] * cfg["hidden_size"]
    n, k, rank = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
                  cfg["mamba_dt_rank"])
    ids = np.asarray(ids, np.int64)
    t = len(ids)
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    cut = t if drop_state_at is None else int(drop_state_at)
    # an array, not a literal: the scan compiles once for every position
    snap_at = jnp.int32(-1 if state_after is None else int(state_after) - 1)
    states = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(
            weights("model.embed_tokens.weight"))[ids]).astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            h = rms_norm(x, w(pre + "input_layernorm.weight"), eps)
            if layer_is_attention(cfg, i):
                q = mm(h, w(pre + "self_attn.q_proj.weight").T)
                kk = mm(h, w(pre + "self_attn.k_proj.weight").T)
                v = mm(h, w(pre + "self_attn.v_proj.weight").T)
                q = q.reshape(t, heads, d)
                kk, v = kk.reshape(t, kv_heads, d), v.reshape(t, kv_heads, d)
                att = []
                for hq in range(heads):
                    g = hq // (heads // kv_heads)
                    s = mm(q[:, hq], kk[:, g].T) / np.sqrt(d)
                    s = jnp.where(causal, s, -jnp.inf)
                    att.append(mm(jax.nn.softmax(s, -1), v[:, g]))
                mixed = mm(jnp.concatenate(att, -1),
                           w(pre + "self_attn.o_proj.weight").T)
            else:
                m = pre + "mamba."
                uz = mm(h, w(m + "in_proj.weight").T)
                u, z = uz[:, :c], uz[:, c:]
                taps = w(m + "conv1d.weight")[:, 0, :]            # [c, k]
                conv = w(m + "conv1d.bias")[None, :]
                for j in range(k):
                    src = pos - (k - 1) + j                      # input index
                    seen = (src >= 0) & ~((pos >= cut) & (src < cut))
                    conv = conv + jnp.where(
                        seen[:, None], u[jnp.clip(src, 0)], 0.0) * taps[:, j]
                u = jax.nn.silu(conv)
                dbc = mm(u, w(m + "x_proj.weight").T)
                dt = rms_norm(dbc[:, :rank], w(m + "dt_layernorm.weight"), eps)
                B = rms_norm(dbc[:, rank:rank + n],
                             w(m + "b_layernorm.weight"), eps)
                C = rms_norm(dbc[:, rank + n:],
                             w(m + "c_layernorm.weight"), eps)
                delta = jax.nn.softplus(
                    mm(dt, w(m + "dt_proj.weight").T) + w(m + "dt_proj.bias"))
                A = -jnp.exp(w(m + "A_log"))                      # [c, n]

                def step(carry, xs):
                    s, snap = carry
                    d_t, b_t, c_t, u_t, at = xs
                    s = jnp.where(at == cut, 0.0, s)
                    s = kept(jnp.exp(d_t[:, None] * A) * s
                             + d_t[:, None] * b_t[None, :] * u_t[:, None])
                    return (s, jnp.where(at == snap_at, s, snap)), s @ c_t

                zero = jnp.zeros((c, n), jnp.float32)
                (_, snap), y = jax.lax.scan(step, (zero, zero),
                                            (delta, B, C, u, pos))
                states.append(snap)
                y = y + w(m + "D")[None, :] * u
                mixed = mm(y * jax.nn.silu(z), w(m + "out_proj.weight").T)
            x = x + mixed
            f = pre + "feed_forward."
            h = rms_norm(x, w(pre + "pre_ff_layernorm.weight"), eps)
            x = x + mm(jax.nn.silu(mm(h, w(f + "gate_proj.weight").T))
                       * mm(h, w(f + "up_proj.weight").T),
                       w(f + "down_proj.weight").T)
        x = rms_norm(x, w("model.final_layernorm.weight"), eps)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int64))]
        logits = mm(x, w("model.embed_tokens.weight").T)
    out = {"logits": np.asarray(logits)}
    if state_after is not None:
        out["states"] = np.asarray(jnp.stack(states))
    return out
