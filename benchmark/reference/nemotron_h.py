"""The benchmark's own copy of ``tpu_air/models/lm/reference_nemotron_h.py`` (PR 47),
kept here so that a later change to the program cannot move the yardstick.

A plain reference of the published Nemotron-H forward pass (``model_type:
nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B): straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.
The Mamba-2 recurrence POSITION BY POSITION (no block form), no cache, no
batching, no kernel, no chunks; a Python loop over layers, attention heads
and experts; the published tensor names and layouts.  The parts of a layer
are compiled one by one (:func:`_parts`), not the model as a whole.  What the
system computes (``CausalLM`` through chunked prefill in the block form, the
paged decode step and the mixed step, the state carried in the engine's
cache) is held to this.

Every layer ``i`` is ONE thing, by ``hybrid_override_pattern[i]``
(``modeling_nemotron_h``)::

    x = x + Mixer_i(RMSNorm(x));   after the last: norm_f, then lm_head

    M, Mamba-2:  [z | xBC | dt] = W_in h       (d_inner | d_inner + 2 G N | H)
       xBC = silu(conv1d_causal_depthwise(xBC) + b);  [u | B | C] = xBC
       u as [H, P], B and C as [G, N], head h reads group h // (H / G)
       delta = softplus(dt + dt_bias);  A = -exp(A_log)            a head
       S_t[h] = exp(delta_t[h] A[h]) S_{t-1}[h] + delta_t[h] u_t[h] (x) B_t[g(h)]
       y_t[h] = S_t[h] C_t[g(h)] + D[h] u_t[h]
       y = RMSNorm over each of the G groups of d_inner / G channels of
           (y * silu(z)), times w;   out = W_out y
    *, attention:  q = W_q h (heads x d), k = W_k h, v = W_v h (kv heads x d);
       no bias, NO position encoding; causal softmax(q k^T / sqrt(d)); a K/V
       head serves heads / kv_heads query heads; W_o
    E, LatentMoE:  s = sigmoid(W_r h) in float32;  the num_experts_per_tok
       largest s + b;  w_e = routed_scaling_factor * s_e / (sum of the chosen
       s + 1e-20);  l = W_down h  (hidden -> moe_latent_size)
       routed = W_up sum_e w_e W2_e relu(W1_e l)^2;   out = routed +
       W2_s relu(W1_s h)^2   (the shared expert takes the FULL hidden state)

Departures from the published code, all of them: (1) every expert is applied
to every position and weighted by ``w_e`` or 0, instead of gathering each
expert's positions: the same sum, and no shape depends on the routing;
(2) ``n_group`` 1 and ``topk_group`` 1 are assumed (one group: nothing is
limited); ties in the top-k go to the lower index; (3) ``held = (first,
count)``: only the routed experts ``first .. first + count`` are computed
(the share of ONE expert-parallel rank) and ``W_up`` is applied to that
partial sum; an assignment to any other expert adds nothing, and the partial
sum goes on to the next layer, as the rank's program has it.  Default: all of
them; (4) no run-time clamp of the step size (``time_step_limit`` is not in
the configuration), no bias anywhere but the convolution's, no norm on the
latent pair; (5) the multi-token module (``num_nextn_predict_layers``) is not
run: the next-token logits do not depend on it; (6) one sequence at a time,
so there is no padding mask; (7) the recurrence is the plain sequential one
over positions, all heads of a layer side by side in one ``lax.scan``, not
the published chunked kernel: the same equations.

``weights`` is ``get(published tensor name) -> array``; ``cfg`` the published
``config.json`` as a dict (``n_routed_experts``: what the router scores).
``round_inputs`` (applied to both inputs of every matrix product, with
``rounded_precision`` the precision of those products), ``drop_state_at`` (a
position at which every Mamba layer forgets: state and convolution inputs
before it read as zero) and ``round_state`` (applied to the state every Mamba
layer carries, after every position) exist so that a caller can compute what
a LOWER precision, a system that loses the carried state between two chunks,
or one that keeps the state in fewer bits than the configuration states,
would give, and see that its tolerance tells them apart.  ``state_after``
asks for the state every Mamba layer carries after that many positions as
well (``"states"``), for a caller that compares the carried state itself.
"""

from __future__ import annotations

import functools
import json
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def relu2(x: Array) -> Array:
    return jnp.square(jnp.maximum(x, 0.0))


def route(cfg: Dict[str, Any], logits: Array, bias: Array,
          held: Optional[Tuple[int, int]] = None) -> Tuple[Array, Array]:
    """``(weights [T, E], gap [T])``: ``weights`` is ``w_e`` for the token's
    experts and 0 elsewhere; ``gap`` how close the choice is to a tie: the
    k-th minus the (k+1)-th selection score.  With ``held = (first, count)``
    it is taken as the rank that holds experts ``first .. first + count``
    sees it: how far its lowest chosen expert lies over the best expert not
    chosen, and its best expert not chosen under the k-th: closer pairs of
    experts held elsewhere may change places and move this rank's sum by the
    renormalisation alone."""
    t, e = logits.shape
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits)
    pick = s + bias
    # a stable order: larger first, lower index first among equals
    order = jnp.argsort(-pick, -1, stable=True)
    chosen = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], order[:, :k]].set(True)
    top = jnp.take_along_axis(pick, order, -1)
    gap = top[:, k - 1] - top[:, k]
    if held is not None:
        own = (jnp.arange(e) >= held[0]) & (jnp.arange(e) < sum(held))
        lowest_in = jnp.where(chosen & own, pick, jnp.inf).min(-1)
        best_out = jnp.where(~chosen & own, pick, -jnp.inf).max(-1)
        gap = jnp.minimum(lowest_in - top[:, k], top[:, k - 1] - best_out)
    w = jnp.where(chosen, s, 0.0)
    w = cfg["routed_scaling_factor"] * w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, gap


def forward(weights: Callable[[str], Any], cfg: Dict[str, Any],
            ids: Sequence[int], rows: Optional[Sequence[int]] = None,
            held: Optional[Tuple[int, int]] = None,
            layer_outputs: bool = False, **how) -> Dict[str, np.ndarray]:
    """Logits of one sequence.  Returns ``{"logits": [len(rows), V],
    "router_gap": [T]}`` (``rows``: the positions whose logits are wanted,
    default all; ``router_gap[t]``: the smallest :func:`route` gap over the
    ``E`` layers at position ``t``, as the rank ``held`` sees it); with
    ``state_after`` also ``"states": [Mamba layers, H, P, N]``; with
    ``layer_outputs`` ``"layers"``: each layer's ``[T, D]`` output before the
    residual is added (for an ``E`` layer the routed part over the held
    experts, ``"shared"`` holding the shared expert's beside it).  ``how``:
    ``round_inputs``, ``rounded_precision``, ``drop_state_at``,
    ``round_state``, ``state_after`` (module doc)."""
    return forward_each(weights, cfg, [{"ids": ids, "rows": rows, **how}],
                        held=held, layer_outputs=layer_outputs)[0]


def forward_each(weights: Callable[[str], Any], cfg: Dict[str, Any],
                 jobs: Sequence[Dict[str, Any]],
                 held: Optional[Tuple[int, int]] = None,
                 layer_outputs: bool = False) -> List[Dict[str, np.ndarray]]:
    """:func:`forward` of several sequences, each what :func:`forward` alone
    gives: ``jobs`` holds for each its own ``ids`` and, where wanted,
    ``rows`` and the readings' arguments.  The sequences do not see one
    another (a loop over them inside each part of a layer); what they share
    is the FETCH: a published tensor is asked for and moved to the device
    once for all of them."""
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("only one group of experts is implemented")
    w = lambda name: jnp.asarray(weights(name))  # noqa: E731
    held = held and tuple(held)
    part = _parts(json.dumps(cfg, sort_keys=True), held)
    first, count = held or (0, cfg["n_routed_experts"])
    embedding = np.asarray(weights("backbone.embeddings.weight"))

    class Seq:
        def __init__(self, ids, rows=None, round_inputs=None,
                     rounded_precision=None, drop_state_at=None,
                     round_state=None, state_after=None):
            self.r = round_inputs and (round_inputs, rounded_precision)
            ids = np.asarray(ids, np.int64)
            self.rows = np.asarray(
                np.arange(len(ids)) if rows is None else rows, np.int64)
            self.x = jnp.asarray(embedding[ids]).astype(jnp.float32)
            self.gap = jnp.full((len(ids),), jnp.inf, jnp.float32)
            self.cut = jnp.int32(len(ids) if drop_state_at is None
                                 else int(drop_state_at))
            self.kept = round_state
            self.want_state = state_after is not None
            self.snap_at = jnp.int32(
                -1 if state_after is None else int(state_after) - 1)
            self.states, self.layers, self.shared = [], [], []

    seqs = [Seq(**job) for job in jobs]
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(
                cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]):
            pre = f"backbone.layers.{i}."
            m = pre + "mixer."
            norm = w(pre + "norm.weight")
            if kind == "M":
                names = ("in_proj.weight", "conv1d.weight", "conv1d.bias",
                         "dt_bias", "A_log", "D", "norm.weight",
                         "out_proj.weight")
                ws = [w(m + name) for name in names]
                for s in seqs:
                    out, state = part.mamba(s.r, s.kept, s.x, norm, s.cut,
                                            s.snap_at, *ws)
                    if s.want_state:
                        s.states.append(np.asarray(state))
                    s.out = out
                del ws
            elif kind == "*":
                q, k, v, o = (w(f"{m}{p}_proj.weight") for p in "qkvo")
                for s in seqs:
                    qkv = part.qkv(s.r, s.x, norm, q, k, v)
                    att = [part.head(s.r, h, *qkv)
                           for h in range(cfg["num_attention_heads"])]
                    s.out = part.attention_out(s.r, att, o)
                    del qkv, att
                del q, k, v, o
            elif kind == "E":
                router = w(m + "gate.weight")
                bias = w(m + "gate.e_score_correction_bias")
                down, up = (w(m + "fc1_latent_proj.weight"),
                            w(m + "fc2_latent_proj.weight"))
                for s in seqs:
                    s.n, s.lat, s.weight, s.gap = part.route(
                        s.r, s.x, norm, router, bias, down, s.gap)
                    s.y = jnp.zeros_like(s.lat)
                for e in range(first, first + count):
                    two = [w(f"{m}experts.{e}.{p}_proj.weight")
                           for p in ("up", "down")]
                    for s in seqs:
                        s.y = part.expert(s.r, s.y, s.lat, s.weight, e, *two)
                    del two
                shared = [w(f"{m}shared_experts.{p}_proj.weight")
                          for p in ("up", "down")]
                for s in seqs:
                    s.out = part.project(s.r, s.y, up)
                    s.also = part.relu2_mlp(s.r, s.n, *shared)
                    del s.n, s.lat, s.weight, s.y
                del shared, router, bias, down, up
            else:
                raise ValueError(f"layer kind {kind!r} in the pattern")
            for s in seqs:
                if layer_outputs:
                    s.layers.append(np.asarray(s.out))
                if kind == "E":
                    if layer_outputs:
                        s.shared.append(np.asarray(s.also))
                    s.out = part.add(s.out, s.also)
                s.x = part.add(s.x, s.out)
                s.out = s.also = None
        norm = w("backbone.norm_f.weight")
        head = w("lm_head.weight")
        out = []
        for s in seqs:
            logits = part.logits(s.r, s.x, norm, jnp.asarray(s.rows), head)
            got = {"logits": np.asarray(logits),
                   "router_gap": np.asarray(s.gap)}
            if s.want_state:
                got["states"] = np.stack(s.states)
            if layer_outputs:
                got["layers"], got["shared"] = s.layers, s.shared
            out.append(got)
    return out


@functools.lru_cache(maxsize=4)
def _parts(cfg_json: str, held: Optional[Tuple[int, int]]):
    """The parts of a layer as :func:`forward_each` calls them, each ONE
    compiled program (``jax.jit``) for a configuration; the loops over
    layers, attention heads and experts stay in Python (a head and an expert
    are the same program with another index).  ``r`` (a sequence's
    ``round_inputs`` with its ``rounded_precision``, or ``None``) and
    ``kept`` (its ``round_state``) are static: the sensitivity readings
    compile their own parts.  Every weight comes in as stored and is raised
    to float32 inside."""
    cfg = json.loads(cfg_json)
    eps = cfg.get("layer_norm_epsilon", cfg.get("norm_eps", 1e-5))
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    c = H * P
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    static = lambda *n: functools.partial(  # noqa: E731
        jax.jit, static_argnums=n)

    def mm(r, a, b):
        if r is None:
            return jnp.matmul(a, b)
        rounded, precision = r
        return jnp.matmul(rounded(a), rounded(b), precision=precision)

    @static(0, 1)
    def mamba(r, kept, x, norm, cut, snap_at, w_in, conv_w, conv_b, dt_bias,
              a_log, d_skip, gate_norm, w_out):
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rms_norm(x, f32(norm), eps)
        zxd = mm(r, h, f32(w_in).T)
        z, xbc, dt = zxd[:, :c], zxd[:, c:2 * c + 2 * G * N], zxd[:, -H:]
        taps = f32(conv_w)[:, 0, :]                        # [conv_dim, K]
        conv = f32(conv_b)[None, :]
        for j in range(K):
            src = pos - (K - 1) + j                        # input index
            seen = (src >= 0) & ~((pos >= cut) & (src < cut))
            conv = conv + jnp.where(
                seen[:, None], xbc[jnp.clip(src, 0)], 0.0) * taps[:, j]
        xbc = jax.nn.silu(conv)
        u = xbc[:, :c].reshape(t, H, P)
        B = jnp.repeat(xbc[:, c:c + G * N].reshape(t, G, N), H // G, axis=1)
        C = jnp.repeat(xbc[:, c + G * N:].reshape(t, G, N), H // G, axis=1)
        delta = jax.nn.softplus(dt + f32(dt_bias))         # [t, H]
        A = -jnp.exp(f32(a_log))                           # [H]
        keep = kept or (lambda a: a)

        def step(carry, xs):
            s, snap = carry
            d_t, b_t, c_t, u_t, at = xs
            s = jnp.where(at == cut, 0.0, s)
            s = keep(jnp.exp(d_t * A)[:, None, None] * s
                     + (d_t[:, None] * u_t)[:, :, None] * b_t[:, None, :])
            y = (s * c_t[:, None, :]).sum(-1)              # [H, P]
            return (s, jnp.where(at == snap_at, s, snap)), y

        zero = jnp.zeros((H, P, N), jnp.float32)
        (_, snap), y = jax.lax.scan(step, (zero, zero),
                                    (delta, B, C, u, pos))
        y = (y + f32(d_skip)[None, :, None] * u).reshape(t, c)
        y = (y * jax.nn.silu(z)).reshape(t, G, c // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        y = y.reshape(t, c) * f32(gate_norm)
        return mm(r, y, f32(w_out).T), snap

    @static(0)
    def qkv(r, x, norm, q, k, v):
        t = x.shape[0]
        h = rms_norm(x, f32(norm), eps)
        return (mm(r, h, f32(q).T).reshape(t, heads, hd),
                mm(r, h, f32(k).T).reshape(t, kv_heads, hd),
                mm(r, h, f32(v).T).reshape(t, kv_heads, hd))

    @static(0)
    def head(r, hq, q, k, v):
        pos = jnp.arange(q.shape[0])
        causal = pos[:, None] >= pos[None, :]
        g = hq // (heads // kv_heads)
        s = mm(r, q[:, hq], k[:, g].T) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return mm(r, jax.nn.softmax(s, -1), v[:, g])

    @static(0)
    def attention_out(r, att, o):
        return mm(r, jnp.concatenate(att, -1), f32(o).T)

    @static(0)
    def route_(r, x, norm, router, bias, down, gap):
        # the router is float32 in the published model whatever the
        # precision of the rest: it is never rounded here
        n = rms_norm(x, f32(norm), eps)
        weight, g = route(cfg, jnp.matmul(n, f32(router).T), f32(bias), held)
        return n, mm(r, n, f32(down).T), weight, jnp.minimum(gap, g)

    def relu2_mlp(r, n, up, down):
        return mm(r, relu2(mm(r, n, f32(up).T)), f32(down).T)

    @static(0)
    def expert(r, y, lat, weight, e, up, down):
        return y + weight[:, e][:, None] * relu2_mlp(r, lat, up, down)

    @static(0)
    def project(r, x, w):
        return mm(r, x, f32(w).T)

    @static(0)
    def logits(r, x, norm, rows, head_w):
        return mm(r, rms_norm(x, f32(norm), eps)[rows], f32(head_w).T)

    @jax.jit
    def add(a, b):
        return a + b

    return SimpleNamespace(
        mamba=mamba, qkv=qkv, head=head, attention_out=attention_out,
        route=route_, expert=expert, relu2_mlp=static(0)(relu2_mlp),
        project=project, logits=logits, add=add)
