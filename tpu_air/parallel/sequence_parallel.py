"""Sequence-parallel (long-context) training step over a (data, sequence)
mesh.

The step runs entirely inside shard_map: activations are sequence-sharded
(each device holds L/P tokens of its batch rows), attention is ring
attention (ops/ring_attention.py — K/V rotate over the ``sequence`` axis via
ppermute/ICI), RoPE and the causal mask use global positions derived from
the shard index, and the loss/grad reductions psum over BOTH axes so the
replicated parameters take an identical update everywhere.

This is the all-to-all-free long-context recipe: context length scales
linearly with the ``sequence`` mesh axis while per-device attention memory
stays O((L/P)^2) and gradient sync stays a single psum — the capability the
reference caps at 512 tokens (NLP_workloads/Anyscale_job/utils.py:23-28).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_air.models.lm import (
    CausalLM,
    LMConfig,
    head_weight,
    lm_chunked_loss_with_targets,
)
from tpu_air.parallel.mesh import make_mesh, visible_devices
from tpu_air.parallel.shardmap_compat import shard_map_unchecked as _shard_map


def make_sp_mesh(n_devices: int = None, dp: int = None, sp: int = None) -> Mesh:
    """(data, sequence) mesh over this process's VISIBLE (lease-aware)
    devices — a chip-leased trial builds its sub-mesh, never the whole slice.
    Default sp: the largest divisor of the device count that is <= 4."""
    devs = visible_devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if sp is None:
        sp = max(d for d in range(1, min(4, n) + 1) if n % d == 0)
    if dp is None:
        if n % sp != 0:
            raise ValueError(f"sp={sp} does not divide {n} devices")
        dp = n // sp
    return make_mesh(("data", "sequence"), (dp, sp), devices=devs)


def shift_targets(input_ids: jax.Array, pad_token_id: int) -> jax.Array:
    """GLOBAL next-token shift, done before sharding: position i's target is
    token i+1 (final position gets pad → masked), so a sequence-sharded loss
    never needs its neighbor's first token."""
    return jnp.concatenate(
        [input_ids[:, 1:],
         jnp.full((input_ids.shape[0], 1), pad_token_id, input_ids.dtype)],
        axis=1,
    )


def sp_local_loss(model, params, input_ids, targets, seq_axis: str = "sequence"):
    """The per-shard unnormalized loss every SP consumer shares (train step
    and eval): global RoPE positions from the shard index, hidden states via
    ``return_hidden``, and the CHUNKED lm-head CE
    (lm_chunked_loss_with_targets) so the local (B, L/P, V) logits never
    materialize — blockwise attention fixes one long-context memory cliff,
    this fixes the other.  Returns local (sum, count)."""
    li = input_ids.shape[1]  # local shard length
    offset = jax.lax.axis_index(seq_axis) * li
    positions = jnp.broadcast_to(
        offset + jnp.arange(li, dtype=jnp.int32), input_ids.shape
    )
    hidden = model.apply({"params": params}, input_ids, positions,
                         return_hidden=True)
    return lm_chunked_loss_with_targets(
        hidden, head_weight(params, model.config), targets,
        model.config.pad_token_id,
    )


def make_sp_train_step(
    config: LMConfig,
    mesh: Mesh,
    tx: optax.GradientTransformation,
    data_axis: str = "data",
    seq_axis: str = "sequence",
):
    """Returns (jitted_step, model).  ``jitted_step(params, opt_state,
    input_ids, targets) -> (params, opt_state, loss)`` with input_ids /
    targets sharded P(data, sequence) and params/opt_state replicated."""
    cfg = LMConfig.from_dict({**config.to_dict(), "sequence_axis": seq_axis})
    model = CausalLM(cfg)

    def local_step(params, opt_state, input_ids, targets):
        # Differentiate the LOCAL unnormalized loss and reduce outside the
        # grad: putting psum inside loss_fn is wrong under shard_map's
        # unchecked-replication mode, where psum's transpose psums the
        # cotangent again (a P-factor error).  loss = S_total / C_total with
        # C independent of params, so grad = psum(dS_local) / C_total.
        def loss_fn(p):
            return sp_local_loss(model, p, input_ids, targets, seq_axis)

        (s_local, c_local), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        c_total = jnp.maximum(jax.lax.psum(c_local, (data_axis, seq_axis)), 1.0)
        loss = jax.lax.psum(s_local, (data_axis, seq_axis)) / c_total
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, (data_axis, seq_axis)) / c_total, grads
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    repl, dsh = P(), P(data_axis, seq_axis)
    step = _shard_map(
        local_step, mesh=mesh,
        in_specs=(repl, repl, dsh, dsh),
        out_specs=(repl, repl, repl),
    )
    return jax.jit(step, donate_argnums=(0, 1)), model


def init_sp_params(config: LMConfig, mesh: Mesh, seed: int = 0):
    """Replicated param init (single-device trace; placed replicated)."""
    model = CausalLM(LMConfig.from_dict({**config.to_dict(),
                                         "sequence_axis": None}))
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    return jax.device_put(params, NamedSharding(mesh, P()))


def shard_batch(mesh: Mesh, input_ids, targets, data_axis="data", seq_axis="sequence"):
    sh = NamedSharding(mesh, P(data_axis, seq_axis))
    return jax.device_put(input_ids, sh), jax.device_put(targets, sh)
