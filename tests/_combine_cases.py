"""The routed experts' sum over a token's choices (``ops/moe.py``, the scope
``moe_combine``), as cases over any kind of expert: ``tests/test_olmoe.py``
(every expert held, gated), ``tests/test_gigachat.py`` (a share held, gated)
and ``tests/test_nemotron_h.py`` (two-matrix experts) each run them.

A routing is ``fn(rng) -> chosen [T, K]`` with ids in ``[0, E]``; the id ``E``
is an expert held elsewhere."""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_air.ops import moe

T, K, E, D, F = 32, 4, 5, 128, 24


def _some_elsewhere(rng):
    # four times as many experts routed over as held, distinct a token
    return np.minimum(
        np.stack([rng.permutation(4 * E)[:K] for _ in range(T)]), E)


def _several_to_this_rank(rng):
    # token i sends i % (K + 1) of its choices here, to distinct experts, in
    # any position among those that go elsewhere
    chosen = np.full((T, K), E)
    for i in range(T):
        here = rng.permutation(K)[:i % (K + 1)]
        chosen[i, here] = rng.permutation(E)[:len(here)]
    return chosen


ROUTINGS = {
    "none_elsewhere": lambda rng: np.stack(
        [rng.permutation(E)[:K] for _ in range(T)]),
    "some_elsewhere": _some_elsewhere,
    "all_elsewhere": lambda rng: np.full((T, K), E),
    "several_to_this_rank": _several_to_this_rank,
}
WHOLE = ["none_elsewhere"]
PARTIAL = ["some_elsewhere", "all_elsewhere", "several_to_this_rank"]


def loop_over_experts(x, chosen, w, gate, up, down):
    """The published sum an expert at a time over ALL tokens, no sort and no
    grouped product: differentiable, float32."""
    out = jnp.zeros((x.shape[0], down.shape[-1]), jnp.float32)
    for ex in range(up.shape[0]):
        hid = (jnp.square(jax.nn.relu(x @ up[ex])) if gate is None
               else jax.nn.silu(x @ gate[ex]) * (x @ up[ex]))
        share = jnp.where(chosen == ex, w, 0.0).sum(1)
        out = out + share[:, None] * (hid @ down[ex])
    return out


def _operands(routing, gated, seed=11):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)  # noqa: E731
    chosen = jnp.asarray(ROUTINGS[routing](rng), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1, (T, K)), jnp.float32)
    return (f(T, D) * 10, chosen, w, f(E, D, F) if gated else None,
            f(E, D, F), f(E, F, D))


def against_the_loop(routing, gated):
    """``expert_ffn`` and its gradient with respect to the rows and the
    weights against the loop's."""
    x, chosen, w, gate, up, down = _operands(routing, gated)
    with jax.default_matmul_precision("highest"):
        got = moe.expert_ffn(x, chosen, w, gate, up, down)
        want = loop_over_experts(x, chosen, w, gate, up, down)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        if routing == "all_elsewhere":
            assert not np.asarray(got).any()
        probe = jnp.asarray(
            np.random.default_rng(3).standard_normal((T, D)), jnp.float32)
        grads = [jax.grad(lambda x, w, fn=fn: (fn(x, chosen, w, gate, up,
                                                  down) * probe).sum(),
                          argnums=(0, 1))(x, w)
                 for fn in (moe.expert_ffn, loop_over_experts)]
    for g, want in zip(*grads):
        np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


def _sorted_products(routing, seed=5):
    """What the grouped product hands the sum: ``y [T*K, D]`` sorted by
    expert, the rows of no group NaN (the kernel never writes them), and the
    sum a loop over the assignments makes of it."""
    rng = np.random.default_rng(seed)
    chosen = ROUTINGS[routing](rng)
    flat = chosen.reshape(-1)
    order = np.argsort(flat, kind="stable")
    y = rng.standard_normal((T * K, D)).astype(np.float32)
    y[(flat < E).sum():] = np.nan
    w = rng.uniform(0.1, 1, (T, K)).astype(np.float32)
    want = np.zeros((T, D), np.float32)
    for row, a in enumerate(order):
        if flat[a] < E:
            want[a // K] += w[a // K, a % K] * y[row]
    return (jnp.asarray(y), jnp.asarray(flat, jnp.int32),
            jnp.asarray(order, jnp.int32), jnp.asarray(w)), want


FORMS = {
    "gathered": moe.gathered_sum,
    "kernel": lambda *a: moe.held_rows_sum(*a, True),     # interpret mode
}


def rows_of_no_group(routing, form):
    """Either form of the sum over products whose unvisited rows are NaN:
    finite, the loop's sum, and exactly zero for a token all of whose
    choices went elsewhere."""
    args, want = _sorted_products(routing)
    assert moe.combine_tile(T, T * K, D) == D
    got = np.asarray(FORMS[form](*args, E))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    elsewhere = np.asarray(args[1]).reshape(T, K).min(1) == E
    assert not got[elsewhere].any()
    if routing == "all_elsewhere":
        assert elsewhere.all()


def kernel_gradient(routing):
    """The kernel is differentiated as the gathered form is."""
    (y, flat, order, w), _ = _sorted_products(routing)
    y = jnp.nan_to_num(y)
    grads = [jax.grad(lambda y, w, fn=fn: jnp.sin(fn(y, flat, order, w,
                                                     E)).sum(),
                      argnums=(0, 1))(y, w) for fn in FORMS.values()]
    for g, want in zip(*grads):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    n = int((np.asarray(flat) < E).sum())
    assert not np.asarray(grads[1][0])[n:].any()     # rows of no group: 0
