"""``parallel/moe.py:routed_experts``: one chip's share of a top-k layer of
SwiGLU experts against a plain loop over the experts, under a skewed router
(nothing dropped), whether or not the rows routed fit its row buffer; the
shares add up to the uncut layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

TOKENS, D, F, EXPERTS, TOP_K = 256, 32, 48, 16, 4


def _layer(seed=0, skew=0.0, skewed=(1,)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (TOKENS, D))
    router = jax.random.normal(ks[1], (D, EXPERTS)) * D ** -0.5
    if skew:
        # The skewed experts' logits gain a constant: they are in most
        # tokens' top-k.
        x = x.at[:, 0].set(1.0)
        router = router.at[0, jnp.asarray(skewed)].add(skew)
    w_gate, w_up = (jax.random.normal(k, (EXPERTS, D, F)) * D ** -0.5
                    for k in ks[2:4])
    w_down = jax.random.normal(ks[4], (EXPERTS, F, D)) * F ** -0.5
    return x, router, w_gate, w_up, w_down


def _loop(x, router, w_gate, w_up, w_down, first=0, renormalize=True):
    """The layer as its equations read: every held expert computes every
    token, and a token not routed to it weighs zero."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    weights, chosen = jax.lax.top_k(probs, TOP_K)
    if renormalize:
        weights = weights / weights.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for i in range(w_gate.shape[0]):
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), axis=-1)
        h = jax.nn.silu(x @ w_gate[i]) * (x @ w_up[i])
        y = y + mine[:, None] * (h @ w_down[i])
    return y


def _held(kernels, first, held):
    return tuple(k[first:first + held] for k in kernels)


@pytest.fixture(autouse=True)
def whole_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("capacity_factor,skewed,rows,fit", [
    (2.0, (1,), 512, True), (2.0, (1, 2), 512, False),
    (4.0, (1, 2), 1024, True), (1.5, (1,), 384, False),
    (0.5, (1,), 128, False), (1.0, (), 256, None)],
    ids=["fits", "two-busy-experts-in-two-parts", "every-row-fits",
         "four-parts-since-three-divide-no-tokens", "eight-parts",
         "an-even-router-on-the-brim"])
def test_a_skewed_router_drops_nothing(capacity_factor, skewed, rows, fit):
    """An expert (or two) takes nearly every token.  Values and gradients
    (tokens, router, the three kernels) against the loop, whether the rows
    routed here fit the buffer or are walked in parts."""
    x, router, *kernels = _layer(skew=6.0 if skewed else 0.0, skewed=skewed)
    first, held = 0, 4
    mine = _held(kernels, first, held)
    assert moe.row_buffer(TOKENS, TOP_K, held, EXPERTS,
                          capacity_factor) == rows

    def layer(x, router, *k):
        return moe.routed_experts(x, router, *k, top_k=TOP_K,
                                  first_expert=first,
                                  capacity_factor=capacity_factor)

    y, routing = layer(x, router, *mine)
    load = np.asarray(routing.load)
    for busy in skewed:
        assert load[busy] >= 0.9 * TOKENS, load
    if skewed == (1,):
        assert load[1] >= 0.45 * load.sum(), load
    assert fit is None or (load.sum() <= rows) == fit, load.sum()
    np.testing.assert_allclose(y, _loop(x, router, *mine, first=first),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2),
                   argnums=(0, 1, 2, 3, 4))(x, router, *mine)
    want = jax.grad(lambda *a: jnp.sum(_loop(*a, first=first) ** 2),
                    argnums=(0, 1, 2, 3, 4))(x, router, *mine)
    for name, a, b in zip(("x", "router", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("skewed", [(1,), (1, 2)], ids=["fits", "in-parts"])
def test_both_sides_inside_a_jitted_shard_map_step(skewed):
    """As a model calls it: under ``jit``, ``shard_map`` over ``hvd`` with
    ``check_vma`` and ``value_and_grad``, the side chosen while the step
    runs."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    x, router, *kernels = _layer(skew=6.0, skewed=skewed)
    mine = _held(kernels, 0, 4)

    def loss(layer):
        return lambda *a: jnp.sum(layer(*a) ** 2)

    def step(*a):
        value, grads = jax.value_and_grad(loss(lambda *a: moe.routed_experts(
            *a, top_k=TOP_K, capacity_factor=2.0)[0]),
            argnums=(0, 1, 2, 3, 4))(*a)
        return jax.lax.psum(value, "hvd"), grads

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("hvd",))
    got = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("hvd"), P(), P(), P(), P()),
        out_specs=(P(), (P("hvd"), P(), P(), P(), P()))))(x, router, *mine)
    want = jax.value_and_grad(loss(_loop), argnums=(0, 1, 2, 3, 4))(
        x, router, *mine)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(("x", "router", "gate", "up", "down"), got[1],
                          want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("products", ["ragged_dot", "kernels"])
@pytest.mark.parametrize("skewed", [(1,), (1, 2)], ids=["fits", "in-parts"])
def test_the_buffer_s_tail_reaches_nothing(monkeypatch, request, skewed,
                                            products):
    """The row buffer's rows past the routed ones are in no group: set to
    NaN just before the products, they touch neither ``y`` nor a gradient,
    by ``ragged_dot`` (what a CPU runs) and by the Pallas kernels (what a TPU
    runs; interpreted here).  Before PR 35 the last expert took the tail as
    rows of its own."""
    from horovod_tpu.ops.grouped_matmul import grouped_dot

    def traced_anew():
        # The layer's passes are traced once a process for a shape: what a
        # test puts in their way is seen by a new trace only, and must not
        # stay behind in the cache.
        moe._forward.clear_cache()
        moe._backward.clear_cache()

    x, router, *kernels = _layer(skew=6.0, skewed=skewed)
    mine = _held(kernels, 0, 4)
    request.addfinalizer(traced_anew)
    if products == "kernels":
        monkeypatch.setattr(moe, "grouped_dot", functools.partial(
            grouped_dot, interpret=True))
        traced_anew()

    def value_and_grads(*a):
        return jax.value_and_grad(lambda *a: jnp.sum(moe.routed_experts(
            *a, top_k=TOP_K, capacity_factor=2.0)[0] ** 2),
            argnums=(0, 1, 2, 3, 4))(*a)

    want = value_and_grads(x, router, *mine)
    swiglu_rows, tails = moe._swiglu_rows, []

    def poisoned(rows, group_sizes, *k):
        past = jnp.arange(rows.shape[0]) >= jnp.sum(group_sizes)
        tails.append(rows.shape[0])
        return swiglu_rows(jnp.where(past[:, None], jnp.nan, rows),
                           group_sizes, *k)

    monkeypatch.setattr(moe, "_swiglu_rows", poisoned)
    traced_anew()
    got = value_and_grads(x, router, *mine)
    assert tails                                  # the hook was on the path
    for name, a, b in zip(("y", "x", "router", "gate", "up", "down"),
                          jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("held", [2, 4, 16])
def test_the_shares_add_up(held):
    """The shares' outputs of one layer (16 experts over 8, 4 or 1 chips)
    sum to the uncut layer: routing, top-k and the renormalisation are over
    all experts on every chip, each adds its own experts' part and no
    other."""
    x, router, *kernels = _layer(seed=1)
    whole = _loop(x, router, *kernels)
    parts, loads = 0.0, []
    for first in range(0, EXPERTS, held):
        y, routing = moe.routed_experts(
            x, router, *_held(kernels, first, held), top_k=TOP_K,
            capacity_factor=2.0, first_expert=first)
        parts, loads = parts + y, loads + [np.asarray(routing.load)]
        np.testing.assert_allclose(
            y, _loop(x, router, *_held(kernels, first, held), first=first),
            rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)
    # every (token, choice) went to exactly one chip
    assert np.concatenate(loads).sum() == TOKENS * TOP_K


@pytest.mark.parametrize("renormalize", [True, False])
def test_weights_come_from_the_router_over_all_experts(renormalize):
    x, router, *kernels = _layer(seed=2)
    y, routing = moe.routed_experts(
        x, router, *_held(kernels, 4, 4), top_k=TOP_K, capacity_factor=2.0,
        first_expert=4, renormalize=renormalize)
    np.testing.assert_allclose(
        y, _loop(x, router, *_held(kernels, 4, 4), first=4,
                 renormalize=renormalize), rtol=1e-5, atol=1e-5)
    total = np.asarray(routing.weights).sum(-1)
    assert (np.allclose(total, 1.0, atol=1e-6) if renormalize
            else (total < 1.0).all())
    assert routing.probs.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(routing.probs).sum(-1), 1.0,
                               atol=1e-6)


def test_bfloat16_rows_route_in_float32():
    """Activations in bfloat16: the router's arithmetic stays float32 (the
    probabilities are those of the rounded input computed whole), and the
    output comes back in the activations' dtype."""
    x, router, *kernels = _layer(seed=3)
    low = x.astype(jnp.bfloat16)
    y, routing = moe.routed_experts(low, router, *_held(kernels, 0, 4),
                                    top_k=TOP_K, capacity_factor=2.0)
    assert y.dtype == jnp.bfloat16 and routing.probs.dtype == jnp.float32
    np.testing.assert_allclose(
        routing.probs, jax.nn.softmax(low.astype(jnp.float32) @ router, -1),
        rtol=1e-5, atol=1e-7)
    want = _loop(low.astype(jnp.float32), router, *_held(kernels, 0, 4))
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))) < 0.05 * float(
        jnp.max(jnp.abs(want)))


def test_the_row_buffer():
    # an even router sends tokens x top_k x held / experts rows here
    assert moe.row_buffer(16384, 8, 16, 128, 2.0) == 32768
    assert moe.row_buffer(16384, 8, 16, 128, 2.25) == 36864
    assert moe.row_buffer(16384, 8, 16, 128, 1.001) == 16384 + 128
    # never more than every token's min(top_k, held) choices
    assert moe.row_buffer(16384, 8, 16, 128, 9.0) == 131072
    assert moe.row_buffer(256, 4, 2, 16, 8.0) == 512
    assert moe.row_buffer(256, 4, 16, 16, 2.0) == 1024
