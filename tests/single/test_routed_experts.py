"""``parallel/moe.py:routed_experts``: one chip's share of a top-k layer of
SwiGLU experts against a plain loop over the experts, under a skewed router
(nothing dropped), whether or not the rows routed fit its row buffer; the
shares add up to the uncut layer; the two ops that walk the rows routed
(``take_rows``, ``add_rows``) against whole-buffer indexing, ``add_rows`` as
a CPU makes it (a scatter-add in a loop) and as a TPU does (a segment sum in
token order, its kernel interpreted here)."""

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


def _traced_anew():
    """The layer's passes are traced once a process for a shape: what a
    test puts in their way is seen by a new trace only, and must not stay
    behind in the cache."""
    moe._forward.clear_cache()
    moe._backward.clear_cache()


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


@pytest.mark.parametrize("chips", [1, 2])
@pytest.mark.parametrize("skewed", [(1,), (1, 2)], ids=["fits", "in-parts"])
def test_both_sides_inside_a_jitted_shard_map_step(skewed, chips):
    """As a model calls it: under ``jit``, ``shard_map`` over ``hvd`` with
    ``check_vma`` and ``value_and_grad``, the side chosen while the step
    runs.  What the forward keeps for the backward varies as its operands
    do: the tokens over the chips, the kernels not, and their gradients are
    the chips' sum."""
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

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("hvd",))
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
    from horovod_tpu.ops.grouped_matmul import grouped_dot, grouped_dot_grads

    traced_anew = _traced_anew
    x, router, *kernels = _layer(skew=6.0, skewed=skewed)
    mine = _held(kernels, 0, 4)
    request.addfinalizer(traced_anew)
    if products == "kernels":
        monkeypatch.setattr(moe, "grouped_dot", functools.partial(
            grouped_dot, interpret=True))
        monkeypatch.setattr(moe, "grouped_dot_grads", functools.partial(
            grouped_dot_grads, interpret=True))
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

    swiglu_rows_grads = moe._swiglu_rows_grads

    def poisoned_grads(rows, group_sizes, gate, up, scale, g, *k):
        past = (jnp.arange(rows.shape[0]) >= jnp.sum(group_sizes))[:, None]
        tails.append(-rows.shape[0])
        return swiglu_rows_grads(
            jnp.where(past, jnp.nan, rows), group_sizes, gate, up, scale,
            jnp.where(past, jnp.nan, g), *k)

    monkeypatch.setattr(moe, "_swiglu_rows", poisoned)
    monkeypatch.setattr(moe, "_swiglu_rows_grads", poisoned_grads)
    traced_anew()
    got = value_and_grads(x, router, *mine)
    assert min(tails) < 0 < max(tails)            # both hooks were traced
    for name, a, b in zip(("y", "x", "router", "gate", "up", "down"),
                          jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=name)


def _plain_reverse_mode(x, local, weights, *kernels):
    """The layer as it was before it kept anything: ``_held_part`` through a
    buffer of every row a router can send, differentiated by ``jax.vjp``."""
    worst = local.shape[0] * min(local.shape[1], kernels[0].shape[0])
    return moe._held_part(worst, x, local, weights, *kernels)[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("entry", ["routed_experts", "dispatch_experts"])
@pytest.mark.parametrize("top_k,held,capacity_factor,skewed,fit", [
    (4, 4, 2.0, (1,), True), (4, 4, 2.0, (1, 2), False),
    (1, 4, 2.0, (1,), False), (1, 4, 4.0, (1,), True),
    (4, 1, 2.0, (0,), False)],
    ids=["fits", "in-parts", "top-1-in-parts", "top-1-every-row-fits",
         "one-expert-held-in-parts"])
def test_the_kept_forward_gives_the_gradients_of_plain_reverse_mode(
        top_k, held, capacity_factor, skewed, fit, entry, dtype):
    """The backward that starts from the forward's sort, sizes, gate and up
    (and takes ``d weights`` from ``g . W_down^T``) against ``jax.vjp`` of
    ``_held_part``: tokens, weights or router, the three kernels; to
    rounding in float32, within the file's bound in bfloat16; where the
    rows fit, where they are walked in parts (nothing kept), and for a
    layer whose tokens have one row each."""
    x, router, *kernels = _layer(skew=6.0, skewed=skewed)
    x = x.astype(dtype)
    mine = _held(kernels, 0, held)
    renormalize = top_k > 1     # top-1 renormalised weighs 1: no gradient
    routing = moe.route(x, router, top_k, 0, held, renormalize)
    rows = moe.row_buffer(TOKENS, top_k, held, EXPERTS, capacity_factor)
    assert moe.forward_kept(int(routing.load.sum()), rows) == fit

    if entry == "routed_experts":
        def layer(x, router, *k):
            return moe.routed_experts(x, router, *k, top_k=top_k,
                                      capacity_factor=capacity_factor,
                                      renormalize=renormalize)[0]

        def plain(x, router, *k):
            r = moe.route(x, router, top_k, 0, held, renormalize)
            return _plain_reverse_mode(x, moe._local(r.experts, 0, held),
                                       r.weights, *k)
        second = router
    else:
        def layer(x, weights, *k):
            return moe.dispatch_experts(
                x, routing.experts, weights, *k, first_expert=0,
                experts_total=EXPERTS, capacity_factor=capacity_factor)

        def plain(x, weights, *k):
            return _plain_reverse_mode(
                x, moe._local(routing.experts, 0, held), weights, *k)
        second = routing.weights

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, second, *mine)

    (got_y, got), (want_y, want) = value_and_grads(layer), value_and_grads(
        plain)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5)
    for name, a, b in zip(("x", entry, "gate", "up", "down"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5 * np.abs(
                b).max(), err_msg=name)
        else:
            assert np.abs(a - b).max() < 0.05 * np.abs(b).max(), name


def _count(jaxpr, primitives, side):
    """How many equations of ``jaxpr`` are one of ``primitives``, under the
    side ``side`` of every ``cond`` (1: the rows fit) and in every other
    sub-jaxpr."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name in primitives
        for key, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            if eqn.primitive.name == "cond" and key == "branches":
                subs = (value[side],)
            for sub in subs:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _count(sub, primitives, side)
    return found


@pytest.mark.parametrize("capacity_factor,fits,in_parts", [
    (2.0, (9, 1), (12, 2)), (4.0, (9, 1), (9, 1))],
    ids=["a-buffer-smaller-than-the-worst", "every-row-fits"])
def test_a_fitting_backward_runs_no_product_and_no_sort_again(
        capacity_factor, fits, in_parts):
    """The traced forward and backward of one layer (off a TPU a grouped
    product is a ``ragged_dot``): where the rows fit, three products and a
    sort forward and six products backward, which were nine and a second
    sort while the backward made its forward again; in parts, as before:
    each part's forward is made again under ``jax.checkpoint``."""
    x, router, *kernels = _layer()
    mine = _held(kernels, 0, 4)
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(moe.routed_experts(
            *a, top_k=TOP_K, capacity_factor=capacity_factor)[0] ** 2),
        argnums=(0, 1, 2, 3, 4)))(x, router, *mine)
    products = {"ragged_dot", "ragged_dot_general"}
    for side, (dots, sorts) in ((1, fits), (0, in_parts)):
        assert _count(traced.jaxpr, products, side) == dots
        assert _count(traced.jaxpr, {"sort"}, side) == sorts


def test_the_kept_forward_under_a_block_s_checkpoint():
    """As ``models/zaya.py`` builds its block: ``nn.remat`` with
    ``save_only_these_names``, none of them the layer's, so that the block's
    backward makes the layer's forward again and hands what it kept to the
    layer's backward.  The same values and gradients as without."""
    import flax.linen as nn
    from horovod_tpu.ops.flash_attention import CHECKPOINT_NAMES

    x, router, *kernels = _layer(seed=5)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            mine = [self.param(name, lambda _, k=k: k[:4])
                    for name, k in zip(("gate", "up", "down"), kernels)]
            return x + moe.routed_experts(
                x, self.param("router", lambda _: router), *mine,
                top_k=TOP_K, capacity_factor=2.0)[0]

    def value_and_grads(module):
        params = module.init(jax.random.key(0), x)
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(module.apply(p, x) ** 2), argnums=(0, 1)))(
                params, x)

    kept = jax.checkpoint_policies.save_only_these_names(*CHECKPOINT_NAMES)
    got = value_and_grads(nn.remat(Layer, policy=kept)())
    want = value_and_grads(Layer())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


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


# ---------------------------------------------------------------------------
# The two row ops: the buffer's first n rows, in trips
# ---------------------------------------------------------------------------


def _rows_case(capacity, dtype, tokens=96, d=16, top_k=4):
    ks = jax.random.split(jax.random.PRNGKey(capacity), 3)
    x = jax.random.normal(ks[0], (tokens, d)).astype(dtype)
    rows = jax.random.normal(ks[1], (capacity, d)).astype(dtype)
    token = (jax.random.permutation(ks[2], tokens * top_k)[:capacity]
             // top_k).astype(jnp.int32)
    return x, rows, token


@pytest.fixture
def trips_of_64(monkeypatch):
    """Trips of 64 rows, so that a small buffer is several; no pass of the
    layer traced with another trip is left in the caches, before or after."""
    monkeypatch.setattr(moe, "WALK_ROWS", 64)
    _traced_anew()
    yield
    _traced_anew()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("capacity", [256, 300],
                         ids=["whole-tiles", "last-tile-overlaps"])
@pytest.mark.parametrize("n", [0, 1, 100, 128, None],
                         ids=["none", "one", "an-edge-inside-a-tile",
                              "a-whole-number-of-tiles", "the-full-buffer"])
def test_the_row_ops_are_indexing_on_the_first_n_rows(trips_of_64, n,
                                                      capacity, dtype):
    """``take_rows`` is ``x[token]`` on the first n rows and zeros past
    them, ``add_rows`` is ``zeros.at[token].add`` of the first n rows: the
    same bits (a token's terms are added in row order), for a buffer of
    whole tiles and for one whose last tile overlaps the one before it."""
    x, rows, token = _rows_case(capacity, jnp.dtype(dtype))
    n = capacity if n is None else n
    live = (jnp.arange(capacity) < n)[:, None]
    np.testing.assert_array_equal(
        moe.take_rows(x, token, jnp.int32(n)),
        jnp.where(live, x[token], jnp.zeros((), x.dtype)))
    np.testing.assert_array_equal(
        jax.jit(moe.add_rows, static_argnums=3)(rows, token, jnp.int32(n),
                                                x.shape[0]),
        jnp.zeros_like(x).at[token].add(jnp.where(live, rows,
                                                  jnp.zeros_like(rows))))
    assert moe.rows_walked(n, capacity) == min(capacity, -(-n // 64) * 64)


@pytest.mark.parametrize("n", [0, 100, 300])
def test_each_row_op_is_the_other_s_transpose(trips_of_64, n):
    """The cotangent of ``take_rows`` is ``add_rows`` of the cotangent and
    the other way round (a loop whose trips are counted while the step runs
    has no reverse mode of its own), and both are what indexing's are."""
    x, rows, token = _rows_case(300, jnp.float32)
    n, live = jnp.int32(n), (jnp.arange(300) < n)[:, None]
    _, take_vjp = jax.vjp(lambda x: moe.take_rows(x, token, n), x)
    _, index_vjp = jax.vjp(lambda x: jnp.where(live, x[token], 0.0), x)
    np.testing.assert_array_equal(take_vjp(rows)[0],
                                  moe.add_rows(rows, token, n, x.shape[0]))
    np.testing.assert_array_equal(take_vjp(rows)[0], index_vjp(rows)[0])
    _, add_vjp = jax.vjp(lambda r: moe.add_rows(r, token, n, x.shape[0]), rows)
    np.testing.assert_array_equal(add_vjp(x)[0], moe.take_rows(x, token, n))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [0, 1, 100, 300])
def test_a_token_with_one_row_gathers_it(trips_of_64, n, dtype):
    """``single``: no token has more than one of the n rows (top-1, or one
    expert held), and ``add_rows`` gathers where it would add: the same
    values, and the same transposes."""
    x, rows, _ = _rows_case(300, jnp.dtype(dtype), tokens=400)
    token = jax.random.permutation(jax.random.PRNGKey(7), 400)[:300].astype(
        jnp.int32)
    n = jnp.int32(n)
    want = moe.add_rows(rows, token, n, 400)
    np.testing.assert_array_equal(moe.add_rows(rows, token, n, 400, True),
                                  want)
    _, take_vjp = jax.vjp(lambda x: moe.take_rows(x, token, n, True), x)
    np.testing.assert_array_equal(take_vjp(rows)[0], want)
    _, add_vjp = jax.vjp(lambda r: moe.add_rows(r, token, n, 400, True), rows)
    np.testing.assert_array_equal(add_vjp(x)[0], moe.take_rows(x, token, n))


@pytest.mark.parametrize("top_k,held", [(1, 4), (4, 1)],
                         ids=["top-1", "one-expert-held"])
def test_a_layer_whose_tokens_have_one_row_each(top_k, held):
    """Top-1 (ZAYA's layer) and a share of one expert: values and gradients
    against the loop, through the gathered sum."""
    x, router, *kernels = _layer(seed=4)
    mine = _held(kernels, 2, held)

    def loop(x, router, *k):
        probs = jax.nn.softmax(x @ router, axis=-1)
        weights, chosen = jax.lax.top_k(probs, top_k)
        weights = weights / weights.sum(-1, keepdims=True)
        y = jnp.zeros_like(x)
        for i in range(held):
            w = jnp.sum(jnp.where(chosen == 2 + i, weights, 0.0), axis=-1)
            y = y + w[:, None] * ((jax.nn.silu(x @ k[0][i]) * (x @ k[1][i]))
                                  @ k[2][i])
        return y

    def layer(x, router, *k):
        return moe.routed_experts(x, router, *k, top_k=top_k, first_expert=2,
                                  capacity_factor=2.0)[0]

    np.testing.assert_allclose(layer(x, router, *mine),
                               loop(x, router, *mine), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(layer(*a) ** 2),
                   argnums=(0, 1, 2, 3, 4))(x, router, *mine)
    want = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2),
                    argnums=(0, 1, 2, 3, 4))(x, router, *mine)
    for name, a, b in zip(("x", "router", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("load_sum,capacity,kept", [
    (0, 36864, True), (16400, 36864, True), (36864, 36864, True),
    (36865, 36864, False), (8192, 16384, True), (5, 384, True),
    (1101, 1100, False)])
def test_forward_kept(load_sum, capacity, kept):
    # the rows routed fit the buffer: ``rows_walked``'s cases, and past them
    assert moe.forward_kept(load_sum, capacity) is kept
    if kept:        # and then a walk visits every one of them
        assert load_sum <= moe.rows_walked(load_sum, capacity) <= capacity


def test_rows_walked():
    # whole trips of WALK_ROWS up to the one that holds the last routed row
    assert moe.WALK_ROWS == 512
    assert moe.rows_walked(0, 36864) == 0
    assert moe.rows_walked(1, 36864) == 512
    assert moe.rows_walked(512, 36864) == 512
    assert moe.rows_walked(513, 36864) == 1024
    assert moe.rows_walked(16400, 36864) == 16896      # 33 of 72 trips
    assert moe.rows_walked(8192, 16384) == 8192        # ZAYA: 16 of 32
    assert moe.rows_walked(36864, 36864) == 36864
    # a buffer smaller than a trip is one trip; one that is no whole number
    # of trips ends at its own end
    assert moe.rows_walked(5, 384) == 384
    assert moe.rows_walked(1025, 1100) == 1100


def _wide_passes_outside_loops(jaxpr, capacity, width, scope="",
                               in_loop=False):
    """The gathers, scatters and selects of ``jaxpr`` under the scope
    ``hvd_moe_route`` that read or write a ``[capacity, width]`` array and
    sit in no ``while`` body."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        wide = any(getattr(v.aval, "shape", None) == (capacity, width)
                   for v in (*eqn.invars, *eqn.outvars))
        if (eqn.primitive.name in ("gather", "scatter", "scatter-add",
                                   "select_n") and wide and not in_loop
                and "hvd_moe_route" in here):
            found.append((eqn.primitive.name, here))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _wide_passes_outside_loops(
                        sub, capacity, width, here,
                        in_loop or eqn.primitive.name == "while")
    return found


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_no_pass_of_the_routing_walks_the_whole_buffer(trips_of_64,
                                                       backward):
    """In the traced ``_forward`` and ``_backward`` at a small buffer, on
    both sides of the ``cond``: every gather, scatter and select of the
    scope ``hvd_moe_route`` that touches a ``[capacity, d]`` array is in a
    ``while`` body, a trip's rows at a time.  (What is paid by the byte
    stays whole: the weighting, the zeros the loops start from; a layer
    whose tokens have one row each sums them back by one gather.)"""
    x, router, *kernels = _layer()
    mine = _held(kernels, 0, 4)
    routing = moe.route(x, router, TOP_K, 0, 4)
    local = moe._local(routing.experts, 0, 4)
    rows = moe.row_buffer(TOKENS, TOP_K, 4, EXPERTS, 2.0)
    assert rows == 512 and rows not in (TOKENS, D, F)
    args = (x, local, routing.weights)
    if backward:
        args += (moe._forward(rows, *args, *mine)[1], x)
    traced = jax.make_jaxpr(
        moe._backward if backward else moe._forward, static_argnums=0)(
            rows, *args, *mine)
    loops = str(traced).count("while[")
    assert loops >= (3 if backward else 2), loops
    assert _wide_passes_outside_loops(traced.jaxpr, rows, D) == []


# ---------------------------------------------------------------------------
# ``add_rows`` as a TPU makes it: a segment sum in token order
# ---------------------------------------------------------------------------


def _scatter_adds_into(jaxpr, shape) -> int:
    """The ``scatter-add`` equations of ``jaxpr``, at any depth, whose result
    has ``shape``."""
    found = 0
    for eqn in jaxpr.eqns:
        found += (eqn.primitive.name == "scatter-add"
                  and eqn.outvars[0].aval.shape == shape)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _scatter_adds_into(sub, shape)
    return found


@pytest.fixture
def sums_by_the_kernel(monkeypatch):
    """What a TPU runs for ``add_rows``, interpreted: the token order and
    ``hvd_moe_sum_rows`` (row tiles of 128, token tiles of 32, so that a small
    buffer is several of each), and no pass traced that way left behind."""
    from jax.experimental.pallas import tpu as pltpu
    from horovod_tpu.ops import grouped_matmul as gm

    def anew():
        _traced_anew()
        gm._sum_rows.clear_cache()

    interpret = pltpu.InterpretParams()     # scalar prefetch under shard_map
    monkeypatch.setattr(gm, "SUM_ROWS", 128)
    monkeypatch.setattr(gm, "SUM_TOKENS", 32)
    monkeypatch.setattr(moe, "token_order", functools.partial(
        gm.token_order, interpret=interpret))
    monkeypatch.setattr(moe, "sum_by_token", functools.partial(
        gm.sum_by_token, interpret=interpret))
    anew()
    yield
    anew()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("capacity", [256, 300],
                         ids=["whole-tiles", "no-whole-number-of-tiles"])
@pytest.mark.parametrize("n", [0, 1, 100, 128, None],
                         ids=["none", "one", "an-edge-inside-a-tile",
                              "a-whole-number-of-tiles", "the-full-buffer"])
def test_add_rows_by_the_kernel_is_the_sum_in_float32(sums_by_the_kernel, n,
                                                      capacity, dtype):
    """On a TPU ``add_rows`` is ``zeros.at[token[:n]].add(rows[:n])`` summed
    in float32 and rounded once (off it, the same sum in ``rows.dtype`` and
    in row order: never further from it than the activations' rounding), and
    the rows past n reach nothing."""
    x, rows, token = _rows_case(capacity, jnp.dtype(dtype))
    n = capacity if n is None else n
    live = (jnp.arange(capacity) < n)[:, None]
    # (a jit of a function of this test's own: one of ``moe.add_rows`` itself
    # would find the trace an earlier test left, the CPU's)
    got = jax.jit(lambda rows, token, n: moe.add_rows(
        rows, token, n, x.shape[0]))(
            jnp.where(live, rows, jnp.nan), token, jnp.int32(n))
    assert "pallas_call" in str(jax.make_jaxpr(lambda rows: moe.add_rows(
        rows, token, jnp.int32(n), x.shape[0]))(rows))
    want = jnp.zeros(x.shape, jnp.float32).at[token].add(
        jnp.where(live, rows, 0).astype(jnp.float32))
    assert got.dtype == rows.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(dtype), np.float32),
                               rtol=2.0 ** -7 if dtype == "bfloat16" else 1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("n", [0, 100, 300])
def test_the_row_ops_stay_each_other_s_transpose_by_the_kernel(
        sums_by_the_kernel, n):
    """``take_rows``' cotangent is ``add_rows`` by the kernel (it sorts for
    itself there: nobody kept an order), ``add_rows``' is ``take_rows``,
    with or without an order handed in; both are what indexing's are."""
    x, rows, token = _rows_case(300, jnp.float32)
    n, live = jnp.int32(n), (jnp.arange(300) < n)[:, None]
    _, take_vjp = jax.vjp(lambda x: moe.take_rows(x, token, n), x)
    _, index_vjp = jax.vjp(lambda x: jnp.where(live, x[token], 0.0), x)
    np.testing.assert_allclose(take_vjp(rows)[0], index_vjp(rows)[0],
                               rtol=1e-6, atol=1e-6)
    by_token = moe.token_order(token, n)
    for order in (None, by_token):
        summed, add_vjp = jax.vjp(lambda r: moe.add_rows(
            r, token, n, x.shape[0], False, order), rows)
        np.testing.assert_array_equal(summed, take_vjp(rows)[0])
        np.testing.assert_array_equal(add_vjp(x)[0],
                                      moe.take_rows(x, token, n))


@pytest.mark.parametrize("chips", [1, 2])
@pytest.mark.parametrize("skewed", [(1,), (1, 2)], ids=["fits", "in-parts"])
def test_the_layer_by_the_kernel_inside_a_jitted_shard_map_step(
        sums_by_the_kernel, skewed, chips):
    """The layer as a TPU sums it back, under ``jit``, ``shard_map`` with
    ``check_vma`` and ``value_and_grad``: where the rows fit (the order made
    in the forward and kept) and in parts (a part's buffer, its order made
    where it is needed), against the loop."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    x, router, *kernels = _layer(skew=6.0, skewed=skewed)
    mine = _held(kernels, 0, 4)

    def step(*a):
        value, grads = jax.value_and_grad(lambda *a: jnp.sum(
            moe.routed_experts(*a, top_k=TOP_K, capacity_factor=2.0)[0] ** 2),
            argnums=(0, 1, 2, 3, 4))(*a)
        return jax.lax.psum(value, "hvd"), grads

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("hvd",))
    sharded = shard_map(
        step, mesh=mesh, in_specs=(P("hvd"), P(), P(), P(), P()),
        out_specs=(P(), (P("hvd"), P(), P(), P(), P())))
    # both passes' sums on each side of the cond, and no scatter-add
    traced = jax.make_jaxpr(sharded)(x, router, *mine)
    assert str(traced).count("hvd_moe_sum_rows") >= 3
    assert _scatter_adds_into(traced.jaxpr, (TOKENS // chips, D)) == 0
    got = jax.jit(sharded)(x, router, *mine)
    want = jax.value_and_grad(lambda *a: jnp.sum(_loop(*a) ** 2),
                              argnums=(0, 1, 2, 3, 4))(x, router, *mine)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(("x", "router", "gate", "up", "down"), got[1],
                          want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("top_k,sorts", [(TOP_K, (2, 0)), (1, (1, 0))],
                         ids=["top-k", "top-1"])
def test_the_token_order_is_made_once_a_layer(sums_by_the_kernel, top_k,
                                              sorts):
    """A fitting forward sorts twice (the choices by expert, the rows by
    token) and its backward not at all: the order is kept; both sums are the
    kernel's and no scatter-add of a ``[tokens, d]`` array is left.  A layer
    whose tokens have one row each makes no order and calls no kernel."""
    x, router, *kernels = _layer()
    mine = _held(kernels, 0, 4)
    routing = moe.route(x, router, top_k, 0, 4)
    local = moe._local(routing.experts, 0, 4)
    rows = TOKENS * min(top_k, 4)       # every row fits: no cond
    args = (x, local, routing.weights)

    def traced(fn, *a):     # through a new function each time: the traces
        return jax.make_jaxpr(lambda *a: fn(rows, *a))(*a)  # of one are kept

    kept = moe._forward(rows, *args, *mine)[1]
    assert (kept.by_token is None) == (top_k == 1)
    traces = (traced(moe._forward, *args, *mine),
              traced(moe._backward, *args, kept, x, *mine))
    with pytest.MonkeyPatch.context() as off_the_tpu:   # what a CPU traces
        off_the_tpu.setattr(moe, "token_order", lambda token, n: None)
        _traced_anew()
        assert _scatter_adds_into(traced(moe._forward, *args, *mine).jaxpr,
                                  x.shape) == (top_k > 1)
        _traced_anew()
    for trace, n_sorts in zip(traces, sorts):
        assert _count(trace.jaxpr, {"sort"}, 1) == n_sorts
        assert _count(trace.jaxpr, {"pallas_call"}, 1) == (top_k > 1)
        assert _scatter_adds_into(trace.jaxpr, x.shape) == 0
