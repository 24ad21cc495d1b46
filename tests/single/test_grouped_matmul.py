"""``ops/grouped_matmul.py``: the grouped product and its two gradients, by
the Pallas kernels in interpret mode, against a loop over the groups.  The
rows past the groups' sum hold NaN on the way in, forward and backward: they
must come out as zeros and reach no dW."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import grouped_matmul as gm

K, N = 64, 128
TILE = gm.TILE_ROWS

# name -> (rows of the buffer, group sizes)
CASES = {
    "an-empty-group": (2 * TILE, (300, 0, 212, 512)),
    "edges-inside-a-tile": (2 * TILE, (100, 250, 400, 274)),
    "every-row-in-one-group": (2 * TILE, (0, 0, 2 * TILE, 0)),
    "a-tail-of-whole-tiles": (4 * TILE, (TILE, 0, TILE // 2, TILE // 2)),
    "a-tail-that-starts-inside-a-tile": (4 * TILE, (500, 41, 0, 300)),
    "trailing-empty-groups-and-a-tail": (3 * TILE, (700, 30, 0, 0)),
    "no-row-routed": (2 * TILE, (0, 0, 0, 0)),
    "a-buffer-that-ends-inside-a-tile": (2 * TILE + 128, (600, 0, 500, 52)),
    "a-buffer-smaller-than-a-tile": (384, (100, 0, 200, 1)),
}
DTYPES = {"bfloat16": (jnp.bfloat16, 2e-2), "float32": (jnp.float32, 1e-5)}


def _operands(case, dtype, w_dtype=None):
    rows_n, sizes = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    rows = jax.random.normal(ks[0], (rows_n, K), dtype)
    w = (jax.random.normal(ks[1], (len(sizes), K, N)) * K ** -0.5).astype(
        w_dtype or dtype)
    ct = jax.random.normal(ks[2], (rows_n, N), dtype)
    past = np.arange(rows_n)[:, None] >= sum(sizes)
    return (jnp.where(past, jnp.nan, rows), w, jnp.where(past, jnp.nan, ct),
            jnp.asarray(sizes, jnp.int32))


def _by_loop(rows, w, ct, sizes):
    """out, d rows, dW a group at a time, in float32; zeros past the sum."""
    rows, w, ct = (np.asarray(a, np.float32) for a in (rows, w, ct))
    out = np.zeros((rows.shape[0], w.shape[2]), np.float32)
    drows, dw = np.zeros_like(rows), np.zeros_like(w)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        mine = slice(start, start + size)
        out[mine] = rows[mine] @ w[g]
        drows[mine] = ct[mine] @ w[g].T
        dw[g] = rows[mine].T @ ct[mine]
        start += size
    return out, drows, dw


@functools.lru_cache(maxsize=None)
def _got_and_want(case, dtype_name):
    dtype, tol = DTYPES[dtype_name]
    rows, w, ct, sizes = _operands(case, dtype)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda r, w: gm.grouped_dot(r, w, sizes, interpret=True), rows, w)
        got = (out, *vjp(ct))
    return ([np.asarray(a, np.float32) for a in got],
            _by_loop(rows, w, ct, sizes), tol, sum(CASES[case][1]))


def _close(got, want, tol):
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_the_product_against_a_loop(case, dtype):
    (out, _, _), (want, _, _), tol, routed = _got_and_want(case, dtype)
    _close(out, want, tol)
    assert not out[routed:].any()           # zeros, whatever the rows hold


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_d_rows_against_a_loop(case, dtype):
    (_, drows, _), (_, want, _), tol, routed = _got_and_want(case, dtype)
    _close(drows, want, tol)
    assert not drows[routed:].any()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_dw_against_a_loop(case, dtype):
    (_, _, dw), (_, _, want), tol, _ = _got_and_want(case, dtype)
    assert np.isfinite(dw).all()            # the tail's NaN reached none
    _close(dw, want, tol)
    for g, size in enumerate(CASES[case][1]):
        assert size or not dw[g].any()      # a group without rows: zeros


@pytest.mark.parametrize("case", ["an-empty-group",
                                  "a-tail-that-starts-inside-a-tile"])
def test_float32_matrices_under_bfloat16_rows(case):
    """Parameters kept in float32: the product reads them cast to the rows'
    dtype (in VMEM, a group at a time) and dW comes back in float32,
    unrounded."""
    rows, w, ct, sizes = _operands(case, jnp.bfloat16, jnp.float32)

    def both(w):
        out, vjp = jax.vjp(
            lambda r, w: gm.grouped_dot(r, w, sizes, interpret=True), rows, w)
        return (out, *vjp(ct))

    (out, drows, dw), (out_c, drows_c, dw_c) = both(w), both(
        w.astype(jnp.bfloat16))
    assert (out.dtype, drows.dtype, dw.dtype) == (jnp.bfloat16, jnp.bfloat16,
                                                  jnp.float32)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out_c, np.float32))
    np.testing.assert_array_equal(np.asarray(drows, np.float32),
                                  np.asarray(drows_c, np.float32))
    np.testing.assert_array_equal(np.asarray(dw.astype(jnp.bfloat16),
                                             np.float32),
                                  np.asarray(dw_c, np.float32))
    _close(np.asarray(dw), _by_loop(rows, w.astype(jnp.bfloat16), ct,
                                    sizes)[2], 1e-5)


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
def test_inside_a_jitted_shard_map_step_under_check_vma(w_dtype):
    """As the expert layer calls it: the rows and the sizes a chip's own,
    the matrices replicated; each chip's dW is its own rows' and
    ``shard_map`` sums them."""
    from jax import shard_map
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, PartitionSpec as P

    chips = [_operands(case, jnp.bfloat16, jnp.dtype(w_dtype))
             for case in ("edges-inside-a-tile", "an-empty-group")]
    rows, ct, sizes = (jnp.concatenate([c[i] for c in chips])
                       for i in (0, 2, 3))
    w = chips[0][1]

    def step(rows, ct, sizes, w):
        out, vjp = jax.vjp(lambda r, w: gm.grouped_dot(
            r, w, sizes, interpret=pltpu.InterpretParams()), rows, w)
        return (out, *vjp(ct))

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))
    out, drows, dw = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("hvd"), P("hvd"), P("hvd"), P()),
        out_specs=(P("hvd"), P("hvd"), P())))(rows, ct, sizes, w)
    want = [_by_loop(c[0], w, c[2], c[3]) for c in chips]
    _close(np.asarray(out, np.float32),
           np.concatenate([o for o, _, _ in want]), 2e-2)
    _close(np.asarray(drows, np.float32),
           np.concatenate([d for _, d, _ in want]), 2e-2)
    assert dw.dtype == jnp.dtype(w_dtype)
    _close(np.asarray(dw, np.float32), want[0][2] + want[1][2], 2e-2)


@pytest.mark.parametrize("rows,tile,sizes", [
    (64, 16, (5, 0, 20, 7)), (48, 16, (16, 16, 0, 16)), (32, 16, (0, 0, 0, 0)),
    (40, 16, (5, 0, 20, 7)), (64, 16, (64, 0, 0, 0)), (64, 16, (0, 0, 0, 64)),
    (36864, 512, (1000,) * 16), (36864, 512, (2304,) * 16)],
    ids=lambda v: str(v).replace(" ", ""))
def test_the_schedule_visits_every_pair_once(rows, tile, sizes):
    """Tiles + groups visits whatever the sizes: each (tile, group) that
    shares rows exactly once, each group without rows once, each tile past
    the routed rows once under the pseudo group, in an order in which
    neither tiles nor groups fall."""
    held, n_tiles = len(sizes), -(-rows // tile)
    offsets, groups, tiles = (np.asarray(a) for a in gm._schedule(
        jnp.asarray(sizes, jnp.int32), rows, tile))
    assert len(groups) == len(tiles) == n_tiles + held
    assert (np.diff(groups) >= 0).all() and (np.diff(tiles) >= 0).all()
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(offsets, [0, *ends, ends[-1]])
    shared = [(t, g) for g in range(held) for t in range(n_tiles)
              if min(ends[g], (t + 1) * tile) > max(ends[g] - sizes[g],
                                                    t * tile)]
    visits = list(zip(tiles.tolist(), groups.tolist()))
    for pair in shared:
        assert visits.count(pair) == 1, pair
    for g in range(held):
        assert sizes[g] or groups.tolist().count(g) == 1
    routed_tiles = -(-int(ends[-1]) // tile)
    idle = [t for t, g in visits if g == held]
    assert idle[:n_tiles - routed_tiles] == list(range(routed_tiles, n_tiles))
    # What is left over stays on the last tile and does nothing there.
    assert set(idle[n_tiles - routed_tiles:]) <= {n_tiles - 1}
    assert len(visits) == len(shared) + sum(s == 0 for s in sizes) + len(idle)


def test_off_the_tpu_it_is_ragged_dot():
    rows, w, _, sizes = _operands("edges-inside-a-tile", jnp.float32)
    np.testing.assert_array_equal(
        gm.grouped_dot(rows, w.astype(jnp.bfloat16), sizes),
        jax.lax.ragged_dot(rows, w.astype(jnp.bfloat16).astype(jnp.float32),
                           sizes))


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernels", "ragged_dot"])
@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["an-empty-group",
                                  "a-tail-that-starts-inside-a-tile"])
def test_the_two_gradients_alone_are_reverse_mode_s(case, w_dtype, interpret):
    """``grouped_dot_grads``: d rows and dW for a caller that kept the
    operands, the bits that ``jax.vjp`` of ``grouped_dot`` gives, by the
    kernels and by ``ragged_dot``'s transposes; and no forward product in
    its jaxpr."""
    rows, w, ct, sizes = _operands(case, jnp.bfloat16, w_dtype)
    if interpret is None:       # ragged_dot takes the tail's NaN as given
        rows, ct = (jnp.nan_to_num(a) for a in (rows, ct))
    want = jax.vjp(lambda r, w: gm.grouped_dot(
        r, w, sizes, interpret=interpret), rows, w)[1](ct)
    got = gm.grouped_dot_grads(rows, w, sizes, ct, interpret=interpret)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    traced = str(jax.make_jaxpr(lambda *a: gm.grouped_dot_grads(
        *a, interpret=interpret))(rows, w, sizes, ct))
    assert (traced.count("pallas_call") if interpret
            else traced.count("ragged_dot_general[")) == 2



# ---------------------------------------------------------------------------
# The way back: rows summed into their tokens (``hvd_moe_sum_rows``)
# ---------------------------------------------------------------------------


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 128 and token tiles of 32, so that a small buffer is
    several of each; nothing traced with them stays in the jit's cache."""
    monkeypatch.setattr(gm, "SUM_ROWS", 128)
    monkeypatch.setattr(gm, "SUM_TOKENS", 32)
    gm._sum_rows.clear_cache()
    yield
    gm._sum_rows.clear_cache()


def _spread(capacity, tokens, top_k=4):
    """Tokens of a buffer as a layer makes them: at most ``top_k`` rows a
    token, in no order, some tokens with no row."""
    return (jax.random.permutation(jax.random.PRNGKey(capacity),
                                   tokens * top_k)[:capacity] // top_k)


# name: (capacity, tokens, n, the rows' tokens)
SUMS = {
    "no-row": (384, 96, 0, _spread),
    "the-full-buffer": (384, 96, 384, _spread),
    "an-edge-inside-a-row-tile": (384, 96, 200, _spread),
    "a-buffer-of-no-whole-row-tiles": (300, 96, 290, _spread),
    "tokens-of-no-whole-token-tiles": (384, 100, 333, _spread),
    "fewer-tokens-than-a-token-tile": (256, 24, 90,
                                       lambda c, t: _spread(c, t, 12)),
    "every-token-with-all-its-rows": (384, 96, 384,
                                      lambda c, t: jnp.arange(c) % t),
    "every-row-on-one-token-tile": (384, 96, 300,
                                    lambda c, t: 64 + jnp.arange(c) % 7),
    "most-tokens-with-no-row": (384, 96, 384,
                                lambda c, t: 3 * (jnp.arange(c) % 5) + 40),
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", SUMS)
def test_the_sum_by_token_against_a_scatter_add(small_tiles, case, dtype):
    """``sum_by_token`` of the first n rows is ``zeros.at[token[:n]].add(
    rows[:n])`` made in float32 and rounded once, whatever the rows past n
    hold: zeros where a token has no row, a token's every row, the edges of
    row tiles and of token tiles anywhere."""
    capacity, tokens, n, draw = SUMS[case]
    dtype = jnp.dtype(dtype)
    token = draw(capacity, tokens).astype(jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(1), (capacity, 128)).astype(
        dtype)
    live = (jnp.arange(capacity) < n)[:, None]
    order = gm.token_order(token, jnp.int32(n), interpret=True)
    got = gm.sum_by_token(jnp.where(live, rows, jnp.nan), order, tokens,
                          interpret=True)
    want = jnp.zeros((tokens, 128), jnp.float32).at[token].add(
        jnp.where(live, rows, 0).astype(jnp.float32)).astype(dtype)
    assert got.dtype == dtype and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-5)
    # rows n.. keep their order behind the others, the first n ascend
    by, tokens_of = np.asarray(order.rows), np.asarray(order.tokens)
    assert sorted(by[:n]) == list(range(n))
    assert list(by[n:]) == list(range(n, capacity))
    assert list(tokens_of[:n]) == sorted(np.asarray(token)[:n])


def test_off_the_tpu_no_order_is_made():
    # there ``parallel/moe.py:add_rows`` is a scatter-add in row order
    assert jax.default_backend() != "tpu"
    assert gm.token_order(jnp.arange(8, dtype=jnp.int32), 8) is None
    assert gm.token_order(jnp.arange(8, dtype=jnp.int32), 8,
                          interpret=True) is not None


def test_the_sum_inside_a_jitted_shard_map_step_under_check_vma(small_tiles):
    """Each chip sums its own rows into its own tokens."""
    from jax import shard_map
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, PartitionSpec as P

    capacity, tokens, chips = 256, 64, 2
    token = jnp.concatenate([_spread(capacity + i, tokens)
                             for i in range(chips)]).astype(jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(2), (chips * capacity, 128))
    n = jnp.asarray([200, 77], jnp.int32)

    def step(rows, token, n):
        interpret = pltpu.InterpretParams()
        order = gm.token_order(token, n[0], interpret=interpret)
        return gm.sum_by_token(rows, order, tokens, interpret=interpret)

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("hvd",))
    got = jax.jit(shard_map(step, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(rows, token, n)
    for i in range(chips):
        mine = slice(i * capacity, i * capacity + int(n[i]))
        np.testing.assert_allclose(
            got[i * tokens:(i + 1) * tokens],
            jnp.zeros((tokens, 128)).at[token[mine]].add(rows[mine]),
            rtol=1e-6, atol=1e-6)
