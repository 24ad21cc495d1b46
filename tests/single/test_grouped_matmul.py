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

