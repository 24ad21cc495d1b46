"""The flash kernels under a band (``flash_attention(..., causal=True,
window=W)``), through the Pallas interpreter against a dense mask: values,
``lse`` and the three gradients at windows of 1, 128, 512 and of the whole
sequence, windows that no step divides, a band that reaches further than a
grid block, a length that is no multiple of the tile, groups of 6 and of 9
query heads on one key/value head, several heads a lane block, bfloat16; a
window that holds every causal pair is the causal call, bit for bit and text
for text; what a banded call is named and what its schedule is: the band's
own grid, blocks and walk."""

from unittest import mock

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops import flash_attention as fa

from _flash_helpers import equations, small_tiles  # noqa: F401

# float32 operands through the interpreter: what is left is the order of the
# sums (the kernels' steps against one dense row).
TOL = 2e-5


def _operands(seq, heads, kv_heads, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, w = (jax.random.normal(k, (1, seq, heads, d)) for k in ks[:2])
    k, v = (jax.random.normal(key, (1, seq, kv_heads, d)) for key in ks[2:4])
    return q, k, v, w, jax.random.normal(ks[4], (1, heads, seq))


def _flash_with_lse(q, k, v, causal, window=None, interpret=None,
                    block_q=None, block_k=None):
    """``(out, lse)`` of a call under a band: what :func:`fa.flash_attention`
    computes before it drops the row statistics (the backward's residual; the
    public ``(out, lse)`` pair is ring attention's and takes no window)."""
    return fa._flash(q, k, v, causal, None, block_q, block_k, interpret, None,
                     window=window)


def _dense_with_lse(q, k, v, causal, window=None):
    return fa._dense(q, k, v, causal, None, None, window=window)


def _out_lse_grads(attend, q, k, v, w, u, **kw):
    """``(out, lse, dq, dk, dv)`` under cotangents on both results (one
    compiled program a case: op by op a case costs three times as much)."""
    def loss(q, k, v):
        out, lse = attend(q, k, v, causal=True, **kw)
        return jnp.sum(out * w) + jnp.sum(lse * u), (out, lse)

    def all_five(q, k, v):
        grads, (out, lse) = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        return (out, lse, *grads)

    return jax.jit(all_five)(q, k, v)


def _dense_mask(q, k, v, w, u, window):
    """The oracle, written here: a [S, S] mask ``j <= i < j + window``."""
    seq, group = q.shape[1], q.shape[2] // k.shape[2]
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = (j <= i) & (i - j < window)

    def attend(q, k, v, causal):
        kk, vv = (jnp.repeat(x, group, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * q.shape[-1] ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]),
                          vv), lse

    return _out_lse_grads(attend, q, k, v, w, u)


def _kernel_names(fn, *args) -> list:
    """The names of the Pallas kernels ``fn`` calls, in the jaxpr's order."""
    return [eqn.params["name"]
            for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def _agree(got, want, tol=TOL):
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < tol, (name, err)


# The plan's own tiles (one grid block a head at 640 rows, sub-tiles of one
# 128-row step): the cell's windows and its two groups, and windows that no
# step divides (200: the far edge crosses two steps of a sub-tile).
@pytest.mark.parametrize("window,heads", [
    (1, 9), (128, 9), (512, 9), (128, 6), (512, 6), (4096, 6), (200, 9),
    (129, 6)])
def test_a_band_against_a_dense_mask(window, heads):
    q, k, v, w, u = _operands(640, heads, 1, 16, seed=window + heads)
    got = _out_lse_grads(_flash_with_lse, q, k, v, w, u,
                         window=window, interpret=True)
    _agree(got, _dense_mask(q, k, v, w, u, window))


# The plan's own tiles over several grid blocks (1,280 rows in blocks of 256:
# five grid steps a head): a window of 1,000 reaches four blocks back, one of
# 200 into the block before; and the heads of a lane block side by side (four
# of 32, two of 64: as many key/value heads).
@pytest.mark.parametrize("window,heads,kv_heads,d", [
    (1000, 2, 1, 16), (200, 2, 1, 16), (200, 4, 4, 32), (300, 2, 2, 64)])
def test_a_band_over_the_plan_s_own_steps(window, heads, kv_heads, d):
    q, k, v, w, u = _operands(1280, heads, kv_heads, d, seed=window + heads)
    got = _out_lse_grads(_flash_with_lse, q, k, v, w, u, window=window,
                         interpret=True, block_q=256, block_k=256)
    want = _dense_mask(q, k, v, w, u, window)
    _agree(got, want)
    # The sequence's first rows see fewer keys than the window holds: row 0
    # its own alone, so its value is v's first row and its lse its score.
    assert jnp.allclose(got[0][0, 0], jnp.repeat(
        v[0, 0], heads // kv_heads, axis=0), atol=1e-5)


def test_a_band_in_bfloat16():
    """bfloat16 operands into the products as they arrive, float32 softmax:
    against the dense mask on the same (rounded) operands in float32, to
    bfloat16's own rounding of p, dS and the results."""
    q, k, v, w, u = (x.astype(jnp.bfloat16).astype(jnp.float32)
                     for x in _operands(640, 6, 1, 16, seed=7))
    got = _out_lse_grads(_flash_with_lse, *(
        x.astype(jnp.bfloat16) for x in (q, k, v)), w, u, window=200,
        interpret=True)
    assert got[0].dtype == got[2].dtype == jnp.bfloat16
    assert got[1].dtype == jnp.float32
    _agree(got, _dense_mask(q, k, v, w, u, 200), tol=2e-2)


# Toy tiles (8-row steps, two sub-tiles a loop trip): several grid blocks,
# blocks of two sizes, a length that pads, a band narrower than a step (5), of
# whole steps (24), that no step divides (13, 70), and wider than a block
# (70 over blocks of 64, 32 and 16: two, three and five blocks beside the
# resident one).
SCHEDULES = {"two-blocks": (128, 64, 64, 2, 1),
             "padded-tail": (100, 64, 64, 3, 1),
             "bq<bk": (128, 32, 64, 6, 2),
             "bq>bk-ungrouped": (128, 64, 32, 2, 2),
             "padded-bq>bk": (75, 64, 16, 2, 1)}


@pytest.mark.parametrize("schedule,window", [
    ("two-blocks", 5), ("two-blocks", 24), ("two-blocks", 70),
    ("two-blocks", 13), ("padded-tail", 24), ("bq<bk", 5), ("bq<bk", 70),
    ("bq>bk-ungrouped", 24), ("padded-bq>bk", 5), ("padded-bq>bk", 70)])
def test_a_band_across_grid_blocks(small_tiles, schedule, window):  # noqa: F811
    seq, block_q, block_k, heads, kv_heads = SCHEDULES[schedule]
    q, k, v, w, u = _operands(seq, heads, kv_heads, 16, seed=seq + window)
    got = _out_lse_grads(_flash_with_lse, q, k, v, w, u,
                         window=window, interpret=True, block_q=block_q,
                         block_k=block_k)
    _agree(got, _dense_mask(q, k, v, w, u, window))


def test_dense_attention_takes_the_same_band():
    q, k, v, w, u = _operands(96, 4, 2, 8)
    got = _out_lse_grads(_dense_with_lse, q, k, v, w, u,
                         window=17)
    _agree(got, _dense_mask(q, k, v, w, u, 17))
    assert jnp.array_equal(fa.dense_attention(q, k, v, causal=True,
                                              window=17), got[0])


@pytest.mark.parametrize("window", [None, 256, 1000])
def test_a_window_of_the_whole_sequence_is_the_causal_call(window):
    """``window=None`` and ``window >= seq`` lower to the text the causal
    call lowers to, and compute its bits."""
    q, k, v, w, u = _operands(256, 6, 1, 16)

    def call(**kw):
        return lambda q, k, v: _out_lse_grads(
            _flash_with_lse, q, k, v, w, u, interpret=True, **kw)

    def text(**kw):
        return jax.jit(lambda q, k, v: _flash_with_lse(
            q, k, v, causal=True, interpret=True, **kw)).lower(
                q, k, v).as_text()

    assert text(window=window) == text()
    assert _kernel_names(call(window=window), q, k, v) == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]
    for a, b in zip(call(window=window)(q, k, v), call()(q, k, v)):
        assert jnp.array_equal(a, b)


def test_a_banded_call_names_its_three_kernels():
    q, k, v, w, u = _operands(256, 6, 1, 16)
    assert _kernel_names(lambda q, k, v: _out_lse_grads(
        _flash_with_lse, q, k, v, w, u, window=64,
        interpret=True), q, k, v) == [
            "hvd_flash_swa_fwd", "hvd_flash_swa_dq", "hvd_flash_swa_dkv"]


def test_a_banded_plan_s_schedule_is_the_band_s_own():
    """At the cell's shape (16,384 x 128 in bfloat16, one head a grid step):
    the grid blocks are the causal call's 2,048 rows, walked in sub-tiles of
    256 rows that take 256-key steps two side by side (``tile_q`` 512), and a
    sub-tile under a band of 512 takes three steps, the diagonal's and two
    before it: 768 keys a row, the window and a step, whichever tile the row
    is in.  A call has 8 grid steps a head, each with the 512 rows the band
    reaches beside its block."""
    causal = fa.tile_plan(16384, 128, 2, True, heads=1)
    banded = fa.tile_plan(16384, 128, 2, True, heads=1, window=512)
    assert (causal.block_q, causal.tile_q, causal.step_k) == (2048, 1024, 256)
    assert banded._replace(vmem_bytes=0) == causal._replace(
        tile_q=512, tile_k=512, vmem_bytes=0)
    assert banded.vmem_bytes <= fa._VMEM_BUDGET
    assert fa.tile_plan(16384, 128, 2, True, heads=1, window=None) == causal
    band = fa.band_of(banded.block_q, banded.step_k, banded.tile_q, 512)
    assert band == fa.Band(block=2048, step=256, steps=3, chains=2,
                           beside=512, n_beside=1)
    assert band == fa.band_of(banded.block_k, banded.step_q, banded.tile_k,
                              512)                   # dkv's, the mirror
    assert (banded.seq_pad // band.block, band.rows_visited) == (8, 768)
    # The sizes follow the shapes, not the window; the steps a sub-tile takes
    # and the rows beside the block follow the window: a window of one key
    # its own step alone, one that no step divides the steps it touches.
    plans = {w: fa.tile_plan(16384, 128, 2, True, heads=1, window=w)
             for w in (1, 2, 128, 256, 257, 511, 513, 1000, 1024, 4096)}
    assert all(p._replace(vmem_bytes=0) == banded._replace(vmem_bytes=0)
               for p in plans.values())
    assert {w: (b.steps, b.beside, b.n_beside) for w, b in (
        (w, fa.band_of(2048, 256, 512, w)) for w in plans)} == {
            1: (1, 0, 0), 2: (2, 256, 1), 128: (2, 256, 1), 256: (2, 256, 1),
            257: (2, 256, 1), 511: (3, 512, 1), 513: (3, 512, 1),
            1000: (5, 1024, 1), 1024: (5, 1024, 1), 4096: (17, 2048, 2)}
    # Rows beside the block come in a part of it that whole steps fill (768
    # rows are no part of 2,048: 1,024), or in whole blocks.
    assert fa.band_of(2048, 256, 512, 600).beside == 1024
    odd = fa.tile_plan(640, 16, 4, True, heads=1, window=8)
    assert (odd.block_q, odd.step_k, odd.tile_q) == (640, 128, 128)
    assert [fa.tile_plan(128, 16, 4, True, 64, 32, heads=1,
                         window=w)[1:7] for w in (8, 64)] == [
                             (64, 32, 64, 32, 32, 64)] * 2


@pytest.mark.parametrize("kw,message", [
    (dict(causal=False, window=8), "band under the causal diagonal"),
    (dict(causal=True, window=0), "its own key at least"),
    (dict(causal=True, window=8, block_diffusion=(16, 4)),
     "band under the causal diagonal")])
def test_a_window_is_refused_where_it_means_nothing(kw, message):
    q, k, v, _, _ = _operands(32, 2, 1, 8)
    for attend in (fa.flash_attention, fa.dense_attention):
        with pytest.raises(ValueError, match=message):
            attend(q, k, v, **kw)


def test_the_band_s_live_blocks():
    """Every grid step of a banded call is live: the grid has no axis over
    the blocks of the sequence beside the resident one, and the index maps
    of the rows beside it name the band's own, by their own arithmetic: at
    blocks of 2,048 under a band of 512, the 512 rows before the query block
    (forward, dq) or after the key block (dkv), held at the sequence's first
    and last where there are none; under a band of 4,096 the two blocks
    before, the farthest first."""
    band = fa.band_of(2048, 256, 512, 512)
    (before,), (after,) = (fa._band_beside(band, 16384, side)
                           for side in (True, False))
    assert [int(before(i)) * 512 for i in range(8)] == [
        0, 1536, 3584, 5632, 7680, 9728, 11776, 13824]
    assert [int(after(i)) * 512 for i in range(8)] == [
        2048, 4096, 6144, 8192, 10240, 12288, 14336, 15872]
    wide = fa.band_of(2048, 256, 512, 4096)
    assert [[int(block(i)) for block in fa._band_beside(wide, 16384, True)]
            for i in range(4)] == [[0, 0], [0, 0], [0, 1], [1, 2]]
    # Where a step lies: the block of the sub-tile's own positions, or the
    # rows beside it; a sub-tile's first step is the diagonal's.
    def rows(t):
        return fa._band_rows(band, t, True)

    assert rows(0)[0] is None and rows(1792)[0] is None
    assert [(rows(t)[0], rows(t)[1].start) for t in (-512, -256)] == [
        (0, 0), (0, 256)]
    assert fa._band_rows(band, 2304, False)[0] == 0
    # The walk itself: the trips that reach beside the block are written out
    # for the sequence's edge and for its inside, the rest once.
    seen = []
    with mock.patch.object(fa.pl, "when", lambda cond: lambda body: body()):
        fa._band_walk(band, 0, 8, True,
                      lambda c0, reach: seen.append((c0, reach)))
    assert seen == [(0, 0), (0, 2048), (2, 0), (4, 0), (6, 0)]
