"""The flash kernels under a band (``flash_attention(..., causal=True,
window=W)``), through the Pallas interpreter against a dense mask: values,
``lse`` and the three gradients at windows of 1, 128, 512 and of the whole
sequence, a length that is no multiple of the tile, groups of 6 and of 9 query
heads on one key/value head; a window that holds every causal pair is the
causal call, bit for bit and text for text; what a banded call is named and
how its tiles are planned."""

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


def _agree(got, want):
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < TOL, (name, err)


# The plan's own tiles (one grid block a head at 640 rows, a resident tile
# of one 128-row step under a band): the cell's windows and its two groups.
@pytest.mark.parametrize("window,heads", [
    (1, 9), (128, 9), (512, 9), (128, 6), (512, 6), (4096, 6)])
def test_a_band_against_a_dense_mask(window, heads):
    q, k, v, w, u = _operands(640, heads, 1, 16, seed=window + heads)
    got = _out_lse_grads(_flash_with_lse, q, k, v, w, u,
                         window=window, interpret=True)
    _agree(got, _dense_mask(q, k, v, w, u, window))


# Toy tiles (32-row tiles in 8-row steps; under a band of 5 keys a tile of one
# step, of 24 two, of 70 the whole tile): several grid blocks, blocks of two
# sizes, a length that pads, a band narrower than a step, as wide as a few,
# wider than a block.
SCHEDULES = {"two-blocks": (128, 64, 64, 2, 1),
             "padded-tail": (100, 64, 64, 3, 1),
             "bq<bk": (128, 32, 64, 6, 2),
             "bq>bk-ungrouped": (128, 64, 32, 2, 2),
             "padded-bq>bk": (75, 64, 16, 2, 1)}


@pytest.mark.parametrize("schedule,window", [
    ("two-blocks", 5), ("two-blocks", 24), ("two-blocks", 70),
    ("padded-tail", 24), ("bq<bk", 5), ("bq<bk", 70),
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


def test_a_banded_plan_s_resident_tile_follows_the_window():
    """At the cell's shape (16,384 x 128 in bfloat16, one head a grid step):
    the grid blocks are the causal call's, the resident tile 512 rows in
    256-row steps, so a row under a band of 512 visits 896 keys; a narrower
    band takes one step, a band of the un-banded tile or more that tile."""
    causal = fa.tile_plan(16384, 128, 2, True, heads=1)
    banded = fa.tile_plan(16384, 128, 2, True, heads=1, window=512)
    assert (causal.block_q, causal.tile_q, causal.step_k) == (2048, 1024, 256)
    assert banded._replace(vmem_bytes=0) == causal._replace(
        tile_q=512, tile_k=512, vmem_bytes=0)
    assert banded.vmem_bytes < causal.vmem_bytes     # a step's tiles shrink
    assert fa.tile_plan(16384, 128, 2, True, heads=1, window=None) == causal
    assert [fa.tile_plan(16384, 128, 2, True, heads=1, window=w).tile_q
            for w in (1, 128, 256, 511, 1023, 1024, 4096)] == [
                256, 256, 256, 256, 512, 1024, 1024]
    # A tile is whole steps that divide the un-banded tile, one at least.
    odd = fa.tile_plan(640, 16, 4, True, heads=1, window=8)
    assert (odd.block_q, odd.step_k, odd.tile_q) == (640, 128, 128)
    assert [fa.tile_plan(128, 16, 4, True, 64, 32, heads=1,
                         window=w).tile_q for w in (8, 64)] == [32, 64]


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
    """Which grid tiles a band keeps, by the index maps' own arithmetic: at
    blocks of 2048 under a band of 512 a query block needs its own key block
    and the one before it, and a key block's last query block is the next."""
    plan = fa.tile_plan(16384, 128, 2, True, heads=1, window=512)
    first = [int(fa._first_live_k(i, plan, 512)) for i in range(8)]
    last = [int(fa._last_live_k(i, True, plan, 16384)) for i in range(8)]
    assert first == [0, 0, 1, 2, 3, 4, 5, 6] and last == list(range(8))
    assert [int(fa._last_live_q(j, plan, 16384, 512)) for j in range(8)] == [
        1, 2, 3, 4, 5, 6, 7, 7]
    live = np.array([[bool(fa._block_live(i, j, True, plan, 16384, 512))
                      for j in range(8)] for i in range(8)])
    assert live.sum() == 15 and np.array_equal(
        live, np.tril(np.ones((8, 8), bool)) & ~np.tril(np.ones((8, 8), bool),
                                                       -2))
