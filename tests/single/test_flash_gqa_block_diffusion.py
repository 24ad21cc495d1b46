"""Grouped-query heads and the block-diffusion mask of
``ops/flash_attention.py``: the three kernels in interpret mode against
``dense_attention``, values and gradients; the mask written out by hand; the
rows that see no key the kernels walk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    block_diffusion_mask, dense_attention, flash_attention,
    flash_attention_with_lse)

from _flash_helpers import small_tiles  # noqa: F401  (a fixture)


def _qkv(batch, seq, heads, kv_heads, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, seq, heads, d), jnp.float32)
    k, v = (jax.random.normal(key, (batch, seq, kv_heads, d), jnp.float32)
            for key in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], q.shape, jnp.float32)


def _value_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


def _assert_same(got, want):
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_the_mask_written_out_by_hand():
    """L = 8 in blocks of 4, rows and columns ``[clean ; noised]``: a clean
    row sees the clean blocks up to its own; a noised row sees the clean
    blocks before its own and its own noised block."""
    c0, c1 = [1] * 4 + [0] * 4, [1] * 8
    want = ([c0 + [0] * 8] * 4 + [c1 + [0] * 8] * 4
            + [[0] * 8 + [1] * 4 + [0] * 4] * 4
            + [[1] * 4 + [0] * 4 + [0] * 4 + [1] * 4] * 4)
    got = np.asarray(block_diffusion_mask(8, 4)).astype(int)
    assert got.tolist() == want
    # a quarter of the square plus the noised diagonal: L^2 (1 + 1/n)
    assert got.sum() == 8 * 8 + 8 * 4
    assert np.asarray(block_diffusion_mask(12, 2)).sum() == 12 * 12 + 12 * 2


# query heads, key/value heads, head width, L, block length, explicit block:
# L = 96 and 160 are no multiple of the 128-row tile (the halves are padded
# apart); a width of 128 takes the model's layout, 32 the turned-round one.
CASES = {"4on1-d32-L96-B4": (4, 1, 32, 96, 4, None),
         "4on2-d32-L96-B32": (4, 2, 32, 96, 32, None),
         "4on2-d128-L160-B4": (4, 2, 128, 160, 4, None),
         "4on1-d128-L128-B32": (4, 1, 128, 128, 32, None),
         "4on4-d32-L64-B4-blocks32": (4, 4, 32, 64, 4, 32),
         "4on2-d16-L96-B32-blocks32": (4, 2, 16, 96, 32, 32)}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_block_diffusion_kernels_match_dense(case):
    heads, kv_heads, d, length, block, blocks = case
    q, k, v, w = _qkv(2, 2 * length, heads, kv_heads, d)
    mask = {"block_diffusion": (length, block)}
    got = _value_and_grads(lambda q, k, v: flash_attention(
        q, k, v, interpret=True, block_q=blocks, block_k=blocks, **mask),
        q, k, v, w)
    want = _value_and_grads(lambda q, k, v: dense_attention(q, k, v, **mask),
                            q, k, v, w)
    _assert_same(got, want)
    # The noised rows of the first block see no clean key: their output is
    # the softmax over their own noised block alone.
    first = slice(length, length + block)
    alone = dense_attention(q[:, first], k[:, first], v[:, first])
    np.testing.assert_allclose(np.asarray(got[0][:, first]),
                               np.asarray(alone), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("blocks,block", [(64, 4), (128, 8), (32, 4)])
def test_block_diffusion_walks_over_several_tiles_and_steps(
        small_tiles, blocks, block):  # noqa: F811
    """The long sequence's schedule at a toy size (tiles of 32 rows walked
    in steps of 8): grid blocks above the diagonal are dead, the diagonal
    crosses a tile in four steps, and the walks stop where the causal ones
    do."""
    q, k, v, w = _qkv(1, 2 * 128, 4, 2, 32, seed=3)
    mask = {"block_diffusion": (128, block)}
    got = _value_and_grads(lambda q, k, v: flash_attention(
        q, k, v, interpret=True, block_q=blocks, block_k=blocks, **mask),
        q, k, v, w)
    want = _value_and_grads(lambda q, k, v: dense_attention(q, k, v, **mask),
                            q, k, v, w)
    _assert_same(got, want)


# The noised copy's own blocks, met inside the kernels.  Query heads,
# key/value heads, head width, L, block length, (block_q, block_k), toy tiles
# (tiles of 32 rows in steps of 8, so L = 128 in grid blocks of 64 is two
# grid blocks of two tiles and the own squares are 8 x 8; without them a
# step is whole lane tiles and the squares are 128 x 128).
OWN = {"two-grid-blocks-8on2-B4": (8, 2, 32, 128, 4, (64, 64), True),
       "two-grid-blocks-4on4-B8": (4, 4, 32, 128, 8, (64, 64), True),
       "bq>bk-4on2-B4": (4, 2, 32, 128, 4, (64, 32), True),
       "bq<bk-4on1-B8": (4, 1, 32, 128, 8, (32, 64), True),
       "lane-tile-squares-2on1-B8": (2, 1, 128, 256, 8, (None, None), False),
       "lane-tile-squares-2on2-B128": (2, 2, 128, 256, 128, (None, None),
                                       False)}


@pytest.mark.parametrize("case", OWN.values(), ids=OWN.keys())
def test_a_noised_row_meets_its_own_block_inside_the_kernels(
        case, request):
    """Values, the lse and dq, dk, dv against the dense form where the
    diagonal grid step is not the first, where the query and key blocks
    differ, and at both sizes of the own squares; the noised keys' and
    values' gradients on their own rows (nothing but the own squares gives
    them any); a noised row of the first block, which sees no clean key:
    a finite lse, the dense one, and the softmax over its own block."""
    heads, kv_heads, d, length, block, (block_q, block_k), toy = case
    if toy:
        request.getfixturevalue("small_tiles")
    from horovod_tpu.ops import flash_attention as fa

    q, k, v, w = _qkv(1, 2 * length, heads, kv_heads, d, seed=4)
    wl = jax.random.normal(jax.random.PRNGKey(5), (1, heads, 2 * length))

    def value_and_grads(pair):
        def loss(q, k, v):
            out, lse = pair(q, k, v)
            return jnp.sum(out * w) + jnp.sum(lse * wl), (out, lse)

        (_, (out, lse)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads), lse

    got, lse = value_and_grads(lambda q, k, v: fa._flash(
        q, k, v, False, None, block_q, block_k, True, None, (length, block)))
    want, want_lse = value_and_grads(lambda q, k, v: fa._dense(
        q, k, v, False, None, None, (length, block)))
    _assert_same(got, want)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("dk", "dv"), got[2:], want[2:]):
        noised = np.asarray(a[:, length:])
        assert np.abs(noised).max() > 1e-3, name
        np.testing.assert_allclose(noised, np.asarray(b[:, length:]),
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    first = slice(length, length + block)
    assert np.isfinite(np.asarray(lse[..., first])).all()
    assert np.abs(np.asarray(lse[..., first])).max() < 1e3
    alone = dense_attention(q[:, first], k[:, first], v[:, first])
    np.testing.assert_allclose(np.asarray(got[0][:, first]),
                               np.asarray(alone), rtol=2e-4, atol=2e-5)


GROUPED = {"causal-4on1-d32": (True, 4, 1, 32, 100),
           "causal-4on2-d128": (True, 4, 2, 128, 200),
           "full-4on2-d32": (False, 4, 2, 32, 130),
           "full-4on1-d128": (False, 4, 1, 128, 96)}


@pytest.mark.parametrize("case", GROUPED.values(), ids=GROUPED.keys())
def test_grouped_query_kernels_match_dense(case):
    """Fewer key/value heads than query heads under the two masks the
    kernels had: query head i reads key/value head i // group, and dk / dv
    gather the group's query heads."""
    causal, heads, kv_heads, d, seq = case
    q, k, v, w = _qkv(2, seq, heads, kv_heads, d, seed=1)
    got = _value_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True), q, k, v, w)
    want = _value_and_grads(lambda q, k, v: dense_attention(
        q, k, v, causal=causal), q, k, v, w)
    _assert_same(got, want)
    # ... which is what repeating the key/value heads gives.
    group = heads // kv_heads
    repeated = dense_attention(q, jnp.repeat(k, group, 2),
                               jnp.repeat(v, group, 2), causal=causal)
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(repeated),
                               rtol=1e-5, atol=1e-6)


def test_the_lse_of_a_block_diffusion_call_is_the_dense_one():
    q, k, v, _ = _qkv(1, 2 * 64, 4, 2, 32, seed=2)
    from horovod_tpu.ops import flash_attention as fa

    out, lse = fa._flash(q, k, v, False, None, None, None, True, None,
                         (64, 4))
    want_out, want_lse = fa._dense(q, k, v, False, None, None, (64, 4))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-4, atol=2e-5)
    assert flash_attention_with_lse(q, k, v, causal=True,
                                    interpret=True)[1].shape == (1, 4, 128)


@pytest.mark.parametrize("kwargs,message", [
    ({"block_diffusion": (64, 4), "causal": True}, "mask of its own"),
    ({"block_diffusion": (64, 4), "kv_lens": [3]}, "mask of its own"),
    ({"block_diffusion": (60, 4)}, "2 x 60 positions"),
    ({"block_diffusion": (64, 5)}, "whole blocks"),
    ({"block_diffusion": (64, 64), "block_q": 32, "block_k": 32},
     "must divide the kernels' steps"),
], ids=["causal", "kv_lens", "length", "block", "steps"])
def test_a_block_diffusion_call_that_cannot_be_is_refused(kwargs, message):
    q, k, v, _ = _qkv(1, 128, 2, 1, 32)
    with pytest.raises(ValueError, match=message):
        flash_attention(q, k, v, interpret=True, **kwargs)


def test_head_counts_that_do_not_divide_are_refused():
    q, k, v, _ = _qkv(1, 64, 4, 3, 32)
    for fn in (dense_attention,
               lambda *a: flash_attention(*a, interpret=True)):
        with pytest.raises(ValueError, match="must divide the query heads"):
            fn(q, k, v)
    q, k, v, _ = _qkv(1, 64, 4, 2, 32)
    with pytest.raises(ValueError, match="kv_lens with fewer key/value"):
        flash_attention(q, k, v, interpret=True, kv_lens=jnp.array([10]))
