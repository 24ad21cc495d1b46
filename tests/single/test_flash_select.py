"""``ops/flash_select.py``: the selection against its definition written a
query at a time, and the selected walk's kernels in interpret mode against the
masked dense softmax, on drawn and on adversarial choices (all chosen, only
the forced, a tile whose queries share nothing), grouped heads, values, lse
and the three gradients; the sizes the walk refuses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_select as fs
from horovod_tpu.ops.flash_attention import flash_attention

D = 128
SPARSE = dict(kernel_size=8, stride=4, block=16, topk=8, init_blocks=1,
              local_blocks=2)


def _operands(seq, heads, groups, dtype=jnp.float32, batch=1):
    ks = jax.random.split(jax.random.key(heads * 1000 + seq), 4)
    q, w = (jax.random.normal(key, (batch, seq, heads, D), dtype)
            for key in ks[:2])
    k, v = (jax.random.normal(key, (batch, seq, groups, D), dtype)
            for key in ks[2:])
    return q, k, v, w.astype(jnp.float32)


def _forced(seq, block, init_blocks, local_blocks):
    own = (np.arange(seq) // block)[:, None]
    blk = np.arange(seq // block)[None, :]
    return (blk < init_blocks) | ((blk > own - local_blocks) & (blk <= own))


def _choices(kind, seq, groups, block=16):
    """bool [1, groups, seq, blocks]."""
    own = (np.arange(seq) // block)[:, None]
    blk = np.arange(seq // block)[None, :]
    seen = blk <= own
    if kind == "all":
        chosen = seen
    elif kind == "forced":
        chosen = _forced(seq, block, 1, 2)
    else:
        # Besides the forced, query t takes block (7 t) mod its own: the
        # queries of a tile share next to nothing.
        lone = blk == (7 * np.arange(seq)[:, None]) % np.maximum(own, 1)
        chosen = (_forced(seq, block, 1, 1) | lone) & seen
    return jnp.asarray(np.broadcast_to(chosen, (1, groups, *chosen.shape)))


def _value_and_grads(attend, q, k, v, w):
    def f(q, k, v):
        out, lse = attend(q, k, v)
        return (jnp.sum(out.astype(jnp.float32) * w)
                + jnp.sum(jnp.sin(lse)), (out, lse))

    (_, seen), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
    return (*seen, *grads)


def _l2(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_the_selection_is_its_definition_a_query_at_a_time():
    """Steps 1 to 5 in numpy, one query after the other, against
    ``sparse_select`` (blocked over tiles of queries)."""
    seq, heads, groups = 128, 4, 2
    q, k, _, _ = _operands(seq, heads, groups)
    with jax.default_matmul_precision("highest"):
        select, scores = fs.sparse_select(q, k, tile=32, with_scores=True,
                                          **SPARSE)
    chosen = np.asarray(fs.unpack_bits(select.bits, seq // 16))
    qn, kn = np.asarray(q[0], np.float64), np.asarray(k[0], np.float64)
    n = (seq - 8) // 4 + 1
    for g in range(groups):
        kc = np.stack([kn[4 * j:4 * j + 8, g].mean(0) for j in range(n)])
        for t in (0, 5, 7, 8, 31, 32, 77, 127):
            visible = [j for j in range(n) if 4 * j + 7 <= t]
            summed = np.zeros(n)
            for h in range(g * 2, g * 2 + 2):
                s = kc[visible] @ qn[t, h] * D ** -0.5
                if visible:
                    e = np.exp(s - s.max())
                    summed[visible] += e / e.sum()
            want = np.array([max([summed[j] for j in range(n) if 4 * j < 16 * b
                                  + 16 and 4 * j + 8 > 16 * b] or [0.0])
                             for b in range(seq // 16)])
            np.testing.assert_allclose(np.asarray(scores)[0, g, t], want,
                                       atol=2e-6)
            own = t // 16
            forced = [b for b in range(own + 1) if b < 1 or b > own - 2]
            free = sorted((b for b in range(own + 1) if b not in forced),
                          key=lambda b: (-want[b], b))
            taken = sorted(forced + free[:8 - len(forced)])
            assert np.flatnonzero(chosen[0, g, t]).tolist() == taken, (g, t)
    assert select.bits.shape == (1, groups, 1, seq)


def test_bits_pack_and_unpack_and_pad_to_whole_words():
    chosen = jax.random.bernoulli(jax.random.key(0), 0.3, (2, 3, 50, 40))
    bits = fs.pack_bits(chosen)
    assert bits.shape == (2, 3, 2, 50) and bits.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(fs.unpack_bits(bits, 40)),
                                  np.asarray(chosen))
    assert not np.any(np.asarray(fs.unpack_bits(bits))[..., 40:])


@pytest.mark.parametrize("kind", ["drawn", "all", "forced", "scattered"])
@pytest.mark.parametrize("sizes", [
    dict(tile_q=128, step_k=128, tile_k=128, step_q=128),
    dict(tile_q=256, step_k=128, tile_k=128, step_q=256),
], ids=["128s", "256x128"])
def test_the_walk_agrees_with_the_masked_dense_softmax(kind, sizes):
    """Values, lse, dq, dk and dv (the sum over a group's heads) over 512
    queries, 4 heads on 2 key/value heads; the steps a tile visits come from
    its queries' union, the mask from each query's own bits."""
    seq, heads, groups = 512, 4, 2
    q, k, v, w = _operands(seq, heads, groups)
    if kind == "drawn":
        select = fs.sparse_select(q, k, **SPARSE)
    else:
        select = fs.Selection(fs.pack_bits(_choices(kind, seq, groups)), 16)
    scale = D ** -0.5
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *x: _value_and_grads(
            lambda q, k, v: fs.flash_select(q, k, v, select, scale,
                                            interpret=True, **sizes), *x))(
                q, k, v, w)
        want = jax.jit(lambda *x: _value_and_grads(
            lambda q, k, v: fs.dense_select(q, k, v, select, scale), *x))(
                q, k, v, w)
    for name, g, wnt in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert np.all(np.isfinite(np.asarray(g))), name
        assert _l2(g, wnt) < 2e-5, (kind, name, _l2(g, wnt))


def test_every_block_chosen_is_plain_causal_attention_and_the_forced_not():
    seq, heads, groups = 256, 2, 1
    q, k, v, _ = _operands(seq, heads, groups)
    causal = flash_attention(q, k, v, causal=True)
    for kind, same in (("all", True), ("forced", False)):
        select = fs.Selection(fs.pack_bits(_choices(kind, seq, groups)), 16)
        got = fs.flash_select(q, k, v, select, interpret=True)[0]
        assert (_l2(got, causal) < 1e-5) == same, kind


def test_the_counters_count_pairs_chosen_visited_and_left_out():
    seq, groups = 512, 1
    select = fs.Selection(fs.pack_bits(_choices("forced", seq, groups)), 16)
    got = {k: float(v) for k, v in fs.walk_counters(select, seq, 128,
                                                    128).items()}
    own = np.arange(seq) // 16
    assert got["chosen"] == float(np.minimum(own + 1, 3).sum())
    # a tile of 128 queries visits its own step and, but for the first, the
    # step of block 0 and the step before its own (the local blocks of its
    # first queries): 128 rows x 8 blocks a step
    assert got["visited"] == (1 + 2 + 3 + 3) * 128 * 8
    assert got["left_out"] == float((own + 1 > 3).sum())
    every = fs.Selection(fs.pack_bits(_choices("all", seq, groups)), 16)
    assert float(fs.walk_counters(every, seq, 128, 128)["left_out"]) == 0


@pytest.mark.parametrize("sizes,message", [
    (dict(tile_q=96), "must divide the sequence"),
    (dict(step_k=8), "hold whole blocks of 16 keys"),
])
def test_the_walk_refuses_sizes_that_cut_a_block_or_a_word(sizes, message):
    q, k, v, _ = _operands(512, 2, 1)
    select = fs.Selection(fs.pack_bits(_choices("all", 512, 1)), 16)
    with pytest.raises(ValueError, match=message):
        fs.flash_select(q, k, v, select, interpret=True, **sizes)


def test_the_selection_passes_no_gradient():
    q, k, v, w = _operands(128, 2, 1)

    def f(q, k):
        select = fs.sparse_select(q, k, **SPARSE)
        return jnp.sum(fs.dense_select(jax.lax.stop_gradient(q),
                                       jax.lax.stop_gradient(k), v, select,
                                       D ** -0.5)[0] * w)

    dq, dk = jax.grad(f, argnums=(0, 1))(q, k)
    assert not np.any(np.asarray(dq)) and not np.any(np.asarray(dk))
