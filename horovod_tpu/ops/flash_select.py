"""Block-selected attention (InfLLM-V2, the trainable sparse attention of
MiniCPM4, arXiv:2506.07900): which key blocks each query sees is chosen by the
data, and the flash kernels walk the blocks that were chosen.

Two halves.  **The selection** (:func:`sparse_select`, XLA, scope
``hvd_sparse_select``) reads q and k and hands out a bit a (query, key block):

1. compressed keys ``K^c_j = mean(k[stride j : stride j + kernel_size])``, a
   key/value head at a time, no parameters;
2. for query t and head h, ``p = softmax_j(scale q_t . K^c_j)`` over the
   compressed keys that end at or before t (none: all zero);
3. the sum of p over the query heads of the key/value head's group;
4. a block's score is the largest of the compressed keys that overlap its
   ``block`` tokens (a max-pool of ``block / stride + kernel_size / stride -
   1`` at stride ``block / stride``, ``kernel_size / stride - 1`` of padding);
5. the first ``init_blocks`` blocks and the ``local_blocks`` blocks up to the
   query's own are always chosen, and the highest-scoring others fill up to
   ``topk`` blocks in all (``lax.top_k``; a query that sees fewer blocks
   chooses them all);
6. the query attends to the keys at or before t inside its chosen blocks,
   every head of the group under the same choice.

It runs a tile of queries at a time (``lax.map``), so no ``[S, S]`` and no
whole ``[H, S, S / stride]`` float32 array is made at once, and passes no
gradient.  The scores' product takes its operands as they come and
accumulates in float32; everything from the softmax on is float32.

**The walk** (:func:`flash_select`; kernels ``hvd_flash_sel_fwd`` / ``_dq`` /
``_dkv``).  A query's own chosen keys against its group's heads would be a
``[heads, 128] x [128, block]`` product a block, a few rows of the MXU; so a
**tile** of queries visits **the union of its queries' blocks**, in key steps
of whole blocks, and inside a visited step the bit masks each (query row, key
block) beside the causal diagonal.  The mathematics is whole: no query's
choice is rounded to its tile's.  What the union costs is read by the counters
of :func:`walk_counters`.

The forward and dq take a query tile a grid step, (batch, query heads, query
tiles), with **the key/value head's whole k and v resident in VMEM** (a
sequence of 16,384 x 128 in bfloat16 is 4 MiB each; every grid step of a
key/value head's group names the same block, so it is fetched once), and walk
the tile's listed key steps in a loop whose length and steps come from
scalar-prefetched lists: no grid step is spent on a step nobody chose.  dkv
takes a key tile a grid step, (batch, query heads, key tiles), with the query
head's q and dO resident, walks the key tile's listed query steps, and leaves
a float32 partial a query head that is summed over the group outside.  A
listed step carries its kind beside its number: all of its pairs chosen and
under the diagonal (no mask), under the diagonal (the bits alone: one add a
score), or crossed by it (the causal comparison besides).  The score tile is
built transposed ([keys, queries]) as ``ops/flash_attention.py`` builds it,
the row statistics lane-dense rows.  ``delta = rowsum(dO * O)`` is XLA's here: a head
is whole lane tiles.

The bits cross as int32 words, ``bits[b, g, w, t]``: bit i of word w of query
t says whether it chose key block ``32 w + i`` of key/value head g
(:class:`Selection`), 512 KiB at 16,384 queries of 256 blocks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES, NEG_INF, _NT, _TN, _out_struct

WORD = 32                    # blocks a word of bits
# Rows of a query tile and keys of a step of the forward's and dq's walk, keys
# of a tile and rows of a step of dkv's.  Read on a v5e at 8 query heads on 1
# key/value head, 16,384 x 128 in bfloat16, 64 of 256 blocks a query (PERF.md
# §6, PR 58): forward / dq + dkv of one call 12.9 / 44.5 ms at 256 all round,
# 10.3 / 38.9 at tiles of 512 in steps of 256, 10.5 / 35.3 at 512 all round,
# 11.0 / 36.2 at tiles of 1,024 in steps of 512: a larger tile visits more
# that its queries did not choose and pays less a visit.
TILE_Q, STEP_K = 512, 512
TILE_K, STEP_Q = 512, 512
# Queries a tile of the selection.
SELECT_TILE = 512
# k and v (fwd, dq) or q and dO (dkv) of one head lie whole in VMEM, twice
# (the pipeline's two buffers): what that may take, and what Mosaic is asked
# for.  A v5e / v6e core has 128 MiB.
_RESIDENT_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


class Selection(NamedTuple):
    """``bits`` int32 [B, key/value heads, words, S]: bit i of word w of query
    t is set where t chose key block ``32 w + i``; ``block``: keys a block."""
    bits: jax.Array
    block: int


def compressed_keys(k, kernel_size: int, stride: int):
    """[B, S, G, D] -> float32 [B, n, G, D], ``n = (S - kernel_size) / stride +
    1``: the mean of ``kernel_size`` keys every ``stride``."""
    batch, seq = k.shape[:2]
    if kernel_size % stride or seq % stride or seq < kernel_size:
        raise ValueError(
            f"sparse_select: kernel_size {kernel_size} must be whole strides "
            f"of {stride}, and the sequence of {seq} whole strides and no "
            "shorter than a kernel")
    parts = k.astype(jnp.float32).reshape(
        batch, seq // stride, stride, *k.shape[2:]).sum(2)
    n = (seq - kernel_size) // stride + 1
    return sum(parts[:, i:i + n]
               for i in range(kernel_size // stride)) / kernel_size


def _tile_scores(q, kc, first, *, scale, kernel_size, stride, block, blocks):
    """Steps 2 to 4 for a tile of queries ``q`` [B, T, G, R, D] (R query heads
    a key/value head) from position ``first`` on against the compressed keys
    ``kc`` [B, n, G, D]: float32 block scores [B, G, T, blocks]."""
    n = kc.shape[1]
    s = jnp.einsum("btgrd,bngd->bgrtn", q, kc.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    t = first + jnp.arange(q.shape[1])
    ends = stride * jnp.arange(n) + kernel_size - 1
    visible = ends[None, :] <= t[:, None]                       # [T, n]
    s = jnp.where(visible, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(visible, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    summed = jnp.sum(p, axis=2)                                 # [B, G, T, n]
    per, pad = block // stride, kernel_size // stride - 1
    pool = per + pad
    length = blocks * per + pool - per
    summed = jnp.pad(summed, [(0, 0), (0, 0), (0, 0),
                              (pad, max(length - pad - n, 0))])[..., :length]
    return functools.reduce(jnp.maximum, (
        summed[..., i:i + per * blocks:per] for i in range(pool)))


def _tile_choice(scores, first, *, block, topk, init_blocks, local_blocks):
    """Step 5: bool [B, G, T, blocks] from the block scores of a tile."""
    tile, blocks = scores.shape[-2:]
    own = (first + jnp.arange(tile))[:, None] // block          # [T, 1]
    blk = jnp.arange(blocks)[None, :]
    forced = (blk < init_blocks) | ((blk > own - local_blocks) & (blk <= own))
    seen = blk <= own
    key = jnp.where(seen, jnp.where(forced, jnp.inf, scores), -1.0)
    chosen = lax.top_k(key, min(topk, blocks))[1]               # [..., T, k]
    picked = jnp.any(chosen[..., None] == jnp.arange(blocks), axis=-2)
    return picked & seen


def pack_bits(chosen):
    """bool [..., T, blocks] -> int32 [..., words, T] (:class:`Selection`'s
    layout), the blocks padded to whole words with blocks nobody chose."""
    blocks = chosen.shape[-1]
    words = -(-blocks // WORD)
    chosen = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1)
                     + [(0, words * WORD - blocks)])
    bit = chosen.reshape(*chosen.shape[:-1], words, WORD).astype(jnp.uint32)
    packed = jnp.sum(bit << jnp.arange(WORD, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return jnp.swapaxes(lax.bitcast_convert_type(packed, jnp.int32), -1, -2)


def unpack_bits(bits, blocks: Optional[int] = None):
    """int32 [..., words, T] -> bool [..., T, blocks]."""
    words = bits.shape[-2]
    shifts = jnp.arange(WORD, dtype=jnp.int32)[:, None]
    bit = (bits[..., None, :] >> shifts) & 1
    out = jnp.moveaxis(bit.reshape(*bits.shape[:-2], words * WORD,
                                   bits.shape[-1]), -1, -2) == 1
    return out if blocks is None else out[..., :blocks]


def sparse_select(q, k, *, kernel_size: int, stride: int, block: int,
                  topk: int, init_blocks: int, local_blocks: int,
                  scale: Optional[float] = None,
                  tile: Optional[int] = None, with_scores: bool = False):
    """The :class:`Selection` of queries q [B, S, H, D] on keys k [B, S, G, D]
    (H / G query heads a key/value head, all of them summed in step 3), steps
    1 to 5 of the module's text.  ``with_scores``: the float32 block scores
    [B, G, S, blocks] beside it (small sizes and checks).  No gradient."""
    with jax.named_scope("hvd_sparse_select"):
        q, k = lax.stop_gradient(q), lax.stop_gradient(k)
        batch, seq, heads, width = q.shape
        groups = k.shape[2]
        if seq % block or block % stride:
            raise ValueError(
                f"sparse_select: blocks of {block} keys must be whole strides "
                f"of {stride} and divide the sequence of {seq}")
        scale = width ** -0.5 if scale is None else scale
        blocks = seq // block
        tile = min(tile or SELECT_TILE, seq)
        if seq % tile:
            raise ValueError(f"sparse_select: a tile of {tile} queries must "
                             f"divide the sequence of {seq}")
        kc = compressed_keys(k, kernel_size, stride)
        q = q.reshape(batch, seq, groups, heads // groups, width)

        def one(i):
            first = i * tile
            scores = _tile_scores(
                lax.dynamic_slice_in_dim(q, first, tile, 1), kc, first,
                scale=scale, kernel_size=kernel_size, stride=stride,
                block=block, blocks=blocks)
            bits = pack_bits(_tile_choice(
                scores, first, block=block, topk=topk,
                init_blocks=init_blocks, local_blocks=local_blocks))
            return (bits, scores) if with_scores else bits

        out = lax.map(one, jnp.arange(seq // tile))
        bits = out[0] if with_scores else out       # [tiles, B, G, words, T]
        bits = jnp.moveaxis(bits, 0, 3).reshape(batch, groups, -1, seq)
        select = Selection(bits, block)
        if not with_scores:
            return select
        return select, jnp.moveaxis(out[1], 0, 2).reshape(
            batch, groups, seq, blocks)


def selection_mask(select: Selection, seq: int):
    """bool [B, G, S, S]: whether query t sees key s, the causal diagonal
    included (small sizes: the dense form and tests)."""
    chosen = unpack_bits(select.bits)                       # [B,G,S,blocks]
    by_key = jnp.repeat(chosen, select.block, axis=-1)[..., :seq]
    return by_key & jnp.tril(jnp.ones((seq, seq), bool))


def dense_select(q, k, v, select: Selection, scale: float):
    """``(out, lse)`` of the masked dense softmax: the walk's mathematics at
    small sizes and off the TPU."""
    batch, seq, heads, _ = q.shape
    group = heads // k.shape[2]
    mask = jnp.repeat(selection_mask(select, seq), group, axis=1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, axis=2),
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      jnp.repeat(v, group, axis=2)), lse


def _walk_lists(select: Selection, seq: int, resident: int, step: int,
                by_key: bool):
    """``(lists, counts, visited)`` of a walk: for each resident tile
    (``resident`` queries in fwd / dq, keys in dkv: ``by_key``) the steps of
    the other operand (``step`` keys or queries) that any (query, key block)
    pair inside chose, first to last, as ``4 x step + kind``, and how many.
    ``kind``: ``DIAGONAL`` where the step holds a key after one of its
    queries, else ``FULL`` where every pair of it is chosen (it takes no
    mask), else ``BITS``.  ``lists`` int32 [B x G x tiles x steps] flat,
    ``counts`` [B x G x tiles]; ``visited`` bool [B, G, tiles, steps]."""
    chosen = unpack_bits(select.bits, seq // select.block)  # [B,G,S,blocks]
    batch, groups = chosen.shape[:2]
    tq, tk = (step, resident) if by_key else (resident, step)
    per = tk // select.block
    tiles = chosen.reshape(batch, groups, seq // tq, tq, seq // tk, per)
    visited, full = tiles.any((3, 5)), tiles.all((3, 5))        # [B,G,nq,nk]
    q0 = (jnp.arange(seq // tq) * tq)[:, None]
    k_last = (jnp.arange(seq // tk) * tk + tk - 1)[None, :]
    kind = jnp.where(k_last > q0, DIAGONAL, jnp.where(full, FULL, BITS))
    if by_key:
        visited, kind = (jnp.swapaxes(x, 2, 3) for x in (visited, kind))
    order = jnp.argsort(~visited, axis=-1, stable=True).astype(jnp.int32)
    lists = 4 * order + jnp.take_along_axis(kind, order, -1).astype(jnp.int32)
    counts = jnp.sum(visited, axis=-1, dtype=jnp.int32)
    return lists.reshape(-1), counts.reshape(-1), visited


def walk_counters(select: Selection, seq: int, tile: int = TILE_Q,
                  step: int = STEP_K) -> dict:
    """What the forward's walk pays beside what was chosen, in (query, key
    block) pairs: ``chosen`` (the bits set), ``visited`` (the steps a tile
    visits x the tile's rows x the step's blocks) and ``left_out`` (queries
    whose choice leaves out a block they could see)."""
    chosen = unpack_bits(select.bits, seq // select.block)
    visited = _walk_lists(select, seq, tile, step, False)[2]
    own = jnp.arange(seq) // select.block + 1
    return {"chosen": jnp.sum(chosen, dtype=jnp.float32),
            "visited": jnp.sum(visited, dtype=jnp.float32) * tile
            * (step // select.block),
            "left_out": jnp.sum(jnp.sum(chosen, -1) < own, dtype=jnp.float32)}


FULL, BITS, DIAGONAL = 0, 1, 2     # what a listed step's mask takes


def _masked(s, kind, words, row0, col0, block: int):
    """The score tile ``s`` [keys, queries] of a listed step under its mask.
    A ``FULL`` step (every pair chosen and under the diagonal) takes none.
    Else each of the step's key blocks takes its queries' bit: a row of
    ``words`` [words, queries] (the step's keys lie in one word; its row is
    picked by comparison, not by a dynamic sublane index, which Mosaic
    refuses at some widths), shifted to the block's bit and spread down the
    block's keys as 0 or the mask value, one add a score.  A ``DIAGONAL``
    step, which holds a key after one of its queries, takes the causal
    comparison besides."""
    keys, queries = s.shape

    def bits(s):
        at = lax.broadcasted_iota(jnp.int32, words.shape, 0)
        word = jnp.sum(jnp.where(at == col0 // (WORD * block), words, 0),
                       axis=0, keepdims=True)                   # [1, queries]
        first = (col0 // block) % WORD
        return s + jnp.concatenate([jnp.broadcast_to(jnp.where(
            jnp.right_shift(word, first + i) & 1 == 1, 0.0, NEG_INF),
            (block, queries)) for i in range(keys // block)], axis=0)

    def diagonal(s):
        kpos = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qpos = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(qpos >= kpos, s, NEG_INF)

    s = lax.cond(kind == FULL, lambda s: s, bits, s)
    return lax.cond(kind == DIAGONAL, diagonal, lambda s: s, s)


def _walk(lists_ref, counts_ref, row, tiles: int, steps: int):
    """``(base, count)`` of grid step's resident tile in the flat lists."""
    at = row * tiles + pl.program_id(2)
    return at * steps, counts_ref[at]


def _fwd_kernel(lists_ref, counts_ref, q_ref, k_ref, v_ref, bits_ref, o_ref,
                lse_ref, *, scale, step, block, group, kv_heads, tiles, steps):
    """grid (B, H, query tiles): the tile's q [T, D] against the listed key
    steps of its key/value head's whole k, v [S, D]; online softmax in
    registers, acc^T [D, T]."""
    tile, width = q_ref.shape
    row = pl.program_id(0) * kv_heads + pl.program_id(1) // group
    base, count = _walk(lists_ref, counts_ref, row, tiles, steps)
    row0 = pl.program_id(2) * tile
    q, words = q_ref[...], bits_ref[...]

    def visit(n, state):
        m, l, acc = state
        entry = lists_ref[base + n]
        col0 = pl.multiple_of((entry // 4) * step, step)
        k, v = k_ref[pl.ds(col0, step), :], v_ref[pl.ds(col0, step), :]
        s = lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
        s = _masked(s, entry % 4, words, row0, col0, block)     # [Tk, Tq]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + lax.dot_general(
            v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(0, count, visit, (
        jnp.full((1, tile), NEG_INF, jnp.float32),
        jnp.zeros((1, tile), jnp.float32),
        jnp.zeros((width, tile), jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = jnp.transpose(acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _dq_kernel(lists_ref, counts_ref, q_ref, k_ref, v_ref, bits_ref, do_ref,
               lse_ref, delta_ref, dq_ref, *, scale, step, block, group,
               kv_heads, tiles, steps):
    """grid (B, H, query tiles), the forward's walk: p from the kept lse,
    dq^T [D, T] gathered over the listed key steps."""
    tile, width = q_ref.shape
    row = pl.program_id(0) * kv_heads + pl.program_id(1) // group
    base, count = _walk(lists_ref, counts_ref, row, tiles, steps)
    row0 = pl.program_id(2) * tile
    q, do, words = q_ref[...], do_ref[...], bits_ref[...]
    lse, delta = lse_ref[...], delta_ref[...]                   # [1, T]

    def visit(n, acc):
        entry = lists_ref[base + n]
        col0 = pl.multiple_of((entry // 4) * step, step)
        k, v = k_ref[pl.ds(col0, step), :], v_ref[pl.ds(col0, step), :]
        s = lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
        s = _masked(s, entry % 4, words, row0, col0, block)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return acc + lax.dot_general(k, ds.astype(k.dtype), _TN,
                                     preferred_element_type=jnp.float32)

    acc = lax.fori_loop(0, count, visit,
                        jnp.zeros((width, tile), jnp.float32))
    dq_ref[...] = jnp.transpose(acc).astype(dq_ref.dtype)


def _dkv_kernel(lists_ref, counts_ref, q_ref, k_ref, v_ref, bits_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, *, scale, step, block,
                group, kv_heads, tiles, steps):
    """grid (B, H, key tiles): the tile's k, v [T, D] against the listed
    steps of the query head's whole q, dO [S, D]; the head's float32 part of
    dk and dv."""
    tile = k_ref.shape[0]
    row = pl.program_id(0) * kv_heads + pl.program_id(1) // group
    base, count = _walk(lists_ref, counts_ref, row, tiles, steps)
    col0 = pl.program_id(2) * tile
    k, v = k_ref[...], v_ref[...]

    def visit(n, state):
        dk, dv = state
        entry = lists_ref[base + n]
        row0 = pl.multiple_of((entry // 4) * step, step)
        rows = pl.ds(row0, step)
        q, do = q_ref[rows, :], do_ref[rows, :]
        s = lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
        s = _masked(s, entry % 4, bits_ref[:, rows], row0, col0, block)
        p = jnp.exp(s - lse_ref[:, rows])                       # [Tk, Tq]
        dv = dv + jnp.dot(p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, rows]) * scale
        dk = dk + jnp.dot(ds.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros(k.shape, jnp.float32)
    dk, dv = lax.fori_loop(0, count, visit, (zeros, zeros))
    dk_ref[...] = dk
    dv_ref[...] = dv


def _check_sizes(seq: int, width: int, itemsize: int, block: int, *sizes):
    if width % LANES:
        raise ValueError(f"flash_select: heads of {width} lanes; the kernels "
                         f"take whole {LANES}-lane tiles a head")
    for size in sizes:
        if seq % size or size % block or (WORD * block) % size:
            raise ValueError(
                f"flash_select: a tile or step of {size} rows must divide "
                f"the sequence of {seq}, hold whole blocks of {block} keys "
                f"and divide a word's {WORD * block} keys")
    if 4 * seq * width * itemsize > _RESIDENT_BUDGET:
        raise ValueError(
            f"flash_select: one head's k and v of {seq} x {width} do not fit "
            f"the {_RESIDENT_BUDGET >> 20} MiB the kernels may keep resident "
            "in VMEM; a sequence this long wants its keys streamed, which is "
            "not built")


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _specs(seq, width, group, words, tile):
    """Block specs over [B, S, heads x D] arrays and the statistics: a query
    head's tile, a key/value head's whole sequence, the bits of a tile."""
    def tile_of(b, h, i, *_):
        return b, i, h

    def whole_kv(b, h, i, *_):
        return b, 0, h // group

    return {
        "q_tile": pl.BlockSpec((None, tile, width), tile_of),
        "kv_whole": pl.BlockSpec((None, seq, width), whole_kv),
        "q_whole": pl.BlockSpec((None, seq, width),
                                lambda b, h, i, *_: (b, 0, h)),
        "kv_tile": pl.BlockSpec((None, tile, width),
                                lambda b, h, i, *_: (b, i, h // group)),
        "bits_tile": pl.BlockSpec((None, None, words, tile),
                                  lambda b, h, i, *_: (b, h // group, 0, i)),
        "bits_whole": pl.BlockSpec((None, None, words, seq),
                                   lambda b, h, i, *_: (b, h // group, 0, 0)),
        "stat_tile": pl.BlockSpec((None, None, 1, tile),
                                  lambda b, h, i, *_: (b, h, 0, i)),
        "stat_whole": pl.BlockSpec((None, None, 1, seq),
                                   lambda b, h, i, *_: (b, h, 0, 0)),
        "part_tile": pl.BlockSpec((None, None, tile, width),
                                  lambda b, h, i, *_: (b, h, i, 0)),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _select_attention(q, k, v, bits, block, scale, heads, sizes, interpret):
    return _forward(q, k, v, bits, block, scale, heads, sizes, interpret)[0]


def _forward(q, k, v, bits, block, scale, heads, sizes, interpret):
    """``((out, lse), (lists, counts))``, the walk's lists as dq takes them
    again: q [B, S, H x D], k, v [B, S, G x D], bits [B, G, words, S];
    ``heads`` = (H, G), ``sizes`` = (tile_q, step_k, tile_k, step_q)."""
    batch, seq, lanes = q.shape
    h, g = heads
    width, group, words = lanes // h, h // g, bits.shape[2]
    tile, step = sizes[:2]
    select = Selection(bits, block)
    with jax.named_scope("hvd_sparse_select"):
        lists, counts, _ = _walk_lists(select, seq, tile, step, False)
    spec = _specs(seq, width, group, words, tile)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, step=step, block=block, group=group,
        kv_heads=g, tiles=seq // tile, steps=seq // step)
    made = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, h, seq // tile),
            in_specs=[spec["q_tile"], spec["kv_whole"], spec["kv_whole"],
                      spec["bits_tile"]],
            out_specs=[spec["q_tile"], spec["stat_tile"]]),
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct((batch, h, 1, seq), jnp.float32, q)],
        compiler_params=_params(), interpret=interpret,
        name="hvd_flash_sel_fwd")(lists, counts, q, k, v, bits)
    return tuple(made), (lists, counts)


def _select_fwd(q, k, v, bits, block, scale, heads, sizes, interpret):
    (out, lse), walk = _forward(q, k, v, bits, block, scale, heads, sizes,
                                interpret)
    out = checkpoint_name(out, "hvd_flash_out")
    lse = checkpoint_name(lse, "hvd_flash_lse")
    return (out, lse), (q, k, v, bits, out, lse, walk)


def _select_bwd(block, scale, heads, sizes, interpret, saved, cotangents):
    q, k, v, bits, out, lse, (lists, counts) = saved
    do, dlse = cotangents
    batch, seq, lanes = q.shape
    h, g = heads
    width, group, words = lanes // h, h // g, bits.shape[2]
    tile_q, step_k, tile_k, step_q = sizes
    select = Selection(bits, block)
    # delta = rowsum(dO * O) - dlse, a row a head: a head is whole lane tiles,
    # so the sum over its lanes is XLA's.
    delta = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(batch, seq, h, width), axis=-1)
    delta = jnp.moveaxis(delta, 1, 2)[:, :, None, :] - dlse
    common = dict(scale=scale, block=block, group=group, kv_heads=g)
    spec = _specs(seq, width, group, words, tile_q)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, step=step_k, tiles=seq // tile_q,
                          steps=seq // step_k, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, h, seq // tile_q),
            in_specs=[spec["q_tile"], spec["kv_whole"], spec["kv_whole"],
                      spec["bits_tile"], spec["q_tile"], spec["stat_tile"],
                      spec["stat_tile"]],
            out_specs=[spec["q_tile"]]),
        out_shape=[_out_struct(q.shape, q.dtype, q)],
        compiler_params=_params(), interpret=interpret,
        name="hvd_flash_sel_dq")(lists, counts, q, k, v, bits, do, lse,
                                 delta)[0]
    with jax.named_scope("hvd_sparse_select"):
        lists, counts, _ = _walk_lists(select, seq, tile_k, step_q, True)
    spec = _specs(seq, width, group, words, tile_k)
    part = _out_struct((batch, h, seq, width), jnp.float32, q)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, step=step_q, tiles=seq // tile_k,
                          steps=seq // step_q, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, h, seq // tile_k),
            in_specs=[spec["q_whole"], spec["kv_tile"], spec["kv_tile"],
                      spec["bits_whole"], spec["q_whole"], spec["stat_whole"],
                      spec["stat_whole"]],
            out_specs=[spec["part_tile"], spec["part_tile"]]),
        out_shape=[part, part], compiler_params=_params(),
        interpret=interpret, name="hvd_flash_sel_dkv")(
            lists, counts, q, k, v, bits, do, lse, delta)

    def over_group(x):          # [B, H, S, D] float32 -> [B, S, G x D]
        x = jnp.sum(x.reshape(batch, g, group, seq, width), axis=2)
        return jnp.moveaxis(x, 1, 2).reshape(batch, seq, g * width).astype(
            k.dtype)

    return dq, over_group(dk), over_group(dv), None


_select_attention.defvjp(_select_fwd, _select_bwd)


def flash_select(q, k, v, select: Selection, scale: Optional[float] = None,
                 *, tile_q: Optional[int] = None, step_k: Optional[int] = None,
                 tile_k: Optional[int] = None, step_q: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """``(out, lse)`` of causal attention over q [B, S, H, D] and k, v [B, S,
    G, D] in which query t sees the keys at or before t inside the blocks
    ``select`` says it chose (every head of a group under its key/value
    head's choice); lse [B, H, S].  **Every query must have chosen a block
    that holds a key it can see** (its own block, under step 5).  On a TPU
    the kernels; elsewhere :func:`dense_select` unless ``interpret=True``."""
    batch, seq, heads, width = q.shape
    groups = k.shape[2]
    scale = width ** -0.5 if scale is None else scale
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dense_select(q, k, v, select, scale)
        interpret = False
    sizes = tuple(min(given or default, seq) for given, default in (
        (tile_q, TILE_Q), (step_k, STEP_K), (tile_k, TILE_K),
        (step_q, STEP_Q)))
    _check_sizes(seq, width, q.dtype.itemsize, select.block, *sizes)
    flat = lambda x: x.reshape(batch, seq, -1)  # noqa: E731
    out, lse = _select_attention(
        flat(q), flat(k), flat(v), select.bits, select.block, float(scale),
        (heads, groups), sizes, bool(interpret))
    return out.reshape(batch, seq, heads, width), lse.reshape(batch, heads,
                                                              seq)
