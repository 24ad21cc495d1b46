"""Flash attention as a Pallas TPU kernel.

The reference's only custom kernels are CUDA memcpy/scale helpers
(horovod/common/ops/cuda/cuda_kernels.cu; SURVEY.md §2.2) — its models come
from torch/TF.  This framework owns its model zoo, so the hot op worth a
hand kernel on TPU is attention: this kernel keeps the [S, S] score matrix
out of HBM entirely (VMEM-blocked online softmax), the classic
flash-attention trade.

Layout: inputs [batch, seq, heads, head_dim].  The kernels read and write
the model's own [batch, seq, heads * head_dim] (a reshape of what a flat
projection produces: no data moves; models/flat_dense.py), a **128-lane
column block** of it a grid step: ``G = 128 // head_dim`` heads side by side
(two at 64, four at 32; one head where ``head_dim`` is whole lane tiles), on
(batch * heads / G, n_resident, n_streamed) grids.  Inside a grid step the
G heads are walked by a static loop, G independent dot -> reduce -> exp ->
dot chains a step: the forward slices each head's lanes out of the block
and stacks the heads' acc^T for one lane-dense store; dq and dkv keep every
operand whole and zero the other heads' lanes in one operand of each dot
(settled on the chip, PERF.md PR 28).  Where no whole
number of heads fills 128 lanes (``head_dim`` 80; three heads of 64) the
same kernels run with G = 1 on operands turned round to
[batch * heads, seq, head_dim], under the scope ``hvd_flash_relayout``.
:func:`tile_plan` decides from ``head_dim`` and ``heads``; nothing else
does.  Either way a block of queries (forward, dQ) or of keys (dK/dV) stays
resident while blocks of the other operand stream through VMEM, and the
online-softmax state (acc/m/l, or the dq/dk/dv partials) persists in f32
scratch across the innermost grid steps — so sequence length is HBM-bound,
not VMEM-bound.

The schedule has three nested sizes, chosen from the shape by
:func:`tile_plan` (no knob): a grid **block** as large as a VMEM budget
allows (the whole sequence up to 2048 x 128 in bf16, so a grid step a
head: the fixed price of a grid step is paid a few hundred times a call,
not once per 128 x 128 tile); inside a grid step a resident **tile** of up
to 1024 rows walks the streamed block in **steps** of up to 256 rows, each
step one large score tile for the scheduler to overlap dots, exps and
reductions in.  Under a causal mask the walk stops at the diagonal: the
steps before it run as one ``fori_loop`` without a mask, and of the static
``tile // step`` steps the diagonal crosses, each takes only the rows of
the tile it can see (a 1024-row tile in 256-row steps computes 0.625 of
the square; whole 128 x 128 tiles would take 0.5625, a 1024 x 1024 tile
all of it).  Grid tiles wholly above the diagonal or in tail padding are
predicated off and their index maps hold the nearest live block, so they
cost no copy.  ``block_q`` and ``block_k`` need not be equal.  Under the
causal mask and under ``kv_lens`` every processed row meets a valid key in
the first step it takes (column 0 under a causal mask; a real key
otherwise), which keeps the running max finite with a -1e30 mask value: no
NaN guards needed.  The third mask keeps that by the order of its visits.

Three masks: none or the causal diagonal (``causal``), a key length per
sequence (``kv_lens``, below), and **block diffusion**
(``block_diffusion=(L, B)``, :func:`block_diffusion_mask`): the sequence is
``[clean ; noised]``, two copies of L positions in blocks of B, and the live
area is L^2 (1 + B / L) of the (2L)^2 square.  It is ONE call of the
kernels on the model's own arrays (:func:`_flash_block_diffusion`): the two
copies are taken as neighbouring sequences of L rows whose index maps read
the same (clean) keys, clean queries on the clean blocks up to their own and
noised queries on the clean blocks before theirs, with the causal walks and
one comparison of block numbers in the masked steps, ``<=`` or ``<`` by the
grid row.  The noised copy's own blocks, L x B pairs a head, are squares on
the diagonal that the same kernels visit (:func:`_own_side`): a noised grid
row holds its own copy's k and v at the resident block's positions through
a second pair of block specs on the same arrays, and meets them under the
mask "same block" before anything else.  A noised row of the first block
sees **no clean key**, but by then it has met itself: its running maximum
and its lse are real scores, and a hidden pair's probability is an
exponential that underflows to zero, as under the other masks.

**Grouped-query heads**: k and v may have fewer heads than q (query head
``i`` reads key/value head ``i // group``).  The forward and dq take a query
head a grid row and their key/value index maps name its group's head; the
dkv kernel takes a key/value head a grid row and its streamed axis walks
the query blocks of the group's heads one after another, so dk / dv gather
the whole group in float32 before they leave.  A grouped call holds one head
a grid step (several query heads side by side would want as many different
key/value heads beside each other).  Calls with as many key/value heads as
query heads and no block-diffusion mask compile to what they were; the
others name their kernels ``hvd_flash_fwd`` / ``hvd_flash_dq`` /
``hvd_flash_dkv``.

**A window** (``window=W`` on a causal call: query i sees the W keys ``i - W
< j <= i``, its own among them) is a band under the diagonal, and a banded
call has a schedule of its own (:func:`band_of`; ``_band_fwd_kernel``,
``_band_dq_kernel``, ``_band_dkv_kernel``), which shares the score's
arithmetic with the kernels above and nothing of their grid or walk.  Its
grid has no streamed axis: a grid step holds a resident block (the plan's,
2,048 rows at 16,384 x 128 in bf16) and, as operands of their own, the rows
of the other operand that the band reaches beside it (the 512 rows before a
query block under a band of 512; after a key block in dkv), so every grid
step is live and a sub-tile's whole span is in VMEM at once.  The block is
cut into sub-tiles of one step's rows (256); a sub-tile takes the diagonal's
step and the ``(W - 2) // step + 1`` before it (after it, in dkv), W rounded
up to steps and a step more keys a row whatever tile the row is in (768 at
512 / 256).  Its statistics and accumulators are values from its first step
to its last, stored once; the forward starts at the diagonal's step, which
shows every row a key it sees, so the running maximum is a real score before
a far-edge step hides a row's whole step, and it needs no scratch, no init
and no flush.  The steps wholly inside the band take no mask; the diagonal's
takes ``k <= q`` and a step the far edge crosses ``q - k < W``, both on the
difference of local positions alone, built once a grid step
(``_band_masks``).  Two sub-tiles take each step side by side and a block's
loop trips are written out, so the scheduler has several independent chains
in one basic block; the trips that reach beside the block are written once
for the sequence's edge and once for its inside, so none holds a branch
(``_band_walk``).  A grouped dkv takes the group's query heads along a third
grid axis and gathers dk, dv in float32 scratch, read and written once a
sub-tile and head.  **Read on a v5e** (PERF.md, PR 64; forward / forward and
backward of one call, 9 query heads on 1 key/value head, 16,384 x 128 in
bf16, window 512, by resident block x sub-tiles side by side): the causal
square's walk cut by a far edge, which this replaced, 1.386 / 4.787 ms; 512 x
2: 0.815 / 2.462, 512 x 1: 0.824 / 2.466, 1,024 x 1: 0.919 / 2.481, 1,024 x
2: 0.793 / 2.329, 1,024 x 4: 0.699 / 2.165, 2,048 x 1: 0.959 / 2.576, 2,048 x
2: 0.815 / 2.333 (0.795 / 2.274 again), 2,048 x 4: 0.670 / 2.100, and 2,048 x
2 with the block's trips written out, the form kept: 0.689 / 2.080.  One
window is measured.  The kernels are named ``hvd_flash_swa_fwd`` / ``_dq`` /
``_dkv``; ``window=None`` or a window of the whole sequence is the causal
call, text for text.

**A second score operand** (``q_rope`` [B, S, H, r] with ``k_rope`` [B, S, 1,
r]: multi-head latent attention, whose scores are ``q . k + q_rope . k_rope``
under one softmax, the rotary key one for all heads, v and the output as wide
as q and k): the three kernels gain the pair as operands of their own and
build a score tile from two dots accumulated in float32 before the mask; dq
returns ``dq_rope`` beside dq, dkv a third gradient, ``dk_rope``, which every
held head owes the one key: a float32 partial a grid row, summed outside.
``k_rope`` is read from its one [B, S, r] array by every head (its block is
the array's whole minor dimension) and nothing is copied a head.  A grid step
holds two heads (``_rope_heads``: their 64-wide rotary parts fill the one
lane tile a block's minor dimension must be, their 128-wide parts lie side by
side, 256 lanes), each head's operands a slice of whole lane tiles and a
chain of its own; the walks (``_k_walk``, ``_q_walk``), ``_scores``, the
statistics and ``delta`` are the causal call's.  The forward takes the pair
in the kernel it had, whose heads were slices and chains already.  The
backward is two kernels of its own (``_mla_dq_kernel``, ``_mla_dkv_kernel``)
beside ``_mha_bwd_*``, because those run a block's heads in the zero-lane
form (:func:`_only`: every operand whole, the other heads' lanes zeroed, one
accumulator and one dot for all heads' dk and dv), which costs nothing at
64-wide heads, where the zeros ride in the half of the MXU a head leaves
idle, and at two 128-wide heads would double every product's passes.  The
kernels are named ``hvd_flash_mla_fwd`` / ``_dq`` / ``_dkv``; the pair goes
with no length, no block-diffusion mask, no window and no grouped heads (each
raises by name), and a call without the pair is what it was, text for text.

``kv_lens`` (non-causal calls: an int32 length per sequence, BERT's padding
mask) reaches the kernels as a scalar-prefetch operand, one length per
row of the grid (a row's heads are one sequence's): the same walks then end at that
sequence's last real key where they otherwise end at the static
``valid_len``, and rows at or beyond the length come out zero.  Without it
the kernels and their operands are what they were.

Every dot takes its operands in the dtype they arrive in (bf16 at the
MXU's native rate, float32 accumulation); softmax math is float32.  The
forward and dK/dV build the score tile transposed ([keys, queries]), so
the row statistics are lane-dense rows that reduce and broadcast along
sublanes, and cross HBM as one float32 a row ([BH / G, G, S]).  The
backward's ``delta = rowsum(dO * O)`` is made inside dq and dkv from the dO
and O blocks they hold: a head's lanes of the model's layout are nothing
XLA can reduce over without relayouting the product.

Off-TPU (CPU tests) the public wrapper falls back to an identical-math
dense implementation; the kernels are unit-tested in interpret mode and
validated on hardware by chip_smoke.py's ``kernels`` phase.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

LANES = 128          # a vreg's lane count and the MXU's width
# The largest resident tile and the largest step of the kernels' inner walk
# (see tile_plan).  Read on a v5e at 128 x 1024 x 64 in bf16 (PERF.md, PR 26).
_MAX_TILE = 1024
_MAX_STEP = 256
# The sub-tiles a loop trip of a banded call's kernels takes side by side
# (see _band_plan).
_BAND_CHAINS = 2
# What tile_plan lets the hungriest kernel (dkv) hold in VMEM, and what the
# pallas_calls ask Mosaic for: the rest is headroom for the compiler's own
# temporaries (spilled score tiles, relayouts).  A v5e / v6e core has
# 128 MiB of VMEM, a v7x core 64 MiB.
_VMEM_BUDGET = 16 * 1024 * 1024
_VMEM_LIMIT = 2 * _VMEM_BUDGET
# The same for a call with a second score operand, whose grid step holds two
# whole-tile heads: three quarters of the limit.  Set from what Mosaic keeps:
# compiled for a v5e at 4 heads of 128 + 64 over 16,384 rows in bf16, the
# three kernels in blocks of 1,024 rows fit a limit of 24 MiB and not one of
# 22 (dq the hungriest; tile_plan's estimate reads 23.6), and in blocks of
# 2,048 one of 32 and not one of 30 (the estimate reads 37.3 and refuses
# them): PERF.md, PR 54.
_VMEM_BUDGET_PAIR = 3 * _VMEM_LIMIT // 4

_NT = (((1,), (1,)), ((), ()))      # A @ B^T: contract the minor dims
_TN = (((0,), (0,)), ((), ()))      # A^T @ B: contract the major dims


class TilePlan(NamedTuple):
    """The schedule and block layout of one flash_attention call (see
    :func:`tile_plan`)."""
    seq_pad: int       # the sequence length the kernels see
    block_q: int       # grid block of queries: resident in fwd / dq
    block_k: int       # grid block of keys: resident in dkv
    tile_q: int        # resident tile of a query block (fwd / dq)
    tile_k: int        # resident tile of a key block (dkv)
    step_q: int        # dkv walks a query block in steps of this many rows
    step_k: int        # fwd / dq walk a key block in steps of this many rows
    vmem_bytes: int    # estimate for the hungriest kernel (dkv)
    heads_per_block: int   # heads a grid step holds side by side (G)
    lanes: int         # lanes a block: heads_per_block * head_dim

    @property
    def lane_dense(self) -> bool:
        """Whether a block fills whole 128-lane tiles: the kernels then
        take [batch, seq, heads * head_dim] as the model holds it.  Else
        the operands are relayouted to [batch * heads, seq, head_dim]."""
        return self.lanes % LANES == 0

    def grid_steps(self, batch_heads: int) -> int:
        return (batch_heads // self.heads_per_block
                * (self.seq_pad // self.block_q)
                * (self.seq_pad // self.block_k))


class _Variant(NamedTuple):
    """What a call adds to the plain kernels (all static).  ``group``: query
    heads a key/value head (1: as many of each).  ``bd``: the block length
    of a block-diffusion call, 0 without; the kernels then see the clean and
    the noised copy as neighbouring sequences (even, odd) of ``q_rows``
    grid rows each, ``kv_rows`` in the dkv kernel's grid.  ``window``: the
    keys a query of a banded causal call sees, 0 without a band.  ``rope``:
    the width of a head's second score operand (``q_rope`` / ``k_rope``), 0
    without the pair."""
    group: int = 1
    bd: int = 0
    q_rows: int = 1
    kv_rows: int = 1
    window: int = 0
    rope: int = 0


_PLAIN = _Variant()


def heads_per_block(head_dim: int, heads: int) -> int:
    """How many heads fill a 128-lane column block of [B, S, H * D]: two at
    64, four at 32, one where ``head_dim`` is whole lane tiles already.
    Where no whole number of heads does (a ``head_dim`` that does not
    divide 128, fewer heads than fill it), one: the fallback layout."""
    g = LANES // head_dim if LANES % head_dim == 0 else 1
    return g if heads % g == 0 else 1


def _rope_heads(head_dim: int, heads: int, rope: int) -> int:
    """Heads a grid step of a call with a second score operand: the two whose
    ``rope``-wide parts fill a lane tile.  The ``head_dim``-wide parts must be
    whole lane tiles, so that a head's operands are slices of them.  Two heads
    of 64 rotary lanes are what was built and run on a chip; another width
    is refused here and not planned on a guess."""
    g = 2
    if head_dim % LANES or g * rope != LANES or heads % g:
        raise ValueError(
            f"q_rope / k_rope {rope} wide on {heads} heads of {head_dim}: "
            f"built for heads of whole {LANES}-lane tiles, {g} at a time, "
            f"whose rotary parts fill one tile ({LANES // g} lanes each)")
    return g


def _vmem_estimate(block_q, block_k, tile, step, lanes, heads, itemsize):
    """VMEM bytes of the dkv kernel, the hungriest of the three: q, dO, O,
    k, v blocks and the dk, dv outputs double-buffered by the pipeline (the
    minor dim padded to the lane count: nothing where the block is
    lane-dense), the two row statistics (a row a head, in whole 8-sublane
    tiles), the two float32 accumulators, and one step's float32 score, p,
    dp and ds tiles with their casts for each of the block's heads (their
    chains are independent, so the scheduler may hold them all)."""
    width = -(-lanes // LANES) * LANES
    stat_rows = -(-heads // 8) * 8
    streamed = 2 * block_q * (3 * width * itemsize + 2 * stat_rows * 4)
    resident = 2 * 4 * block_k * width * itemsize             # k, v, dk, dv
    accumulators = 2 * block_k * width * 4
    one_step = heads * tile * step * (4 * 4 + 2 * itemsize)
    return streamed + resident + accumulators + one_step


def _divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` under ``cap``, in whole LANES if ``n``
    is (what the TPU's tiling wants; an explicit small block is not)."""
    unit = LANES if n % LANES == 0 and cap >= LANES else 1
    return next(d for d in range(min(n, cap) // unit * unit, 0, -unit)
                if n % d == 0)


def tile_plan(seq: int, head_dim: int, itemsize: int, causal: bool,
              block_q: Optional[int] = None,
              block_k: Optional[int] = None, heads: int = 1,
              window: Optional[int] = None, rope: int = 0) -> TilePlan:
    """Choose the schedule and the block layout from the shape.  Pure:
    shapes in, sizes out.

    The layout: a grid step holds :func:`heads_per_block` heads side by
    side, a 128-lane column block of [B, S, H * D] (``heads`` = 1, the
    default, can fill 128 lanes only with a ``head_dim`` of whole lane
    tiles).

    Three sizes nest.  A grid **block** is what a grid step holds in VMEM.
    Without explicit blocks the sequence is padded to a multiple of LANES
    and both blocks are the largest divisor of it (in whole LANES) whose
    VMEM estimate fits the budget: at GPT-2-medium's 1024 x 64 in bf16 the
    whole sequence, so a call's grid is one step a pair of heads.  An
    explicit block is honoured in either layout (clipped to the sequence;
    the sequence padded to the blocks' least common multiple).  A resident
    **tile** (at most _MAX_TILE rows of the block that stays) walks the
    other block in **steps** (at most _MAX_STEP rows); a step divides the
    tile it walks past, so the causal diagonal crosses a tile in a whole
    number of steps.  ``causal`` does not change the sizes: the kernels'
    walks stop at the diagonal whatever they are.  Under a ``window`` the
    three sizes are the band's own (:func:`_band_plan`).

    With a second score operand ``rope`` lanes wide a head (``q_rope`` /
    ``k_rope``) a grid step holds the two heads whose rotary parts fill a
    lane tile (``_rope_heads``), their ``head_dim``-wide parts side by side in
    ``lanes``; the rotary blocks count in the estimate as lanes more, and the
    estimate is held to ``_VMEM_BUDGET_PAIR``: blocks of 1,024 rows at 4 heads
    of 128 + 64 over 16,384 rows in bf16 (on a v5e forward and backward of a
    call read 16.0 ms in them and 19.4 in blocks of 512: PERF.md, PR 54).
    """
    del causal
    g = _rope_heads(head_dim, heads, rope) if rope else heads_per_block(
        head_dim, heads)
    lanes = g * head_dim
    held = lanes + g * rope         # the estimate's lanes
    budget = _VMEM_BUDGET_PAIR if rope else _VMEM_BUDGET
    if block_q is None and block_k is None:
        seq_pad = -(-seq // LANES) * LANES
        block_q = block_k = next(
            b for b in range(seq_pad, 0, -LANES) if seq_pad % b == 0
            and _vmem_estimate(b, b, min(b, _MAX_TILE), min(b, _MAX_STEP),
                               held, g, itemsize) <= budget)
    else:
        block_q = min(block_q if block_q is not None else block_k, seq)
        block_k = min(block_k if block_k is not None else block_q, seq)
        lcm = math.lcm(block_q, block_k)
        seq_pad = -(-seq // lcm) * lcm
    if window is not None:
        return _band_plan(seq_pad, block_q, block_k, window, held, g,
                          itemsize, lanes)
    tile_q, tile_k = _divisor(block_q, _MAX_TILE), _divisor(block_k, _MAX_TILE)
    step_q = math.gcd(_divisor(block_q, _MAX_STEP), tile_k)
    step_k = math.gcd(_divisor(block_k, _MAX_STEP), tile_q)
    vmem = _vmem_estimate(block_q, block_k, max(tile_q, tile_k),
                          max(step_q, step_k), held, g, itemsize)
    return TilePlan(seq_pad, block_q, block_k, tile_q, tile_k, step_q,
                    step_k, vmem, g, lanes)


class Band(NamedTuple):
    """The schedule of one kernel of a banded call (:func:`band_of`): its
    resident ``block`` is cut into sub-tiles of ``step`` rows, each of which
    takes ``steps`` steps of ``step`` rows of the other operand, the
    diagonal's and the ``steps - 1`` on the band's side of it, ``chains``
    sub-tiles side by side a loop trip.  The rows on the band's side of the
    resident block reach the kernel as ``n_beside`` blocks of ``beside`` rows
    each (one, a divisor of the block, where the band reaches no further
    than a block; else whole blocks)."""
    block: int
    step: int
    steps: int
    chains: int
    beside: int
    n_beside: int

    @property
    def rows_visited(self) -> int:
        """Rows of the other operand that a row of the block is computed
        against: the window rounded up to steps, and a step for the
        diagonal's."""
        return self.steps * self.step


def band_of(block: int, step: int, tile: int, window: int) -> Band:
    """The band's schedule for a resident ``block`` walked in ``step``s, a
    loop trip ``tile`` rows.  A sub-tile of ``step`` rows at row ``r0`` and
    the step ``j`` steps away from its own meet in pairs ``j * step - step
    < q - k < j * step + step``: some of them inside a band of ``window``
    keys up to ``j = (window - 2) // step + 1``."""
    far = (window - 2) // step + 1 if window > 1 else 0
    reach = far * step
    if reach <= block:
        beside = next((d for d in range(reach, block + 1, step)
                       if block % d == 0), 0) if reach else 0
        n_beside = 1 if reach else 0
    else:
        beside, n_beside = block, -(-reach // block)
    return Band(block, step, far + 1, tile // step, beside, n_beside)


def _band_plan(seq_pad, block_q, block_k, window, held, g, itemsize,
               lanes) -> TilePlan:
    """:func:`tile_plan` under a ``window``: the grid blocks as without one
    (a banded call's kernels have no streamed grid axis: a grid step holds a
    resident block and the rows of the other operand that the band reaches
    beside it, :func:`band_of`).  A step is a sub-tile's rows and a key
    step's keys alike (at most _MAX_STEP), and ``tile_q`` / ``tile_k`` the
    rows a loop trip takes side by side, ``_BAND_CHAINS`` sub-tiles where the
    block holds as many."""
    def sizes(block):
        step = _divisor(block, _MAX_STEP)
        return math.gcd(block // step, _BAND_CHAINS) * step, step

    (tile_q, step_k), (tile_k, step_q) = sizes(block_q), sizes(block_k)
    beside = max(band_of(block_q, step_k, tile_q, window).beside,
                 band_of(block_k, step_q, tile_k, window).beside)
    vmem = _vmem_estimate(block_q + beside, block_k, max(tile_q, tile_k),
                          max(step_q, step_k), held, g, itemsize)
    return TilePlan(seq_pad, block_q, block_k, tile_q, tile_k, step_q,
                    step_k, vmem, g, lanes)


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct for a pallas_call output, carrying the varying-
    manual-axes type of ``like`` so the kernel can run inside shard_map
    (check_vma requires outputs to declare their mesh-axis variance)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# batch*heads and the resident axis are independent; the streamed axis
# carries the accumulators.  One TensorCore (v5e) gains nothing from it.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _len_at(b, lens, valid_len):
    """The sequence length at grid row ``b``: an index map's trailing
    arguments are the scalar-prefetch refs, the per-row lengths if the call
    has them, else nothing and the length is the static one."""
    return lens[0][b] if lens else valid_len


def _last_live_k(iq, causal: bool, plan: TilePlan, valid_len):
    """The last key block a query block needs: the one that holds the end
    of the real sequence or, under a causal mask, the block's last row."""
    last = (valid_len - 1) // plan.block_k
    if causal:
        last = jnp.minimum(last, ((iq + 1) * plan.block_q - 1) // plan.block_k)
    return last


def _last_live_q(jk, plan: TilePlan, valid_len):
    """The last query block a key block needs (dkv): the one that holds the
    end of the real sequence."""
    del jk
    return (valid_len - 1) // plan.block_q


def _block_live(iq, jk, causal: bool, plan: TilePlan, valid_len):
    """Whether the (q-block iq, k-block jk) grid tile can contribute.  The
    grid of these kernels is the whole square, so a dead tile (above the
    causal diagonal or wholly tail padding) is still a grid step: its body
    is predicated off and its index maps hold the block of the nearest live
    step, so it costs neither dots nor copies.  (A banded call has a grid of
    its own, every step of it live: :func:`_band_fwd_kernel`.)"""
    return jnp.logical_and(jk <= _last_live_k(iq, causal, plan, valid_len),
                           iq * plan.block_q < valid_len)


def _steps(valid_len, step: int):
    """How many steps of a sequence hold a real row."""
    return (valid_len + step - 1) // step


def _diag_steps(first_step, count: int, block_first, n: int, last: int,
                body):
    """``body(d, j)`` for the d-th of ``count`` (static) consecutive steps
    of the sequence from ``first_step`` on, wherever that step is local
    step ``j`` of a grid block that holds steps ``block_first`` ..
    ``block_first + n``, and one of the ``last`` steps with a real row."""
    for d in range(count):
        j = first_step + d - block_first
        live = jnp.logical_and(jnp.logical_and(j >= 0, j < n),
                               first_step + d < last)
        pl.when(live)(functools.partial(body, d, j))


def _run(body, lo, hi, state):
    """Local steps [lo, hi) of a walk, or (``hi`` None) the one step ``lo``."""
    return (body(lo, state) if hi is None else
            jax.lax.fori_loop(lo, hi, body, state))


def _k_walk(row0, jk, causal: bool, plan: TilePlan, valid_len, visit):
    """fwd / dq: walk the key steps of key block ``jk`` that the query tile
    starting at row ``row0`` can see.  ``visit(off, masked, lo, hi)`` takes
    local steps [lo, hi) (``hi`` None: the one step ``lo``) with the tile's
    rows from ``off`` on.  Steps before the diagonal are seen whole, in one
    run; the diagonal crosses a static ``tile_q // step_k`` steps, and the
    d-th of them is seen by the rows from ``d * step_k`` on only, so the
    part of a tile above the diagonal is never computed.  Without a causal
    mask the run ends with the real keys, and a step that holds the end of
    them is masked (a length read in the kernel may end inside a step or
    not: the one masked step is then live only if it does)."""
    step = plan.step_k
    n = plan.block_k // step
    first, last = jk * n, _steps(valid_len, step)
    if causal:
        on_diag = row0 // step
        until = jnp.minimum(on_diag, last)
        visit(0, False, 0, jnp.clip(until - first, 0, n))
        _diag_steps(on_diag, plan.tile_q // step, first, n, last,
                    lambda d, j: visit(d * step, True, j, None))
    else:
        whole = valid_len // step
        visit(0, False, 0, jnp.clip(whole - first, 0, n))
        partial = last - whole if isinstance(valid_len, int) else 1
        _diag_steps(whole, partial, first, n, last,
                    lambda d, j: visit(0, True, j, None))


def _q_walk(col0, iq, last, causal: bool, plan: TilePlan, valid_len, visit):
    """dkv: walk the query steps of query block ``iq`` that see the key tile
    starting at column ``col0``, of the ``last`` steps with a real row.
    ``visit(size, masked, lo, hi)`` takes local steps [lo, hi) (``hi`` None:
    the one step ``lo``) with the tile's first ``size`` keys.  The steps
    after the diagonal see the tile whole, in one run up to the last real
    row (the rows past it carry a zero dO); of the static ``tile_k //
    step_q`` steps the diagonal crosses,
    the d-th sees the tile's first ``(d + 1) * step_q`` keys only.  Without
    a causal mask every step sees the whole tile, and padded keys are masked
    in each."""
    tile, step = plan.tile_k, plan.step_q
    n = plan.block_q // step
    first = iq * n
    hi = jnp.clip(last - first, 0, n)
    if causal:
        on_diag, count = col0 // step, tile // step
        _diag_steps(on_diag, count, first, n, last,
                    lambda d, j: visit((d + 1) * step, True, j, None))
        visit(tile, False, jnp.clip(on_diag + count - first, 0, hi), hi)
    elif isinstance(valid_len, int):
        visit(tile, valid_len < plan.seq_pad, 0, hi)
    else:
        # A length read in the kernel: a tile of real keys alone takes no
        # mask, a tile wholly beyond the length no step.
        whole = col0 + tile <= valid_len
        pl.when(whole)(lambda: visit(tile, False, 0, hi))
        pl.when(jnp.logical_and(jnp.logical_not(whole), col0 < valid_len))(
            lambda: visit(tile, True, 0, hi))


def _scores(q, k, row0, col0, masked: bool, *, sm_scale, causal, valid_len,
            transposed: bool = False, bd: int = 0, strict=0,
            own: bool = False, rope=None):
    """The float32 score tile q @ k^T * sm_scale ([Tq, Tk]; or its
    transpose k @ q^T), masked where the diagonal or the tail padding
    crosses it.  The dot takes its operands as they arrive.  ``rope``: the
    pair ``(q_rope, k_rope)`` of a second product, added in float32 before
    the scale."""
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if rope is not None:
        a, b = rope[::-1] if transposed else rope
        s = s + jax.lax.dot_general(a, b, _NT,
                                    preferred_element_type=jnp.float32)
    s = s * sm_scale
    if masked:
        q_axis, k_axis = (1, 0) if transposed else (0, 1)
        kpos = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
        if bd:
            # Block diffusion.  Over clean keys a clean query sees the blocks
            # up to its own, a noised one (``strict`` 1) those before it;
            # over the noised copy's keys (``own``) a noised query sees its
            # own block.
            qpos = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
            seen = (kpos // bd == qpos // bd if own else
                    kpos // bd + strict <= qpos // bd)
            s = jnp.where(seen, s, NEG_INF)
        elif causal:
            # Padding lives at the tail, so kpos > any real qpos: the
            # causal mask already excludes padded keys.
            qpos = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        else:
            s = jnp.where(kpos < valid_len, s, NEG_INF)
    return s


def _strict(variant: _Variant, rows: int):
    """1 in the grid rows of a noised copy (the odd sequences), else 0."""
    return (pl.program_id(0) // rows) % 2 if variant.bd else 0


def _own_side(step: int, bd: int) -> int:
    """Rows (= keys) of one square of a noised copy's visit to its own
    blocks: the positions ``[c * side, (c + 1) * side)`` of the noised
    queries against the same positions of the noised keys, under the mask
    "same block".  One lane tile where whole blocks fill it (the MXU's
    width: nothing smaller computes less), else the walk's own step, which
    whole blocks always fill."""
    return LANES if step % LANES == 0 and LANES % bd == 0 else step


def _own_squares(lo, hi, rows: int, side: int, square):
    """``square(c)`` for the squares of the stretches ``[lo, hi)`` of
    ``rows`` positions each (a resident tile's), square ``c`` at position
    ``c * side`` of the block.  A stretch's squares are written out one
    after another: a square alone is two to four small products that wait
    for each other, and only side by side do the squares fill the MXU
    (read on a v5e at 2 x 8,192 x 32 x 128: 128-row squares one a loop
    trip cost the three kernels 2.3 ms a call, eight a trip 1.0; PERF.md,
    PR 45)."""
    per = rows // side

    def stretch(t, _):
        for d in range(per):
            square(t * per + d)

    jax.lax.fori_loop(lo, hi, stretch, None)


def _head_lanes(plan: TilePlan):
    """The lanes of each head of a block, as static slices: of an operand
    block's minor dim, and of the rows of the forward's transposed
    accumulator."""
    d = plan.lanes // plan.heads_per_block
    return [slice(g * d, (g + 1) * d) for g in range(plan.heads_per_block)]


def _rope_lanes(plan: TilePlan, rope: int):
    """The lanes of each head's rotary part in a ``q_rope`` block."""
    return [slice(g * rope, (g + 1) * rope)
            for g in range(plan.heads_per_block)]


def _only(x, h, plan: TilePlan):
    """``x`` [T, lanes] with the lanes of every head but ``h`` zeroed: as
    an operand of a dot it contributes head ``h`` alone, and the zeros ride
    in the half of the MXU a 64-wide operand leaves idle."""
    if plan.heads_per_block == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, plan.lanes), 1)
    return jnp.where((lane >= h.start) & (lane < h.stop), x,
                     jnp.zeros_like(x))


def _row_to_col(row):
    """[1, T] -> [T, 1]: dq wants the statistics along the score's rows."""
    t = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (min(t, LANES), t)))[:, :1]


def _mha_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float, causal: bool,
                plan: TilePlan, valid_len, variant: _Variant = _PLAIN):
    """Forward: grid (BH / G, n_q, n_kv).  A query block stays resident
    while key/value blocks stream past it.  The score tile is built
    transposed ([Tk, Tq] = k @ q^T): the online-softmax statistics are then
    rows ([1, Tq], one vreg per 1024 queries where a column takes one per
    8), their reductions run down the sublanes on the VPU, and the
    accumulator is acc^T [D, Tq]; m / l / acc^T persist in scratch across
    the kv grid steps and ride in registers inside a run of steps.  The
    block's G heads take each step together, G independent chains; their
    acc^T stack to [G * D, Tq], which leaves as one lane-dense [Tq, G * D]
    store.

    ``rest``: the outputs o, lse and the scratch acc^T, m, l; before them,
    in a block-diffusion call, the noised copy's own k and v at the resident
    query block's positions (:func:`_own_side`), or, in a call with a second
    score operand, ``q_rope``'s block and ``k_rope``'s."""
    if variant.bd:
        k_own_ref, v_own_ref, *rest = rest
    if variant.rope:
        qr_ref, kr_ref, *rest = rest
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)
    tile, step = plan.tile_q, plan.step_k
    heads = _head_lanes(plan)
    bd, strict = variant.bd, _strict(variant, variant.q_rows)
    score = functools.partial(_scores, sm_scale=sm_scale, causal=causal,
                              valid_len=valid_len, transposed=True, bd=bd,
                              strict=strict)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    if bd:
        # A noised row starts with its own block, which holds the row
        # itself: its running maximum is a real score before the walk over
        # the clean keys shows it a hidden pair (a row of the first block
        # sees no clean key at all), so the mask value never stands in for
        # a maximum and exp(s - m) is zero wherever the mask hides a pair.
        @pl.when(jnp.logical_and(jk == 0, strict == 1))
        def _own():
            side = _own_side(step, bd)

            def square(c):
                rows = pl.ds(pl.multiple_of(c * side, side), side)
                for g, h in enumerate(heads):
                    v = v_own_ref[rows, h]
                    s = score(q_ref[rows, h], k_own_ref[rows, h], 0, 0, True,
                              own=True)                     # [Tk, Tq]
                    m = jnp.max(s, axis=0, keepdims=True)
                    p = jnp.exp(s - m)
                    m_ref[g:g + 1, rows] = m
                    l_ref[g:g + 1, rows] = jnp.sum(p, axis=0, keepdims=True)
                    acc_ref[h, rows] = jax.lax.dot_general(
                        v, p.astype(v.dtype), _TN,
                        preferred_element_type=jnp.float32)

            _own_squares(0, plan.block_q // tile, tile, side, square)

    @pl.when(_block_live(iq, jk, causal, plan, valid_len))
    def _compute():
        def q_tile(c, _):
            start = pl.multiple_of(c * tile, tile)
            row0 = iq * plan.block_q + c * tile

            def visit(off, masked, lo, hi):
                rows = pl.ds(start + off, tile - off)
                qs = [q_ref[rows, h] for h in heads]        # [Tq, D] a head
                qrs = [qr_ref[rows, r] for r in _rope_lanes(
                    plan, variant.rope)] if variant.rope else None

                def body(j, state):
                    cols = pl.ds(pl.multiple_of(j * step, step), step)
                    col0 = jk * plan.block_k + j * step
                    kr = kr_ref[cols, :] if qrs else None   # [Tk, r]
                    ropes = ([(qr, kr) for qr in qrs] if qrs
                             else [None] * len(qs))
                    new = []
                    for q, rope, h, (m, l, acc) in zip(qs, ropes, heads,
                                                       state):
                        # m, l [1, Tq]; acc [D, Tq]
                        v = v_ref[cols, h]                  # [Tk, D]
                        s = score(q, k_ref[cols, h], row0 + off, col0,
                                  masked, rope=rope)
                        m_new = jnp.maximum(
                            m, jnp.max(s, axis=0, keepdims=True))
                        p = jnp.exp(s - m_new)              # [Tk, Tq]
                        alpha = jnp.exp(m - m_new)
                        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
                        acc = acc * alpha + jax.lax.dot_general(  # v^T @ p
                            v, p.astype(v.dtype), _TN,
                            preferred_element_type=jnp.float32)
                        new.append((m_new, l, acc))
                    return tuple(new)

                state = _run(body, lo, hi, tuple(
                    (m_ref[g:g + 1, rows], l_ref[g:g + 1, rows],
                     acc_ref[h, rows]) for g, h in enumerate(heads)))
                for g, (h, (m, l, acc)) in enumerate(zip(heads, state)):
                    m_ref[g:g + 1, rows] = m
                    l_ref[g:g + 1, rows] = l
                    acc_ref[h, rows] = acc

            _k_walk(row0, jk, causal, plan, valid_len, visit)

        jax.lax.fori_loop(0, plan.block_q // tile, q_tile, None)

    @pl.when(jk == n_kv - 1)
    def _flush():
        def q_tile(c, _):
            rows = pl.ds(pl.multiple_of(c * tile, tile), tile)
            outs = []
            for g, h in enumerate(heads):
                l = jnp.maximum(l_ref[g:g + 1, rows], 1e-30)
                outs.append(acc_ref[h, rows] / l)           # [D, Tq]
                # Log-sum-exp per query row, the residual the backward
                # pass needs to re-materialize P = exp(S - lse) tile by
                # tile.
                lse_ref[g:g + 1, rows] = m_ref[g:g + 1, rows] + jnp.log(l)
            out = jnp.concatenate(outs, axis=0)             # [G * D, Tq]
            if not isinstance(valid_len, int):
                # A row at or beyond its sequence's length is padding.
                qpos = (iq * plan.block_q + c * tile
                        + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1))
                out = jnp.where(qpos < valid_len, out, 0.0)
            o_ref[rows, :] = jnp.transpose(out).astype(o_ref.dtype)

        jax.lax.fori_loop(0, plan.block_q // tile, q_tile, None)


def _operand_spec(rows: int, plan: TilePlan, n_col: int, seq_block,
                  row_of=None):
    """A [rows, lanes] block of an operand [N, S, n_col * lanes]: grid row
    ``r`` is column block ``r % n_col`` of sequence ``r // n_col`` (the
    model's [B, S, H * D] in 128-lane blocks; ``n_col`` is 1 and N = B * H
    in the fallback layout).  ``seq_block(r, i, j, lens)`` picks the block
    along the sequence.  ``row_of(r, j)``, where a call has one, names the
    operand's row for grid row ``r`` (a query head's key/value head, a
    noised copy's clean keys, the query heads a key/value head gathers)."""
    def index(r, i, j, *lens):
        row = r if row_of is None else row_of(r, j)
        return row // n_col, seq_block(r, i, j, lens), row % n_col

    return pl.BlockSpec((None, rows, plan.lanes), index)


def _row_stat_spec(plan: TilePlan, seq_block, row_of=None):
    """One float32 a row, lane-dense: a [BH / G, G, S] array in
    (G, block_q) blocks, a row a head of the grid row."""
    return pl.BlockSpec(
        (None, plan.heads_per_block, plan.block_q),
        lambda r, i, j, *lens: (r if row_of is None else row_of(r, j), 0,
                                seq_block(r, i, j, lens)))


def _clean_row(row, rows_per_seq: int):
    """The same row of the clean copy: sequences lie (clean, noised) by
    turns, ``rows_per_seq`` rows each."""
    return row - ((row // rows_per_seq) % 2) * rows_per_seq


def _kv_row_of(variant: _Variant, own: bool = False):
    """fwd / dq: the key/value row that the query row ``r`` reads; under
    the block-diffusion mask the clean copy's or (``own``) its own copy's."""
    if variant == _PLAIN:
        return None

    def row_of(r, j):
        row = r if variant.group == 1 else r // variant.group
        return (_clean_row(row, variant.kv_rows) if variant.bd and not own
                else row)

    return row_of


def _own_kv(variant: _Variant, plan: TilePlan, kb, vb):
    """``(in_specs, operands)`` that a block-diffusion call appends to the
    forward's and dq's: k and v once more, under a spec that keeps the grid
    row's own copy at the resident query block's positions, so the noised
    rows meet their own blocks where q already is.  Nothing without the
    mask."""
    if not variant.bd:
        return [], ()
    spec = _operand_spec(plan.block_q, plan, kb.shape[2] // plan.lanes,
                         _by_i, _kv_row_of(variant, own=True))
    return [spec, spec], (kb, vb)


def _by_i(r, i, j, lens):
    """The block of the resident grid axis."""
    return i


def _streamed_k(causal: bool, plan: TilePlan, valid_len):
    """Key/value blocks streaming past query block ``i`` (fwd, dq): a dead
    grid tile holds the block of the nearest live one, so it costs no
    copy."""
    def block(r, i, j, lens):
        return jnp.minimum(j, _last_live_k(i, causal, plan,
                                           _len_at(r, lens, valid_len)))

    return block


def _named(variant: _Variant, kernel: str) -> dict:
    """The kernel arguments a grouped or block-diffusion call adds: the
    variant, and a name by which a trace tells its three kernels apart
    (``hvd_flash_fwd`` / ``_dq`` / ``_dkv``; ``hvd_flash_mla_fwd`` / ``_dq``
    / ``_dkv`` with a second score operand; a banded call names its own:
    ``_band_call``).  A plain call adds neither, so its kernels compile to
    what they were."""
    pair = "mla_" if variant.rope else ""
    return {} if variant == _PLAIN else {
        "variant": variant, "name": f"hvd_flash_{pair}{kernel}"}


def _kernel_call(kernel, lens, *, grid, in_specs, out_specs, out_shape,
                 scratch_shapes, interpret, name=None, **kernel_args):
    """The ``pallas_call`` of one of the three kernels.  ``lens`` None: the
    kernel's ``valid_len`` is the static one in ``kernel_args``.  Else
    ``lens`` (int32, one per grid row) is a scalar-prefetch operand and
    each grid row takes its own length from it."""
    call_args = dict(out_shape=out_shape, compiler_params=_COMPILER_PARAMS,
                     interpret=interpret, **({"name": name} if name else {}))
    if lens is None:
        return pl.pallas_call(
            functools.partial(kernel, **kernel_args), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **call_args)

    def per_sequence(lens_ref, *refs):
        kernel(*refs, **{**kernel_args,
                         "valid_len": lens_ref[pl.program_id(0)]})

    return functools.partial(pl.pallas_call(
        per_sequence, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        **call_args), lens)


# Inlined jits: a model calls these once a layer with the same shapes, and
# jit's cache then traces each kernel once a process and not once a layer
# (the step is traced in every process; that time is part of a job's
# start).  ``inline`` leaves no call in the jaxpr, so an op's name keeps the
# scope of the layer that made it.
def _rope_specs(variant: _Variant, plan: TilePlan, n_col: int, q_block,
                k_block):
    """The block specs of ``q_rope`` [N, S, n_col * G * r] (``q_block``
    names its block along the sequence) and of ``k_rope`` [N, S, r], one key
    for every head of a sequence: the array's whole minor dimension, read
    where it lies by each grid row."""
    r = variant.rope
    qr = pl.BlockSpec(
        (None, plan.block_q, plan.heads_per_block * r),
        lambda row, i, j: (row // n_col, q_block(row, i, j, ()), row % n_col))
    kr = pl.BlockSpec(
        (None, plan.block_k, r),
        lambda row, i, j: (row // n_col, k_block(row, i, j, ()), 0))
    return qr, kr


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 9), inline=True)
def _flash_fwd(qb, kb, vb, sm_scale, causal, plan, interpret, valid_len,
               lens=None, variant=_PLAIN, rope=()):
    """Forward kernel over operands [N, S, n_col * lanes] (S already
    padded; see :func:`_operand_spec`): out likewise + the rows' lse
    [BH / G, G, S].  ``rope``: ``(q_rope, k_rope)`` in the kernels' layout
    (:func:`_rope_specs`) where ``variant.rope``."""
    if variant.window:
        return _band_fwd(qb, kb, vb, sm_scale, plan, interpret, variant)
    n, s, width = qb.shape
    n_col, g = width // plan.lanes, plan.heads_per_block
    bq, bk = plan.block_q, plan.block_k
    q_spec = _operand_spec(bq, plan, n_col, _by_i)
    kv_spec = _operand_spec(bk, plan, kb.shape[2] // plan.lanes,
                            _streamed_k(causal, plan, valid_len),
                            _kv_row_of(variant))
    # What the variant's mask or second operand adds to q, k and v.
    more_specs, more = (
        (_rope_specs(variant, plan, n_col, _by_i,
                     _streamed_k(causal, plan, valid_len)), rope)
        if variant.rope else _own_kv(variant, plan, kb, vb))
    return _kernel_call(
        _mha_kernel, lens, sm_scale=sm_scale, causal=causal, plan=plan,
        valid_len=valid_len, interpret=interpret, **_named(variant, "fwd"),
        grid=(n * n_col, s // bq, s // bk),
        in_specs=[q_spec, kv_spec, kv_spec, *more_specs],
        out_specs=[q_spec, _row_stat_spec(plan, _by_i)],
        out_shape=[
            _out_struct(qb.shape, qb.dtype, qb),
            _out_struct((n * n_col, g, s), jnp.float32, qb),
        ],
        scratch_shapes=[
            pltpu.VMEM((plan.lanes, bq), jnp.float32),
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq), jnp.float32),
        ],
    )(qb, kb, vb, *more)


def _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan: TilePlan,
                rows: Optional[int] = None, at: int = 0):
    """delta_i = rowsum(dO_i * O_i) - dlse_i of each head of a query block,
    the standard backward residual, into ``delta_ref`` [G, block_q] as
    lane-dense rows like lse.  (An lse cotangent folds in here: both enter
    as ``ds = p * (dp - delta)``.)  It is made where it is used: a head's
    ``head_dim`` lanes of the model's layout are no dimension XLA could
    reduce over without turning the whole product round first.  ``rows``:
    how many the blocks hold (a query block's), and ``at``: where in
    ``delta_ref`` theirs begin (a banded dkv's blocks, own and beside)."""
    step = plan.step_q

    def chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * step, step), step)
        into = pl.ds(pl.multiple_of(at + c * step, step), step) if at else rows
        prod = jnp.transpose(do_ref[rows, :].astype(jnp.float32)
                             * o_ref[rows, :].astype(jnp.float32))
        for g, h in enumerate(_head_lanes(plan)):           # [lanes, Tq]
            delta_ref[g:g + 1, into] = (
                jnp.sum(prod[h], axis=0, keepdims=True)
                - dlse_ref[g:g + 1, rows])

    jax.lax.fori_loop(0, (rows or plan.block_q) // step, chunk, None)


def _mha_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
                       *rest, sm_scale: float, causal: bool, plan: TilePlan,
                       valid_len, variant: _Variant = _PLAIN):
    """dQ: grid (BH / G, n_q, n_kv); key/value blocks stream past a
    resident query block while dq accumulates in f32 scratch.  P is
    re-materialized from the lse residual: the [S, S] score matrix never
    exists.

    The block's G heads share one [Tq, lanes] accumulator and every operand
    keeps all its lanes: a head's q and dO enter the score and dP dots with
    the other heads' lanes zeroed (:func:`_only`), its dS meets k zeroed
    likewise, so each dot writes its head's lanes and zeros elsewhere.  A
    64-wide operand fills half the MXU's contraction or output width as it
    is; the zeros ride in the other half (read on the chip, PERF.md PR 28:
    faster than slicing a head's lanes out and shifting the second head's
    back in).

    ``rest``: the output dq and the scratch acc, delta; before them, in a
    block-diffusion call, the noised copy's own k and v as in the
    forward."""
    if variant.bd:
        k_own_ref, v_own_ref, *rest = rest
    dq_ref, acc_ref, delta_ref = rest
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)
    tile, step = plan.tile_q, plan.step_k
    heads = _head_lanes(plan)
    bd, strict = variant.bd, _strict(variant, variant.q_rows)
    score = functools.partial(_scores, sm_scale=sm_scale, causal=causal,
                              valid_len=valid_len, bd=bd, strict=strict)

    def resident(rows):
        """Per head: a tile's q, dO with the other heads' lanes zeroed;
        lse, delta [Tq, 1]."""
        q, do = q_ref[rows, :], do_ref[rows, :]             # [Tq, lanes]
        return [(_only(q, h, plan), _only(do, h, plan),
                 _row_to_col(lse_ref[g:g + 1, rows]),
                 _row_to_col(delta_ref[g:g + 1, rows]))
                for g, h in enumerate(heads)]

    def gather(acc, tile_of, k, v, row0, off, col0, masked, **mask):
        """``acc`` [Tq, lanes] + what the keys k, v [Tk, lanes] from
        position ``col0`` on give the dq of a tile's rows from ``row0 + off``
        on, each head into its own lanes."""
        for (q, do, lse, delta), h in zip(tile_of, heads):
            s = score(q, k, row0 + off, col0, masked, **mask)
            p = jnp.exp(s - lse)                            # [Tq, Tk]
            dp = jax.lax.dot_general(
                do, v, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            acc = acc + jnp.dot(                            # head h's lanes
                ds.astype(k.dtype), _only(k, h, plan),
                preferred_element_type=jnp.float32)
        return acc

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan)

    if bd:
        @pl.when(jnp.logical_and(jk == 0, strict == 1))
        def _own():
            side = _own_side(step, bd)

            def square(c):
                rows = pl.ds(pl.multiple_of(c * side, side), side)
                acc_ref[rows, :] = gather(
                    acc_ref[rows, :], resident(rows), k_own_ref[rows, :],
                    v_own_ref[rows, :], 0, 0, 0, True, own=True)

            _own_squares(0, plan.block_q // tile, tile, side, square)

    @pl.when(_block_live(iq, jk, causal, plan, valid_len))
    def _compute():
        def q_tile(c, _):
            start = pl.multiple_of(c * tile, tile)
            row0 = iq * plan.block_q + c * tile

            def visit(off, masked, lo, hi):
                rows = pl.ds(start + off, tile - off)
                tile_of = resident(rows)

                def body(j, acc):                           # [Tq, lanes]
                    cols = pl.ds(pl.multiple_of(j * step, step), step)
                    col0 = jk * plan.block_k + j * step
                    return gather(acc, tile_of, k_ref[cols, :],
                                  v_ref[cols, :], row0, off, col0, masked)

                acc_ref[rows, :] = _run(body, lo, hi, acc_ref[rows, :])

            _k_walk(row0, jk, causal, plan, valid_len, visit)

        jax.lax.fori_loop(0, plan.block_q // tile, q_tile, None)

    @pl.when(jk == n_kv - 1)
    def _flush():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _mha_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
                        *rest, sm_scale: float, causal: bool, plan: TilePlan,
                        valid_len, variant: _Variant = _PLAIN):
    """dK/dV: grid (BH / G, n_kv, n_q); query/dO/statistic blocks stream
    past a resident key block while dk/dv accumulate in f32 scratch, the
    block's G heads side by side in [Tk, lanes] accumulators (operands
    whole, a head's k and v with the other heads' lanes zeroed, as in dq;
    the heads' p and dS tiles meet dO and q in one dot each).  The score
    tile is built transposed ([Tk, Tq] = k @ q^T), so the row statistics
    broadcast down the sublanes as they arrive and all four dots are
    plain: no operand is transposed on the way to the MXU.

    Which query steps a key tile meets, and how much of it each sees:
    :func:`_q_walk`.

    Grouped queries (``variant.group`` query heads read this key/value
    head): the streamed axis walks the query blocks of one head after
    another, ``group * n_q`` grid steps, and dk / dv gather all of them
    before they leave.

    ``rest``: the outputs dk, dv and the scratch dk, dv accumulators and
    delta.  A block-diffusion call has the noised copy's own k and v block
    before them and, after each of the three groups, what the own block
    gathers: outputs dk, dv and two accumulators more.  A noised grid row
    keeps the *clean* keys resident as k, v (what its queries owe them
    leaves as dk, dv and is the clean keys' by right) and beside them its
    own keys, which meet the queries of their own block
    (:func:`_own_side`)."""
    if variant.bd:
        (k_own_ref, v_own_ref, dk_ref, dv_ref, dk_own_ref, dv_own_ref,
         dk_acc, dv_acc, delta_ref, dk_own_acc, dv_own_acc) = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc, delta_ref = rest
    jk, t = pl.program_id(1), pl.program_id(2)
    last_t, bd = pl.num_programs(2) - 1, variant.bd
    iq = t if variant.group == 1 else t % (pl.num_programs(2)
                                           // variant.group)
    tile, step = plan.tile_k, plan.step_q
    last = _steps(valid_len, step)
    heads = _head_lanes(plan)
    strict = _strict(variant, variant.kv_rows)
    score = functools.partial(_scores, sm_scale=sm_scale, causal=causal,
                              valid_len=valid_len, transposed=True, bd=bd,
                              strict=strict)

    def gather(state, kv, rows, row0, col0, masked, **mask):
        """``(dk, dv)`` [Tk, lanes] + what the queries ``rows`` of the
        streamed block, at position ``row0``, owe the keys ``kv`` (per head:
        k, v with the other heads' lanes zeroed) at position ``col0``."""
        dk, dv = state
        q, do = q_ref[rows, :], do_ref[rows, :]             # [Tq, lanes]
        ps, dss = [], []
        for g, (k, v) in enumerate(kv):
            s = score(q, k, row0, col0, masked, **mask)
            p = jnp.exp(s - lse_ref[g:g + 1, rows])         # [Tk, Tq]
            dp = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[g:g + 1, rows]) * sm_scale
            ps.append(p.astype(do.dtype))
            dss.append(ds.astype(q.dtype))
        # One dot for all heads: [p_0 | p_1] @ [dO_0; dO_1], each dO_h zero
        # outside head h's lanes.
        dv = dv + jnp.dot(
            jnp.concatenate(ps, axis=1), jnp.concatenate(
                [_only(do, h, plan) for h in heads], axis=0),
            preferred_element_type=jnp.float32)
        dk = dk + jnp.dot(
            jnp.concatenate(dss, axis=1), jnp.concatenate(
                [_only(q, h, plan) for h in heads], axis=0),
            preferred_element_type=jnp.float32)
        return dk, dv

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if bd:
            dk_own_acc[:] = jnp.zeros_like(dk_own_acc)
            dv_own_acc[:] = jnp.zeros_like(dv_own_acc)

    @pl.when(_block_live(iq, jk, causal, plan, valid_len))
    def _compute():
        _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan)

        if bd:
            # The own keys meet the queries at their positions, which the
            # streamed block holds for all of the key block or for none
            # where the two blocks are of one size; else for some of its
            # stretches, each short enough to lie inside one query block.
            # ``ahead``: by how many stretches the key block starts after
            # the streamed one.
            @pl.when(strict == 1)
            def _own():
                side = _own_side(step, bd)
                stretch = math.gcd(tile, plan.block_q)
                per_q, per_k = plan.block_q // stretch, plan.block_k // stretch
                ahead = jk * per_k - iq * per_q

                def square(c):
                    cols = pl.ds(pl.multiple_of(c * side, side), side)
                    rows = pl.ds(pl.multiple_of(
                        c * side + ahead * stretch, side), side)
                    k, v = k_own_ref[cols, :], v_own_ref[cols, :]
                    dk_own_acc[cols, :], dv_own_acc[cols, :] = gather(
                        (dk_own_acc[cols, :], dv_own_acc[cols, :]),
                        [(_only(k, h, plan), _only(v, h, plan))
                         for h in heads], rows, 0, 0, True, own=True)

                _own_squares(jnp.clip(-ahead, 0, per_k),
                             jnp.clip(per_q - ahead, 0, per_k), stretch,
                             side, square)

        def k_tile(c, _):
            start = pl.multiple_of(c * tile, tile)
            col0 = jk * plan.block_k + c * tile

            def visit(size, masked, lo, hi):
                cols = pl.ds(start, size)
                k, v = k_ref[cols, :], v_ref[cols, :]       # [Tk, lanes]
                # Per head: k, v with the other heads' lanes zeroed.
                kv = [(_only(k, h, plan), _only(v, h, plan)) for h in heads]

                def body(j, state):                         # [Tk, lanes]
                    rows = pl.ds(pl.multiple_of(j * step, step), step)
                    row0 = iq * plan.block_q + j * step
                    return gather(state, kv, rows, row0, col0, masked)

                dk_acc[cols, :], dv_acc[cols, :] = _run(
                    body, lo, hi, (dk_acc[cols, :], dv_acc[cols, :]))

            _q_walk(col0, iq, last, causal, plan, valid_len, visit)

        jax.lax.fori_loop(0, plan.block_k // tile, k_tile, None)

    @pl.when(t == last_t)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)
        if bd:
            dk_own_ref[:] = dk_own_acc[:].astype(dk_own_ref.dtype)
            dv_own_ref[:] = dv_own_acc[:].astype(dv_own_ref.dtype)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 13),
                   inline=True)
def _flash_bwd(qb, kb, vb, ob, lse, dob, dlse, sm_scale, causal, plan,
               interpret, valid_len, lens=None, variant=_PLAIN):
    if variant.window:
        return _band_bwd(qb, kb, vb, ob, lse, dob, dlse, sm_scale, plan,
                         interpret, variant)
    n, s, width = qb.shape
    n_col, g = width // plan.lanes, plan.heads_per_block
    kv_col, group = kb.shape[2] // plan.lanes, variant.group
    bq, bk = plan.block_q, plan.block_k
    if lens is not None:
        # A row at or beyond its length came out as a constant zero: what
        # arrives as its cotangent is no gradient, and the walks that end
        # with the real rows count on its being zero.  (No caller with
        # lengths reads lse, so dlse is zero already.)
        real = jnp.arange(s)[None, :] < lens[::n_col, None]    # [N, S]
        dob = jnp.where(real[..., None], dob, 0)
    # The row statistics cross HBM one float32 a row, lane-dense.
    lse, dlse = lse.astype(jnp.float32), dlse.astype(jnp.float32)
    delta_scratch = pltpu.VMEM((g, bq), jnp.float32)
    kernel_args = dict(sm_scale=sm_scale, causal=causal, plan=plan,
                       valid_len=valid_len, interpret=interpret)

    # dq: q-block fixed per outer step, k/v stream on the inner grid dim.
    q_by_i = _operand_spec(bq, plan, n_col, _by_i)
    kv_by_j = _operand_spec(bk, plan, kv_col,
                            _streamed_k(causal, plan, valid_len),
                            _kv_row_of(variant))
    row_by_i = _row_stat_spec(plan, _by_i)
    own_specs, own_kv = _own_kv(variant, plan, kb, vb)
    dq = _kernel_call(
        _mha_bwd_dq_kernel, lens, **kernel_args, **_named(variant, "dq"),
        grid=(n * n_col, s // bq, s // bk),
        in_specs=[q_by_i, kv_by_j, kv_by_j, q_by_i, q_by_i, row_by_i,
                  row_by_i, *own_specs],
        out_specs=q_by_i,
        out_shape=_out_struct(qb.shape, qb.dtype, qb),
        scratch_shapes=[pltpu.VMEM((bq, plan.lanes), jnp.float32),
                        delta_scratch],
    )(qb, kb, vb, dob, ob, lse, dlse, *own_kv)

    # dk/dv: k-block fixed per outer step, q/do/stats stream inside, from
    # the first query block that sees it to the last real one.
    # Grouped queries: the streamed axis holds the query blocks of one
    # head of the group after another's.
    n_q = s // bq

    def streamed_q(r, i, j, lens):
        first = (i * bk) // bq if causal else 0
        return jnp.minimum(jnp.maximum(j if group == 1 else j % n_q, first),
                           _last_live_q(i, plan, _len_at(r, lens, valid_len)))

    q_row_of = None if group == 1 else (lambda r, j: r * group + j // n_q)
    k_row_of = (None if not variant.bd else
                lambda r, j: _clean_row(r, variant.kv_rows))
    q_by_j = _operand_spec(bq, plan, n_col, streamed_q, q_row_of)
    kv_by_i = _operand_spec(bk, plan, kv_col, _by_i)
    kv_in = _operand_spec(bk, plan, kv_col, _by_i, k_row_of)
    row_by_j = _row_stat_spec(plan, streamed_q, q_row_of)
    # Under the block-diffusion mask a key/value grid row holds its own
    # copy's block too, and what that gathers leaves beside dk, dv.
    more = 1 if variant.bd else 0
    accumulator = pltpu.VMEM((bk, plan.lanes), jnp.float32)
    dk, dv, *d_own = _kernel_call(
        _mha_bwd_dkv_kernel, lens, **kernel_args, **_named(variant, "dkv"),
        grid=(kb.shape[0] * kv_col, s // bk, group * n_q),
        in_specs=[q_by_j, kv_in, kv_in, q_by_j, q_by_j, row_by_j,
                  row_by_j] + [kv_by_i, kv_by_i] * more,
        out_specs=[kv_by_i, kv_by_i] * (1 + more),
        out_shape=[_out_struct(kb.shape, kb.dtype, kb),
                   _out_struct(vb.shape, vb.dtype, vb)] * (1 + more),
        scratch_shapes=[accumulator, accumulator, delta_scratch]
        + [accumulator, accumulator] * more,
    )(qb, kb, vb, dob, ob, lse, dlse, *own_kv)
    return dq, dk, dv, d_own


# ---- A banded call's kernels: the band's own grid, blocks and walk --------

_BAND_PARAMS = {
    rank: pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")[:rank],
        vmem_limit_bytes=_VMEM_LIMIT) for rank in (2, 3)}


def _band_masks(band: Band, window: int, k_axis: int):
    """The mask of each of a sub-tile's ``band.steps`` steps, None where the
    step lies wholly inside the band: booleans [step, step] with the keys
    along ``k_axis``, true where the query sees the key.  With ``d`` a key's
    local position less a query's, the pair of step ``j`` is ``q - k = j *
    step - d`` apart: the diagonal's step (``j`` 0) is cut by ``d <= 0``, a
    step that the far edge crosses by ``q - k < window`` (both, under a
    window narrower than a step).  They depend on local positions alone, so
    they are the same for every sub-tile and built once a grid step."""
    shape = (band.step, band.step)
    d = (jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
         - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - k_axis))
    masks = []
    for j in range(band.steps):
        seen = d <= 0 if j == 0 else None
        if (j + 1) * band.step > window:
            far = d > j * band.step - window
            seen = far if seen is None else jnp.logical_and(seen, far)
        masks.append(seen)
    return masks


def _band_rows(band: Band, t: int, before: bool):
    """Where the step that starts ``t`` rows from the resident block's first
    row lies: ``(which, rows)``, ``which`` None for the block of the
    resident block's own positions, else the index of the block beside it
    (``before`` it, the farthest first; or after it, the nearest first)."""
    if 0 <= t < band.block:
        return None, pl.ds(t, band.step)
    at = t + band.n_beside * band.beside if before else t - band.block
    return at // band.beside, pl.ds(at % band.beside, band.step)


def _band_walk(band: Band, i, n_blocks, before: bool, sub_tiles):
    """Take every sub-tile of the resident block ``i`` (of ``n_blocks``)
    through its steps: ``sub_tiles(c0, reach)`` takes the ``band.chains``
    sub-tiles from the ``c0``-th on, of whose steps those within ``reach``
    rows beside the block exist.  Every trip is written out, its positions
    static.  The trips that reach into the blocks beside the resident one
    (the first where the band lies ``before`` the block, else the last) are
    written once for each number of blocks that exist on that side, under
    the condition on ``i`` that says so, and a step beyond them (before the
    sequence's start, after its end) is left out of the text: no trip holds
    a branch, and the trips that stay inside the block are one basic block
    for the scheduler."""
    chains = band.chains
    trips = band.block // band.step // chains
    peeled = min(trips, -(-(band.steps - 1) // chains))
    edge = i if before else n_blocks - 1 - i
    inside, beside = ((range(peeled, trips), range(peeled)) if before else
                      (range(trips - peeled), range(trips - peeled, trips)))
    for held in range(band.n_beside + 1 if peeled else 0):
        last = held == band.n_beside

        @pl.when(edge >= held if last else edge == held)
        def _(held=held):
            for trip in beside:
                sub_tiles(trip * chains, held * band.block)

    for trip in inside:
        sub_tiles(trip * chains, 0)


def _band_scores(q, k, mask, sm_scale: float, transposed: bool = False):
    """:func:`_scores`' tile of one step of a banded walk, under the step's
    mask of :func:`_band_masks` (None: wholly inside the band)."""
    s = _scores(q, k, 0, 0, False, sm_scale=sm_scale, causal=True,
                valid_len=None, transposed=transposed)
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _softmax_step(state, s, v):
    """``(m, l, acc^T)`` of a sub-tile after the step whose transposed score
    tile is ``s`` [Tk, Tq] and values ``v`` [Tk, D]; ``state`` None: its
    first step."""
    top = jnp.max(s, axis=0, keepdims=True)
    m = top if state is None else jnp.maximum(state[0], top)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=0, keepdims=True)
    acc = jax.lax.dot_general(v, p.astype(v.dtype), _TN,    # v^T @ p
                              preferred_element_type=jnp.float32)
    if state is not None:
        alpha = jnp.exp(state[0] - m)
        l, acc = state[1] * alpha + l, state[2] * alpha + acc
    return m, l, acc


def _band_fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float,
                     plan: TilePlan, variant: _Variant, band: Band):
    """Forward of a banded call: grid (BH / G, n_q), every step live.  A
    query block is resident with the key/value block of its own positions
    and the keys the band reaches before it (``rest``: ``band.n_beside``
    blocks of k, as many of v; then the outputs o, lse), so a sub-tile's
    whole key span is here and its softmax begins and ends in this grid
    step: m, l and acc^T are values from the diagonal's step, which shows
    every row a key it sees (the running maximum is a real score before a
    far-edge step hides a whole row's pairs), to the far edge's; no scratch,
    nothing carried between grid steps.  The score tile is transposed as in
    :func:`_mha_kernel`, and ``band.chains`` sub-tiles take each step side
    by side, chains independent of each other by row."""
    n = band.n_beside
    k_by, v_by, (o_ref, lse_ref) = rest[:n], rest[n:2 * n], rest[2 * n:]
    heads, step = _head_lanes(plan), band.step
    masks = _band_masks(band, variant.window, 0)

    def sub_tiles(c0, reach):
        tiles = [_band_rows(band, (c0 + u) * step, True)[1]
                 for u in range(band.chains)]
        qs = [[q_ref[rows, h] for h in heads] for rows in tiles]
        state = [[None] * len(heads) for _ in tiles]
        for j in range(band.steps):
            for u in range(band.chains):
                t = (c0 + u - j) * step
                if t < -reach:
                    continue
                which, cols = _band_rows(band, t, True)
                k_at, v_at = ((k_ref, v_ref) if which is None
                              else (k_by[which], v_by[which]))
                for g, h in enumerate(heads):
                    s = _band_scores(qs[u][g], k_at[cols, h], masks[j],
                                     sm_scale, transposed=True)  # [Tk, Tq]
                    state[u][g] = _softmax_step(state[u][g], s,
                                                v_at[cols, h])
        for rows, per_head in zip(tiles, state):
            outs = []
            for g, (m, l, acc) in enumerate(per_head):
                outs.append(acc / l)                        # [D, Tq]
                lse_ref[g:g + 1, rows] = m + jnp.log(l)
            o_ref[rows, :] = jnp.transpose(
                jnp.concatenate(outs, axis=0)).astype(o_ref.dtype)

    _band_walk(band, pl.program_id(1), pl.num_programs(1), True, sub_tiles)


def _band_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
                    *rest, sm_scale: float, plan: TilePlan,
                    variant: _Variant, band: Band):
    """dQ of a banded call: the grid, the blocks and the walk of
    :func:`_band_fwd_kernel` (``rest``: the blocks of k and of v beside the
    own, then the output dq).  A sub-tile's dq [Tq, D] is a value through
    its steps and stored once; its ``delta`` is a sum along the lanes of the
    dO and O rows it holds, so no scratch at all."""
    n = band.n_beside
    k_by, v_by, (dq_ref,) = rest[:n], rest[n:2 * n], rest[2 * n:]
    heads, step = _head_lanes(plan), band.step
    masks = _band_masks(band, variant.window, 1)

    def sub_tiles(c0, reach):
        tiles = [_band_rows(band, (c0 + u) * step, True)[1]
                 for u in range(band.chains)]
        held, dqs = [], []
        for rows in tiles:
            do = do_ref[rows, :]
            prod = do.astype(jnp.float32) * o_ref[rows, :].astype(jnp.float32)
            held.append([
                (q_ref[rows, h], do[:, h],
                 _row_to_col(lse_ref[g:g + 1, rows]),
                 jnp.sum(prod[:, h], axis=1, keepdims=True)
                 - _row_to_col(dlse_ref[g:g + 1, rows]))
                for g, h in enumerate(heads)])
            dqs.append([None] * len(heads))
        for j in range(band.steps):
            for u in range(band.chains):
                t = (c0 + u - j) * step
                if t < -reach:
                    continue
                which, cols = _band_rows(band, t, True)
                k_at, v_at = ((k_ref, v_ref) if which is None
                              else (k_by[which], v_by[which]))
                for g, (h, (q, do, lse, delta)) in enumerate(
                        zip(heads, held[u])):
                    k = k_at[cols, h]                       # [Tk, D]
                    s = _band_scores(q, k, masks[j], sm_scale)  # [Tq, Tk]
                    p = jnp.exp(s - lse)
                    dp = jax.lax.dot_general(
                        do, v_at[cols, h], _NT,
                        preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
                    dq = jnp.dot(ds, k, preferred_element_type=jnp.float32)
                    dqs[u][g] = dq if dqs[u][g] is None else dqs[u][g] + dq
        for rows, per_head in zip(tiles, dqs):
            dq_ref[rows, :] = jnp.concatenate(per_head, axis=1).astype(
                dq_ref.dtype)

    _band_walk(band, pl.program_id(1), pl.num_programs(1), True, sub_tiles)


def _band_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
                     *rest, sm_scale: float, plan: TilePlan,
                     variant: _Variant, band: Band):
    """dK/dV of a banded call: grid (BH_kv / G, n_kv, group), every step
    live.  A key/value block is resident with the query head's q, dO, O and
    statistics at its own positions and at those the band reaches after it
    (``rest``: ``band.n_beside`` blocks each of q, dO, O, lse, dlse; then
    the outputs dk, dv, the scratch ``delta`` over all those rows and, in a
    grouped call, two float32 accumulators): the mirror of the forward's
    walk, a sub-tile of keys through the query steps from the diagonal's to
    the far edge's, score tiles transposed, dk and dv [Tk, D] values through
    the steps.  The grid's last axis takes the query heads of the group one
    after another; without a group a sub-tile's dk and dv leave as they are
    finished, with one they gather in the accumulators, read and written
    once a sub-tile and head."""
    n, group = band.n_beside, variant.group
    q_by, do_by, o_by, lse_by, dlse_by = (
        rest[x * n:(x + 1) * n] for x in range(5))
    dk_ref, dv_ref, delta_ref, *accs = rest[5 * n:]
    heads, step = _head_lanes(plan), band.step
    member = pl.program_id(2)
    masks = _band_masks(band, variant.window, 0)
    _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan, band.block)
    for d in range(n):
        _delta_rows(do_by[d], o_by[d], dlse_by[d], delta_ref, plan,
                    band.beside, band.block + d * band.beside)

    def sub_tiles(c0, reach):
        tiles = [_band_rows(band, (c0 + u) * step, False)[1]
                 for u in range(band.chains)]
        kvs = [[(k_ref[cols, h], v_ref[cols, h]) for h in heads]
               for cols in tiles]
        grads = [[None] * len(heads) for _ in tiles]
        for j in range(band.steps):
            for u in range(band.chains):
                t = (c0 + u + j) * step
                if t + step > band.block + reach:
                    continue
                which, rows = _band_rows(band, t, False)
                q_at, do_at, lse_at = (
                    (q_ref, do_ref, lse_ref) if which is None else
                    (q_by[which], do_by[which], lse_by[which]))
                # delta lies in one piece, the own block's rows first.
                over = rows if which is None else pl.ds(t, step)
                for g, (h, (k, v)) in enumerate(zip(heads, kvs[u])):
                    q, do = q_at[rows, h], do_at[rows, h]   # [Tq, D]
                    s = _band_scores(q, k, masks[j], sm_scale,
                                     transposed=True)       # [Tk, Tq]
                    p = jnp.exp(s - lse_at[g:g + 1, rows])
                    dp = jax.lax.dot_general(
                        v, do, _NT, preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta_ref[g:g + 1, over])
                          * sm_scale).astype(q.dtype)
                    dv = jnp.dot(p.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32)
                    dk = jnp.dot(ds, q, preferred_element_type=jnp.float32)
                    grads[u][g] = (dk, dv) if grads[u][g] is None else (
                        grads[u][g][0] + dk, grads[u][g][1] + dv)
        for cols, per_head in zip(tiles, grads):
            for h, pair in zip(heads, per_head):
                for x, out_ref, acc_ref in zip(pair, (dk_ref, dv_ref),
                                               accs or (None, None)):
                    if group > 1:
                        x = jnp.where(member == 0, x, acc_ref[cols, h] + x)
                        acc_ref[cols, h] = x
                    # The block leaves when the grid row moves on: what the
                    # last head of the group stored.
                    out_ref[cols, h] = x.astype(out_ref.dtype)

    _band_walk(band, pl.program_id(1), pl.num_programs(1), False, sub_tiles)


def _own(i):
    """The resident block's own positions."""
    return i


def _band_spec(rows: int, plan: TilePlan, n_col: int, seq_block,
               row_of=None, stat: bool = False):
    """A block of ``rows`` positions in a banded call's grid ``(r, i)`` or
    ``(r, i, member)``: of an operand [N, S, n_col * lanes] as
    :func:`_operand_spec` takes it or (``stat``) of a row statistic [BH / G,
    G, S].  ``seq_block(i)`` names the block along the sequence,
    ``row_of(r, *member)`` the operand's row where it is not the grid's."""
    def index(r, i, *member):
        row = r if row_of is None else row_of(r, *member)
        return ((row, 0, seq_block(i)) if stat else
                (row // n_col, seq_block(i), row % n_col))

    return pl.BlockSpec((None, plan.heads_per_block, rows) if stat else
                        (None, rows, plan.lanes), index)


def _band_beside(band: Band, seq: int, before: bool):
    """``seq_block`` of each block beside the resident one, in the order
    :func:`_band_rows` counts them: the rows before block ``i`` (the
    farthest first) or after it, in blocks of ``band.beside`` rows, held at
    the sequence's first or last block where they do not exist (no step
    reads them then)."""
    per, n = band.block // max(band.beside, 1), band.n_beside
    return [(lambda i, d=d: jnp.maximum(i * per - (n - d), 0)) if before else
            (lambda i, d=d: jnp.minimum((i + 1) * per + d,
                                        seq // band.beside - 1))
            for d in range(n)]


def _band_call(kernel, band: Band, plan, variant, sm_scale, interpret,
               **call):
    """The ``pallas_call`` of one of a banded call's kernels; ``call`` holds
    its ``name``, by which a trace tells the three apart."""
    return pl.pallas_call(
        functools.partial(kernel, sm_scale=sm_scale, plan=plan,
                          variant=variant, band=band),
        interpret=interpret,
        compiler_params=_BAND_PARAMS[len(call["grid"])], **call)


def _band_query_side(plan: TilePlan, variant: _Variant, kv_col: int, seq):
    """What the forward and dq share: their band, ``own`` (the spec of a
    block at the resident query block's positions), that of k and v there,
    and those of the key/value blocks the band reaches before it."""
    band = band_of(plan.block_q, plan.step_k, plan.tile_q, variant.window)

    def kv_row(r):
        return r // variant.group

    own = functools.partial(_band_spec, band.block, plan, seq_block=_own)
    by = [_band_spec(band.beside, plan, kv_col, block, kv_row)
          for block in _band_beside(band, seq, True)]
    return band, own, own(kv_col, row_of=kv_row), by


def _band_fwd(qb, kb, vb, sm_scale, plan: TilePlan, interpret,
              variant: _Variant):
    """:func:`_flash_fwd` of a banded call."""
    n, s, width = qb.shape
    n_col, kv_col = width // plan.lanes, kb.shape[2] // plan.lanes
    band, own, kv_own, by = _band_query_side(plan, variant, kv_col, s)
    return _band_call(
        _band_fwd_kernel, band, plan, variant, sm_scale, interpret,
        name="hvd_flash_swa_fwd",
        grid=(n * n_col, s // band.block),
        in_specs=[own(n_col), kv_own, kv_own, *by, *by],
        out_specs=[own(n_col), own(n_col, stat=True)],
        out_shape=[
            _out_struct(qb.shape, qb.dtype, qb),
            _out_struct((n * n_col, plan.heads_per_block, s), jnp.float32,
                        qb)],
    )(qb, kb, vb, *[kb] * len(by), *[vb] * len(by))


def _band_bwd(qb, kb, vb, ob, lse, dob, dlse, sm_scale, plan: TilePlan,
              interpret, variant: _Variant):
    """:func:`_flash_bwd` of a banded call: dq on the forward's grid, dk and
    dv on the key blocks' with the group's query heads along its last
    axis."""
    n, s, width = qb.shape
    n_col, kv_col = width // plan.lanes, kb.shape[2] // plan.lanes
    g, group = plan.heads_per_block, variant.group
    lse, dlse = lse.astype(jnp.float32), dlse.astype(jnp.float32)

    band, own, kv_own, by = _band_query_side(plan, variant, kv_col, s)
    dq = _band_call(
        _band_dq_kernel, band, plan, variant, sm_scale, interpret,
        name="hvd_flash_swa_dq",
        grid=(n * n_col, s // band.block),
        in_specs=[own(n_col), kv_own, kv_own, own(n_col), own(n_col),
                  own(n_col, stat=True), own(n_col, stat=True), *by, *by],
        out_specs=own(n_col),
        out_shape=_out_struct(qb.shape, qb.dtype, qb),
    )(qb, kb, vb, dob, ob, lse, dlse, *[kb] * len(by), *[vb] * len(by))

    def q_row(r, member):
        return r * group + member

    band = band_of(plan.block_k, plan.step_q, plan.tile_k, variant.window)
    own = functools.partial(_band_spec, band.block, plan, seq_block=_own)
    q_own, stat_own = own(n_col, row_of=q_row), own(n_col, row_of=q_row,
                                                    stat=True)
    after = _band_beside(band, s, False)
    q_by = [_band_spec(band.beside, plan, n_col, block, q_row)
            for block in after]
    stat_by = [_band_spec(band.beside, plan, n_col, block, q_row, stat=True)
               for block in after]
    accumulator = pltpu.VMEM((band.block, plan.lanes), jnp.float32)
    dk, dv = _band_call(
        _band_dkv_kernel, band, plan, variant, sm_scale, interpret,
        name="hvd_flash_swa_dkv",
        grid=(kb.shape[0] * kv_col, s // band.block, group),
        in_specs=[q_own, own(kv_col), own(kv_col), q_own, q_own, stat_own,
                  stat_own, *q_by * 3, *stat_by * 2],
        out_specs=[own(kv_col), own(kv_col)],
        out_shape=[_out_struct(kb.shape, kb.dtype, kb),
                   _out_struct(vb.shape, vb.dtype, vb)],
        scratch_shapes=[
            pltpu.VMEM((g, band.block + len(after) * band.beside),
                       jnp.float32)] + [accumulator] * (2 * (group > 1)),
    )(qb, kb, vb, dob, ob, lse, dlse, *[qb] * len(after),
      *[dob] * len(after), *[ob] * len(after), *[lse] * len(after),
      *[dlse] * len(after))
    return dq, dk, dv, []


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_lse(qb, kb, vb, lens, sm_scale, causal, plan, interpret,
               valid_len, variant=_PLAIN):
    """Differentiable kernel entry over operands in the kernels' layout
    ([N, S, n_col * lanes], S already padded), returning ``(out, lse)``,
    the pair ring attention merges across hops (the public
    ``flash_attention`` wrapper simply discards the lse).

    The backward for the pair is the standard flash backward with one
    twist: dL/dS_ij gains a ``+ dlse_i * p_ij`` term, which folds into the
    existing kernels as ``delta_i -> delta_i - dlse_i`` (:func:`_delta_rows`)
    — no separate kernels needed.

    ``lens`` is None (no operand: the static ``valid_len`` holds) or the
    int32 lengths of a ``kv_lens`` call, one per row of the grid.
    """
    return _flash_fwd(qb, kb, vb, sm_scale, causal, plan, interpret,
                      valid_len, lens, variant)


# The names the forward kernel's two results carry for ``jax.checkpoint``:
# a policy ``save_only_these_names(*CHECKPOINT_NAMES)`` round a block keeps
# them ([N, S, n_col * lanes] in the operands' dtype and one float32 a row
# and head) and the block's backward runs no second forward kernel to get
# them back.  Under any other checkpoint, and under none, a name is an
# identity that lowers to nothing.
CHECKPOINT_NAMES = ("hvd_flash_out", "hvd_flash_lse")


def _named_residuals(out, lse):
    """The forward kernel's two results under their checkpoint names.  Named
    in the forward rules and nowhere further out: the backward kernels read
    the residuals, ``lse`` among them, which never leaves some callers."""
    return (checkpoint_name(out, "hvd_flash_out"),
            checkpoint_name(lse, "hvd_flash_lse"))


def _flash_lse_fwd(qb, kb, vb, lens, sm_scale, causal, plan, interpret,
                   valid_len, variant):
    out, lse = _named_residuals(*_flash_fwd(
        qb, kb, vb, sm_scale, causal, plan, interpret, valid_len, lens,
        variant))
    return (out, lse), (qb, kb, vb, lens, out, lse)


def _flash_lse_bwd(sm_scale, causal, plan, interpret, valid_len, variant,
                   res, cotangents):
    qb, kb, vb, lens, ob, lse = res
    dob, dlse = cotangents
    dq, dk, dv, d_own = _flash_bwd(qb, kb, vb, ob, lse, dob, dlse, sm_scale,
                                   causal, plan, interpret, valid_len, lens,
                                   variant)
    if variant.bd:
        # The noised copy's rows read the clean copy's keys: what the dkv
        # kernel gathered in their name belongs to those, and the noised
        # keys are owed what their own blocks gathered.
        # (The copies are parted along the leading dimension alone: a view.
        # Flattened to [pairs, 2, everything] the same arrays are relaid,
        # 0.4 ms a layer at SDAR's k and v; PERF.md, PR 45.)
        def to_clean(x, own):
            rows = x.shape[0] * (x.shape[2] // plan.lanes)
            pairs = rows // (2 * variant.kv_rows)
            x, own = (y.reshape(pairs, 2, -1, *y.shape[1:]) for y in (x, own))
            both = x[:, 0].astype(jnp.float32) + x[:, 1]
            return jnp.stack([both.astype(x.dtype), own[:, 1]],
                             1).reshape(-1, *x.shape[3:])

        dk, dv = to_clean(dk, d_own[0]), to_clean(dv, d_own[1])
    return dq, dk, dv, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _mla_dq_kernel(q_ref, k_ref, v_ref, qr_ref, kr_ref, do_ref, o_ref,
                   lse_ref, dlse_ref, dq_ref, dqr_ref, acc_ref, accr_ref,
                   delta_ref, *, sm_scale: float, causal: bool,
                   plan: TilePlan, valid_len, variant: _Variant):
    """dQ and dQ_rope of a call with a second score operand: the grid, the
    walk and the statistics of :func:`_mha_bwd_dq_kernel`.  A head's q, dO, k
    and v are slices of whole lane tiles of their blocks and its rotary part
    a slice of ``q_rope``'s; each head is a chain of its own with its own
    accumulators, dq's [Tq, D] in its lanes of ``acc_ref`` and dq_rope's
    [Tq, r] in ``accr_ref[g]``, which leave side by side through the
    transposes the forward's output takes."""
    iq, jk = pl.program_id(1), pl.program_id(2)
    tile, step = plan.tile_q, plan.step_k
    heads, ropes = _head_lanes(plan), _rope_lanes(plan, variant.rope)
    score = functools.partial(_scores, sm_scale=sm_scale, causal=causal,
                              valid_len=valid_len)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        accr_ref[:] = jnp.zeros_like(accr_ref)
        _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan)

    @pl.when(_block_live(iq, jk, causal, plan, valid_len))
    def _compute():
        def q_tile(c, _):
            start = pl.multiple_of(c * tile, tile)
            row0 = iq * plan.block_q + c * tile

            def visit(off, masked, lo, hi):
                rows = pl.ds(start + off, tile - off)
                tile_of = [(q_ref[rows, h], qr_ref[rows, r], do_ref[rows, h],
                            _row_to_col(lse_ref[g:g + 1, rows]),
                            _row_to_col(delta_ref[g:g + 1, rows]))
                           for g, (h, r) in enumerate(zip(heads, ropes))]

                def body(j, state):
                    cols = pl.ds(pl.multiple_of(j * step, step), step)
                    col0 = jk * plan.block_k + j * step
                    kr = kr_ref[cols, :]                    # [Tk, r]
                    new = []
                    for (q, qr, do, lse, delta), h, (acc, accr) in zip(
                            tile_of, heads, state):
                        k = k_ref[cols, h]                  # [Tk, D]
                        s = score(q, k, row0 + off, col0, masked,
                                  rope=(qr, kr))
                        p = jnp.exp(s - lse)                # [Tq, Tk]
                        dp = jax.lax.dot_general(
                            do, v_ref[cols, h], _NT,
                            preferred_element_type=jnp.float32)
                        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
                        new.append((
                            acc + jnp.dot(
                                ds, k, preferred_element_type=jnp.float32),
                            accr + jnp.dot(
                                ds, kr, preferred_element_type=jnp.float32)))
                    return tuple(new)

                state = _run(body, lo, hi, tuple(
                    (acc_ref[rows, h], accr_ref[g, rows, :])
                    for g, h in enumerate(heads)))
                for g, (h, (acc, accr)) in enumerate(zip(heads, state)):
                    acc_ref[rows, h] = acc
                    accr_ref[g, rows, :] = accr

            _k_walk(row0, jk, causal, plan, valid_len, visit)

        jax.lax.fori_loop(0, plan.block_q // tile, q_tile, None)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _flush():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)

        def q_tile(c, _):
            rows = pl.ds(pl.multiple_of(c * tile, tile), tile)
            parts = [jnp.transpose(accr_ref[g, rows, :])   # [r, Tq] a head
                     for g in range(len(heads))]
            dqr_ref[rows, :] = jnp.transpose(
                jnp.concatenate(parts, axis=0)).astype(dqr_ref.dtype)

        jax.lax.fori_loop(0, plan.block_q // tile, q_tile, None)


def _mla_dkv_kernel(q_ref, k_ref, v_ref, qr_ref, kr_ref, do_ref, o_ref,
                    lse_ref, dlse_ref, dk_ref, dv_ref, dkr_ref, dk_acc,
                    dv_acc, dkr_acc, delta_ref, *, sm_scale: float,
                    causal: bool, plan: TilePlan, valid_len,
                    variant: _Variant):
    """dK, dV and this grid row's part of dK_rope of a call with a second
    score operand: the grid and the walk of :func:`_mha_bwd_dkv_kernel`
    (score tiles transposed, [Tk, Tq]).  A head's k, v, q and dO are slices
    of whole lane tiles, each head a chain of its own into its lanes of the
    [Tk, lanes] accumulators; what the block's heads owe the one rotary key
    gathers in one [Tk, r] float32 accumulator and leaves as this grid row's
    partial, which the caller sums over a sequence's rows."""
    jk, iq = pl.program_id(1), pl.program_id(2)
    tile, step = plan.tile_k, plan.step_q
    last = _steps(valid_len, step)
    heads, ropes = _head_lanes(plan), _rope_lanes(plan, variant.rope)
    score = functools.partial(_scores, sm_scale=sm_scale, causal=causal,
                              valid_len=valid_len, transposed=True)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        dkr_acc[:] = jnp.zeros_like(dkr_acc)

    @pl.when(_block_live(iq, jk, causal, plan, valid_len))
    def _compute():
        _delta_rows(do_ref, o_ref, dlse_ref, delta_ref, plan)

        def k_tile(c, _):
            start = pl.multiple_of(c * tile, tile)
            col0 = jk * plan.block_k + c * tile

            def visit(size, masked, lo, hi):
                cols = pl.ds(start, size)
                kr = kr_ref[cols, :]                        # [Tk, r]
                kv = [(k_ref[cols, h], v_ref[cols, h]) for h in heads]

                def body(j, state):
                    rows = pl.ds(pl.multiple_of(j * step, step), step)
                    row0 = iq * plan.block_q + j * step
                    *per_head, dkr = state
                    new = []
                    for g, (h, r, (k, v), (dk, dv)) in enumerate(zip(
                            heads, ropes, kv, per_head)):
                        q, qr, do = (q_ref[rows, h], qr_ref[rows, r],
                                     do_ref[rows, h])
                        s = score(q, k, row0, col0, masked, rope=(qr, kr))
                        p = jnp.exp(s - lse_ref[g:g + 1, rows])  # [Tk, Tq]
                        dp = jax.lax.dot_general(
                            v, do, _NT, preferred_element_type=jnp.float32)
                        ds = (p * (dp - delta_ref[g:g + 1, rows])
                              * sm_scale).astype(q.dtype)
                        new.append((
                            dk + jnp.dot(
                                ds, q, preferred_element_type=jnp.float32),
                            dv + jnp.dot(
                                p.astype(do.dtype), do,
                                preferred_element_type=jnp.float32)))
                        dkr = dkr + jnp.dot(
                            ds, qr, preferred_element_type=jnp.float32)
                    return (*new, dkr)

                *per_head, dkr = _run(body, lo, hi, (
                    *((dk_acc[cols, h], dv_acc[cols, h]) for h in heads),
                    dkr_acc[cols, :]))
                for h, (dk, dv) in zip(heads, per_head):
                    dk_acc[cols, h] = dk
                    dv_acc[cols, h] = dv
                dkr_acc[cols, :] = dkr

            _q_walk(col0, iq, last, causal, plan, valid_len, visit)

        jax.lax.fori_loop(0, plan.block_k // tile, k_tile, None)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)
        dkr_ref[:] = dkr_acc[:]


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12, 13, 14),
                   inline=True)
def _mla_bwd(qb, kb, vb, qrb, krb, ob, lse, dob, dlse, sm_scale, causal,
             plan, interpret, valid_len, variant):
    """The two backward kernels of a call with a second score operand:
    ``(dq, dk, dv, dq_rope, dk_rope)``, the last summed over a sequence's
    grid rows in float32."""
    n, s, width = qb.shape
    n_col, g, r = width // plan.lanes, plan.heads_per_block, variant.rope
    bq, bk = plan.block_q, plan.block_k
    lse, dlse = lse.astype(jnp.float32), dlse.astype(jnp.float32)
    delta_scratch = pltpu.VMEM((g, bq), jnp.float32)
    kernel_args = dict(sm_scale=sm_scale, causal=causal, plan=plan,
                       valid_len=valid_len, interpret=interpret)
    streamed_k = _streamed_k(causal, plan, valid_len)
    q_by_i = _operand_spec(bq, plan, n_col, _by_i)
    kv_by_j = _operand_spec(bk, plan, n_col, streamed_k)
    row_by_i = _row_stat_spec(plan, _by_i)
    qr_by_i, kr_by_j = _rope_specs(variant, plan, n_col, _by_i, streamed_k)
    dq, dqr = _kernel_call(
        _mla_dq_kernel, None, **kernel_args, **_named(variant, "dq"),
        grid=(n * n_col, s // bq, s // bk),
        in_specs=[q_by_i, kv_by_j, kv_by_j, qr_by_i, kr_by_j, q_by_i, q_by_i,
                  row_by_i, row_by_i],
        out_specs=[q_by_i, qr_by_i],
        out_shape=[_out_struct(qb.shape, qb.dtype, qb),
                   _out_struct(qrb.shape, qrb.dtype, qrb)],
        scratch_shapes=[pltpu.VMEM((bq, plan.lanes), jnp.float32),
                        pltpu.VMEM((g, bq, r), jnp.float32), delta_scratch],
    )(qb, kb, vb, qrb, krb, dob, ob, lse, dlse)

    def streamed_q(row, i, j, lens):
        first = (i * bk) // bq if causal else 0
        return jnp.minimum(jnp.maximum(j, first),
                           _last_live_q(i, plan, valid_len))

    q_by_j = _operand_spec(bq, plan, n_col, streamed_q)
    kv_by_i = _operand_spec(bk, plan, n_col, _by_i)
    row_by_j = _row_stat_spec(plan, streamed_q)
    qr_by_j, kr_by_i = _rope_specs(variant, plan, n_col, streamed_q, _by_i)
    part_by_i = pl.BlockSpec((None, bk, r), lambda row, i, j: (row, i, 0))
    accumulator = pltpu.VMEM((bk, plan.lanes), jnp.float32)
    dk, dv, dkr = _kernel_call(
        _mla_dkv_kernel, None, **kernel_args, **_named(variant, "dkv"),
        grid=(n * n_col, s // bk, s // bq),
        in_specs=[q_by_j, kv_by_i, kv_by_i, qr_by_j, kr_by_i, q_by_j, q_by_j,
                  row_by_j, row_by_j],
        out_specs=[kv_by_i, kv_by_i, part_by_i],
        out_shape=[_out_struct(kb.shape, kb.dtype, kb),
                   _out_struct(vb.shape, vb.dtype, vb),
                   _out_struct((n * n_col, s, r), jnp.float32, kb)],
        scratch_shapes=[accumulator, accumulator,
                        pltpu.VMEM((bk, r), jnp.float32), delta_scratch],
    )(qb, kb, vb, qrb, krb, dob, ob, lse, dlse)
    dkr = jnp.sum(dkr.reshape(n, n_col, s, r), axis=1).astype(krb.dtype)
    return dq, dk, dv, dqr, dkr


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_mla(qb, kb, vb, qrb, krb, sm_scale, causal, plan, interpret,
               valid_len, variant):
    """:func:`_flash_lse` with a second score operand: ``q_rope`` [N, S,
    n_col * G * r] and ``k_rope`` [N, S, r] in the kernels' layout."""
    return _flash_fwd(qb, kb, vb, sm_scale, causal, plan, interpret,
                      valid_len, None, variant, (qrb, krb))


def _flash_mla_fwd(qb, kb, vb, qrb, krb, sm_scale, causal, plan, interpret,
                   valid_len, variant):
    out, lse = _named_residuals(*_flash_fwd(
        qb, kb, vb, sm_scale, causal, plan, interpret, valid_len, None,
        variant, (qrb, krb)))
    return (out, lse), (qb, kb, vb, qrb, krb, out, lse)


def _flash_mla_bwd(sm_scale, causal, plan, interpret, valid_len, variant,
                   res, cotangents):
    qb, kb, vb, qrb, krb, ob, lse = res
    return _mla_bwd(qb, kb, vb, qrb, krb, ob, lse, *cotangents, sm_scale,
                    causal, plan, interpret, valid_len, variant)


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def _kv_lens(kv_lens, batch: int, seq: int, causal: bool):
    """``kv_lens`` as the int32 [B] the masks read: a sequence's real
    tokens are its first ``kv_lens[b]``, at least one and at most all."""
    if causal:
        raise ValueError(
            "kv_lens with causal=True: padding lies at the tail, where no "
            "real query sees it under a causal mask; pass no length")
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    if kv_lens.shape != (batch,):
        raise ValueError(f"kv_lens has shape {kv_lens.shape}, expected one "
                         f"length per sequence: ({batch},)")
    return jnp.clip(kv_lens, 1, seq)


def block_diffusion_mask(length: int, block: int):
    """[2L, 2L] booleans, true where query i sees key j, over a sequence
    laid out as ``[clean ; noised]`` (BD3-LM, arXiv:2503.09573, section 3.1):
    with ``blk(i) = (i mod L) // block``, a clean query sees the clean keys
    of the blocks up to its own and no noised key; a noised query sees the
    noised keys of its own block and the clean keys of the blocks before
    it."""
    pos = jnp.arange(2 * length)
    blk, noised = (pos % length) // block, pos >= length
    qb, kb, qn, kn = blk[:, None], blk[None, :], noised[:, None], noised[None]
    return jnp.where(qn, jnp.where(kn, kb == qb, kb < qb), ~kn & (kb <= qb))


def _block_diffusion(block_diffusion, seq: int, causal: bool, kv_lens):
    """``(L, B)`` checked against the call it came with."""
    length, block = block_diffusion
    if causal or kv_lens is not None:
        raise ValueError("block_diffusion is a mask of its own: pass neither "
                         "causal=True nor kv_lens with it")
    if seq != 2 * length or block < 1 or length % block:
        raise ValueError(
            f"block_diffusion={block_diffusion}: the sequence holds the clean "
            f"and the noised copy, 2 x {length} positions (got {seq}), in "
            "whole blocks")
    return length, block


def _group(q, k, v) -> int:
    """Query heads a key/value head: head ``i`` reads key/value head
    ``i // group``."""
    h, hkv = q.shape[2], k.shape[2]
    if k.shape != v.shape or hkv < 1 or h % hkv or (
            q.shape[:2] + q.shape[3:] != k.shape[:2] + k.shape[3:]):
        raise ValueError(f"q {q.shape} against k {k.shape}, v {v.shape}: the "
                         "key/value heads must divide the query heads")
    return h // hkv


def _window(window, seq: int, causal: bool, kv_lens, block_diffusion):
    """``window`` checked against the call it came with: the keys a query
    sees, its own among them, or None where the band holds every causal pair
    (the call is then the causal one, text for text)."""
    if window is None:
        return None
    if not causal or kv_lens is not None or block_diffusion is not None:
        raise ValueError("window is a band under the causal diagonal: pass "
                         "causal=True with it and no other mask")
    if window < 1:
        raise ValueError(f"window={window}: a query sees its own key at "
                         "least")
    return None if window >= seq else int(window)


def _rope_pair(q, k, q_rope, k_rope, kv_lens, block_diffusion, window):
    """The width of a call's second score operand, 0 without the pair:
    ``q_rope`` [B, S, H, r] on ``k_rope`` [B, S, 1, r], one key for every
    head, checked against the call it came with."""
    if q_rope is None and k_rope is None:
        return 0
    if q_rope is None or k_rope is None:
        raise ValueError("q_rope and k_rope come as a pair")
    for what, given in (("kv_lens", kv_lens is not None),
                        ("window", window is not None),
                        ("block_diffusion", block_diffusion is not None),
                        ("grouped key/value heads",
                         k.shape[2] != q.shape[2])):
        if given:
            raise ValueError(f"q_rope / k_rope with {what} is not built "
                             "(ROADMAP Reach B2)")
    r = q_rope.shape[-1]
    if q_rope.shape != q.shape[:3] + (r,) or k_rope.shape != (
            *k.shape[:2], 1, r):
        raise ValueError(
            f"q_rope {q_rope.shape} against q {q.shape}, k_rope "
            f"{k_rope.shape} against k {k.shape}: a rotary part a query "
            "head, and one rotary key for all heads")
    return r


def _default_scale(q, rope: int) -> float:
    """1 / sqrt of the scores' width: q's and, with the pair, q_rope's."""
    return (q.shape[-1] + rope) ** -0.5


def _dense(q, k, v, causal, scale, kv_lens, block_diffusion=None,
           window=None, q_rope=None, k_rope=None):
    b, s = q.shape[:2]
    rope = _rope_pair(q, k, q_rope, k_rope, kv_lens, block_diffusion, window)
    window = _window(window, s, causal, kv_lens, block_diffusion)
    scale = _default_scale(q, rope) if scale is None else scale
    group = _group(q, k, v)
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    if rope:
        logits = logits + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope[:, :, 0],
                                     preferred_element_type=jnp.float32)
    logits = logits * scale
    if block_diffusion is not None:
        mask = block_diffusion_mask(
            *_block_diffusion(block_diffusion, s, causal, kv_lens))
        logits = jnp.where(mask[None, None], logits,
                           jnp.finfo(jnp.float32).min)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((s, s), bool), -window)
        logits = jnp.where(mask[None, None], logits,
                           jnp.finfo(jnp.float32).min)
    if kv_lens is not None:
        real = jnp.arange(s)[None, :] < _kv_lens(kv_lens, b, s, causal)[:, None]
        logits = jnp.where(real[:, None, None, :], logits,
                           jnp.finfo(jnp.float32).min)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)      # [B, H, S]
    probs = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if kv_lens is not None:
        out = jnp.where(real[:, :, None, None], out, 0)
    return out, lse


def dense_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, kv_lens=None,
                    block_diffusion=None, window: Optional[int] = None,
                    q_rope=None, k_rope=None):
    """Reference-math dense attention over [B, S, H, D] (fp32 softmax).
    ``kv_lens``, ``block_diffusion``, ``window``, the pair ``q_rope`` /
    ``k_rope`` and fewer key/value heads than query heads as in
    :func:`flash_attention`."""
    out, _ = _dense(q, k, v, causal, scale, kv_lens, block_diffusion, window,
                    q_rope, k_rope)
    return out


def dense_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None, q_rope=None,
                             k_rope=None):
    """Dense attention that also returns log-sum-exp [B, H, S] (the chunk
    statistic ring attention merges across hops)."""
    return _dense(q, k, v, causal, scale, None, q_rope=q_rope, k_rope=k_rope)


def _kernel_layout(plan: TilePlan, n: int, s_pad: int, d: int):
    """``(to_kernel, from_kernel)`` between [n, s_pad, heads, d] and what the
    kernels take."""
    if plan.lane_dense:
        # The model's own layout: heads side by side in the minor dim.
        def to_kernel(x):
            return x.reshape(n, s_pad, x.shape[2] * d)

        def from_kernel(x):
            return x.reshape(n, s_pad, x.shape[2] // d, d)
    else:
        # No whole number of heads fills 128 lanes: one head a grid row,
        # turned round under a scope that says so on the profiler's clock
        # (docs/observability.md); no op carries it on the path above.
        def to_kernel(x):
            with jax.named_scope("hvd_flash_relayout"):
                return x.transpose(0, 2, 1, 3).reshape(-1, s_pad, d)

        def from_kernel(x):
            with jax.named_scope("hvd_flash_relayout"):
                return x.reshape(n, -1, s_pad, d).transpose(0, 2, 1, 3)

    return to_kernel, from_kernel


def _flash(q, k, v, causal, scale, block_q, block_k, interpret, kv_lens,
           block_diffusion=None, window=None, q_rope=None, k_rope=None):
    """``(out, lse)`` of the kernels, or of the dense fallback off-TPU."""
    b, s, h, d = q.shape
    rope = _rope_pair(q, k, q_rope, k_rope, kv_lens, block_diffusion, window)
    group = _group(q, k, v)
    window = _window(window, s, causal, kv_lens, block_diffusion)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _dense(q, k, v, causal, scale, kv_lens, block_diffusion,
                          window, q_rope, k_rope)
        interpret = False
    sm_scale = _default_scale(q, rope) if scale is None else scale
    if rope:
        return _flash_rope_pair(q, k, v, q_rope, k_rope, causal, sm_scale,
                                block_q, block_k, bool(interpret))
    if block_diffusion is not None:
        return _flash_block_diffusion(
            q, k, v, *_block_diffusion(block_diffusion, s, causal, kv_lens),
            sm_scale, block_q, block_k, bool(interpret))
    if group > 1 and kv_lens is not None:
        raise ValueError("kv_lens with fewer key/value heads than query "
                         "heads is not built: the dkv kernel's grid rows "
                         "are key/value heads")
    # Grouped queries: one head a grid step (a block of several query heads
    # would want as many different key/value heads beside each other).
    plan = tile_plan(s, d, q.dtype.itemsize, causal, block_q, block_k,
                     heads=h if group == 1 else 1, window=window)
    s_pad = plan.seq_pad
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    to_kernel, from_kernel = _kernel_layout(plan, b, s_pad, d)
    # One length per row of the kernels' grid.
    lens = (None if kv_lens is None else
            jnp.repeat(_kv_lens(kv_lens, b, s, causal),
                       h // plan.heads_per_block))
    variant = _Variant(group=group, window=window or 0)
    out, lse = _flash_lse(to_kernel(q), to_kernel(k), to_kernel(v), lens,
                          sm_scale, causal, plan, bool(interpret), s, variant)
    out = from_kernel(out)[:, :s]
    lse = lse.reshape(b, h, s_pad)[:, :, :s]
    return out, lse


def _flash_rope_pair(q, k, v, q_rope, k_rope, causal, sm_scale, block_q,
                     block_k, interpret):
    """``(out, lse)`` of a call with a second score operand: the operands in
    the model's own layout, heads side by side in the minor dimension (a
    view), ``k_rope`` as its one [B, S, r] array."""
    b, s, h, d = q.shape
    r = q_rope.shape[-1]
    plan = tile_plan(s, d, q.dtype.itemsize, causal, block_q, block_k,
                     heads=h, rope=r)
    s_pad = plan.seq_pad

    def flat(x):
        x = x.reshape(b, s, -1)
        return x if s_pad == s else jnp.pad(
            x, [(0, 0), (0, s_pad - s), (0, 0)])

    out, lse = _flash_mla(*(flat(x) for x in (q, k, v, q_rope, k_rope)),
                          sm_scale, causal, plan, interpret, s,
                          _Variant(rope=r))
    return (out[:, :s].reshape(b, s, h, d),
            lse.reshape(b, h, s_pad)[:, :, :s])


def _flash_block_diffusion(q, k, v, length, block, sm_scale, block_q,
                           block_k, interpret):
    """``(out, lse)`` under the block-diffusion mask: one call of the
    kernels on the model's own arrays.  The clean and the noised copy are
    taken as neighbouring sequences of L rows (a view of [b, 2L, ...] as
    [2b, L, ...]; only a length the plan pads moves anything).  Both copies'
    queries walk the *clean* keys and stop at the diagonal as a causal call
    does, differing in one comparison of block numbers (the index maps send
    a noised row to the clean copy's keys), and a noised row visits its own
    block besides, through a second view of the same k and v.  The live
    area is a quarter of the 2L x 2L square and L x B pairs a head.  dk and
    dv of the clean keys come out in two parts, one a copy's queries, and
    are added in the backward (:func:`_flash_lse_bwd`)."""
    b, _, h, d = q.shape
    hkv, group = k.shape[2], _group(q, k, v)
    plan = tile_plan(length, d, q.dtype.itemsize, True, block_q, block_k,
                     heads=h if group == 1 else 1)
    if plan.step_q % block or plan.step_k % block:
        raise ValueError(
            f"block_diffusion: the block length {block} must divide the "
            f"kernels' steps ({plan.step_q}, {plan.step_k})")
    s_pad, g = plan.seq_pad, plan.heads_per_block

    def halves(x):                    # [b, 2L, H, D] -> [2b, L_pad, H, D]
        x = x.reshape(2 * b, length, *x.shape[2:])
        return x if s_pad == length else jnp.pad(
            x, [(0, 0), (0, s_pad - length), (0, 0), (0, 0)])

    to_kernel, from_kernel = _kernel_layout(plan, 2 * b, s_pad, d)
    variant = _Variant(group, block, h // g, hkv // g)
    out, lse = _flash_lse(*(to_kernel(halves(x)) for x in (q, k, v)), None,
                          sm_scale, True, plan, interpret, length, variant)
    out = from_kernel(out)[:, :length].reshape(b, 2 * length, h, d)
    lse = lse.reshape(b, 2, h, s_pad)[..., :length]
    return out, lse.transpose(0, 2, 1, 3).reshape(b, h, 2 * length)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None, q_rope=None,
                             k_rope=None):
    """Pallas attention over [B, S, H, D] returning ``(out, lse)`` with
    lse shaped [B, H, S].  Same dispatch rules as :func:`flash_attention`;
    off-TPU it falls back to :func:`dense_attention_with_lse`."""
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret, None,
                  q_rope=q_rope, k_rope=k_rope)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, kv_lens=None,
                    block_diffusion=None, window: Optional[int] = None,
                    q_rope=None, k_rope=None):
    """Attention over q [batch, seq, heads, head_dim] and k, v [batch, seq,
    kv_heads, head_dim]; with fewer key/value heads than query heads, query
    head ``i`` reads key/value head ``i // (heads // kv_heads)``.

    On TPU this is the Pallas kernel; elsewhere it falls back to the dense
    implementation (identical math) unless ``interpret=True`` forces the
    kernel through the Pallas interpreter (tests).

    ``kv_lens`` (int32 [batch], non-causal calls only): sequence ``b``
    holds ``kv_lens[b]`` real tokens (clipped to 1 .. seq) and padding
    after them.  Keys at or beyond the length are masked for every query,
    and the rows at or beyond it come out zero and pass no gradient on,
    here and in :func:`dense_attention`.

    ``block_diffusion=(L, B)``: the sequence is ``[clean ; noised]``, two
    copies of L positions in blocks of B, under
    :func:`block_diffusion_mask` (neither ``causal`` nor ``kv_lens`` with
    it).

    ``window`` (causal calls only): query i sees the ``window`` keys ``i -
    window < j <= i``, its own among them; None, or a window of the whole
    sequence, is the causal call.

    ``q_rope`` [batch, seq, heads, r] with ``k_rope`` [batch, seq, 1, r] (no
    other mask than ``causal``, as many key/value heads as query heads): the
    scores are ``scale x (q . k + q_rope . k_rope)``, the rotary key one for
    all heads, and ``scale`` defaults to ``(head_dim + r) ** -0.5``; v and the
    output stay ``head_dim`` wide.  Gradients reach both, ``k_rope``'s summed
    over the heads.
    """
    out, _ = _flash(q, k, v, causal, scale, block_q, block_k, interpret,
                    kv_lens, block_diffusion, window, q_rope, k_rope)
    return out
