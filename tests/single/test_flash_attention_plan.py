"""The flash kernels' tile plan: which layout a shape gets, its VMEM
estimate, heads a 128-lane block.  No kernel runs here.  Split from
test_flash_attention.py."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import tile_plan
from _flash_helpers import SCHEDULES


# heads_per_block and whether the blocks are lane-dense, where not (1, False).
LAYOUTS = {"16x16-b2h4": (4, True), "g2-two-blocks": (2, True),
           "g2-bq>bk-b2h4": (2, True), "g2-padded-tail": (2, True),
           "g2-default-plan": (2, True), "g1-head_dim-128": (1, True)}


@pytest.mark.parametrize("name", SCHEDULES.keys())
def test_schedule_cases_run_the_layout_they_say(name):
    (_, seq, heads, head_dim), block_q, block_k = SCHEDULES[name]
    plan = tile_plan(seq, head_dim, 4, True, block_q, block_k, heads=heads)
    assert (plan.heads_per_block, plan.lane_dense) == LAYOUTS.get(
        name, (1, False))
    assert plan.lanes == plan.heads_per_block * head_dim


PLAN_SHAPES = {"gpt2-medium": (1024, 64), "bench": (2048, 128),
               "five-tiles": (640, 64), "tiny": (32, 16),
               "long": (8192, 128)}


@pytest.mark.parametrize("seq,head_dim", PLAN_SHAPES.values(),
                         ids=PLAN_SHAPES.keys())
@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("heads", [1, 16])
def test_tile_plan(seq, head_dim, itemsize, heads):
    """The schedule is decided at trace time from the shape alone: this is
    the record of where the large blocks engage."""
    plan = tile_plan(seq, head_dim, itemsize, True, heads=heads)
    if heads == 1:                                   # the default
        assert plan == tile_plan(seq, head_dim, itemsize, True)
    # Whole heads side by side fill 128 lanes, or one head a block.
    per_block = 128 // head_dim if heads == 16 and head_dim < 128 else 1
    assert plan.heads_per_block == per_block
    assert plan.lanes == per_block * head_dim
    assert plan.lane_dense == (plan.lanes % 128 == 0)
    assert plan.lane_dense == (heads == 16 or head_dim == 128)
    padded = -(-seq // 128) * 128
    assert plan.seq_pad == padded                    # 640 stays 640
    assert padded % plan.block_q == 0 and plan.block_q == plan.block_k
    assert plan.block_q % plan.tile_q == 0 and plan.tile_q % plan.step_k == 0
    assert plan.block_k % plan.tile_k == 0 and plan.tile_k % plan.step_q == 0
    assert plan.step_q % 128 == 0 and plan.step_k % 128 == 0
    assert plan.vmem_bytes <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
    assert plan == tile_plan(seq, head_dim, itemsize, False, heads=heads)
    whole = fa._vmem_estimate(padded, padded, min(padded, 1024),
                              min(padded, 256), plan.lanes, per_block,
                              itemsize) <= fa._VMEM_BUDGET
    if whole:
        assert plan.block_q == padded          # one grid step a block
    else:
        assert plan.grid_steps(heads) > 1      # streams, and fits
    # Up to 2048 x 128 lanes in bf16 with one head a block, 1024 with two
    # (each head has a score tile of its own in flight).
    if itemsize == 2 and seq <= (2048 if per_block == 1 else 1024):
        assert whole
    if (seq, head_dim, itemsize) == (1024, 64, 2):
        # 8 x 16 head-sequences: 128 grid steps a call (64 with two heads
        # a block) where 128 x 128 blocks took 8,192.
        assert plan.grid_steps(128) == 128 // per_block
        assert (plan.tile_q, plan.step_k) == (1024, 256)


def test_vmem_estimate_counts_the_block_as_it_lies():
    """A 64-wide block is padded to the lane count in VMEM, a 128-lane
    block of two heads is not, and holds two heads' score tiles: the same
    operand bytes, twice the step."""
    one = fa._vmem_estimate(1024, 1024, 1024, 256, 64, 1, 2)
    two = fa._vmem_estimate(1024, 1024, 1024, 256, 128, 2, 2)
    step = 1024 * 256 * (4 * 4 + 2 * 2)
    assert two - one == step
    assert fa._vmem_estimate(1024, 1024, 1024, 256, 128, 1, 2) == one
    assert tile_plan(1024, 64, 2, True, heads=16).vmem_bytes == two
    # 80 lanes take the room of 128.
    assert fa._vmem_estimate(512, 512, 512, 256, 80, 1, 2) == \
        fa._vmem_estimate(512, 512, 512, 256, 128, 1, 2)


@pytest.mark.parametrize("head_dim,heads,expected", [
    (64, 16, 2), (64, 12, 2), (32, 4, 4), (128, 8, 1), (256, 2, 1),
    (64, 3, 1), (64, 1, 1), (80, 16, 1), (16, 2, 1), (16, 8, 8)])
def test_heads_per_block(head_dim, heads, expected):
    assert fa.heads_per_block(head_dim, heads) == expected


def test_tile_plan_explicit_block_wins():
    plan = tile_plan(1024, 64, 2, True, block_q=256, block_k=128)
    assert (plan.block_q, plan.block_k, plan.seq_pad) == (256, 128, 1024)
    assert plan.grid_steps(1) == 4 * 8
    plan = tile_plan(23, 8, 4, True, block_q=16, block_k=16)
    assert (plan.block_q, plan.tile_q, plan.step_k, plan.seq_pad) == (
        16, 16, 16, 32)
    # blocks that differ, and do not nest: the steps shrink until they do
    plan = tile_plan(96, 8, 4, True, block_q=24, block_k=32)
    assert (plan.block_q, plan.block_k, plan.seq_pad) == (24, 32, 96)
    assert plan.tile_q % plan.step_k == 0 and plan.tile_k % plan.step_q == 0


# The benchmark's cells: sequence, head width, a call's heads (one where the
# heads are grouped), then the plan.  A mask of its own (block diffusion) may
# hold more in VMEM than the estimate counts; it does not move these.
CELLS = {
    "gpt2m-causal-1024x64": ((1024, 64, 16), (
        1024, 1024, 1024, 1024, 1024, 256, 256, 15335424, 2, 128)),
    "bert-kv_lens-512x64": ((512, 64, 16), (
        512, 512, 512, 512, 512, 256, 256, 7667712, 2, 128)),
    "sdar-grouped-4096x128": ((4096, 128, 1), (
        4096, 2048, 2048, 1024, 1024, 256, 256, 14942208, 1, 128)),
    "zaya-grouped-16384x128": ((16384, 128, 1), (
        16384, 2048, 2048, 1024, 1024, 256, 256, 14942208, 1, 128))}


@pytest.mark.parametrize("shape,plan", CELLS.values(), ids=CELLS.keys())
def test_tile_plan_at_the_cells_shapes(shape, plan):
    seq, head_dim, heads = shape
    for causal in (True, False):
        assert tuple(tile_plan(seq, head_dim, 2, causal, heads=heads)) == plan


def _kernel_calls(jaxpr):
    """(inputs, outputs) of every ``pallas_call`` in a jaxpr, nested ones
    too, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((len(eqn.invars), len(eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


# What reaches the three kernels: q, k, v into the forward, those with dO, O,
# lse and dlse into dq and dkv (and the lengths of a ``kv_lens`` call before
# them).  Only the block-diffusion mask hands k and v over a second time,
# for the noised copy's own blocks, and takes their dk, dv from dkv apart.
OPERANDS = {
    "plain-causal": ((1, 128, 2, 64), 2, {"causal": True},
                     [(3, 2), (7, 1), (7, 2)]),
    "kv_lens": ((2, 128, 2, 64), 2, {"kv_lens": [100, 128]},
                [(4, 2), (8, 1), (8, 2)]),
    "grouped-causal": ((1, 128, 4, 128), 2, {"causal": True},
                       [(3, 2), (7, 1), (7, 2)]),
    "grouped-block-diffusion": ((1, 256, 4, 128), 2,
                                {"block_diffusion": (128, 4)},
                                [(5, 2), (9, 1), (9, 4)])}


@pytest.mark.parametrize("shape,kv_heads,mask,calls", OPERANDS.values(),
                         ids=OPERANDS.keys())
def test_only_a_block_diffusion_call_hands_k_and_v_over_twice(
        shape, kv_heads, mask, calls):
    q = jnp.zeros(shape, jnp.bfloat16)
    k = v = jnp.zeros((*shape[:2], kv_heads, shape[3]), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, interpret=True, **mask)
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert _kernel_calls(jaxpr.jaxpr) == calls
