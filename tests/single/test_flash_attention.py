"""Pallas flash-attention kernel vs dense attention (interpret mode on CPU)
and the GPT model family."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import (
    dense_attention, dense_attention_with_lse, flash_attention,
    flash_attention_with_lse, tile_plan)


@pytest.fixture
def small_tiles(monkeypatch):
    """The schedule of a long sequence at a toy size: resident tiles of 32
    rows walked in steps of 8, so a 64-long block holds two tiles and the
    diagonal crosses each in four steps."""
    monkeypatch.setattr(fa, "_MAX_TILE", 32)
    monkeypatch.setattr(fa, "_MAX_STEP", 8)


def _qkv(shape, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


# (shape, block_q, block_k): explicit 16-wide blocks; one block of several
# tiles and steps (with small_tiles); blocks that differ; a tail-padded
# length whose last block is partly dead; the plan's own default.
SCHEDULES = {
    "16x16": ((1, 32, 2, 16), 16, 16),
    "16x16-b2h4": ((2, 64, 4, 32), 16, 16),
    "one-block": ((1, 128, 2, 16), 128, 128),
    "two-blocks": ((1, 128, 2, 16), 64, 64),
    "bq>bk": ((1, 128, 2, 16), 64, 32),
    "bq<bk": ((1, 128, 2, 16), 32, 64),
    "padded-tail": ((1, 100, 2, 16), 64, 64),
    "padded-bq>bk": ((1, 75, 2, 8), 64, 16),
    "default-plan": ((1, 200, 2, 16), None, None),
    # The block layout (PR 28).  The cases above run the fallback
    # ([B * H, S, D]: two heads of 16 or 8 fill no lane tile) except
    # 16x16-b2h4, four heads of 32 a block.  Lane-dense blocks of the
    # model's [B, S, H * D]: two heads of 64 a grid step, streamed in small
    # blocks, tail-padded, and on the default plan; one head of 128; and the
    # fallback where no whole number of heads fills 128 lanes.
    "g2-two-blocks": ((1, 128, 2, 64), 64, 64),
    "g2-bq>bk-b2h4": ((2, 64, 4, 64), 32, 16),
    "g2-padded-tail": ((1, 100, 2, 64), 64, 64),
    "g2-default-plan": ((1, 200, 4, 64), None, None),
    "g1-head_dim-128": ((1, 64, 2, 128), 32, 32),
    "fallback-head_dim-80": ((1, 64, 2, 80), 32, 32),
    "fallback-3-heads-of-64": ((1, 64, 3, 64), 32, 32),
}
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


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("schedule", SCHEDULES.values(), ids=SCHEDULES.keys())
def test_flash_kernel_matches_dense(causal, schedule, small_tiles):
    shape, block_q, block_k = schedule
    q, k, v = _qkv(shape)
    expected, expected_lse = dense_attention_with_lse(q, k, v, causal=causal)
    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=block_q, block_k=block_k,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(expected_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_default_plan_at_gpt2_medium(causal):
    """The schedule the benchmark's GPT cells run (1024 x 64: one grid step
    a head, a 1024-row tile walked in 256-row steps), B*H = 2."""
    q, k, v = _qkv((1, 1024, 2, 64), seed=5)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_attention(q, k, v, causal=causal)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_padded_seq(causal):
    """Non-block-multiple sequence lengths run through the kernel with tail
    masking (no dense fallback)."""
    b, s, h, d = 1, 23, 2, 8
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    expected = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    assert out.shape == (b, s, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_mismatched_blocks():
    """s a multiple of one block size but not the other: padding must go to
    the lcm so both the q grid and the kv loop tile the sequence."""
    b, s, h, d = 1, 32, 2, 8
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    expected = dense_attention(q, k, v)
    for bq, bk in [(24, 32), (32, 24)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5, err_msg=f"{bq},{bk}")


def test_flash_cpu_fallback_is_dense():
    # On CPU (interpret=None) the wrapper must route to the dense path.
    q = k = v = jnp.ones((1, 8, 2, 4))
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)))


def test_gpt_tiny_train_step():
    import optax

    from horovod_tpu.models import GPT, GPT_TINY, lm_loss

    model = GPT(GPT_TINY)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    params = model.init(jax.random.PRNGKey(1), ids)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 32, 512)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(model.apply(p, ids), ids))(params)
    assert np.isfinite(float(loss))
    assert float(optax.global_norm(grads)) > 0


def test_gpt_sequence_parallel_matches_dense():
    import dataclasses

    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.ops.collectives import shard_map

    from horovod_tpu.models import GPT, GPT_TINY

    cfg_sp = dataclasses.replace(GPT_TINY, sp_axis_name="sp", num_layers=1)
    cfg_dense = dataclasses.replace(GPT_TINY, num_layers=1)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 512)

    m_dense = GPT(cfg_dense)
    variables = m_dense.init(jax.random.PRNGKey(3), ids)
    expected = m_dense.apply(variables, ids)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("sp",))
    m_sp = GPT(cfg_sp)
    out = shard_map(lambda i: m_sp.apply(variables, i),
                    mesh=mesh, in_specs=P(None, "sp"),
                    out_specs=P(None, "sp"))(ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("schedule", SCHEDULES.values(), ids=SCHEDULES.keys())
def test_flash_kernel_grads_match_dense(causal, schedule, small_tiles):
    """The custom-VJP backward kernels (dQ, dK/dV) against autodiff through
    the dense reference, for the (out, lse) pair with a cotangent on each
    (the lse cotangent folds into delta; ring attention needs it)."""
    shape, block_q, block_k = schedule
    b, s, h, d = shape
    q, k, v = _qkv(shape, seed=3)
    w_lse = jax.random.normal(jax.random.PRNGKey(4), (b, h, s), jnp.float32)

    def loss(fn, q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(jnp.sin(out)) + jnp.sum(lse * w_lse)

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=block_q, block_k=block_k,
                                        interpret=True)

    def dense(q, k, v):
        return dense_attention_with_lse(q, k, v, causal=causal)

    gf = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(functools.partial(loss, dense), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_default_plan_grads_at_gpt2_medium():
    q, k, v = _qkv((1, 1024, 2, 64), seed=6)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v, causal=True)))

    gf = jax.grad(functools.partial(
        loss, functools.partial(flash_attention, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(functools.partial(loss, dense_attention),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_grads_padded_seq(causal):
    """Backward through tail-masked padding: padded rows/keys contribute
    zero gradient and real gradients match dense."""
    b, s, h, d = 1, 23, 2, 8
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_kernel_grads_bf16():
    """bf16 inputs through the backward kernels (the dtype the models
    train in): grads match dense within bf16 tolerance."""
    b, s, h, d = 1, 32, 2, 16
    key = jax.random.PRNGKey(13)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            interpret=True).astype(jnp.float32)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(
            q, k, v, causal=True).astype(jnp.float32)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b_, dtype=np.float32),
                                   rtol=0.1, atol=0.05)


def _dot_operand_dtypes(jaxpr, found):
    """Every dot_general of a jaxpr and of the jaxprs inside it (the Pallas
    kernel's body, its loops and branches): the dtypes of its operands."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(v.aval.dtype for v in eqn.invars))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_operand_dtypes(sub, found)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_dots_take_operands_as_they_arrive(dtype):
    """bf16 inputs meet the MXU as bf16 in all three kernels (float32
    accumulation): no convert_element_type to float32 feeds a dot."""
    dtype = jnp.dtype(dtype)
    q, k, v = _qkv((1, 64, 2, 16), dtype)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True)
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    dots = _dot_operand_dtypes(jaxpr.jaxpr, [])
    assert len(dots) >= 2 + 3 + 4            # fwd, dq, dkv
    assert all(a == b == dtype for a, b in dots), dots


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
