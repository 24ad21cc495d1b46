"""Pallas flash-attention kernel vs dense attention (interpret mode on CPU)
and the GPT model family.  The kernels' gradients are in
test_flash_attention_grads.py, the tile plan in test_flash_attention_plan.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import (
    dense_attention, dense_attention_with_lse, flash_attention,
    flash_attention_with_lse)
from _flash_helpers import SCHEDULES, _qkv, small_tiles  # noqa: F401


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
