"""Gradients of the Pallas flash-attention kernels (dq, dkv) vs dense
attention, interpret mode on CPU.  Split from test_flash_attention.py so that
no pytest-xdist worker (``--dist loadfile``) holds both halves."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import (
    CHECKPOINT_NAMES, dense_attention, dense_attention_with_lse,
    flash_attention, flash_attention_with_lse)
from _flash_helpers import (  # noqa: F401
    SCHEDULES, _qkv, equations, kernel_calls, small_tiles)


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


MASKS = {"causal": {"causal": True}, "kv_lens": {"kv_lens": [19]},
         "block_diffusion": {"block_diffusion": (16, 8)}}


def _checkpointed(mask):
    """``{kind: value and gradients of a loss over one call}`` traced under
    ``jax.jit``: the call bare, inside ``jax.checkpoint``, and inside one
    whose policy asks for the names ``_flash_lse_fwd`` gives its output and
    row statistics."""
    def attend(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               interpret=True, **mask)

    kept = jax.checkpoint_policies.save_only_these_names(*CHECKPOINT_NAMES)
    q, k, v = operands = _qkv((1, 32, 2, 16), seed=17)
    return {kind: jax.jit(jax.value_and_grad(
        lambda q, k, v, fn=fn: jnp.sum(jnp.sin(fn(q, k, v))),
        argnums=(0, 1, 2))).trace(q, k, v)
        for kind, fn in {"none": attend, "plain": jax.checkpoint(attend),
                         "policy": jax.checkpoint(attend, policy=kept)}.items()
    }, operands


@pytest.mark.parametrize("mask", MASKS.values(), ids=MASKS.keys())
def test_a_checkpoint_keeps_the_forward_s_results_only_where_asked(mask):
    """Forward, dq, dkv.  With no policy the names are identities and the
    checkpoint runs the forward kernel again, as it did; a policy over the
    names keeps the two and the second run is gone.  Counted, not run."""
    traced, _ = _checkpointed(mask)
    assert {kind: kernel_calls(t.jaxpr.jaxpr) for kind, t in traced.items()
            } == {"none": 3, "plain": 4, "policy": 3}


def test_a_call_inside_a_checkpoint_gives_the_bits_it_gave_outside():
    traced, operands = _checkpointed(MASKS["causal"])
    bare, inside = (jax.tree.leaves(traced[kind].lower().compile()(*operands))
                    for kind in ("none", "plain"))
    for a, b_ in zip(inside, bare):
        assert float(jnp.linalg.norm(b_)) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def _dot_operand_dtypes(jaxpr) -> list:
    """Every dot_general of a jaxpr and of the jaxprs inside it (the Pallas
    kernel's body, its loops and branches): the dtypes of its operands."""
    return [tuple(v.aval.dtype for v in eqn.invars)
            for eqn in equations(jaxpr) if eqn.primitive.name == "dot_general"]


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
    dots = _dot_operand_dtypes(jaxpr.jaxpr)
    assert len(dots) >= 2 + 3 + 4            # fwd, dq, dkv
    assert all(a == b == dtype for a, b in dots), dots
