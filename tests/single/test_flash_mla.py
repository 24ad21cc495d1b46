"""The flash kernels with a second score operand (``q_rope`` / ``k_rope``:
multi-head latent attention's scores, ``q . k + q_rope . k_rope`` under one
softmax, the rotary key one for all heads) in interpret mode against
``dense_attention`` with the pair: values, ``lse`` and the five gradients;
what the pair refuses; and the plan it is given."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import (
    dense_attention, dense_attention_with_lse, flash_attention,
    flash_attention_with_lse, tile_plan)

GRADS = ("dq", "dk", "dv", "dq_rope", "dk_rope")


def _operands(seq, heads, dtype, d=128, r=64, batch=1):
    ks = jax.random.split(jax.random.key(heads * 1000 + seq), 6)
    q, k, v = (jax.random.normal(key, (batch, seq, heads, d), dtype)
               for key in ks[:3])
    qr = jax.random.normal(ks[3], (batch, seq, heads, r), dtype)
    kr = jax.random.normal(ks[4], (batch, seq, 1, r), dtype)
    w = jax.random.normal(ks[5], (batch, seq, heads, d), jnp.float32)
    return (q, k, v, qr, kr), w


def _value_and_grads(attend, operands, w, causal, **kw):
    """``out``, ``lse`` and the gradients of a function of both (so that an
    lse cotangent reaches the kernels' ``delta``)."""
    def f(q, k, v, qr, kr):
        out, lse = attend(q, k, v, causal=causal, q_rope=qr, k_rope=kr, **kw)
        return (jnp.sum(out.astype(jnp.float32) * w)
                + jnp.sum(jnp.sin(lse)), (out, lse))

    (_, seen), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True)(*operands)
    return seen, grads


@pytest.mark.parametrize("heads,seq,blocks,causal,dtype", [
    (2, 256, {}, True, jnp.float32),
    (4, 200, {"block_q": 128, "block_k": 128}, True, jnp.float32),
    (2, 200, {"block_q": 128, "block_k": 256}, False, jnp.float32),
    (4, 256, {"block_q": 128, "block_k": 128}, True, jnp.bfloat16),
], ids=["2h-256", "4h-200-ragged", "2h-200-noncausal", "4h-256-bf16"])
def test_paired_kernels_agree_with_dense_attention(heads, seq, blocks, causal,
                                                   dtype):
    """Values, ``lse``, dq, dq_rope, dk, dk_rope (the sum over the heads) and
    dv: 2 and 4 heads (one and two grid rows of two heads), a length that is
    no multiple of the tile, several blocks a side, float32 (what is left is
    the order of the sums) and bfloat16 (both sides round p and ds to it)."""
    operands, w = _operands(seq, heads, dtype)
    (out, lse), grads = _value_and_grads(
        flash_attention_with_lse, operands, w, causal, interpret=True,
        **blocks)
    (want_out, want_lse), want = _value_and_grads(
        dense_attention_with_lse, operands, w, causal)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    assert out.shape == operands[0].shape and out.dtype == dtype
    assert lse.shape == (1, heads, seq) and lse.dtype == jnp.float32
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want_out.astype(jnp.float32), atol=tol)
    np.testing.assert_allclose(lse, want_lse, atol=tol)
    for name, g, b, x in zip(GRADS, grads, want, operands):
        assert g.shape == x.shape and g.dtype == x.dtype, name
        g, b = g.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(g - b))) < tol * max(
            1.0, float(jnp.max(jnp.abs(b)))), name


def test_the_default_scale_is_over_both_widths():
    """``scale=None`` is ``(128 + 64) ** -0.5``."""
    (q, k, v, qr, kr), _ = _operands(128, 2, jnp.float32)
    got = flash_attention(q, k, v, causal=True, interpret=True, q_rope=qr,
                          k_rope=kr)
    want = dense_attention(q, k, v, causal=True, scale=192 ** -0.5,
                           q_rope=qr, k_rope=kr)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_plan_of_the_pair_stays_under_what_mosaic_is_asked_for():
    """Two heads a grid step, in blocks whose estimate leaves a quarter of
    the limit free: 1,024 rows at ``joyai-mla-ep16-s16384``'s shape, what ran
    on the chip (2,048 need 31.7 of the 32 MiB: PERF.md, PR 54)."""
    plan = tile_plan(16384, 128, 2, True, heads=4, rope=64)
    assert (plan.heads_per_block, plan.lanes) == (2, 256)
    assert plan.block_q == plan.block_k == 1024
    assert plan.vmem_bytes <= fa._VMEM_BUDGET_PAIR < fa._VMEM_LIMIT
    assert tile_plan(16384, 128, 2, True, heads=4).block_q == 2048


@pytest.mark.parametrize("rope", [32, 128])
def test_a_rotary_width_never_run_on_the_chip_is_refused(rope):
    """Two heads of 64 rotary lanes fill a lane tile; nothing else was built
    or timed, and nothing else is planned on a guess."""
    (q, k, v, qr, kr), _ = _operands(128, 2, jnp.float32, r=rope)
    with pytest.raises(ValueError, match=f"q_rope / k_rope {rope} wide"):
        flash_attention(q, k, v, causal=True, interpret=True, q_rope=qr,
                        k_rope=kr)
    assert dense_attention(q, k, v, causal=True, q_rope=qr,
                           k_rope=kr).shape == q.shape


@pytest.mark.parametrize("attend", [flash_attention, dense_attention],
                         ids=["flash", "dense"])
def test_the_pair_with_any_other_mask_raises_by_name(attend):
    (q, k, v, qr, kr), _ = _operands(128, 2, jnp.float32)
    kw = {"interpret": True} if attend is flash_attention else {}
    for what, more, operands in (
            ("kv_lens", {"kv_lens": jnp.array([64])}, (q, k, v)),
            ("window", {"causal": True, "window": 16}, (q, k, v)),
            ("block_diffusion", {"block_diffusion": (64, 16)}, (q, k, v)),
            ("grouped key/value heads", {"causal": True},
             (q, k[:, :, :1], v[:, :, :1]))):
        with pytest.raises(ValueError, match=f"q_rope / k_rope with {what}"):
            attend(*operands, q_rope=qr, k_rope=kr, **more, **kw)
    with pytest.raises(ValueError, match="come as a pair"):
        attend(q, k, v, causal=True, q_rope=qr, **kw)
    with pytest.raises(ValueError, match="one rotary key for all heads"):
        attend(q, k, v, causal=True, q_rope=qr,
               k_rope=jnp.broadcast_to(kr, qr.shape), **kw)
    if attend is flash_attention:
        with pytest.raises(ValueError, match="3 heads"):
            attend(q[:, :, :1].repeat(3, 2), k[:, :, :1].repeat(3, 2),
                   v[:, :, :1].repeat(3, 2), causal=True, interpret=True,
                   q_rope=qr[:, :, :1].repeat(3, 2), k_rope=kr)
