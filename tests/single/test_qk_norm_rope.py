"""``ops/qk_norm_rope.py``: a per-head RMSNorm then rotary as one op on
[B, S, H * D], against ``RMSNorm(eps, dtype)(x, rope)`` on [B, S, H, D] as
``models/sdar.py:SDARAttention`` composed it before the op (value, ``dx``,
``dscale``).  The kernels run in the Pallas interpreter here; the chip's
compiler sees them in ``test_tpu_compile.py`` and the chip in
``chip_smoke.py --qk-norm-rope``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import models
from horovod_tpu.models import sdar
from horovod_tpu.ops import qk_norm_rope as op

D, EPS, THETA = 128, 1e-6, 1e6


def _composed(x, scale, positions, heads, head_dim=D, theta=THETA):
    """The parent's lines: ``RMSNorm(eps, dtype)(q, rope)`` on the heads."""
    def rope(x):
        return sdar.rotary(x, positions, theta)

    out = sdar.RMSNorm(EPS, x.dtype).apply(
        {"params": {"scale": scale}},
        x.reshape(*x.shape[:-1], heads, head_dim), rope)
    return out.reshape(x.shape)


def _inputs(batch, length, heads, dtype, head_dim=D):
    ks = jax.random.split(jax.random.PRNGKey(heads + length), 3)
    x, g = (jax.random.normal(k, (batch, 2 * length, heads * head_dim),
                              jnp.float32).astype(dtype) for k in ks[:2])
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (head_dim,))
    # The clean and the noised copy carry the same positions.
    return x, scale, jnp.tile(jnp.arange(length), 2), g


def _fwd_bwd(fn, x, scale, g):
    out, vjp = jax.vjp(fn, x, scale)
    return (out, *vjp(g))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# One rounding to bfloat16 where the composition rounds once too: the
# results differ where a float32 sum lands on a rounding edge.
TOL = {"float32": 1e-5, "bfloat16": 8e-3}


@pytest.mark.parametrize("length", [48, 50], ids=["whole-chunks", "ragged"])
@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_against_the_norm_then_rotary(dtype, heads, length):
    x, scale, positions, g = _inputs(2, length, heads, jnp.dtype(dtype))
    kw = dict(heads=heads, head_dim=D, eps=EPS, theta=THETA)
    got = _fwd_bwd(lambda x, s: op.qk_norm_rope(x, s, positions,
                                                interpret=True, **kw),
                   x, scale, g)
    want = _fwd_bwd(lambda x, s: _composed(x, s, positions, heads),
                    x, scale, g)
    for name, a, b in zip(("out", "dx", "dscale"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < TOL[dtype], name
    assert got[2].dtype == jnp.float32


def test_kernels_over_several_grid_tiles(monkeypatch):
    """Three tiles a sequence: each takes its own rows of the tables, and
    ``dscale`` is the sum of every tile's part."""
    heads, length = 4, 48
    monkeypatch.setattr(op, "_BLOCK_BYTES", op._CHUNK * heads * D * 4)
    assert op._tile_rows(2 * length, heads * D, 4) == op._CHUNK
    x, scale, positions, g = _inputs(2, length, heads, jnp.float32)
    kw = dict(heads=heads, head_dim=D, eps=EPS, theta=THETA)
    got = _fwd_bwd(lambda x, s: op.qk_norm_rope(x, s, positions,
                                                interpret=True, **kw),
                   x, scale, g)
    want = _fwd_bwd(lambda x, s: _composed(x, s, positions, heads),
                    x, scale, g)
    for name, a, b in zip(("out", "dx", "dscale"), got, want):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("head_dim", [128, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_jax_numpy_form_is_the_norm_then_rotary(dtype, head_dim):
    """Off a TPU ``interpret=None`` is the plain form, and a head that is no
    lane tile takes it wherever it runs: the composition's own arithmetic."""
    x, scale, positions, g = _inputs(2, 24, 4, jnp.dtype(dtype), head_dim)
    kw = dict(heads=4, head_dim=head_dim, eps=EPS, theta=1e4)
    want = _fwd_bwd(lambda x, s: _composed(x, s, positions, 4, head_dim, 1e4),
                    x, scale, g)
    forms = [op.dense_qk_norm_rope, op.qk_norm_rope]
    if head_dim != D:
        forms.append(functools.partial(op.qk_norm_rope, interpret=True))
    for fn in forms:
        got = _fwd_bwd(lambda x, s: fn(x, s, positions, **kw), x, scale, g)
        for name, a, b in zip(("out", "dx", "dscale"), got, want):
            assert _rel(a, b) < (1e-6 if dtype == "float32" else 4e-3), name


def test_kernels_inside_a_jitted_shard_map_step():
    """As a cell runs it: x a chip's own, the scale replicated, so the
    scale's gradient is summed over the chips."""
    heads, length = 4, 32
    x, scale, positions, g = _inputs(4, length, heads, jnp.bfloat16)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))
    kw = dict(heads=heads, head_dim=D, eps=EPS, theta=THETA)

    def grads(fn):
        def loss(x, scale):
            out = fn(x, scale, positions, **kw).astype(jnp.float32)
            return jax.lax.psum(jnp.sum(out * g_of(x)), "hvd")

        def g_of(x):
            return jnp.cos(x.astype(jnp.float32))

        return jax.jit(jax.shard_map(
            jax.grad(loss, argnums=(0, 1)), mesh=mesh,
            in_specs=(P("hvd"), P()), out_specs=(P("hvd"), P())))(x, scale)

    got = grads(functools.partial(op.qk_norm_rope,
                                  interpret=pltpu.InterpretParams()))
    want = grads(op.dense_qk_norm_rope)
    assert _rel(got[0], want[0]) < TOL["bfloat16"]
    assert _rel(got[1], want[1]) < 1e-3


def test_a_projection_of_another_width_is_refused():
    x, scale, positions, _ = _inputs(1, 16, 4, jnp.float32)
    with pytest.raises(ValueError, match="not \\[B, S, 3 \\* 128\\]"):
        op.qk_norm_rope(x, scale, positions, heads=3, head_dim=D, eps=EPS,
                        theta=THETA)
    with pytest.raises(ValueError, match="positions"):
        op.qk_norm_rope(x, scale, positions[:-1], heads=4, head_dim=D,
                        eps=EPS, theta=THETA)


class _ComposedAttention(sdar.nn.Module):
    """``SDARAttention`` as it was before the op: the same parameters."""
    config: sdar.SDARConfig

    @sdar.nn.compact
    def __call__(self, x):
        cfg = self.config
        length = x.shape[1] // 2
        lead, d = x.shape[:-1], cfg.head_dim

        def proj(name, heads):
            return sdar.nn.Dense(heads * d, use_bias=False, dtype=cfg.dtype,
                                 name=name)(x).reshape(*lead, heads, d)

        q, k = proj("q_proj", cfg.num_heads), proj("k_proj", cfg.num_kv_heads)
        v = proj("v_proj", cfg.num_kv_heads)
        positions = jnp.tile(jnp.arange(length), 2)

        def rope(x):
            return sdar.rotary(x, positions, cfg.rope_theta)

        q = sdar.RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q, rope)
        k = sdar.RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k, rope)
        ctx = sdar.dense_attention(q, k, v, block_diffusion=(
            length, cfg.block_length))
        return sdar.nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                             name="o_proj")(ctx.reshape(*lead, -1))


def test_the_attention_block_is_what_it_was_at_the_tiny_model():
    """``SDAR_TINY``'s block (a head 16 wide: the ``jax.numpy`` form): the
    same parameter tree, the same output and the same gradients as the
    composition it replaces."""
    cfg = models.SDAR_TINY
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    new, old = sdar.SDARAttention(cfg), _ComposedAttention(cfg)
    variables = new.init(jax.random.PRNGKey(1), x)
    assert jax.tree.map(jnp.shape, variables) == jax.tree.map(
        jnp.shape, old.init(jax.random.PRNGKey(1), x))
    scales = jax.tree.map(lambda p: p + 0.1 * jnp.cos(jnp.arange(p.size)
                                                      .reshape(p.shape)),
                          variables)

    def loss(module, v, x):
        return jnp.sum(jnp.sin(module.apply(v, x)))

    got = jax.value_and_grad(functools.partial(loss, new), (0, 1))(scales, x)
    want = jax.value_and_grad(functools.partial(loss, old), (0, 1))(scales, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_the_model_s_logits_and_gradients_at_the_tiny_model():
    """``models.SDAR`` end to end at ``SDAR_TINY`` with the block swapped for
    the composition: logits and every leaf's gradient agree."""
    cfg = models.SDAR_TINY
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                             cfg.mask_token_id)
    noised = jnp.where(jnp.arange(16) % 3 == 0, cfg.mask_token_id, ids)
    model = models.SDAR(cfg)
    variables = model.init(jax.random.PRNGKey(3), ids, noised)

    def loss(v):
        logits = model.apply(v, ids, noised)
        return jnp.sum(jnp.sin(logits)), logits

    (_, got_logits), got = jax.value_and_grad(loss, has_aux=True)(variables)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sdar, "SDARAttention", _ComposedAttention)
        (_, want_logits), want = jax.value_and_grad(loss, has_aux=True)(
            variables)
    np.testing.assert_allclose(got_logits, want_logits, rtol=1e-5, atol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert any("q_norm" in jax.tree_util.keystr(p) for p, _ in flat)
    for (path, b), a in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
