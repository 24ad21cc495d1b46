"""The flash kernels with a key length per sequence (``kv_lens``: BERT's
tail-padding mask) in interpret mode against masked dense attention, and the
meaning the two share: padded keys are seen by no query, padded rows come
out zero and pass no gradient on."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import dense_attention, flash_attention

# BERT-Large's attention at phase 2 (512 x 64, the default plan: one grid
# step a head, a 512-row tile in 256-row steps), one sequence per case: a
# single key; one key short of a lane tile, a whole one, one key into the
# next; a length that ends inside the second step; no padding at all.
LENGTHS = (1, 127, 128, 129, 300, 512)
# float32 operands in the interpreter: the kernels and the dense form differ
# by the order of float32 sums only (read: 4e-7 on outputs near 1, 4e-7 on
# gradients near 3).  bf16 operands would read 1e-2.
TOL = dict(rtol=2e-5, atol=2e-5)


def _qkvw(shape, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def _masked_dense(q, k, v, lens):
    """Masked softmax attention written out, apart from ``dense_attention``:
    what both implementations are held to."""
    s, d = q.shape[1], q.shape[-1]
    real = jnp.arange(s)[None, :] < lens[:, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    scores = jnp.where(real[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out * real[:, :, None, None]


@pytest.fixture(scope="module")
def bert_phase2():
    q, k, v, w = _qkvw((len(LENGTHS), 512, 2, 64), seed=21)
    return q, k, v, w, jnp.asarray(LENGTHS, jnp.int32)


@pytest.mark.parametrize("impl", ["kernels", "dense"])
def test_kv_lens_forward_matches_masked_attention(bert_phase2, impl):
    q, k, v, _, lens = bert_phase2
    fn = (functools.partial(flash_attention, interpret=True)
          if impl == "kernels" else dense_attention)
    out = np.asarray(fn(q, k, v, kv_lens=lens))
    np.testing.assert_allclose(out, np.asarray(_masked_dense(q, k, v, lens)),
                               **TOL)
    for b, n in enumerate(LENGTHS):     # padded rows are zeros, exactly
        assert not out[b, n:].any()
        assert out[b, :n].any()


@pytest.mark.parametrize("impl", ["kernels", "dense"])
def test_kv_lens_gradients_match_masked_attention(bert_phase2, impl):
    """dq, dk and dv of the three kernels under the mask.  The cotangent is
    dense over every row, padded ones too: they pass nothing on."""
    q, k, v, w, lens = bert_phase2
    fn = (functools.partial(flash_attention, interpret=True, kv_lens=lens)
          if impl == "kernels" else
          functools.partial(dense_attention, kv_lens=lens))

    def loss(attend, q, k, v):
        return jnp.sum(jnp.sin(attend(q, k, v)) * w)

    got = jax.grad(functools.partial(loss, fn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(
        loss, functools.partial(_masked_dense, lens=lens)),
        argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), **TOL)
        for b, n in enumerate(LENGTHS):
            assert not np.asarray(g)[b, n:].any()


# [heads, head_dim]: the fallback layout (one head a grid row), and the
# model's own with two and four heads a grid step, which share the row's one
# length (``bert_phase2`` above is two heads of 64 on the default plan).
HEADS = {"fallback-2x16": (2, 16), "g2-2x64": (2, 64), "g2-4x64": (4, 64),
         "g4-4x32": (4, 32), "fallback-3x64": (3, 64)}


@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS.keys())
def test_kv_lens_streamed_blocks_and_small_steps(monkeypatch, heads):
    """The same mask where the plan streams: two grid blocks a sequence,
    tiles of 32 rows in steps of 8, lengths that end inside a step, at a
    block's edge and inside the second block, on a sequence that is itself
    tail-padded (100 -> 128)."""
    monkeypatch.setattr(fa, "_MAX_TILE", 32)
    monkeypatch.setattr(fa, "_MAX_STEP", 8)
    lens = jnp.asarray([1, 7, 64, 65, 93, 100], jnp.int32)
    q, k, v, w = _qkvw((6, 100, *heads), seed=22)

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        return jnp.sum(jnp.sin(out) * w), out

    flash = functools.partial(flash_attention, block_q=64, block_k=64,
                              interpret=True, kv_lens=lens)
    dense = functools.partial(_masked_dense, lens=lens)
    (got, out), want = [
        jax.grad(functools.partial(loss, f), argnums=(0, 1, 2),
                 has_aux=True)(q, k, v) for f in (flash, dense)]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[1]), **TOL)
    for g, e in zip(got, want[0]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), **TOL)


def test_kv_lens_bf16_operands():
    """The dtype the models train in: bf16 operands to every dot, float32
    softmax.  Against float32 masked attention on the same (rounded) inputs
    the error is bf16's own (read 6e-3 on outputs, 3e-2 on gradients)."""
    lens = jnp.asarray([40, 128], jnp.int32)
    q, k, v, w = (x.astype(jnp.bfloat16)
                  for x in _qkvw((2, 128, 2, 64), seed=23))

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32)
                       * w.astype(jnp.float32))

    got = jax.grad(functools.partial(loss, functools.partial(
        flash_attention, interpret=True, kv_lens=lens)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, functools.partial(
        _masked_dense, lens=lens)), argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, e in zip(got, want):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(e),
                                   rtol=0.1, atol=0.06)


def test_kv_lens_is_clipped_and_refused_under_a_causal_mask():
    q, k, v, _ = _qkvw((2, 16, 1, 8), seed=24)
    out = dense_attention(q, k, v, kv_lens=jnp.asarray([0, 99]))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_attention(
            q, k, v, kv_lens=jnp.asarray([1, 16]))))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=True, kv_lens=jnp.asarray([4, 4]))
    with pytest.raises(ValueError, match="one length per sequence"):
        dense_attention(q, k, v, kv_lens=jnp.asarray([4]))


def test_without_kv_lens_the_kernels_take_no_new_operand():
    """``kv_lens=None`` is the call GPT makes: three operands to the forward
    kernel as before, four with the lengths."""
    q = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    yield from pallas_calls(inner)

    def operands(**kw):
        jaxpr = jax.make_jaxpr(functools.partial(
            flash_attention, interpret=False, **kw))(q, q, q)
        call, = pallas_calls(jaxpr.jaxpr)
        return len(call.invars)

    assert operands(causal=True) == operands(causal=False) == 3
    assert operands(kv_lens=jnp.asarray([3, 256], jnp.int32)) == 4
