"""``ops/lightning_attention.py``: the kernels in interpret mode and the
``lax.scan`` form against the quadratic form ``((Q K^T) * D) V`` (values, dq,
dk, dv), at several chunk and block sizes, at the steepest and the flattest
of the published slopes; a sequence that is no whole number of chunks refused
by name; no decay power a quotient of two powers."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import lightning_attention as la

D = 128
# a_h = 2^(-8 (h + 1) / 32) x the layer's factor: head 0 of layer 0 (lambda^256
# = 3e-91: nothing is carried) and head 31 of layer 31 (factor 1e-5: nothing
# decays), and two of the benchmark's (heads 24 and 31 of layer 1).
STEEPEST, FLATTEST = 2.0 ** (-8 / 32) * (1 + 1e-5), 2.0 ** -8 * 1e-5
HELD = (2.0 ** (-8 * 25 / 32) * (1 - 1 / 31 + 1e-5),
        2.0 ** -8 * (1 - 1 / 31 + 1e-5))


def _operands(seq, heads, dtype=jnp.float32, batch=1):
    ks = jax.random.split(jax.random.key(heads * 1000 + seq), 4)
    return tuple(jax.random.normal(key, (batch, seq, heads, D), dtype)
                 for key in ks)


def _value_and_grads(form, q, k, v, w):
    def f(q, k, v):
        out = form(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


def _l2(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("slopes", [(STEEPEST, FLATTEST), HELD],
                         ids=["steepest-flattest", "held"])
@pytest.mark.parametrize("form,sizes", [
    ("kernel", {"chunk": 128, "block": 256}),
    ("kernel", {"chunk": 128, "block": 128}),
    ("kernel", {"chunk": 256}),
    ("scan", {"chunk": 64}),
    ("scan", {"chunk": 256}),
], ids=["kernel-c128-b256", "kernel-c128-b128", "kernel-c256", "scan-c64",
        "scan-c256"])
def test_chunked_forms_agree_with_the_quadratic_form(form, sizes, slopes):
    """Values and the three gradients over 512 rows: several chunks a block
    and several blocks a call, so that the carried state crosses both kinds
    of boundary, in both directions."""
    q, k, v, w = _operands(512, 2)
    a, scale = jnp.asarray(slopes, jnp.float32), D ** -0.5

    def chunked(q, k, v):
        if form == "scan":
            return la.lightning_attention_scan(q, k, v, a, scale, **sizes)
        return la.lightning_attention(q, k, v, a, scale, interpret=True,
                                      **sizes)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *x: _value_and_grads(chunked, *x))(q, k, v, w)
        want = jax.jit(lambda *x: _value_and_grads(
            lambda q, k, v: la.lightning_attention_quadratic(
                q, k, v, a, scale), *x))(q, k, v, w)
    for name, g, wnt in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.all(np.isfinite(np.asarray(g))), name
        assert _l2(g, wnt) < 2e-5, (name, _l2(g, wnt))


def test_the_carried_state_is_a_real_part_of_a_held_head_s_output():
    """At the benchmark's slopes what earlier chunks carry in is no rounding,
    64 rows into a chunk and on: a kernel that dropped the state between
    chunks would read far off.  (At the steepest head it is nothing there,
    ``lambda^64`` under 2e-7, which is why the cell holds heads 24 to 31.)"""
    q, k, v, _ = _operands(512, 2)
    scale = D ** -0.5
    for slopes, least, most in ((HELD, 0.05, 2.0), ((STEEPEST,) * 2, 0, 1e-6)):
        a = jnp.asarray(slopes, jnp.float32)
        whole = la.lightning_attention_quadratic(q, k, v, a, scale)
        alone = la.lightning_attention_quadratic(
            q[:, 256:], k[:, 256:], v[:, 256:], a, scale)
        carried = _l2(alone[:, 64:], whole[:, 320:])
        assert least <= carried <= most, (slopes, carried)


def test_bfloat16_operands_round_where_the_flash_kernels_round():
    q, k, v, w = _operands(256, 2, jnp.bfloat16)
    a, scale = jnp.asarray(HELD, jnp.float32), D ** -0.5
    got = _value_and_grads(lambda q, k, v: la.lightning_attention(
        q, k, v, a, scale, chunk=128, interpret=True), q, k, v, w)
    want = _value_and_grads(lambda q, k, v: la.lightning_attention_quadratic(
        q, k, v, a, scale), *(x.astype(jnp.float32) for x in (q, k, v)), w)
    for name, g, wnt in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.bfloat16, name
        assert _l2(g, wnt) < 1e-2, (name, _l2(g, wnt))


@pytest.mark.parametrize("seq,sizes,message", [
    (200, {"chunk": 128}, "not a whole number of chunks of 128"),
    (512, {"chunk": 128, "block": 192}, "must be whole chunks of 128"),
])
def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused(seq, sizes,
                                                                 message):
    q, k, v, _ = _operands(seq, 1)
    with pytest.raises(ValueError, match=message):
        la.lightning_attention(q, k, v, jnp.ones((1,)), interpret=True,
                               **sizes)


def test_heads_narrower_than_a_lane_tile_are_refused_by_the_kernels():
    x = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        la.lightning_attention(x, x, x, jnp.ones((2,)), interpret=True)


def test_plan_takes_whole_chunks_under_the_block_rows():
    assert la.plan(16384) == (256, 2048)
    assert la.plan(16384, 128) == (128, 2048)
    assert la.plan(64) == (64, 64)
    assert la.plan(768, 256) == (256, 768)


def test_off_the_tpu_the_call_is_the_scan_form_and_passes_no_slope_gradient():
    q, k, v, w = _operands(128, 2)
    a = jnp.asarray(HELD, jnp.float32)
    got = la.lightning_attention(q, k, v, a)
    want = la.lightning_attention_scan(q, k, v, a, D ** -0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grad = jax.grad(lambda a: jnp.sum(
        la.lightning_attention(q, k, v, a, interpret=True) * w))(a)
    assert not np.any(np.asarray(grad))
