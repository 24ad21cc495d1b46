"""Differential attention's two softmax maps as ``models/phi4flash.py`` runs
them on the flash kernels (one grouped call a map at the value's width, q and
k zero-padded to it), through the Pallas interpreter, against the dense
two-map form of ``benchmark/references/phi4flash.py``: values and dq, dk, dv,
causal and under a band, two query pairs a key/value pair."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import common
from benchmark.references import phi4flash as reference
from horovod_tpu.models import phi4flash

SEQ, HEADS, KV_HEADS, D = 256, 8, 4, 64
CFG = phi4flash.Phi4FlashConfig(num_heads=HEADS, num_kv_heads=KV_HEADS,
                                head_dim=D, use_flash=True)
MASKS = {"causal": None, "band": 96}
RESULTS = ("a1", "a2", "dq", "dk", "dv")


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(keys[0], (1, SEQ, HEADS * D))
    k, v = (jax.random.normal(key, (1, SEQ, KV_HEADS * D))
            for key in keys[1:3])
    g = tuple(jax.random.normal(key, (1, SEQ, HEADS // 2, 2 * D))
              for key in keys[3:])
    return q, k, v, g


def _both(fn, g, *args):
    out, pull = jax.vjp(fn, *args)
    return (*out, *pull(g))


@pytest.fixture(scope="module")
def results(operands):
    """Per mask: what the kernels (interpreted) and the dense form give."""
    q, k, v, g = operands
    out = {}
    with jax.default_matmul_precision("highest"):
        for mask, window in MASKS.items():
            got = jax.jit(functools.partial(_both, lambda q, k, v: (
                phi4flash.two_maps(CFG, q, k, v, window, interpret=True))))(
                    g, q, k, v)

            def dense(q, k, v):
                a1, a2 = reference.two_maps(
                    q[0].reshape(SEQ, HEADS, D),
                    k[0].reshape(SEQ, KV_HEADS, D),
                    v[0].reshape(SEQ, KV_HEADS, D), window)
                return a1[None], a2[None]

            want = jax.jit(functools.partial(_both, dense))(g, q, k, v)
            out[mask] = dict(zip(RESULTS, zip(got, want)))
    return out


@pytest.mark.parametrize("name", RESULTS)
@pytest.mark.parametrize("mask", MASKS)
def test_the_two_maps_on_the_kernels_are_the_dense_two_maps(results, mask,
                                                            name):
    got, want = results[mask][name]
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert common.rel_err(got, want) < 2e-5, (mask, name)


def test_the_band_is_not_the_causal_call(results):
    a, b = results["causal"]["a1"][1], results["band"]["a1"][1]
    assert common.rel_err(a[:, :MASKS["band"]], b[:, :MASKS["band"]]) < 1e-6
    assert common.rel_err(a, b) > 1e-2
