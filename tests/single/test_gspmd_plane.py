"""Differential parity + demotion coverage for the gspmd data plane.

``plane="gspmd"`` (ops/gspmd_plane.py) must train to the same parameters
as the eager shard_map plane — the sharding annotations only guide
GSPMD's scheduler, the math is the global-mean gradient either way — and
every configuration that cannot compose must demote to the eager plane
bit-identically, with a named counter recording why (ISSUE 17: the
tolerance budget covers fp32 reduction order ONLY; demotions get zero
tolerance).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from horovod_tpu.ops import gspmd_plane as gp
from horovod_tpu.optimizer import DistributedOptimizer

pytestmark = pytest.mark.usefixtures("hvd_single")

N_DEV = 8
# fp32 reduction-order tolerance: the two planes may associate the 8
# shard contributions differently, nothing else.
RTOL = 2e-6


@pytest.fixture(autouse=True)
def _fresh_counters():
    gp.reset_plane_counters()
    yield
    gp.reset_plane_counters()


# ---------------------------------------------------------------------------
# Mesh + sharding-tree utilities
# ---------------------------------------------------------------------------

def test_mesh_1d_default():
    mesh = gp.build_gspmd_mesh()
    assert mesh.axis_names == (gp.BATCH_AXIS,)
    assert mesh.size == len(jax.devices())


def test_mesh_2d_model_parallel_degrades():
    mesh = gp.build_gspmd_mesh(model_parallel=True)
    assert mesh.axis_names == (gp.BATCH_AXIS, gp.MODEL_AXIS)
    assert mesh.shape[gp.BATCH_AXIS] == 2
    assert mesh.shape[gp.MODEL_AXIS] == N_DEV // 2
    # Degradation ladder as devices run out (SNIPPETS.md [3]).
    assert gp._model_factors(8) == (2, 4)
    assert gp._model_factors(4) == (2, 2)
    assert gp._model_factors(2) == (1, 2)
    assert gp._model_factors(1) == (1, 1)


def test_batch_pspec_divisibility_rule():
    mesh = gp.build_gspmd_mesh()
    n = mesh.shape[gp.BATCH_AXIS]
    divisible = jnp.zeros((n * 4, 3), jnp.float32)
    ragged = jnp.zeros((n * 4 + 1, 3), jnp.float32)
    scalar = jnp.zeros((), jnp.float32)
    assert gp.batch_pspec(divisible, mesh) == P(gp.BATCH_AXIS, None)
    assert gp.batch_pspec(ragged, mesh) == P()
    assert gp.batch_pspec(scalar, mesh) == P()


def test_tree_shardings_mirror_tree():
    mesh = gp.build_gspmd_mesh()
    tree = {"x": jnp.zeros((N_DEV * 2, 5), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}
    sh = gp.tree_shardings(tree, mesh)
    assert isinstance(sh["x"], NamedSharding)
    assert sh["x"].spec == P(gp.BATCH_AXIS, None)
    assert sh["b"].spec == P()  # 3 does not divide 8: replicated


# ---------------------------------------------------------------------------
# resolve_plane rules
# ---------------------------------------------------------------------------

def test_resolve_plane_rules():
    # Explicit eager is a choice, not a demotion: no counter.
    assert gp.resolve_plane("eager") == ("eager", None)
    assert gp.plane_counters() == {}
    # A quantized device codec owns the traced reduction: demote.
    assert gp.resolve_plane("gspmd", device_codec="int4")[0] == "eager"
    assert gp.plane_counters() == {"demote_quantized": 1}
    # codec "none" does not demote.
    plane, mesh = gp.resolve_plane("gspmd", device_codec="none")
    assert plane == "gspmd" and mesh is not None
    # Single-device mesh: nothing to overlap.
    mesh1 = gp.build_gspmd_mesh(devices=jax.devices()[:1])
    assert gp.resolve_plane("gspmd", mesh=mesh1)[0] == "eager"
    c = gp.plane_counters()
    assert c["demote_world1"] == 1 and c["gspmd"] == 1
    # count=False (the auto probe) resolves silently.
    gp.reset_plane_counters()
    assert gp.resolve_plane("auto", mesh=mesh1, count=False)[0] == "eager"
    assert gp.resolve_plane("auto", count=False)[0] == "gspmd"
    assert gp.plane_counters() == {}


# ---------------------------------------------------------------------------
# Train-step harnesses: one problem, both calling conventions
# ---------------------------------------------------------------------------

def _data(n=64, d=4, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    w_true = jnp.asarray(rs.randn(d), jnp.float32)
    y = x @ w_true + jnp.asarray(0.1 * rs.randn(n), jnp.float32)
    return x, y


def _params(d=4):
    return {"w": jnp.zeros((d,), jnp.float32),
            "b": jnp.zeros((), jnp.float32)}


def _loss(p, x, y):
    pred = x @ p["w"] + p["b"]
    return jnp.mean((pred - y) ** 2)


def _train_gspmd(tx, steps=5):
    """gspmd convention: plain jit, batch-sharded inputs, global-mean
    loss — backprop inserts the reduction, the optimizer only annotates."""
    mesh = gp.build_gspmd_mesh()
    x, y = _data()
    x = jax.device_put(x, NamedSharding(mesh, P(gp.BATCH_AXIS)))
    y = jax.device_put(y, NamedSharding(mesh, P(gp.BATCH_AXIS)))
    params = _params()
    state = tx.init(params)

    @jax.jit
    def step(p, s, xs, ys):
        g = jax.grad(_loss)(p, xs, ys)
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    for _ in range(steps):
        params, state = step(params, state, x, y)
    return params


def _train_eager(tx, steps=5):
    """eager convention: shard_map with a bound mesh axis, per-shard mean
    loss, optimizer psum-averages to the global mean."""
    mesh = Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))
    x, y = _data()
    params = _params()
    state = tx.init(params)

    def shard_step(p, s, xs, ys):
        g = jax.grad(_loss)(p, xs, ys)  # local mean over this shard
        u, s2 = tx.update(g, s, p)      # psum-average -> global mean
        return optax.apply_updates(p, u), s2

    smap = shard_map(shard_step, mesh=mesh,
                     in_specs=(P(), P(), P("hvd"), P("hvd")),
                     out_specs=(P(), P()), check_vma=False)
    step = jax.jit(smap)
    for _ in range(steps):
        params, state = step(params, state, x, y)
    return params


def _assert_close(a, b, rtol=RTOL):
    ja, jb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    for la, lb in zip(ja, jb):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=0)


def _assert_bit_identical(a, b):
    ja, jb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    for la, lb in zip(ja, jb):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# The parity bar (acceptance): gspmd == eager up to fp32 reduction order
# ---------------------------------------------------------------------------

def test_parity_gspmd_vs_eager():
    p_gspmd = _train_gspmd(DistributedOptimizer(optax.sgd(0.1),
                                                plane="gspmd"))
    assert gp.plane_counters().get("gspmd") == 1
    p_eager = _train_eager(DistributedOptimizer(optax.sgd(0.1),
                                                plane="eager"))
    _assert_close(p_gspmd, p_eager)


def test_auto_adapts_to_either_convention():
    """One ``plane="auto"`` optimizer serves both conventions: the plane
    is picked per trace from whether the mesh axis is bound — and the
    probe never reads as a demotion stream."""
    p_gspmd = _train_gspmd(DistributedOptimizer(optax.sgd(0.1)))
    p_eager = _train_eager(DistributedOptimizer(optax.sgd(0.1)))
    _assert_close(p_gspmd, p_eager)
    assert gp.plane_counters() == {}


# ---------------------------------------------------------------------------
# Demotions: compose or fall back bit-identically, counted
# ---------------------------------------------------------------------------

def test_world1_demotes_bit_identical():
    mesh1 = gp.build_gspmd_mesh(devices=jax.devices()[:1])
    tx_g = DistributedOptimizer(optax.sgd(0.1), plane="gspmd", mesh=mesh1)
    assert gp.plane_counters() == {"demote_world1": 1}
    tx_e = DistributedOptimizer(optax.sgd(0.1), plane="eager")

    # Demoted means the SAME eager path: run both un-jitted in the
    # single-process runtime and require exact equality.
    x, y = _data(n=8)
    p_g, p_e = _params(), _params()
    s_g, s_e = tx_g.init(p_g), tx_e.init(p_e)
    g_g = jax.grad(_loss)(p_g, x, y)
    g_e = jax.grad(_loss)(p_e, x, y)
    u_g, _ = tx_g.update(g_g, s_g, p_g)
    u_e, _ = tx_e.update(g_e, s_e, p_e)
    _assert_bit_identical(optax.apply_updates(p_g, u_g),
                          optax.apply_updates(p_e, u_e))


def test_quantized_codec_demotes_whole_optimizer():
    """device=int4 and gspmd cannot mix within one step: the quantized
    ppermute ring is an explicit shard_map program GSPMD cannot schedule
    through, so the optimizer stays eager end to end (docs/compression.md
    compose-or-demote rule) — bit-identically."""
    tx_q = DistributedOptimizer(optax.sgd(0.1), plane="gspmd",
                                device_compression="int4")
    c = gp.plane_counters()
    assert c == {"demote_quantized": 1}, c
    tx_ref = DistributedOptimizer(optax.sgd(0.1), plane="eager",
                                  device_compression="int4")
    p_q = _train_eager(tx_q, steps=3)
    p_ref = _train_eager(tx_ref, steps=3)
    _assert_bit_identical(p_q, p_ref)


def test_non_fp32_leaves_demote_per_leaf_bit_identical():
    """A bf16 leaf skips the annotation (demote_dtype) and passes through
    untouched; fp32 leaves still take the plane.  Against a raw optax
    baseline in the same convention the whole update must be bit-identical
    — the constraint is a scheduling hint, not math."""
    mesh = gp.build_gspmd_mesh()
    x, y = _data()
    x = jax.device_put(x, NamedSharding(mesh, P(gp.BATCH_AXIS)))
    y = jax.device_put(y, NamedSharding(mesh, P(gp.BATCH_AXIS)))

    def loss(p, xs, ys):
        pred = xs @ p["w"] + p["e"].astype(jnp.float32)
        return jnp.mean((pred - ys) ** 2)

    def one_step(tx):
        p = {"w": jnp.zeros((4,), jnp.float32),
             "e": jnp.zeros((), jnp.bfloat16)}
        s = tx.init(p)

        @jax.jit
        def step(p, s, xs, ys):
            g = jax.grad(loss)(p, xs, ys)
            u, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, u), s2

        p, _ = step(p, s, x, y)
        return p

    p_g = one_step(DistributedOptimizer(optax.sgd(0.1), plane="gspmd"))
    c = gp.plane_counters()
    assert c.get("gspmd") == 1
    assert c.get("demote_dtype", 0) >= 1  # the bf16 leaf, at trace time
    p_raw = one_step(optax.sgd(0.1))
    _assert_bit_identical(p_g, p_raw)


def test_optimizer_level_demotions_counted():
    """Features the gspmd plane cannot express yet demote at construction
    with their own counters (accumulation, process sets, predivide,
    ZeRO-1 sharding)."""
    DistributedOptimizer(optax.sgd(0.1), plane="gspmd",
                         backward_passes_per_step=2)
    assert gp.plane_counters() == {"demote_accum": 1}
    gp.reset_plane_counters()
    DistributedOptimizer(optax.sgd(0.1), plane="gspmd",
                         gradient_predivide_factor=2.0)
    assert gp.plane_counters() == {"demote_predivide": 1}
    gp.reset_plane_counters()
    DistributedOptimizer(optax.sgd(0.1), plane="gspmd",
                         shard_optimizer_states=True, axis_name="hvd")
    assert gp.plane_counters() == {"demote_sharded": 1}


def test_invalid_plane_rejected():
    with pytest.raises(ValueError, match="plane"):
        DistributedOptimizer(optax.sgd(0.1), plane="xla")
