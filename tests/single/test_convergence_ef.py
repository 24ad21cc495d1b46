"""Convergence parity for the device-plane int8 codec + error feedback.

The int8 block codec rounds every gradient entry to the nearest multiple of
``scale = max|block|/127``.  A coordinate whose gradient stays below
``scale/2`` therefore quantizes to zero on *every* step and never trains —
unless error feedback carries the rounding error forward until it crosses
the threshold.  These tests pin both halves of that story:

- ``DistributedOptimizer(device_compression="int8")`` (EF on) reaches the
  same solution as uncompressed fp32, on a quadratic built to trigger the
  failure mode and on a real MLP classifier;
- the same int8 ring *without* error feedback measurably stalls on the
  quadratic (an order of magnitude worse than fp32), which is exactly why
  the optimizer refuses to expose a no-EF device codec.

The quadratic pins the block scale with one "leader" coordinate per
256-element block whose gradient is a constant 1.0 (a linear loss term), so
the quantization step stays at ``1/127`` forever while the other
coordinates' gradients shrink below it.  All losses consume the sharded
operand — XLA's CPU collectives rendezvous can stall if a shard_map output
does not depend on the sharded input.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

optax = pytest.importorskip("optax")

import horovod_tpu.ops.collectives as cl
import horovod_tpu.ops.quantize as qz
from horovod_tpu.optimizer import DistributedOptimizer
from horovod_tpu.wire import ReduceOp
from _jit_helpers import N_DEV, _smap

MIN_BYTES = 4096


@pytest.fixture
def small_min_bytes(monkeypatch):
    """Drop the demotion floor to 4 KiB so test-sized leaves quantize.

    ``_device_codec_defaults`` prefers the live context config over the
    environment once ``hvd.init()`` has run (earlier tests in the session
    may have initialized the singleton), so patch both.
    """
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", str(MIN_BYTES))
    from horovod_tpu.context import HorovodContext
    if HorovodContext.initialized():
        cfg = HorovodContext.instance().cfg
        monkeypatch.setattr(cfg, "wire_compression_min_bytes", MIN_BYTES,
                            raising=False)
    yield


def _train(loss_fn, params, tx, data, steps, reduce_mode="opt",
           noef_codec="int8"):
    """SGD loop under jit+shard_map; data is sharded rank-major on dim 0.

    ``reduce_mode="opt"`` lets the (Distributed)optimizer handle the
    reduction; ``"manual_noef"`` averages gradients through the raw
    block-scaled ring (``noef_codec``) with no error feedback — the path
    the optimizer deliberately does not offer, reconstructed here to
    measure why.
    """
    def step(p, s, x):
        g = jax.grad(loss_fn)(p, x)
        if reduce_mode == "manual_noef":
            def red(leaf):
                if cl.quantized_allreduce_eligible(leaf, N_DEV, MIN_BYTES):
                    return cl.quantized_allreduce(
                        leaf, "hvd", op=ReduceOp.AVERAGE,
                        min_bytes=MIN_BYTES, codec=noef_codec)
                return jax.lax.pmean(leaf, "hvd")
            g = jax.tree_util.tree_map(red, g)
        upd, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, upd), s2

    jitted = jax.jit(_smap(step, in_specs=(P(), P(), P("hvd")),
                           out_specs=(P(), P())))
    state = tx.init(params)
    for _ in range(steps):
        params, state = jitted(params, state, data)
    return params, state


# ---------------------------------------------------------------------------
# Quadratic with pinned block scale: EF converges, no-EF stalls.
# ---------------------------------------------------------------------------

def test_quadratic_int8_ef_matches_fp32_and_noef_stalls(small_min_bytes):
    n = 2048
    h_np = np.tile(np.logspace(-2, 0, qz.WIRE_BLOCK), n // qz.WIRE_BLOCK)
    leader = np.zeros(n, bool)
    leader[::qz.WIRE_BLOCK] = True
    h_np[leader] = 0.0
    hs = jnp.asarray(h_np, jnp.float32)
    lead = jnp.asarray(leader, jnp.float32)
    target = jnp.ones(n, jnp.float32)
    data = jnp.ones((N_DEV, n), jnp.float32)

    def loss_fn(p, x):
        # x is all-ones: mean(x[0]) == 1.0 keeps the loss data-dependent
        # without changing the curvature.
        quad = jnp.sum(hs * (p["w"] - target) ** 2 * jnp.mean(x[0]))
        return quad + jnp.sum(lead * p["w"])

    def quad_err(p):
        w = np.asarray(p["w"])
        return float(np.sum(h_np * (w - 1.0) ** 2))

    lr, steps = 0.45, 300
    p0 = {"w": jnp.zeros(n, jnp.float32)}

    p_fp32, _ = _train(loss_fn, p0,
                       DistributedOptimizer(optax.sgd(lr),
                                            device_compression="none"),
                       data, steps)
    p_ef, s_ef = _train(loss_fn, p0,
                        DistributedOptimizer(optax.sgd(lr),
                                             device_compression="int8"),
                        data, steps)
    p_noef, _ = _train(loss_fn, p0, optax.sgd(lr), data, steps,
                       reduce_mode="manual_noef")

    e_fp32, e_ef, e_noef = quad_err(p_fp32), quad_err(p_ef), quad_err(p_noef)

    # Error feedback keeps the quantized run within a small factor of fp32
    # (measured ~1.3x on this construction) ...
    assert e_ef <= 2.0 * e_fp32, (e_ef, e_fp32)
    # ... while the no-EF ring stalls the sub-threshold coordinates at their
    # starting error (measured ~45x fp32; 10x/5x leave calibration margin).
    assert e_noef >= 10.0 * e_fp32, (e_noef, e_fp32)
    assert e_noef >= 5.0 * e_ef, (e_noef, e_ef)

    # The EF state carried a residual tree and it is doing real work: the
    # sub-threshold coordinates' rounding error lives there between steps.
    assert s_ef.residual is not None
    res = np.asarray(s_ef.residual["w"])
    assert res.shape == (n,)
    assert np.any(res != 0.0)


# ---------------------------------------------------------------------------
# MLP classifier: int8 + EF tracks fp32 end-to-end through a real model.
# ---------------------------------------------------------------------------

def test_mlp_int8_ef_tracks_fp32(small_min_bytes):
    from horovod_tpu.models.mlp import MLP, xent_loss

    rng = np.random.RandomState(0)
    batch, dim, classes = 16, 64, 10
    x_np = rng.randn(N_DEV, batch, dim).astype(np.float32)
    y_np = rng.randint(0, classes, size=(N_DEV, batch))
    data = (jnp.asarray(x_np), jnp.asarray(y_np, jnp.int32))

    model = MLP(features=(128, 64, classes))
    params = model.init(jax.random.PRNGKey(1), x_np[0])

    def loss_fn(p, xy):
        x, y = xy
        return xent_loss(model.apply(p, x[0]), y[0])

    def run(tx):
        def step(p, s, x, y):
            g = jax.grad(loss_fn)(p, (x, y))
            upd, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, upd), s2
        jitted = jax.jit(_smap(step,
                               in_specs=(P(), P(), P("hvd"), P("hvd")),
                               out_specs=(P(), P())))
        p, s = params, tx.init(params)
        for _ in range(40):
            p, s = jitted(p, s, *data)
        full_x = jnp.asarray(x_np.reshape(-1, dim))
        full_y = jnp.asarray(y_np.reshape(-1), jnp.int32)
        return float(xent_loss(model.apply(p, full_x), full_y))

    qz.reset_device_byte_counters()
    loss_fp32 = run(DistributedOptimizer(optax.sgd(0.3),
                                         device_compression="none"))
    assert qz.device_byte_counters() == (0, 0)  # fp32 arm never quantizes

    loss_ef = run(DistributedOptimizer(optax.sgd(0.3),
                                       device_compression="int8"))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw  # the int8 arm really went through the ring

    # Both runs must actually have learned something ...
    loss_init = float(xent_loss(
        model.apply(params, jnp.asarray(x_np.reshape(-1, dim))),
        jnp.asarray(y_np.reshape(-1), jnp.int32)))
    assert loss_fp32 < 0.5 * loss_init
    # ... and the quantized run lands on the fp32 curve.
    assert abs(loss_ef - loss_fp32) <= 0.05 * loss_fp32, (loss_ef, loss_fp32)


# ---------------------------------------------------------------------------
# ResNetTiny: same parity through conv + batchnorm parameter structure.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_resnet_tiny_int8_ef_tracks_fp32(small_min_bytes):
    from horovod_tpu import models

    rng = np.random.RandomState(2)
    batch, side, classes = 4, 16, 10
    x_np = rng.randn(N_DEV, batch, side, side, 3).astype(np.float32)
    y_np = rng.randint(0, classes, size=(N_DEV, batch))
    data = (jnp.asarray(x_np), jnp.asarray(y_np, jnp.int32))

    model = models.ResNetTiny(num_classes=classes)
    variables = model.init(jax.random.PRNGKey(3), x_np[0], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # train=False: frozen (init) batch statistics keep the objective
    # deterministic and the optimizer state a pure params pytree, which is
    # what this test is about — EF parity, not BN schedules.
    def loss_fn(p, xy):
        x, y = xy
        logits = model.apply({"params": p, "batch_stats": batch_stats},
                             x[0], train=False)
        return models.xent_loss(logits, y[0])

    def run(tx):
        def step(p, s, x, y):
            g = jax.grad(loss_fn)(p, (x, y))
            upd, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, upd), s2
        jitted = jax.jit(_smap(step,
                               in_specs=(P(), P(), P("hvd"), P("hvd")),
                               out_specs=(P(), P())))
        p, s = params, tx.init(params)
        for _ in range(12):
            p, s = jitted(p, s, *data)
        losses = [
            float(loss_fn(p, (data[0][r:r + 1], data[1][r:r + 1])))
            for r in range(N_DEV)]
        return float(np.mean(losses))

    loss_fp32 = run(DistributedOptimizer(optax.sgd(0.05),
                                         device_compression="none"))
    loss_ef = run(DistributedOptimizer(optax.sgd(0.05),
                                       device_compression="int8"))
    assert abs(loss_ef - loss_fp32) <= 0.10 * max(loss_fp32, 1e-3), (
        loss_ef, loss_fp32)


# ---------------------------------------------------------------------------
# int4: the same pinned-scale story at a 1/7 quantization step.  EF must
# still converge (the residual just takes more steps to cross the coarser
# threshold) while the no-EF int4 ring stalls even harder than int8.
# ---------------------------------------------------------------------------

def test_quadratic_int4_ef_matches_fp32_and_noef_stalls(small_min_bytes):
    n = 2048
    h_np = np.tile(np.logspace(-2, 0, qz.WIRE_BLOCK), n // qz.WIRE_BLOCK)
    leader = np.zeros(n, bool)
    leader[::qz.WIRE_BLOCK] = True
    h_np[leader] = 0.0
    hs = jnp.asarray(h_np, jnp.float32)
    lead = jnp.asarray(leader, jnp.float32)
    target = jnp.ones(n, jnp.float32)
    data = jnp.ones((N_DEV, n), jnp.float32)

    def loss_fn(p, x):
        quad = jnp.sum(hs * (p["w"] - target) ** 2 * jnp.mean(x[0]))
        return quad + jnp.sum(lead * p["w"])

    def quad_err(p):
        w = np.asarray(p["w"])
        return float(np.sum(h_np * (w - 1.0) ** 2))

    # lr 0.2 (vs int8's 0.45): int4's EF noise floor scales with
    # lr * scale/2 at a 14x coarser scale — the smaller step keeps the
    # floor below fp32's 300-step error (measured ef/fp32 ~2.2x here,
    # vs ~108x at lr 0.45 where fp32 has left the floor far behind).
    lr, steps = 0.2, 300
    p0 = {"w": jnp.zeros(n, jnp.float32)}

    p_fp32, _ = _train(loss_fn, p0,
                       DistributedOptimizer(optax.sgd(lr),
                                            device_compression="none"),
                       data, steps)
    p_ef, s_ef = _train(loss_fn, p0,
                        DistributedOptimizer(optax.sgd(lr),
                                             device_compression="int4"),
                        data, steps)
    p_noef, _ = _train(loss_fn, p0, optax.sgd(lr), data, steps,
                       reduce_mode="manual_noef", noef_codec="int4")

    e_fp32, e_ef, e_noef = quad_err(p_fp32), quad_err(p_ef), quad_err(p_noef)

    # ISSUE acceptance: int4 + EF within 4x of the fp32 final error on the
    # scale-pinned quadratic ...
    assert e_ef <= 4.0 * e_fp32, (e_ef, e_fp32)
    # ... while the no-EF int4 ring stalls (the 1/14 threshold freezes the
    # small-curvature coordinates near their starting error).
    assert e_noef >= 10.0 * e_fp32, (e_noef, e_fp32)
    assert e_noef >= 5.0 * e_ef, (e_noef, e_ef)
    assert s_ef.residual is not None
    assert np.any(np.asarray(s_ef.residual["w"]) != 0.0)


# ---------------------------------------------------------------------------
# BERT family (BASELINE.json config 3): int4 + EF tracks fp32 through a
# transformer's parameter structure — embeddings, fused QKV projections,
# layernorms, and an MLM head sharing the encoder width.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bert_tiny_int4_ef_tracks_fp32(small_min_bytes):
    from horovod_tpu import models

    cfg = models.BERT_TINY
    rng = np.random.RandomState(5)
    batch, seq = 2, 32
    ids_np = rng.randint(0, cfg.vocab_size, size=(N_DEV, batch, seq))
    labels_np = rng.randint(0, cfg.vocab_size, size=(N_DEV, batch, seq))
    w_np = (rng.rand(N_DEV, batch, seq) < 0.15).astype(np.float32)
    w_np[:, :, 0] = 1.0                       # never an all-zero mask
    data = (jnp.asarray(ids_np, jnp.int32),
            jnp.asarray(labels_np, jnp.int32),
            jnp.asarray(w_np, jnp.float32))

    model = models.BertForPreTraining(cfg)
    params = model.init(jax.random.PRNGKey(7), ids_np[0])

    def loss_fn(p, xyw):
        ids, labels, w = xyw
        logits = model.apply(p, ids[0])
        return models.mlm_loss(logits, labels[0], w[0])

    def run(tx):
        def step(p, s, ids, labels, w):
            g = jax.grad(loss_fn)(p, (ids, labels, w))
            upd, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, upd), s2
        jitted = jax.jit(_smap(
            step, in_specs=(P(), P(), P("hvd"), P("hvd"), P("hvd")),
            out_specs=(P(), P())))
        p, s = params, tx.init(params)
        for _ in range(15):
            p, s = jitted(p, s, *data)
        full = (jnp.asarray(ids_np.reshape(-1, seq), jnp.int32),
                jnp.asarray(labels_np.reshape(-1, seq), jnp.int32),
                jnp.asarray(w_np.reshape(-1, seq), jnp.float32))
        logits = model.apply(p, full[0])
        return float(models.mlm_loss(logits, full[1], full[2]))

    loss_init = float(models.mlm_loss(
        model.apply(params, jnp.asarray(ids_np.reshape(-1, seq), jnp.int32)),
        jnp.asarray(labels_np.reshape(-1, seq), jnp.int32),
        jnp.asarray(w_np.reshape(-1, seq), jnp.float32)))

    qz.reset_device_byte_counters()
    loss_fp32 = run(DistributedOptimizer(optax.sgd(0.1),
                                         device_compression="none"))
    assert qz.device_byte_counters() == (0, 0)

    loss_ef = run(DistributedOptimizer(optax.sgd(0.1),
                                       device_compression="int4"))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc / raw <= 0.20  # int4 wire ratio on real leaves

    # Training moved (random-label MLM overfits toward memorization) and
    # the int4 run stays on the fp32 curve.
    assert loss_fp32 < loss_init
    assert abs(loss_ef - loss_fp32) <= 0.15 * loss_fp32, (
        loss_ef, loss_fp32)
