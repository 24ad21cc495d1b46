"""Unified CNN benchmark: the reference's tf_cnn_benchmarks workload.

Horovod's published numbers (BASELINE.md) come from synthetic-data training
of ResNet-50/101, Inception V3, and VGG-16 under DistributedOptimizer —
this is that harness for TPU: pick a model, measure images/sec/chip with
the gradient averaging riding the in-jit ICI plane.

Run:  python examples/jax_cnn_benchmark.py --model resnet50 --steps 20
      python examples/jax_cnn_benchmark.py --model vgg16 --batch-per-chip 32
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.utils.compile_cache import enable_compile_cache

MODELS = {
    "resnet50": (lambda dt: models.ResNet50(dtype=dt, bn_axis_name="hvd"),
                 224),
    "resnet101": (lambda dt: models.ResNet101(dtype=dt, bn_axis_name="hvd"),
                  224),
    "inception3": (lambda dt: models.InceptionV3(dtype=dt,
                                                 bn_axis_name="hvd"), 299),
    "vgg16": (lambda dt: models.VGG16(dtype=dt), 224),
    "resnet_tiny": (lambda dt: models.ResNetTiny(num_classes=100,
                                                 bn_axis_name="hvd"), 32),
}


def build_train_step(model, mesh, images, labels, tx, init_opt_state=True):
    """The DistributedOptimizer train step of ``model`` over ``mesh``'s
    ``hvd`` axis, and the state it starts from.

    Returns ``(step, train_step, (params, batch_stats, opt_state))``:
    ``step(params, batch_stats, opt_state, images, labels)`` is the jitted
    ``shard_map`` of ``train_step`` (donating the three state arguments) and
    returns the new state and the loss averaged over the axis; ``train_step``
    is the per-shard function, for callers that put it in a loop of their
    own.  ``opt_state`` is None when ``init_opt_state`` is false (sharded
    optimizer states are built on the mesh, inside the caller's shard_map).
    """
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, *images.shape[1:]), images.dtype),
        train=False))()
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = bool(batch_stats)
    opt_state = tx.init(params) if init_opt_state else None

    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            vs = {"params": p}
            if has_bn:
                vs["batch_stats"] = batch_stats
                logits, upd = model.apply(vs, images, train=True,
                                          mutable=["batch_stats"])
                return models.xent_loss(logits, labels), upd["batch_stats"]
            # Non-BN models (VGG): still a *training* forward — dropout on,
            # matching the reference's tf_cnn_benchmarks workload.
            logits = model.apply(
                vs, images, train=True,
                rngs={"dropout": jax.random.PRNGKey(0)})
            return models.xent_loss(logits, labels), batch_stats

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), stats, opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))
    return step, train_step, (params, batch_stats, opt_state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), default="resnet50")
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--shard-optimizer", action="store_true",
                    help="ZeRO-1-style optimizer-state sharding over the "
                         "mesh axis (fp32 master weights)")
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()
    n_dev = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), ("hvd",))
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    build, hw = MODELS[args.model]
    model = build(dtype)
    batch = args.batch_per_chip * n_dev

    images = jnp.ones((batch, hw, hw, 3), dtype)
    labels = jnp.zeros((batch,), jnp.int32)

    tx = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), axis_name="hvd",
        shard_optimizer_states=args.shard_optimizer)
    # Sharded optimizer states live on the mesh (per-rank fp32 shards), so
    # the whole measured loop runs inside one shard_map with the state in
    # a fori_loop carry; the replicated path keeps the per-step python
    # loop (same step math either way).
    step, train_step, (params, batch_stats, opt_state) = build_train_step(
        model, mesh, images, labels, tx,
        init_opt_state=not args.shard_optimizer)

    if args.shard_optimizer:
        def run_steps(params, batch_stats, images, labels, n):
            st = tx.init(params)

            def body(i, carry):
                p, bs, st, _ = carry
                p, bs, st, loss = train_step(p, bs, st, images, labels)
                return p, bs, st, loss

            _, _, _, loss = jax.lax.fori_loop(
                0, n, body, (params, batch_stats, st,
                             jnp.zeros((), jnp.float32)))
            return loss

        sharded_run = jax.jit(shard_map(
            lambda p, bs, im, lb: run_steps(p, bs, im, lb, args.steps),
            mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=P()), donate_argnums=(0, 1))
        # Donated args can't be reused: warm up on copies so the timed
        # call measures execution only (one compiled n-step program).
        float(sharded_run(jax.tree_util.tree_map(jnp.copy, params),
                          jax.tree_util.tree_map(jnp.copy, batch_stats),
                          images, labels))
        t0 = time.perf_counter()
        loss = sharded_run(params, batch_stats, images, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    else:
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    if hvd.rank() == 0:
        ips = batch * args.steps / dt
        print(f"{args.model}: {ips:.1f} images/sec "
              f"({ips / n_dev:.1f}/chip), loss={float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
