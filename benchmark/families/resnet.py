"""Family ``resnet``: ``horovod_tpu.models.ResNet*`` trained as the Horovod
paper's benchmark trains it (tf_cnn_benchmarks, synthetic ImageNet shapes).

The step is the user's loop of ``examples/jax_cnn_benchmark.py`` (same
arithmetic as its ``build_train_step`` for a BatchNorm model): a jitted
``shard_map`` over the ``hvd`` axis, sync-BN over that axis, the optimizer
wrapped in ``hvd.DistributedOptimizer``.  It is written out here so that the
weights come from ``--seed`` in one jitted call; the system under test is
everything it calls.
"""

from __future__ import annotations

from benchmark import common, flops

# How a limit is set: the rule at the head of families/bert.py, held on the
# readings in benchmark/testdata/check_readings/resnet.json.  Both are kept
# where PR 23's readings put them.
#
# (a) First loss, system (bf16 activations, sync-BN over the mesh) against
# the float32 whole-batch reference at "highest" matmul precision.  The loss
# is a mean over the batch of a log-softmax taken in float32 on both sides,
# so errors of single activations (2^-9 = 2e-3 a bf16 rounding) average out.
# With the last BN scale of every block zero at initialisation each residual
# branch is the identity, so this loss holds the stem, the shortcuts and the
# head only.  Sound: 2.5e-6 to 2.6e-5 over 15 runs (PR 23).  No fault on
# record.
TOL_FIRST_LOSS = 3e-4
# (a') The same comparison with every zero-initialised BN scale set to one,
# so that every convolution and every BN of every block is in the loss at
# full strength: a forward comparison through the whole model.  The backward
# pass is held only by "losses fall".  Activations are not renormalised
# after each sum here, so bf16's error is larger than in (a).  Sound: 4.3e-4
# to 6.1e-4 in three runs (PR 23), 1.7e-3 at seed 3 (PR 29).  Fault (CPU,
# PR 23): zeroing any one convolution of a tiny ResNet moved such a loss by
# 1e-2 to 5e-2, against 4e-4 after four SGD steps from the zero scales.
TOL_LIVE_LOSS = 6e-3


def _model(cfg: dict, rehearse: bool, **overrides):
    import jax.numpy as jnp

    from horovod_tpu import models

    spec = dict(cfg["rehearse"]["model"] if rehearse else cfg["model"])
    ctor = getattr(models, spec.pop("constructor"))
    spec["dtype"] = jnp.dtype(spec["dtype"])
    spec.update(overrides)
    return ctor(**spec)


def _image_size(cfg: dict, rehearse: bool) -> int:
    return (cfg["rehearse"] if rehearse else cfg)["image_size"]


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and seeded weights (replicated), made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = _model(cfg, rehearse, bn_axis_name="hvd")
    size = _image_size(cfg, rehearse)

    def init(key):
        return model.init(key, jnp.zeros((2, size, size, 3), model.dtype),
                          train=False)

    # The key is an argument, not a constant of the program: one program
    # for every seed, so a new seed finds it in the compile cache.
    key = jax.random.fold_in(jax.random.key(seed), 0)
    variables = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "rehearse": rehearse,
            "params": variables["params"],
            "batch_stats": variables["batch_stats"], "image_size": size}


def inputs(cell: dict, traffic: dict) -> list:
    """Two arguments of the step: images and labels."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    model, size = cell["model"], cell["image_size"]
    return [Input((size, size, 3), model.dtype, "normal"),
            Input((), jnp.int32, "randint", model.num_classes)]


def _reference_loss(cell: dict, params, batch_stats) -> float:
    """The loss of the same flax module in float32 on one device: no
    ``shard_map``, no mesh axis, BN over the whole first batch in one
    forward pass, matmuls at "highest" precision."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    model = _model(cell["cfg"], cell["rehearse"], dtype=jnp.float32,
                   bn_axis_name=None)
    device = cell["mesh"].devices.flat[0]
    variables = common.first_shard({"params": params,
                                    "batch_stats": batch_stats})
    images, labels = jax.device_put(cell["batches"][0], device)

    def loss_fn(variables, images, labels):
        logits, _ = model.apply(variables, images.astype(jnp.float32),
                                train=True, mutable=["batch_stats"])
        return models.xent_loss(logits, labels)

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(loss_fn)(variables, images, labels))


def reference(cell: dict) -> dict:
    """(a) The reference's first loss.  Forward only: a float32 backward at
    256 images does not fit one chip, and on one chip the gradient exchange
    is the identity; the parameter check across chips belongs to the
    ``resnet50-dp4`` cell (PERF.md, Open questions)."""
    return {"loss": _reference_loss(cell, cell["params"],
                                    cell["batch_stats"])}


def probe(cell: dict, step, state) -> list:
    """(a') One more step of the compiled program, before the run's own
    first, on a copy of the state in which the zero-initialised BN scales
    are one, against the reference's loss on the same copy.  The step
    donates the copy; the run's state is not touched."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def live(path, x):
        if jax.tree_util.keystr(path).endswith("['scale']"):
            return jnp.where(jnp.all(x == 0), jnp.ones_like(x), x)
        return jnp.copy(x)

    def copy(state):
        params, batch_stats, opt_state = state
        return (jax.tree_util.tree_map_with_path(live, params),
                *jax.tree_util.tree_map(jnp.copy, (batch_stats, opt_state)))

    params, batch_stats, opt_state = jax.jit(
        copy, out_shardings=NamedSharding(cell["mesh"], P()))(tuple(state))
    want = _reference_loss(cell, params, batch_stats)
    *_, loss = step(params, batch_stats, opt_state, *cell["batches"][0])
    return [common.check("live_branches_loss_vs_reference",
                         common.rel_err(float(loss), want), TOL_LIVE_LOSS)]


def build(cell: dict):
    """``(compiled step, state)``: the step is compiled ahead of time, so
    nothing can compile inside the measured window.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import models

    model, mesh = cell["model"], cell["mesh"]
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, images, train=True,
                mutable=["batch_stats"])
            return models.xent_loss(logits, labels), upd["batch_stats"]

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), stats, opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    state = (cell["params"], cell["batch_stats"], opt_state)
    return step.lower(*state, *cell["batches"][0]).compile(), state


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    import jax
    import jax.numpy as jnp

    model, size = cell["model"], cell["image_size"]
    variables = {"params": cell["params"], "batch_stats": cell["batch_stats"]}
    image = jax.ShapeDtypeStruct((1, size, size, 3), model.dtype)
    macs = flops.forward_macs(
        lambda v, x: model.clone(bn_axis_name=None).apply(v, x, train=False),
        variables, image)
    return flops.train_flops(macs) * cell["batches"][0][0].shape[0]


def units(cell: dict) -> tuple:
    """What one step processes, for the images/s line."""
    return "images", cell["batches"][0][0].shape[0]
