"""Family ``gpt``: ``horovod_tpu.models.GPT`` (decoder-only, pre-LN, causal),
trained with the flash kernels of ``horovod_tpu/ops/flash_attention.py``.

The step is a data-parallel training step as a user of the library writes
it: a jitted ``shard_map`` over the ``hvd`` axis, ``jax.value_and_grad`` of
``models.lm_loss``, the optimizer wrapped in ``hvd.DistributedOptimizer``,
``optax.apply_updates``, the loss averaged over the axis.
"""

from __future__ import annotations

from benchmark import common, flops

# How a limit is set: the rule at the head of families/bert.py, held on the
# readings in benchmark/testdata/check_readings/gpt.json.  All three are kept
# where PR 23's readings put them.
#
# (a) First loss, system (bf16 activations, flash kernels) against the same
# module in float32 with dense attention at "highest" matmul precision.  At
# initialisation on random targets this loss is ln V + sigma^2/2 whatever the
# blocks below ``ln_f`` compute: it holds the embedding, the head and the
# float32 log-softmax, and no more; the kernels are held by (b).  Sound: 1.0e-6
# to 1.5e-5 over 29 runs of the two cells (PR 23), 1.7e-5 since (PR 29), 2.1e-5
# at seed 2147483801 (PR 33).
# Fault (CPU, review of PR 23): every block's attention output zeroed moves it
# by 5.9e-4 (full bf16 by 6.6e-6: it tells no precision from another).
TOL_FIRST_LOSS = 2e-4
# (b) The first moment after one step is (1 - b1) x the exchanged gradient:
# bf16 backward through all layers and the flash dq/dkv kernels against
# float32 dense attention, as an L2 error over the leaf.  Sound: 4.8e-3 (ln_f
# scale) to 1.5e-2 (first qkv kernel) in both cells alike.  Faults, by the
# measure: a sum over the chips in place of a mean reads n - 1; a leaf of the
# first block's gradient has passed through every layer's dq and dkv, and
# without the causal mask or with a wrong softmax scale it reads near 1.
TOL_FIRST_MOMENT = 5e-2
# (b) The parameters themselves after one AdamW step move by lr x g/(|g|+eps):
# the sign of the gradient, not its size.  Elements whose gradient is small
# against bf16's noise take either sign, each such element contributing 2 lr,
# so whole leaves agree only to some tenths.  Sound: 6e-6 to 0.15.  Fault, by
# the measure: a gradient of one shard alone or of the wrong sign reads near
# 1 or 2.
TOL_PARAM_DELTA = 0.5
# The micro-batch of the reference: what one chip holds in float32 with
# dense attention and one block rematerialized at a time.
REF_MICRO_BATCH = 4


def _gpt_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu import models

    c = {**cfg["assumed"], **cfg, **(cfg["rehearse"] if rehearse else {})}
    return models.GPTConfig(
        vocab_size=c["vocab_size_padded"], hidden_size=c["n_embd"],
        num_layers=c["n_layer"], num_heads=c["n_head"],
        max_seq_len=c["n_positions"], dropout_rate=c["dropout"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"],
        remat=c["remat"])


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and seeded weights (replicated), made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import models

    gcfg = _gpt_config(cfg, rehearse)
    model = models.GPT(gcfg)

    def init(key):
        return model.init(key, jnp.zeros((1, 32), jnp.int32))

    # The key is an argument, not a constant of the program: one program
    # for every seed, so a new seed finds it in the compile cache.
    key = jax.random.fold_in(jax.random.key(seed), 0)
    params = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "gcfg": gcfg,
            "rehearse": rehearse, "params": params}


def inputs(cell: dict, traffic: dict) -> list:
    """One argument of the step: token ids (the targets are the same ids,
    shifted, in ``models.lm_loss``)."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    gcfg = cell["gcfg"]
    seq = traffic.get("seq_len", gcfg.max_seq_len)
    if seq > gcfg.max_seq_len:
        raise ValueError(f"traffic asks for {seq} tokens a sequence, the "
                         f"configuration has {gcfg.max_seq_len} positions")
    return [Input((seq,), jnp.int32, "randint", gcfg.vocab_size)]


KERNEL_ROWS = 8  # of the first kernel, the rows that are compared


def _cut(path: str, leaf):
    return leaf[:KERNEL_ROWS] if path.endswith("['kernel']") else leaf


def _checked_tree(tree) -> dict:
    """The leaves check (b) compares, as a sub-tree with the whole tree's
    paths: a bias, a norm scale and a slice of the first kernel; small, and
    at both ends of the backward pass."""
    p = tree["params"]
    kernel = p["h_0"]["attn"]["qkv"]["kernel"]
    return {"params": {
        "h_0": {"mlp_in": {"bias": p["h_0"]["mlp_in"]["bias"]},
                "attn": {"qkv": {"kernel": kernel[:KERNEL_ROWS]}}},
        "ln_f": {"scale": p["ln_f"]["scale"]}}}


def _checked(tree) -> dict:
    """``{path: leaf}`` of ``_checked_tree``."""
    return common.leaf_paths(_checked_tree(tree))


def reference(cell: dict) -> dict:
    """The same flax module in float32, dense attention, one device, no
    ``shard_map``, no ``DistributedOptimizer``, "highest" matmul precision,
    the first global batch in micro-batches.

    (a) its loss on the seeded batch.  (b) its gradient of three small
    leaves accumulated over the micro-batches, one plain optax update of
    those leaves, and the first moment that update leaves behind.  Exact for
    GPT, which couples no examples.  XLA drops the weight gradients nobody
    reads, so this costs one backward chain, with each block rematerialized
    to fit.  On one chip too: there the exchange is the identity, and the
    check holds the flash dq/dkv kernels and the precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import models

    ref_cfg = dataclasses.replace(cell["gcfg"], dtype=jnp.float32,
                                  use_flash=False, remat=True)
    model = models.GPT(ref_cfg)
    device = cell["mesh"].devices.flat[0]
    params = common.first_shard(cell["params"])
    ids = jax.device_put(cell["batches"][0][0], device)
    micro = min(REF_MICRO_BATCH, ids.shape[0])
    assert ids.shape[0] % micro == 0, (ids.shape, micro)
    n_micro = ids.shape[0] // micro

    def loss_and_leaf_grads(p, x):
        loss, grads = jax.value_and_grad(
            lambda p: models.lm_loss(model.apply(p, x), x))(p)
        return loss, _checked_tree(grads)

    fn = jax.jit(loss_and_leaf_grads)
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(n_micro):
            part, part_grads = fn(params, ids[i * micro:(i + 1) * micro])
            loss += float(part) / n_micro
            scaled = jax.tree_util.tree_map(lambda g: g / n_micro, part_grads)
            grads = scaled if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, scaled)
    leaves = _checked_tree(params)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    updates, opt_state = tx.update(grads, tx.init(leaves), leaves)
    new = common.leaf_paths(optax.apply_updates(leaves, updates))
    return {"loss": loss, "leaves": {
        k: {"delta": np.asarray(new[k] - old),
            "first_moment": np.asarray(common.first_moments(opt_state, k)[0])}
        for k, old in common.leaf_paths(leaves).items()}}


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
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

    def train_step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: models.lm_loss(model.apply(p, ids), ids))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    # The step donates the parameters; keep what check (b) subtracts.
    cell["old_leaves"] = jax.device_get(_checked(cell["params"]))
    state = (cell["params"], opt_state)
    return step.lower(*state, *cell["batches"][0]).compile(), state


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    import jax

    gcfg = cell["gcfg"]
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if gcfg.use_flash:
        # forward, dq and dkv per layer: the Pallas kernels, not the dense
        # fallback, are in the compiled step.
        out.append(common.at_least("tpu_custom_calls",
                                   hlo["tpu_custom_call"],
                                   3 * gcfg.num_layers))
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    params, opt_state = state
    for k, new in _checked(params).items():
        want = ref["leaves"][k]
        delta = jax.device_get(new) - cell["old_leaves"][k]
        out.append(common.check(f"param_delta{k}", common.l2_rel_err(
            delta, want["delta"]), TOL_PARAM_DELTA))
        moments = common.first_moments(opt_state, k)
        assert len(moments) == 1, (k, len(moments))
        out.append(common.check(f"first_moment{k}", common.l2_rel_err(
            jax.device_get(_cut(k, moments[0])), want["first_moment"]),
            TOL_FIRST_MOMENT))
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch.
    Traced with dense attention (a Pallas call shows no dot_general); of
    attention's two S x S products per layer, the dot_generals with batch
    dimensions, half is counted: under the causal mask only the lower
    triangle is needed."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    gcfg = cell["gcfg"]
    model = models.GPT(dataclasses.replace(gcfg, use_flash=False))
    batch, seq = cell["batches"][0][0].shape
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    macs = flops.forward_macs(model.apply, cell["params"], ids,
                              batched_scale=0.5)
    return flops.train_flops(macs) * batch


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
