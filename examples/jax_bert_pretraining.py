"""BERT pretraining with fp16-compressed fused allreduce (config 3).

Reference analog: Horovod's BERT examples with
``compression=hvd.Compression.fp16`` and gradient tensor fusion.

The in-jit path compresses each gradient leaf to bfloat16 before the psum
and decompresses after — halving ICI bytes the way the reference's fp16
compression halves NCCL bytes.  Optionally shards long sequences over an
``sp`` axis with ring attention (--seq-parallel).

The objective is the published one: the masked positions (15 % of a
sequence) are gathered before the head, the decoder is tied to the word
embeddings, and the next-sentence loss is added; every tenth sequence is
padded to half its length and the padding is masked through the flash
kernels' per-sequence key length.  With --seq-parallel each shard holds a
slice of every sequence, so a gather of positions is not local: that path
keeps the every-position masked-LM call and no padding.

Run:  python examples/jax_bert_pretraining.py [--large] [--seq-parallel]
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
from horovod_tpu.utils.compile_cache import enable_compile_cache
from horovod_tpu import models


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--large", action="store_true", help="BERT-Large")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="shard the sequence over an sp axis (ring attention)")
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--sp-flash", action="store_true",
                    help="Pallas flash kernel per ring-attention hop")
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()
    devices = jax.devices()
    n_dev = len(devices)

    if args.seq_parallel and n_dev >= 2:
        sp = 2
        dp = n_dev // sp
        mesh = Mesh(np.asarray(devices[:dp * sp]).reshape(dp, sp),
                    ("hvd", "sp"))
        axes = ("hvd", "sp")
        sp_axis = "sp"
        data_spec = P("hvd", "sp")
    else:
        mesh = Mesh(np.asarray(devices), ("hvd",))
        axes = "hvd"
        sp_axis = None
        data_spec = P("hvd")

    base = models.BERT_LARGE if args.large else models.BERT_TINY
    import dataclasses

    cfg = dataclasses.replace(base, sp_axis_name=sp_axis,
                              sp_use_flash=args.sp_flash,
                              max_position_embeddings=max(
                                  args.seq_len, base.max_position_embeddings))
    model = models.BertForPreTraining(cfg)

    batch = args.batch_per_chip * mesh.shape["hvd"]
    S = args.seq_len
    ids = jnp.ones((batch, S), jnp.int32)
    labels = jnp.zeros((batch, S), jnp.int32)
    weights = jnp.ones((batch, S), jnp.float32)
    # The published data shape: 15 % of a sequence masked, a padding mask.
    n_masked = max(1, round(0.15 * S))
    lengths = jnp.where(jnp.arange(batch) % 10 == 9, S // 2, S).astype(
        jnp.int32)
    positions = jnp.arange(n_masked, dtype=jnp.int32)[None, :] % lengths[:, None]
    mlm_labels = jnp.zeros((batch, n_masked), jnp.int32)
    mlm_weights = jnp.ones((batch, n_masked), jnp.float32)
    nsp_labels = jnp.zeros((batch,), jnp.int32)

    cfg_dense = dataclasses.replace(cfg, sp_axis_name=None)
    params = jax.jit(lambda: models.BertForPreTraining(cfg_dense).init(
        jax.random.PRNGKey(0), ids[:1, :16], ids[:1, :16])["params"])()

    tx = hvd.DistributedOptimizer(
        optax.adamw(1e-4), compression=hvd.Compression.fp16, axis_name=axes)
    opt_state = tx.init(params)

    def train_step(params, opt_state, *batch):
        def loss_fn(p):
            if sp_axis is not None:
                ids, labels, weights = batch
                logits = model.apply({"params": p}, ids, jnp.zeros_like(ids))
                return models.mlm_loss(logits, labels, weights)
            ids, lengths, positions, mlm_labels, mlm_weights, nsp_labels = \
                batch
            mlm, nsp = model.apply({"params": p}, ids, jnp.zeros_like(ids),
                                   lengths=lengths,
                                   masked_positions=positions)
            return models.pretraining_loss(mlm, nsp, mlm_labels, mlm_weights,
                                           nsp_labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name=axes))

    data = ((ids, labels, weights) if sp_axis is not None else
            (ids, lengths, positions, mlm_labels, mlm_weights, nsp_labels))
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), *(data_spec for _ in data)),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))

    params, opt_state, loss = step(params, opt_state, *data)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, *data)
    float(loss)  # host readback bounds the donated-state chain
    dt = time.perf_counter() - t0
    if hvd.rank() == 0:
        print(f"sequences/sec: {batch * args.steps / dt:.1f}, "
              f"loss={float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
