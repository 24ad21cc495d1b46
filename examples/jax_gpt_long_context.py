"""GPT causal LM with long-context sequence parallelism (ring attention).

Demonstrates the capability the reference lacks (SURVEY.md §5
"long-context"): sequences sharded over an ``sp`` mesh axis, exact causal
attention via K/V rotation on the ICI ring, gradients averaged over
dp x sp through hvd.DistributedOptimizer.

Run:  python examples/jax_gpt_long_context.py --seq-len 512 --sp 2

Note: the demo's LM loss shifts targets within each sequence shard, so the
one boundary token between adjacent shards is skipped — production input
pipelines pass an explicit [B, S+1] target slice instead.
"""

import argparse
import dataclasses
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
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--sp", type=int, default=2, help="sequence-parallel ways")
    ap.add_argument("--batch-per-dp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each block (HBM for FLOPs)")
    ap.add_argument("--sp-flash", action="store_true",
                    help="Pallas flash kernel per ring-attention hop "
                         "(linear memory in the per-device chunk)")
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()
    devices = jax.devices()
    sp = args.sp if len(devices) % args.sp == 0 else 1
    dp = len(devices) // sp
    mesh = Mesh(np.asarray(devices[:dp * sp]).reshape(dp, sp), ("dp", "sp"))

    cfg = dataclasses.replace(
        models.GPT_TINY, sp_axis_name="sp" if sp > 1 else None,
        sp_use_flash=args.sp_flash,
        max_seq_len=args.seq_len, remat=args.remat)
    model = models.GPT(cfg)
    cfg_init = dataclasses.replace(cfg, sp_axis_name=None)

    batch = args.batch_per_dp * dp
    ids = jax.random.randint(jax.random.PRNGKey(0),
                             (batch, args.seq_len), 0, cfg.vocab_size)
    params = jax.jit(lambda: models.GPT(cfg_init).init(
        jax.random.PRNGKey(1), ids[:1, :32]))()

    tx = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name=("dp", "sp"))
    opt_state = tx.init(params)

    def train_step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: models.lm_loss(model.apply(p, ids), ids))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name=("dp", "sp")))

    spec = P("dp", "sp") if sp > 1 else P("dp")
    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), spec),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))

    params, opt_state, loss = step(params, opt_state, ids)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, ids)
    float(loss)  # host readback bounds the donated-state chain
    dt = time.perf_counter() - t0
    if hvd.rank() == 0:
        tok = batch * args.seq_len * args.steps / dt
        print(f"tokens/sec: {tok:.0f} (mesh {dp}x{sp} dp x sp, "
              f"seq {args.seq_len}), loss={float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
