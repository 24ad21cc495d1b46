"""Benchmark: ResNet-50 training throughput through hvd.DistributedOptimizer.

The reference's headline benchmark is ResNet-50 images/sec/GPU under
``hvd.DistributedOptimizer`` (BASELINE.md: ~235 img/s on a P100 in the
Horovod paper's setup, arXiv:1802.05799).  This measures the same workload
on the attached TPU: full fwd+bwd+optimizer train step, bfloat16
activations, synthetic ImageNet-shaped data (the reference benchmarks use
synthetic data too), with the gradient allreduce riding the framework's XLA
data plane over a mesh axis — the code path multi-chip runs use — followed
by the flash-attention, BERT, device-codec and compiled-collective
appendices.

One process.  It prints ONE JSON line, which names the device it ran on
(``platform``, ``device_kind``, ``n_devices``), and exits non-zero when any
phase fails or when a full-size run finds no TPU: a number from another
backend is not a measurement of this system.  ``_HVD_TPU_BENCH_TINY=1``
runs every phase at toy sizes on whatever backend is there (the CPU smoke
in tests/single/test_bench.py); its numbers mean nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

REFERENCE_IMG_PER_SEC_PER_DEVICE = 235.0  # Horovod paper, ResNet-50 on P100

# Published per-chip peak bf16 matmul throughput, by device_kind prefix.
_PEAK_BF16_FLOPS = (
    ("TPU v6", 918e12),
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v5", 459e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 45e12),
)


def _chip_peak_flops(device_kind: str) -> float:
    for prefix, peak in _PEAK_BF16_FLOPS:
        if device_kind.startswith(prefix):
            return peak
    raise ValueError(f"no published bf16 peak on record for device kind "
                     f"{device_kind!r}: add it to _PEAK_BF16_FLOPS with its "
                     "source before reporting a utilization")


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _tiny() -> bool:
    return os.environ.get("_HVD_TPU_BENCH_TINY") == "1"


def _flash_attention_entry() -> dict:
    """Single-chip flash-vs-dense attention timing + correctness, the
    custom-VJP backward included."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import dense_attention, flash_attention

    if _tiny():
        b, s, h, d = 1, 128, 2, 32
        iters = 2
    else:
        b, s, h, d = 4, 2048, 8, 128
        iters = 20
    rng = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)

    # CPU smoke path forces the kernel through the Pallas interpreter;
    # None keeps flash_attention's own backend dispatch (Pallas on TPU,
    # dense fallback elsewhere).
    interpret = True if _tiny() else None

    flash = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=interpret))
    dense = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))

    out_f = jax.block_until_ready(flash(q, k, v))
    out_d = jax.block_until_ready(dense(q, k, v))
    err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32)
                                - out_d.astype(jnp.float32))))

    def timeit(fn, iters=iters):
        # block_until_ready waits for the device (chip_smoke.py's sync
        # phase: 990.15 ms against 990.39 ms for a scalar readback over the
        # same ten ResNet steps), so it ends the timed window.
        jax.block_until_ready(fn(q, k, v))  # warmup
        t0 = time.perf_counter()
        out = q
        for _ in range(iters):
            out = fn(out, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    flash_ms = timeit(flash)
    dense_ms = timeit(dense)

    # Gradient path: jax.grad recomputes the forward inside each call, so
    # these time forward+backward together — keys say "fwdbwd" accordingly.
    # (The flash backward is the custom-VJP Pallas kernel pair.)
    def fgrad_loss(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    flash_g = fgrad_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    dense_g = fgrad_loss(lambda q, k, v: dense_attention(q, k, v, causal=True))

    def timeit_grad(fn, iters=max(2, iters // 2)):
        jax.block_until_ready(fn(q, k, v))  # warmup
        t0 = time.perf_counter()
        qq = q
        for _ in range(iters):
            qq = fn(qq, k, v)[0].astype(jnp.bfloat16)
        jax.block_until_ready(qq)
        return (time.perf_counter() - t0) / iters * 1e3

    flash_fwdbwd_ms = timeit_grad(flash_g)
    dense_fwdbwd_ms = timeit_grad(dense_g)
    return {
        "flash_attn_ms": round(flash_ms, 3),
        "dense_attn_ms": round(dense_ms, 3),
        "flash_attn_speedup_vs_dense": round(dense_ms / flash_ms, 3),
        "flash_attn_max_abs_err": round(err, 4),
        "flash_attn_fwdbwd_ms": round(flash_fwdbwd_ms, 3),
        "dense_attn_fwdbwd_ms": round(dense_fwdbwd_ms, 3),
        "flash_attn_fwdbwd_speedup_vs_dense": round(
            dense_fwdbwd_ms / flash_fwdbwd_ms, 3),
    }


def _bert_entry(mesh) -> dict:
    """Secondary headline: BERT pretraining step throughput (BASELINE.md
    config 3 is BERT-Large fp16 allreduce scaling; this records the
    single-chip tokens/sec for a BERT-Base-shaped model in bf16 through
    the same DistributedOptimizer data plane)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    import horovod_tpu as hvd
    from horovod_tpu import models

    n_dev = mesh.devices.size
    if _tiny():  # CPU smoke in tests
        batch, seq = 4 * n_dev, 32
        cfg = models.BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                                num_heads=2, intermediate_size=128,
                                max_position_embeddings=64,
                                dtype=jnp.float32)
        n_steps = 2
    else:
        batch, seq = 32 * n_dev, 128
        cfg = models.BertConfig(vocab_size=30522, hidden_size=768,
                                num_layers=12, num_heads=12,
                                intermediate_size=3072,
                                max_position_embeddings=512,
                                dtype=jnp.bfloat16)
        n_steps = 10
    model = models.BertForPreTraining(cfg)
    ids = jnp.ones((batch, seq), jnp.int32)
    labels = jnp.zeros((batch, seq), jnp.int32)
    weights = jnp.ones((batch, seq), jnp.float32)
    params = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), ids[:2]))()["params"]
    tx = hvd.DistributedOptimizer(optax.adamw(1e-4), axis_name="hvd")
    opt_state = tx.init(params)

    def train_step(params, opt_state, ids, labels, weights):
        def loss_fn(p):
            logits = model.apply({"params": p}, ids)
            return models.mlm_loss(logits, labels, weights)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    step = jax.jit(shard_map(train_step, mesh=mesh,
                             in_specs=(P(), P(), P("hvd"), P("hvd"),
                                       P("hvd")),
                             out_specs=(P(), P(), P())),
                   donate_argnums=(0, 1))
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, ids, labels,
                                       weights)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, ids, labels,
                                       weights)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return {
        "bert_base_tokens_per_sec_per_chip": round(
            batch * seq * n_steps / dt / n_dev, 1),
        "bert_base_step_ms": round(dt / n_steps * 1e3, 2),
    }


def _device_codec_entry(mesh) -> dict:
    """Device-plane int8 ring appendix: the quantized in-jit allreduce
    (docs/compression.md) vs the plain psum on the same fp32 payload —
    step time for both, plus the encoded/raw wire ratio straight from the
    device-plane byte counters (which tick at trace time)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    import horovod_tpu.ops.collectives as cl
    import horovod_tpu.ops.quantize as qz
    from horovod_tpu.wire import ReduceOp

    n_dev = len(np.asarray(mesh.devices).reshape(-1))
    if n_dev < 2:
        return {"device_codec_skipped": "single device: no ring"}
    per_dev = (1 << 16) if _tiny() else (1 << 22)  # fp32 elems per device
    n_steps = 3 if _tiny() else 10

    rng = np.random.RandomState(23)
    x = jnp.asarray(rng.randn(n_dev, per_dev).astype(np.float32))

    def q_fn(shard):
        return cl.quantized_allreduce(shard, "hvd", op=ReduceOp.SUM,
                                      min_bytes=4096)

    def p_fn(shard):
        return jax.lax.psum(shard, "hvd")

    def timeit(fn):
        # the ppermute ring has no replication rule: turn checks off
        sm = shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                       check_vma=False)
        jitted = jax.jit(sm)
        out = jitted(x)
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = jitted(x)
        out.block_until_ready()
        return out, (time.perf_counter() - t0) / n_steps

    qz.reset_device_byte_counters()
    q_out, q_dt = timeit(q_fn)
    raw, enc = qz.device_byte_counters()
    p_out, p_dt = timeit(p_fn)
    max_err = float(jnp.max(jnp.abs(q_out - p_out)))
    return {
        "device_codec": "int8",
        "device_codec_wire_ratio": round(enc / max(raw, 1), 3),
        "device_codec_step_ms": round(q_dt * 1e3, 2),
        "device_codec_fp32_step_ms": round(p_dt * 1e3, 2),
        "device_codec_max_abs_err": max_err,
    }


def _hlo_inventory_entry() -> dict:
    """Compiled-collective provenance appendix: run one tiny gspmd-plane
    SGD step through ops/hlo_inspect.instrument and stamp the
    compiler-inserted collective inventory — kinds plus analytic
    ring-model bytes — so the benchmark line records what XLA actually
    scheduled on this backend, not just what the plane requested."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import gspmd_plane as gp
    from horovod_tpu.ops import hlo_inspect as hi

    if len(jax.devices()) < 2:
        return {"hlo_skipped": "single device: gspmd demotes to eager"}
    if not hi.enabled():
        return {"hlo_skipped": "HOROVOD_HLO_INSPECT=0"}

    mesh = gp.build_gspmd_mesh()
    n = mesh.shape[gp.BATCH_AXIS] * 8  # divisible batch -> sharded inputs
    rs = np.random.RandomState(7)
    x = jax.device_put(jnp.asarray(rs.randn(n, 4), jnp.float32),
                       NamedSharding(mesh, P(gp.BATCH_AXIS)))
    y = jax.device_put(jnp.asarray(rs.randn(n), jnp.float32),
                       NamedSharding(mesh, P(gp.BATCH_AXIS)))
    params = {"w": jnp.zeros((4,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), plane="gspmd")
    state = tx.init(params)

    def step(p, s, xs, ys):
        def loss(p):
            return jnp.mean((xs @ p["w"] + p["b"] - ys) ** 2)
        g = jax.grad(loss)(p)
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    wrapped = hi.instrument(jax.jit(step), label="bench_hlo")
    params, state = wrapped(params, state, x, y)
    jax.block_until_ready(params)
    invs = [i for i in hi.inventories() if i.label == "bench_hlo"]
    if not invs:
        return {"hlo_skipped": "no inventory (trace did not resolve gspmd)"}
    inv = invs[-1]
    return {
        "hlo_collectives": inv.collectives,
        "hlo_kinds": inv.kind_counts(),
        "hlo_raw_bytes": inv.raw_bytes,
        "hlo_wire_bytes": inv.wire_bytes,
    }


def main() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from examples.jax_cnn_benchmark import build_train_step
    from horovod_tpu import models
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform
    _log(f"platform={platform} devices={n_dev} "
         f"kind={devices[0].device_kind}")
    if platform != "tpu" and not _tiny():
        print(f"bench.py: a full-size run needs a TPU, JAX found "
              f"{platform!r}", file=sys.stderr)
        sys.exit(1)
    mesh = Mesh(np.asarray(devices), ("hvd",))

    # The reference benchmarks use 64/GPU; per-chip batch is a free knob on
    # TPU HBM and 256 fills a v5e chip.
    batch_per_chip = 8 if _tiny() else 256
    batch = batch_per_chip * n_dev
    # bn_axis_name: cross-replica BN stats (and replica-invariant
    # batch_stats, required by the P() out_spec under shard_map).
    if _tiny():
        model = models.ResNetTiny(num_classes=10, bn_axis_name="hvd")
        images_shape = (batch, 32, 32, 3)
        n_steps, n_warmup = 2, 1
    else:
        model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                                bn_axis_name="hvd")
        images_shape = (batch, 224, 224, 3)
        n_steps, n_warmup = 20, 3

    images = jax.random.normal(
        jax.random.PRNGKey(0), images_shape,
        jnp.float32 if _tiny() else jnp.bfloat16)
    labels = jnp.zeros((batch,), jnp.int32)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                  axis_name="hvd")
    step, _, (params, batch_stats, opt_state) = build_train_step(
        model, mesh, images, labels, tx)
    _log("model initialized")

    # Per-step flop count from XLA itself — the numerator for MFU.
    cost = step.lower(params, batch_stats, opt_state, images,
                      labels).compile().cost_analysis()
    flops_per_step = float(cost["flops"])

    _log("compiling + warmup")
    for _ in range(n_warmup):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    _log(f"warmup done (loss={float(loss):.3f}); measuring")

    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    img_per_sec_per_chip = batch * n_steps / dt / n_dev
    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            img_per_sec_per_chip / REFERENCE_IMG_PER_SEC_PER_DEVICE, 3),
        "step_ms": round(dt / n_steps * 1e3, 2),
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": n_dev,
        # Which gradient-exchange plane produced these numbers (the
        # headline rides shard_map + explicit psum; the gspmd plane is
        # benchmarked separately in bench_negotiation --data-plane).
        "plane": "eager",
    }
    if platform == "tpu":
        # cost_analysis() reports the per-partition SPMD module, i.e.
        # per-device flops already — don't divide by n_dev again.
        peak = _chip_peak_flops(devices[0].device_kind)
        result["mfu"] = round(flops_per_step / (dt / n_steps) / peak, 4)
        result["tflops_per_sec_per_chip"] = round(
            flops_per_step / (dt / n_steps) / 1e12, 2)

    # An appendix that fails fails the run: its exception is the result.
    _log("flash attention micro-bench")
    result.update(_flash_attention_entry())
    _log("bert pretraining micro-bench")
    result.update(_bert_entry(mesh))
    _log("device-plane int8 codec micro-bench")
    result.update(_device_codec_entry(mesh))
    _log("compiled-collective (gspmd) inventory provenance")
    result.update(_hlo_inventory_entry())
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
