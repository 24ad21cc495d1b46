"""Bring-up smoke: the trainer's main path, once, on the TPU.

    python chip_smoke.py             one chip, one process, every phase below
    python chip_smoke.py --chips 4   four chips: launch_np4 and spmd_dp4 only

Default mode drives ``hvd.init()`` -> ``hvd.DistributedOptimizer`` -> a
jitted ``shard_map`` step over the ``hvd`` mesh axis on ResNet-50 and on
GPT-2-small with the flash kernels, the Pallas kernels alone against their
references, and the eager spine (C++ core -> device plane) on device-resident
arrays.  Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any phase that
fails raises, and the script exits non-zero.  There is no CPU mode: without
a TPU it exits before the first phase.

The phases are plain functions with size arguments, so
tests/single/test_chip_smoke.py calls them tiny on the CPU mesh.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))

# Stated tolerances (max |a-b| / max |b|).  f32 inputs still go through the
# MXU's default bf16 passes in kernel and reference alike; bf16 carries 8
# bits of mantissa per operation, and a 12-layer backward compounds them.
TOL_F32 = 6e-3
TOL_BF16_FWD = 2e-2
TOL_BF16_BWD = 4e-2
TOL_GPT_LOSS = 1e-2
TOL_GPT_GRAD = 5e-2
TOL_DP4_LOSS = 2e-2


def emit(phase: str, **kv) -> dict:
    line = {"phase": phase, **kv}
    print(json.dumps(line), flush=True)
    return line


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1e-12, float(np.max(np.abs(b)))))


def _check(checks: list, name: str, a, b, tol: float) -> None:
    err = rel_err(a, b)
    checks.append({"name": name, "rel": err, "tol": tol, "ok": err < tol})


def _raise_on_failed(phase: str, checks: list) -> None:
    bad = [c for c in checks if not c["ok"]]
    if bad:
        raise AssertionError(f"{phase}: out of tolerance: {bad}")


# ---------------------------------------------------------------------------
# Phases (one chip)
# ---------------------------------------------------------------------------


def device(expect_count: int = 1, platform: str = "tpu") -> dict:
    """The accelerator JAX found, or exit: nothing below has a CPU mode."""
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != platform:
        print(f"chip_smoke: JAX found no {platform} (platform="
              f"{devs[0].platform!r}); nothing to smoke", file=sys.stderr)
        sys.exit(1)
    if len(devs) != expect_count:
        raise AssertionError(f"expected {expect_count} device(s), JAX reports "
                             f"{len(devs)}: {devs}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit("device", **info, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"),
         compile_cache=jax.config.jax_compilation_cache_dir)
    return info


def native_core(clean: bool = True) -> dict:
    """Rebuild the C++ core from the sources in this copy and init on it."""
    cpp = os.path.join(REPO, "horovod_tpu", "cpp")
    if clean:
        subprocess.run(["make", "-s", "clean"], cwd=cpp, check=True)
    t0 = time.perf_counter()
    import horovod_tpu as hvd
    from horovod_tpu import _core
    from horovod_tpu.context import HorovodContext

    _core._load_library()
    build_s = time.perf_counter() - t0
    hvd.init()
    core = HorovodContext.instance().core
    assert isinstance(core, _core.NativeCore), type(core)
    return emit("native_core", core=type(core).__name__, clean_build=clean,
                build_s=round(build_s, 2), size=hvd.size())


def _hvd_mesh(devices):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), ("hvd",))


def _resnet_run(model, devices, batch: int, image: int):
    """The example's own step (examples/jax_cnn_benchmark.build_train_step)
    compiled ahead of time for a fixed seeded batch."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from examples.jax_cnn_benchmark import build_train_step

    mesh = _hvd_mesh(devices)
    k_img, k_lbl = jax.random.split(jax.random.PRNGKey(0))
    images = jax.random.normal(k_img, (batch, image, image, 3), model.dtype)
    labels = jax.random.randint(k_lbl, (batch,), 0, model.num_classes)
    data = NamedSharding(mesh, P("hvd"))
    images, labels = jax.device_put((images, labels), data)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  axis_name="hvd")
    step, _, state = build_train_step(model, mesh, images, labels, tx)
    state = jax.device_put(state, NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    compiled = step.lower(*state, images, labels).compile()
    compile_s = time.perf_counter() - t0
    return {"step": compiled, "state": state, "images": images,
            "labels": labels, "compile_s": compile_s}


def _run_steps(run: dict, n: int) -> list:
    """n steps through the donated state; losses read back one by one."""
    losses = []
    for _ in range(n):
        *run["state"], loss = run["step"](*run["state"], run["images"],
                                          run["labels"])
        losses.append(float(loss))
    return losses


def resnet50_train(model=None, devices=None, batch: int = 256,
                   image: int = 224, steps: int = 5):
    """ResNet-50 as published through DistributedOptimizer: one compile,
    ``steps`` steps on a fixed batch, loss finite and falling."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import models

    devices = devices or jax.devices()
    if model is None:
        model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                                bn_axis_name="hvd")
    run = _resnet_run(model, devices, batch, image)
    t0 = time.perf_counter()
    losses = _run_steps(run, steps)
    step_ms = (time.perf_counter() - t0) / steps * 1e3
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    stats = devices[0].memory_stats() or {}
    report = emit("resnet50_train", batch=batch, image=image, steps=steps,
                  losses=losses, compile_s=round(run["compile_s"], 2),
                  step_ms_info=round(step_ms, 2),
                  peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return report, run


def sync(run: dict, n: int = 10) -> dict:
    """Does ``block_until_ready`` wait for the device?  Time ``n`` chained
    steps three ways: enqueue only, ended by block_until_ready, ended by a
    scalar readback; and how long a readback still takes after
    block_until_ready returned."""
    import jax

    def chain():
        loss = None
        for _ in range(n):
            *run["state"], loss = run["step"](*run["state"], run["images"],
                                              run["labels"])
        return loss

    float(chain())  # settle
    t0 = time.perf_counter()
    loss = chain()
    enqueue_ms = (time.perf_counter() - t0) * 1e3
    float(loss)
    t0 = time.perf_counter()
    loss = chain()
    jax.block_until_ready(loss)
    bur_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    float(loss)
    after_bur_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    float(chain())
    readback_ms = (time.perf_counter() - t0) * 1e3
    assert min(bur_ms, readback_ms) > 0
    # It waits if the window it ends is as long as the one a readback ends,
    # and nothing is left for a readback to wait for afterwards.
    waits = bur_ms > 0.9 * readback_ms and after_bur_ms < 0.1 * readback_ms
    return emit("sync", steps=n, enqueue_only_ms=round(enqueue_ms, 2),
                block_until_ready_ms=round(bur_ms, 2),
                scalar_readback_ms=round(readback_ms, 2),
                readback_after_block_until_ready_ms=round(after_bur_ms, 3),
                block_until_ready_waits=bool(waits))


def _gpt_step(cfg, mesh):
    """AdamW through DistributedOptimizer over the hvd axis; the step also
    hands back the (reduced) gradient of the token embedding."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import models

    model = models.GPT(cfg)
    tx = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name="hvd")

    def train_step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: models.lm_loss(model.apply(p, ids), ids))(params)
        wte_grad = hvd.allreduce(grads["params"]["wte"]["embedding"],
                                 axis_name="hvd")
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"), wte_grad)

    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1))
    return step, tx


def _gpt_run(cfg, devices, ids, params):
    """The step compiled ahead of time, and its own copy of the state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _hvd_mesh(devices)
    step, tx = _gpt_step(cfg, mesh)
    rep = NamedSharding(mesh, P())
    params = jax.device_put(jax.tree_util.tree_map(lambda x: x.copy(),
                                                   params), rep)
    opt_state = jax.device_put(tx.init(params), rep)
    ids = jax.device_put(ids, NamedSharding(mesh, P("hvd")))
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, ids).compile()
    return compiled, (params, opt_state, ids), time.perf_counter() - t0


def gpt_flash_train(cfg=None, devices=None, batch: int = 8, steps: int = 3,
                    expect_kernel: bool = True) -> dict:
    """GPT-2-small, flash kernels in the compiled step; its first loss and
    token-embedding gradient against the dense path on the same weights."""
    import dataclasses

    import jax
    import numpy as np

    from horovod_tpu import models

    devices = devices or jax.devices()
    cfg = cfg or models.GPT_SMALL
    assert cfg.use_flash
    ids = jax.random.randint(jax.random.PRNGKey(0), (batch, cfg.max_seq_len),
                             0, cfg.vocab_size)
    params = jax.jit(lambda: models.GPT(cfg).init(
        jax.random.PRNGKey(1), ids[:1, :32]))()

    flash, (p, s, x), compile_s = _gpt_run(cfg, devices, ids, params)
    n_kernels = flash.as_text().count("tpu_custom_call")
    if expect_kernel:
        # forward, dq and dkv per layer: the Pallas kernels, not the dense
        # fallback, are in the program.
        assert n_kernels >= 3 * cfg.num_layers, n_kernels
    losses, wte_flash = [], None
    t0 = time.perf_counter()
    for i in range(steps):
        p, s, loss, g = flash(p, s, x)
        losses.append(float(loss))
        if i == 0:
            wte_flash = np.asarray(g, np.float32)
    step_ms = (time.perf_counter() - t0) / steps * 1e3
    assert np.all(np.isfinite(losses)), losses
    assert np.all(np.isfinite(wte_flash))
    del p, s, flash

    dense_cfg = dataclasses.replace(cfg, use_flash=False)
    dense, (p, s, x), dense_compile_s = _gpt_run(dense_cfg, devices, ids,
                                                 params)
    _, _, dense_loss, dense_g = dense(p, s, x)
    checks = []
    _check(checks, "first_loss", losses[0], float(dense_loss), TOL_GPT_LOSS)
    _check(checks, "wte_grad", wte_flash, dense_g, TOL_GPT_GRAD)
    stats = devices[0].memory_stats() or {}
    report = emit("gpt_flash_train", batch=batch, seq=cfg.max_seq_len,
                  layers=cfg.num_layers, steps=steps, losses=losses,
                  dense_first_loss=float(dense_loss),
                  tpu_custom_calls=n_kernels, checks=checks,
                  compile_s=round(compile_s, 2),
                  dense_compile_s=round(dense_compile_s, 2),
                  step_ms_info=round(step_ms, 2),
                  peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    _raise_on_failed("gpt_flash_train", checks)
    return report


def _qkv(b, s, h, d, dtype, key):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


def kernels(interpret: bool = False, seqs=(512, 777), heads: int = 4,
            head_dim: int = 64, codec_elems: int = 1 << 22,
            dtypes=("float32", "bfloat16"), causals=(False, True)) -> dict:
    """The Pallas kernels alone, compiled for the device (``interpret`` off):
    flash forward, dq/dk/dv, the (out, lse) pair the ring hop differentiates
    through, one ring hop under shard_map, and the three wire codecs
    bit-exact against their jnp mirror."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.flash_attention import (
        dense_attention, dense_attention_with_lse, flash_attention,
        flash_attention_with_lse)
    from horovod_tpu.parallel.ring_attention import ring_attention

    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: x.astype(jnp.float32))
    flash = functools.partial(flash_attention, interpret=interpret)
    flash_lse = functools.partial(flash_attention_with_lse,
                                  interpret=interpret)
    checks = []
    s0 = seqs[0]
    tols = {"float32": (TOL_F32, TOL_F32),
            "bfloat16": (TOL_BF16_FWD, TOL_BF16_BWD)}
    for name in dtypes:
        dtype, (tol_f, tol_b) = jnp.dtype(name), tols[name]
        for causal in causals:
            for s in seqs:  # the second length exercises the padding path
                q, k, v = _qkv(2, s, heads, head_dim, dtype, key=0)
                got, ref = jax.jit(lambda q, k, v: (
                    flash(q, k, v, causal),
                    dense_attention(*f32((q, k, v)), causal)))(q, k, v)
                _check(checks, f"fwd/{name}/causal={causal}/s={s}", got, ref,
                       tol_f)
            q, k, v = _qkv(2, s0, heads, head_dim, dtype, key=1)
            w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

            def loss(fn, q, k, v):
                return jnp.sum(fn(q, k, v, causal).astype(jnp.float32) * w)

            got = jax.jit(jax.grad(functools.partial(loss, flash),
                                   argnums=(0, 1, 2)))(q, k, v)
            ref = jax.jit(jax.grad(functools.partial(loss, dense_attention),
                                   argnums=(0, 1, 2)))(*f32((q, k, v)))
            for g, a, b in zip(("dq", "dk", "dv"), got, ref):
                _check(checks, f"bwd/{name}/causal={causal}/{g}", a, b, tol_b)

    # The (out, lse) pair: the lse cotangent folds into delta.
    q, k, v = _qkv(2, s0 // 2, heads, head_dim, jnp.float32, key=2)
    wo = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)
    wl = jax.random.normal(jax.random.PRNGKey(4), (2, heads, s0 // 2),
                           jnp.float32)

    def pair_loss(fn, q, k, v):
        out, lse = fn(q, k, v, True)
        return jnp.sum(out.astype(jnp.float32) * wo) + jnp.sum(lse * wl)

    got = jax.jit(jax.grad(functools.partial(pair_loss, flash_lse),
                           argnums=(0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(
        functools.partial(pair_loss, dense_attention_with_lse),
        argnums=(0, 1, 2)))(q, k, v)
    for g, a, b in zip(("dq", "dk", "dv"), got, ref):
        _check(checks, f"lse_vjp/{g}", a, b, TOL_F32)

    # pallas inside lax.switch inside fori_loop inside shard_map: the
    # composition ring_attention(use_flash=True) builds, on a 1-device mesh.
    q, k, v = _qkv(2, s0, heads, head_dim, jnp.bfloat16, key=5)
    ring = jax.jit(shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True,
                          use_flash=True, block_size=128,
                          interpret=interpret),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("sp",)),
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    _check(checks, "ring_hop/causal", ring(q, k, v),
           jax.jit(lambda q, k, v: dense_attention(*f32((q, k, v)), True))(
               q, k, v), TOL_BF16_FWD)

    # Codecs: Pallas encode+decode against the jnp path quantize() takes off
    # the TPU, bit for bit.
    flat = jax.random.normal(jax.random.PRNGKey(6), (codec_elems,),
                             jnp.float32) * 3.0
    codecs = []
    def roundtrip(flat, codec, interpret):
        q = qz.quantize(flat, codec, interpret)
        return q, qz.dequantize(*q, codec_elems, codec, interpret)

    for codec in ("int8", "int4", "int8g"):
        got_q, got_x = jax.jit(functools.partial(
            roundtrip, codec=codec, interpret=interpret))(flat)
        with mock.patch.object(qz, "_dispatch", lambda _interpret: None):
            ref_q, ref_x = jax.jit(functools.partial(
                roundtrip, codec=codec, interpret=None))(flat)
        same = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves((got_q, got_x)),
                jax.tree_util.tree_leaves((ref_q, ref_x))))
        codecs.append({"codec": codec, "bit_exact": same,
                       "roundtrip_rel": rel_err(got_x, flat)})
    report = emit("kernels", interpret=interpret, checks=checks,
                  codecs=codecs)
    _raise_on_failed("kernels", checks)
    assert all(c["bit_exact"] for c in codecs), codecs
    return report


def eager(n: int = 1 << 20, steps: int = 3, batch: int = 512) -> dict:
    """The un-jitted spine on device-resident arrays: mpi_ops -> C++ core ->
    device plane.  Needs hvd.init() (the native_core phase)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext
    from horovod_tpu.models import MLP, xent_loss

    plane = HorovodContext.instance().device_plane
    before = dict(plane.stats)
    size, rank = hvd.size(), hvd.rank()
    x = jnp.arange(n, dtype=jnp.float32) + rank
    want_sum = np.arange(n, dtype=np.float32) * size + sum(range(size))
    got = hvd.allreduce(x, op=hvd.Sum, name="smoke.sum")
    assert isinstance(got, jax.Array), type(got)
    np.testing.assert_allclose(np.asarray(got), want_sum, rtol=1e-6)
    got = hvd.allreduce(x, op=hvd.Average, name="smoke.avg")
    np.testing.assert_allclose(np.asarray(got), want_sum / size, rtol=1e-6)
    outs = hvd.grouped_allreduce([x, 2 * x], op=hvd.Sum, name="smoke.group")
    np.testing.assert_allclose(np.asarray(outs[1]),
                               2 * want_sum, rtol=1e-6)

    rng = np.random.RandomState(rank)
    xs = jnp.asarray(rng.rand(batch, 28, 28, 1).astype(np.float32))
    ys = jnp.asarray(rng.randint(0, 10, size=batch).astype(np.int32))
    model = MLP()
    params = model.init(jax.random.PRNGKey(rank), xs[:1])
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Average)
    opt_state = tx.init(params)
    loss_and_grad = jax.value_and_grad(
        lambda p: xent_loss(model.apply(p, xs), ys))
    losses = []
    for _ in range(steps):  # no jit around the step
        loss, grads = loss_and_grad(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses

    moved = {k: plane.stats[k] - before[k] for k in plane.stats
             if plane.stats[k] != before[k]}
    done = "identity" if size == 1 else "allreduce"
    assert moved.get(done, 0) > 0, (done, moved)
    assert plane.stats["host_fallback"] == 0, plane.stats
    return emit("eager", size=size, losses=losses, device_plane_moved=moved,
                host_fallback=plane.stats["host_fallback"])


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def launch_np4_worker(platform: str = "tpu") -> None:
    """One of four launcher-spawned processes, one chip each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    hvd.init()
    dev = jax.local_devices()[0]
    assert dev.platform == platform, dev
    assert jax.local_device_count() == 1, jax.local_devices()
    assert jax.device_count() == 4, jax.devices()
    assert hvd.size() == 4
    n = 1 << 20
    x = jnp.full((n,), float(hvd.rank() + 1), jnp.float32)
    got = hvd.allreduce(x, op=hvd.Sum, name="np4.sum")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full((n,), 10.0, np.float32))
    # Rank order survives the rank -> chip mapping (a jax process's index
    # follows its chip, not its rank).
    order = hvd.allgather(jnp.full((1,), hvd.rank(), jnp.int32),
                          name="np4.order")
    np.testing.assert_array_equal(np.asarray(order), np.arange(4))
    stats = HorovodContext.instance().device_plane.stats
    assert stats["allreduce"] > 0 and stats["allgather"] > 0, stats
    assert stats["host_fallback"] == 0, stats
    emit("launch_np4_worker", rank=hvd.rank(),
         jax_process_index=jax.process_index(), device_id=dev.id,
         coords=list(getattr(dev, "coords", ())), kind=dev.device_kind,
         device_count=jax.device_count(), allreduce_sum=float(got[0]),
         device_plane_allreduce=stats["allreduce"],
         host_fallback=stats["host_fallback"])
    hvd.shutdown()


def launch_np4(np_workers: int = 4, timeout: float = 600.0) -> dict:
    """One process per chip through the launcher.  Runs while this process
    has not initialised a JAX backend: a parent that held the chips would
    leave none for the workers."""
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
           str(np_workers), "--jax-distributed", sys.executable,
           os.path.join(REPO, "chip_smoke.py"), "--worker"]
    # Its own process group: on a timeout the launcher AND its workers go.
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        sys.stderr.write(out[-8000:])
        raise AssertionError(f"launch_np4: no result within {timeout:.0f}s")
    workers = []
    for line in out.splitlines():
        at = line.find('{"phase": "launch_np4_worker"')
        if at >= 0:
            workers.append(json.loads(line[at:]))
    if proc.returncode != 0 or len(workers) != np_workers:
        sys.stderr.write(out[:6000] + "\n[...]\n" + out[-6000:])
        raise AssertionError(
            f"launch_np4: launcher rc={proc.returncode}, "
            f"{len(workers)}/{np_workers} workers reported")
    workers.sort(key=lambda w: w["rank"])
    assert len({w["device_id"] for w in workers}) == np_workers, workers
    assert all(w["device_count"] == np_workers and w["host_fallback"] == 0
               for w in workers), workers
    return emit("launch_np4", workers=workers)


def spmd_dp4(model=None, devices=None, batch: int = 256, image: int = 224,
             steps: int = 3) -> dict:
    """The ResNet step on a 4-device hvd mesh against the same images on a
    one-device mesh: same losses, parameters on four chips, all-reduces in
    the compiled module."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import models

    devices = devices or jax.devices()
    assert len(devices) == 4, devices
    if model is None:
        model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                                bn_axis_name="hvd")
    dp = _resnet_run(model, devices, batch, image)
    text = dp["step"].as_text()
    n_allreduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    assert n_allreduce > 0, "no all-reduce in the 4-device module"
    dp_losses = _run_steps(dp, steps)
    leaves = jax.tree_util.tree_leaves(dp["state"][0])
    on = {s.device for leaf in leaves for s in leaf.addressable_shards}
    assert on == set(devices), (on, devices)
    assert dp["images"].sharding.shard_shape(
        dp["images"].shape)[0] == batch // 4
    dp_compile_s = dp.pop("compile_s")
    del dp

    one = _resnet_run(model, devices[:1], batch, image)
    one_losses = _run_steps(one, steps)
    assert np.all(np.isfinite(dp_losses + one_losses))
    checks = []
    for i, (a, b) in enumerate(zip(dp_losses, one_losses)):
        _check(checks, f"loss[{i}]", a, b, TOL_DP4_LOSS)
    report = emit("spmd_dp4", batch=batch, per_chip=batch // 4, steps=steps,
                  dp4_losses=dp_losses, one_device_losses=one_losses,
                  all_reduce_ops=n_allreduce,
                  param_devices=sorted(d.id for d in on), checks=checks,
                  compile_s=round(dp_compile_s, 2),
                  one_device_compile_s=round(one["compile_s"], 2))
    _raise_on_failed("spmd_dp4", checks)
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run launch_np4 and spmd_dp4 and nothing else")
    ap.add_argument("--worker", action="store_true",
                    help="internal: one launch_np4 worker")
    args = ap.parse_args(argv)
    if args.worker:
        launch_np4_worker()
        return 0

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # exported: the launcher's workers share it
    if args.chips == 4:
        launch_np4()
        info = device(expect_count=4)
        import horovod_tpu as hvd

        hvd.init()
        spmd_dp4()
    else:
        info = device()
        native_core()
        _, run = resnet50_train()
        sync(run)
        del run
        gpt_flash_train()
        kernels()
        eager()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
