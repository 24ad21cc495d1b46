"""Bring-up smoke: what only a run on the TPU can show, once.

    python chip_smoke.py             one chip, one process, every phase below
    python chip_smoke.py --chips 4   four chips: launch_np4, then device(4)

One chip: the device JAX found, a clean build of the C++ core and
``hvd.init()`` on it, the Pallas kernels alone against their references (at
layouts no benchmark cell runs), and the eager spine (C++ core -> device
plane) on device-resident arrays.  Four chips: one process a chip through
``runner/launch.py --jax-distributed``.  Each phase prints one JSON line; the
last line of stdout is ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  Any phase that fails raises, and the script exits non-zero.
There is no CPU mode: without a TPU it exits before the first phase.

The training steps it once drove are the benchmark's cells now
(``python benchmark/run.py --workload <cell>``), at published widths and held
to stricter checks on every PR: ``resnet50_train`` and ``sync`` went to
``resnet50-1chip``, ``gpt_flash_train`` to ``gpt2m-1chip``, ``spmd_dp4`` to
``gpt2m-dp4``.

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
# bits of mantissa per operation.
TOL_F32 = 6e-3
TOL_BF16_FWD = 2e-2
TOL_BF16_BWD = 4e-2


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


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run launch_np4 and nothing else")
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
    else:
        info = device()
        native_core()
        kernels()
        eager()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
