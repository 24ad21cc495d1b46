"""Steady-state negotiation benchmark: response-cache on vs off vs
fusion-off at np=4 (SURVEY.md §5 — "the response-cache bit-vector trick
matters even more on TPU": DCN round-trips are pricier than MPI ones).

Measures, per configuration:
- steady-state cycle throughput (gradient-bucket steps/s, 50 named
  tensors per step, the DistributedOptimizer eager shape), and
- negotiation ctrl-channel bytes per step on a worker rank (cache hits
  travel as 16-byte (id, handle) pairs; misses re-serialize the full
  request metadata every cycle).

Usage: python tools/bench_negotiation.py [--np 4] [--steps 60]
Prints one JSON line per configuration plus a summary ratio line.

With --wire-compression {bf16,int8} an additional data-plane section runs:
a large fp32 allreduce over two fake hosts with the hierarchical plane (so
the codec engages on the cross-host leader ring), reporting cross-host
wire bytes/step against the fp32 baseline and the max abs error the codec
introduced.

With --device-codec {int8,int4} an additional device-plane section
runs: a jitted shard_map allreduce over a forced 8-device CPU host
platform with the HOROVOD_WIRE_COMPRESSION ``device=`` plane on vs off,
reporting the codec's encoded-vs-raw wire ratio (from the device-plane
byte counters), the quantization error, and throughput against the
uncompressed traced ring.  --device-schedule {auto,ring,bidi,torus}
selects the ring topology (HOROVOD_DEVICE_SCHEDULE); pass it alone or
with --device-codec to sweep schedules at a fixed codec.  On CPU the
ratio is the point — the hop count is what the schedules change, and
interpret-mode kernels are not a speed story.

With --data-plane an additional section times one SGD train step under
the eager plane (shard_map + the optimizer's explicit psum) vs the gspmd
plane (batch-sharded inputs + compiler-inserted collectives) on the
forced 8-device CPU mesh — interleaved, best-of-3 per plane like the
flight section — and reports the gspmd-vs-eager step ratio recorded in
docs/benchmarks.md (the acceptance bar: gspmd's step time <= eager's,
i.e. step_time_ratio_gspmd_vs_eager <= 1.0).  The gspmd leg runs through
ops/hlo_inspect.instrument, and its compiled-collective inventory (kinds
plus analytic ring-model bytes) is stamped into the summary line as
provenance for the numbers.

With --hlo-inspect an additional section reruns the gspmd-plane worker
with HOROVOD_HLO_INSPECT=0 vs 1 — interleaved, best-of-3 per config like
the flight section — and reports compiled-collective introspection's
step-throughput overhead.  The bar is <= 1%: inspection (one extra
lower + compile + module-text walk) happens once per trace signature at
warmup, never inside the timed step loop.

With --metrics an additional section reruns the cache_on configuration
with HOROVOD_METRICS=1 and reports the registry's negotiation-throughput
overhead against the metrics-off baseline (disabled is the baseline
itself: every instrumentation site is behind one relaxed bool load, so
disabled overhead is zero by construction).

With --flight-recorder an additional section runs the cache_on
configuration with HOROVOD_FLIGHT_RECORDER=off vs on — interleaved,
best-of-3 per config, because loopback wall clock is noisier than the
effect — and reports the always-on event black box's
negotiation-throughput overhead (the bar is <= 1%: a record is a handful
of relaxed atomic stores into a per-thread ring).

With --step-trace an additional section runs the cache_on configuration
with HOROVOD_STEP_TRACE=0 vs 1 (plus a third leg stacking
HOROVOD_METRICS=1 on top, the full CYCLE-trailer marker-2 payload) —
interleaved, best-of-3 per config like the flight section — and reports
the causal step tracer's negotiation-throughput overhead.  The bar is
<= 1% with the cockpit disabled: span capture is relaxed atomic adds at
already-instrumented sites, and the per-cycle trailer is 6 extra i64s.

With --fleet-telemetry an additional section runs the cache_on
configuration with HOROVOD_METRICS=1 and HOROVOD_FLEET_TELEMETRY=0 vs 1 —
interleaved, best-of-3 per config like the flight section — and reports
the v11 fleet telemetry plane's negotiation-throughput overhead: the
delta/varint sketch section every rank appends to its CYCLE frame, the
coordinator-side sketch merge, and the ~1 Hz history/goodput/sentinel
tick.  The bar is <= 1%; the metrics-on baseline isolates the plane's own
cost from the registry's.

With --np-sweep N,N,... the tool instead sweeps job sizes over fake
multi-host topologies (4 ranks per fake host) and prints the O(n)-vs-
O(hosts)-vs-O(fanout) table behind the leader tree: coordinator inbound
control messages and bytes per negotiation cycle — flat, auto-depth tree
(v9 shape below 32 hosts), and the tree forced three levels deep
(HOROVOD_CONTROL_TREE_DEPTH=3, the v12 adaptive-depth plane) — from the
ctrl_msgs_/ctrl_bytes_ counters normalised by cycle_count.  Results are
recorded in docs/benchmarks.md.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _worker(steps: int, tensors: int):
    import time

    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import mpi_ops
    from horovod_tpu.context import HorovodContext

    hvd.init(build_mesh=False)
    grads = [np.full(64, float(i), np.float32) for i in range(tensors)]

    def step(tag):
        hs = [mpi_ops.allreduce_async(g, name=f"grad.{i}", op=hvd.Sum)
              for i, g in enumerate(grads)]
        for h in hs:
            mpi_ops.synchronize(h)

    # Warmup: populate the response cache / reach steady state.
    for s in range(5):
        step(s)
    core = HorovodContext.instance().core
    stats0 = core.negotiation_stats() if hasattr(core, "negotiation_stats") \
        else None
    t0 = time.perf_counter()
    for s in range(steps):
        step(s)
    dt = time.perf_counter() - t0
    result = {"rank": hvd.rank(), "steps_per_s": steps / dt,
              "tensor_ops_per_s": steps * len(grads) / dt}
    if stats0 is not None:
        stats1 = core.negotiation_stats()
        # Announce direction (worker -> coordinator): where the cache's
        # (id, handle) pairs replace full request metadata.  The recv
        # direction is the response list, identical in both configs.
        result["announce_bytes_per_step"] = (
            (stats1["ctrl_sent"] - stats0["ctrl_sent"]) / steps)
        result["ctrl_bytes_per_step"] = (
            (stats1["ctrl_sent"] + stats1["ctrl_recv"]
             - stats0["ctrl_sent"] - stats0["ctrl_recv"]) / steps)
    hvd.shutdown()
    return result


def run_config(name: str, env: dict, np_: int, steps: int, tensors: int):
    from horovod_tpu.runner import run

    full_env = {"JAX_PLATFORMS": "cpu", **env}
    results = run(_worker, args=(steps, tensors), np=np_, env=full_env,
                  stream_prefix=False)
    agg = {
        "config": name,
        "np": np_,
        "steps_per_s": round(min(r["steps_per_s"] for r in results), 2),
        "tensor_ops_per_s": round(
            min(r["tensor_ops_per_s"] for r in results), 1),
    }
    per_step = [r.get("ctrl_bytes_per_step") for r in results[1:]]
    if per_step and per_step[0] is not None:
        # Worker ranks only: the coordinator's ctrl traffic counts every
        # worker's frames and would double-book.
        agg["worker_ctrl_bytes_per_step"] = round(max(per_step), 1)
        agg["worker_announce_bytes_per_step"] = round(
            max(r["announce_bytes_per_step"] for r in results[1:]), 1)
    print(json.dumps(agg), flush=True)
    return agg


def _wire_worker(steps: int, elems: int):
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    x = ((np.arange(elems) % 251) + r).astype(np.float32)
    exact = sum(((np.arange(elems) % 251) + rr).astype(np.float64)
                for rr in range(s))
    core = HorovodContext.instance().core
    hvd.allreduce(x, op=hvd.Sum, name="wb.warm")
    hvd.barrier()
    s0 = core.data_plane_stats()
    max_err = 0.0
    import time

    t0 = time.perf_counter()
    for i in range(steps):
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"wb.{i}"),
                         dtype=np.float64)
        max_err = max(max_err, float(np.max(np.abs(out - exact))))
    dt = time.perf_counter() - t0
    s1 = core.data_plane_stats()
    hvd.barrier()
    hvd.shutdown()
    return {"rank": r, "steps_per_s": steps / dt, "max_abs_err": max_err,
            "xhost_bytes_per_step":
                (s1["data_sent_xhost"] - s0["data_sent_xhost"]) / steps,
            "raw_xhost_bytes_per_step":
                (s1["data_raw_xhost"] - s0["data_raw_xhost"]) / steps}


def run_wire_config(codec: str, np_: int, steps: int, elems: int):
    from horovod_tpu.runner import run

    env = {"JAX_PLATFORMS": "cpu", "HOROVOD_HIER_FAKE_HOSTS": "2",
           "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
           "HOROVOD_WIRE_COMPRESSION": codec}
    results = run(_wire_worker, args=(steps, elems), np=np_, env=env,
                  stream_prefix=False)
    agg = {
        "config": f"wire_{codec}",
        "np": np_,
        "payload_bytes": elems * 4,
        "steps_per_s": round(min(r["steps_per_s"] for r in results), 2),
        "xhost_bytes_per_step": round(
            sum(r["xhost_bytes_per_step"] for r in results), 1),
        "raw_xhost_bytes_per_step": round(
            sum(r["raw_xhost_bytes_per_step"] for r in results), 1),
        "max_abs_err": max(r["max_abs_err"] for r in results),
    }
    print(json.dumps(agg), flush=True)
    return agg


def _device_worker(steps: int, elems: int):
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    import horovod_tpu as hvd
    import horovod_tpu.ops.quantize as qz

    hvd.init(build_mesh=False)
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("q",))

    def fn(shard):
        return hvd.allreduce(shard, axis_name="q", op=hvd.Sum)

    sm = shard_map(fn, mesh=mesh, in_specs=P("q"), out_specs=P("q"),
                   check_vma=False)
    jitted = jax.jit(sm)

    per_dev = max(1, elems // len(devs))
    x_np = (((np.arange(len(devs) * per_dev) % 509) / 509.0 - 0.5)
            .astype(np.float32).reshape(len(devs), per_dev))
    exact = np.sum(x_np.astype(np.float64), axis=0)
    x = jnp.asarray(x_np)

    # The byte counters tick at trace time (once per compile), so the
    # delta around the warmup call IS one step's ring volume.
    qz.reset_device_byte_counters()
    out = np.asarray(jitted(x))
    raw, enc = qz.device_byte_counters()
    max_err = float(np.max(np.abs(out.astype(np.float64) - exact)))

    t0 = time.perf_counter()
    for _ in range(steps):
        jitted(x).block_until_ready()
    dt = time.perf_counter() - t0

    hvd.shutdown()
    return {"steps_per_s": steps / dt, "max_abs_err": max_err,
            "device_raw_bytes_per_step": raw,
            "device_encoded_bytes_per_step": enc}


def run_device_config(codec: str, steps: int, elems: int,
                      schedule: str | None = None):
    from horovod_tpu.runner import run

    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "HOROVOD_WIRE_COMPRESSION_MIN_BYTES": "4096"}
    if codec != "none":
        env["HOROVOD_WIRE_COMPRESSION"] = f"device={codec}"
    if schedule:
        env["HOROVOD_DEVICE_SCHEDULE"] = schedule
    results = run(_device_worker, args=(steps, elems), np=1, env=env,
                  stream_prefix=False)
    agg = dict(results[0])
    name = f"device_{codec}" + (f"_{schedule}" if schedule else "")
    agg.update({"config": name, "payload_bytes": elems * 4,
                "steps_per_s": round(agg["steps_per_s"], 2)})
    print(json.dumps(agg), flush=True)
    return agg


def _plane_worker(steps: int, elems: int, plane: str):
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    import horovod_tpu as hvd
    from horovod_tpu.ops import gspmd_plane as gp
    from horovod_tpu.ops import hlo_inspect as hi
    from horovod_tpu.optimizer import DistributedOptimizer

    hvd.init(build_mesh=False)
    devs = jax.devices()
    n = len(devs)

    # One SGD step on an elementwise model: the weight vector IS the
    # collective payload (elems fp32), the batch is sharded n ways.  An
    # elementwise (not matmul) backward keeps the comparison about the
    # planes: the SPMD partitioner lowers a matmul's weight gradient
    # through a post-all-reduce transpose copy on the CPU backend, a
    # partitioner artifact that would swamp the collective delta.
    d = max(8, elems)
    batch = 2 * n
    rs = np.random.RandomState(0)
    x_np = rs.randn(batch, d).astype(np.float32)
    y_np = rs.randn(batch, d).astype(np.float32)
    params = {"w": jnp.zeros((d,), jnp.float32)}

    def loss(p, xs, ys):
        return jnp.mean((xs * p["w"] - ys) ** 2)

    if plane == "gspmd":
        # gspmd convention: plain jit, batch-sharded inputs, global-mean
        # loss — GSPMD inserts and schedules the gradient reduction.
        mesh = gp.build_gspmd_mesh()
        tx = DistributedOptimizer(optax.sgd(0.01), plane="gspmd")
        x = jax.device_put(jnp.asarray(x_np),
                           NamedSharding(mesh, P(gp.BATCH_AXIS)))
        y = jax.device_put(jnp.asarray(y_np),
                           NamedSharding(mesh, P(gp.BATCH_AXIS)))

        @jax.jit
        def step(p, s, xs, ys):
            g = jax.grad(loss)(p, xs, ys)
            u, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, u), s2

        # Compiled-collective introspection rides the warmup compile
        # (once per trace signature); with HOROVOD_HLO_INSPECT=0 this
        # returns ``step`` unchanged — the --hlo-inspect baseline.
        step = hi.instrument(step, label="bench_plane")
    else:
        # eager convention: shard_map with the bound mesh axis, explicit
        # psum-average inside the optimizer.  Inputs are committed
        # sharded exactly like the gspmd leg — neither plane pays a
        # per-call scatter.
        mesh = Mesh(np.asarray(devs), ("hvd",))
        tx = DistributedOptimizer(optax.sgd(0.01), plane="eager")
        x = jax.device_put(jnp.asarray(x_np),
                           NamedSharding(mesh, P("hvd")))
        y = jax.device_put(jnp.asarray(y_np),
                           NamedSharding(mesh, P("hvd")))

        def shard_step(p, s, xs, ys):
            g = jax.grad(loss)(p, xs, ys)
            u, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, u), s2

        sm = shard_map(shard_step, mesh=mesh,
                       in_specs=(P(), P(), P("hvd"), P("hvd")),
                       out_specs=(P(), P()), check_vma=False)
        step = jax.jit(sm)

    state = tx.init(params)
    p, s = step(params, state, x, y)  # compile outside the timed loop
    jax.tree_util.tree_leaves(p)[0].block_until_ready()

    t0 = time.perf_counter()
    for _ in range(steps):
        p, s = step(p, s, x, y)
    jax.tree_util.tree_leaves(p)[0].block_until_ready()
    dt = time.perf_counter() - t0

    hvd.shutdown()
    res = {"steps_per_s": steps / dt, "plane": plane, "grad_bytes": d * 4}
    invs = [i for i in hi.inventories() if i.label == "bench_plane"]
    if invs:
        # Provenance: what XLA actually scheduled for this step (empty
        # when introspection is off or the plane resolved eager).
        inv = invs[-1]
        res["hlo"] = {"collectives": inv.collectives,
                      "kinds": inv.kind_counts(),
                      "raw_bytes": inv.raw_bytes,
                      "wire_bytes": inv.wire_bytes}
    return res


def run_plane_config(plane: str, steps: int, elems: int,
                     extra_env=None, tag: str = ""):
    from horovod_tpu.runner import run

    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    if extra_env:
        env.update(extra_env)
    results = run(_plane_worker, args=(steps, elems, plane), np=1, env=env,
                  stream_prefix=False)
    agg = dict(results[0])
    agg.update({"config": f"plane_{plane}{tag}",
                "steps_per_s": round(agg["steps_per_s"], 2)})
    print(json.dumps(agg), flush=True)
    return agg


def _sweep_worker(steps: int, tensors: int):
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import mpi_ops

    hvd.init(build_mesh=False)
    grads = [np.full(64, float(i), np.float32) for i in range(tensors)]

    def step():
        hs = [mpi_ops.allreduce_async(g, name=f"sw.{i}", op=hvd.Sum)
              for i, g in enumerate(grads)]
        for h in hs:
            mpi_ops.synchronize(h)

    for _ in range(5):  # steady state: response cache populated
        step()
    hvd.barrier()
    c0 = hvd.metrics()["counters"]
    for _ in range(steps):
        step()
    hvd.barrier()
    c1 = hvd.metrics()["counters"]
    rank = hvd.rank()
    hvd.shutdown()
    return {"rank": rank,
            "cycles": c1["cycle_count"] - c0["cycle_count"],
            "msgs_recv": c1["ctrl_msgs_recv"] - c0["ctrl_msgs_recv"],
            "msgs_sent": c1["ctrl_msgs_sent"] - c0["ctrl_msgs_sent"],
            "bytes_recv": c1["ctrl_bytes_recv"] - c0["ctrl_bytes_recv"],
            "bytes_sent": c1["ctrl_bytes_sent"] - c0["ctrl_bytes_sent"]}


def run_np_sweep(np_list, steps: int, tensors: int):
    """Coordinator control messages + bytes per cycle — flat vs the
    auto-depth tree vs the tree forced three levels deep — at each job
    size over fake hosts (4 consecutive ranks per host).  The lockstep
    makes messages/cycle a topology constant — (np-1) flat,
    (local-1)+(hosts-1) for the two-level tree, (local-1)+direct-children
    once a super layer absorbs leader clusters — so the per-cycle numbers
    are exact while bytes/cycle reflect the measured aggregate framing
    overhead."""
    from horovod_tpu.runner import run

    for np_ in np_list:
        hosts = max(2, np_ // 4)
        row = {"metric": "ctrl_plane_np_sweep", "np": np_, "hosts": hosts}
        modes = [("flat", "off", None), ("tree", "on", None)]
        if hosts >= 3:  # depth 3 needs >= 3 leaders to grow a super layer
            modes.append(("tree_d3", "on", "3"))
        for mode, tree, depth in modes:
            env = {"JAX_PLATFORMS": "cpu", "HOROVOD_METRICS": "1",
                   "HOROVOD_SHM_DISABLE": "1",
                   "HOROVOD_HIER_FAKE_HOSTS": str(hosts),
                   "HOROVOD_CONTROL_TREE": tree}
            if depth is not None:
                env["HOROVOD_CONTROL_TREE_DEPTH"] = depth
            results = run(_sweep_worker, args=(steps, tensors), np=np_,
                          env=env, stream_prefix=False)
            coord = next(r for r in results if r["rank"] == 0)
            cycles = max(coord["cycles"], 1)
            row[f"{mode}_msgs_per_cycle"] = round(
                coord["msgs_recv"] / cycles, 2)
            row[f"{mode}_bytes_per_cycle"] = round(
                coord["bytes_recv"] / cycles, 1)
        row["msgs_ratio"] = round(
            row["flat_msgs_per_cycle"]
            / max(row["tree_msgs_per_cycle"], 1e-9), 2)
        if "tree_d3_msgs_per_cycle" in row:
            row["msgs_ratio_d3"] = round(
                row["flat_msgs_per_cycle"]
                / max(row["tree_d3_msgs_per_cycle"], 1e-9), 2)
        print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, default=4)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--tensors", type=int, default=50)
    ap.add_argument("--wire-compression", default=None,
                    choices=["bf16", "int8", "int4"],
                    help="also benchmark the wire codec on a cross-host "
                         "(fake two-host, hierarchical) topology against "
                         "the fp32 baseline: bytes/step + max abs error")
    ap.add_argument("--wire-mb", type=float, default=4.0,
                    help="fp32 payload size for the wire benchmark (MiB)")
    ap.add_argument("--wire-steps", type=int, default=10)
    ap.add_argument("--device-codec", default=None,
                    choices=["int8", "int4"],
                    help="also benchmark the in-jit device-plane codec "
                         "(HOROVOD_WIRE_COMPRESSION device= plane) over a "
                         "forced 8-device CPU host platform: encoded/raw "
                         "wire ratio, quantization error, steps/s vs the "
                         "uncompressed traced ring")
    ap.add_argument("--device-schedule", default=None,
                    choices=["auto", "ring", "bidi", "torus"],
                    help="ring topology for the device benchmark "
                         "(HOROVOD_DEVICE_SCHEDULE); implies the device "
                         "section with codec int8 if --device-codec is "
                         "not given")
    ap.add_argument("--device-mb", type=float, default=4.0,
                    help="fp32 payload size for the device benchmark (MiB)")
    ap.add_argument("--device-steps", type=int, default=20)
    ap.add_argument("--data-plane", action="store_true",
                    help="also measure one SGD train step under the eager "
                         "plane (shard_map + explicit psum) vs the gspmd "
                         "plane (sharded inputs, compiler-inserted "
                         "collectives) on the 8-device CPU mesh — "
                         "interleaved, best-of-3 — and report the "
                         "gspmd-vs-eager step ratio")
    ap.add_argument("--hlo-inspect", action="store_true",
                    help="also measure compiled-collective introspection's "
                         "step overhead: the gspmd-plane worker with "
                         "HOROVOD_HLO_INSPECT=0 vs 1, interleaved "
                         "best-of-3 (<= 1%% is the acceptance bar — "
                         "inspection runs once per trace, never per step)")
    ap.add_argument("--metrics", action="store_true",
                    help="also measure the metrics registry's negotiation "
                         "overhead: cache_on rerun with HOROVOD_METRICS=1, "
                         "steps/s ratio vs the metrics-off baseline")
    ap.add_argument("--step-trace", action="store_true",
                    help="also measure causal step tracing's negotiation "
                         "overhead (off vs on vs on+metrics, interleaved "
                         "best-of-3; cockpit stays disabled)")
    ap.add_argument("--flight-recorder", action="store_true",
                    help="also measure the flight recorder's negotiation "
                         "overhead: cache_on with the recorder off vs on, "
                         "steps/s ratio (<= 1%% is the acceptance bar)")
    ap.add_argument("--fleet-telemetry", action="store_true",
                    help="also measure the v11 fleet telemetry plane's "
                         "negotiation overhead: metrics-on with "
                         "HOROVOD_FLEET_TELEMETRY=0 vs 1, interleaved "
                         "best-of-3 (<= 1%% is the acceptance bar)")
    ap.add_argument("--np-sweep", default=None, metavar="N,N,...",
                    help="run ONLY the control-plane scaling sweep: "
                         "coordinator ctrl messages + bytes per cycle — "
                         "flat vs auto-depth tree vs forced depth-3 "
                         "(v12) — at each np over fake hosts "
                         "(4 ranks/host)")
    ap.add_argument("--sweep-steps", type=int, default=30)
    args = ap.parse_args()

    if args.np_sweep:
        run_np_sweep([int(n) for n in args.np_sweep.split(",")],
                     args.sweep_steps, args.tensors)
        return

    cache_on = run_config("cache_on", {}, args.np, args.steps, args.tensors)
    cache_off = run_config("cache_off", {"HOROVOD_CACHE_CAPACITY": "0"},
                           args.np, args.steps, args.tensors)
    fusion_off = run_config(
        "fusion_off", {"HOROVOD_FUSION_THRESHOLD": "1"},
        args.np, args.steps, args.tensors)

    summary = {
        "metric": "negotiation_cache_speedup",
        "steps_ratio_cache_on_vs_off": round(
            cache_on["steps_per_s"] / cache_off["steps_per_s"], 3),
        "steps_ratio_cache_on_vs_fusion_off": round(
            cache_on["steps_per_s"] / fusion_off["steps_per_s"], 3),
    }
    if "worker_ctrl_bytes_per_step" in cache_on and \
            "worker_ctrl_bytes_per_step" in cache_off:
        summary["ctrl_bytes_ratio_on_vs_off"] = round(
            cache_on["worker_ctrl_bytes_per_step"]
            / max(cache_off["worker_ctrl_bytes_per_step"], 1.0), 3)
        summary["announce_bytes_ratio_on_vs_off"] = round(
            cache_on["worker_announce_bytes_per_step"]
            / max(cache_off["worker_announce_bytes_per_step"], 1.0), 3)
    print(json.dumps(summary), flush=True)

    if args.metrics:
        metrics_on = run_config("cache_on_metrics", {"HOROVOD_METRICS": "1"},
                                args.np, args.steps, args.tensors)
        ratio = metrics_on["steps_per_s"] / max(cache_on["steps_per_s"], 1e-9)
        print(json.dumps({
            "metric": "metrics_overhead",
            "steps_ratio_on_vs_off": round(ratio, 3),
            "overhead_pct": round(max(0.0, (1.0 - ratio)) * 100.0, 2),
        }), flush=True)

    if args.flight_recorder:
        # Loopback wall clock is scheduler-noise-dominated: one config's
        # steps/s varies far more run-to-run than the <= 1% bar being
        # measured.  Interleave the pair and keep the best of three — the
        # fastest (least-perturbed) run per config bounds its true cost.
        best_off = best_on = 0.0
        for i in range(3):
            flight_off = run_config(
                f"cache_on_flight_off_r{i}",
                {"HOROVOD_FLIGHT_RECORDER": "off"},
                args.np, args.steps, args.tensors)
            flight_on = run_config(
                f"cache_on_flight_on_r{i}", {"HOROVOD_FLIGHT_RECORDER": "1"},
                args.np, args.steps, args.tensors)
            best_off = max(best_off, flight_off["steps_per_s"])
            best_on = max(best_on, flight_on["steps_per_s"])
        ratio = best_on / max(best_off, 1e-9)
        print(json.dumps({
            "metric": "flight_recorder_overhead",
            "best_of": 3,
            "steps_ratio_on_vs_off": round(ratio, 3),
            "overhead_pct": round(max(0.0, (1.0 - ratio)) * 100.0, 2),
        }), flush=True)

    if args.step_trace:
        # Same interleaved best-of-3 discipline as the flight section:
        # the <= 1% bar is far below loopback scheduler noise.  The third
        # leg stacks metrics on so the full marker-2 CYCLE trailer
        # (7 metric + 6 step-trace i64s) is priced too.
        best_off = best_on = best_both = 0.0
        for i in range(3):
            trace_off = run_config(
                f"cache_on_trace_off_r{i}", {"HOROVOD_STEP_TRACE": "0"},
                args.np, args.steps, args.tensors)
            trace_on = run_config(
                f"cache_on_trace_on_r{i}", {"HOROVOD_STEP_TRACE": "1"},
                args.np, args.steps, args.tensors)
            trace_both = run_config(
                f"cache_on_trace_metrics_r{i}",
                {"HOROVOD_STEP_TRACE": "1", "HOROVOD_METRICS": "1"},
                args.np, args.steps, args.tensors)
            best_off = max(best_off, trace_off["steps_per_s"])
            best_on = max(best_on, trace_on["steps_per_s"])
            best_both = max(best_both, trace_both["steps_per_s"])
        ratio = best_on / max(best_off, 1e-9)
        print(json.dumps({
            "metric": "step_trace_overhead",
            "best_of": 3,
            "steps_ratio_on_vs_off": round(ratio, 3),
            "overhead_pct": round(max(0.0, (1.0 - ratio)) * 100.0, 2),
            "steps_ratio_with_metrics_vs_off": round(
                best_both / max(best_off, 1e-9), 3),
        }), flush=True)

    if args.fleet_telemetry:
        # Interleaved best-of-3 against a metrics-ON baseline: the plane
        # rides the metrics plumbing (sketches are captured from the
        # registry's histograms), so the delta being priced is the v11
        # sketch sections + coordinator merge + 1 Hz tick alone.
        best_off = best_on = 0.0
        for i in range(3):
            fleet_off = run_config(
                f"cache_on_fleet_off_r{i}",
                {"HOROVOD_METRICS": "1", "HOROVOD_FLEET_TELEMETRY": "0"},
                args.np, args.steps, args.tensors)
            fleet_on = run_config(
                f"cache_on_fleet_on_r{i}",
                {"HOROVOD_METRICS": "1", "HOROVOD_FLEET_TELEMETRY": "1"},
                args.np, args.steps, args.tensors)
            best_off = max(best_off, fleet_off["steps_per_s"])
            best_on = max(best_on, fleet_on["steps_per_s"])
        ratio = best_on / max(best_off, 1e-9)
        print(json.dumps({
            "metric": "fleet_telemetry_overhead",
            "best_of": 3,
            "steps_ratio_on_vs_off": round(ratio, 3),
            "overhead_pct": round(max(0.0, (1.0 - ratio)) * 100.0, 2),
        }), flush=True)

    if args.data_plane:
        # Interleaved best-of-3 like the flight section: loopback wall
        # clock is noisier than the plane delta being measured.  Same
        # train step, both calling conventions (docs/architecture.md
        # "Three data planes"), sized by --device-mb / --device-steps.
        elems = int(args.device_mb * (1 << 20)) // 4
        best_eager = best_gspmd = 0.0
        hlo = None
        for _ in range(3):
            e = run_plane_config("eager", args.device_steps, elems)
            g = run_plane_config("gspmd", args.device_steps, elems)
            best_eager = max(best_eager, e["steps_per_s"])
            best_gspmd = max(best_gspmd, g["steps_per_s"])
            hlo = g.get("hlo") or hlo
        print(json.dumps({
            "metric": "data_plane",
            "best_of": 3,
            "steps_ratio_gspmd_vs_eager": round(
                best_gspmd / max(best_eager, 1e-9), 3),
            "step_time_ratio_gspmd_vs_eager": round(
                best_eager / max(best_gspmd, 1e-9), 3),
            # Compiled-collective provenance for the gspmd leg (None on
            # a HOROVOD_HLO_INSPECT=0 run).
            "hlo": hlo,
        }), flush=True)

    if args.hlo_inspect:
        # Interleaved best-of-3 like the flight section: introspection's
        # lower+compile+parse rides the warmup trace, so the timed loop
        # must not move — <= 1% is the bar.
        elems = int(args.device_mb * (1 << 20)) // 4
        best_off = best_on = 0.0
        hlo = None
        for i in range(3):
            h_off = run_plane_config(
                "gspmd", args.device_steps, elems,
                extra_env={"HOROVOD_HLO_INSPECT": "0"},
                tag=f"_hlo_off_r{i}")
            h_on = run_plane_config(
                "gspmd", args.device_steps, elems,
                extra_env={"HOROVOD_HLO_INSPECT": "1"},
                tag=f"_hlo_on_r{i}")
            best_off = max(best_off, h_off["steps_per_s"])
            best_on = max(best_on, h_on["steps_per_s"])
            hlo = h_on.get("hlo") or hlo
        ratio = best_on / max(best_off, 1e-9)
        print(json.dumps({
            "metric": "hlo_inspect_overhead",
            "best_of": 3,
            "steps_ratio_on_vs_off": round(ratio, 3),
            "overhead_pct": round(max(0.0, (1.0 - ratio)) * 100.0, 2),
            "hlo": hlo,
        }), flush=True)

    if args.wire_compression:
        elems = int(args.wire_mb * (1 << 20)) // 4
        base = run_wire_config("none", args.np, args.wire_steps, elems)
        comp = run_wire_config(args.wire_compression, args.np,
                               args.wire_steps, elems)
        print(json.dumps({
            "metric": "wire_compression",
            "codec": args.wire_compression,
            "xhost_bytes_ratio_vs_fp32": round(
                comp["xhost_bytes_per_step"]
                / max(base["xhost_bytes_per_step"], 1.0), 3),
            "wire_vs_raw_ratio": round(
                comp["xhost_bytes_per_step"]
                / max(comp["raw_xhost_bytes_per_step"], 1.0), 3),
            "max_abs_err": comp["max_abs_err"],
            "steps_ratio_vs_fp32": round(
                comp["steps_per_s"] / max(base["steps_per_s"], 1e-9), 3),
        }), flush=True)

    if args.device_codec or args.device_schedule:
        codec = args.device_codec or "int8"
        elems = int(args.device_mb * (1 << 20)) // 4
        dbase = run_device_config("none", args.device_steps, elems)
        dcomp = run_device_config(codec, args.device_steps, elems,
                                  schedule=args.device_schedule)
        assert dbase["device_raw_bytes_per_step"] == 0, \
            "baseline must not touch the device codec"
        print(json.dumps({
            "metric": "device_codec",
            "codec": codec,
            "schedule": args.device_schedule or "auto",
            "device_encoded_vs_raw_ratio": round(
                dcomp["device_encoded_bytes_per_step"]
                / max(dcomp["device_raw_bytes_per_step"], 1.0), 3),
            "max_abs_err": dcomp["max_abs_err"],
            "steps_ratio_vs_fp32": round(
                dcomp["steps_per_s"] / max(dbase["steps_per_s"], 1e-9), 3),
        }), flush=True)


if __name__ == "__main__":
    main()
