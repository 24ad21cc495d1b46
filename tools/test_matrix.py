#!/usr/bin/env python
"""Controller/config test matrix (reference analog: the docker-compose +
Buildkite matrix exercising framework x controller x device combos,
SURVEY.md §4.5).

Runs a canonical collective-correctness workload across every supported
combination of:

- core:    native (LocalController at np=1, socket controller at np>1)
           x pure-python (np=1 only — the fallback core's contract)
- np:      1, 2, 3
- fusion:  default threshold / disabled (HOROVOD_FUSION_THRESHOLD=0)
- cache:   default capacity / disabled (HOROVOD_CACHE_CAPACITY=0)
- plane:   shared-memory / pipelined TCP ring (HOROVOD_SHM_DISABLE=1),
           np>1 only / hierarchical (HOROVOD_HIERARCHICAL_ALLREDUCE=1 over
           two fake hosts via HOROVOD_HIER_FAKE_HOSTS=2), np>=3 only —
           smaller np degenerates to one rank per fake host
- wire:    none / bf16 / int8 (HOROVOD_WIRE_COMPRESSION) — codecs engage
           on the hier plane's cross-host leader ring; plus demotion
           combos where the knob is set on an all-local topology and the
           coordinator must turn it into a no-op
- metrics: off / on (HOROVOD_METRICS=1) — native-core combos appended to
           the full set; the workload asserts the registry populated
           (cycle occupancy, negotiation-wait histogram) when enabled
- ctrl_tree: auto (default) / on (HOROVOD_CONTROL_TREE, the leader
           tree) / d3 (tree forced three levels deep via
           HOROVOD_CONTROL_TREE_DEPTH=3 over three fake hosts, the v12
           adaptive-depth plane: coordinator <- super-leader <- leader) —
           "on"/"d3" combos run over fake hosts since auto stays flat
           below np=8; one on-combo and one d3-combo in the quick set,
           the rest (plus a single-host demotion row) full only
- flight:  def (ambient default) / on / off (HOROVOD_FLIGHT_RECORDER) —
           "on" combos assert the black box recorded the workload
           (hvd.flight_record() non-empty, right rank), "off" combos that
           it reports {}; one on-combo in the quick set
- autopilot: off / on (HOROVOD_AUTOPILOT=1) — "on" combos route through
           the elastic driver with the fleet-autopilot policy thread
           polling the coordinator; a healthy fleet must produce zero
           decisions and an unchanged workload result; one on-combo in
           the quick set
- qdev:    off / <codec>[:<schedule>] / demote (the
           HOROVOD_WIRE_COMPRESSION ``device=`` plane) — the in-jit
           block-scaled device ring, exercised over a forced 4-device CPU
           host platform; codec is int8 / int4, the optional
           schedule suffix pins HOROVOD_DEVICE_SCHEDULE (ring/bidi/torus).
           A codec value asserts the auto-dispatch engaged (byte counters
           moved, scale/2-bounded error — int4's bound is 127/7 wider),
           "demote" that the min-bytes floor keeps the codec cold and the
           result bit-identical to the plain collective; np=1 rows plus
           one cross-plane row (host bf16 x device int8); int8 and
           int4:bidi combos in the quick set
- migrate: off / on (HOROVOD_MIGRATE_REPLICAS) — "on" combos commit an
           elastic ObjectState and assert peer-shard replication landed
           the committed snapshot bit-exact on the ring successors' shard
           stores (docs/elastic.md "Zero-downtime migration"); one
           on-combo in the quick set
- trace:   def (ambient default: tracing on) / on / off
           (HOROVOD_STEP_TRACE) — "on" combos assert the causal step ring
           recorded the workload (completed steps with wall-clock bounds
           and a non-zero 5-phase breakdown; fleet attribution on the
           coordinator at np>1), "off" combos that hvd.step_trace()
           reports {}; one on-combo in the quick set
- fleet:   def (ambient default) / on / off (HOROVOD_FLEET_TELEMETRY,
           the v11 sketch sections; rides the metrics plane, so "on"
           combos force HOROVOD_METRICS=1) — "on" combos assert the
           coordinator's true fleet histograms populated
           (metrics()["fleet"]) and hvd.fleet_history() serves the
           fleethistory-v1 payload, "off" combos that both stay empty;
           one on-combo in the quick set
- dplane:  off / gspmd / diff (HOROVOD_DATA_PLANE, the gspmd
           compiler-inserted gradient-exchange plane over a forced
           4-device host) — "gspmd" asserts the env-plumbed request
           reaches the optimizer (ops/gspmd_plane.py selection counter)
           and a jitted train step runs; "diff" trains the same problem
           under the eager and gspmd calling conventions and asserts
           parity within fp32 reduction-order tolerance; the gspmd
           on-combo rides in the quick set
- hloinspect: def / on / off (HOROVOD_HLO_INSPECT, compiled-collective
           introspection over a forced 8-device host) — "on" runs a
           gspmd-plane train step through ops/hlo_inspect.instrument and
           asserts a non-empty collective inventory whose analytic byte
           totals match the live gspmd counters exactly; "off" asserts
           HOROVOD_HLO_INSPECT=0 returns the step unchanged (identity
           wrapper, zero per-step work) and every counter stays zero;
           the on-combo rides in the quick set

Plus non-workload check rows: `lint` (tools/hvd_lint.py — ABI/env/protocol
consistency, both sets), `lint-atomic`/`lint-lockorder`/`lint-sigsafe`
(the concurrency-discipline passes standalone via `--only`, both sets),
`fault-spec` (the HOROVOD_FAULT_INJECT parser
contract, both sets), and — full set only — the ASan/UBSan selftest
builds, the `chaos` fault-injection/fast-abort selftest, the np=4
fault-injection pytest (`fault-np4`: abort bound, corrupt-tag fail-fast,
elastic recovery under --fault-inject), the np=4 chaos-postmortem pytest
(`postmortem-np4`: injected death -> merged postmortem.json with the right
culprit within the abort bound), the np=4 hands-off autopilot chaos loop
(`autopilot-np4`: persistent injected straggle -> detect, evict, elastic
recovery, blacklist-expiry re-admission — zero human input), the np=4
zero-downtime migration chaos pytest (`migration-np4`: rank death ->
re-form np=3 resuming bit-identically from peer shards with zero
checkpoint reads -> blacklist-expiry re-grow to np=4, plus the degraded
checkpoint-fallback path), the np=4 live-cockpit attribution pytest
(`cockpit-np4`: injected coordinator-recv delay -> the live /state
snapshot AND tools/critical_path.py both name the delayed rank /
negotiation-wait), the np=4 anomaly-sentinel chaos pytest
(`sentinel-np4`: persistent injected delay on one rank -> sentinel
anomaly naming that rank, journaled and flight-recorded strictly before
the eviction rule can fire), the np=256 control-plane soak (`ctrl-soak`:
flat vs tree coordinator message counts, plus a migration-noting row),
the np=1024 / 64-fake-host pod-scale soak (`ctrl-soak-1024`: the
auto-grown three-level v12 tree holds coordinator inbound at O(fanout),
bucket-exact sketch merges, chaos arms at every tree level), the np=8
tree-vs-flat parity pytest (`ctrl-np8`), and the np=8 adaptive-depth
pytest (`ctrl-depth-np8`: flat == depth-2 == depth-3 parity plus the
super-leader-death abort bound).

Usage:
    python tools/test_matrix.py              # full matrix
    python tools/test_matrix.py --quick      # one combo per axis value

Prints one PASS/FAIL line per combination and exits nonzero if any fail.
"""

from __future__ import annotations

import argparse
import itertools
import os
import subprocess
import sys
import tempfile
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPP_DIR = os.path.join(REPO, "horovod_tpu", "cpp")

WORKLOAD = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()

    # allreduce ops + dtypes
    x = np.full(33, float(r + 1), np.float32)
    total = s * (s + 1) / 2.0
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Sum, name="m.sum"),
                               total)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Average, name="m.avg"),
                               total / s)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Min, name="m.min"), 1.0)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Max, name="m.max"),
                               float(s))
    v = (np.arange(6) + r).astype(np.int64)
    expected = sum((np.arange(6) + rr) for rr in range(s))
    np.testing.assert_array_equal(hvd.allreduce(v, op=hvd.Sum, name="m.i64"),
                                  expected)

    # fusion sweep: many small tensors in one window
    handles = [hvd.allreduce_async(np.full(8, float(i + r), np.float32),
                                   op=hvd.Sum, name=f"m.f.{i}")
               for i in range(40)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(hvd.synchronize(h),
                                   s * i + s * (s - 1) / 2.0)

    # cache steady state: identical negotiation repeated
    for it in range(20):
        out = hvd.allreduce(np.full(16, float(r), np.float32), op=hvd.Sum,
                            name="m.cached")
        np.testing.assert_allclose(out, s * (s - 1) / 2.0)

    # ragged allgather
    g = np.asarray(hvd.allgather(np.full((r + 1, 2), float(r), np.float32),
                                 name="m.ag"))
    assert g.shape == (s * (s + 1) // 2, 2), g.shape

    # broadcast from every root
    for root in range(s):
        out = hvd.broadcast(np.full(5, float(r), np.float64), root_rank=root,
                            name=f"m.bc.{root}")
        np.testing.assert_allclose(out, float(root))

    # equal-splits alltoall
    data = (np.arange(2 * s, dtype=np.float32) + 10 * r).reshape(2 * s, 1)
    out, _ = hvd.alltoall(data, splits=[2] * s, name="m.a2a")
    assert np.asarray(out).shape == (2 * s, 1)

    # process set (channel + lane + per-set plane)
    if s >= 2:
        ps = hvd.add_process_set(list(range(s - 1)))
        if r < s - 1:
            out = hvd.allreduce(np.full(7, float(r + 1), np.float32),
                                op=hvd.Sum, process_set=ps, name="m.ps")
            np.testing.assert_allclose(out, (s - 1) * s / 2.0)

    # big fp32 payload above the wire-compression floor: rides the codec
    # on cross-host topologies (tolerance keyed off the knob; the small
    # tensors above stay under the floor, so their exact asserts hold).
    wire = os.environ.get("HOROVOD_WIRE_COMPRESSION", "none")
    if "=" in wire:  # per-plane syntax: the host ring takes the host= entry
        wire = dict(kv.split("=", 1)
                    for kv in wire.split(",")).get("host", "none")
    wtol = {"bf16": dict(rtol=0.04, atol=1e-3),
            "int8": dict(rtol=0.05, atol=6.0)}.get(wire, dict(rtol=1e-6))
    big = ((np.arange(1 << 16) % 251) + r).astype(np.float32)
    wexp = sum(((np.arange(1 << 16) % 251) + rr).astype(np.float32)
               for rr in range(s))
    np.testing.assert_allclose(hvd.allreduce(big, op=hvd.Sum, name="m.wire"),
                               wexp, **wtol)

    # qdev axis: the in-jit device-plane ring (HOROVOD_WIRE_COMPRESSION
    # device=<codec>) over the forced multi-device host platform.  A codec
    # value ("int8" / "int4", optional ":<schedule>" suffix) must
    # engage the auto-dispatch (byte counters move) within the codec's
    # scale/2 error bound; "demote" pins the min-bytes floor: codec stays
    # cold and the result is bit-identical to the plain collective.
    qdev = os.environ.get("HVD_MATRIX_QDEV", "off")
    if qdev != "off":
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        import horovod_tpu.ops.quantize as qz
        devs = jax.devices()
        assert len(devs) >= 2, "qdev combo expects a forced multi-dev host"
        mesh = Mesh(np.asarray(devs), ("q",))

        def _smap(fn):
            return shard_map(fn, mesh=mesh, in_specs=P("q"),
                             out_specs=P("q"), check_vma=False)

        qx = ((np.arange(len(devs) * 4096) % 509) / 509.0 - 0.5) \\
            .astype(np.float32).reshape(len(devs), 4096)
        qz.reset_device_byte_counters()
        qout = np.asarray(jax.jit(_smap(
            lambda shard: hvd.allreduce(shard, axis_name="q")))(
                jnp.asarray(qx)))
        qraw, qenc = qz.device_byte_counters()
        qmean = np.broadcast_to(qx.mean(axis=0), qx.shape)
        if qdev != "demote":
            qcodec = qdev.split(":", 1)[0]
            assert qraw > 0 and qenc < qraw, (qraw, qenc)
            # int4's scale/2 is 127/7 ≈ 18x the int8 one; 2.0 covers it
            # with slack while staying far under the signal's magnitude.
            qbound = {"int4": 2.0}.get(qcodec, 0.5) / len(devs)
            qerr = float(np.max(np.abs(qout - qmean)))
            assert qerr < qbound, (qcodec, qerr, qbound)
        else:  # demote
            assert (qraw, qenc) == (0, 0), (qraw, qenc)
            import jax.lax as lax
            qplain = np.asarray(jax.jit(_smap(
                lambda shard: lax.pmean(shard, "q")))(jnp.asarray(qx)))
            np.testing.assert_array_equal(qout, qplain)

    # dplane axis: the gspmd data plane (HOROVOD_DATA_PLANE / the
    # DistributedOptimizer plane= knob) over the forced multi-device host
    # platform.  "gspmd" asserts the env-plumbed request reaches the
    # optimizer (selection counter moves) and a jitted train step runs;
    # "diff" trains the same problem under both planes and asserts parity
    # within fp32 reduction-order tolerance.
    dplane = os.environ.get("HVD_MATRIX_DPLANE", "off")
    if dplane != "off":
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from horovod_tpu.ops import gspmd_plane as gp
        from horovod_tpu.optimizer import DistributedOptimizer

        devs = jax.devices()
        assert len(devs) >= 2, "dplane combo expects a forced multi-dev host"
        drs = np.random.RandomState(7)
        dx = drs.randn(8 * len(devs), 4).astype(np.float32)
        dy = drs.randn(8 * len(devs)).astype(np.float32)
        dp0 = {"w": np.zeros(4, np.float32), "b": np.float32(0.0)}

        def dloss(p, xs, ys):
            return jnp.mean((xs @ p["w"] + p["b"] - ys) ** 2)

        def train_gspmd(tx):
            mesh = gp.build_gspmd_mesh()
            xs = jax.device_put(jnp.asarray(dx),
                                NamedSharding(mesh, P(gp.BATCH_AXIS)))
            ys = jax.device_put(jnp.asarray(dy),
                                NamedSharding(mesh, P(gp.BATCH_AXIS)))
            p = jax.tree_util.tree_map(jnp.asarray, dp0)
            st = tx.init(p)

            @jax.jit
            def step(p, st, xs, ys):
                g = jax.grad(dloss)(p, xs, ys)
                u, st2 = tx.update(g, st, p)
                return optax.apply_updates(p, u), st2

            for _ in range(3):
                p, st = step(p, st, xs, ys)
            return p

        gp.reset_plane_counters()
        if dplane == "gspmd":
            # plane unset: HOROVOD_DATA_PLANE=gspmd must have ridden
            # env.py -> Config -> data_plane_default into the optimizer.
            pg = train_gspmd(DistributedOptimizer(optax.sgd(0.1)))
            dc = gp.plane_counters()
            assert dc.get("gspmd") == 1, dc
            assert np.isfinite(np.asarray(pg["w"])).all()
        else:  # diff: eager-vs-gspmd differential parity
            pg = train_gspmd(DistributedOptimizer(optax.sgd(0.1),
                                                  plane="gspmd"))
            emesh = Mesh(np.asarray(devs), ("dpx",))
            tx_e = DistributedOptimizer(optax.sgd(0.1), plane="eager",
                                        axis_name="dpx")

            def eshard(p, st, xs, ys):
                g = jax.grad(dloss)(p, xs, ys)
                u, st2 = tx_e.update(g, st, p)
                return optax.apply_updates(p, u), st2

            especs = dict(mesh=emesh, in_specs=(P(), P(), P("dpx"),
                                                P("dpx")),
                          out_specs=(P(), P()))
            esm = shard_map(eshard, check_vma=False, **especs)
            estep = jax.jit(esm)
            pe = jax.tree_util.tree_map(jnp.asarray, dp0)
            ste = tx_e.init(pe)
            for _ in range(3):
                pe, ste = estep(pe, ste, jnp.asarray(dx), jnp.asarray(dy))
            np.testing.assert_allclose(np.asarray(pg["w"]),
                                       np.asarray(pe["w"]),
                                       rtol=2e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(pg["b"]),
                                       np.asarray(pe["b"]),
                                       rtol=2e-6, atol=1e-7)

    # hloinspect axis: compiled-collective introspection — a gspmd-plane
    # train step through ops/hlo_inspect.instrument must yield a
    # non-empty inventory whose analytic byte totals match the live
    # counters exactly; "off" asserts HOROVOD_HLO_INSPECT=0 makes
    # instrument the identity (same object back, counters untouched).
    hli = os.environ.get("HVD_MATRIX_HLOINSPECT", "def")
    if hli != "def":
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from horovod_tpu.ops import gspmd_plane as gp
        from horovod_tpu.ops import hlo_inspect as hi
        from horovod_tpu.optimizer import DistributedOptimizer

        devs = jax.devices()
        assert len(devs) >= 2, "hloinspect combo expects a multi-dev host"
        hi.reset()
        hmesh = gp.build_gspmd_mesh()
        hn = hmesh.shape[gp.BATCH_AXIS] * 4
        hrs = np.random.RandomState(11)
        hx = jax.device_put(jnp.asarray(hrs.randn(hn, 4), jnp.float32),
                            NamedSharding(hmesh, P(gp.BATCH_AXIS)))
        hy = jax.device_put(jnp.asarray(hrs.randn(hn), jnp.float32),
                            NamedSharding(hmesh, P(gp.BATCH_AXIS)))
        hp = {"w": jnp.zeros((4,), jnp.float32)}
        htx = DistributedOptimizer(optax.sgd(0.1), plane="gspmd")
        hst = htx.init(hp)

        def hstep(p, st, xs, ys):
            def hl(p):
                return jnp.mean((xs @ p["w"] - ys) ** 2)
            g = jax.grad(hl)(p)
            u, st2 = htx.update(g, st, p)
            return optax.apply_updates(p, u), st2

        hbase = jax.jit(hstep)
        hwrapped = hi.instrument(hbase, label="matrix")
        if hli == "on":
            hp, hst = hwrapped(hp, hst, hx, hy)
            jax.block_until_ready(hp)
            hinvs = [i for i in hi.inventories() if i.label == "matrix"]
            assert hinvs, "gspmd trace yielded no collective inventory"
            hinv = hinvs[-1]
            assert hinv.collectives > 0, hinv.to_dict()
            hraw, hwire = hi.gspmd_byte_counters()
            assert (hinv.raw_bytes, hinv.wire_bytes) == (hraw, hwire), \
                (hinv.raw_bytes, hinv.wire_bytes, hraw, hwire)
        else:  # off: zero-overhead contract — the identity wrapper
            assert hwrapped is hbase, \
                "HOROVOD_HLO_INSPECT=0 must return the step unchanged"
            hp, hst = hwrapped(hp, hst, hx, hy)
            jax.block_until_ready(hp)
            assert hi.inventories() == [], "introspection off but recorded"
            assert hi.gspmd_byte_counters() == (0, 0)

    # flight axis: the always-on black box must have recorded the work
    # (ctrl frames exist at np>1 only; np=1 has no socket control plane).
    fl = os.environ.get("HOROVOD_FLIGHT_RECORDER", "")
    if fl == "1" and s > 1:
        fr = hvd.flight_record()
        assert fr.get("events"), fr
        assert fr.get("rank") == r, fr
        assert fr.get("types"), fr
    elif fl == "off":
        assert hvd.flight_record() == {}, "recorder off but ring non-empty"

    # migrate axis: a committed elastic state must land, bit-exact, on the
    # ring successors' shard stores via the data-plane replication path.
    if os.environ.get("HVD_MATRIX_MIGRATE") == "on" and s > 1:
        import pickle
        from horovod_tpu.elastic import migrate as mig

        est = hvd.elastic.ObjectState(
            step=0, w=np.full(4, float(r), np.float32))
        est.step = 1
        est.commit()
        st = mig.store()
        assert st.own is not None and st.own.owner == r, (r, st.own)
        assert len(st.peers) >= min(2, s - 1), sorted(st.peers)
        pred = (r - 1) % s
        recs = [p for p in st.peers.values() if p.owner == pred]
        assert recs, sorted(st.peers)
        attrs = pickle.loads(recs[0].data)["attrs"]
        assert attrs["step"] == 1, attrs
        np.testing.assert_array_equal(
            attrs["w"], np.full(4, float(pred), np.float32))

    # trace axis: the causal step ring must carry the work done above —
    # completed steps with wall-clock bounds and the 5-phase breakdown,
    # plus the coordinator's fleet attribution at np>1.
    tr = os.environ.get("HOROVOD_STEP_TRACE", "")
    if tr == "1":
        t = hvd.step_trace()
        assert t.get("completed", 0) > 0, t
        assert t["phases"] == ["negotiation_wait", "fusion", "ring",
                               "fence", "idle"], t["phases"]
        assert t["steps"] and all(len(row) == 9 and row[2] >= row[1] > 0
                                  for row in t["steps"]), t["steps"][:3]
        assert any(sum(row[3:8]) > 0 for row in t["steps"]), t["steps"][:3]
        if r == 0 and s > 1:
            assert t["fleet"], "coordinator recorded no fleet attribution"
    elif tr == "0":
        assert hvd.step_trace() == {}, "tracing off but ring non-empty"

    # metrics axis: the registry must have seen the work done above.
    if os.environ.get("HOROVOD_METRICS") == "1":
        m = hvd.metrics()
        assert m.get("enabled"), m
        assert m["counters"]["cycle_count"] > 0, m["counters"]
        assert m["histograms"]["negotiation_wait_us"]["count"] > 0, \
            m["histograms"]
        assert hvd.metrics_prometheus().startswith("# HELP")

    # fleet axis: the v11 sketch sections must have landed true fleet
    # histograms on the coordinator, and the history endpoint must serve
    # the fleethistory-v1 payload; "off" keeps both surfaces empty.
    ft = os.environ.get("HOROVOD_FLEET_TELEMETRY", "")
    if ft == "1":
        if r == 0:
            fleet = hvd.metrics().get("fleet") or {}
            assert fleet.get("negotiation_wait_us", {}).get("count", 0) > 0, \
                fleet
            fh = hvd.fleet_history()
            assert fh.get("schema") == "fleethistory-v1", fh
            assert fh.get("tiers"), fh
    elif ft == "0":
        assert "fleet" not in (hvd.metrics() or {}), \
            "fleet telemetry off but metrics carries a fleet section"
        assert hvd.fleet_history() == {}, \
            "fleet telemetry off but history non-empty"

    hvd.barrier()
    hvd.shutdown()
    print(f"WORKLOAD-OK rank={r}", flush=True)
""")


TORCH_WORKLOAD = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import torch
    import horovod_tpu.torch as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()

    x = torch.full((33,), float(r + 1))
    total = s * (s + 1) / 2.0
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Sum, name="m.sum").numpy(), total)
    np.testing.assert_allclose(
        hvd.allreduce_(x.clone(), op=hvd.Average, name="m.avg").numpy(),
        total / s)

    # fusion sweep through the grad-hook shape: many small in-place ops
    ts = [torch.full((8,), float(i + r)) for i in range(40)]
    handles = [hvd.allreduce_async_(t, op=hvd.Sum, name=f"m.f.{i}")
               for i, t in enumerate(ts)]
    for i, h in enumerate(handles):
        hvd.synchronize(h)
        np.testing.assert_allclose(ts[i].numpy(),
                                   s * i + s * (s - 1) / 2.0)

    # cache steady state
    for it in range(20):
        out = hvd.allreduce(torch.full((16,), float(r)), op=hvd.Sum,
                            name="m.cached")
        np.testing.assert_allclose(out.numpy(), s * (s - 1) / 2.0)

    # ragged allgather + broadcast + equal-splits alltoall
    g = hvd.allgather(torch.full((r + 1, 2), float(r)), name="m.ag")
    assert tuple(g.shape) == (s * (s + 1) // 2, 2), g.shape
    for root in range(s):
        out = hvd.broadcast(torch.full((5,), float(r), dtype=torch.float64),
                            root_rank=root, name=f"m.bc.{root}")
        np.testing.assert_allclose(out.numpy(), float(root))
    data = (torch.arange(2 * s, dtype=torch.float32) + 10 * r).reshape(-1, 1)
    out, _ = hvd.alltoall(data, splits=[2] * s, name="m.a2a")
    assert tuple(out.shape) == (2 * s, 1)

    # big fp32 payload above the wire-compression floor (see jax workload).
    wire = os.environ.get("HOROVOD_WIRE_COMPRESSION", "none")
    if "=" in wire:  # per-plane syntax: the host ring takes the host= entry
        wire = dict(kv.split("=", 1)
                    for kv in wire.split(",")).get("host", "none")
    wtol = {"bf16": dict(rtol=0.04, atol=1e-3),
            "int8": dict(rtol=0.05, atol=6.0)}.get(wire, dict(rtol=1e-6))
    big = torch.remainder(torch.arange(1 << 16, dtype=torch.float32),
                          251.0) + r
    wexp = sum((np.arange(1 << 16) % 251 + rr).astype(np.float32)
               for rr in range(s))
    np.testing.assert_allclose(
        hvd.allreduce(big, op=hvd.Sum, name="m.wire").numpy(), wexp, **wtol)

    hvd.barrier()
    hvd.shutdown()
    print(f"WORKLOAD-OK rank={r}", flush=True)
""")


def combos(quick: bool):
    cores = ["native", "purepy"]
    nps = [1, 2, 3]
    fusion = ["on", "off"]
    cache = ["on", "off"]
    planes = ["shm", "tcp", "hier"]
    wires = ["none", "bf16", "int8"]
    if quick:
        # One covering set instead of the full product (every axis value
        # appears; hier+none pairing is covered by tests/parallel).  The
        # metrics axis stays "off" here — its on-combos live in the full
        # set and tests/parallel/test_metrics.py covers the plane directly.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "off")
        # Same-host links: the coordinator must demote the codec (knob
        # harmless, results exact).
        yield ("jax", "native", 2, "off", "off", "tcp", "bf16", "off")
        yield ("jax", "native", 3, "on", "off", "tcp", "none", "off")
        yield ("jax", "native", 3, "on", "on", "hier", "bf16", "off")
        yield ("jax", "native", 3, "on", "off", "hier", "int8", "off")
        # ctrl_tree axis: the one quick on-combo (2 fake hosts via hier)
        # plus the forced depth-3 combo (3 fake hosts; the v12 chain
        # coordinator <- super <- leader carries every frame).
        yield ("jax", "native", 3, "on", "on", "hier", "none", "off", "on")
        yield ("jax", "native", 3, "on", "on", "hier", "none", "off", "d3")
        # flight axis: the one quick recorder-on combo.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
               "on")
        # autopilot axis: the one quick on-combo — elastic driver + policy
        # thread over a healthy fleet; zero decisions, same results.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
               "def", "on")
        # qdev axis: the quick device-codec combos (forced 4-dev host) —
        # the int8 baseline plus one new-codec/new-schedule row.
        yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "int8")
        yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "int4:bidi")
        # dplane axis: the one quick gspmd on-combo — HOROVOD_DATA_PLANE
        # plumbed env -> Config -> optimizer over a forced 4-dev host.
        yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "off", "off", "def", "def", "gspmd")
        # hloinspect axis: the one quick on-combo — a gspmd trace's
        # inventory matching the live byte counters bit-for-bit.
        yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "off", "off", "def", "def", "off", "on")
        # migrate axis: the one quick on-combo — peer-shard replication
        # rides a committed elastic state over the shm data plane.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "off", "on")
        # trace axis: the one quick on-combo — the step ring populated
        # with fleet attribution on the coordinator.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
               "def", "off", "off", "off", "on")
        # fleet axis: the one quick on-combo — v11 sketch sections summed
        # into coordinator fleet histograms + the history payload served.
        yield ("jax", "native", 3, "on", "on", "shm", "none", "on", "auto",
               "def", "off", "off", "off", "def", "on")
        yield ("jax", "native", 1, "on", "off", "shm", "none", "off")
        yield ("jax", "purepy", 1, "off", "on", "shm", "none", "off")
        yield ("torch", "native", 2, "on", "on", "shm", "none", "off")
        yield ("torch", "native", 3, "off", "off", "tcp", "none", "off")
        yield ("torch", "purepy", 1, "on", "on", "shm", "none", "off")
        return
    for core, np_, f, c, p, w in itertools.product(cores, nps, fusion,
                                                   cache, planes, wires):
        if core == "purepy" and np_ > 1:
            continue  # pure-python core is single-process by contract
        if np_ == 1 and p != "shm":
            continue  # no data plane at np=1; plane axis is meaningless
        if p == "hier" and np_ < 3:
            continue  # 2 ranks / 2 fake hosts has no multi-rank host
        if w != "none" and (p != "hier" or core != "native"):
            continue  # codec engages only on cross-host hops (leader ring)
        yield ("jax", core, np_, f, c, p, w, "off")
    # Demotion coverage: codec requested on an all-local flat ring.
    yield ("jax", "native", 2, "on", "on", "tcp", "bf16", "off")
    yield ("jax", "native", 3, "on", "on", "shm", "int8", "off")
    # Metrics-axis coverage: registry populated across controller shapes
    # (local np=1, socket, hierarchical) without disturbing the results.
    yield ("jax", "native", 1, "on", "on", "shm", "none", "on")
    yield ("jax", "native", 3, "on", "on", "shm", "none", "on")
    yield ("jax", "native", 3, "off", "off", "tcp", "none", "on")
    yield ("jax", "native", 3, "on", "on", "hier", "bf16", "on")
    # Control-tree axis: v9 leader tree forced on over fake hosts ("auto"
    # stays flat below np=8), with caching/fusion/metrics variation, plus
    # a single-host demotion row (tree=on without multiple hosts must
    # quietly stay flat and change nothing).
    yield ("jax", "native", 3, "on", "on", "hier", "none", "off", "on")
    yield ("jax", "native", 3, "off", "off", "hier", "none", "on", "on")
    yield ("jax", "native", 3, "on", "on", "hier", "bf16", "off", "on")
    yield ("jax", "native", 3, "on", "on", "tcp", "none", "off", "on")
    yield ("torch", "native", 3, "on", "on", "hier", "none", "off", "on")
    # Adaptive-depth (v12) rows: the forced depth-3 chain with metrics on
    # (telemetry sketches relayed through the super-leader) and with
    # caching/fusion off (every cycle renegotiates through two hops).
    yield ("jax", "native", 3, "on", "on", "hier", "none", "on", "d3")
    yield ("jax", "native", 3, "off", "off", "hier", "none", "off", "d3")
    # Flight-recorder axis: explicit on (black box populated) across plane
    # shapes including the v9 tree, and explicit off (flight_record == {}).
    yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
           "on")
    yield ("jax", "native", 3, "off", "off", "tcp", "none", "on", "auto",
           "on")
    yield ("jax", "native", 3, "on", "on", "hier", "none", "off", "on",
           "on")
    yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
           "off")
    # Autopilot axis: policy thread over a healthy fleet (no decisions),
    # with and without the flat-TCP plane; the adversarial (straggling)
    # path is the autopilot-np4 check row.
    yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
           "def", "on")
    yield ("jax", "native", 3, "off", "off", "tcp", "none", "off", "auto",
           "def", "on")
    # qdev axis: in-jit device-plane codec over a forced 4-device host
    # platform — engagement (counters move, bounded error), purepy parity
    # (the device ring is pure jax; it must not care which core runs the
    # host plane), one cross-plane combo (host bf16 leader ring + device
    # int8 ring in the same process), and the min-bytes demotion (codec
    # configured but cold, bit-identical result).
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "int8")
    yield ("jax", "purepy", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "int8")
    yield ("jax", "native", 3, "on", "on", "hier", "bf16", "off", "auto",
           "def", "off", "int8")
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "demote")
    # int4 (nibble-packed, coarser bound) and the schedule suffix pinning
    # the bidi and torus rings — 4 forced devices factor as 2x2, exercising
    # the torus demotion-to-bidi rule as well as the explicit bidi path.
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "int4")
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "int8:bidi")
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "int4:torus")
    # dplane axis: the gspmd data plane over a forced 4-device host — the
    # env-plumbed engagement row (HOROVOD_DATA_PLANE=gspmd reaches the
    # optimizer, selection counter moves) and the eager-vs-gspmd
    # differential row (same problem trained under both calling
    # conventions, parity within fp32 reduction-order tolerance).
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "off", "def", "def", "gspmd")
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "off", "def", "def", "diff")
    # hloinspect axis: compiled-collective introspection on (a gspmd
    # trace's inventory matches the live counters exactly) and explicitly
    # off (instrument is the identity, counters stay zero).
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "off", "def", "def", "off", "on")
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "off", "def", "def", "off", "off")
    # Migrate axis: replication across the plane shapes the shards actually
    # ride in production — shm, the flat TCP ring, and the hier topology —
    # plus a metrics-on row so the hvd_migrate_* counters are scraped live.
    yield ("jax", "native", 3, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "on")
    yield ("jax", "native", 2, "on", "on", "tcp", "none", "off", "auto",
           "def", "off", "off", "on")
    yield ("jax", "native", 3, "on", "on", "hier", "none", "on", "auto",
           "def", "off", "off", "on")
    # Trace axis: explicit on across controller shapes — local np=1, the
    # socket controller, and the v9 tree over fake hosts — plus a
    # metrics-on row (the CYCLE trailer carries both the metrics and the
    # step-trace sections, marker 2) and explicit off (step_trace == {}).
    yield ("jax", "native", 1, "on", "on", "shm", "none", "off", "auto",
           "def", "off", "off", "off", "on")
    yield ("jax", "native", 3, "on", "on", "shm", "none", "on", "auto",
           "def", "off", "off", "off", "on")
    yield ("jax", "native", 3, "on", "on", "hier", "none", "off", "on",
           "def", "off", "off", "off", "on")
    yield ("jax", "native", 3, "off", "off", "tcp", "none", "off", "auto",
           "def", "off", "off", "off", "off")
    # Fleet-telemetry axis: v11 sketch sections across controller shapes —
    # flat shm, the flat TCP ring, and the v9 leader tree (host-summed
    # sketches up the tree) — plus explicit off (no fleet section in the
    # metrics dump, empty history payload).
    yield ("jax", "native", 3, "on", "on", "shm", "none", "on", "auto",
           "def", "off", "off", "off", "def", "on")
    yield ("jax", "native", 3, "off", "off", "tcp", "none", "on", "auto",
           "def", "off", "off", "off", "def", "on")
    yield ("jax", "native", 3, "on", "on", "hier", "none", "on", "on",
           "def", "off", "off", "off", "def", "on")
    yield ("jax", "native", 3, "on", "on", "shm", "none", "on", "auto",
           "def", "off", "off", "off", "def", "off")
    # Torch-binding covering subset (same core spine underneath; a full
    # product would double the wall time for little marginal coverage).
    yield ("torch", "native", 2, "on", "on", "shm", "none", "off")
    yield ("torch", "native", 2, "off", "off", "tcp", "none", "off")
    yield ("torch", "native", 2, "on", "off", "tcp", "none", "off")
    yield ("torch", "native", 3, "on", "on", "tcp", "none", "off")
    yield ("torch", "native", 3, "off", "on", "shm", "none", "off")
    yield ("torch", "native", 3, "on", "on", "hier", "none", "off")
    yield ("torch", "native", 3, "on", "on", "hier", "bf16", "off")
    yield ("torch", "native", 3, "on", "on", "hier", "int8", "off")
    yield ("torch", "native", 1, "on", "on", "shm", "none", "off")
    yield ("torch", "purepy", 1, "on", "on", "shm", "none", "off")


def checks(quick: bool):
    """Non-workload rows: static analysis, the sanitizer builds, and the
    fault axis.

    Yields (name, [argv, ...], cwd[, timeout]) — the argvs run in
    sequence, all must exit 0.  `lint` is pure text analysis (no build)
    and belongs in the quick set, as does `fault-spec` (the parser
    contract the quick chaos story rests on); the sanitizer rows compile
    the whole controller stack (~1 min each on a laptop core), and the
    chaos/np=4 fault rows exercise whole-job collapse, so full matrix
    only.
    """
    yield ("lint",
           [[sys.executable, os.path.join(REPO, "tools", "hvd_lint.py")]],
           REPO)
    # The concurrency-discipline passes also run standalone so a failure
    # is attributed to the discipline that broke (atomic memory-order
    # audit / lock-order cycles / async-signal-safety), not just "lint".
    for cpass in ("atomic", "lockorder", "sigsafe"):
        yield (f"lint-{cpass}",
               [[sys.executable, os.path.join(REPO, "tools", "hvd_lint.py"),
                 "--only", cpass]],
               REPO)
    yield ("fault-spec",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "single", "test_fault_spec.py")]],
           REPO)
    if quick:
        return
    for target in ("asan_selftest", "ubsan_selftest"):
        yield (target.split("_")[0],
               [["make", target], [os.path.join(CPP_DIR, target)]],
               CPP_DIR)
    yield ("chaos",
           [["make", "chaos_selftest"],
            [os.path.join(CPP_DIR, "chaos_selftest")]],
           CPP_DIR)
    # Whole-job collapse measured from Python: injected rank death within
    # the abort bound, corrupt-tag fail-fast, elastic --fault-inject
    # recovery.  Three multi-process scenarios: give them their own cap.
    yield ("fault-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_fault_injection.py")]],
           REPO, 600.0)
    # Chaos-postmortem: an injected rank death must leave a complete
    # merged postmortem.json (right culprit, a pre-abort digest from every
    # survivor) without stretching the abort bound.
    yield ("postmortem-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_postmortem.py")]],
           REPO, 600.0)
    # Hands-off autopilot chaos loop: one rank persistently straggles
    # (injected delay) -> the autopilot detects, attributes, evicts, the
    # elastic driver recovers at smaller np, and blacklist expiry
    # re-admits the host -- asserted end to end with zero human input.
    yield ("autopilot-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_autopilot.py")]],
           REPO, 600.0)
    # Zero-downtime migration chaos: injected rank death -> fast abort ->
    # re-form np=3 resuming bit-identically from peer shards (zero
    # checkpoint reads) -> blacklist-expiry re-grow to np=4; plus the
    # degraded path (replicas lost -> sharded-checkpoint fallback).
    yield ("migration-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_migration.py")]],
           REPO, 600.0)
    # Live cockpit + critical path at np=4: an injected coordinator-recv
    # delay against rank 3 must be attributed to rank 3 / negotiation_wait
    # by BOTH surfaces — the live /state snapshot queried mid-run and
    # tools/critical_path.py over the shutdown step-trace dumps.
    yield ("cockpit-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_step_trace.py")]],
           REPO, 600.0)
    # Anomaly sentinel end to end at np=4: a persistent injected delay on
    # one rank must raise a sentinel anomaly (flight type 15 + the
    # autopilot journal) naming that rank strictly BEFORE the
    # eviction-windows rule can fire, with /history showing the
    # inflection; includes the fleet bucket-exactness assertions.
    yield ("sentinel-np4",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_fleet_telemetry.py")]],
           REPO, 600.0)
    # np=256 in-process control-plane soak: flat vs v9 tree coordinator
    # message counts (>= 8x cut at 256 ranks / 16 fake hosts) plus the
    # sharded rendezvous acceptors under the full HELLO herd.
    yield ("ctrl-soak",
           [["make", "ctrl_soak_selftest"],
            [os.path.join(CPP_DIR, "ctrl_soak_selftest")]],
           CPP_DIR, 600.0)
    # np=1024 / 64-fake-host pod-scale soak (v12): the auto-grown
    # three-level tree cuts coordinator inbound to O(fanout) (17 msgs per
    # cycle vs 1023 flat), bucket-exact sketch merges, and the chaos arms
    # (super-leader death, mid-level leader death, adaptive-depth site)
    # abort within the bound naming the right culprit.
    yield ("ctrl-soak-1024",
           [["make", "ctrl_soak_selftest"],
            ["env", "CTRL_SOAK_NP=1024", "CTRL_SOAK_HOSTS=64",
             os.path.join(CPP_DIR, "ctrl_soak_selftest")]],
           CPP_DIR, 600.0)
    # np=8 fake-host end-to-end: tree-vs-flat collective/attribution
    # parity and leader-death abort bounds.
    yield ("ctrl-np8",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel", "test_ctrl_tree_np8.py")]],
           REPO, 600.0)
    # np=8 adaptive-depth end-to-end: flat == depth-2 == depth-3 parity
    # and the super-leader-death abort bound (v12).
    yield ("ctrl-depth-np8",
           [[sys.executable, "-m", "pytest", "-q",
             os.path.join("tests", "parallel",
                          "test_ctrl_tree_depth.py")]],
           REPO, 600.0)


def run_check(cmds, cwd: str, timeout: float) -> tuple:
    t0 = time.monotonic()
    for cmd in cmds:
        try:
            proc = subprocess.run(cmd, cwd=cwd, capture_output=True,
                                  text=True, timeout=timeout)
        except subprocess.TimeoutExpired as exc:
            return False, time.monotonic() - t0, f"timeout: {exc}"
        if proc.returncode != 0:
            return False, time.monotonic() - t0, \
                (proc.stdout + proc.stderr)[-800:]
    return True, time.monotonic() - t0, ""


def run_combo(core: str, np_: int, fusion: str, cache: str,
              plane: str, wire: str, metrics: str, tree: str, flight: str,
              autopilot: str, qdev: str, migrate: str, trace: str,
              fleet: str, dplane: str, hloinspect: str, script: str,
              timeout: float) -> tuple:
    env = dict(os.environ)
    env.pop("HOROVOD_HIERARCHICAL_ALLREDUCE", None)
    env.pop("HOROVOD_HIER_FAKE_HOSTS", None)
    # Same for the wire axis: ambient codec settings would skew both the
    # exact asserts (wire=none combos) and the demotion combos.
    env.pop("HOROVOD_WIRE_COMPRESSION", None)
    env.pop("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", None)
    # And the metrics axis: an ambient HOROVOD_METRICS_FILE would make
    # every combo write snapshot files (and "off" combos assert nothing).
    env.pop("HOROVOD_METRICS", None)
    env.pop("HOROVOD_METRICS_FILE", None)
    env.pop("HOROVOD_METRICS_INTERVAL", None)
    # An ambient fault-injection spec would sabotage every workload combo
    # (that's its job); faults belong to the dedicated check rows only.
    env.pop("HOROVOD_FAULT_INJECT", None)
    # The ctrl_tree axis owns the control-plane topology knobs (v12:
    # depth/fanout shape the tree, so ambient values would change every
    # combo's frame routing).
    env.pop("HOROVOD_CONTROL_TREE", None)
    env.pop("HOROVOD_CONTROL_TREE_DEPTH", None)
    env.pop("HOROVOD_CTRL_TREE_FANOUT", None)
    # The flight axis owns the recorder knobs; an ambient postmortem dir
    # would scatter crash bundles on every combo failure.
    env.pop("HOROVOD_FLIGHT_RECORDER", None)
    env.pop("HOROVOD_FLIGHT_RECORDER_SLOTS", None)
    env.pop("HOROVOD_POSTMORTEM_DIR", None)
    # The autopilot axis owns the policy-engine knob (and its port is
    # per-generation driver state, never ambient).
    env.pop("HOROVOD_AUTOPILOT", None)
    env.pop("HOROVOD_AUTOPILOT_PORT", None)
    # The migrate axis owns the replication knobs: an ambient setting
    # would make every combo pay the replication alltoall per commit.
    env.pop("HOROVOD_MIGRATE_REPLICAS", None)
    env.pop("HOROVOD_MIGRATE_INTERVAL_STEPS", None)
    # The trace axis owns the step-trace knobs, and the cockpit binds a
    # listener — an ambient HOROVOD_COCKPIT would open a port per combo.
    env.pop("HOROVOD_STEP_TRACE", None)
    env.pop("HOROVOD_STEP_TRACE_SLOTS", None)
    env.pop("HOROVOD_COCKPIT", None)
    env.pop("HOROVOD_COCKPIT_PORT", None)
    # The fleet axis owns the v11 telemetry knobs; an ambient sentinel
    # threshold would skew the anomaly-free expectation of "on" combos.
    env.pop("HOROVOD_FLEET_TELEMETRY", None)
    env.pop("HOROVOD_SENTINEL_ZSCORE", None)
    # The dplane axis owns the data-plane knob: an ambient gspmd request
    # would reroute every combo's optimizer path.
    env.pop("HOROVOD_DATA_PLANE", None)
    # The hloinspect axis owns the introspection knob: "off" combos
    # assert the identity-wrapper contract an ambient =1 would break.
    env.pop("HOROVOD_HLO_INSPECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if core == "purepy":
        env["HVD_TPU_PURE_PY"] = "1"
    if fusion == "off":
        env["HOROVOD_FUSION_THRESHOLD"] = "0"
    if cache == "off":
        env["HOROVOD_CACHE_CAPACITY"] = "0"
    if plane == "tcp":
        env["HOROVOD_SHM_DISABLE"] = "1"
    if plane == "hier":
        # Two fake hosts carved out of the rank space: block partition, so
        # np=3 gives hosts {0,1} + {2} — the smallest hierarchical topology.
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
        env["HOROVOD_HIER_FAKE_HOSTS"] = "2"
    # The wire and qdev axes share one knob: bare codec = host plane only,
    # per-plane syntax once the device ring is in play.  A qdev value is
    # "<codec>[:<schedule>]" or "demote" (int8 under a prohibitive floor).
    wire_planes = []
    if wire != "none":
        wire_planes.append(f"host={wire}" if qdev != "off" else wire)
    if qdev != "off":
        qcodec, _, qsched = qdev.partition(":")
        if qcodec == "demote":
            qcodec = "int8"
        wire_planes.append(f"device={qcodec}")
        if qsched:
            env["HOROVOD_DEVICE_SCHEDULE"] = qsched
    if wire_planes:
        env["HOROVOD_WIRE_COMPRESSION"] = ",".join(wire_planes)
    if qdev != "off":
        env["HVD_MATRIX_QDEV"] = qdev
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
        env["HOROVOD_WIRE_COMPRESSION_MIN_BYTES"] = str(
            (1 << 30) if qdev == "demote" else 4096)
    if metrics == "on":
        env["HOROVOD_METRICS"] = "1"
    if tree == "d3":
        # Forced three-level tree needs >= 3 leaders: three single-rank
        # fake hosts give the chain coordinator <- super <- leaf leader.
        env["HOROVOD_CONTROL_TREE"] = "on"
        env["HOROVOD_CONTROL_TREE_DEPTH"] = "3"
        env["HOROVOD_HIER_FAKE_HOSTS"] = "3"
    elif tree != "auto":
        env["HOROVOD_CONTROL_TREE"] = tree
    if flight == "on":
        env["HOROVOD_FLIGHT_RECORDER"] = "1"
    elif flight == "off":
        env["HOROVOD_FLIGHT_RECORDER"] = "off"
    if autopilot == "on":
        # Routes the launch through the elastic driver with the policy
        # thread attached (launch.py reads the env fallback); the driver
        # forces HOROVOD_METRICS=1 on the workers.
        env["HOROVOD_AUTOPILOT"] = "1"
    if migrate == "on":
        env["HVD_MATRIX_MIGRATE"] = "on"
        env["HOROVOD_MIGRATE_REPLICAS"] = "2"
        env["HOROVOD_MIGRATE_INTERVAL_STEPS"] = "1"
    if trace == "on":
        env["HOROVOD_STEP_TRACE"] = "1"
    elif trace == "off":
        env["HOROVOD_STEP_TRACE"] = "0"
    if dplane != "off":
        env["HVD_MATRIX_DPLANE"] = dplane
        if "xla_force_host_platform_device_count" not in \
                env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=4")
        if dplane == "gspmd":
            env["HOROVOD_DATA_PLANE"] = "gspmd"
    if hloinspect != "def":
        env["HVD_MATRIX_HLOINSPECT"] = hloinspect
        env["HOROVOD_HLO_INSPECT"] = "1" if hloinspect == "on" else "0"
        if "xla_force_host_platform_device_count" not in \
                env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=8")
    if fleet == "on":
        # The fleet plane rides the metrics registry: sketches encode the
        # local histograms, so the combo forces the metrics plane on.
        env["HOROVOD_FLEET_TELEMETRY"] = "1"
        env["HOROVOD_METRICS"] = "1"
    elif fleet == "off":
        env["HOROVOD_FLEET_TELEMETRY"] = "0"
    if np_ == 1:
        cmd = [sys.executable, script]
    else:
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               "-np", str(np_), sys.executable, script]
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as exc:
        return False, time.monotonic() - t0, f"timeout: {exc}"
    ok = proc.returncode == 0 and \
        proc.stdout.count("WORKLOAD-OK") == np_
    detail = "" if ok else (proc.stdout + proc.stderr)[-800:]
    return ok, time.monotonic() - t0, detail


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="covering subset instead of the full product")
    ap.add_argument("--timeout", type=float, default=180.0)
    args = ap.parse_args()

    failures = []
    for row in checks(args.quick):
        name, cmds, cwd = row[:3]
        timeout = row[3] if len(row) > 3 else args.timeout
        ok, dt, detail = run_check(cmds, cwd, timeout)
        label = f"check={name}"
        print(f"{'PASS' if ok else 'FAIL'}  {label}  ({dt:5.1f}s)",
              flush=True)
        if not ok:
            failures.append((label, detail))
    with tempfile.TemporaryDirectory() as td:
        scripts = {}
        for binding, text in (("jax", WORKLOAD), ("torch", TORCH_WORKLOAD)):
            scripts[binding] = os.path.join(td, f"workload_{binding}.py")
            with open(scripts[binding], "w") as f:
                f.write(text)
        for combo in combos(args.quick):
            if len(combo) == 8:  # rows predating the ctrl_tree axis
                combo = combo + ("auto",)
            if len(combo) == 9:  # rows predating the flight axis
                combo = combo + ("def",)
            if len(combo) == 10:  # rows predating the autopilot axis
                combo = combo + ("off",)
            if len(combo) == 11:  # rows predating the qdev axis
                combo = combo + ("off",)
            if len(combo) == 12:  # rows predating the migrate axis
                combo = combo + ("off",)
            if len(combo) == 13:  # rows predating the trace axis
                combo = combo + ("def",)
            if len(combo) == 14:  # rows predating the fleet axis
                combo = combo + ("def",)
            if len(combo) == 15:  # rows predating the dplane axis
                combo = combo + ("off",)
            if len(combo) == 16:  # rows predating the hloinspect axis
                combo = combo + ("def",)
            (binding, core, np_, fusion, cache, plane, wire, metrics,
             tree, flight, autopilot, qdev, migrate, trace, fleet,
             dplane, hloinspect) = combo
            label = (f"bind={binding:<5} core={core:<7} np={np_} "
                     f"fusion={fusion:<3} cache={cache:<3} plane={plane:<4} "
                     f"wire={wire:<4} metrics={metrics:<3} tree={tree:<4} "
                     f"flight={flight:<4} ap={autopilot} qdev={qdev} "
                     f"mig={migrate} trace={trace} fleet={fleet} "
                     f"dp={dplane} hlo={hloinspect}")
            ok, dt, detail = run_combo(core, np_, fusion, cache, plane,
                                       wire, metrics, tree, flight,
                                       autopilot, qdev, migrate, trace,
                                       fleet, dplane, hloinspect,
                                       script=scripts[binding],
                                       timeout=args.timeout)
            print(f"{'PASS' if ok else 'FAIL'}  {label}  ({dt:5.1f}s)",
                  flush=True)
            if not ok:
                failures.append((label, detail))
    for label, detail in failures:
        print(f"\n--- {label} ---\n{detail}", file=sys.stderr)
    print(f"\n{'ALL PASS' if not failures else f'{len(failures)} FAILED'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
