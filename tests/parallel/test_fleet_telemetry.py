"""Fleet telemetry plane at np=4 (protocol v11, docs/observability.md
"Fleet telemetry").

Two halves:

1. **Bucket exactness.**  The coordinator's fleet histograms — built by
   summing the delta/varint sketch sections riding CYCLE frames — must be
   *bucket-exact* equal to an offline merge of every rank's local
   HOROVOD_METRICS_FILE dump, with the leader tree both off and on.  The
   BYE frame carries each rank's final sketch, so the comparison holds at
   full precision provided shutdown is staggered leaves-first: a
   departing rank's BYE must be absorbed by its parent while the parent's
   background loop is still cycling.  (Per-rank metric files are written
   after Farewell, and no histogram observation can land between the
   final barrier and Farewell, so file locals == final sketches.)

2. **Anomaly sentinel end-to-end.**  An np=4 chaos run where rank 3
   becomes a persistent straggler *mid-run* (after the sentinel's EWMA
   warmup) must produce a sentinel anomaly naming rank 3 — in the
   autopilot journal, on stderr, and as a type-15 flight event — strictly
   before the 3-window eviction rule fires, and /history must show the
   step-p99 inflection.  The delay onset is time-based (not
   --fault-inject) because a delay present from process start would be
   absorbed into the EWMA baseline during warmup and never register as an
   anomaly.
"""

import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner import run

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SERIES = ("negotiation_wait_us", "ring_hop_us", "step_time_us",
          "shm_fence_us")

BASE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HOROVOD_HIER_FAKE_HOSTS": "2",
    "HOROVOD_SHM_DISABLE": "1",
    "HOROVOD_METRICS": "1",
    "HOROVOD_FLEET_TELEMETRY": "1",
}


def _fleet_worker(tmpdir: str, delays: dict):
    """Paced collectives, then staggered shutdown (leaves first) so every
    final BYE sketch is absorbed by a still-cycling parent."""
    import time

    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    # ~2.5 s of paced steps so the coordinator's 1 Hz fleet tick fills
    # the 1 s history tier with real samples.
    for i in range(30):
        time.sleep(0.08)
        out = hvd.allreduce(np.full(64, float(r), np.float32), op=hvd.Sum,
                            name=f"ft.{i % 10}")
        np.testing.assert_allclose(out, s * (s - 1) / 2.0)
    hvd.barrier()
    live = hvd.metrics().get("fleet")
    history = hvd.fleet_history() if r == 0 else None
    time.sleep(delays.get(r, 0.0))
    hvd.shutdown()
    return {"rank": r, "fleet_live": live, "history": history}


def _merge_local(dumps):
    """Offline merge of per-rank local histograms: elementwise bucket sum
    plus count/sum_us — the ground truth the coordinator must equal."""
    merged = {}
    for name in SERIES:
        buckets, count, sum_us = [], 0, 0
        for d in dumps:
            h = (d.get("histograms") or {}).get(name)
            if not h:
                continue
            b = h.get("buckets") or []
            if len(buckets) < len(b):
                buckets.extend([0] * (len(b) - len(buckets)))
            for i, v in enumerate(b):
                buckets[i] += v
            count += h.get("count", 0)
            sum_us += h.get("sum_us", 0)
        merged[name] = {"buckets": buckets, "count": count, "sum_us": sum_us}
    tenants = {}
    for d in dumps:
        for psid, t in (d.get("tenants") or {}).items():
            h = t.get("negotiation_wait_us") or {}
            agg = tenants.setdefault(
                psid, {"buckets": [], "count": 0, "sum_us": 0})
            b = h.get("buckets") or []
            if len(agg["buckets"]) < len(b):
                agg["buckets"].extend([0] * (len(b) - len(agg["buckets"])))
            for i, v in enumerate(b):
                agg["buckets"][i] += v
            agg["count"] += h.get("count", 0)
            agg["sum_us"] += h.get("sum_us", 0)
    merged["tenants"] = tenants
    return merged


# Shutdown stagger (seconds) per topology.  Flat: every worker BYEs at
# once, the coordinator absorbs all three finals.  Tree (fake hosts
# {0,1},{2,3}; leaders 0 and 2): leaves 1/3 first, then leader 2 (its
# host-sum BYE now carries rank 3's final), then the coordinator.
_DELAYS = {
    "off": {0: 2.5},
    "on": {2: 1.5, 0: 3.0},
}


@pytest.mark.parametrize("tree", ["off", "on"])
def test_fleet_histograms_bucket_exact_vs_offline_merge(tmp_path, tree):
    tmpdir = str(tmp_path)
    env = dict(BASE_ENV,
               HOROVOD_CONTROL_TREE=tree,
               HOROVOD_METRICS_FILE=os.path.join(tmpdir, "metrics.{rank}"))
    res = run(_fleet_worker, args=(tmpdir, _DELAYS[tree]), np=4, env=env)
    assert [r["rank"] for r in res] == [0, 1, 2, 3]

    # The live mid-run view on the coordinator was already populated.
    live = res[0]["fleet_live"]
    assert live and live["negotiation_wait_us"]["count"] > 0, live
    history = res[0]["history"]
    assert history.get("schema") == "fleethistory-v1", history
    tiers = history.get("tiers") or []
    assert tiers and tiers[0]["period_s"] == 1
    assert len(tiers[0]["samples"]) >= 1, history
    # Workers never carry the coordinator-side plane.
    assert res[1]["fleet_live"] is None

    dumps = []
    for rank in range(4):
        path = os.path.join(tmpdir, f"metrics.{rank}")
        assert os.path.exists(path), os.listdir(tmpdir)
        with open(path) as f:
            dumps.append(json.load(f))

    fleet = dumps[0].get("fleet")
    assert fleet, "rank 0's metrics file must carry the fleet section"
    merged = _merge_local(dumps)

    # Non-trivial workload: every rank negotiated every tensor.
    assert merged["negotiation_wait_us"]["count"] >= 4 * 30

    for name in SERIES:
        f, m = fleet[name], merged[name]
        assert f["buckets"] == m["buckets"], \
            (tree, name, f["buckets"], m["buckets"])
        assert f["count"] == m["count"], (tree, name, f, m)
        assert f["sum_us"] == m["sum_us"], (tree, name, f, m)

    # Per-tenant sketches merge with the same exactness (zero-count
    # tenants may legally be absent from either side).
    for psid, m in merged["tenants"].items():
        if m["count"] == 0:
            continue
        f = ((fleet.get("tenants") or {}).get(psid) or {}).get(
            "negotiation_wait_us")
        assert f is not None, (tree, psid, fleet.get("tenants"))
        assert f["buckets"] == m["buckets"], (tree, psid)
        assert f["count"] == m["count"], (tree, psid)
        assert f["sum_us"] == m["sum_us"], (tree, psid)
    for psid, f in (fleet.get("tenants") or {}).items():
        if f["negotiation_wait_us"]["count"] > 0:
            assert psid in merged["tenants"], (tree, psid)


# -- sentinel end-to-end ------------------------------------------------------

# Rank 3 turns straggler at t0+15 s: past the sentinel's 10-tick (10 s)
# EWMA warmup, so the 0.25 s/step delay is a z-spike against a settled
# baseline, not part of it.  The baseline step rate is throttled to
# 0.05 s/step so the fleet step-p99 — a *cumulative* histogram quantile —
# shifts within a couple of slow steps (>1% of all observations land in
# the slow bucket quickly), keeping the anomaly strictly ahead of the
# >=6 s eviction rule (3 windows x 2 s).  Rank 0 prints the /history
# payload the moment a step_p99 anomaly appears, because the elastic
# re-formation after the eviction re-inits (and so wipes) the plane.
WORKER = textwrap.dedent("""
    import json
    import os
    import time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    t0 = time.time()
    state = hvd.elastic.ObjectState(phase=0, steps=0, printed=0)

    @hvd.elastic.run
    def train(state):
        while state.phase < 1:
            if hvd.rank() == 3 and time.time() - t0 > 15.0:
                time.sleep(0.25)
            time.sleep(0.05)
            hvd.allreduce(np.ones(256, np.float32), op=hvd.Sum,
                          name=f"sn.{state.steps % 8}")
            state.steps += 1
            if hvd.rank() == 0 and not state.printed:
                h = hvd.fleet_history()
                if any(a.get("kind") == "step_p99"
                       for a in h.get("anomalies") or []):
                    print("HISTORY " + json.dumps(h), flush=True)
                    state.printed = 1
            if hvd.size() < 4:
                state.phase = 1
            state.commit()
        return state.phase

    phase = train(state)
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} phase={phase} "
          f"steps={state.steps}", flush=True)
    hvd.shutdown()
""")


def test_sentinel_names_straggler_before_eviction(tmp_path):
    td = str(tmp_path)
    pm_dir = os.path.join(td, "pm")
    os.makedirs(pm_dir)
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(WORKER)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_SHM_DISABLE"] = "1"
    env["HOROVOD_METRICS"] = "1"
    env["HOROVOD_FLEET_TELEMETRY"] = "1"
    env["HOROVOD_SENTINEL_ZSCORE"] = "3"
    # 2 s straggler windows and 3 consecutive flagged windows before the
    # autopilot may evict: the eviction can fire no earlier than ~6 s
    # after onset, while the sentinel needs only a couple of slow steps.
    env["HOROVOD_METRICS_REPORT_SECONDS"] = "2"
    env["HOROVOD_STRAGGLER_SKEW"] = "2"
    env["HOROVOD_STRAGGLER_MIN_MS"] = "20"
    env["HOROVOD_AUTOPILOT_EVICT_WINDOWS"] = "3"
    env["HOROVOD_AUTOPILOT_COOLDOWN_SECS"] = "60"
    # A long blacklist sentence: the test ends at the shrink, no re-grow.
    env["HOROVOD_ELASTIC_BLACKLIST_BASE_SECS"] = "120"
    env["HOROVOD_ELASTIC_BLACKLIST_FAILURES"] = "10"
    env["HOROVOD_FLIGHT_RECORDER"] = "1"
    # The flight dump is written at final shutdown, ~6 s of ~1k ctrl/ring
    # events per second after the anomaly: the default 4k-slot ring would
    # lap the type-15 event before it is ever persisted.
    env["HOROVOD_FLIGHT_RECORDER_SLOTS"] = "65536"
    env["HOROVOD_POSTMORTEM_DIR"] = pm_dir

    # "127.0.0.1" < "localhost" lexicographically, so rank 3 — the
    # mid-run straggler — lands alone on "localhost": evictable and never
    # the coordinator.
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "4", "--min-np", "2", "-H", "127.0.0.1:3,localhost:1",
           "--autopilot", "--verbose",
           sys.executable, script]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=280,
                          env=env, cwd=td)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "phase=1" in proc.stdout, proc.stdout + proc.stderr

    # The journal shows the whole advisory-then-act sequence, in order:
    # a sentinel anomaly naming rank 3 strictly before the eviction.
    ap_log = os.path.join(pm_dir, "autopilot.jsonl")
    assert os.path.exists(ap_log), os.listdir(pm_dir)
    rows = [json.loads(line)
            for line in open(ap_log).read().splitlines() if line]
    actions = [r["action"] for r in rows]
    assert "anomaly" in actions and "evict" in actions, rows
    anomaly_idx = next(i for i, r in enumerate(rows)
                       if r["action"] == "anomaly" and r.get("rank") == 3)
    evict_idx = actions.index("evict")
    assert anomaly_idx < evict_idx, rows
    assert rows[anomaly_idx]["ts"] <= rows[evict_idx]["ts"], rows
    assert "step_p99" in rows[anomaly_idx]["detail"], rows[anomaly_idx]
    assert rows[evict_idx]["rank"] == 3, rows[evict_idx]

    # The driver log narrates both: advisory first, action second.
    assert "autopilot: anomaly rank=3" in proc.stderr, proc.stderr
    assert "autopilot: evict rank=3" in proc.stderr, proc.stderr
    assert proc.stderr.index("autopilot: anomaly rank=3") < \
        proc.stderr.index("autopilot: evict rank=3")

    # /history (printed by rank 0 at detection time, before re-formation
    # wiped the plane): the 1 s tier shows the step-p99 inflection and the
    # anomaly record names rank 3 with a z-score over the threshold.
    # The launcher prefixes worker stdout with "[rank]<stdout>: ".
    hline = next(line for line in proc.stdout.splitlines()
                 if "HISTORY " in line)
    history = json.loads(hline.split("HISTORY ", 1)[1])
    assert history["schema"] == "fleethistory-v1"
    cols = history["columns"]
    i_p99 = cols.index("step_p99_us")
    samples = history["tiers"][0]["samples"]
    vals = [row[i_p99] for row in samples if row[i_p99] > 0]
    assert len(vals) >= 5, history["tiers"][0]
    assert vals[-1] >= 2 * min(vals), vals
    anom = next(a for a in history["anomalies"]
                if a["kind"] == "step_p99")
    assert anom["rank"] == 3, history["anomalies"]
    assert anom["score"] >= 3.0, anom
    assert anom["value"] > anom["baseline"], anom

    # The native flight record carries the type-15 sentinel event with
    # the packed attribution a = kind<<8 | (rank+1) = 1<<8 | 4.
    flights = sorted(glob.glob(os.path.join(pm_dir, "flight.*.json")))
    assert flights, os.listdir(pm_dir)
    found = False
    for path in flights:
        dump = json.load(open(path))
        types = dump.get("types") or {}
        s_type = next((int(k) for k, v in types.items()
                       if v == "sentinel"), None)
        if s_type is None:
            continue
        for row in dump.get("events") or []:
            if row[2] == s_type and row[4] == (1 << 8 | 4):
                found = True
    assert found, f"no step_p99 sentinel event naming rank 3 in {flights}"
