"""Zero-downtime elastic state migration at np=4 (docs/elastic.md
"Zero-downtime migration").

The tentpole proof: a rank death must NOT send the fleet back to a
checkpoint.  Each rank continuously replicates its committed training
state (params, optimizer moments, error-feedback residuals, step counter)
onto ring-successor ranks; on re-formation the migration phase resumes
every survivor — and, after the blacklist sentence expires, the returning
rank — bit-for-bit from those in-memory peer shards.

Two scenarios:

- ``test_zero_downtime_migration_np4_chaos``: rank 3 kills itself
  mid-training; the driver fast-aborts, blacklists the host, re-forms at
  np=3 (survivors resume from peer shards), the sentence expires and the
  fleet re-grows to np=4 with the returning rank reclaiming its parked
  shard.  A no-fault reference run of the identical worker produces the
  per-rank state digests the chaos run must reproduce exactly — zero
  checkpoint reads anywhere.

- ``test_degraded_replicas_fall_back_to_sharded_checkpoint``: every rank
  deliberately discards the dead rank's replicas, so no replication cut
  covers the loss; the deterministic fallback restores each survivor's
  own shard from the attached async ShardedCheckpointer.
"""

import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# World-size-invariant training: the "gradient" is an allreduce of a
# tensor that is identical on every rank, so params/moments/step depend
# only on how many steps ran — a faulted run that truly resumed from peer
# shards lands on the same bytes as the no-fault reference.  The
# error-feedback residual is salted per ORIGINAL rank at step 0 and then
# updated deterministically: it only survives a re-formation if migration
# carried that rank's shard bit-for-bit.
WORKER = textwrap.dedent("""
    import hashlib
    import os
    import time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd

    DIE_STEP = int(os.environ.get("TEST_DIE_STEP", "0"))
    FINAL_STEP = int(os.environ.get("TEST_FINAL_STEP", "12"))
    MARKER = os.environ.get("TEST_DIE_MARKER", "")

    hvd.init()
    state = hvd.elastic.ObjectState(
        params=np.zeros(64, np.float32),
        mom=np.zeros(64, np.float32),
        resid=np.zeros(32, np.float32),
        step=0, orig=-1)

    def digest(state):
        h = hashlib.sha256()
        for a in (state.params, state.mom, state.resid):
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(str((int(state.step), int(state.orig))).encode())
        return h.hexdigest()

    shrink_seen = []
    t_last_commit = [time.time()]

    @hvd.elastic.run
    def train(state):
        while True:
            if state.orig < 0:
                # Generation 0 only: salt the per-rank error-feedback
                # residual.  Migration must carry it bit-for-bit — a
                # checkpointless rank-0 broadcast would erase the salt.
                state.orig = hvd.rank()
                state.resid = np.full(32, 1000.0 + hvd.rank(), np.float32)
            if hvd.size() >= 4:
                if state.step >= FINAL_STEP:
                    return
                s = hvd.allreduce(
                    np.full(64, float(state.step + 1), np.float32),
                    op=hvd.Sum, name=f"grad.{state.step % 8}")
                g = np.asarray(s, np.float32) / np.float32(hvd.size())
                state.mom = np.float32(0.9) * state.mom + g
                state.params = state.params - np.float32(0.1) * state.mom
                state.resid = state.resid + np.float32(0.001 * state.step)
                state.step += 1
                state.commit()
                t_last_commit[0] = time.time()
                if (DIE_STEP and int(state.orig) == 3
                        and int(state.step) == DIE_STEP
                        and not os.path.exists(MARKER)):
                    with open(MARKER, "w") as f:
                        f.write("died")
                    print("DYING orig=3", flush=True)
                    os._exit(17)
            else:
                if not shrink_seen:
                    shrink_seen.append(True)
                    print(f"SHRINK-LATENCY rank={hvd.rank()} "
                          f"secs={time.time() - t_last_commit[0]:.2f}",
                          flush=True)
                # Shrunken window: heartbeat only — no commits, no
                # progress — until the blacklist sentence expires and the
                # driver re-grows the fleet.
                hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="hb")
                time.sleep(0.05)
                state.check_host_updates()

    train(state)

    if DIE_STEP:
        # Identity must have survived both hops (shrink claim r->r, then
        # the returning rank reclaiming its parked shard on the re-grow).
        assert int(state.orig) == hvd.rank(), (state.orig, hvd.rank())
        m = hvd.metrics()
        counters = m.get("counters") or {}
        gauges = m.get("gauges") or {}
        assert counters.get("migrate_events_total", 0) > 0, counters
        # Zero checkpoint reads: the fallback path never ran.
        assert counters.get("migrate_fallbacks_total", 0) == 0, counters
        assert gauges.get("elastic_generation", 0) >= 2, gauges
        fr = hvd.flight_record()
        types = {int(k): v for k, v in (fr.get("types") or {}).items()}
        mig_t = next((k for k, v in types.items() if v == "migrate"), None)
        assert mig_t is not None, types
        mig_rows = [r for r in fr.get("events") or [] if r[2] == mig_t]
        assert mig_rows, "no migrate events in the final generation"
        assert all(1 <= (r[4] >> 8) <= 5 for r in mig_rows), mig_rows
    print(f"DIGEST rank={hvd.rank()} orig={int(state.orig)} "
          f"sha={digest(state)}", flush=True)
    hvd.shutdown()
""")


def _common_env(pm_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_SHM_DISABLE"] = "1"
    env["HOROVOD_MIGRATE_REPLICAS"] = "2"
    env["HOROVOD_MIGRATE_INTERVAL_STEPS"] = "1"
    env["HOROVOD_METRICS"] = "1"
    env["HOROVOD_FLIGHT_RECORDER"] = "1"
    env["HOROVOD_POSTMORTEM_DIR"] = pm_dir
    # One fast failure is enough to sentence the dying host (the worker
    # self-terminates well within the fast-failure horizon).
    env["HOROVOD_ELASTIC_BLACKLIST_FAILURES"] = "1"
    env["HOROVOD_ELASTIC_FAST_FAILURE_SECS"] = "60"
    return env


def _digests(stdout):
    out = {}
    for m in re.finditer(r"DIGEST rank=(\d+) orig=(-?\d+) sha=([0-9a-f]+)",
                         stdout):
        out[int(m.group(1))] = (int(m.group(2)), m.group(3))
    return out


def test_zero_downtime_migration_np4_chaos(tmp_path):
    td = str(tmp_path)
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(WORKER)

    # Reference: the identical worker, no fault — the ground-truth digests.
    ref_pm = os.path.join(td, "pm_ref")
    os.makedirs(ref_pm)
    env = _common_env(ref_pm)
    env["TEST_DIE_STEP"] = "0"
    env["TEST_DIE_MARKER"] = os.path.join(td, "unused_marker")
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "4", "--min-np", "2", "-H", "127.0.0.1:3,localhost:1",
           "--verbose", sys.executable, script]
    ref = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                         env=env, cwd=td)
    assert ref.returncode == 0, ref.stdout[-4000:] + ref.stderr[-4000:]
    ref_digests = _digests(ref.stdout)
    assert sorted(ref_digests) == [0, 1, 2, 3], ref.stdout

    # Chaos: rank 3 (alone on "localhost") kills itself at step 6.
    pm_dir = os.path.join(td, "pm")
    os.makedirs(pm_dir)
    env = _common_env(pm_dir)
    env["TEST_DIE_STEP"] = "6"
    env["TEST_DIE_MARKER"] = os.path.join(td, "die_marker")
    # Short sentence so the re-admission leg runs inside the test.
    env["HOROVOD_ELASTIC_BLACKLIST_BASE_SECS"] = "7"
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          env=env, cwd=td)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "DYING orig=3" in proc.stdout, proc.stdout

    # The driver blacklisted the host, re-formed at 3, then re-grew to 4.
    assert "blacklisting host localhost" in proc.stderr, proc.stderr
    assert " formed with 3 " in proc.stderr, proc.stderr
    assert proc.stderr.count(" formed with 4 ") >= 2, proc.stderr

    # THE acceptance bar: every rank of the final np=4 generation —
    # including the returning rank 3 — carries state bit-identical to the
    # no-fault reference (params, moments, EF residuals, step, identity).
    digests = _digests(proc.stdout)
    assert sorted(digests) == [0, 1, 2, 3], proc.stdout
    assert digests == ref_digests, (digests, ref_digests)

    # Zero checkpoint reads: no fallback anywhere in either stream.
    blob = proc.stdout + proc.stderr
    assert "falling back" not in blob, blob

    # Recovery was prompt: fast-abort + re-rendezvous + migration, well
    # under a minute from the last pre-fault commit.
    lat = [float(m.group(1))
           for m in re.finditer(r"SHRINK-LATENCY rank=\d+ secs=([0-9.]+)",
                                proc.stdout)]
    assert lat, proc.stdout
    assert max(lat) < 60.0, lat

    # The migration journal names both hops as peer-shard resumes.
    ap_log = os.path.join(pm_dir, "autopilot.jsonl")
    assert os.path.exists(ap_log), os.listdir(pm_dir)
    rows = [json.loads(line)
            for line in open(ap_log).read().splitlines() if line]
    mig_rows = [r for r in rows if r["action"] == "migrate"]
    assert len(mig_rows) >= 2, rows
    assert any("mode=replica" in r["detail"] for r in mig_rows), mig_rows
    assert not any("fallback" in r["detail"] for r in mig_rows), mig_rows

    # The crash dumps carry type-14 migrate events (the replication
    # refreshes that ran before the abort).
    flights = sorted(glob.glob(os.path.join(pm_dir, "flight.*.json")))
    assert flights, os.listdir(pm_dir)
    found = False
    for path in flights:
        dump = json.load(open(path))
        types = dump.get("types") or {}
        mig_t = next((int(k) for k, v in types.items() if v == "migrate"),
                     None)
        if mig_t is None:
            continue
        for row in dump.get("events") or []:
            if row[2] == mig_t and 1 <= (row[4] >> 8) <= 5:
                found = True
    assert found, f"no migrate event in {flights}"

    # The rendered post-mortem report names the migration.
    report = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         pm_dir],
        capture_output=True, text=True, timeout=60)
    assert report.returncode == 0, report.stdout + report.stderr
    assert "migrate" in report.stdout, report.stdout


# Degraded path: every rank discards the dying rank's replicas as they
# arrive, so when it dies no replication cut covers the loss and the
# deterministic fallback restores from the attached ShardedCheckpointer.
FALLBACK_WORKER = textwrap.dedent("""
    import os
    import time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.elastic import migrate as mig

    DIE_STEP = 4
    FINAL_STEP = 8
    MARKER = os.environ["TEST_DIE_MARKER"]

    hvd.init()
    ckpt = ShardedCheckpointer(os.environ["TEST_CKPT_DIR"],
                               use_orbax=False, async_write=True)
    mig.attach_checkpointer(ckpt)
    state = hvd.elastic.ObjectState(
        w=np.zeros(16, np.float32), step=0, orig=-1)

    @hvd.elastic.run
    def train(state):
        while state.step < FINAL_STEP:
            if state.orig < 0:
                state.orig = hvd.rank()
                state.w = np.full(16, 100.0 * (hvd.rank() + 1), np.float32)
            hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                          name=f"d.{state.step % 4}")
            state.w = state.w + np.float32(1.0)
            state.step += 1
            state.commit()
            ckpt.save(int(state.step),
                      {"w": state.w, "step": int(state.step),
                       "orig": int(state.orig)})
            # Simulate replica loss: every rank discards rank 2's peer
            # shards the moment they land, so its death is uncoverable.
            st = mig.store()
            for key in [k for k in list(st.peers) if k[1] == 2]:
                del st.peers[key]
            for key in [k for k in list(st.parked) if k[1] == 2]:
                del st.parked[key]
            if (int(state.orig) == 2 and int(state.step) == DIE_STEP
                    and not os.path.exists(MARKER)):
                ckpt.wait_until_finished()  # the shard must be durable
                with open(MARKER, "w") as f:
                    f.write("died")
                print("DYING orig=2", flush=True)
                os._exit(17)

    train(state)

    # Each survivor resumed ITS OWN shard from the checkpoint (a rank-0
    # broadcast would have cloned orig=0 everywhere).
    assert int(state.orig) == hvd.rank(), (state.orig, hvd.rank())
    assert int(state.step) == FINAL_STEP, state.step
    expect = 100.0 * (int(state.orig) + 1) + FINAL_STEP
    np.testing.assert_array_equal(
        state.w, np.full(16, expect, np.float32))
    counters = hvd.metrics().get("counters") or {}
    assert counters.get("migrate_fallbacks_total", 0) >= 1, counters
    print(f"FALLBACK-OK rank={hvd.rank()} orig={int(state.orig)}",
          flush=True)
    hvd.shutdown()
""")


def test_degraded_replicas_fall_back_to_sharded_checkpoint(tmp_path):
    td = str(tmp_path)
    pm_dir = os.path.join(td, "pm")
    ckpt_dir = os.path.join(td, "ckpt")
    os.makedirs(pm_dir)
    os.makedirs(ckpt_dir)
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(FALLBACK_WORKER)

    env = _common_env(pm_dir)
    env["TEST_CKPT_DIR"] = ckpt_dir
    env["TEST_DIE_MARKER"] = os.path.join(td, "die_marker")
    # A long sentence: the job finishes at np=2, no re-grow leg here.
    env["HOROVOD_ELASTIC_BLACKLIST_BASE_SECS"] = "600"

    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "3", "--min-np", "2", "-H", "127.0.0.1:2,localhost:1",
           "--verbose", sys.executable, script]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          env=env, cwd=td)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "DYING orig=2" in proc.stdout, proc.stdout
    assert " formed with 2 " in proc.stderr, proc.stderr
    assert proc.stdout.count("FALLBACK-OK") == 2, proc.stdout

    # The journal names the degraded verdict (owner 2 uncoverable).
    ap_log = os.path.join(pm_dir, "autopilot.jsonl")
    assert os.path.exists(ap_log), os.listdir(pm_dir)
    rows = [json.loads(line)
            for line in open(ap_log).read().splitlines() if line]
    fb = [r for r in rows if r["action"] == "migrate"
          and "fallback" in r["detail"]]
    assert fb, rows
    assert "2" in fb[0]["detail"], fb
