"""Hands-off fleet-autopilot chaos loop at np=4 (docs/elastic.md).

One rank is made a persistent straggler with deterministic fault injection
(150 ms of injected delay on every one of the coordinator's receives from
rank 3).  With `--autopilot` the whole response is autonomous — no human
input anywhere in the loop:

  detect    the coordinator's straggler reports flag rank 3 every window
  attribute POLL carries the culprit rank and its host over the policy
            channel
  evict     after EVICT_WINDOWS consecutive flagged windows the autopilot
            sentences the host to the elastic blacklist and the driver
            re-forms at np=3 (above the --min-np rail)
  recover   survivors resume through the @hvd.elastic.run retry loop
  re-admit  the blacklist sentence expires, discovery re-adds the host,
            and the fleet re-forms at np=4

Workers run collectives until they have observed the shrink AND the
re-grow, then exit 0; the test asserts the driver log, the autopilot
decision journal, and the native flight record all name each decision.
"""

import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The worker watches its own world size: phase 0 -> full fleet, phase 1 ->
# it has seen the eviction shrink (size < 4), phase 2 -> it has seen the
# blacklist-expiry re-grow (size back to 4).  commit() every step both
# snapshots state and surfaces the driver's hosts-updated pushes.
WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ObjectState(phase=0, steps=0)

    @hvd.elastic.run
    def train(state):
        while state.phase < 2:
            hvd.allreduce(np.ones(256, np.float32), op=hvd.Sum,
                          name=f"ap.{state.steps % 8}")
            state.steps += 1
            if state.phase == 0 and hvd.size() < 4:
                state.phase = 1
            elif state.phase == 1 and hvd.size() >= 4:
                state.phase = 2
            state.commit()
        return state.phase

    phase = train(state)
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} phase={phase} "
          f"steps={state.steps}", flush=True)
    hvd.shutdown()
""")


def test_autopilot_evicts_straggler_and_readmits(tmp_path):
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
    # Fast straggler verdicts: 1 s report windows, low skew/floor so the
    # injected 150 ms lag is unambiguous, eviction after 2 flagged windows.
    env["HOROVOD_METRICS_REPORT_SECONDS"] = "1"
    env["HOROVOD_STRAGGLER_SKEW"] = "2"
    env["HOROVOD_STRAGGLER_MIN_MS"] = "20"
    env["HOROVOD_AUTOPILOT_EVICT_WINDOWS"] = "2"
    env["HOROVOD_AUTOPILOT_COOLDOWN_SECS"] = "60"
    # A short sentence so the re-admission leg runs inside the test; a
    # high failure threshold so collateral teardown deaths never blacklist
    # a host on their own (the autopilot's sentence is explicit).
    env["HOROVOD_ELASTIC_BLACKLIST_BASE_SECS"] = "7"
    env["HOROVOD_ELASTIC_BLACKLIST_FAILURES"] = "10"
    env["HOROVOD_FLIGHT_RECORDER"] = "1"
    env["HOROVOD_POSTMORTEM_DIR"] = pm_dir

    # Host names sort lexicographically into rank order ("127.0.0.1" <
    # "localhost"), so rank 3 — the injected straggler — lands alone on
    # "localhost": evictable (1 slot, 4-1 >= min_np=2) and never the
    # coordinator.
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "4", "--min-np", "2", "-H", "127.0.0.1:3,localhost:1",
           "--autopilot", "--verbose",
           "--fault-inject", "coordinator-recv:*:3:delay:150",
           sys.executable, script]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          env=env, cwd=td)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]

    # Every worker of the final generation saw shrink AND re-grow.
    assert "phase=2" in proc.stdout, proc.stdout + proc.stderr

    # The driver log names the autonomous decision and both re-formations.
    assert "autopilot evicted host localhost" in proc.stderr, proc.stderr
    assert "autopilot: evict rank=3" in proc.stderr, proc.stderr
    assert " formed with 3 " in proc.stderr, proc.stderr
    # Initial formation at 4 plus the post-expiry re-grow back to 4.
    assert proc.stderr.count(" formed with 4 ") >= 2, proc.stderr

    # The decision journal records the whole loop: evict, then the
    # re-admission leg (blacklist expiry and/or the re-grown formation).
    ap_log = os.path.join(pm_dir, "autopilot.jsonl")
    assert os.path.exists(ap_log), os.listdir(pm_dir)
    rows = [json.loads(line)
            for line in open(ap_log).read().splitlines() if line]
    actions = [r["action"] for r in rows]
    assert "evict" in actions, rows
    evict = rows[actions.index("evict")]
    assert evict["rank"] == 3, evict
    assert "localhost" in evict["detail"], evict
    assert {"readmit", "scale_up"} & set(actions), rows

    # The native record survived the eviction: the coordinator's flight
    # dump carries the autopilot event (type legend "autopilot", a=action
    # code 1=evict, b=subject rank).
    flights = sorted(glob.glob(os.path.join(pm_dir, "flight.*.json")))
    assert flights, os.listdir(pm_dir)
    found = False
    for path in flights:
        dump = json.load(open(path))
        types = dump.get("types") or {}
        ap_type = next((int(k) for k, v in types.items()
                        if v == "autopilot"), None)
        if ap_type is None:
            continue
        for row in dump.get("events") or []:
            if row[2] == ap_type and row[4] == 1 and row[5] == 3:
                found = True
    assert found, f"no autopilot evict event in {flights}"

    # The rendered post-mortem report includes the decisions.
    report = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         pm_dir],
        capture_output=True, text=True, timeout=60)
    assert report.returncode == 0, report.stdout + report.stderr
    assert "Autopilot decisions" in report.stdout, report.stdout
    assert "evict" in report.stdout, report.stdout
