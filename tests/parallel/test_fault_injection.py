"""Fault injection end-to-end at np=4 over two fake hosts: the v8
fast-abort contract measured from Python (docs/elastic.md "Failure
detection & bounds").  An injected `die` mid-ring makes every survivor
raise HorovodInternalError naming the culprit within the
HOROVOD_ABORT_PROPAGATION_TIMEOUT bound (plus detection/scheduling
slack); an injected corrupt-tag fails every rank fast with no hang; and
an elastic job launched with `horovodrun --fault-inject` recovers from
the injected death — the flag-file latch keeps the respawned worker
alive — and trains to completion.
"""

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

ABORT_TIMEOUT_S = 2.0   # the documented default, pinned explicitly below
BOUND_SLACK_S = 13.0    # failure detection + scheduling on a loaded box

BASE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HOROVOD_HIER_FAKE_HOSTS": "2",
    # Force the TCP ring data plane so ring-send/frame-header sit on the
    # hot path (the shm handshake still runs and votes no).
    "HOROVOD_SHM_DISABLE": "1",
    "HOROVOD_ABORT_PROPAGATION_TIMEOUT": str(ABORT_TIMEOUT_S),
}


def _collapse_worker(tmpdir: str):
    """Allreduce until the injected fault collapses the job, then persist
    what this rank observed.  Files, not return values: when a rank dies
    run() raises, and the launcher SIGTERMs survivors on the first death —
    ignored here so every survivor gets to record its exception."""
    import signal
    import time

    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.exceptions import HorovodInternalError

    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    r = int(os.environ.get("HOROVOD_RANK", "-1"))
    out = {"rank": r, "error": "", "args": "", "elapsed": -1.0, "iters": 0}
    t0 = time.monotonic()
    try:
        hvd.init(build_mesh=False)
        for i in range(2000):
            t0 = time.monotonic()
            hvd.allreduce(np.full(1024, float(r), np.float32), op=hvd.Sum,
                          name=f"chaos.{i % 8}")
            out["iters"] = i + 1
    except HorovodInternalError as exc:
        out["error"] = str(exc)
        out["args"] = repr(exc.args)
        out["elapsed"] = time.monotonic() - t0
    with open(os.path.join(tmpdir, f"rank{r}.json"), "w") as f:
        json.dump(out, f)
    try:
        hvd.shutdown()
    except Exception:
        pass
    return out


def test_rank_death_aborts_survivors_within_bound(tmp_path):
    """Rank 1 is killed by the `die` action at its 200th ring-send hit
    (well past the init fences, a few dozen iterations into the loop).
    Every survivor must fail its in-flight collective with the culprit
    named — carried by the kTagAbort broadcast into the exception and its
    .args (what elastic retry loops inspect) — within the propagation
    bound, not a multi-minute TCP timeout."""
    tmpdir = str(tmp_path)
    latch = os.path.join(tmpdir, "die.latch")
    env = dict(BASE_ENV,
               HOROVOD_FAULT_INJECT=f"ring-send:200:1:die:{latch}")
    with pytest.raises(RuntimeError, match="rank 1"):
        run(_collapse_worker, args=(tmpdir,), np=4, env=env)
    assert os.path.exists(latch), "die action never fired"
    assert not os.path.exists(os.path.join(tmpdir, "rank1.json"))
    for r in (0, 2, 3):
        path = os.path.join(tmpdir, f"rank{r}.json")
        assert os.path.exists(path), (r, os.listdir(tmpdir))
        with open(path) as f:
            out = json.load(f)
        assert out["error"], out            # raised, never hung
        assert "culprit rank 1" in out["error"], out
        assert "culprit rank 1" in out["args"], out
        assert 0 <= out["elapsed"] < ABORT_TIMEOUT_S + BOUND_SLACK_S, out


def test_corrupt_tag_fails_fast_everywhere(tmp_path):
    """A corrupted frame tag on rank 2 is a protocol violation, not a
    death: no rank exits, every rank's collective fails fast through the
    abort machinery, and the job never hangs."""
    tmpdir = str(tmp_path)
    env = dict(BASE_ENV,
               HOROVOD_FAULT_INJECT="frame-header:300:2:corrupt-tag")
    res = run(_collapse_worker, args=(tmpdir,), np=4, env=env)
    assert [r["rank"] for r in res] == [0, 1, 2, 3]
    for out in res:
        assert out["error"], out
        assert 0 <= out["elapsed"] < ABORT_TIMEOUT_S + BOUND_SLACK_S, out


ELASTIC_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ObjectState(epoch=0, total=0.0)

    @hvd.elastic.run
    def train(state):
        while state.epoch < 4:
            val = hvd.allreduce(np.ones(4, np.float32),
                                name=f"step.{state.epoch}")
            state.total += float(val.sum())
            state.epoch += 1
            state.commit()
        return state.total

    total = train(state)
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} "
          f"epoch={state.epoch} total={total}", flush=True)
    hvd.shutdown()
""")


def test_elastic_recovers_from_injected_death(tmp_path):
    """End-to-end through the launcher flag: `horovodrun --fault-inject`
    exports the spec, rank 1 dies at its first ring-send hit, the elastic
    driver re-forms, and the respawned worker — finding the flag-file
    latch already present — survives to train to completion."""
    td = str(tmp_path)
    latch = os.path.join(td, "die.latch")
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(ELASTIC_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_SHM_DISABLE"] = "1"
    # The death may land during generation 0's init, taking innocent
    # ranks down with it; collateral fast failures must not blacklist
    # the only host.
    env["HOROVOD_ELASTIC_BLACKLIST_FAILURES"] = "10"
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--min-np", "1", "-np", "2", "-H", "localhost:2", "--verbose",
           "--fault-inject", f"ring-send:*:1:die:{latch}",
           sys.executable, script]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=240,
                          env=env, cwd=td)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert os.path.exists(latch), "die action never fired"
    assert "epoch=4" in proc.stdout, proc.stdout + proc.stderr
    # The injected death forced at least one re-formation.
    assert proc.stderr.count(" formed with ") >= 2, proc.stderr
