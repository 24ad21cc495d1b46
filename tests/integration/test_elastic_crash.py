"""Elastic integration tests in the reference's shape (SURVEY.md §4):
multi-process on localhost via the launcher, scripted discovery, and
worker death by self-SIGKILL mid-training (elastic_common.py patterns).

A rank dies while it holds the shm plane (or the chain broadcast), and a job
that is killed again and again.  Split from test_elastic.py so that no
pytest-xdist worker (``--dist loadfile``) holds all of them.
"""

import os
import re
import subprocess
import sys
import tempfile
import textwrap

from _elastic_helpers import REPO, _run_launcher


SHM_CRASH_WORKER = textwrap.dedent("""
    import os, sys, threading, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ObjectState(epoch=0, total=0.0)
    KILL_EPOCH = int(os.environ.get("TEST_KILL_EPOCH", "-1"))
    KILL_RANK = int(os.environ.get("TEST_KILL_RANK", "-1"))
    FLAG = os.environ.get("TEST_KILL_FLAG", "")
    EPOCHS = int(os.environ.get("TEST_EPOCHS", "5"))
    BIG = (32 << 20) // 4  # 32 MiB: the shm collective runs long enough
                           # that a 50 ms-delayed SIGKILL lands mid-op

    @hvd.elastic.run
    def train(state):
        while state.epoch < EPOCHS:
            if (state.epoch == KILL_EPOCH and hvd.rank() == KILL_RANK
                    and hvd.size() > 1 and FLAG
                    and not os.path.exists(FLAG)):
                open(FLAG, "w").write("died")
                # Die MID-collective: enter the allreduce below normally
                # while a watchdog thread SIGKILLs this process partway
                # through, leaving the survivors inside the shm op.
                threading.Thread(
                    target=lambda: (time.sleep(0.05),
                                    os.kill(os.getpid(), 9)),
                    daemon=True).start()
            val = hvd.allreduce(np.ones(BIG, np.float32),
                                name=f"big.{state.epoch}")
            state.total += float(val[0])
            port = os.environ.get("HOROVOD_GLOO_RENDEZVOUS_PORT", "0")
            if os.path.exists(f"/dev/shm/hvd_{port}_0"):
                print(f"SHM-ACTIVE rank={hvd.rank()} port={port}",
                      flush=True)
            state.epoch += 1
            state.commit()
        return state.total

    total = train(state)
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} "
          f"epoch={state.epoch} total={total}")
    hvd.shutdown()
""")


def _shm_files():
    try:
        return {f for f in os.listdir("/dev/shm") if f.startswith("hvd_")}
    except OSError:
        return set()


def _run_shm_crash(kill_rank, env_extra=None, body=None, expect_shm=True):
    """VERDICT r3 #7: SIGKILL a worker mid-collective; survivors must
    surface the tombstone (no deadlock), restore, and recover.  With the
    shm plane active the next generation must re-open a FRESH region —
    with no stale /dev/shm file left when the job ends."""
    before = _shm_files()
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(body or SHM_CRASH_WORKER)
        flag = os.path.join(td, "killed.flag")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update({"TEST_KILL_EPOCH": "1", "TEST_KILL_RANK": str(kill_rank),
                    "TEST_KILL_FLAG": flag})
        env.update(env_extra or {})
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               "--min-np", "1", "-np", "3", "-H", "localhost:3", "--verbose",
               sys.executable, script]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=240, env=env, cwd=td)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert os.path.exists(flag), "kill hook never fired"
    assert "epoch=5" in proc.stdout, proc.stdout
    if expect_shm:
        # The shm plane was active (region present during collectives).
        assert "SHM-ACTIVE" in proc.stdout, proc.stdout
    else:
        # The disable must actually bite, or this silently re-tests shm.
        assert "SHM-ACTIVE" not in proc.stdout, proc.stdout
    # The post-kill generation re-formed.
    assert proc.stderr.count(" formed with ") >= 2, proc.stderr
    # No stale region file survives the run (the creator-death case would
    # leak without the unconditional unlink in ShmRegion teardown).
    # Only this job's regions count (hvd_<rendezvous port>_<n>, the ports
    # its workers reported): other tests run beside this one and own theirs.
    own_ports = set(re.findall(r"SHM-ACTIVE rank=\d+ port=(\d+)", proc.stdout))
    leaked = {f for f in _shm_files() - before
              if f.split("_")[1] in own_ports}
    assert not leaked, f"stale /dev/shm regions: {leaked}"
    return proc


def test_elastic_shm_crash_highest_rank():
    _run_shm_crash(kill_rank=2)


def test_elastic_chain_broadcast_crash_recovers():
    """Worker death mid-chain-broadcast on the TCP plane: the pipelined
    chain's blocking hops must fail fast through the broken sockets (no
    abort polling inside SendAll/RecvAll), surface the tombstone, and
    recover.  Uses the shm-crash worker with shm disabled and a broadcast
    big enough (32 MiB > 1 MiB threshold) to ride the chain; rank 1 is an
    interior chain hop, so its death breaks both its upstream's send and
    its downstream's recv."""
    body = SHM_CRASH_WORKER.replace(
        "hvd.allreduce(np.ones(BIG, np.float32),",
        "hvd.broadcast(np.ones(BIG, np.float32), root_rank=0,")
    assert "hvd.broadcast(np.ones(BIG" in body  # replace really matched
    _run_shm_crash(kill_rank=1, env_extra={"HOROVOD_SHM_DISABLE": "1"},
                   body=body, expect_shm=False)


def test_elastic_shm_crash_region_creator():
    # Rank 0 is both the shm region creator and the negotiation
    # coordinator — its death must still unwedge survivors and leave no
    # orphaned region.
    _run_shm_crash(kill_rank=0)


def test_elastic_survives_repeated_kills():
    """Chaos: the highest rank dies at epoch 1 AND the (respawned) highest
    rank dies again at epoch 3.  With the blacklist threshold raised via
    env, the driver re-forms twice and training still completes."""
    with tempfile.TemporaryDirectory() as td:
        f1 = os.path.join(td, "k1.flag")
        f2 = os.path.join(td, "k2.flag")
        proc = _run_launcher(
            ["--min-np", "1", "-np", "2", "-H", "localhost:2", "--verbose"],
            env_extra={"TEST_KILLS": f"1:{f1},3:{f2}",
                       "HOROVOD_ELASTIC_BLACKLIST_FAILURES": "10"})
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "epoch=6" in proc.stdout
        assert os.path.exists(f1) and os.path.exists(f2), proc.stderr
        # Two deaths -> at least three formations.
        assert proc.stderr.count(" formed with ") >= 3, proc.stderr
