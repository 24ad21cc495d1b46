"""Elastic integration tests in the reference's shape (SURVEY.md §4):
multi-process on localhost via the launcher, scripted discovery, and
worker death by self-SIGKILL mid-training (elastic_common.py patterns)."""

import os
import re
import subprocess
import sys
import tempfile
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKER_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ObjectState(epoch=0, total=0.0)

    PRE_KILL_TOUCH = os.environ.get("TEST_PRE_KILL_TOUCH", "")
    # One or more scripted self-kills: "epoch:flagfile" pairs; each fires
    # once (the flag file records that the death already happened).
    KILLS = []
    if os.environ.get("TEST_KILL_EPOCH", "-1") != "-1":
        KILLS.append((int(os.environ["TEST_KILL_EPOCH"]),
                      os.environ.get("TEST_KILL_FLAG", "")))
    for spec in os.environ.get("TEST_KILLS", "").split(","):
        if spec:
            ep, flag = spec.split(":", 1)
            KILLS.append((int(ep), flag))

    # Scale-up hook: at TEST_GROW_EPOCH, rank 0 rewrites the discovery
    # file with TEST_GROW_CONTENT (once — guarded by TEST_GROW_FLAG),
    # mirroring the reference's "new hosts are new lines in the file"
    # pattern (elastic_common.py, SURVEY.md §4.2).
    GROW_EPOCH = int(os.environ.get("TEST_GROW_EPOCH", "-1"))
    GROW_FILE = os.environ.get("TEST_GROW_FILE", "")
    GROW_CONTENT = os.environ.get("TEST_GROW_CONTENT", "")
    GROW_FLAG = os.environ.get("TEST_GROW_FLAG", "")
    EPOCHS = int(os.environ.get("TEST_EPOCHS", "6"))
    EPOCH_SLEEP = float(os.environ.get("TEST_EPOCH_SLEEP", "0"))

    @hvd.elastic.run
    def train(state):
        import time
        while state.epoch < EPOCHS:
            for ep, flag in KILLS:
                if (state.epoch == ep and hvd.rank() == hvd.size() - 1
                        and hvd.size() > 1 and flag
                        and not os.path.exists(flag)):
                    if PRE_KILL_TOUCH:
                        open(PRE_KILL_TOUCH, "w").write("x")
                    open(flag, "w").write("died")
                    os.kill(os.getpid(), 9)
            if (state.epoch >= GROW_EPOCH and GROW_EPOCH >= 0
                    and hvd.rank() == 0 and GROW_FILE
                    and not os.path.exists(GROW_FLAG)):
                open(GROW_FLAG, "w").write("grown")
                open(GROW_FILE, "w").write(GROW_CONTENT + "\\n")
            val = hvd.allreduce(np.ones(4, np.float32),
                                name=f"step.{state.epoch}")
            state.total += float(val.sum())
            state.epoch += 1
            state.commit()
            if EPOCH_SLEEP:
                time.sleep(EPOCH_SLEEP)
        return state.total

    total = train(state)
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} "
          f"epoch={state.epoch} total={total} "
          f"host={os.environ.get('HOROVOD_HOSTNAME', '?')}")
    hvd.shutdown()
""")


def _run_launcher(extra_args, env_extra=None, timeout=180):
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(WORKER_SCRIPT)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update(env_extra or {})
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               *extra_args, sys.executable, script]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=td)
        return proc


def test_elastic_basic_completion():
    """Two workers, fixed hosts, no failures: trains to epoch 6."""
    proc = _run_launcher(["--min-np", "2", "-np", "2", "-H", "localhost:2",
                          "--verbose"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "RESULT" in proc.stdout
    assert "epoch=6" in proc.stdout
    # Regression: registrations racing the first formation used to leave a
    # stale poke that re-formed (and restarted training) once per run.
    assert proc.stderr.count(" formed with ") == 1, proc.stderr


def test_elastic_worker_failure_recovers():
    """The highest rank SIGKILLs itself at epoch 2; the driver re-forms the
    job (respawn on the same host) and training completes."""
    with tempfile.NamedTemporaryFile(suffix=".flag", delete=True) as tf:
        flag = tf.name
    proc = _run_launcher(
        ["--min-np", "1", "-np", "2", "-H", "localhost:2", "--verbose"],
        env_extra={"TEST_KILL_EPOCH": "2", "TEST_KILL_FLAG": flag})
    try:
        os.unlink(flag)
    except OSError:
        pass
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "epoch=6" in proc.stdout


def test_elastic_discovery_script():
    """Hosts come from a discovery script (reference: HostDiscoveryScript)."""
    with tempfile.TemporaryDirectory() as td:
        hosts_file = os.path.join(td, "hosts.txt")
        with open(hosts_file, "w") as f:
            f.write("localhost:2\n")
        proc = _run_launcher(
            ["--min-np", "2", "--host-discovery-script",
             f"cat {hosts_file}", "--verbose"])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "epoch=6" in proc.stdout


def test_elastic_discovery_blip_reuses_last_hosts():
    """A transient discovery failure during a re-formation must not tear
    down the job: the driver reuses the last good host set.  The dying
    worker flips the discovery script into failure mode right before
    SIGKILLing itself, so the respawn round's discovery call fails."""
    with tempfile.TemporaryDirectory() as td:
        fail_flag = os.path.join(td, "fail.flag")
        kill_flag = os.path.join(td, "killed.flag")
        script = os.path.join(td, "discover.sh")
        with open(script, "w") as f:
            f.write(f"#!/bin/sh\nif [ -e {fail_flag} ]; then exit 1; fi\n"
                    "echo localhost:2\n")
        os.chmod(script, 0o755)
        proc = _run_launcher(
            ["--min-np", "1", "--host-discovery-script", script,
             "--verbose"],
            env_extra={"TEST_KILL_EPOCH": "2", "TEST_KILL_FLAG": kill_flag,
                       "TEST_PRE_KILL_TOUCH": fail_flag})
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "epoch=6" in proc.stdout
        assert "reusing previous host set" in proc.stderr, proc.stderr


def test_elastic_scale_up_absorbs_new_slot():
    """VERDICT r2 #5: the discovery file GROWS mid-training (2 -> 3 slots).
    The driver must notice, push hosts_updated, spawn the extra worker,
    and form the next generation with np+1, contiguous ranks, and state
    synced from rank 0 (all workers report the same epoch/total)."""
    with tempfile.TemporaryDirectory() as td:
        hosts_file = os.path.join(td, "hosts.txt")
        with open(hosts_file, "w") as f:
            f.write("localhost:2\n")
        grow_flag = os.path.join(td, "grown.flag")
        proc = _run_launcher(
            ["--min-np", "1", "--max-np", "3", "--host-discovery-script",
             f"cat {hosts_file}", "--verbose"],
            env_extra={"TEST_GROW_EPOCH": "1",
                       "TEST_GROW_FILE": hosts_file,
                       "TEST_GROW_CONTENT": "localhost:3",
                       "TEST_GROW_FLAG": grow_flag,
                       "TEST_EPOCHS": "8",
                       "TEST_EPOCH_SLEEP": "0.5"},
            timeout=240)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert os.path.exists(grow_flag), "grow hook never fired"
        results = [ln for ln in proc.stdout.splitlines() if "RESULT" in ln]
        assert len(results) == 3, proc.stdout + proc.stderr
        ranks = sorted(int(ln.split("rank=")[1].split()[0])
                       for ln in results)
        assert ranks == [0, 1, 2], results          # contiguous ranks
        assert all("size=3" in ln for ln in results), results  # np+1
        assert all("epoch=8" in ln for ln in results), results
        totals = {ln.split("total=")[1].split()[0] for ln in results}
        assert len(totals) == 1, results  # state synced from rank 0
        assert " formed with 3 " in proc.stderr, proc.stderr


def test_elastic_scale_up_adds_remote_host():
    """VERDICT r3 weak #5: scale-up onto a NEW HOST, not just a new slot.
    127.0.0.2 routes to loopback but is not in local_hostnames(), so the
    driver takes the real remote-spawn path — preflight, env forwarding
    with the HMAC secret over stdin, coordinator address exchange — via a
    fake-ssh transport (HOROVOD_SSH_COMMAND; the sandbox has no sshd)
    that executes the remote command locally."""
    with tempfile.TemporaryDirectory() as td:
        hosts_file = os.path.join(td, "hosts.txt")
        with open(hosts_file, "w") as f:
            f.write("localhost:2\n")
        ssh_log = os.path.join(td, "ssh.log")
        fake_ssh = os.path.join(td, "fakessh.sh")
        with open(fake_ssh, "w") as f:
            # argv: <host> <remote-shell-string>
            f.write(f"#!/bin/sh\necho \"$1\" >> {ssh_log}\nshift\n"
                    "exec sh -c \"$1\"\n")
        os.chmod(fake_ssh, 0o755)
        grow_flag = os.path.join(td, "grown.flag")
        proc = _run_launcher(
            ["--min-np", "1", "--max-np", "3", "--host-discovery-script",
             f"cat {hosts_file}", "--verbose"],
            env_extra={"TEST_GROW_EPOCH": "1",
                       "TEST_GROW_FILE": hosts_file,
                       "TEST_GROW_CONTENT": "localhost:2\n127.0.0.2:1",
                       "TEST_GROW_FLAG": grow_flag,
                       "TEST_EPOCH_SLEEP": "0.5",
                       "HOROVOD_SSH_COMMAND": fake_ssh},
            timeout=240)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert os.path.exists(grow_flag), "grow hook never fired"
        # The fake transport really carried the spawn for the new host.
        with open(ssh_log) as f:
            assert "127.0.0.2" in f.read()
        results = [ln for ln in proc.stdout.splitlines() if "RESULT" in ln]
        assert len(results) == 3, proc.stdout + proc.stderr
        assert all("size=3" in ln for ln in results), results
        # TEST_* env is deliberately NOT ssh-forwarded, so every worker
        # runs the default 6 epochs; the remote one reports its host.
        assert all("epoch=6" in ln for ln in results), results
        remote = [ln for ln in results if "host=127.0.0.2" in ln]
        assert len(remote) == 1, results
        assert " formed with 3 " in proc.stderr, proc.stderr


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


def test_elastic_discovery_flap_within_one_poll():
    """VERDICT r4 #8a: discovery adds a slot and removes it again within
    one poll interval (exactly ONE discovery invocation sees the larger
    set).  The driver re-checks discovery at formation time, so the flap
    must be a no-op: no extra worker, no re-formation, training undisturbed."""
    with tempfile.TemporaryDirectory() as td:
        grow_flag = os.path.join(td, "grow.flag")
        seen_flag = os.path.join(td, "seen.flag")
        script = os.path.join(td, "discover.sh")
        with open(script, "w") as f:
            f.write(f"#!/bin/sh\n"
                    f"if [ -e {grow_flag} ] && [ ! -e {seen_flag} ]; then\n"
                    f"  touch {seen_flag}\n"
                    f"  echo localhost:3\n"
                    f"else\n"
                    f"  echo localhost:2\n"
                    f"fi\n")
        os.chmod(script, 0o755)
        proc = _run_launcher(
            ["--min-np", "2", "--max-np", "3", "--host-discovery-script",
             script, "--verbose"],
            env_extra={
                # The worker's grow hook fires the flap mid-training (it
                # only touches the flag; the discovery script self-reverts
                # after a single sighting).
                "TEST_GROW_EPOCH": "1",
                "TEST_GROW_FILE": os.path.join(td, "unused.txt"),
                "TEST_GROW_CONTENT": "ignored",
                "TEST_GROW_FLAG": grow_flag,
                "TEST_EPOCHS": "6",
                "TEST_EPOCH_SLEEP": "0.7",
            },
            timeout=240)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert os.path.exists(seen_flag), "flap never reached discovery"
        results = [ln for ln in proc.stdout.splitlines() if "RESULT" in ln]
        assert len(results) == 2, proc.stdout  # no third worker survived
        assert all("size=2" in ln and "epoch=6" in ln for ln in results)
        # The flap resolved before formation: exactly the initial one.
        assert proc.stderr.count(" formed with ") == 1, proc.stderr


def test_blacklist_sentence_expires_and_backs_off():
    """The blacklist is a sentence, not a death warrant: entries expire
    after BLACKLIST_BASE_SECS, each repeat offence doubles the sentence,
    and the doubling caps at 64x.  Driven directly with an injected clock
    (no processes)."""
    from horovod_tpu.runner import elastic_driver as ed

    drv = ed.ElasticDriver(ed.FixedHosts({"badhost": 2}), ["true"],
                           min_np=1, max_np=None)
    t = [1000.0]
    drv._clock = lambda: t[0]
    base = ed.BLACKLIST_BASE_SECS

    assert drv._blacklist_host("badhost", t[0]) == base
    assert drv._blacklisted("badhost")
    assert "badhost" not in drv._target_hosts()   # filtered while serving
    t[0] += base - 1
    assert drv._blacklisted("badhost")            # still serving
    t[0] += 2
    assert not drv._blacklisted("badhost")        # sentence served
    assert drv._target_hosts() == {"badhost": 2}  # back in the pool

    # Repeat offence: the count persisted, so the sentence doubles...
    assert drv._blacklist_host("badhost", t[0]) == 2 * base
    t[0] += 2 * base + 1
    assert not drv._blacklisted("badhost")
    # ...and keeps doubling up to the 64x cap, never beyond.
    for _ in range(10):
        duration = drv._blacklist_host("badhost", t[0])
    assert duration == 64 * base
    # An unrelated host starts at the base sentence.
    assert drv._blacklist_host("otherhost", t[0]) == base


def test_elastic_min_np_not_met_fails_cleanly():
    """VERDICT r4 #8b: repeated fast worker deaths blacklist the only
    host; with min-np unreachable the driver must fail the job cleanly
    (non-zero exit, named reason) instead of hanging — blacklist intact."""
    with tempfile.TemporaryDirectory() as td:
        f1 = os.path.join(td, "k1.flag")
        f2 = os.path.join(td, "k2.flag")
        f3 = os.path.join(td, "k3.flag")
        proc = _run_launcher(
            ["--min-np", "2", "-np", "2", "-H", "localhost:2",
             "--start-timeout", "10", "--verbose"],
            env_extra={
                "TEST_KILLS": f"1:{f1},2:{f2},3:{f3}",
                "TEST_EPOCHS": "30",
                "TEST_EPOCH_SLEEP": "0.3",
                # Default threshold (2 fast failures) blacklists localhost.
            },
            timeout=240)
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "blacklisting host localhost" in proc.stderr, proc.stderr
        assert "could not reach min_np=2" in proc.stderr, proc.stderr
        # Clean failure, not a partial success: no worker reached the end.
        assert "epoch=30" not in proc.stdout


TORCH_WORKER_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import torch
    import horovod_tpu.torch as hvd
    from horovod_tpu.torch.elastic import TorchState

    hvd.init(build_mesh=False)

    torch.manual_seed(40 + hvd.rank())  # diverged init; sync() aligns
    model = torch.nn.Linear(4, 2)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    state = TorchState(model=model, optimizer=opt, epoch=0)

    KILL_EPOCH = int(os.environ.get("TEST_KILL_EPOCH", "-1"))
    KILL_FLAG = os.environ.get("TEST_KILL_FLAG", "")
    EPOCHS = int(os.environ.get("TEST_EPOCHS", "6"))

    torch.manual_seed(7)  # same data everywhere
    x = torch.randn(16, 4)
    y = torch.randn(16, 2)

    @hvd.elastic.run
    def train(state):
        while state.epoch < EPOCHS:
            if (state.epoch == KILL_EPOCH and hvd.rank() == hvd.size() - 1
                    and hvd.size() > 1 and KILL_FLAG
                    and not os.path.exists(KILL_FLAG)):
                open(KILL_FLAG, "w").write("died")
                os.kill(os.getpid(), 9)
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            state.epoch += 1
            state.commit()
        return float(loss.detach())

    loss = train(state)
    w = model.weight.detach().reshape(1, -1)
    g = hvd.allgather(w, name="final.w")
    in_sync = bool(np.allclose(g[0].numpy(), g[-1].numpy()))
    print(f"RESULT rank={hvd.rank()} size={hvd.size()} "
          f"epoch={state.epoch} loss={loss:.4f} in_sync={in_sync}")
    hvd.shutdown()
""")


def test_elastic_torch_worker_failure_recovers():
    """Torch-binding elastic loop: TorchState commit/restore/sync through a
    mid-training SIGKILL; training resumes, completes, and ends with
    identical parameters on every rank."""
    with tempfile.NamedTemporaryFile(suffix=".flag", delete=True) as tf:
        flag = tf.name
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "torch_worker.py")
        with open(script, "w") as f:
            f.write(TORCH_WORKER_SCRIPT)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["TEST_KILL_EPOCH"] = "2"
        env["TEST_KILL_FLAG"] = flag
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               "--min-np", "1", "-np", "2", "-H", "localhost:2",
               "--verbose", sys.executable, script]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=240, env=env, cwd=td)
    killed = os.path.exists(flag)
    try:
        os.unlink(flag)
    except OSError:
        pass
    assert killed, "kill hook never fired"
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "epoch=6" in proc.stdout
    # Re-formation back to 2 ranks (a 1-rank finish would make in_sync
    # trivially true) and parameter lockstep on both.
    assert "size=2" in proc.stdout
    assert proc.stdout.count("in_sync=True") == 2, proc.stdout
