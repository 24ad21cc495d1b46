"""Elastic integration tests in the reference's shape (SURVEY.md §4):
multi-process on localhost via the launcher, scripted discovery, and
worker death by self-SIGKILL mid-training (elastic_common.py patterns).

Completion, recovery from one worker's death (jax and torch workers), the
blacklist and min-np; scripted discovery is in test_elastic_discovery.py, the
shm-plane crashes and repeated kills in test_elastic_crash.py.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

from _elastic_helpers import REPO, _run_launcher


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
