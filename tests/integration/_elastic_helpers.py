"""What the elastic integration tests share: the worker every launcher run
trains with and the launcher call itself.  Not a test file; test_elastic.py,
test_elastic_discovery.py and test_elastic_crash.py import it (pytest puts
``tests/integration`` on the path)."""

import os
import subprocess
import sys
import tempfile
import textwrap

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
