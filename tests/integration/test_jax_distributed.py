"""Multi-host (multi-process) SPMD through hvd.init(): the DCN control
plane + cross-process ICI-analog data plane (SURVEY.md §2.8 — the TPU
equivalent of the reference's NCCL+MPI multi-node path), validated with
two CPU processes whose devices form one global mesh."""

import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    try:                     # same jax-version drift shim as device_plane
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    import horovod_tpu as hvd

    hvd.init()   # jax.distributed via HOROVOD_JAX_DISTRIBUTED + coordinator
    assert jax.process_count() == 2, jax.process_count()
    mesh = hvd.parallel.global_mesh()
    assert mesh is not None and mesh.devices.size == 2

    # One global array sharded over both processes; psum through hvd API.
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("hvd")),
        np.full((2, 4), float(hvd.rank() + 1), np.float32))
    out = jax.jit(shard_map(
        lambda s: hvd.allreduce(s, axis_name="hvd", op=hvd.Sum),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd")))(arr)
    local = np.asarray([s.data for s in out.addressable_shards])
    assert np.allclose(local, 3.0), local

    # Eager spine still works alongside the jax.distributed runtime.
    r = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="mh")
    assert np.allclose(np.asarray(r), 2.0), r
    print(f"MULTIHOST OK rank={hvd.rank()}")
    hvd.shutdown()

    # Elastic-reset shape 1: same (coordinator, size, rank) — the
    # process-level jax.distributed runtime is reused across the cycle.
    # Real elastic generations get a FRESH rendezvous port from the driver
    # (back-to-back cycles on one fixed port race each other's teardown);
    # derive one deterministically the same way on both workers.
    base_port = int(os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"])
    os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(base_port + 1)
    hvd.init()
    assert jax.process_count() == 2
    r = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="mh2")
    assert np.allclose(np.asarray(r), 2.0), r
    print(f"REINIT OK rank={hvd.rank()}")
    hvd.shutdown()

    # Elastic-reset shape 2: rank reassignment (0 <-> 1) forces a full
    # jax.distributed teardown + re-initialize in the same process.
    old_rank = int(os.environ["HOROVOD_RANK"])
    os.environ["HOROVOD_RANK"] = str(1 - old_rank)
    os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(base_port + 2)
    hvd.init()
    assert jax.process_count() == 2
    assert hvd.rank() == 1 - old_rank
    r = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="mh3")
    assert np.allclose(np.asarray(r), 2.0), r
    print(f"RERANK OK rank={hvd.rank()}")
    hvd.shutdown()
""")


def test_multihost_mesh_np2():
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # conftest forces 8 virtual devices per process for single-process
        # tests; here each worker must own exactly one device so the global
        # mesh is 2 processes x 1 device.
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
             "--jax-distributed", sys.executable, script],
            capture_output=True, text=True, timeout=180, env=env, cwd=td)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert proc.stdout.count("MULTIHOST OK") >= 2, proc.stdout
        assert proc.stdout.count("REINIT OK") >= 2, proc.stdout
        assert proc.stdout.count("RERANK OK") >= 2, proc.stdout
