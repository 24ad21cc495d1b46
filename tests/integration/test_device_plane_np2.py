"""Eager device plane end-to-end at np=2: the negotiated ``device`` bit
drives every rank to dispatch the same cached jitted fused collective over
a one-device-per-rank mesh (reference analog: ops/nccl_operations.cc — the
eager data plane executes on the accelerator; SURVEY.md §2.2).

Two CPU processes under jax.distributed stand in for two TPU hosts: the
jitted psum rides jax's cross-process CPU transport the way it rides ICI on
a pod — same programs, same negotiation, same dispatch path.
"""

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
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    hvd.init()
    assert jax.process_count() == 2, jax.process_count()
    rank = hvd.rank()
    stats = HorovodContext.instance().device_plane.stats

    # Device-negotiated fused allreduce: jax.Array in, jax.Array out,
    # executed as a jitted psum over the rank mesh (no host TCP ring).
    x = jnp.full((3, 4), float(rank + 1), jnp.float32)
    r = hvd.allreduce(x, op=hvd.Sum, name="devsum")
    assert isinstance(r, jax.Array), type(r)
    assert np.allclose(np.asarray(r), 3.0), np.asarray(r)
    assert stats["allreduce"] == 1, stats

    # Grouped -> one fused device bucket.
    outs = hvd.grouped_allreduce(
        [jnp.full((4,), float(rank + i), jnp.float32) for i in range(6)],
        op=hvd.Sum, name="devgroup")
    for i, o in enumerate(outs):
        assert np.allclose(np.asarray(o), 2.0 * i + 1.0), (i, np.asarray(o))

    # Steady state: the same bucket class reuses the compiled program.
    built = stats["programs_built"]
    for it in range(5):
        g = hvd.allreduce(x, op=hvd.Sum, name="steady")
        assert np.allclose(np.asarray(g), 3.0)
    assert stats["programs_built"] == built, stats

    # Reduce-op coverage on the device plane.
    assert np.allclose(np.asarray(hvd.allreduce(x, op=hvd.Average,
                                                name="devavg")), 1.5)
    assert np.allclose(np.asarray(hvd.allreduce(x, op=hvd.Min,
                                                name="devmin")), 1.0)
    assert np.allclose(np.asarray(hvd.allreduce(x, op=hvd.Max,
                                                name="devmax")), 2.0)
    assert np.allclose(np.asarray(hvd.allreduce(x, op=hvd.Product,
                                                name="devprod")), 2.0)
    assert np.allclose(np.asarray(hvd.allreduce(
        x, op=hvd.Sum, name="devscale",
        prescale_factor=0.5, postscale_factor=3.0)), 4.5)

    # Reducescatter on the device plane: rows divisible by 2 -> device
    # psum_scatter; rank p keeps rows [2p, 2p+2) of the sum.
    base = jnp.arange(8.0, dtype=jnp.float32).reshape(4, 2)
    rs = hvd.reducescatter(base, op=hvd.Sum, name="devrs")
    assert isinstance(rs, jax.Array)
    assert np.allclose(np.asarray(rs),
                       2.0 * np.asarray(base)[2 * rank:2 * rank + 2]), rs
    assert stats.get("reducescatter", 0) == 1, stats
    # Non-divisible first dim (3 rows over 2 ranks) -> host plane, with the
    # reference's extra-row slicing.
    odd = jnp.arange(6.0, dtype=jnp.float32).reshape(3, 2)
    ro = hvd.reducescatter(odd, op=hvd.Sum, name="devrs.odd")
    expect = 2.0 * np.arange(6.0, dtype=np.float32).reshape(3, 2)
    mine = expect[:2] if rank == 0 else expect[2:]
    assert np.allclose(np.asarray(ro), mine), np.asarray(ro)
    assert stats.get("reducescatter", 0) == 1, stats  # still one (host path)

    # Broadcast on the device plane, each root.
    for root in range(2):
        b = hvd.broadcast(jnp.full((4,), float(rank * 10), jnp.float32),
                          root_rank=root, name=f"devbc{root}")
        assert np.allclose(np.asarray(b), float(root * 10)), np.asarray(b)

    # Mixed planes: one rank submits numpy -> the coordinator ANDs the
    # device bits to 0 and BOTH ranks ride the host plane, correctly.
    if rank == 0:
        m = hvd.allreduce(np.full((2,), 5.0, np.float32), op=hvd.Sum,
                          name="mixed")
    else:
        m = hvd.allreduce(jnp.full((2,), 7.0, jnp.float32), op=hvd.Sum,
                          name="mixed")
    assert np.allclose(np.asarray(m), 12.0), np.asarray(m)
    assert stats["host_fallback"] == (1 if rank == 1 else 0), (rank, stats)

    # Allgather on the device plane: equal dims, then ragged dims (rank 0
    # contributes 1 row, rank 1 three rows) — the payload stays a
    # jax.Array, only int64 counts cross the host ctrl channel.
    ag = hvd.allgather(jnp.full((2, 3), float(rank), jnp.float32),
                       name="devag")
    assert isinstance(ag, jax.Array), type(ag)
    expect_ag = np.repeat([0.0, 1.0], 2)[:, None] * np.ones(3)
    assert np.allclose(np.asarray(ag), expect_ag), np.asarray(ag)
    assert stats.get("allgather", 0) == 1, stats
    nrag = 1 if rank == 0 else 3
    agr = hvd.allgather(jnp.full((nrag, 2), float(rank), jnp.float32),
                        name="devag.ragged")
    expect_ragged = np.concatenate(
        [np.zeros((1, 2)), np.ones((3, 2))]).astype(np.float32)
    assert np.allclose(np.asarray(agr), expect_ragged), np.asarray(agr)
    assert stats.get("allgather", 0) == 2, stats
    # Zero-row contribution from rank 0 (regression: -1 reshapes are
    # ambiguous on size-0 arrays).
    nz = 0 if rank == 0 else 2
    agz = hvd.allgather(jnp.full((nz, 2), 9.0, jnp.float32),
                        name="devag.zero")
    assert np.allclose(np.asarray(agz), 9.0 * np.ones((2, 2))), agz
    assert np.asarray(agz).shape == (2, 2), agz.shape

    # Alltoall on the device plane: uniform splits (one all_to_all), then
    # ragged splits (pad-to-max exchange).  recv_splits mirror the host
    # plane's contract.
    send = jnp.arange(4.0, dtype=jnp.float32).reshape(4, 1) + 10.0 * rank
    a2a, rsp = hvd.alltoall(send, name="deva2a")
    assert isinstance(a2a, jax.Array), type(a2a)
    expect_a2a = (np.concatenate([np.arange(2.0), np.arange(2.0) + 10.0])
                  + 2.0 * rank).reshape(4, 1).astype(np.float32)
    assert np.allclose(np.asarray(a2a), expect_a2a), np.asarray(a2a)
    assert np.array_equal(np.asarray(rsp), [2, 2]), rsp
    assert stats.get("alltoall", 0) == 1, stats
    # Ragged: rank 0 sends [1, 2] rows, rank 1 sends [3, 0].
    my_splits = [1, 2] if rank == 0 else [3, 0]
    sendr = jnp.full((3, 2), float(rank + 1), jnp.float32)
    ar, rspr = hvd.alltoall(sendr, splits=my_splits, name="deva2a.ragged")
    if rank == 0:
        expect_r = np.concatenate([np.ones((1, 2)), 2.0 * np.ones((3, 2))])
        expect_split = [1, 3]
    else:
        expect_r = np.ones((2, 2))
        expect_split = [2, 0]
    assert np.allclose(np.asarray(ar), expect_r.astype(np.float32)), (
        rank, np.asarray(ar))
    assert np.array_equal(np.asarray(rspr), expect_split), rspr
    assert stats.get("alltoall", 0) == 2, stats

    # join(): device traffic keeps flowing while rank 1 is joined — the
    # coordinator demotes via-join responses to the host plane so the
    # joined rank can zero-participate.
    if rank == 0:
        j = hvd.allreduce(jnp.full((3,), 4.0, jnp.float32), op=hvd.Sum,
                          name="joinsum")
        assert np.allclose(np.asarray(j), 4.0), np.asarray(j)
        hvd.join()
    else:
        hvd.join()

    assert stats["allreduce"] >= 8, stats
    print(f"DEVPLANE OK rank={rank} stats={stats}")
    hvd.shutdown()
""")


def test_device_plane_np2():
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # One device per worker process: the rank mesh is 2 processes x 1.
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
             "--jax-distributed", sys.executable, script],
            capture_output=True, text=True, timeout=240, env=env, cwd=td)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert proc.stdout.count("DEVPLANE OK") == 2, proc.stdout
