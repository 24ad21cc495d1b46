"""Host data plane: which path a collective takes, counted, and one timing.

On one host the shared-memory plane carries a collective; with
``HOROVOD_SHM_DISABLE=1`` (every cross-host job) the chunk-pipelined TCP ring,
the pipelined chain (broadcasts of 1 MiB and more among three or more
members) or the binomial tree does.  The cases below read the path from what
``HOROVOD_METRICS=1`` counts, ``ring_hop_us`` (one entry a chunk-exchange
hop), ``shm_fence_us`` (one a shm barrier) and the data plane's bytes sent
through sockets, and hold the results to closed forms.  No case of the
tier-1 gate times anything: beside five busy pytest-xdist workers a ratio of
two wall times inverts on a sound tree (ROADMAP.md, D2).  The one timing case
is marked ``slow``.
"""

import pytest

from horovod_tpu.runner import run

_TCP = {"HOROVOD_SHM_DISABLE": "1", "HOROVOD_METRICS": "1"}


def _counted(fn):
    """Run ``fn`` and return (its value, ring hops, shm fences, data-plane
    bytes this rank sent through sockets) counted across the call."""
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    core = HorovodContext.instance().core

    def read():
        h = hvd.metrics()["histograms"]
        return (h["ring_hop_us"]["count"], h["shm_fence_us"]["count"],
                core.data_plane_stats()["data_sent_local"])

    before = read()
    out = fn()
    return (out,) + tuple(b - a for a, b in zip(before, read()))


def _allreduce_worker():
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext
    from horovod_tpu.wire import ReduceOp

    hvd.init(build_mesh=False)
    ctx = HorovodContext.instance()
    x = np.full((4 << 20) // 4, float(hvd.rank() + 1), np.float32)  # 4 MiB
    hvd.barrier()
    out, hops, fences, sent = _counted(
        lambda: ctx.core.allreduce_buffer(x.copy(), 0, ReduceOp.SUM))
    np.testing.assert_array_equal(out, float(sum(range(1, hvd.size() + 1))))
    hvd.barrier()
    hvd.shutdown()
    return {"hops": hops, "fences": fences, "sent": sent}


def test_shm_plane_moves_the_bytes_and_the_ring_records_no_hop_np4():
    # Same host, default settings: the 4 MiB a rank are reduced through the
    # shared region between barriers, whose frames are all a socket carries.
    for r in run(_allreduce_worker, np=4, env={"HOROVOD_METRICS": "1"}):
        assert r["hops"] == 0 and r["fences"] > 0, r
        assert r["sent"] < 4096, r


@pytest.mark.parametrize("chunk_bytes", [None, "0"])
def test_pipelined_ring_is_the_tcp_path_the_default_takes_np4(chunk_bytes):
    # With shm off an allreduce crosses the chunk-pipelined ring: 2 (n - 1)
    # chunk-exchange hops on every rank that carry 2 (n - 1) / n of the
    # buffer.  HOROVOD_RING_CHUNK_BYTES=0 once chose a whole-segment format;
    # it is read as the default chunk now.
    env = dict(_TCP)
    if chunk_bytes is not None:
        env["HOROVOD_RING_CHUNK_BYTES"] = chunk_bytes
    for r in run(_allreduce_worker, np=4, env=env):
        assert r["hops"] == 6 and r["fences"] == 0, r
        assert 0 <= r["sent"] - 6 * (1 << 20) < 4096, r


def _bcast_worker():
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    sent = {}
    for nbytes in (32 << 20, 64 << 10):
        # Non-uniform root payload, full-array compare.
        n = nbytes // 4
        x = (np.arange(n) % 509 + 7.0 * r).astype(np.float32)
        hvd.barrier()
        out, hops, _, sent[nbytes] = _counted(
            lambda: hvd.broadcast(x, root_rank=0, name=f"b.{nbytes}"))
        assert hops == 0
        np.testing.assert_array_equal(
            np.asarray(out), (np.arange(n) % 509).astype(np.float32))
    hvd.barrier()
    hvd.shutdown()
    return sent


def test_large_broadcast_takes_the_chain_and_small_the_tree_np4():
    # Large broadcasts (the broadcast_parameters case) take the pipelined
    # chain: every member but the last forwards the payload once, where the
    # tree's root sends it log2(m) times.  Small ones keep the tree's fewer
    # hop latencies: rank 0 sends to ranks 2 and 1, rank 2 to rank 3.
    big, small = 32 << 20, 64 << 10
    res = run(_bcast_worker, np=4, env=_TCP)
    for r, (chain, tree) in enumerate(zip([1, 1, 1, 0], [2, 0, 1, 0])):
        assert 0 <= res[r][big] - chain * big < 4096, (r, res[r])
        assert 0 <= res[r][small] - tree * small < 4096, (r, res[r])


def _allgather_worker():
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    hvd.init(build_mesh=False)
    r, size = hvd.rank(), hvd.size()
    ctx = HorovodContext.instance()
    n = (8 << 20) // 4
    # 8 MiB a rank, no two ranks' blocks alike at any offset.
    x = (np.arange(n) % 509 + 1000.0 * r).astype(np.float32)
    hvd.barrier()
    (out, counts), hops, _, sent = _counted(
        lambda: ctx.core.allgather_buffer(x, 0))
    assert list(counts) == [n] * size  # elements/rank
    out = np.asarray(out).reshape(size, n)
    for rr in range(size):
        np.testing.assert_array_equal(out[rr], x - 1000.0 * (r - rr))
    hvd.barrier()
    hvd.shutdown()
    return {"hops": hops, "sent": sent}


def test_allgather_crosses_n_minus_1_chunked_hops_np4():
    # A size ring of 8-byte frames, then n - 1 chunk-pipelined hops that land
    # each block straight in its slot of the output.
    for r in run(_allgather_worker, np=4, env=_TCP):
        assert r["hops"] == 3, r
        assert 0 <= r["sent"] - 3 * (8 << 20) < 4096, r


def _timed_allreduce_worker():
    import time

    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext
    from horovod_tpu.wire import ReduceOp

    hvd.init(build_mesh=False)
    ctx = HorovodContext.instance()
    x = np.full((4 << 20) // 4, float(hvd.rank() + 1), np.float32)  # 4 MiB
    hvd.barrier()
    for _ in range(2):
        ctx.core.allreduce_buffer(x.copy(), 0, ReduceOp.SUM)
    t0 = time.perf_counter()
    iters = 8
    for _ in range(iters):
        ctx.core.allreduce_buffer(x.copy(), 0, ReduceOp.SUM)
    dt = (time.perf_counter() - t0) / iters
    hvd.barrier()
    hvd.shutdown()
    return dt * 1e3


# Outside the tier-1 gate (it runs wherever ``-m 'not slow'`` is not given):
# on loopback every byte of the ring is a CPU copy, so busy neighbours move
# the ratio more than the planes do.
@pytest.mark.slow
def test_shm_plane_beats_pipelined_tcp_ring():
    # Min over runs of the slowest rank, both sides measured again in each
    # round so that a burst of load that slowed one side does not survive.
    def best_ms(env):
        return min(max(run(_timed_allreduce_worker, np=4, env=env))
                   for _ in range(2))

    for _ in range(3):
        ring_ms = best_ms({"HOROVOD_SHM_DISABLE": "1"})
        shm_ms = best_ms(None)
        if ring_ms > 1.1 * shm_ms:
            return
    raise AssertionError(
        f"shm plane not faster after 3 rounds: ring={ring_ms:.1f}ms "
        f"shm={shm_ms:.1f}ms (margin 1.1x)")


def _shm_correctness_worker():
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    assert s == 3

    # allreduce across dtypes (shm ReduceInto path)
    for dt in (np.float32, np.float64, np.float16, np.int32, np.int64):
        v = (np.arange(5) + r).astype(dt)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"shm.ar.{np.dtype(dt).name}")
        expected = sum((np.arange(5) + rr).astype(dt) for rr in range(s))
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   expected.astype(np.float64))
    # min/max/product
    x = np.full(7, float(r + 1), np.float32)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Min, name="shm.min"),
                               1.0)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Max, name="shm.max"),
                               3.0)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Product,
                                             name="shm.prod"), 6.0)
    # ragged allgather (header size exchange + offsets)
    g = hvd.allgather(np.full((r + 1, 2), float(r), np.float32),
                      name="shm.ag")
    assert np.asarray(g).shape == (6, 2)
    np.testing.assert_allclose(np.asarray(g)[0], 0.0)
    np.testing.assert_allclose(np.asarray(g)[-1], 2.0)
    # broadcast from each root
    for root in range(s):
        out = hvd.broadcast(np.full(6, float(r), np.float64),
                            root_rank=root, name=f"shm.bc.{root}")
        np.testing.assert_allclose(out, float(root))
    # uneven alltoall (m*m header geometry)
    splits = [[1, 2, 1], [2, 1, 1], [1, 1, 2]][r]
    data = (np.arange(4, dtype=np.float32) + 10 * r).reshape(4, 1)
    out, rsplits = hvd.alltoall(data, splits=splits, name="shm.a2a")
    assert int(np.asarray(rsplits).sum()) == np.asarray(out).shape[0]
    # growth: a payload far bigger than the initial region
    big = np.full((3 << 20) // 4, float(r), np.float32)
    out = hvd.allreduce(big, op=hvd.Sum, name="shm.grow")
    np.testing.assert_allclose(np.asarray(out)[:4], 3.0)
    # a process set gets its own region (channel + shm)
    ps = hvd.add_process_set([0, 2])
    if r in (0, 2):
        out = hvd.allreduce(np.full(9, float(r), np.float32), op=hvd.Sum,
                            process_set=ps, name="shm.ps")
        np.testing.assert_allclose(out, 2.0)
    hvd.barrier()
    hvd.shutdown()
    return r


def test_shm_collectives_correct_np3():
    assert run(_shm_correctness_worker, np=3) == [0, 1, 2]
