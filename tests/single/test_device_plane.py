"""Eager device data plane (ops/device_plane.py): np=1 no-host-copy
guarantee, the fused collective programs on a simulated multi-rank mesh,
and the program cache.

Reference analog being covered: the NCCL ops path of
horovod/common/ops/nccl_operations.cc — eager collectives execute ON the
accelerator with a device-resident fused buffer (SURVEY.md §2.2, §7).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops.device_plane import AXIS, DevicePlane, bucket_len
from horovod_tpu.wire import ReduceOp


class _FakeCore:
    """A 4-rank world for driving the plane's program stack locally."""

    def __init__(self, n=4):
        self._n = n

    def size(self):
        return self._n

    def rank(self):
        return 0

    def process_set_ranks(self, psid):
        return list(range(self._n))


@pytest.fixture()
def transfer_guard():
    """Fail the test on ANY implicit host<->device transfer once armed
    (global config: the executor thread must be covered too).  Tests arm
    AFTER creating their device inputs — eager jnp.full()'s fill scalar is
    itself a transfer."""

    def arm():
        jax.config.update("jax_transfer_guard", "disallow")

    try:
        yield arm
    finally:
        jax.config.update("jax_transfer_guard", "allow")


def test_bucket_len_size_classes():
    assert bucket_len(1) == 1024
    assert bucket_len(1024) == 1024
    assert bucket_len(1025) == 1280  # 1.25 * 1024
    assert bucket_len(1300) == 1536
    assert bucket_len(1537) == 1792
    assert bucket_len(1793) == 2048
    # <= 25% padding everywhere
    for n in (3000, 50_000, 123_457, 1 << 20):
        L = bucket_len(n)
        assert L >= n and L <= n * 1.25 + 1


def test_np1_device_allreduce_no_host_copy(hvd_single, transfer_guard):
    """The VERDICT 'done' criterion: eager hvd.allreduce of a sharded array
    executes with no host copy — asserted by jax's transfer guard covering
    every thread, including the executor."""
    hvd = hvd_single
    mesh = hvd.parallel.global_mesh()
    x = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
                       NamedSharding(mesh, P("hvd")))
    exp = np.arange(16, dtype=np.float32).reshape(8, 2)

    transfer_guard()
    r = hvd.allreduce(x, op=hvd.Sum, name="dp.sum")
    assert isinstance(r, jax.Array)
    assert r.sharding == x.sharding  # sharding preserved, not gathered

    r2 = hvd.allreduce(x, op=hvd.Average, name="dp.avg",
                       prescale_factor=2.0, postscale_factor=0.5)
    r3 = hvd.broadcast(x, root_rank=0, name="dp.bc")
    rmin = hvd.allreduce(x, op=hvd.Min, name="dp.min")

    jax.config.update("jax_transfer_guard", "allow")
    np.testing.assert_allclose(np.asarray(r), exp)
    np.testing.assert_allclose(np.asarray(r2), exp)
    np.testing.assert_allclose(np.asarray(r3), exp)
    np.testing.assert_allclose(np.asarray(rmin), exp)

    from horovod_tpu.context import HorovodContext

    stats = HorovodContext.instance().device_plane.stats
    assert stats["identity"] >= 4
    assert stats["host_fallback"] == 0


def test_np1_grouped_device_bucket(hvd_single, transfer_guard):
    """A grouped eager allreduce of jax arrays rides the device plane as
    one pure device bucket."""
    hvd = hvd_single
    xs = [jnp.full((4, i + 1), float(i), jnp.float32) for i in range(5)]
    transfer_guard()
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="dp.group")
    jax.config.update("jax_transfer_guard", "allow")
    for i, o in enumerate(outs):
        assert isinstance(o, jax.Array)
        np.testing.assert_allclose(np.asarray(o), float(i))


def test_np1_bf16_device(hvd_single, transfer_guard):
    hvd = hvd_single
    x = jnp.full((8,), 1.5, jnp.bfloat16)
    transfer_guard()
    r = hvd.allreduce(x, op=hvd.Sum, name="dp.bf16")
    jax.config.update("jax_transfer_guard", "allow")
    assert r.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(r, np.float32), 1.5)


def test_np1_reducescatter_device_identity(hvd_single, transfer_guard):
    """np=1 reducescatter on the device plane: one member keeps the whole
    reduced buffer (identity modulo scales), no host copy."""
    hvd = hvd_single
    x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    transfer_guard()
    r = hvd.reducescatter(x, op=hvd.Sum, name="dp.rs")
    r2 = hvd.reducescatter(x, op=hvd.Sum, name="dp.rs2",
                           prescale_factor=2.0)
    jax.config.update("jax_transfer_guard", "allow")
    assert isinstance(r, jax.Array)
    np.testing.assert_allclose(np.asarray(r),
                               np.arange(12, dtype=np.float32).reshape(4, 3))
    np.testing.assert_allclose(np.asarray(r2), 2.0 * np.asarray(r))


def test_sim_reducescatter_program():
    plane = DevicePlane(_FakeCore(4), None)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    # Each "rank" contributes rows [8, 2] valued rank+1; reduced rows sum
    # to 10; rank p keeps rows [2p, 2p+2).
    rows = [jnp.full((1, 16), float(r + 1), jnp.float32) for r in range(4)]
    garr = plane._to_global(mesh, rows)
    fn = plane._reducescatter_program(0, mesh, ReduceOp.SUM, jnp.float32,
                                      16, 1.0, 1.0)
    out = fn(garr)
    for d in devs:
        np.testing.assert_allclose(np.asarray(plane._shard_on(out, d)), 10.0)
        assert plane._shard_on(out, d).shape == (1, 4)
    # AVERAGE + scales variant compiles separately and divides by k.
    fa = plane._reducescatter_program(0, mesh, ReduceOp.AVERAGE, jnp.float32,
                                      16, 2.0, 1.0)
    oa = fa(garr)
    np.testing.assert_allclose(np.asarray(plane._shard_on(oa, devs[1])), 5.0)
    assert plane.stats["programs_built"] == 2


def test_np1_adasum_falls_back_to_host(hvd_single):
    """Adasum is not served by the device plane; a jax input must still
    work via host materialization (negotiated device=False)."""
    hvd = hvd_single
    x = jnp.full((6,), 2.0, jnp.float32)
    r = hvd.allreduce(x, op=hvd.Adasum, name="dp.adasum")
    np.testing.assert_allclose(np.asarray(r), 2.0)


def test_np1_bool_falls_back_to_host(hvd_single):
    hvd = hvd_single
    b = jnp.asarray([True, False, True])
    r = hvd.allreduce(b, op=hvd.Sum, name="dp.bool")
    assert np.asarray(r).dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(r), [True, False, True])


def test_device_plane_env_off(monkeypatch):
    monkeypatch.setenv("HOROVOD_DEVICE_PLANE", "off")
    plane = DevicePlane(_FakeCore(1), None)
    assert plane.adopt(jnp.ones((2,)), __import__(
        "horovod_tpu.wire", fromlist=["OpType"]).OpType.ALLREDUCE,
        ReduceOp.SUM, 0) is None


def test_adopt_rejects_tracer_and_numpy():
    from horovod_tpu.wire import OpType

    plane = DevicePlane(_FakeCore(1), None)
    assert plane.adopt(np.ones(4, np.float32), OpType.ALLREDUCE,
                       ReduceOp.SUM, 0) is None
    # allgather/alltoall ride the plane for >=1-d arrays; scalars don't
    # (no first dim to gather/split over — host plane semantics apply).
    assert plane.adopt(jnp.ones(4), OpType.ALLTOALL, ReduceOp.SUM, 0) is not None
    assert plane.adopt(jnp.ones(4), OpType.ALLGATHER, ReduceOp.SUM, 0) is not None
    assert plane.adopt(jnp.float32(1.0), OpType.ALLGATHER,
                       ReduceOp.SUM, 0) is None
    assert plane.adopt(jnp.ones(4), OpType.ALLREDUCE,
                       ReduceOp.ADASUM, 0) is None

    seen = []

    def f(t):
        seen.append(plane.adopt(t, OpType.ALLREDUCE, ReduceOp.SUM, 0))
        return t

    jax.jit(f)(jnp.ones(4))
    assert seen == [None]  # tracers never ride the eager plane


# ---------------------------------------------------------------------------
# Simulated multi-rank mesh: the same pack -> global -> collective -> unpack
# stack production uses, with one [1, L] row per "rank" on a local mesh.
# ---------------------------------------------------------------------------

SHAPES = ((3, 2), (5,), (2, 2, 2))


def _sim_setup(plane, n=4, dtype=jnp.float32):
    devs = jax.devices()[:n]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    packs = []
    total = sum(int(np.prod(s)) for s in SHAPES)
    L = bucket_len(total)
    for r in range(n):
        arrs = tuple(jnp.full(s, float(r + 1) * (i + 1), dtype)
                     for i, s in enumerate(SHAPES))
        packs.append(plane._pack()(arrs, 1.0, L))
    return mesh, devs, packs, L


@pytest.mark.parametrize("rop,expect", [
    (ReduceOp.SUM, lambda i: 10.0 * (i + 1)),
    (ReduceOp.AVERAGE, lambda i: 2.5 * (i + 1)),
    (ReduceOp.MIN, lambda i: 1.0 * (i + 1)),
    (ReduceOp.MAX, lambda i: 4.0 * (i + 1)),
    (ReduceOp.PRODUCT, lambda i: 24.0 * (i + 1) ** 4),
])
def test_sim_fused_allreduce(rop, expect):
    plane = DevicePlane(_FakeCore(4), None)
    mesh, devs, packs, L = _sim_setup(plane)
    garr = plane._to_global(mesh, packs)
    out = plane._collective(0, mesh, rop, jnp.float32, L)(garr)
    for d in devs:  # every rank's shard holds the reduced bucket
        row = plane._shard_on(out, d)
        res = plane._unpack()(row, 1.0, SHAPES)
        for i in range(len(SHAPES)):
            np.testing.assert_allclose(np.asarray(res[i]), expect(i),
                                       rtol=1e-6)


def test_sim_program_cache_reuse():
    """Steady state: repeated dispatches with the same bucket class reuse
    the compiled program; a new dtype/op/length compiles anew."""
    plane = DevicePlane(_FakeCore(4), None)
    mesh, devs, packs, L = _sim_setup(plane)
    garr = plane._to_global(mesh, packs)
    for _ in range(3):
        plane._collective(0, mesh, ReduceOp.SUM, jnp.float32, L)(garr)
    assert plane.stats["programs_built"] == 1
    plane._collective(0, mesh, ReduceOp.AVERAGE, jnp.float32, L)(garr)
    assert plane.stats["programs_built"] == 2
    # Different member shapes, same padded class -> same program.
    other = tuple(jnp.ones((19,), jnp.float32) for _ in range(1))
    packs2 = [plane._pack()(other, 1.0, L) for _ in range(4)]
    garr2 = plane._to_global(mesh, packs2)
    plane._collective(0, mesh, ReduceOp.SUM, jnp.float32, L)(garr2)
    assert plane.stats["programs_built"] == 2


def test_sim_broadcast_program():
    plane = DevicePlane(_FakeCore(4), None)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    rows = [jnp.full((1, 3, 2), float(r + 7), jnp.float32) for r in range(4)]
    garr = plane._to_global(mesh, rows)
    fn = plane._broadcast_program(0, mesh, jnp.float32, (3, 2), 2)
    out = fn(garr)
    for d in devs:
        np.testing.assert_allclose(
            np.asarray(plane._shard_on(out, d)), 9.0)  # root pos 2 -> 7+2


def test_sim_pack_prescale_unpack_postscale():
    plane = DevicePlane(_FakeCore(4), None)
    arrs = (jnp.full((4,), 3.0, jnp.float32),)
    L = bucket_len(4)
    packed = plane._pack()(arrs, 2.0, L)
    np.testing.assert_allclose(np.asarray(packed)[0, :4], 6.0)
    np.testing.assert_allclose(np.asarray(packed)[0, 4:], 0.0)
    res = plane._unpack()(packed, 0.5, ((4,),))
    np.testing.assert_allclose(np.asarray(res[0]), 3.0)


def test_sim_allgather_program_uniform():
    """Device allgather, equal first dims: every member receives the full
    concatenation (reference analog: NCCLAllgather; SURVEY.md §2.2)."""
    plane = DevicePlane(_FakeCore(4), None)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    rows = [jnp.full((1, 2, 3), float(r), jnp.float32) for r in range(4)]
    garr = plane._to_global(mesh, rows)
    fn = plane._allgather_program(0, mesh, jnp.float32, (2, 2, 2, 2), (3,))
    out = fn(garr)
    expect = np.repeat(np.arange(4, dtype=np.float32), 2)[:, None] * np.ones(3)
    for d in devs:
        got = np.asarray(plane._shard_on(out, d)).reshape(8, 3)
        np.testing.assert_allclose(got, expect)


def test_sim_allgather_program_ragged():
    """Ragged first dims (1, 3, 0, 2): members pad to the max, the program
    slices per-member counts back out; a zero-row member contributes
    nothing."""
    plane = DevicePlane(_FakeCore(4), None)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    counts = (1, 3, 0, 2)
    maxn = 3
    rows = []
    for r, c in enumerate(counts):
        row = jnp.full((1, c, 1), float(r), jnp.float32)
        pad = jnp.zeros((1, maxn - c, 1), jnp.float32)
        rows.append(jnp.concatenate([row, pad], axis=1))
    garr = plane._to_global(mesh, rows)
    fn = plane._allgather_program(0, mesh, jnp.float32, counts, (1,))
    out = fn(garr)
    expect = np.concatenate(
        [np.full((c,), float(r)) for r, c in enumerate(counts)])[:, None]
    for d in devs:
        np.testing.assert_allclose(
            np.asarray(plane._shard_on(out, d)).reshape(6, 1), expect)


def test_sim_alltoall_program_uniform():
    """Uniform splits lower to one tiled lax.all_to_all: member r sends
    chunk j (valued 10*r + j) to member j."""
    plane = DevicePlane(_FakeCore(4), None)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    k = 4
    rows = []
    for r in range(k):
        chunks = [jnp.full((2, 1), 10.0 * r + j, jnp.float32)
                  for j in range(k)]
        rows.append(jnp.concatenate(chunks)[None])    # [1, 8, 1]
    garr = plane._to_global(mesh, rows)
    splits_mat = tuple(tuple(2 for _ in range(k)) for _ in range(k))
    fn = plane._alltoall_program(0, mesh, jnp.float32, splits_mat, 1)
    out = fn(garr)
    for j, d in enumerate(devs):
        got = np.asarray(plane._shard_on(out, d)).reshape(-1)
        expect = np.repeat([10.0 * r + j for r in range(k)], 2)
        np.testing.assert_allclose(got, expect)


def test_sim_alltoall_program_ragged():
    """Ragged splits: member r sends r+j rows valued 10*r+j to member j;
    the pad-to-max exchange reassembles exact (unpadded) per-source
    counts in source order."""
    plane = DevicePlane(_FakeCore(3), None)
    devs = jax.devices()[:3]
    mesh = Mesh(np.asarray(devs), (AXIS,))
    k = 3
    splits_mat = tuple(tuple(r + j for j in range(k)) for r in range(k))
    d0s = [sum(row) for row in splits_mat]
    d0max = max(d0s)
    rows = []
    for r in range(k):
        chunks = [jnp.full((r + j, 1), 10.0 * r + j, jnp.float32)
                  for j in range(k)]
        row = jnp.concatenate([c for c in chunks if c.size] or
                              [jnp.zeros((0, 1), jnp.float32)])
        pad = jnp.zeros((d0max - row.shape[0], 1), jnp.float32)
        rows.append(jnp.concatenate([row, pad])[None])
    garr = plane._to_global(mesh, rows)
    fn = plane._alltoall_program(0, mesh, jnp.float32, splits_mat, 1)
    out = fn(garr)
    for j, d in enumerate(devs):
        recv = [splits_mat[r][j] for r in range(k)]
        got = np.asarray(plane._shard_on(out, d)).reshape(-1)[:sum(recv)]
        expect = np.concatenate(
            [np.full((splits_mat[r][j],), 10.0 * r + j) for r in range(k)])
        np.testing.assert_allclose(got, expect)


def test_np1_allgather_alltoall_device_identity(hvd_single, transfer_guard):
    """np=1: allgather returns the tensor itself, alltoall splits to self —
    both complete on the device plane with no host copy."""
    hvd = hvd_single
    x = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    transfer_guard()
    g = hvd.allgather(x, name="dp.ag")
    a, recv = hvd.alltoall(x, name="dp.a2a")
    jax.config.update("jax_transfer_guard", "allow")
    assert isinstance(g, jax.Array) and isinstance(a, jax.Array)
    np.testing.assert_allclose(np.asarray(g), np.asarray(x))
    np.testing.assert_allclose(np.asarray(a), np.asarray(x))
    np.testing.assert_allclose(np.asarray(recv), [3])


def test_public_shard_map_is_jax_shard_map():
    """One installation, one name: the package's public shard_map is
    jax.shard_map itself and binds a mesh axis (the gspmd plane
    discriminates calling conventions on exactly that binding)."""
    from horovod_tpu.ops.collectives import shard_map as sm

    assert sm is jax.shard_map
    mesh = Mesh(np.asarray(jax.devices()[:4]), (AXIS,))
    fn = sm(lambda x: jax.lax.psum(x, AXIS), mesh=mesh,
            in_specs=P(AXIS), out_specs=P(AXIS), check_vma=False)
    x = jnp.ones((4, 2), jnp.float32)
    np.testing.assert_allclose(np.asarray(fn(x)), np.full((4, 2), 4.0))


def test_rank_of_process_follows_the_learned_mapping():
    """On a TPU a jax process's index follows its chips, not the process_id
    it gave the coordinator (four one-chip workers on a v5e 2x2: ranks
    0,1,2,3 are processes 0,2,3,1).  hvd.init() learns the mapping; without
    one the two coincide."""
    from horovod_tpu.parallel import mesh as pm

    assert [pm.rank_of_process(p) for p in range(4)] == [0, 1, 2, 3]
    pm.set_process_ranks({0: 0, 2: 1, 3: 2, 1: 3})
    try:
        assert [pm.rank_of_process(p) for p in range(4)] == [0, 3, 1, 2]
    finally:
        pm.reset()
    assert pm.rank_of_process(2) == 2
