"""Multi-process collective correctness, run under the launcher on localhost.

Mirror of the reference's test/parallel strategy (SURVEY.md §4): every test
function runs as N real worker processes (socket controller rendezvous over
127.0.0.1), asserting op semantics per rank.  Assertions are bundled into a
few worker functions because each worker pays JAX import cost.
"""

import numpy as np
import pytest

from horovod_tpu.runner import run


def _collectives_worker():
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    assert s == 2
    results = {}

    # allreduce: sum/avg/min/max/product over rank-dependent values
    x = np.full(8, float(r + 1), np.float32)
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Sum, name="ar.sum"), 3.0)
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Average, name="ar.avg"), 1.5)
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Min, name="ar.min"), 1.0)
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Max, name="ar.max"), 2.0)
    np.testing.assert_allclose(
        hvd.allreduce(x, op=hvd.Product, name="ar.prod"), 2.0)

    # dtypes incl. 16-bit reductions in the native data plane
    for dt in (np.float64, np.float16, np.int32, np.int64, np.uint8, np.int8):
        v = (np.arange(6) % 3 + r).astype(dt)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"ar.{np.dtype(dt).name}")
        expected = sum((np.arange(6) % 3 + rr).astype(dt) for rr in range(2))
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   expected.astype(np.float64))
        assert out.dtype == dt
    # bool: SUM == logical OR
    b = np.array([r == 0, r == 1, False])
    out = hvd.allreduce(b, op=hvd.Sum, name="ar.bool")
    np.testing.assert_array_equal(out, [True, True, False])

    # pre/postscale
    out = hvd.allreduce(np.full(4, 2.0, np.float32), op=hvd.Sum,
                        prescale_factor=0.5, postscale_factor=3.0,
                        name="ar.scale")
    np.testing.assert_allclose(out, 2.0 * 0.5 * 2 * 3.0)

    # fusion: many small tensors with one barrier-free sweep
    handles = [hvd.allreduce_async(np.full(16, float(i + r), np.float32),
                                   op=hvd.Sum, name=f"fuse.{i}")
               for i in range(50)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(hvd.synchronize(h), 2 * i + 1.0)

    # response cache steady state: same tensor re-negotiated repeatedly
    for it in range(30):
        out = hvd.allreduce(np.full(32, float(r), np.float32), op=hvd.Sum,
                            name="cached.grad")
        np.testing.assert_allclose(out, 1.0)

    # allgather (ragged first dim)
    g = hvd.allgather(np.full((r + 1, 3), float(r), np.float32), name="ag")
    assert np.asarray(g).shape == (3, 3)
    np.testing.assert_allclose(np.asarray(g)[:1], 0.0)
    np.testing.assert_allclose(np.asarray(g)[1:], 1.0)

    # broadcast from each root
    for root in range(s):
        out = hvd.broadcast(np.full(5, float(r), np.float64), root_rank=root,
                            name=f"bc.{root}")
        np.testing.assert_allclose(out, float(root))

    # alltoall with uneven splits: rank0 sends [1,2], rank1 sends [3,1]
    splits = [1, 2] if r == 0 else [3, 1]
    data = np.arange(3 if r == 0 else 4, dtype=np.float32).reshape(-1, 1) + \
        10 * r
    out, rsplits = hvd.alltoall(data, splits=splits, name="a2a")
    if r == 0:
        np.testing.assert_array_equal(rsplits, [1, 3])
        np.testing.assert_allclose(np.asarray(out).ravel(), [0, 10, 11, 12])
    else:
        np.testing.assert_array_equal(rsplits, [2, 1])
        np.testing.assert_allclose(np.asarray(out).ravel(), [1, 2, 13])

    # reducescatter (4 rows over 2 ranks -> 2 rows each)
    base = np.arange(8, dtype=np.float32).reshape(4, 2)
    out = hvd.reducescatter(base, op=hvd.Sum, name="rs")
    expected = 2 * base[2 * r:2 * r + 2]
    np.testing.assert_allclose(out, expected)

    # barrier
    hvd.barrier()

    # objects
    objs = hvd.allgather_object({"rank": r})
    assert objs == [{"rank": 0}, {"rank": 1}]
    obj = hvd.broadcast_object({"val": 42} if r == 0 else None, root_rank=0)
    assert obj == {"val": 42}

    hvd.shutdown()
    return r


def test_collectives_np2():
    assert run(_collectives_worker, np=2) == [0, 1]


def _process_set_worker():
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    assert s == 3
    even = hvd.add_process_set([0, 2])
    solo = hvd.add_process_set([1])
    assert even.process_set_id is not None and solo.process_set_id is not None
    assert hvd.global_process_set.size() == 3

    if r in (0, 2):
        assert even.included()
        assert even.rank() == (0 if r == 0 else 1)
        out = hvd.allreduce(np.full(4, float(r), np.float32), op=hvd.Sum,
                            process_set=even, name="ps.even")
        np.testing.assert_allclose(out, 2.0)
    else:
        assert not even.included()
        assert solo.included()
        out = hvd.allreduce(np.full(4, 7.0, np.float32), op=hvd.Sum,
                            process_set=solo, name="ps.solo")
        np.testing.assert_allclose(out, 7.0)

    # global collective still works alongside subset collectives
    out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="ps.global")
    np.testing.assert_allclose(out, 3.0)

    # uneven reducescatter: 4 rows over 3 ranks -> 2/1/1
    base = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = hvd.reducescatter(base, op=hvd.Sum, name="ps.rs")
    starts = [0, 2, 3]
    lengths = [2, 1, 1]
    np.testing.assert_allclose(
        out, 3 * base[starts[r]:starts[r] + lengths[r]])

    hvd.shutdown()
    return r


def test_process_sets_np3():
    assert run(_process_set_worker, np=3) == [0, 1, 2]


def _error_worker():
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    # mismatched shapes across ranks -> HorovodInternalError on every rank
    bad = np.ones(4 if r == 0 else 5, np.float32)
    try:
        hvd.allreduce(bad, op=hvd.Sum, name="bad.shape")
        raised = False
    except hvd.HorovodInternalError as exc:
        raised = "shape" in str(exc).lower()
    assert raised, "expected HorovodInternalError with shape mismatch"

    # mismatched dtype
    bad = np.ones(4, np.float32 if r == 0 else np.float64)
    try:
        hvd.allreduce(bad, op=hvd.Sum, name="bad.dtype")
        raised = False
    except hvd.HorovodInternalError as exc:
        raised = "dtype" in str(exc).lower()
    assert raised

    # the controller survives errors: a good collective still completes
    out = hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum, name="good.after")
    np.testing.assert_allclose(out, 2.0)
    hvd.shutdown()
    return r


def test_negotiation_errors_np2():
    assert run(_error_worker, np=2) == [0, 1]


def _optimizer_worker():
    import numpy as np
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    # eager DistributedOptimizer: grads averaged across processes
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.zeros(4)}
    state = tx.init(params)
    grads = {"w": jnp.full(4, float(r + 1))}  # avg = 1.5
    updates, state = tx.update(grads, state, params)
    new = optax.apply_updates(params, updates)
    np.testing.assert_allclose(np.asarray(new["w"]), -1.5, rtol=1e-6)

    # broadcast_parameters synchronises initial state from rank 0
    params = {"w": jnp.full(3, float(r) + 5.0)}
    synced = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(synced["w"]), 5.0)

    # compression over the wire
    out = hvd.allreduce(np.full(8, 0.25, np.float32), op=hvd.Sum,
                        compression=hvd.Compression.fp16, name="comp")
    np.testing.assert_allclose(out, 0.5, atol=1e-3)
    hvd.shutdown()
    return r


def test_optimizer_np2():
    assert run(_optimizer_worker, np=2) == [0, 1]


def _timeline_autotune_worker(tmpdir):
    import os
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    path = os.path.join(tmpdir, f"tl_{r}.json")
    hvd.start_timeline(path, mark_cycles=True)
    for i in range(5):
        hvd.allreduce(np.ones(16, np.float32), op=hvd.Sum, name=f"tl.{i}")
    hvd.stop_timeline()
    import json

    with open(path) as f:
        events = json.load(f)
    assert any(ev.get("name") == "NEGOTIATE" for ev in events)
    hvd.shutdown()
    return r


def test_timeline_np2(tmp_path):
    assert run(_timeline_autotune_worker, args=(str(tmp_path),), np=2) == [0, 1]


def _autotune_worker(tmpdir):
    import os
    import numpy as np
    import horovod_tpu as hvd

    os.environ["HOROVOD_AUTOTUNE"] = "1"
    os.environ["HOROVOD_AUTOTUNE_LOG"] = os.path.join(
        tmpdir, f"autotune_{os.environ['HOROVOD_RANK']}.csv")
    hvd.init(build_mesh=False)
    r = hvd.rank()
    # Push traffic for > 2 autotune windows (window_s = 2.0) so the
    # optimizer records at least one score line and proposes a move.  Ranks
    # agree on the stop iteration via a Min-allreduced flag — wall-clock
    # loops diverge once autotuning stretches the cycle time.
    import time
    t0 = time.monotonic()
    i = 0
    while True:
        cont = 1.0 if time.monotonic() - t0 < 5.0 else 0.0
        flag = hvd.allreduce(np.array([cont], np.float32), op=hvd.Min,
                             name=f"at.cont.{i}")
        if float(np.asarray(flag)[0]) < 1.0:
            break
        hvd.allreduce(np.ones(4096, np.float32), op=hvd.Sum,
                      name=f"at.{i}")
        i += 1
    hvd.shutdown()
    log = os.environ["HOROVOD_AUTOTUNE_LOG"]
    with open(log) as f:
        lines = f.read().strip().splitlines()
    assert lines[0].startswith("time_s,fusion_bytes,cycle_ms")
    assert len(lines) >= 2, lines  # header + >=1 scored window
    score = float(lines[1].rsplit(",", 1)[1])
    assert score > 0
    return r


def test_autotune_np2(tmp_path):
    from horovod_tpu.runner import run

    assert run(_autotune_worker, args=(str(tmp_path),), np=2) == [0, 1]


def _ring_np4_worker():
    """Ring/tree/pairwise data plane at np=4: payloads large enough to span
    multiple ring chunks and the kernel socket buffers (exercises the
    deadlock-free duplex path), every op, plus a non-contiguous process set
    whose ring skips ranks."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    assert s == 4

    # Large allreduce: 4 MB per rank (>> socket buffers), odd length so the
    # ring chunking hits the remainder path.
    n = 1_000_003
    x = np.arange(n, dtype=np.float32) * (r + 1) / n
    out = hvd.allreduce(x, op=hvd.Sum, name="ring.big")
    np.testing.assert_allclose(
        out, np.arange(n, dtype=np.float32) * 10.0 / n, rtol=1e-5)

    # min/max/product ride the same ring reduce-scatter
    v = np.full(5, float(r + 1), np.float64)
    np.testing.assert_allclose(
        hvd.allreduce(v, op=hvd.Min, name="ring.min"), 1.0)
    np.testing.assert_allclose(
        hvd.allreduce(v, op=hvd.Max, name="ring.max"), 4.0)
    np.testing.assert_allclose(
        hvd.allreduce(v, op=hvd.Product, name="ring.prod"), 24.0)

    # ragged ring allgather, blocks of different sizes per rank
    g = hvd.allgather(np.full((r + 1, 2), float(r), np.float32),
                      name="ring.ag")
    got = np.asarray(g)
    assert got.shape == (10, 2)
    row = 0
    for rr in range(4):
        np.testing.assert_allclose(got[row:row + rr + 1], float(rr))
        row += rr + 1

    # binomial-tree broadcast from every root, payload > one chunk
    for root in range(s):
        out = hvd.broadcast(
            np.full(100_000, float(r), np.float32), root_rank=root,
            name=f"ring.bc.{root}")
        np.testing.assert_allclose(np.asarray(out), float(root))

    # pairwise alltoall: rank r sends (j+1) rows to member j
    splits = [j + 1 for j in range(s)]
    rows = sum(splits)
    data = (np.arange(rows, dtype=np.float32) + 100 * r).reshape(rows, 1)
    out, rsplits = hvd.alltoall(data, splits=splits, name="ring.a2a")
    np.testing.assert_array_equal(rsplits, [r + 1] * s)
    expected = []
    for src in range(s):
        off = sum(range(1, r + 1))  # rows for me start after splits[:r]
        expected.extend((np.arange(off, off + r + 1) + 100 * src).tolist())
    np.testing.assert_allclose(np.asarray(out).ravel(), expected)

    # non-contiguous process set: ring over ranks {0, 2, 3}
    ps = hvd.add_process_set([0, 2, 3])
    if r in (0, 2, 3):
        out = hvd.allreduce(np.full(7, float(r), np.float32), op=hvd.Sum,
                            process_set=ps, name="ring.ps")
        np.testing.assert_allclose(out, 5.0)

    hvd.barrier()
    hvd.shutdown()
    return r


def test_ring_collectives_np4():
    assert run(_ring_np4_worker, np=4) == [0, 1, 2, 3]


def _stall_shutdown_worker():
    """Stall-shutdown watchdog (reference: StallInspector + HOROVOD_STALL_
    SHUTDOWN_TIME_SECONDS, core_api.cc FailAllOutstanding): rank 1 never
    submits the second tensor; every rank's synchronize must raise
    HorovodInternalError naming the stall, within the shutdown window."""
    import os
    import time
    import numpy as np
    import horovod_tpu as hvd

    os.environ["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "1"
    os.environ["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = "3"
    hvd.init(build_mesh=False)
    r = hvd.rank()

    # A healthy collective first: the watchdog must not fire on live traffic.
    out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="ok")
    np.testing.assert_allclose(np.asarray(out), 2.0)

    t0 = time.monotonic()
    raised = False
    try:
        if r == 0:
            hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="stalled")
        else:
            # rank 1 never submits "stalled"; its next op arrives only after
            # rank 0's watchdog has torn the job down.
            time.sleep(8.0)
            hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="late")
    except hvd.HorovodInternalError as exc:
        raised = True
        if r == 0:
            assert "stall" in str(exc).lower(), exc
    waited = time.monotonic() - t0
    assert raised, f"rank {r}: expected HorovodInternalError"
    assert waited < 15.0, f"rank {r}: stall shutdown took {waited:.1f}s"
    hvd.shutdown()
    return r


def test_stall_shutdown_np2():
    assert run(_stall_shutdown_worker, np=2) == [0, 1]


def _duplicate_name_worker():
    """Duplicate in-flight names queue behind each other (reference
    semantics: the negotiation layer keys by name and processes instances
    in submission order) instead of raising."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    h1 = hvd.allreduce_async(np.full(4, 1.0 + r, np.float32), op=hvd.Sum,
                             name="dup")
    h2 = hvd.allreduce_async(np.full(4, 10.0 + r, np.float32), op=hvd.Sum,
                             name="dup")
    h3 = hvd.allreduce_async(np.full(4, 100.0 + r, np.float32), op=hvd.Sum,
                             name="dup")
    np.testing.assert_allclose(hvd.synchronize(h1), 3.0)
    np.testing.assert_allclose(hvd.synchronize(h2), 21.0)
    np.testing.assert_allclose(hvd.synchronize(h3), 201.0)
    # out-of-order synchronize also works
    ha = hvd.allreduce_async(np.full(2, 1.0, np.float32), op=hvd.Sum,
                             name="dup2")
    hb = hvd.allreduce_async(np.full(2, 2.0, np.float32), op=hvd.Sum,
                             name="dup2")
    np.testing.assert_allclose(hvd.synchronize(hb), 4.0)
    np.testing.assert_allclose(hvd.synchronize(ha), 2.0)
    hvd.shutdown()
    return r


def test_duplicate_names_queue_np2():
    assert run(_duplicate_name_worker, np=2) == [0, 1]


def _join_worker():
    """hvd.join() with uneven step counts (reference: torch join tests):
    rank r runs r+1 allreduce steps then joins; later steps sum only the
    still-active ranks (joined ranks contribute zeros), and every rank's
    join() returns the last rank to join."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    assert s == 3
    # step k is executed by ranks with r >= k; value contributed: r + 1
    for k in range(r + 1):
        out = hvd.allreduce(np.full(4, float(r + 1), np.float32),
                            op=hvd.Sum, name=f"join.step{k}")
        expected = sum(rr + 1 for rr in range(s) if rr >= k)
        np.testing.assert_allclose(np.asarray(out), expected, err_msg=f"step{k}")
    last = hvd.join()
    assert last == 2, last

    # the runtime is healthy after a join round: a fresh collective works
    out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="post.join")
    np.testing.assert_allclose(np.asarray(out), 3.0)

    # ops with no zero-neutral element fail cleanly while ranks are joined
    if r == 0:
        hvd.join()
        raised = True  # rank 0 submits nothing; join returns when others do
    else:
        try:
            hvd.allreduce(np.ones(2, np.float32), op=hvd.Min,
                          name="join.min")
            raised = False
        except hvd.HorovodInternalError as exc:
            raised = "join" in str(exc).lower()
        hvd.join()
    assert raised
    hvd.shutdown()
    return r


def test_join_np3():
    assert run(_join_worker, np=3) == [0, 1, 2]


def _tombstone_resubmit_worker():
    """Error-tombstone semantics, np=3 (the tombstone only forms when a
    member has NOT yet announced at error time): ranks 0/1 collide on
    "grad.0" with mismatched dtypes and error; straggler rank 2 announces
    the same name late and must receive the stored error instead of
    waiting forever; then a consistent resubmission of the SAME name by
    all ranks must succeed (tombstones deliver once per owed rank — the
    recurring-gradient-name case)."""
    import time
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    if r == 2:
        time.sleep(1.5)  # announce after the error fired -> owed rank
        bad = np.ones(4, np.float32)
    else:
        bad = np.ones(4, np.float32 if r == 0 else np.float64)
    try:
        hvd.allreduce(bad, op=hvd.Sum, name="grad.0")
        raised = None
    except hvd.HorovodInternalError as exc:
        raised = str(exc)
    assert raised is not None, f"rank {r}: expected the mismatch error"
    assert "ismatch" in raised, raised  # tombstone text reaches rank 2 too
    # Consistent resubmission of the same name -> completes with right sum.
    out = hvd.allreduce(np.full(4, float(r + 1), np.float32), op=hvd.Sum,
                        name="grad.0")
    np.testing.assert_allclose(np.asarray(out), 6.0)
    # and again (steady state through the response cache)
    out = hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                        name="grad.0")
    np.testing.assert_allclose(np.asarray(out), 3.0)
    hvd.shutdown()
    return r


def test_tombstone_delivers_to_straggler_then_allows_resubmit_np3():
    assert run(_tombstone_resubmit_worker, np=3) == [0, 1, 2]


def _tombstone_inflight_race_worker():
    """In-flight-announce race, np=3: a rank whose announce of "race.i" is
    already in flight when the coordinator emits the mismatch error gets the
    error TWICE — once via the cycle broadcast (name-mapped to its handle)
    and once via the targeted tombstone for its stale announce.  The stale
    targeted delivery must not be absorbed by the rank's fresh, consistent
    resubmission of the same name (core_api matches the echoed submission
    handle).  Many near-simultaneous iterations to cover interleavings."""
    import random
    import time
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    rng = random.Random(1234 + r)
    for i in range(25):
        if r == 2:
            time.sleep(rng.uniform(0.0, 0.005))  # vary arrival order
        bad = np.ones(4, np.float64 if r == 1 else np.float32)
        try:
            hvd.allreduce(bad, op=hvd.Sum, name=f"race.{i}")
            raised = None
        except hvd.HorovodInternalError as exc:
            raised = str(exc)
        assert raised is not None and "ismatch" in raised, \
            f"rank {r} iter {i}: {raised}"
        # Fresh consistent resubmission must never absorb the stale error.
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            name=f"race.{i}")
        np.testing.assert_allclose(np.asarray(out), 3.0)
    hvd.shutdown()
    return r


def test_tombstone_inflight_announce_race_np3():
    assert run(_tombstone_inflight_race_worker, np=3) == [0, 1, 2]


def _tombstone_cached_straggler_worker():
    """Tombstone delivery for a CACHE-HIT announce, np=3: "cgrad.0" first
    negotiates successfully (now in every rank's response cache), then
    ranks 0/1 resubmit it with mismatched dtypes -> error + tombstone owed
    to straggler rank 2.  Rank 2's late announce travels as a bare cache id;
    the frame must carry rank 2's own submission handle so the targeted
    error maps onto its outstanding entry (a cache-reconstructed foreign
    handle would be dropped as stale -> permanent hang)."""
    import time
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="cgrad.0")
    np.testing.assert_allclose(np.asarray(out), 3.0)
    if r == 2:
        time.sleep(1.5)  # announce after the error fired -> owed rank
        bad = np.ones(4, np.float32)  # cache hit: same signature as before
    else:
        bad = np.ones(4, np.float32 if r == 0 else np.float64)
    try:
        hvd.allreduce(bad, op=hvd.Sum, name="cgrad.0")
        raised = None
    except hvd.HorovodInternalError as exc:
        raised = str(exc)
    assert raised is not None, f"rank {r}: expected the mismatch error"
    assert "ismatch" in raised, raised
    # Consistent resubmission still works afterwards.
    out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="cgrad.0")
    np.testing.assert_allclose(np.asarray(out), 3.0)
    hvd.shutdown()
    return r


def test_tombstone_cached_straggler_np3():
    assert run(_tombstone_cached_straggler_worker, np=3) == [0, 1, 2]


def _early_exit_worker():
    """Clean shutdown of one rank: survivors' next collective fails with a
    named 'has shut down' error instead of a connection error or a hang
    (BYE/farewell handshake)."""
    import time
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r = hvd.rank()
    out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="ok")
    np.testing.assert_allclose(np.asarray(out), 2.0)
    if r == 1:
        hvd.shutdown()  # leaves deliberately
        return r
    # rank 0: give the BYE a moment, then attempt a collective rank 1
    # will never join
    time.sleep(1.0)
    try:
        hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="after.exit")
        raised = None
    except hvd.HorovodInternalError as exc:
        raised = str(exc)
    assert raised is not None, "expected failure after peer shutdown"
    assert "shut down" in raised, raised
    hvd.shutdown()
    return r


def test_clean_early_exit_np2():
    assert run(_early_exit_worker, np=2) == [0, 1]


def _rendezvous_worker_script(tmpdir):
    import os
    import textwrap
    path = os.path.join(tmpdir, "rdv_worker.py")
    with open(path, "w") as f:
        f.write(textwrap.dedent("""
            import os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import horovod_tpu as hvd

            hvd.init(build_mesh=False)
            out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                                name="rdv")
            assert float(out.sum()) == 4.0, out
            print(f"RDV OK rank={hvd.rank()}")
            hvd.shutdown()
        """))
    return path


def _spawn_rank(script, rank, port):
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        "HOROVOD_RANK": str(rank), "HOROVOD_SIZE": "2",
        "HOROVOD_LOCAL_RANK": str(rank), "HOROVOD_LOCAL_SIZE": "2",
        "HOROVOD_CONTROLLER": "socket",
        "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
        "HOROVOD_GLOO_RENDEZVOUS_PORT": str(port),
    })
    return subprocess.Popen([sys.executable, script],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True, env=env)


def test_rendezvous_ignores_stray_connections():
    """A garbage connection to the rendezvous port (port scanner, stale
    client) must be dropped, not fail the job: the real worker still
    rendezvouses and the collective completes."""
    import os
    import socket as socketlib
    import struct
    import tempfile
    import time

    from horovod_tpu.runner.util import find_free_port

    with tempfile.TemporaryDirectory() as td:
        script = _rendezvous_worker_script(td)
        port = find_free_port()
        p0 = _spawn_rank(script, 0, port)
        # Two strays: one sends a wrong-magic frame, one connects and
        # stays silent (must be dropped by the HELLO read timeout).
        payload = struct.pack("<iiii", 0x600DF00D, 1, 1, 12345)
        sent = False
        silent = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not sent:
            try:
                s = socketlib.create_connection(("127.0.0.1", port),
                                                timeout=2)
                s.sendall(struct.pack("<I", len(payload)) + payload)
                s.close()
                silent = socketlib.create_connection(("127.0.0.1", port),
                                                     timeout=2)
                sent = True
            except OSError:
                time.sleep(0.2)
        assert sent, "stray payload was never delivered"
        p1 = _spawn_rank(script, 1, port)
        out0, _ = p0.communicate(timeout=120)
        out1, _ = p1.communicate(timeout=120)
        if silent is not None:
            silent.close()
        assert p0.returncode == 0 and "RDV OK" in out0, out0
        assert p1.returncode == 0 and "RDV OK" in out1, out1


def test_rendezvous_rejects_version_mismatch():
    """A worker speaking a different protocol version fails the job with a
    named error (not garbled frames)."""
    import os
    import socket as socketlib
    import struct
    import tempfile
    import time

    from horovod_tpu.runner.util import find_free_port

    with tempfile.TemporaryDirectory() as td:
        script = _rendezvous_worker_script(td)
        port = find_free_port()
        p0 = _spawn_rank(script, 0, port)
        payload = struct.pack("<iiii", 0x48565354, 999, 1, 12345)
        deadline = time.monotonic() + 30
        s = None
        while time.monotonic() < deadline:
            try:
                s = socketlib.create_connection(("127.0.0.1", port),
                                                timeout=2)
                s.sendall(struct.pack("<I", len(payload)) + payload)
                break
            except OSError:
                time.sleep(0.2)
        assert s is not None, "version-mismatch payload was never delivered"
        out0, _ = p0.communicate(timeout=120)
        s.close()
        assert p0.returncode != 0, out0
        assert "protocol version mismatch" in out0, out0


def _soak_worker():
    """Randomized differential soak: a seeded schedule of mixed collectives
    (op type, dtype, shape, sync/async bursts) is identical on every rank;
    payloads are rank-dependent; every result is checked against the numpy
    ground truth.  Exercises negotiation, fusion, the response cache, and
    arrival-order interleavings far beyond the hand-written cases."""
    import random
    import numpy as np
    import horovod_tpu as hvd

    hvd.init(build_mesh=False)
    r, size = hvd.rank(), hvd.size()
    sched = random.Random(0xC0FFEE)        # same schedule on all ranks
    jitter = random.Random(1000 + r)       # rank-local timing jitter
    dtypes = [np.float32, np.float64, np.int32, np.float16]

    def payload(i, rank, dt, n):
        return (np.arange(n) % 7 + rank + i % 5).astype(dt)

    def flush(pending):
        for h, j, dt2, n2 in pending:
            want = sum(payload(j, rr, dt2, n2) for rr in range(size))
            np.testing.assert_allclose(
                np.asarray(hvd.synchronize(h), np.float64),
                want.astype(np.float64),
                rtol=1e-3 if dt2 == np.float16 else 1e-6)

    pending = []
    for i in range(120):
        kind = sched.choice(["allreduce", "allreduce_async", "allgather",
                             "broadcast", "barrier"])
        dt = sched.choice(dtypes)
        n = sched.choice([1, 3, 16, 257])
        name = f"soak.{i}"
        if jitter.random() < 0.1:
            import time
            time.sleep(jitter.random() * 0.002)
        if kind == "allreduce":
            out = hvd.allreduce(payload(i, r, dt, n), op=hvd.Sum, name=name)
            want = sum(payload(i, rr, dt, n) for rr in range(size))
            np.testing.assert_allclose(
                np.asarray(out, np.float64), want.astype(np.float64),
                rtol=1e-3 if dt == np.float16 else 1e-6)
        elif kind == "allreduce_async":
            h = hvd.allreduce_async(payload(i, r, dt, n), op=hvd.Sum,
                                    name=name)
            pending.append((h, i, dt, n))
            if len(pending) >= sched.randint(2, 6):
                flush(pending)
                pending = []
        elif kind == "allgather":
            rows = (r % 2) + 1      # ragged first dim
            data = np.full((rows, max(n % 5, 1)), float(r), dt)
            out = np.asarray(hvd.allgather(data, name=name))
            want = np.concatenate(
                [np.full(((rr % 2) + 1, max(n % 5, 1)), float(rr), dt)
                 for rr in range(size)])
            np.testing.assert_allclose(out.astype(np.float64),
                                       want.astype(np.float64))
        elif kind == "broadcast":
            root = sched.randrange(size)
            out = hvd.broadcast(payload(i, r, dt, n), root_rank=root,
                                name=name)
            np.testing.assert_allclose(np.asarray(out, np.float64),
                                       payload(i, root, dt, n)
                                       .astype(np.float64))
        else:
            hvd.barrier()
    flush(pending)
    hvd.shutdown()
    return r


def test_soak_mixed_collectives_np3():
    assert run(_soak_worker, np=3) == [0, 1, 2]
