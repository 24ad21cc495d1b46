"""Randomized differential soak for the chunk-pipelined TCP ring.

The pipelined ring (ChunkedDuplexExchange; VERDICT r3 #5) is the wire
format of the hot data-plane path.  This soak drives it through the FULL
public eager API with randomized shapes (including odd element counts that
exercise remainder segments and sub-chunk tails), dtypes, ops, and a
process-set subset, and checks every result against a numpy ground truth.
A tiny chunk size forces many chunks per segment; shm is disabled so
everything rides TCP.
"""

import numpy as np

from horovod_tpu.runner import run

_SEED = 0xC0FFEE


def _soak_worker():
    import os

    import numpy as np
    import horovod_tpu as hvd

    # Mixed-chunk interop mode: rank 1 runs a much larger chunk size than
    # the others (must be set before init — the native core reads it once).
    if (os.environ.get("TEST_MIXED_CHUNKS") == "1"
            and os.environ.get("HOROVOD_RANK") == "1"):
        os.environ["HOROVOD_RING_CHUNK_BYTES"] = "1048576"
    hvd.init(build_mesh=False)
    r, s = hvd.rank(), hvd.size()
    rng = np.random.RandomState(_SEED)  # same schedule on every rank
    checks = 0
    for i in range(14):
        dtype = rng.choice([np.float32, np.float64, np.int32, np.float16])
        # Odd sizes: remainder ring segments + final sub-chunk tails.
        n = int(rng.randint(1, 200_000))
        op = rng.choice([0, 1, 2, 3])
        # Deterministic per-rank values a closed form can verify.
        base = np.arange(n) % 97
        vals = [(base + rr + 1).astype(dtype) for rr in range(s)]
        x = vals[r].copy()
        name = f"soak.{i}"
        if op == 0:
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=name))
            expect = sum(v.astype(np.float64) for v in vals)
            np.testing.assert_allclose(out.astype(np.float64), expect,
                                       rtol=1e-2 if dtype == np.float16
                                       else 1e-6)
        elif op == 1:
            out = np.asarray(hvd.allreduce(x, op=hvd.Max, name=name))
            np.testing.assert_allclose(out, np.maximum.reduce(vals))
        elif op == 2:
            out = np.asarray(hvd.allgather(x, name=name))
            np.testing.assert_allclose(out, np.concatenate(vals))
        else:
            root = int(rng.randint(0, s))
            out = np.asarray(hvd.broadcast(x, root_rank=root, name=name))
            np.testing.assert_allclose(out, vals[root])
        checks += 1
    # Deterministic pipelined-chain broadcast coverage: 6 MB crosses the
    # 1 MiB chain threshold, the odd element count hits the remainder
    # chunk, the non-uniform payload + full-array compare catches any
    # offset bug, and root=1 exercises a mid-ring root.
    n = 1_500_001
    chain_vals = [(np.arange(n) % 251 + rr).astype(np.float32)
                  for rr in range(s)]
    out = np.asarray(hvd.broadcast(chain_vals[r].copy(), root_rank=1,
                                   name="soak.chain.bcast"))
    np.testing.assert_array_equal(out, chain_vals[1])
    checks += 1

    # Ragged allgather across the pipelined path: per-rank sizes differ,
    # so the size ring must agree before any payload moves.
    g = np.asarray(hvd.allgather(
        np.full((r + 1, 3), float(r), np.float32), name="soak.ragged.ag"))
    assert g.shape == (sum(range(1, s + 1)), 3)
    row = 0
    for rr in range(s):
        np.testing.assert_allclose(g[row:row + rr + 1], float(rr))
        row += rr + 1
    checks += 1

    # Uneven alltoall on the TCP path: ragged splits exchange geometry
    # before any payload moves; contents checked against closed form.
    # Zero splits (incl. zero-to-self on every rank) cover the degenerate
    # empty-hop case, and 4 KiB rows with a small chunk size make the
    # larger hops span multiple chunk frames.
    M = [[0, 3, 1], [2, 0, 2], [1, 2, 0]]  # M[q][j]: rows q sends to j
    if s == 3:
        W = 1024  # floats per row = 4 KiB
        datas = [(np.arange(sum(M[q]) * W, dtype=np.float32)
                  .reshape(-1, W) + 10_000 * q) for q in range(s)]
        out2, rsplits = hvd.alltoall(datas[r], splits=M[r],
                                     name="soak.a2a")
        expect_rows = []
        for q in range(s):
            off = sum(M[q][:r])
            expect_rows.append(datas[q][off:off + M[q][r]])
        np.testing.assert_array_equal(np.asarray(out2),
                                      np.concatenate(expect_rows))
        assert list(np.asarray(rsplits)) == [M[q][r] for q in range(s)]
        checks += 1

    # Ring reduce-scatter on the TCP path (phase-1-only ring, (m-1)/m of
    # the bytes): uneven rows (7 over 3 ranks -> 3/2/2), Average op, big
    # enough rows to span chunks at the 4 KiB setting.
    W = 2000
    rs_in = (np.arange(7 * W, dtype=np.float64).reshape(7, W) + r * 1000.0)
    rs_out = np.asarray(hvd.reducescatter(rs_in, op=hvd.Average,
                                          name="soak.rs"))
    base7, extra7 = divmod(7, s)
    my_rows = base7 + (1 if r < extra7 else 0)
    start = r * base7 + min(r, extra7)
    expect_rs = (np.arange(7 * W, dtype=np.float64).reshape(7, W)
                 + 1000.0 * (s - 1) / 2.0)[start:start + my_rows]
    np.testing.assert_allclose(rs_out, expect_rs)
    checks += 1

    # Grouped variants: one atomic negotiation group per list.
    ga = hvd.grouped_allgather(
        [np.full((2, 2), float(r), np.float32),
         np.full((1, 2), float(10 + r), np.float32)], name="soak.gag")
    assert np.asarray(ga[0]).shape == (2 * s, 2)
    np.testing.assert_allclose(np.asarray(ga[1])[:, 0],
                               [10.0 + rr for rr in range(s)])
    # No name=: the default auto-naming must still agree across ranks
    # (a process-local default would deadlock negotiation).
    grs = hvd.grouped_reducescatter(
        [np.full((s, 4), float(r + 1), np.float32)], op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(grs[0]),
                               float(s * (s + 1) / 2))
    checks += 1

    # Subset collectives ride a dedicated channel over the same wire.
    ps = hvd.add_process_set([0, s - 1])
    if r in (0, s - 1):
        x = np.full(12_345, float(r + 1), np.float32)
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum, process_set=ps,
                                       name="soak.ps"))
        np.testing.assert_allclose(out, float(1 + s))
        checks += 1
    hvd.barrier()
    hvd.shutdown()
    return checks


def _totals(env):
    base = {"HOROVOD_SHM_DISABLE": "1"}
    base.update(env)
    return run(_soak_worker, np=3, env=base)


def test_pipelined_ring_soak_matches_ground_truth():
    # 4 KiB chunks: a 200k-element f64 buffer crosses ~130 chunk frames
    # per ring hop.
    res = _totals({"HOROVOD_RING_CHUNK_BYTES": "4096"})
    assert res == [20, 19, 20]


def test_mixed_chunk_sizes_interoperate():
    # The chunk size is per-process (discovered per-frame on the wire);
    # rank 1 deliberately disagrees with the others.
    res = _totals({"HOROVOD_RING_CHUNK_BYTES": "8192",
                   "TEST_MIXED_CHUNKS": "1"})
    assert res == [20, 19, 20]
