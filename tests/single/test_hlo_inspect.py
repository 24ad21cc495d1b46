"""Compiled-collective introspection (ops/hlo_inspect.py).

Two layers: pure-text inventory parsing on synthetic optimized-HLO
modules (the exact analytic wire model every consumer shares), and the
live ``instrument`` path on the forced 8-device CPU mesh — a gspmd-plane
SGD step must yield a non-empty inventory whose analytic byte totals
match the live counters exactly, while the eager shard_map convention
(whose HLO also contains all-reduce ops the explicit pillars already
count) reports empty.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from horovod_tpu.ops import gspmd_plane as gp
from horovod_tpu.ops import hlo_inspect as hi
from horovod_tpu.optimizer import DistributedOptimizer

pytestmark = pytest.mark.usefixtures("hvd_single")

N_DEV = 8


@pytest.fixture(autouse=True)
def _fresh():
    hi.reset()
    gp.reset_plane_counters()
    yield
    hi.reset()
    gp.reset_plane_counters()


# ---------------------------------------------------------------------------
# The analytic ring wire model (exact integer arithmetic)
# ---------------------------------------------------------------------------

def test_ring_wire_bytes_model():
    # all-reduce: reduce-scatter + all-gather halves of the ring.
    assert hi.ring_wire_bytes("all-reduce", 1024, 8) == 2 * 1024 * 7 // 8
    # one-directional shard exchange.
    assert hi.ring_wire_bytes("all-gather", 1024, 8) == 1024 * 7 // 8
    assert hi.ring_wire_bytes("reduce-scatter", 1024, 4) == 1024 * 3 // 4
    assert hi.ring_wire_bytes("all-to-all", 1024, 4) == 1024 * 3 // 4
    # permute: one full hop.
    assert hi.ring_wire_bytes("collective-permute", 1024, 8) == 1024
    # a group of one moves nothing.
    for kind in hi.COLLECTIVE_KINDS:
        assert hi.ring_wire_bytes(kind, 1024, 1) == 0


# ---------------------------------------------------------------------------
# Inventory parsing on synthetic module text
# ---------------------------------------------------------------------------

_SYNTH = """\
HloModule m, num_partitions=8

ENTRY %main {
  %p0 = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(f32[128]{0} %p0), \
replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%sum
  ROOT %ag = f32[1024]{0} all-gather(f32[128]{0} %ar), \
replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
}
"""


def test_inventory_braced_replica_groups():
    inv = hi.inventory_from_text(_SYNTH, label="synth")
    assert inv.world == 8  # from the num_partitions header
    assert inv.kind_counts() == {"all-reduce": 1, "all-gather": 1}
    ar, ag = inv.ops
    # all-reduce: f32[128] over {{0..3},{4..7}} -> g=4.
    assert (ar.dtype, ar.elements, ar.group_size) == ("f32", 128, 4)
    assert ar.raw_bytes == 512
    assert ar.wire_bytes == 2 * 512 * 3 // 4
    # all-gather result f32[1024] over the full group -> g=8.
    assert (ag.group_size, ag.raw_bytes) == (8, 4096)
    assert ag.wire_bytes == 4096 * 7 // 8
    assert inv.raw_bytes == 512 + 4096
    assert inv.wire_bytes == ar.wire_bytes + ag.wire_bytes


def test_inventory_iota_replica_groups():
    text = ("%rs = f32[16]{0} reduce-scatter(f32[64]{0} %p0), "
            "replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%sum\n")
    inv = hi.inventory_from_text(text, world=8)
    (op,) = inv.ops
    assert op.group_size == 4  # iota form: [groups, group_size]
    # reduce-scatter raw is the logical full tensor: result bytes * g.
    assert op.raw_bytes == 16 * 4 * 4
    assert op.wire_bytes == op.raw_bytes * 3 // 4


def test_inventory_async_start_counted_once():
    text = """\
%ars = (f32[64]{0}, f32[64]{0}) all-reduce-start(f32[64]{0} %p0), \
replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum
%ard = f32[64]{0} all-reduce-done((f32[64]{0}, f32[64]{0}) %ars)
"""
    inv = hi.inventory_from_text(text, world=8)
    (op,) = inv.ops  # the -done half never double-counts
    assert op.asynchronous
    # (operand, result) alias: payload is the result's 256 bytes alone.
    assert (op.elements, op.raw_bytes) == (64, 256)
    assert op.wire_bytes == 2 * 256 * 7 // 8


def test_inventory_async_all_gather_takes_result():
    text = ("%ags = (f32[32]{0}, f32[256]{0}) all-gather-start("
            "f32[32]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, "
            "dimensions={0}\n")
    inv = hi.inventory_from_text(text, world=8)
    (op,) = inv.ops
    # The gathered result (the largest tuple part) is the payload.
    assert (op.elements, op.raw_bytes) == (256, 1024)
    assert op.wire_bytes == 1024 * 7 // 8


def test_inventory_collective_permute_full_hop():
    text = ("%cp = f32[32]{0} collective-permute(f32[32]{0} %p0), "
            "source_target_pairs={{0,1},{1,2}}\n")
    inv = hi.inventory_from_text(text, world=8)
    (op,) = inv.ops
    assert op.wire_bytes == op.raw_bytes == 128  # one full hop


def test_inventory_subbyte_dtypes_round_up():
    text = ("%ar = s4[3]{0} all-reduce(s4[3]{0} %p0), "
            "replica_groups={{0,1,2,3}}, to_apply=%sum\n")
    inv = hi.inventory_from_text(text, world=4)
    (op,) = inv.ops
    assert op.raw_bytes == (3 * 4 + 7) // 8  # 2 bytes, rounded up
    text = ("%ar = bf16[10]{0} all-reduce(bf16[10]{0} %p0), "
            "replica_groups={{0,1}}, to_apply=%sum\n")
    (op,) = hi.inventory_from_text(text, world=2).ops
    assert (op.dtype, op.raw_bytes) == ("bf16", 20)


def test_inventory_empty_on_collective_free_text():
    inv = hi.inventory_from_text(
        "HloModule m\nENTRY %e {\n  ROOT %a = f32[4]{0} add(...)\n}\n")
    assert inv.ops == [] and inv.raw_bytes == inv.wire_bytes == 0


def test_inventory_to_dict_shape():
    d = hi.inventory_from_text(_SYNTH, label="synth").to_dict()
    assert d["label"] == "synth" and d["world"] == 8
    assert d["collectives"] == 2 and len(d["ops"]) == 2
    assert set(d["kinds"]) == {"all-reduce", "all-gather"}
    assert d["ops"][0]["kind"] == "all-reduce"


# ---------------------------------------------------------------------------
# Counters + the native-sink contract (old-.so tolerance)
# ---------------------------------------------------------------------------

def test_note_inventory_counts_without_native_sink():
    # A stale .so leaves no sink wired: the Python-side counters (the
    # data_plane_stats fallback) must still carry the totals.
    hi.set_native_sink(None)
    inv = hi.inventory_from_text(_SYNTH, label="t")
    hi.note_inventory(inv)
    assert hi.gspmd_byte_counters() == (inv.raw_bytes, inv.wire_bytes)
    c = hi.counters()
    assert c["gspmd_collectives_total"] == 2
    assert c["gspmd_traces_total"] == 1
    # A sink that blows up (ABI drift) must never surface to the caller.
    hi.set_native_sink(lambda ops, raw, wire: 1 // 0)
    hi.note_inventory(inv)
    assert hi.counters()["gspmd_traces_total"] == 2


# ---------------------------------------------------------------------------
# Live instrument() on the forced 8-device mesh
# ---------------------------------------------------------------------------

def _gspmd_step(tx):
    mesh = gp.build_gspmd_mesh()
    rs = np.random.RandomState(3)
    n = mesh.shape[gp.BATCH_AXIS] * 4
    x = jax.device_put(jnp.asarray(rs.randn(n, 4), jnp.float32),
                       NamedSharding(mesh, P(gp.BATCH_AXIS)))
    y = jax.device_put(jnp.asarray(rs.randn(n), jnp.float32),
                       NamedSharding(mesh, P(gp.BATCH_AXIS)))
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = tx.init(params)

    @jax.jit
    def step(p, s, xs, ys):
        def loss(p):
            return jnp.mean((xs @ p["w"] - ys) ** 2)
        g = jax.grad(loss)(p)
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    return step, (params, state, x, y)


def test_instrument_gspmd_inventory_matches_counters(hvd_single):
    from horovod_tpu.context import HorovodContext

    core = HorovodContext.instance().core
    s0 = core.data_plane_stats()
    tx = DistributedOptimizer(optax.sgd(0.1), plane="gspmd")
    step, args = _gspmd_step(tx)
    wrapped = hi.instrument(step, label="live")
    p, s = wrapped(*args)
    jax.block_until_ready(p)

    invs = [i for i in hi.inventories() if i.label == "live"]
    assert len(invs) == 1
    inv = invs[0]
    assert inv.collectives > 0
    assert "all-reduce" in inv.kind_counts()
    assert inv.world == N_DEV
    for op in inv.ops:
        assert op.wire_bytes == hi.ring_wire_bytes(
            op.kind, op.raw_bytes, op.group_size)
    # Analytic totals == live counters, bit for bit.
    assert hi.gspmd_byte_counters() == (inv.raw_bytes, inv.wire_bytes)
    # ... and the same pair shows through data_plane_stats (native
    # counters when the .so has the ABI, the Python fallback otherwise).
    s1 = core.data_plane_stats()
    assert s1["gspmd_raw"] - s0.get("gspmd_raw", 0) == inv.raw_bytes
    assert s1["gspmd_wire"] - s0.get("gspmd_wire", 0) == inv.wire_bytes

    # Same abstract signature again: cache hit, no second inspection.
    p, s = wrapped(p, s, args[2], args[3])
    jax.block_until_ready(p)
    assert hi.counters()["gspmd_traces_total"] == 1


def test_instrument_eager_trace_reports_empty():
    # The eager shard_map convention's HLO also contains all-reduce ops,
    # but those bytes are already counted by the explicit pillars — the
    # plane gate must keep the inventory empty.
    mesh = Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))
    tx = DistributedOptimizer(optax.sgd(0.1), plane="eager",
                              axis_name="hvd")
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(N_DEV * 4, 4), jnp.float32)
    y = jnp.asarray(rs.randn(N_DEV * 4), jnp.float32)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = tx.init(params)

    def shard_step(p, s, xs, ys):
        def loss(p):
            return jnp.mean((xs @ p["w"] - ys) ** 2)
        g = jax.grad(loss)(p)
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    specs = dict(mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
                 out_specs=(P(), P()))
    sm = shard_map(shard_step, check_vma=False, **specs)
    wrapped = hi.instrument(jax.jit(sm), label="eager")
    p, s = wrapped(params, state, x, y)
    jax.block_until_ready(p)
    assert hi.inventories() == []
    assert hi.gspmd_byte_counters() == (0, 0)
    assert hi.counters()["gspmd_traces_total"] == 0


def test_disabled_returns_fn_unchanged(monkeypatch):
    from horovod_tpu.context import HorovodContext

    monkeypatch.setattr(HorovodContext.instance().cfg,
                        "hlo_inspect_enabled", False)
    fn = jax.jit(lambda x: x + 1)
    assert hi.instrument(fn) is fn  # zero per-step work when off


def test_inspect_lowered_does_not_record():
    # inspect_lowered is the raw primitive: it inventories but leaves
    # recording to the caller (instrument gates on the resolved plane).
    lowered = jax.jit(lambda x: x * 2).lower(jnp.zeros((4,), jnp.float32))
    inv = hi.inspect_lowered(lowered, label="raw")
    assert inv is not None and inv.ops == []
    assert hi.counters()["gspmd_traces_total"] == 0
