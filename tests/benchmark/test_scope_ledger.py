"""A traced step by the program's layer scopes at self time
(``benchmark/scope_ledger.py``) on hand-built traces and on the recorded chip
traces, and ``tools/step_ledger.py`` over it, run in process.  Names and
arithmetic only: nothing here is a measurement."""

import glob
import importlib.util
import io
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import scope_ledger, trace_reduce  # noqa: E402
from benchmark.trace_reduce import ASYNC_LINE, SYNC_LINE, Event, Trace  # noqa: E402

FUSION = "%fusion.{} = f32[8] fusion(f32[8] %p), kind=kLoop"
WHILE = ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), "
         "condition=%cond, body=%body")
BLOCK = "jit(step)/jvp(SDAR)/layer_0/hvd_block/"
RECORDED = sorted(glob.glob(os.path.join(REPO, "benchmark", "testdata",
                                         "*.xplane.pb.gz")))
METRIC_FILES = sorted(glob.glob(os.path.join(REPO, "benchmark",
                                             "layer_metrics", "*.json")))


def _examples_of(path) -> list:
    with open(path) as f:
        return trace_reduce.examples_of(json.load(f))


def _short(path) -> str:
    return os.path.basename(path).split(".")[0]


EXAMPLES = [pytest.param(path, i, id=f"{_short(path)}:{example['cell']}"
                         if "cell" in example else _short(path))
            for path in METRIC_FILES
            for i, example in enumerate(_examples_of(path))]


def _op(i, start, dur, scope="", line=SYNC_LINE, name=None):
    return Event(name or FUSION.format(i), start, dur, line, scope)


def _a_while_and_its_body():
    """A ``while`` of 1,000 ns under the block that holds two body ops of 300
    and 400 ns under different layers, and a copy in flight beside them."""
    return [
        _op(0, 0, 1000, BLOCK + "moe/while:", name=WHILE),
        _op(1, 100, 300, BLOCK + "moe/hvd_moe_route/while/body/gather:"),
        _op(2, 500, 400,
            BLOCK + "moe/hvd_moe_experts/while/body/dot_general:"),
        _op(3, 0, 5000, BLOCK + "moe/hvd_moe_route/gather:", ASYNC_LINE,
            "%copy-start.1 = (f32[8], f32[8], u32[]) copy-start(f32[8] %w)"),
    ]


def test_self_time_by_hand():
    events, window = _a_while_and_its_body(), (0, 2000)
    rows = scope_ledger.self_times(events, window)
    assert [(scope_ledger.layer_of(e), own) for e, own in rows] == [
        ("hvd_block", 300.0), ("hvd_moe_route", 300.0),
        ("hvd_moe_experts", 400.0)]
    assert sum(own for _, own in rows) == trace_reduce.busy_ns(
        trace_reduce.sync_ops(events), window) == 1000.0
    trace = Trace({0: events}, [], window, 2)
    for layer, ms in (("^hvd_block$", 150e-6), ("^hvd_moe_route$", 150e-6),
                      ("^hvd_moe_experts$", 200e-6), ("^hvd_moe_", 350e-6)):
        assert scope_ledger.self_time_ms(trace, {}, layer=layer) == \
            pytest.approx(ms)
    assert scope_ledger.self_time_ms(trace, {}, layer="^hvd_mlp$") is None
    assert scope_ledger.unattributed_pct(trace, {}) == 0.0
    # The classes the accepted metrics time from outside count the while and
    # its body both: 1,700 ns where the core was busy for 1,000.
    assert trace_reduce.op_time_ms(
        trace, {}, scope=r"jvp\(", line="sync") == pytest.approx(500e-6)
    assert sum(e.dur_ns for e in trace_reduce.sync_ops(events)) == 1700


@pytest.mark.parametrize("case,spans,want", [
    ("a body inside a call inside a while",
     [(0, 1000), (100, 600), (200, 300)], [400, 300, 300]),
    ("the window cuts the while and its last body op",
     [(1500, 1000), (1600, 200), (1900, 400)], [200, 200, 100]),
    ("two ops that end together", [(0, 500), (200, 300)], [200, 300]),
    ("two ops that overlap in part count once",
     [(0, 1000), (500, 1000), (1200, 200)], [500, 800, 200]),
    ("the same interval twice", [(0, 400), (0, 400)], [0, 400]),
])
def test_self_times_add_up_to_busy_time(case, spans, want):
    events = [_op(i, start, dur) for i, (start, dur) in enumerate(spans)]
    window = (0, 2000)
    rows = scope_ledger.self_times(events, window)
    assert [own for _, own in rows] == want, case
    assert sum(want) == trace_reduce.busy_ns(events, window), case


@pytest.mark.parametrize("path,which", [
    pytest.param(path, None, id=_short(path)) for path in RECORDED
] + EXAMPLES)
def test_self_time_adds_up_on_every_recorded_trace_and_example(path, which):
    """Sum of self = ``busy_ns`` of the same events: on the two traces
    recorded on a TPU v5e and on every metric file's hand-made events."""
    if which is not None:
        trace = trace_reduce.from_example(_examples_of(path)[which])
    else:
        trace = trace_reduce.read_xplane(path, steps=2)
        assert trace.devices
    for events in trace.devices.values():
        rows = scope_ledger.self_times(events, trace.window)
        assert sum(own for _, own in rows) == pytest.approx(
            trace_reduce.busy_ns(trace_reduce.sync_ops(events),
                                 trace.window), rel=1e-12)
        assert all(own >= 0 for _, own in rows)
    if trace.devices:
        table = scope_ledger.table(trace)
        assert sum(row["ms"] for row in table) == pytest.approx(
            scope_ledger.busy_ms(trace), rel=1e-9)


@pytest.mark.parametrize("scope,want", [
    ("jit(step)/jvp(GPT)/h_0/hvd_block/ln_1/mul:", "hvd_block"),
    ("jit(step)/jvp(GPT)/h_0/hvd_block/hvd_attn/attn/hvd_attn_proj/qkv/"
     "dot_general:", "hvd_attn_proj"),
    # A plain kernel call is its layer's; a named one is a scope of its own
    # with ``pallas_call`` under it (as a chip's trace has them).  The last
    # segment is the operation, no scope.
    ("jit(step)/jvp(GPT)/h_0/hvd_block/hvd_attn/attn/pallas_call:",
     "hvd_attn"),
    ("jit(step)/jvp(SDAR)/layer_0/hvd_block/hvd_attn/attn/hvd_flash_fwd/"
     "pallas_call:", "hvd_flash_fwd"),
    ("jit(step)/transpose(jvp(JoyAI.loss))/JoyAI.hidden/layer_1/hvd_block/"
     "moe/cond/branch_0_fun/while/body/closed_call/checkpoint/"
     "transpose(jvp(hvd_moe_experts))/hvd_moe_tgmm/pallas_call:",
     "hvd_moe_tgmm"),
    ("jit(step)/jvp(Jamba.loss)/Jamba.hidden/layer_0/hvd_block/mamba/"
     "hvd_ssm_scan/reshape:", "hvd_ssm_scan"),
    ("jit(step)/jvp(SDAR)/layer_0/hvd_block/hvd_attn/attn/hvd_flash_fwd:",
     "hvd_attn"),
    # A custom_vjp's backward and a checkpointed block's second forward.
    ("jit(step)/transpose(jvp(Laguna.loss))/Laguna.hidden/layer_1/hvd_block/"
     "hvd_attn/attn/transpose(jvp(hvd_attn_gate))/mul:", "hvd_attn_gate"),
    ("jit(step)/transpose(jvp(Zaya.loss))/Zaya.hidden/checkpoint/"
     "rematted_computation/layer_3/hvd_block/moe/hvd_moe_router/router/"
     "mlp_0/dot_general:", "hvd_moe_router"),
    ("jit(step)/hvd_update/mul:", "hvd_update"),
    ("jit(step)/hvd_exchange/psum", "hvd_exchange"),
    ("jit(step)/add:", None),
    ("jit(step)/jvp(GPT)/h_0/attn/pallas_call:", None),
    ("hvd_update", None),       # an operation of that name, under no scope
    # No scope: not_hvd_block, hvd_Block and a name inside a longer one.
    ("jit(step)/not_hvd_block/mul:", None),
    ("jit(step)/hvd_Block/mul:", None),
    ("", None),
])
def test_layer_of_is_the_innermost_scope(scope, want):
    # The instruction's own name says nothing: XLA names a kernel call for
    # its kernel or for its innermost scope of any kind.
    named = Event("%jvp_hvd_flash_fwd_.1 = bf16[8] custom-call(bf16[8] %q), "
                  "custom_call_target=\"tpu_custom_call\"", 0, 10, SYNC_LINE,
                  scope)
    assert scope_ledger.layer_of(named) == want
    assert scope_ledger.layer_of(_op(1, 0, 10, scope)) == want
    # A compiled text's ``op_name`` has no closing colon.
    assert scope_ledger.layer_of_scope(scope.rstrip(":")) == want


def test_the_readers_take_the_mean_over_four_devices():
    scope = "jit(step)/jvp(GPT)/h_0/hvd_block/hvd_mlp/mlp_in/dot_general:"
    devices = {d: [_op(1, 0, 100.0 * (d + 1), scope),
                   _op(2, 500, 100, "jit(step)/add:")] for d in range(4)}
    trace = Trace(devices, [], (0, 1000), 2)
    # 100, 200, 300, 400 ns: 250 a device, over 2 steps.
    assert scope_ledger.self_time_ms(trace, {}, layer="^hvd_mlp$") == \
        pytest.approx(125e-6)
    want = 100 * sum(100 / (100.0 * (d + 1) + 100) for d in range(4)) / 4
    assert scope_ledger.unattributed_pct(trace, {}) == pytest.approx(want)
    assert scope_ledger.busy_ms(trace) == pytest.approx(175e-6)
    assert scope_ledger.unattributed_pct(Trace({}, [], (0, 1000), 2),
                                         {}) is None


def test_the_table_s_rows_and_their_order():
    events = _a_while_and_its_body() + [
        _op(4, 1000, 50, "jit(step)/add:"),
        _op(5, 1050, 150, "",
            name="%copy.3 = f32[8] copy(f32[8] %w)"),
        _op(6, 1200, 500, "jit(step)/transpose(jvp(SDAR))/layer_0/hvd_block/"
            "moe/hvd_moe_experts/while/body/dot_general:")]
    trace = Trace({0: events}, [], (0, 2000), 2)
    stats = {events[2].name: {"flops": 800, "bytes_accessed": 64}}
    rows = scope_ledger.table(trace, stats=stats)
    assert [(r["layer"], r["pass"], r["op"], r["ms"] * 2e6) for r in rows] \
        == [("hvd_moe_experts", "backward", "fusion", 500.0),
            ("hvd_moe_experts", "forward", "fusion", 400.0),
            ("hvd_block", "forward", "while", 300.0),
            ("hvd_moe_route", "forward", "fusion", 300.0),
            (scope_ledger.UNATTRIBUTED, "neither", "copy", 150.0),
            (scope_ledger.UNATTRIBUTED, "neither", "fusion", 50.0)]
    assert rows[1]["flops"] == 400 and rows[1]["bytes"] == 32   # a step
    assert [r.get("scope") for r in rows[-2:]] == ["", "jit(step)/add:"]
    assert all("scope" not in r for r in rows[:-2])
    by_layer = scope_ledger.table(trace, by=("layer",))
    assert [(r["layer"], r["ms"] * 2e6) for r in by_layer] == [
        ("hvd_moe_experts", 900.0), ("hvd_block", 300.0),
        ("hvd_moe_route", 300.0), (scope_ledger.UNATTRIBUTED, 150.0),
        (scope_ledger.UNATTRIBUTED, 50.0)]
    assert sum(r["ms"] for r in rows) == pytest.approx(
        scope_ledger.busy_ms(trace))
    assert scope_ledger.unattributed_pct(trace, {}) == pytest.approx(
        100 * 200 / 1700)
    with pytest.raises(ValueError, match="by="):
        scope_ledger.table(trace, by=("kernel",))


def _tool():
    spec = importlib.util.spec_from_file_location(
        "step_ledger", os.path.join(REPO, "tools", "step_ledger.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_tool_s_closing_line_on_a_recorded_trace():
    """``tools/step_ledger.py`` on the recorded flash trace, in process:
    every row, the closing sum equal to ``busy_s / steps`` to three
    decimals, and the JSON the same table."""
    tool = _tool()
    path, = (p for p in RECORDED if "flash" in p)
    out = io.StringIO()
    assert tool.main([path, "--steps", "2"], out=out) == 0
    lines = out.getvalue().splitlines()
    closing = lines[-1]
    assert closing.startswith("sum of rows = ")
    total, busy = (float(part.split(" = ")[1].split(" ")[0])
                   for part in closing.split("; "))
    assert total == busy == pytest.approx(0.101, abs=5e-4)
    assert lines[-2] == "step_unattributed_pct = 100.000"   # recorded before
    as_json = io.StringIO()                                  # the scopes
    assert tool.main([path, "--steps", "2", "--json"], out=as_json) == 0
    found = json.loads(as_json.getvalue())
    assert found["sum_of_rows_ms"] == pytest.approx(
        found["busy_ms_per_step"], rel=1e-9)
    # A header, a line a layer, a line a row, the two closing lines.
    assert len(lines) == 2 + 1 + len(found["rows"]) + 2
    kernels = [r for r in found["rows"] if r["op"] == "attn"]
    assert {r["pass"] for r in kernels} == {"forward", "backward"}
    assert any(r["flops"] > 0 for r in found["rows"])
    shorter = io.StringIO()
    tool.main([path, "--steps", "2", "--rows", "5", "--by", "pass,op"],
              out=shorter)
    assert "more rows (--rows)" in shorter.getvalue()
    assert shorter.getvalue().splitlines()[-1] == closing


HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}

%fused_computation.1 (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  ROOT %multiply.9 = f32[8,16]{1,0} multiply(f32[8,16]{1,0} %param_0, f32[8,16]{1,0} %param_0), metadata={op_name="jit(step)/hvd_update/mul"}
}

ENTRY %main.5 (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16]{1,0} parameter(0), metadata={op_name="params"}
  %copy.1 = f32[8,16]{0,1} copy(f32[8,16]{1,0} %p)
  %dot.2 = (bf16[8,16]{1,0}, f32[8]{0}) custom-call(f32[8,16]{0,1} %copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(GPT)/h_0/hvd_block/hvd_attn/attn/pallas_call"}
  %transpose.3 = f32[16,8]{1,0} transpose(f32[8,16]{1,0} %p), dimensions={1,0}, metadata={op_name="jit(step)/jvp(GPT)/h_0/hvd_block/hvd_mlp/mlp_in/transpose"}
  ROOT %fusion.4 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/hvd_update/mul"}
}
"""


def test_the_tool_s_census_of_a_compiled_text(tmp_path):
    tool = _tool()
    found = tool.census(HLO)
    rows = {(r["layer"], r["opcode"]): (r["instructions"], r["result_bytes"])
            for r in found["rows"]}
    assert rows == {
        ("hvd_attn", "custom-call"): (1, 8 * 16 * 2 + 8 * 4),
        ("hvd_mlp", "transpose"): (1, 512), ("hvd_update", "fusion"): (1, 512),
        (scope_ledger.UNATTRIBUTED, "parameter"): (1, 512),
        (scope_ledger.UNATTRIBUTED, "copy"): (1, 512)}   # no fused insides
    assert [(r["opcode"], r["layer"], r["result"]) for r in
            found["relayouts"]] == [
        ("copy", scope_ledger.UNATTRIBUTED, "f32[8,16]"),
        ("transpose", "hvd_mlp", "f32[16,8]")]
    text = tmp_path / "step.txt"
    text.write_text(HLO)
    out = io.StringIO()
    assert tool.main(["--hlo", str(text)], out=out) == 0
    assert "# 2 of copy, copy-done, slice-done, transpose" in out.getvalue()
    with pytest.raises(SystemExit):
        tool.main([])
