"""The benchmark's own files: every name resolves, the arithmetic of the
yardstick (FLOPs, trace reduction, end-to-end statistics) against hand
numbers, and ``run.py --rehearse`` end to end at tiny sizes on the CPU.

Nothing here measures anything: a CPU run says what the program counts and
that the control flow is right.  No TPU topology is described anywhere in
this file."""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, run, trace_reduce, xplane_raw  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.trace_reduce import Event, Trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
DATA_DIRS = ("configs", "traffic", "layer_metrics")
DATA_FILES = sorted(
    os.path.relpath(p, BENCH) for d in DATA_DIRS
    for p in glob.glob(os.path.join(BENCH, d, "*.json")))


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Files and names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rel", DATA_FILES)
def test_data_file_loads_and_is_named_for_its_content(rel):
    data = _load(rel)
    assert data["name"] == os.path.splitext(os.path.basename(rel))[0]
    assert NAME.match(data["name"])
    kind = os.path.dirname(rel)
    if kind == "configs":
        family = run.load_family(data["family"])
        for fn in ("setup", "inputs", "model_flops", "reference", "build",
                   "checks", "units"):
            assert callable(getattr(family, fn)), (data["family"], fn)
        assert data["source"].startswith("http")
        assert isinstance(data["reduced"], list)
        assert common.make_optimizer(data["optimizer"]).update
    elif kind == "traffic":
        for sizes in (traffic_gen.resolve(data, False),
                      traffic_gen.resolve(data, True)):
            assert sizes["batch_per_chip"] > 0
            assert sizes["distinct_batches"] >= 1
            assert sizes["warmup_steps"] >= 1 and sizes["trace_steps"] >= 2
            assert "rehearse" not in sizes
    else:
        assert callable(common.load_function(data["reducer"]))
        # What BENCHMARK.json states of a metric is stated there alone.
        assert set(data) <= {"name", "reducer", "params", "what",
                             "pattern_note", "example", "examples"}
        # A metric proves itself: what its reader returns for a few events,
        # and a case in which there is nothing for it to read; a metric
        # that several families share, once for each cell that brought one.
        assert ("example" in data) != bool(data.get("examples")), (
            f"{rel} has no \"example\" (benchmark/README.md, \"A per-layer "
            "metric\")")
        entry, = [m for m in _spec()["per_layer"] if m["name"] == data["name"]]
        for cell, example in _examples(data["name"]):
            assert cell is None or cell in entry["workloads"], (rel, cell)
            assert {"window", "steps", "value", "nothing"} <= set(example) <= {
                "what", "events", "host", "context", "window", "steps",
                "value", "nothing", "cell", "was"}
            assert isinstance(example["value"], (int, float))
            assert set(example["nothing"]) <= {"events", "host", "context"}


@pytest.mark.parametrize("group,key,folder", [
    ("configs", "name", "configs"), ("workloads", "traffic", "traffic"),
    ("per_layer", "name", "layer_metrics")])
def test_every_name_resolves_to_a_file_and_back(group, key, folder):
    """What makes the harness data-driven: what an entry of BENCHMARK.json
    names is a file, and every file is named by an entry."""
    named = {e[key] for e in _spec()[group]}
    files = {os.path.splitext(os.path.basename(p))[0]
             for p in DATA_FILES if p.startswith(folder + "/")}
    assert named == files
    for entry in _spec()["configs"] if group == "configs" else ():
        data = _load(f"configs/{entry['name']}.json")
        assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
        assert (entry["source"], entry["reduced"]) == (data["source"],
                                                       data["reduced"])
    for w in _spec()["workloads"]:
        assert run.cell_entry(_spec(), w["name"]) == w
        assert w["config"] in {c["name"] for c in _spec()["configs"]}


def test_benchmark_json_keeps_the_contract():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python", "benchmark/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[g]]
    assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(cells) // 4)
    for w in spec["workloads"]:  # every cell reports a per-layer metric
        assert run.metrics_of(spec, "per_layer", w["name"])


def test_unknown_device_kind_is_an_error_and_v5e_is_on_record():
    table = _load("peaks.json")["peaks"]
    v5e = flops.chip_peaks("TPU v5 lite", table)
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert flops.chip_peaks("TPU v5p", table)["bf16_flops_per_s"] == 459e12
    with pytest.raises(ValueError, match="no published peaks"):
        flops.chip_peaks("TPU v9 imaginary", table)


# ---------------------------------------------------------------------------
# flops.py against hand numbers
# ---------------------------------------------------------------------------


def _shapes(fn):
    import jax

    return jax.eval_shape(fn)


def test_flops_of_one_convolution():
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((2, 16, 16, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 8, 32), jnp.float32)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    # 2 x 8 x 8 x 32 outputs, each a 3 x 3 x 8 window.
    assert flops.forward_macs(conv, x, w) == 2 * 8 * 8 * 32 * 72
    assert flops.train_flops(100.0) == 600.0


def test_flops_of_one_gpt_block():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu import models
    from horovod_tpu.models.gpt import GPTBlock

    cfg = dataclasses.replace(models.GPT_TINY, hidden_size=64, num_heads=4)
    block = GPTBlock(cfg)
    s, h = 32, 64
    x = jax.ShapeDtypeStruct((1, s, h), jnp.float32)
    params = _shapes(lambda: block.init(jax.random.key(0),
                                        jnp.zeros((1, s, h))))
    dense = 12 * h * h * s           # qkv 3h^2, out h^2, mlp 8h^2 per token
    attention = 2 * s * s * h        # QK^T and PV over all heads
    assert flops.forward_macs(block.apply, params, x) == dense + attention
    assert flops.forward_macs(block.apply, params, x,
                              batched_scale=0.5) == dense + attention / 2


@pytest.mark.parametrize("model,gmacs", [
    ("resnet50", 4.0892), ("gpt2-medium", 413.525)])
def test_flops_of_the_published_models(model, gmacs):
    """ResNet-50 at 224^2: 4.089 GMACs forward per image (24.5 GFLOP forward
    + backward).  GPT-2-medium at 1024 tokens: 413.5 GMACs forward per
    sequence with full attention, 2.272 GFLOP per token forward + backward
    with causal attention halved."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    if model == "resnet50":
        m = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        v = _shapes(lambda: m.init(jax.random.key(0),
                                   jnp.zeros((2, 224, 224, 3)), train=False))
        x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.bfloat16)
        macs = flops.forward_macs(lambda v, x: m.apply(v, x, train=False),
                                  v, x)
        assert flops.train_flops(macs) / 1e9 == pytest.approx(24.535, abs=1e-3)
    else:
        m = models.GPT(models.GPTConfig(hidden_size=1024, num_layers=24,
                                        num_heads=16, use_flash=False))
        v = _shapes(lambda: m.init(jax.random.key(0),
                                   jnp.zeros((1, 32), jnp.int32)))
        assert sum(x.size for x in jax.tree_util.tree_leaves(v)) == 406382592
        ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
        macs = flops.forward_macs(m.apply, v, ids)
        causal = flops.forward_macs(m.apply, v, ids, batched_scale=0.5)
        assert flops.train_flops(causal) / 1024 / 1e9 == pytest.approx(
            2.272, abs=1e-3)
    assert macs / 1e9 == pytest.approx(gmacs, abs=1e-3)


def test_flash_least_seconds_by_hand():
    # One layer, one head, one sequence of 1024 x 64 in bf16, causal:
    # an S x S x D matmul is 2 * 1024 * 1024 * 64 = 134.2 MFLOP, halved.
    one = 2 * 1024 * 1024 * 64 * 0.5
    array = 1024 * 64 * 2
    got = flops.flash_least_seconds(1, 1, 1024, 64, 1, True, 2,
                                    peak_flops=1e12, peak_bytes_per_s=1e9)
    k = got["kernels"]
    assert [k[n]["flops"] for n in ("fwd", "dq", "dkv")] == [
        2 * one, 3 * one, 4 * one]
    assert k["fwd"]["bytes"] == 4 * array + 1024 * 4
    assert k["dkv"]["bytes"] == 6 * array + 2 * 1024 * 4
    # At 1 TFLOP/s against 1 GB/s every kernel is bound by its bytes.
    assert {v["bound"] for v in k.values()} == {"bytes"}
    assert got["seconds"] == pytest.approx(got["bytes"] / 1e9)
    fast = flops.flash_least_seconds(8, 16, 1024, 64, 24, True, 2,
                                     197e12, 819e9)
    assert {v["bound"] for v in fast["kernels"].values()} == {"flops"}
    assert fast["seconds"] == pytest.approx(9 * one * 128 * 24 / 197e12)


# ---------------------------------------------------------------------------
# The reduction's core on hand-made events
# ---------------------------------------------------------------------------

# One device, window 0..1000 ns, two steps.  fusion.1 and all-reduce.1
# overlap for 100 ns; all-reduce.2 runs alone.
EVENTS = [
    Event("fusion.1", 0, 300, "XLA Ops"),
    Event("all-reduce.1", 200, 200, "XLA Ops"),
    Event("_mha_kernel", 500, 100, "XLA Ops"),
    Event("all-reduce.2", 700, 100, "XLA Ops"),
    Event("fusion.1", 900, 200, "XLA Ops"),     # runs past the window
]
HOST = [Event("bench_dispatch", 390, 20, "python"),
        Event("bench_wait", 410, 500, "python")]
WINDOW = (0.0, 1000.0)
TRACE = Trace({0: EVENTS}, HOST, WINDOW, 2)
# The same host loop with the program's own spans inside it: hvd_launch
# covers most of the gap 400..500 from inside bench_wait, hvd_enqueue only
# a fifth of it.
NESTED = HOST + [Event("hvd_launch", 420, 75, "python"),
                 Event("hvd_enqueue", 495, 25, "python"),
                 Event("hvd_poll", 100, 50, "worker")]
# Scopes as JAX writes them: forward, backward, optimizer, none.
SCOPED = [
    Event("%fusion.1 = f32[8] fusion()", 0, 300, "XLA Ops",
          "jit(step)/jvp(GPT)/h_0/attn/dot_general:"),
    Event("%attn.1 = bf16[8] custom-call()", 300, 100, "XLA Ops",
          "jit(step)/jvp(GPT)/h_0/attn/pallas_call:"),
    Event("%attn.2 = bf16[8] custom-call()", 400, 200, "XLA Ops",
          "jit(step)/transpose(jvp(GPT))/h_0/attn/pallas_call:"),
    Event("%fusion.2 = f32[8] fusion()", 600, 100, "XLA Ops",
          "jit(step)/add:"),
    Event("%copy.1 = f32[8] copy()", 700, 50, "XLA Ops"),
    Event("%copy-start.1 = f32[8] copy-start()", 0, 900, "Async XLA Ops",
          "jit(step)/jvp(GPT)/wte/gather:"),
]
SCOPED_TRACE = Trace({0: SCOPED}, NESTED, WINDOW, 2)


def _in_ns(rows):
    return [[name, round(seconds * 1e9)] for name, seconds in rows]


@pytest.mark.parametrize("case,got,want", [
    ("merge", lambda: trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8),
                                          (4, 4)]), [(0, 3), (5, 8)]),
    ("subtract", lambda: trace_reduce.subtract([(0, 10), (20, 30)],
                                               [(2, 4), (8, 22), (25, 26)]),
     [(0, 2), (4, 8), (22, 25), (26, 30)]),
    ("busy", lambda: trace_reduce.busy_ns(EVENTS, WINDOW), 700.0),
    ("idle_share", lambda: trace_reduce.idle_share(EVENTS, WINDOW), 0.3),
    ("classify", lambda: [e.name for e in trace_reduce.classify(
        EVENTS, "all-reduce|all-gather")[0]],
     ["all-reduce.1", "all-reduce.2"]),
    ("exposed", lambda: trace_reduce.exposed_ns(EVENTS, "all-reduce", WINDOW),
     200.0),
    ("per_step", lambda: trace_reduce.per_step(3e6, 2), 1.5),
    ("top_ops", lambda: _in_ns(trace_reduce.top_ops(EVENTS, WINDOW, n=2)),
     [["fusion", 400], ["all-reduce", 300]]),
    ("idle_gaps", lambda: _in_ns(trace_reduce.idle_gaps(
        EVENTS, HOST, WINDOW, n=2)),
     [["bench_wait", 100], ["bench_wait", 100]]),
    # The innermost span that covers most of a gap names it; a span that
    # covers less than half does not, however deep it lies.
    ("idle_gaps_innermost", lambda: _in_ns(trace_reduce.idle_gaps(
        EVENTS, NESTED, WINDOW, n=3)),
     [["hvd_launch", 100], ["bench_wait", 100], ["bench_wait", 100]]),
    ("classify_scope", lambda: [e.name[:7] for e in trace_reduce.classify(
        SCOPED, "", scope=r"jvp\(", scope_not=r"transpose\(")[0]],
     ["%fusion", "%attn.1", "%copy-s"]),
    ("classify_sync_line", lambda: [e.name[:7] for e in trace_reduce.classify(
        SCOPED, "", scope_not=r"jvp\(|transpose\(", line="sync")[0]],
     ["%fusion", "%copy.1"]),
    ("from_example", lambda: trace_reduce.from_example(
        {"events": [["op", 5, 10, "XLA Ops", "a/b:"]],
         "host": [["hvd_x", 0, 4, "python"]], "window": [0, 20], "steps": 3},
        shift_ns=100),
     Trace({0: [Event("op", 105, 10, "XLA Ops", "a/b:")]},
           [Event("hvd_x", 100, 4, "python")], (100, 120), 3)),
])
def test_reduction_core(case, got, want):
    got = got()
    assert got == want or got == pytest.approx(want)


def _least_25ns(ctx):
    return {"seconds": 25e-9}


@pytest.mark.parametrize("reader,params,ctx,want", [
    ("device_idle_pct", {}, {}, 30.0),
    ("op_time_ms", {"pattern": "all-reduce"}, {}, 300 / 2 / 1e6),
    ("op_time_ms", {"pattern": "all-reduce", "exposed": True}, {},
     200 / 2 / 1e6),
    ("op_time_ms", {"pattern": "no-such-op"}, {}, None),
    ("roofline_pct", {"pattern": "_mha_", "least": "least_25ns.fn"}, {},
     50.0),
    ("roofline_pct", {"pattern": "no-such-op", "least": "least_25ns.fn"}, {},
     None),
    ("host_dispatch_ms", {}, {"run": {"dispatch_s": [0.001, 0.003]}}, 2.0),
    ("host_dispatch_ms", {}, {}, None),
])
def test_layer_metric_readers(reader, params, ctx, want, monkeypatch):
    # A metric's file names the function that computes its least time.
    monkeypatch.setattr(common, "load_function", lambda dotted: {
        "least_25ns.fn": _least_25ns}[dotted])
    got = getattr(trace_reduce, reader)(TRACE, ctx, **params)
    assert got == (want if want is None else pytest.approx(want))


def _least_by_kernel(ctx):
    return {"seconds": 90e-9,
            "kernels": {"fwd": {"seconds": 25e-9}, "dq": {"seconds": 30e-9}}}


@pytest.mark.parametrize("reader,params,want", [
    # The three classes of a step: disjoint, on the core's own line, and
    # together all the time the core is busy (750 ns over 2 steps).
    ("op_time_ms", {"scope": r"jvp\(", "scope_not": r"transpose\(",
                    "line": "sync"}, 400 / 2 / 1e6),
    ("op_time_ms", {"scope": r"transpose\(", "line": "sync"}, 200 / 2 / 1e6),
    ("op_time_ms", {"scope_not": r"jvp\(|transpose\(", "line": "sync"},
     150 / 2 / 1e6),
    # Without ``line`` the async line counts, as it always did.
    ("op_time_ms", {"scope": r"jvp\(", "scope_not": r"transpose\("},
     900 / 2 / 1e6),
    # Name and scope together: the kernel calls of one pass.
    ("op_time_ms", {"pattern": "^%attn", "scope_not": r"transpose\("},
     100 / 2 / 1e6),
    ("op_time_ms", {"pattern": "^%attn", "scope": r"transpose\("},
     200 / 2 / 1e6),
    ("op_time_ms", {"pattern": "^%attn", "scope": "no-such-scope"}, None),
    ("roofline_pct", {"pattern": "^%attn", "least": "by_kernel.fn"},
     100 * 90 / 150),
    ("roofline_pct", {"pattern": "^%attn", "least": "by_kernel.fn",
                      "least_key": "kernels.fwd",
                      "scope_not": r"transpose\("}, 100 * 25 / 50),
    # Host spans, the benchmark's and the program's: the union of those
    # that match, or each one's own time without its children's.
    ("host_span_ms", {"pattern": "^bench_wait$"}, 500 / 2 / 1e6),
    ("host_span_ms", {"pattern": "^bench_wait$", "self_time": True},
     400 / 2 / 1e6),
    ("host_span_ms", {"pattern": "^hvd_"}, 150 / 2 / 1e6),
    ("host_span_ms", {"pattern": "^hvd_", "self_time": True}, 150 / 2 / 1e6),
    ("host_span_ms", {"pattern": "^(bench_wait|hvd_launch)$",
                      "self_time": True}, 475 / 2 / 1e6),
    ("host_span_ms", {"pattern": "^hvd_no_such_span$"}, None),
])
def test_readers_by_scope_line_kernel_and_host_span(reader, params, want,
                                                    monkeypatch):
    monkeypatch.setattr(common, "load_function", lambda dotted: {
        "by_kernel.fn": _least_by_kernel}[dotted])
    got = getattr(trace_reduce, reader)(SCOPED_TRACE, {}, **params)
    assert got == (want if want is None else pytest.approx(want))
    with pytest.raises(ValueError, match="sync"):
        trace_reduce.classify(SCOPED, "", line="async")


def test_flash_least_time_comes_from_the_cells_own_files():
    spec = _spec()
    entry = run.cell_entry(spec, "gpt2m-1chip")
    up = {"cfg": _load(f"configs/{entry['config']}.json"), "cell": {},
          "traffic": traffic_gen.resolve(
              _load(f"traffic/{entry['traffic']}.json"), False)}
    table = _load("peaks.json")["peaks"]
    ctx = run.reader_context(up, {}, flops.chip_peaks("TPU v5 lite", table),
                             xplane="benchmark/_trace/x.xplane.pb",
                             workload="gpt2m-1chip")
    assert set(ctx) == {"run", "cell", "cfg", "traffic", "peaks", "xplane",
                        "workload"}
    assert (ctx["xplane"], ctx["workload"]) == (
        "benchmark/_trace/x.xplane.pb", "gpt2m-1chip")
    direct = flops.flash_least_seconds(8, 16, 1024, 64, 24, True, 2,
                                       197e12, 819e9)
    assert flops.flash_step_least(ctx) == direct
    assert direct["seconds"] == pytest.approx(9.42e-3, rel=1e-3)
    with pytest.raises(ValueError, match="no HBM peak"):
        flops.flash_step_least(run.reader_context(
            up, {}, flops.chip_peaks("TPU v4", table)))


def test_an_async_collective_counts_once_and_is_not_the_core_being_busy():
    """The async line shows a collective in flight beside the core's ops:
    it is collective time, hidden while the core computes, exposed where it
    does not, and by itself it does not make the device busy."""
    events = [Event("%fusion.1 = f32[8] fusion(...)", 0, 400, "XLA Ops"),
              Event("%all-reduce-start.1 = ...", 100, 10, "XLA Ops"),
              Event("%all-reduce-start.1 = ...", 100, 500, "Async XLA Ops"),
              Event("%all-reduce-done.1 = ...", 590, 10, "XLA Ops")]
    trace = Trace({0: events}, [], WINDOW, 1)
    assert trace_reduce.op_time_ms(trace, {}, pattern="all-reduce") == \
        pytest.approx(500 / 1e6)
    assert trace_reduce.op_time_ms(trace, {}, pattern="all-reduce",
                                   exposed=True) == pytest.approx(200 / 1e6)
    assert trace_reduce.device_idle_pct(trace, {}) == pytest.approx(59.0)
    assert trace_reduce.top_ops(events, WINDOW, n=1) == [["fusion", 400e-9]]


METRIC_FILES = sorted(os.path.splitext(os.path.basename(p))[0]
                      for p in DATA_FILES if p.startswith("layer_metrics/"))


def _examples(name):
    """``[(cell, example), ...]`` of a metric file: its one ``example`` (the
    first cell of its entry's list reads it) or its ``examples``, each of the
    cell it names."""
    return [(e.get("cell"), e) for e in trace_reduce.examples_of(
        _load(f"layer_metrics/{name}.json"))]


def _example(name):
    """The file's first example: the one that goes into the trace of all."""
    return _examples(name)[0][1]


EXAMPLES = [pytest.param(name, i, id=f"{name}:{cell}" if cell else name)
            for name in METRIC_FILES
            for i, (cell, _) in enumerate(_examples(name))]


def _nothing(example):
    """The example's case with nothing to read, at its window and steps."""
    return {"window": example["window"], "steps": example["steps"],
            **example["nothing"]}


def _merged(a: dict, b: dict, where: str) -> dict:
    """``a`` and ``b`` as one context; a key both state must agree."""
    out = dict(a)
    for key, value in b.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merged(out[key], value, f"{where}.{key}")
        else:
            assert out.setdefault(key, value) == value, (
                f"examples disagree on context{where}.{key}: give it the "
                "value the other metric files' examples use")
    return out


def _all_examples_in_one_trace():
    """Every metric file's example, one after the other in time on the same
    devices, and their contexts merged: a trace in which every reader finds
    what it reads."""
    devices, host, ctx, at, steps = {}, [], {}, 0.0, 0
    for name in METRIC_FILES:
        example = _example(name)
        part = trace_reduce.from_example(example,
                                         shift_ns=at - example["window"][0])
        for d, events in part.devices.items():
            devices.setdefault(d, []).extend(events)
        host.extend(part.host)
        ctx = _merged(ctx, example.get("context", {}), "")
        at, steps = part.window[1], max(steps, part.steps)
    return Trace(devices, host, (0.0, at), steps), ctx


@pytest.mark.parametrize("name,which", EXAMPLES)
def test_metric_file_reads_its_own_example(name, which):
    """``reducer``, ``params`` and one example of one file held together,
    through the harness's own lookup: the reader returns the value the file
    states for the example's events, in the cell the example is of, and
    nothing for its ``nothing`` case.  A new metric brings its file and its
    entry; no test is edited for it."""
    spec = _spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    alone = {**spec, "per_layer": [entry]}
    cell, example = _examples(name)[which]
    cell = cell or (entry.get("workloads")
                    or [spec["workloads"][0]["name"]])[0]
    got = run.per_layer(alone, cell, trace_reduce.from_example(example),
                        example.get("context", {}))
    assert got == {name: {"value": pytest.approx(example["value"],
                                                 rel=1e-9),
                          "unit": entry["unit"]}}
    nothing = _nothing(example)
    assert run.per_layer(alone, cell, trace_reduce.from_example(nothing),
                         nothing.get("context", {})) == {}


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_per_layer_goes_through_each_metric_file_of_the_cell(cell):
    """The harness finds each reader by the name in BENCHMARK.json.  From a
    trace made of every metric file's example, a cell reports exactly the
    metrics BENCHMARK.json lists for it (what other cells' metrics could
    read is there too, and is not reported); what a reader cannot find is
    left out of the line."""
    trace, ctx = _all_examples_in_one_trace()
    got = run.per_layer(_spec(), cell, trace, ctx)
    want = {m["name"] for m in run.metrics_of(_spec(), "per_layer", cell)}
    assert set(got) == want
    assert all(set(v) == {"value", "unit"} for v in got.values())
    # Nothing to read: every metric is left out (each file's own ``nothing``
    # case goes through its reader in the test above).
    assert run.per_layer(_spec(), cell, Trace({}, [], WINDOW, 2), {}) == {}


def test_a_new_metric_is_one_file_and_one_entry(tmp_path):
    """A later PR adds ``layer_metrics/<metric>.json`` and an entry under
    ``per_layer`` and edits nothing: in a copy of the spec and the
    benchmark's files with a seventh metric added that way, the tests of
    files, names and examples pass as they stand; with the file's example
    taken out they fail and say what is missing."""
    import shutil

    for rel in ("BENCHMARK.json", "benchmark", os.path.join("tests",
                                                            "benchmark")):
        src, dst = os.path.join(REPO, rel), tmp_path / rel
        if os.path.isdir(src):
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                "_trace", "__pycache__"))
        else:
            shutil.copy(src, dst)
    metric = {
        "name": "matmul_fusion_ms", "reducer": "trace_reduce.op_time_ms",
        "params": {"pattern": "^%fusion", "scope": "dot_general:$",
                   "line": "sync"},
        "what": "device time per step of the fusions rooted in a matmul",
        "example": {
            "events": [["%fusion.1 = f32[8] fusion()", 0, 300, "XLA Ops",
                        "jit(step)/jvp(GPT)/h_0/mlp_in/dot_general:"],
                       ["%fusion.2 = f32[8] fusion()", 300, 100, "XLA Ops",
                        "jit(step)/add:"]],
            "window": [0, 1000], "steps": 2, "value": 300 / 2 / 1e6,
            "nothing": {"events": [["%copy.1 = f32[8] copy()", 0, 9,
                                    "XLA Ops"]]}}}
    spec = _spec()
    spec["per_layer"].append({
        "name": metric["name"], "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model (forward / backward)",
        "moves": "step_ms", "workloads": ["gpt2m-1chip", "gpt2m-dp4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    file = tmp_path / "benchmark" / "layer_metrics" / "matmul_fusion_ms.json"

    def tests_of_the_copy():
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join([str(tmp_path), REPO])}
        return subprocess.run(
            [sys.executable, "-m", "pytest", "tests/benchmark", "-v",
             "-p", "no:cacheprovider", "-k",
             "data_file_loads or resolves_to_a_file or benchmark_json_keeps "
             "or own_example or per_layer_goes_through"],
            env=env, cwd=tmp_path, capture_output=True, text=True,
            timeout=240)

    file.write_text(json.dumps(metric))
    done = tests_of_the_copy()
    assert done.returncode == 0, done.stdout[-3000:]
    assert re.search(r"own_example\[matmul_fusion_ms\] PASSED", done.stdout)
    assert re.search(r"data_file_loads\w+\[layer_metrics/matmul_fusion_ms"
                     r"\.json\] PASSED", done.stdout)
    del metric["example"]
    file.write_text(json.dumps(metric))
    done = tests_of_the_copy()
    assert done.returncode != 0
    assert 'matmul_fusion_ms.json has no "example"' in done.stdout


def test_collective_pattern_matches_the_opcode_not_an_operand():
    pattern = _load("layer_metrics/collective_ms.json")["params"]["pattern"]
    assert pattern == _load(
        "layer_metrics/collective_exposed_ms.json")["params"]["pattern"]
    op = ("%psum_invariant.2347 = f32[1024,50304]{1,0:T(8,128)} all-reduce("
          "f32[1024,50304]{1,0:T(8,128)} %fusion.14), channel_id=1")
    start = "%ar.1 = (f32[8], f32[8]) all-reduce-start(f32[8] %x)"
    user = "%fusion.7 = f32[8] fusion(f32[8] %all-reduce.3), kind=kLoop"
    events = [Event(n, 0, 10, "XLA Ops") for n in (op, start, user)]
    assert [e.name for e in trace_reduce.classify(events, pattern)[0]] == [
        op, start]
    flash = _load("layer_metrics/flash_kernel_ms.json")["params"]["pattern"]
    kernel = ('%attn.72 = (bf16[128,1024,64]{2,1,0}) custom-call(bf16[128,'
              '1024,64]{2,1,0} %b), custom_call_target="tpu_custom_call"')
    concat = ('%custom-call.29 = f32[3,3,256,256] custom-call(f32[1,3,256,'
              '256] %s), custom_call_target="ConcatBitcast"')
    codec = ('%quantize.3 = s8[4096]{0} custom-call(f32[4096]{0} %x), '
             'custom_call_target="tpu_custom_call"')
    user = ('%fusion.9 = bf16[8] fusion(bf16[8] %attn.72), kind=kLoop, '
            'calls=%fused_computation.9')
    events = [Event(n, 0, 10, "XLA Ops")
              for n in (kernel, concat, codec, user)]
    assert [e.name for e in trace_reduce.classify(events, flash)[0]] == [
        kernel]
    assert flash == _load("layer_metrics/flash_roofline.json")["params"][
        "pattern"]


def test_readers_average_over_the_chips_of_the_cell():
    busy_all = [Event("fusion.1", 0, 1000, "XLA Ops")]
    two = Trace({0: EVENTS, 1: busy_all}, [], WINDOW, 2)
    assert trace_reduce.device_idle_pct(two, {}) == pytest.approx(15.0)


def _stamps(step_s, stall_every=None, stall_s=0.0, steps=100):
    stamps, t = [0.0], 0.0
    for i in range(steps):
        t += step_s + (stall_s if stall_every and i % stall_every ==
                       stall_every - 1 else 0.0)
        stamps.append(t)
    return {"stamps": stamps}


def test_end_to_end_statistics():
    # 100 steps of 10 ms, ten of them 20 ms: the whole window over all its
    # steps is 11 ms, the tail lies on the edge, and the utilization counts
    # every stall.
    got = run.end_to_end(_stamps(0.010, 10, 0.010), flops_per_step=1e9,
                         chips=2, peak_flops=1e12)
    assert got["step_ms"] == pytest.approx(11.0)
    assert 10.0 <= got["step_ms_p90"] <= 20.0
    assert got["mfu_pct"] == pytest.approx(100 * 100 * 1e9 / (1.1 * 2 * 1e12))


def test_a_stall_every_twentieth_step_moves_step_ms():
    """The case a median of the gaps would not see (REVIEW of PR 23): five
    steps in a hundred wait 40 ms for the host.  The tail does not see it
    either (5 % of the samples), so the time per step has to."""
    kw = dict(flops_per_step=1e9, chips=1, peak_flops=1e12)
    smooth = run.end_to_end(_stamps(0.010), **kw)
    stalled = run.end_to_end(_stamps(0.010, 20, 0.040), **kw)
    assert smooth["step_ms"] == pytest.approx(10.0)
    assert stalled["step_ms"] == pytest.approx(12.0)
    assert stalled["step_ms_p90"] == pytest.approx(smooth["step_ms_p90"])
    assert stalled["mfu_pct"] == pytest.approx(smooth["mfu_pct"] / 1.2)


def test_timed_metrics_are_the_cells_end_to_end_metrics(capsys):
    class Family:
        @staticmethod
        def units(cell):
            return "tokens", 8192

    run_ = {**_stamps(0.010, 20, 0.040), "losses": [1.0] * 100,
            "dispatch_s": [0.002] * 100,
            "gc": {"collections": 0, "seconds": 0.0, "longest_s": 0.0},
            "compiles": run.CompileWatch().summary()}
    up = {"family": Family, "cell": {}, "device": {"count": 1},
          "flops_per_step": 1e9, "setup_s": 30.0, "first_loss": 2.0}
    got = run.timed_metrics(
        type("Args", (), {"workload": "gpt2m-1chip"}), _spec(), up, run_,
        {"bf16_flops_per_s": 1e12})
    assert set(got) == {m["name"] for m in _spec()["end_to_end"]}
    assert got["step_ms"] == {"value": pytest.approx(12.0), "unit": "ms"}
    assert got["setup_s"]["value"] == 30.0
    shown = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert shown["note"] == "throughput" and shown["steps"] == 100
    assert shown["step_ms_median"] == pytest.approx(10.0)
    # Where the window stood still: the five longest steps, by index.
    assert {i for i, _ in shown["longest_steps_ms"]} == {19, 39, 59, 79, 99}
    assert shown["longest_steps_ms"][0][1] == pytest.approx(50.0)
    # ... and how much of each was the dispatch of the next step (the last
    # step has none after it).
    by_step = dict(zip((i for i, _ in shown["longest_steps_ms"]),
                       shown["dispatch_ms_in_longest_steps"]))
    assert by_step.pop(99) is None
    assert list(by_step.values()) == [pytest.approx(2.0)] * 4
    assert shown["compiles_in_window"] == {
        "compilations": 0, "cache_reads": 0, "cache_writes": 0,
        "seconds": 0.0, "events": {}}


def test_gc_watch_times_the_collections_it_is_told_of():
    watch = run.GcWatch()
    watch("stop", {"generation": 0})           # a stop with no start: ignored
    for _ in range(3):
        watch("start", {"generation": 2})
        watch("stop", {"generation": 2})
    got = watch.summary()
    assert got["collections"] == 3
    assert 0.0 <= got["longest_s"] <= got["seconds"] < 1.0


def test_compile_watch_counts_what_jax_compiles_while_it_is_entered():
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    before = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_duration_listeners()))
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    with run.CompileWatch() as cold:
        fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
        jax.block_until_ready(fn(x))
    with run.CompileWatch() as warm:     # the same shapes: nothing compiles
        jax.block_until_ready(fn(x))
    assert cold.summary()["compilations"] >= 1
    assert cold.summary()["seconds"] > 0.0
    assert "backend_compile_duration" in cold.summary()["events"]
    assert warm.summary() == {"compilations": 0, "cache_reads": 0,
                              "cache_writes": 0, "seconds": 0.0, "events": {}}
    assert before == (len(monitoring.get_event_listeners()),
                      len(monitoring.get_event_duration_listeners()))


def test_the_loop_takes_the_batches_in_turn():
    import jax.numpy as jnp

    seen = []

    def step(total, x):
        seen.append(int(x))
        return total + x, total + x

    got = run.run_steps(step, (jnp.float32(0.0),),
                        [(jnp.float32(1.0),), (jnp.float32(10.0),)],
                        seconds=60.0, max_steps=5)
    assert seen == [1, 10, 1, 10, 1]
    assert got["losses"] == [1.0, 11.0, 12.0, 22.0, 23.0]
    assert len(got["stamps"]) == 6 and len(got["dispatch_s"]) == 5
    assert got["error"] is None


@pytest.mark.parametrize("name", sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in DATA_FILES if p.startswith("traffic/")))
def test_traffic_generator_reads_the_file_and_the_seed(name):
    """One generator, any traffic file: shapes from the file and the
    family's inputs, values from the seed alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = {**traffic_gen.resolve(_load(f"traffic/{name}.json"), True),
             "distinct_batches": 2}
    inputs = [traffic_gen.Input((sizes.get("seq_len", 4),), jnp.int32,
                                "randint", 50),
              traffic_gen.Input((3, 3), jnp.float32, "normal")]
    mesh = common.hvd_mesh(jax.devices()[:1])
    a = traffic_gen.make_batches(sizes, inputs, mesh, 3000000019)
    b = traffic_gen.make_batches(sizes, inputs, mesh, 3000000019)
    c = traffic_gen.make_batches(sizes, inputs, mesh, 5)
    assert len(a) == 2 and len(a[0]) == 2
    n = sizes["batch_per_chip"]
    assert a[0][0].shape == (n, sizes.get("seq_len", 4))
    assert a[1][1].shape == (n, 3, 3)
    assert int(a[0][0].min()) >= 0 and int(a[0][0].max()) < 50
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][0], a[1][0])     # distinct batches
    assert not np.array_equal(a[0][0], c[0][0])     # another seed


@pytest.mark.parametrize("count", [1, 3])
def test_a_batchs_argument_is_the_draw_of_its_own_fold_of_the_seed(count):
    """The generator draws an argument over all the batches at once; what
    batch b gets as argument i is still what ``fold_in(fold_in(fold_in(
    key(seed), 1), b), i)`` draws by itself, bit for bit, so a mix's first
    batch does not change with the number of its batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = {"batch_per_chip": 2, "distinct_batches": count}
    inputs = [traffic_gen.Input((8,), jnp.int32, "randint", 18991),
              traffic_gen.Input((3, 5), jnp.bfloat16, "normal"),
              traffic_gen.Input((2,), jnp.int32, "randint", 1 << 20)]
    mesh = common.hvd_mesh(jax.devices()[:1])
    seed = 2 ** 31 + 5
    got = traffic_gen.make_batches(sizes, inputs, mesh, seed)
    assert len(got) == count
    key = jax.random.fold_in(jax.random.key(seed), 1)
    for b, batch in enumerate(got):
        for i, (spec, x) in enumerate(zip(inputs, batch)):
            k = jax.random.fold_in(jax.random.fold_in(key, b), i)
            want = (jax.random.normal(k, (2, *spec.shape), spec.dtype)
                    if spec.draw == "normal" else jax.random.randint(
                        k, (2, *spec.shape), 0, spec.high, spec.dtype))
            assert x.dtype == want.dtype and x.shape == want.shape
            np.testing.assert_array_equal(np.asarray(x), np.asarray(want))
    with pytest.raises(ValueError, match="unknown draw"):
        traffic_gen.make_batches(sizes, [traffic_gen.Input(
            (2,), jnp.int32, "poisson")], mesh, seed)


# Recorded on a TPU v5 lite by run.py's traced window (two steps each of a
# tiny GPT): the rehearsal sizes with dense attention (PR 23), and 256 wide
# with the flash kernels on (PR 24).
DENSE_TRACE = os.path.join(BENCH, "testdata", "gpt_tiny_2steps_v5e.xplane.pb.gz")
FLASH_TRACE = os.path.join(BENCH, "testdata",
                           "gpt_tiny_flash_2steps_v5e.xplane.pb.gz")


def _busy_ms_per_step(trace):
    return trace_reduce.per_step(trace_reduce.mean_over_devices(
        trace, lambda ev: trace_reduce.busy_ns(trace_reduce.sync_ops(ev),
                                               trace.window)), trace.steps)


@pytest.mark.parametrize("path", [DENSE_TRACE, FLASH_TRACE])
def test_recorded_chip_trace_has_a_device_plane_and_busy_time(path):
    trace = trace_reduce.read_xplane(path, steps=2)
    assert trace.devices, "no /device:TPU:<n> plane with an XLA Ops line"
    assert trace.window[1] > trace.window[0]
    busy = trace_reduce.mean_over_devices(
        trace, lambda ev: trace_reduce.busy_ns(ev, trace.window))
    assert 0 < busy <= trace.window[1] - trace.window[0]
    assert any(h.name == "bench_window" for h in trace.host)
    assert trace_reduce.top_ops(trace.devices[min(trace.devices)],
                                trace.window)


def test_recorded_trace_reads_as_it_did_before_events_had_a_scope():
    """The values PR 23's reader gave for this file (read with the parent
    commit's trace_reduce.py): the scope, the ``hvd_`` prefix and the
    innermost-span rule change none of them."""
    trace = trace_reduce.read_xplane(DENSE_TRACE, steps=2)
    assert trace.window == (42861587.0, 46098696.0)
    assert len(trace.devices[0]) == 1288 and len(trace.host) == 5
    ctx = {"run": {"dispatch_s": [0.001, 0.003]}}
    got = run.per_layer(_spec(), "resnet50-1chip", trace, ctx)
    assert got["host_dispatch_ms"]["value"] == 2.0
    assert got["device_idle_pct"]["value"] == 96.26388854993762
    assert _busy_ms_per_step(trace) * 2 / 1e3 == 0.000120942   # busy_s
    first = trace.devices[0]
    assert trace_reduce.top_ops(first, trace.window)[:4] == [
        ["fusion", 8.8045e-05], ["multiply_reduce_fusion", 1.1386e-05],
        ["convolution_add_fusion", 7.062e-06], ["copy-done", 3.774e-06]]
    assert trace_reduce.idle_gaps(first, trace.host, trace.window)[:4] == [
        ["bench_dispatch", 0.00190976], ["bench_dispatch", 0.000609184],
        ["bench_dispatch", 0.000594514], ["bench_dispatch", 4.58e-07]]


@pytest.mark.parametrize("path", [DENSE_TRACE, FLASH_TRACE])
def test_every_device_op_gets_the_scope_the_file_gives_it(path):
    """``ProfileData`` names an event by its metadata's name, so the raw
    file's ``{name: tf_op}`` is the scope of every event; not every op has
    one (copies, layout changes), and a host plane has none at all."""
    import gzip

    with gzip.open(path, "rb") as f:
        raw = f.read()
    scopes = xplane_raw.op_scopes(raw, r"^/device:TPU:\d+$")
    stats = xplane_raw.event_stats(raw)
    trace = trace_reduce.read_xplane(path, steps=2)
    assert set(scopes) == {f"/device:TPU:{d}" for d in trace.devices}
    for d, events in trace.devices.items():
        names = stats[f"/device:TPU:{d}"]
        assert all(e.name in names for e in events)
        assert all(e.scope == scopes[f"/device:TPU:{d}"].get(e.name, "")
                   for e in events)
        with_scope = [e for e in events if e.scope]
        assert with_scope and all(e.scope.endswith(":") for e in with_scope)
        assert any(not e.scope for e in events)
    assert all("tf_op" not in s for s in stats["/host:CPU"].values())
    assert not any(h.scope for h in trace.host)
    op = next(e for e in trace.devices[0] if "dot_general" in e.scope)
    assert names[op.name]["flops"] > 0 and names[op.name]["bytes_accessed"] > 0


def test_recorded_dense_trace_splits_into_forward_backward_and_the_rest():
    """JAX writes ``jvp(`` and ``transpose(`` into every op's scope with no
    change to the program.  Of this trace's 938 sync-line ops 708 carry no
    scope; by time the classes are 38.4 / 54.4 / 7.3 % (ISSUE 24 read 38.2 /
    54.2 / 7.6 from the file's picoseconds; ``ProfileData`` gives whole
    nanoseconds, which costs ops of a few ns up to one each)."""
    trace = trace_reduce.read_xplane(DENSE_TRACE, steps=2)
    sync = trace_reduce.sync_ops(trace.devices[0])
    assert (len(sync), sum(not e.scope for e in sync)) == (938, 708)
    busy = _busy_ms_per_step(trace)
    parts = [trace_reduce.op_time_ms(
        trace, {}, **_load(f"layer_metrics/{name}.json")["params"])
        for name in ("forward_ms", "backward_ms", "outside_model_ms")]
    assert sum(parts) == pytest.approx(busy, rel=1e-9)
    assert [100 * p / busy for p in parts] == pytest.approx(
        [38.36, 54.37, 7.27], abs=0.01)
    assert [100 * p / busy for p in parts] == pytest.approx(
        [38.2, 54.2, 7.6], abs=0.4)


def test_recorded_flash_trace_parts_the_kernel_calls_by_pass():
    """The flash kernels carry no name of their own; the scope tells the
    forward call from the two backward calls (a ``custom_vjp``'s backward
    is traced under ``transpose(jvp(...))``).  Two layers, two steps: four
    forward and eight backward calls."""
    trace = trace_reduce.read_xplane(FLASH_TRACE, steps=2)
    params = {n: _load(f"layer_metrics/{n}.json")["params"]
              for n in ("flash_kernel_ms", "flash_fwd_ms", "flash_bwd_ms",
                        "forward_ms", "backward_ms", "outside_model_ms")}
    got = {n: trace_reduce.op_time_ms(trace, {}, **p)
           for n, p in params.items()}
    assert got["flash_kernel_ms"] == pytest.approx(0.0348855, rel=1e-9)
    assert got["flash_fwd_ms"] == pytest.approx(0.0143085, rel=1e-9)
    assert got["flash_bwd_ms"] == pytest.approx(0.020577, rel=1e-9)
    assert got["flash_fwd_ms"] + got["flash_bwd_ms"] == pytest.approx(
        got["flash_kernel_ms"], rel=1e-9)
    assert (got["forward_ms"] + got["backward_ms"] + got["outside_model_ms"]
            == pytest.approx(_busy_ms_per_step(trace), rel=1e-9))
    calls = trace_reduce.classify(trace.devices[0],
                                  params["flash_kernel_ms"]["pattern"])[0]
    backward = [e for e in calls if "transpose(" in e.scope]
    assert (len(calls), len(backward)) == (12, 8)
    assert all(e.scope.endswith("/attn/pallas_call:") for e in calls)


def test_the_raw_decoder_needs_the_standard_library_only():
    code = ("import sys; before = set(sys.modules); "
            "import benchmark.xplane_raw; "
            "print(sorted({m.split('.')[0] for m in set(sys.modules) - before}"
            " - set(sys.stdlib_module_names) - {'benchmark'}))")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert done.stdout.strip() == "[]", done.stdout + done.stderr


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: a varint for an int, length-delimited bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_raw_decoder_on_a_hand_made_xspace():
    """Every kind of XStat value, a stat that refers to a name, two metadata
    entries of one name, a line that is skipped, a plane that is not asked
    for and a plane without stats."""
    import struct

    def entry(key, message):
        return _field(1, key) + _field(2, message)

    stat_names = b"".join(_field(5, entry(i, _field(1, i) + _field(2, n)))
                          for i, n in enumerate(
                              ["tf_op", "flops", "delta", "share", "blob",
                               "jit(f)/jvp(M)/dot_general:"], start=1))
    fixed64 = _varint(2 << 3 | 1) + struct.pack("<d", 0.25)
    op = (_field(1, 7) + _field(2, "%fusion.1 = f32[8] fusion()")
          + _field(5, _field(1, 1) + _field(7, 6))            # ref_value
          + _field(5, _field(1, 2) + _field(3, 1 << 40))      # uint64
          + _field(5, _field(1, 3) + _field(4, (1 << 64) - 5))  # int64 -5
          + _field(5, _field(1, 4) + fixed64)                 # double
          + _field(5, _field(1, 5) + _field(6, b"\x00\x01")))  # bytes
    twin = (_field(1, 8) + _field(2, "%fusion.1 = f32[8] fusion()")
            + _field(5, _field(1, 9) + _field(5, "by-value")))
    copy = _field(1, 9) + _field(2, "%copy.1 = f32[8] copy()")
    line = _field(2, "XLA Ops") + _field(4, _field(1, 7) + _field(3, 1000))
    device = (_field(2, "/device:TPU:0") + _field(3, line) + stat_names
              + b"".join(_field(4, entry(k, m))
                         for k, m in ((7, op), (8, twin), (9, copy))))
    host = _field(2, "/host:CPU") + _field(4, entry(1, _field(2, "bench_wait")))
    space = _field(1, device) + _field(1, host) + _field(4, "a hostname")
    stats = xplane_raw.event_stats(space)
    assert stats["/device:TPU:0"]["%fusion.1 = f32[8] fusion()"] == {
        "tf_op": "jit(f)/jvp(M)/dot_general:", "flops": 1 << 40, "delta": -5,
        "share": 0.25, "blob": b"\x00\x01", "9": "by-value"}
    assert stats["/host:CPU"] == {"bench_wait": {}}
    assert xplane_raw.op_scopes(space, "^/device:") == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(f)/jvp(M)/dot_general:"}}
    assert xplane_raw.op_scopes(b"") == {}
    with pytest.raises(ValueError, match="wire type"):
        list(xplane_raw.fields(_varint(1 << 3 | 3)))


# ---------------------------------------------------------------------------
# run.py end to end
# ---------------------------------------------------------------------------

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, tmp_path, devices=1):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               # one thread for the arithmetic: the suite's other workers
               # run multi-process tests that time out on a starved machine
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices} "
                         "--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("cell,trace,devices", [
    ("resnet50-1chip", 0, 1), ("gpt2m-1chip", 1, 1), ("gpt2m-dp4", 0, 4)])
def test_rehearsal_prints_the_contract_keys_and_no_metric(cell, trace,
                                                          devices, tmp_path):
    done = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "1",
                 "--trace", str(trace), "--rehearse"], tmp_path, devices)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    # The keys the driver reads, then every compared number and its limit.
    assert set(result) == RESULT_KEYS | {"checks"}
    assert list(result)[-1] == "checks" and all(
        {"value", "limit"} <= set(c) or {"value", "least"} <= set(c)
        for c in result["checks"].values())
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] >= 2 and result["failed"] == 0
    # A CPU number is never written under a device metric's name.
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices


@pytest.mark.parametrize("args,message", [
    (["--workload", "resnet50-1chip"], "no TPU"),
    (["--workload", "no-such-cell"], "not found"),
])
def test_run_refuses_loudly_and_prints_no_result(args, message, tmp_path):
    done = _run([*args, "--seed", "1", "--seconds", "1", "--trace", "0"],
                tmp_path)
    assert done.returncode != 0
    assert message in done.stderr
    assert '"correct"' not in done.stdout
