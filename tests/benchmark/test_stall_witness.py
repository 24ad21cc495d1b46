"""The two witnesses of a stalled step (``benchmark/stall_witness.py``) on
hand-built traces, their metric files through the harness's own lookup, and
``tools/stall_hunt.py``'s loop at tiny sizes.  Names and arithmetic only:
nothing here is a measurement."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import run, stall_witness, trace_reduce  # noqa: E402
from benchmark.trace_reduce import Event, Trace  # noqa: E402

MS = 1e6
FUSION = "%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop"
WINDOW = (0.0, 5000 * MS)


def _ops(*spans):
    return [Event(FUSION, start * MS, dur * MS, "XLA Ops")
            for start, dur in spans]


def _marks(*at_ms):
    return [Event("hvd_alive", at * MS, 900.0, "hvd-executor")
            for at in at_ms]


@pytest.mark.parametrize("case,trace,want", [
    ("a hole of 2 s in marks every 50 ms",
     Trace({}, _marks(*range(50, 1001, 50), *range(3000, 5000, 50)),
           WINDOW, 10), 2000.0),
    ("marks every 50 ms to the window's edges",
     Trace({}, _marks(*range(50, 5000, 50)), WINDOW, 10), 50.0),
    ("one mark: the window's edges count as marks",
     Trace({}, _marks(1200), WINDOW, 10), 3800.0),
    ("a mark outside the window is none",
     Trace({}, _marks(-20, 5100), WINDOW, 10), None),
    ("other spans of the program are no marks",
     Trace({}, [Event("hvd_wait", 100.0, 50.0, "python")], WINDOW, 10), None),
])
def test_host_alive_gap_max_ms(case, trace, want):
    got = stall_witness.host_alive_gap_max_ms(trace, {})
    assert got == (want if want is None else pytest.approx(want)), case


@pytest.mark.parametrize("case,trace,want", [
    ("1.3 s before the first op",
     Trace({0: _ops((1300, 3700))}, [], WINDOW, 10), 1300.0),
    ("the longest of three gaps",
     Trace({0: _ops((0, 1000), (1007, 993), (2002.5, 2997.5))}, [], WINDOW,
           10), 7.0),
    ("the async line fills no gap",
     Trace({0: _ops((0, 1000), (3000, 2000)) + [Event(
         "%copy-start.1 = (f32[8]) copy-start(f32[8] %p)", 1000 * MS,
         2000 * MS, "Async XLA Ops")]}, [], WINDOW, 10), 2000.0),
    ("the first chip's, as breakdown.idle_gaps ranks them",
     Trace({1: _ops((0, 5000)), 0: _ops((0, 4000))}, [], WINDOW, 10), 1000.0),
    ("busy from edge to edge", Trace({0: _ops((-5, 5010))}, [], WINDOW, 10),
     0.0),
    ("no device plane", Trace({}, _marks(100), WINDOW, 10), None),
])
def test_device_gap_max_ms(case, trace, want):
    got = stall_witness.device_gap_max_ms(trace, {})
    assert got == (want if want is None else pytest.approx(want)), case
    if want:  # the same gap as the one the breakdown names first
        first = trace.devices[min(trace.devices)]
        assert trace_reduce.idle_gaps(first, [], trace.window)[0][1] == \
            pytest.approx(want / 1e3)


def test_the_two_read_beside_each_other_part_the_host_from_what_is_beneath():
    """A device gap of 2.6 s with the marks going on is not the host's; with
    a hole as long in the marks it is."""
    ops = _ops((0, 1000), (3636, 1364))
    alive = Trace({0: ops}, _marks(*range(40, 5000, 50)), WINDOW, 10)
    still = Trace({0: ops}, _marks(*range(40, 1000, 50),
                                   *range(3630, 5000, 50)), WINDOW, 10)
    for trace, host_gap in ((alive, 50.0), (still, 2640.0)):
        assert stall_witness.device_gap_max_ms(trace, {}) == \
            pytest.approx(2636.0)
        assert stall_witness.host_alive_gap_max_ms(trace, {}) == \
            pytest.approx(host_gap)
    # A mark covers half of no gap: the breakdown names gaps as it did.
    wait = Event("bench_wait", 990 * MS, 2700 * MS, "python")
    assert trace_reduce.idle_gaps(ops, alive.host + [wait],
                                  WINDOW)[0][0] == "bench_wait"


@pytest.mark.parametrize("name", ["device_gap_max_ms",
                                  "host_alive_gap_max_ms"])
def test_every_cell_reports_both_in_their_order_and_neither_lists_cells(name):
    """The pair's order, not its place: the families' metrics are appended
    after it (the driver reads an entry put before the two as a change to
    ``device_gap_max_ms``; PERF.md section 6, PR 47)."""
    spec = run.load_spec()
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    assert (entry["layer"], entry["moves"]) == ("device", "step_ms")
    meta = run.load_json("layer_metrics", name + ".json")
    assert meta["reducer"] == f"stall_witness.{name}"
    for cell in spec["workloads"]:
        assert name in {m["name"] for m in run.metrics_of(
            spec, "per_layer", cell["name"])}
    assert [m["name"] for m in spec["per_layer"]
            if m["name"].endswith("_gap_max_ms")] == [
                "device_gap_max_ms", "host_alive_gap_max_ms"]


def test_stall_hunt_rehearses_its_traced_windows(tmp_path):
    """The tool's loop at the cell's tiny sizes: windows under the profiler
    with the watch on, the injected sleep caught as a stall of that lap."""
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "stall_hunt.py"),
         "--workload", "resnet50-1chip", "--rehearse", "--traced-windows",
         "4", "--inject-ms", "400", "--out", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-3000:]
    summary = json.loads(done.stdout.strip().splitlines()[-1])
    assert summary["mode"] == "traced_windows" and summary["windows"] == 4
    assert summary["device"]["platform"] == "cpu"
    injected, = summary["injected"]
    assert injected["window"] == 1
    assert injected["longest_steps_ms"][0][1] > 390
    with open(tmp_path / "windows.jsonl") as f:
        assert len(f.readlines()) == 4
