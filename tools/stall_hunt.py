"""Catch a stalled step of a benchmark cell on the chip, with ``hvd.StepWatch``
running (docs/observability.md, "Stalls"; PERF.md sections 6 and 7).

    python tools/stall_hunt.py --workload <cell> --seed <n> --minutes 25 --stalls 5
    python tools/stall_hunt.py --workload <cell> --seed <n> --traced-windows 60
    python tools/stall_hunt.py --workload <cell> --seed <n> --compare-watch 3

One process: ``benchmark.run.set_up`` builds the cell as a run of the
benchmark does (the benchmark's files are read, none is edited), then a loop
of this tool's own in ``run_steps``' shape (one step in flight, the stamp
after ``block_until_ready``) with ``watch.lap()`` in it.

* The hunt (default): timed-shape windows of ``--window-seconds`` one after
  the other until ``--stalls`` records or ``--minutes``.  The watch's
  ``on_stall`` starts a profiler session (host and device, no Python
  tracer) from a thread of the tool's, and the loop stops it two laps after
  the stalled lap ended, so a stall that outlives the profiler's start
  leaves a trace of how it ended: ``how_it_ended`` lists the host events
  that close as the device's first op starts.
* ``--traced-windows K``: K windows of the traffic's ``trace_steps`` steps
  under ``start_trace`` / ``stop_trace`` as ``run.traced_window`` makes
  them, with the watch on: how many of them stall, and of each that does
  the two witnesses of ``benchmark/stall_witness.py``.  ``--inject-ms``
  makes the loop of the second window sleep once, so that a stall's record
  and the ``hvd_step`` span of its lap can be laid on one clock.
* ``--compare-watch N``: N pairs of windows, without a watch and with one,
  alternated: what the watch costs while it is on.

Everything long goes to ``--out`` (``chiprun_out/stall_hunt/<cell>``):
``stalls.jsonl`` (the records), ``windows.jsonl``, ``ended_<n>.json``.  The
last line of stdout is the summary.  ``--rehearse`` runs the control flow at
the cell's tiny sizes on any backend; no number of such a run is a
measurement.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402

NEAR_BEFORE_NS, NEAR_AFTER_NS = 5e6, 1e6   # "as the device's first op starts"


def note(kind: str, **kv) -> None:
    print(json.dumps({"note": kind, **kv}), flush=True)


# ---------------------------------------------------------------------------
# The loop
# ---------------------------------------------------------------------------


def stepping(step, state, batches, seconds: float, max_steps=None, watch=None,
             after_lap=None, sleep_before=None) -> dict:
    """``benchmark.run.run_steps``' loop with ``watch.lap()`` after each
    stamp.  ``after_lap(lap)`` runs after each; ``sleep_before`` is ``(step
    index, seconds)``: the loop sleeps once before that dispatch."""
    import jax
    from jax.profiler import TraceAnnotation

    def dispatch(state):
        if sleep_before and len(dispatch_s) == sleep_before[0]:
            time.sleep(sleep_before[1])
        batch = batches[len(dispatch_s) % len(batches)]
        t = time.perf_counter()
        with TraceAnnotation("bench_dispatch"):
            *state, loss = step(*state, *batch)
        dispatch_s.append(time.perf_counter() - t)
        return state, loss

    stamps, dispatch_s, loss = [time.perf_counter()], [], None
    if watch is not None:
        watch.lap()
    state, pending = dispatch(state)
    while pending is not None:
        more = (time.perf_counter() - stamps[0] < seconds
                and (max_steps is None or len(dispatch_s) < max_steps))
        state, coming = dispatch(state) if more else (state, None)
        with TraceAnnotation("bench_wait"):
            jax.block_until_ready(pending)
        stamps.append(time.perf_counter())
        if watch is not None:
            watch.lap()
        if after_lap is not None:
            after_lap(len(stamps) - 2)
        loss, pending = pending, coming
    if watch is not None:
        watch.pause()
    return {"stamps": stamps, "dispatch_s": dispatch_s, "state": state,
            "last_loss": float(loss)}


def keep_window(out: str, stats: dict) -> None:
    with open(os.path.join(out, "windows.jsonl"), "a") as f:
        f.write(json.dumps(stats) + "\n")


def window_stats(run: dict) -> dict:
    stamps = run["stamps"]
    samples = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    longest = sorted(enumerate(samples), key=lambda kv: -kv[1])[:3]
    return {"steps": len(samples), "window_s": stamps[-1] - stamps[0],
            "step_ms": 1e3 * (stamps[-1] - stamps[0]) / len(samples),
            "step_ms_median": statistics.median(samples),
            "longest_steps_ms": longest,
            "host_dispatch_ms_mean": 1e3 * statistics.mean(run["dispatch_s"]),
            "last_loss": run["last_loss"]}


# ---------------------------------------------------------------------------
# A trace of how a stall ended
# ---------------------------------------------------------------------------


def start_trace(where: str) -> None:
    """A profiler session as ``benchmark.run.traced_window`` starts it: host
    and device, no Python tracer."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)


class StallTracer:
    """Starts a profiler session when the watch notices a stall (from a
    thread of its own: the watch's goes on sampling) and stops it from the
    loop two laps after the stalled lap ended."""

    def __init__(self, out_dir: str, most: int):
        shutil.rmtree(out_dir, ignore_errors=True)
        self.out_dir, self.most = out_dir, most
        self.taken = 0
        self.lap = None          # the stalled lap being traced
        self.started = None      # (perf_counter_ns before, after) start_trace
        self._go = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stall-hunt-tracer")
        self._thread.start()

    def on_stall(self, so_far: dict) -> None:
        if self.lap is None and self.taken < self.most:
            self.lap, self.started = so_far["lap"], None
            self._go.set()

    def _run(self) -> None:
        while True:
            self._go.wait()
            self._go.clear()
            before = time.perf_counter_ns()
            start_trace(os.path.join(self.out_dir, str(self.taken)))
            self.started = (before, time.perf_counter_ns())

    def stop_if_due(self, lap: int, offset: int, watch) -> dict | None:
        """Called by the loop after its ``lap``-th stamp of a window whose
        first lap is the watch's ``offset``-th."""
        if (self.lap is None or self.started is None
                or offset + lap < self.lap + 2):
            return None
        import jax

        watch.pause()   # writing the trace is no lap; the next stamp opens one
        jax.profiler.stop_trace()
        traced_lap, started = self.lap, self.started
        found = glob.glob(os.path.join(self.out_dir, str(self.taken),
                                       "plugins", "profile", "*",
                                       "*.xplane.pb"))
        self.taken += 1
        self.lap = self.started = None
        return {"lap": traced_lap, "start_trace_began_ns": started[0],
                "start_trace_took_ms": (started[1] - started[0]) / 1e6,
                "xplane": found[0] if len(found) == 1 else None}


class SpinWitness:
    """A process of its own that does nothing but read the clock, and keeps
    the gaps of 5 ms or more between two readings.  The watch's thread and
    the native loop sleep, so a kernel that wakes sleepers late and a
    machine that stopped look alike to them; this one never sleeps, holds
    no lock of the loop's process (not its interpreter either) and reads the
    same ``CLOCK_MONOTONIC``: a gap of its own inside a stalled lap says the
    whole machine (or its sandbox) stood still, none says this process
    alone did.  It burns a core: an experiment's witness, not the
    library's."""

    GAP_NS = 5_000_000
    CHILD = (
        "import sys, time\n"
        "gap, out = int(sys.argv[1]), open(sys.argv[2], 'w', buffering=1)\n"
        "last = time.perf_counter_ns()\n"
        "while True:\n"
        "    now = time.perf_counter_ns()\n"
        "    if now - last >= gap:\n"
        "        out.write(f'{last} {now - last}\\n')\n"
        "    last = now\n")

    def __init__(self, path: str):
        import subprocess

        self.path, self.gaps = path, []   # (perf_counter_ns before, ns)
        self._child = subprocess.Popen(
            [sys.executable, "-c", self.CHILD, str(self.GAP_NS), path])

    def close(self) -> None:
        self._child.terminate()
        self._child.wait(timeout=5.0)
        with open(self.path) as f:
            self.gaps = [tuple(map(int, row.split())) for row in f
                         if len(row.split()) == 2]

    def longest_inside_ms(self, start_ns: int, end_ns: int) -> float:
        return max((lasted for at, lasted in self.gaps
                    if at + lasted > start_ns and at < end_ns),
                   default=0) / 1e6


def how_it_ended(xplane: str, record: dict | None) -> dict:
    """Of a trace that begins inside a stall: how long the first chip went
    on doing nothing, and the host events that close (or are open) as its
    first op starts, the longest first."""
    from jax.profiler import ProfileData

    from benchmark import stall_witness

    data = ProfileData.from_file(xplane)
    ops, host, first_event = [], [], math.inf
    for plane in data.planes:
        device = plane.name == "/device:TPU:0"
        for line in plane.lines:
            for e in line.events:
                first_event = min(first_event, e.start_ns)
                if device and line.name == "XLA Ops":
                    ops.append(e.start_ns)
                elif plane.name.startswith("/host:"):
                    host.append((line.name, e.name, e.start_ns,
                                 e.duration_ns))
    out = {"device_ops": len(ops), "host_events": len(host)}
    if not ops:
        return out
    resumed = min(ops)
    out["device_idle_from_trace_start_ms"] = (resumed - first_event) / 1e6
    near = [(thread, name, start, dur) for thread, name, start, dur in host
            if resumed - NEAR_BEFORE_NS <= start + dur <= resumed + NEAR_AFTER_NS
            or (start < resumed < start + dur)]
    near.sort(key=lambda h: -h[3])
    out["host_events_as_the_device_resumes"] = [
        {"thread": thread, "name": name, "ms": dur / 1e6,
         "ends_ms_after_first_op": (start + dur - resumed) / 1e6}
        for thread, name, start, dur in near[:25]]
    steps = [(start, dur) for _, name, start, dur in host
             if name == "hvd_step"]
    out["hvd_step_spans"] = len(steps)
    out["hvd_stall_samples"] = sum(name == "hvd_stall_sample"
                                   for _, name, _, _ in host)
    began = stall_witness.profile_start_unix_ns(xplane)
    if record is not None and began is not None:
        out["record_end_minus_first_op_ms"] = (
            record["end_unix_ns"] - began - resumed) / 1e6
    return out


def clock_check(trace, xplane: str, record: dict) -> dict:
    """Where a record's two stamps on ``time.time_ns()``, less the session's
    beginning as the file states it, lie against the ``hvd_step`` span of
    the traced window that starts nearest the record's start."""
    from benchmark import stall_witness

    spans = [h for h in trace.host if h.name == "hvd_step"]
    began = stall_witness.profile_start_unix_ns(xplane)
    if not spans or began is None:
        return {"hvd_step_spans": len(spans), "profile_start_unix_ns": began}
    start, end = (record["start_unix_ns"] - began,
                  record["end_unix_ns"] - began)
    span = min(spans, key=lambda h: abs(h.start_ns - start))
    return {"hvd_step_spans": len(spans), "profile_start_unix_ns": began,
            "span_ms": span.dur_ns / 1e6, "record_ms": record["ms"],
            "record_start_minus_span_start_us": (start - span.start_ns) / 1e3,
            "record_end_minus_span_end_us": (end - span.end_ns) / 1e3}


# ---------------------------------------------------------------------------
# The three modes
# ---------------------------------------------------------------------------


def hunt(args, up: dict, out: str) -> dict:
    import gc

    import horovod_tpu as hvd

    tracer = (StallTracer(os.path.join(out, "_stall_trace"), args.traces)
              if args.traces else None)
    watch = hvd.StepWatch(on_stall=tracer.on_stall if tracer else None,
                          file=os.path.join(out, "stalls.jsonl"))
    spin = (SpinWitness(os.path.join(out, "spin_gaps.txt"))
            if args.spin else None)
    state, batches = up["state"], up["cell"]["batches"]
    began, windows, stepped_s, ended = time.perf_counter(), [], 0.0, []
    while (len(watch.stalls) < args.stalls
           and time.perf_counter() - began < 60 * args.minutes):
        offset, had = watch.laps, len(watch.stalls)

        def after_lap(lap):
            done = tracer.stop_if_due(lap, offset, watch) if tracer else None
            if done is not None:
                ended.append(done)

        collections = bench.GcWatch()
        gc.callbacks.append(collections)
        with bench.CompileWatch() as compiles:
            run = stepping(up["step"], state, batches, args.window_seconds,
                           watch=watch, after_lap=after_lap)
        gc.callbacks.remove(collections)
        state = run["state"]
        stats = {**window_stats(run), "window": len(windows),
                 "first_lap": offset, "gc": collections.summary(),
                 "compilations": compiles.summary()["compilations"],
                 "stalls": [r["lap"] - offset for r in watch.stalls[had:]]}
        stepped_s += stats["window_s"]
        windows.append(stats)
        keep_window(out, stats)
        note("window", **stats)
    watch.close()
    stalls = [brief(r) for r in watch.stalls]
    if spin is not None:
        spin.close()
        for r, b in zip(watch.stalls, stalls):
            b["spin_gap_max_ms"] = spin.longest_inside_ms(r["start_ns"],
                                                          r["end_ns"])
    for n, done in enumerate(ended):
        record = next((r for r in watch.stalls if r["lap"] == done["lap"]),
                      None)
        if done["xplane"]:
            done["ended"] = how_it_ended(done.pop("xplane"), record)
        if record is not None:
            done["start_trace_began_ms_into_stall"] = (
                done["start_trace_began_ns"] - record["start_ns"]) / 1e6
            done["stall_ms"] = record["ms"]
        with open(os.path.join(out, f"ended_{n}.json"), "w") as f:
            json.dump(done, f, indent=1)
    return {"mode": "hunt", "minutes_stepping": stepped_s / 60,
            "windows": len(windows),
            "steps": sum(w["steps"] for w in windows),
            "step_ms_by_window": [w["step_ms"] for w in windows],
            "cycle_time_ms": os.environ.get("HOROVOD_CYCLE_TIME", "default"),
            "spin_gaps_of_5ms_or_more": len(spin.gaps) if spin else None,
            "spin_gaps_longest_ms": sorted(
                (lasted / 1e6 for _, lasted in spin.gaps),
                reverse=True)[:40] if spin else None,
            "stalls": stalls, "traces_of_an_end": ended}


def traced_windows(args, up: dict, out: str) -> dict:
    import jax

    import horovod_tpu as hvd
    from benchmark import stall_witness, trace_reduce

    watch = hvd.StepWatch(file=os.path.join(out, "stalls.jsonl"))
    state, batches = up["state"], up["cell"]["batches"]
    steps = up["traffic"]["trace_steps"]
    trace_dir = os.path.join(out, "_window_trace")
    windows, stalled = [], []
    # One window outside the profiler first: the watch's median.
    state = stepping(up["step"], state, batches, math.inf, max_steps=steps,
                     watch=watch)["state"]
    for k in range(args.traced_windows):
        offset, had = watch.laps, len(watch.stalls)
        inject = ((steps // 2, args.inject_ms / 1e3)
                  if args.inject_ms and k == 1 else None)
        shutil.rmtree(trace_dir, ignore_errors=True)
        began = time.perf_counter()
        start_trace(trace_dir)
        started = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench_window"):
                run = stepping(up["step"], state, batches, math.inf,
                               max_steps=steps, watch=watch,
                               sleep_before=inject)
        finally:
            stepped = time.perf_counter()
            jax.profiler.stop_trace()
        state = run["state"]
        records = watch.stalls[had:]
        stats = {**window_stats(run), "window": k, "injected": bool(inject),
                 "start_trace_ms": 1e3 * (started - began),
                 "stop_trace_ms": 1e3 * (time.perf_counter() - stepped),
                 "stalls": [r["lap"] - offset for r in records]}
        if records or k == 0:   # read the witnesses where there is a stall
            found = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            trace = trace_reduce.read_xplane(found[0], steps=steps)
            first = trace.devices.get(min(trace.devices, default=0), [])
            stats["witnesses"] = {
                "device_gap_max_ms": stall_witness.device_gap_max_ms(trace, {}),
                "host_alive_gap_max_ms":
                    stall_witness.host_alive_gap_max_ms(trace, {}),
                "hvd_alive_marks": sum(h.name == "hvd_alive"
                                       for h in trace.host),
                "hvd_alive_longest_us": max(
                    (h.dur_ns / 1e3 for h in trace.host
                     if h.name == "hvd_alive"), default=None),
                "idle_gaps": trace_reduce.idle_gaps(first, trace.host,
                                                    trace.window, n=4)}
            if records:
                stats["clock"] = clock_check(trace, found[0], records[0])
        windows.append(stats)
        keep_window(out, stats)
        if records or k < 2:
            note("traced_window", **stats)
        if records and not inject:
            stalled.append(stats)
    watch.close()
    return {"mode": "traced_windows", "windows": len(windows),
            "steps_a_window": steps,
            "stalled_windows": len(stalled), "stalled": stalled,
            "injected": [w for w in windows if w["injected"]],
            "first": windows[0] if windows else None,
            "window_step_ms_median": statistics.median(
                w["step_ms"] for w in windows) if windows else None,
            "start_trace_ms_median": statistics.median(
                w["start_trace_ms"] for w in windows) if windows else None,
            "stalls": [brief(r) for r in watch.stalls]}


def compare_watch(args, up: dict, out: str) -> dict:
    import horovod_tpu as hvd

    state, batches = up["state"], up["cell"]["batches"]
    rows, stalls = [], []
    for k in range(2 * args.compare_watch):
        # without, with, with, without, ...: neither side is always second
        on = k % 4 in (1, 2)
        watch = hvd.StepWatch(
            file=os.path.join(out, "stalls.jsonl")) if on else None
        run = stepping(up["step"], state, batches, args.window_seconds,
                       watch=watch)
        if watch is not None:
            watch.close()
            stalls += [brief(r) for r in watch.stalls]
        state = run["state"]
        rows.append({**window_stats(run), "watch": on})
        note("window", **rows[-1])
    with_, without = ([r["step_ms"] for r in rows if r["watch"] is on]
                      for on in (True, False))
    return {"mode": "compare_watch", "step_ms_with_watch": with_,
            "step_ms_without": without,
            "median_with_over_without": statistics.median(with_)
            / statistics.median(without),
            "stalls": stalls}


def brief(record: dict) -> dict:
    """A record without its threads and stacks: what fits a summary."""
    keep = ("lap", "live", "ms", "median_ms", "wakes", "wake_late_max_ms",
            "wake_work_max_ms", "noticed_after_ms", "reading", "evidence", "threads_seen",
            "asleep_by_wchan", "loop_top_frames", "start_unix_ns",
            "end_unix_ns", "open")
    out = {k: record[k] for k in keep if k in record}
    out["counters_moved"] = {
        k: v for k, v in record["counters"].items()
        if v.get("during") and not k.startswith("ru_")
        and k != "native_cycles"}
    out["native_cycles"] = record["counters"].get("native_cycles")
    out["ran"] = [{k: t[k] for k in ("comm", "name", "run_ms", "wait_ms",
                                     "states", "wchan") if k in t}
                  for t in record["threads"][:6]]
    return out


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--minutes", type=float, default=10.0)
    ap.add_argument("--stalls", type=int, default=5)
    ap.add_argument("--window-seconds", type=float, default=50.0)
    ap.add_argument("--traces", type=int, default=3,
                    help="at most this many traces of a stall's end (0: none)")
    ap.add_argument("--spin", type=int, choices=(0, 1), default=0,
                    help="the hunt beside a process that only reads the clock")
    ap.add_argument("--traced-windows", type=int, default=0)
    ap.add_argument("--inject-ms", type=float, default=0.0)
    ap.add_argument("--compare-watch", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    args.trace = 0
    out = args.out or os.path.join(ROOT, "chiprun_out", "stall_hunt",
                                   args.workload)
    os.makedirs(out, exist_ok=True)
    up = bench.set_up(args, bench.load_spec(), bench.Phases())
    if up is None:
        return 1
    import horovod_tpu as hvd

    mode = (traced_windows if args.traced_windows
            else compare_watch if args.compare_watch else hunt)
    summary = mode(args, up, out)
    hvd.shutdown()
    summary = {"workload": args.workload, "seed": args.seed,
               "device": up["device"], **summary}
    with open(os.path.join(out, f"summary_{summary['mode']}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
