"""``hvd.StepWatch`` (docs/observability.md, "Stalls"): what a stalled lap's
record holds, that nothing exists before one is constructed, and the names
it and the dispatcher write on the profiler's clock.  All on the CPU, no
process started: the laps are sleeps, never a device's."""

import glob
import json
import os
import threading
import time

import jax
import pytest

from benchmark import stall_witness, trace_reduce
from horovod_tpu.utils import step_watch

WATCH_THREAD = "hvd-step-watch"


def _lapped(watch, laps=40, stalled=25, stall_s=0.3, lap_s=0.01,
            during=None):
    """``laps`` laps of ``lap_s``, the ``stalled``-th of ``stall_s``."""
    watch.lap()
    for i in range(laps):
        if i == stalled and during is not None:
            during(stall_s)
        else:
            time.sleep(stall_s if i == stalled else lap_s)
        watch.lap()


def test_one_long_lap_is_one_record_noticed_while_it_lasts():
    calls = []

    def on_stall(so_far):
        calls.append((time.perf_counter_ns(),
                      threading.current_thread().name, so_far))

    watch = step_watch.StepWatch(on_stall=on_stall)
    try:
        _lapped(watch)
    finally:
        watch.close()
    # (On a machine busy enough to stretch a 10 ms sleep past 30 ms that
    # lap is a stall too, and a short one.)
    record, = [r for r in watch.stalls if r["ms"] > 150]
    assert record["lap"] == 25 and record["live"]
    assert record["median_ms"] == pytest.approx(10.0, rel=0.2)
    assert 290 < record["ms"] < 400
    assert record["end_ns"] - record["start_ns"] == pytest.approx(
        record["ms"] * 1e6)
    # The same two instants on time.time_ns(): the profiler's clock.
    assert record["end_unix_ns"] - record["start_unix_ns"] == pytest.approx(
        record["ms"] * 1e6, rel=0.01)
    assert abs(record["end_unix_ns"] - time.time_ns()) < 60e9
    (at, thread, so_far), = calls
    assert thread == WATCH_THREAD
    assert at < record["end_ns"] and so_far["lap"] == 25
    assert so_far["so_far_ms"] < record["ms"]
    assert record["reading"] in ("H1", "H2", "H3", "H4")
    json.dumps(record)  # a record is a JSON line


def test_a_burning_hvd_thread_shows_in_the_record():
    def burn_meanwhile(seconds):
        stop = threading.Event()

        def burn():
            while not stop.is_set():
                pass

        thread = threading.Thread(target=burn, name="hvd-test-burn")
        thread.start()
        time.sleep(seconds)
        stop.set()
        thread.join(timeout=5)
        assert not thread.is_alive()

    watch = step_watch.StepWatch()
    try:
        # A second: while the burner holds the interpreter every read of the
        # watch's waits a switch interval for it, and a record wants two.
        _lapped(watch, stall_s=1.0, during=burn_meanwhile)
    finally:
        watch.close()
    record, = [r for r in watch.stalls if r["ms"] > 150]
    burner, = [t for t in record["threads"]
               if t.get("name") == "hvd-test-burn"]
    assert burner["run_ms"] > 100 and "R" in burner["states"]
    assert burner["utime_s"] + burner["stime_s"] > 0.1
    assert any(frame.split(" | ")[0].endswith(" burn")
               for frame in record["stacks"]["hvd-test-burn"][:2])
    # The loop's thread stood in the sleep, at every wake.
    assert "time.sleep(seconds)" in record["stacks"]["loop"][0]
    top = max(record["loop_top_frames"], key=record["loop_top_frames"].get)
    assert "time.sleep(seconds)" in top
    assert WATCH_THREAD not in record["stacks"]


def _record(**over):
    """A stall of 2.6 s on a step of 0.45 s in which nothing moved."""
    asleep = {"tid": 7, "comm": "python", "name": "hvd-executor",
              "states": {"S": 26}, "utime_s": 0.0, "stime_s": 0.0,
              "run_ms": 0.4, "wait_ms": 0.0, "wchan": "futex_wait_queue"}
    return {"lap": 42, "live": True, "ms": 3050.0, "median_ms": 450.0,
            "wake_late_max_ms": 0.8, "sampled_ms": 2480.0, "wakes": 124,
            "sweeps": 26, "threads_seen": 190, "threads": [asleep],
            "asleep_by_wchan": {"futex_wait_queue": 180, "do_sys_poll": 9},
            "counters": {"ru_majflt": {"during": 0, "before": 0},
                         "native_cycles": {"during": 2590, "before": 2601}},
            **over}


@pytest.mark.parametrize("want,starts,over", [
    ("H3", "wakes on time", {}),
    ("H1", "the watch woke 2588 ms late",
     {"wake_late_max_ms": 2588.0, "live": False, "threads": [],
      "counters": {"steal_s": {"during": 2.4, "before": 0.0},
                   "native_cycles": {"during": 35, "before": 2159}}}),
    ("H?", "it ended between two wakes that came on time",
     {"ms": 520.0, "live": False, "threads": []}),
    # A host that stood still while the watch was reading its counters.
    ("H1", "a wake of the watch stood 95 ms over its few reads",
     {"ms": 600.0, "live": False, "threads": [], "wake_work_max_ms": 95.0,
      "counters": {"native_cycles": {"during": 480, "before": 575}}}),
    # As late a wake while the native loop turned at its rate: not the host.
    ("H?", "the watch woke 2588 ms late in a lap 2600 ms over its median, "
           "but the native loop ran on",
     {"wake_late_max_ms": 2588.0, "live": False, "threads": []}),
    # A loop that turns 3 times a lap (HOROVOD_CYCLE_TIME=50) counts nothing.
    ("H1", "the watch woke 101 ms late",
     {"ms": 136.0, "median_ms": 98.6, "wake_late_max_ms": 101.0,
      "live": False, "threads": [], "before_ms": 144.0,
      "counters": {"native_cycles": {"during": 2, "before": 3}}}),
    ("H2", "wakes on time (latest 0.8 ms); tpu_driver in D at pci_wait",
     {"threads": [{"tid": 9, "comm": "tpu_driver", "states": {"D": 20,
                                                               "S": 6},
                   "utime_s": 0.0, "stime_s": 0.0, "run_ms": 0.0,
                   "wait_ms": 0.0, "wchan": "pci_wait"}]}),
    ("H2", "wakes on time (latest 0.8 ms); ru_majflt +310",
     {"counters": {"ru_majflt": {"during": 312, "before": 2}}}),
    ("H4", "hvd-executor ran 2400 of the 2480 ms sampled",
     {"threads": [{"tid": 7, "comm": "python", "name": "hvd-executor",
                   "states": {"R": 26}, "utime_s": 2.3, "stime_s": 0.1,
                   "run_ms": 2400.0, "wait_ms": 3.0, "wchan": "-"}]}),
    # The native loop's standing cost where a 1 ms sleep is dear is none.
    ("H3", "wakes on time",
     {"threads": [{"tid": 8, "comm": "hvd-core", "states": {"S": 26},
                   "utime_s": 1.1, "stime_s": 0.1, "run_ms": 1245.0,
                   "wait_ms": 0.0, "wchan": "-"}]}),
    # The watch's own thread is never the culprit.
    ("H3", "wakes on time",
     {"threads": [{"tid": 6, "comm": "python", "name": WATCH_THREAD,
                   "states": {"R": 26}, "utime_s": 2.0, "stime_s": 0.3,
                   "run_ms": 2300.0, "wait_ms": 0.0, "wchan": "-"}]}),
])
def test_reading_parts_the_four_causes(want, starts, over):
    got, evidence = step_watch.reading(_record(**over))
    assert got == want and evidence.startswith(starts), evidence


def test_a_machine_without_pressure_files_or_a_cgroup_gives_fewer_keys(
        tmp_path):
    bare = {"pressure": str(tmp_path / "no-pressure"),
            "cgroup": str(tmp_path / "no-cgroup"),
            "stat": str(tmp_path / "no-stat")}
    watch = step_watch.StepWatch(paths=bare, file=str(tmp_path / "w.jsonl"))
    try:
        _lapped(watch, laps=20, stalled=12, stall_s=0.15)
    finally:
        watch.close()
    record, = [r for r in watch.stalls if r["ms"] > 100]
    assert not [k for k in record["counters"]
                if k.startswith(("psi_", "nr_", "throttled", "steal",
                                 "iowait"))]
    assert {"ru_majflt", "ru_nivcsw", "ru_utime_s",
            "ru_stime_s"} <= set(record["counters"])
    assert all(f.path is None for name, f in watch._files.items())
    # close() wrote the record; a second close writes nothing more.
    watch.close()
    with open(tmp_path / "w.jsonl") as f:
        assert [json.loads(line)["lap"] for line in f] == [
            r["lap"] for r in watch.stalls]


def test_a_pause_is_no_lap_and_an_open_stall_is_closed_with_the_watch(
        tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_STEP_WATCH_FILE",
                       str(tmp_path / "stalls.{rank}.jsonl"))
    monkeypatch.setenv("HOROVOD_RANK", "3")
    watch = step_watch.StepWatch()
    _lapped(watch, laps=10, stalled=-1)
    watch.pause()
    time.sleep(0.15)          # an evaluation, a checkpoint: not a stall
    watch.lap()
    assert watch.laps == 12
    time.sleep(0.15)          # a lap that never ends
    step_watch.close_all()    # what hvd.shutdown() calls
    record = watch.stalls[-1]
    assert record["lap"] == 11 and record["open"] is True
    assert all(r["ms"] < 100 for r in watch.stalls[:-1])   # not the pause
    with open(tmp_path / "stalls.3.jsonl") as f:
        assert [json.loads(line)["lap"] for line in f][-1] == 11


def test_a_host_that_stood_still_is_read_from_the_late_wake():
    """Where the whole host stands still the watch's thread does too: it
    wakes after the lap has ended, as late as the lap was long, and that
    lateness is the record (H1)."""
    watch = step_watch.StepWatch()
    try:
        _lapped(watch, laps=12, stalled=-1)
        # What a wake finds after 500 ms in which nothing ran: a lap of that
        # length already ended, and its own sample that late.
        ended = time.perf_counter_ns()
        watch._stop.set()
        watch._thread.join(timeout=2)
        assert not watch._thread.is_alive()
        watch._laps.append((watch.laps, ended + 500_000_000))
        woke = ended + 501_000_000
        watch._ring.append({**watch._ring[-1], "t_ns": woke,
                            "late_ns": 481_000_000})
        watch._check(woke)
        # ... and after 300 ms in which nothing ran while the wake before
        # was at its reads: on time, and that long over them.
        watch._laps.append((watch.laps + 1, woke + 300_000_000))
        watch._ring[-1]["work_ns"] = 290_000_000
        watch._ring.append({**watch._ring[-1], "t_ns": woke + 310_000_000,
                            "late_ns": 0, "work_ns": 0})
        watch._check(woke + 310_000_000)
    finally:
        watch.close()
    late, at_work = watch.stalls[-2:]
    assert (late["lap"], late["live"]) == (12, False)
    assert late["ms"] == pytest.approx(500, abs=1)
    assert late["wake_late_max_ms"] == pytest.approx(481)
    assert late["reading"] == "H1" and late["threads"] == []
    assert (at_work["lap"], at_work["live"]) == (13, False)
    assert at_work["wake_late_max_ms"] == pytest.approx(0, abs=1)
    assert at_work["wake_work_max_ms"] == pytest.approx(290)
    assert at_work["reading"] == "H1", at_work["evidence"]


def test_no_watch_no_thread(hvd_single):
    assert WATCH_THREAD not in {t.name for t in threading.enumerate()}
    watch = hvd_single.StepWatch()
    assert WATCH_THREAD in {t.name for t in threading.enumerate()}
    hvd_single.shutdown()     # closes the watches still open
    assert WATCH_THREAD not in {t.name for t in threading.enumerate()}
    assert watch.stalls == []


def test_the_thresholds_of_a_stalled_lap():
    def stalled(median_ms, lasted_ms):
        return step_watch._stalled(lasted_ms * 1e6, median_ms * 1e6)

    assert stalled(98.6, 143) and not stalled(98.6, 120)
    assert not stalled(398, 399) and stalled(398, 498)
    assert not stalled(10, 29) and stalled(10, 31)
    # The median is of the laps that ended; a pause ends one and is none.
    laps = [(0, 0), (1, 10), (2, 20), (None, 25), (3, 100), (4, 130)]
    assert step_watch._median_lap_ns(laps) is None   # four have ended
    assert step_watch._median_lap_ns(laps + [(5, 140)]) == 10


def test_names_on_the_profilers_clock(hvd_single, tmp_path):
    """A lapped loop and an idle dispatcher under the profiler: ``hvd_step``
    spans lap by lap, ``hvd_alive`` marks too short to name a gap."""
    watch = hvd_single.StepWatch()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            began = time.time_ns()
            watch.lap()
            for _ in range(6):
                time.sleep(0.05)
                watch.lap()
            watch.pause()
    finally:
        jax.profiler.stop_trace()
        watch.close()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    trace = trace_reduce.read_xplane(path, steps=6)
    steps = sorted((h for h in trace.host if h.name == "hvd_step"),
                   key=lambda h: h.start_ns)
    assert len(steps) == 7               # the last one closed by pause()
    assert all(45e6 < h.dur_ns < 200e6 for h in steps[:6])
    # The file's clock is time.time_ns() less the session's beginning,
    # which the file states.
    began -= stall_witness.profile_start_unix_ns(path)
    assert 0 <= steps[0].start_ns - began < 50e6
    alive = [h for h in trace.host if h.name == "hvd_alive"]
    assert 3 <= len(alive) <= 8          # 20 a second for 0.3 s
    assert all(h.dur_ns <= 50e3 for h in alive)
    gaps = trace_reduce.idle_gaps([], trace.host, trace.window)
    assert gaps and "hvd_alive" not in {name for name, _ in gaps}
