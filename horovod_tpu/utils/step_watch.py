"""``hvd.StepWatch``: the compiled path's stall inspector.

A compiled training loop runs no line of the program on the host while it
waits for the device, so when one step takes 4.5 s instead of 0.45 there is
nothing to ask afterwards.  The watch is a clock that keeps running while the
loop waits, and a record of what it saw::

    watch = hvd.StepWatch()               # starts one daemon thread
    watch.lap()                           # the loop starts here
    for batch in batches:
        *state, loss = step(*state, *batch)
        jax.block_until_ready(previous_loss)
        watch.lap()                       # one clock read, one deque append
    watch.close()
    watch.stalls                          # one record per stalled lap

The thread (``hvd-step-watch``) wakes every 20 ms, notes how late it woke
(the host-alive reading) and one cheap sample of the process's and the
machine's counters.  A lap is stalled once it has lasted longer than both
1.25 x the median of the last 32 laps and that median + 20 ms; from the wake
that notices it to the lap's end every wake also samples each thread of the
process (state, CPU time, run and wait time, ``wchan``), and the first takes
the Python stacks of the loop's thread, the main thread and every ``hvd-*``
thread.  ``reading(record)`` parts the causes (docs/observability.md,
"Stalls"): H1 the host stood still, H2 a thread of the runtime was blocked,
H3 beneath the runtime, H4 the program's own threads.

Constructing a watch is the switch: without one there is no thread and no
file.  Records are kept in ``watch.stalls``, logged once each at ``warning``,
and appended as JSON lines to ``HOROVOD_STEP_WATCH_FILE`` (where set) by
``close()``, which ``hvd.shutdown()`` calls for every watch still open.
"""

from __future__ import annotations

import collections
import json
import linecache
import os
import resource
import statistics
import sys
import threading
import time
import weakref
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

from .env import step_watch_file
from .logging import get_logger

PERIOD_NS = 20_000_000      # the watch thread's wake
LAPS_KEPT = 32              # laps the median is taken over
LEAST_LAPS = 5              # no verdict on fewer
RING_S = 60.0               # how far back the cheap samples reach
STALL_FACTOR = 1.25         # a stalled lap has passed both this x the median
STALL_SLACK_NS = 20_000_000  # ... and the median + this
SWEEP_NS = PERIOD_NS // 2    # a sweep's time for the threads not Python's
STACK_DEPTH = 16
LEAST_CYCLES = 20           # native cycles a lap must expect to be read by

# Where the samples come from; a test hands other paths in.
PATHS = {"stat": "/proc/stat", "pressure": "/proc/pressure",
         "cgroup": "/proc/self/cgroup", "cgroup_root": "/sys/fs/cgroup",
         "task": "/proc/self/task"}

_LIVE = weakref.WeakSet()


def close_all() -> None:
    """Close every watch still open (``hvd.shutdown()``)."""
    for watch in list(_LIVE):
        watch.close()


class _File:
    """A small /proc or cgroup file read at offset 0 of one descriptor kept
    open.  One that cannot be opened or read is left out from then on."""

    def __init__(self, path: Optional[str]):
        self.path, self.fd = path, None

    def read(self) -> Optional[bytes]:
        if self.path is None:
            return None
        try:
            if self.fd is None:
                self.fd = os.open(self.path, os.O_RDONLY)
            return os.pread(self.fd, 4096, 0)
        except OSError:
            self.close()
            self.path = None
            return None

    def close(self) -> None:
        if self.fd is not None:
            os.close(self.fd)
            self.fd = None


def _cgroup_cpu_stat(paths: dict) -> Optional[str]:
    """The process's cgroup ``cpu.stat``: cgroup v2's one hierarchy, or
    v1's cpu controller."""
    try:
        with open(paths["cgroup"]) as f:
            rows = [line.strip().split(":", 2) for line in f]
    except OSError:
        return None
    root = paths["cgroup_root"]
    for row in rows:
        if len(row) != 3:
            continue
        _, controllers, where = row
        if controllers == "":
            found = os.path.join(root, where.lstrip("/"), "cpu.stat")
        elif "cpu" in controllers.split(","):
            found = os.path.join(root, controllers, where.lstrip("/"),
                                 "cpu.stat")
        else:
            continue
        if os.path.exists(found):
            return found
    return None


def _read(path: str) -> Optional[bytes]:
    """A small file whole, in three system calls: each gives the interpreter
    away, and where another thread holds it each costs a switch interval."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return None
    try:
        return os.read(fd, 4096)
    except OSError:
        return None
    finally:
        os.close(fd)


def _stack(frame) -> list:
    """``file:line function | source line``, innermost first."""
    out = []
    while frame is not None and len(out) < STACK_DEPTH:
        code = frame.f_code
        line = linecache.getline(code.co_filename, frame.f_lineno).strip()
        out.append(f"{code.co_filename}:{frame.f_lineno} {code.co_name}"
                   + (f" | {line}" if line else ""))
        frame = frame.f_back
    return out


class StepWatch:
    """See the module's docstring.  ``on_stall(record_so_far)`` is called
    once for each stalled lap, from the watch thread, at the wake that
    notices it; ``file`` overrides ``HOROVOD_STEP_WATCH_FILE``; ``paths``
    overrides entries of ``PATHS``."""

    def __init__(self, on_stall: Optional[Callable[[dict], None]] = None,
                 file: Optional[str] = None, paths: Optional[dict] = None):
        self.stalls = []
        self._on_stall = on_stall
        self._file = file if file is not None else step_watch_file()
        self._written = 0
        self._paths = {**PATHS, **(paths or {})}
        # (lap index or None while paused, perf_counter_ns at its start):
        # written by the loop's thread alone, one append a lap.
        self._laps = collections.deque(maxlen=LAPS_KEPT + 1)
        self._opened = 0
        self._span = None
        self._loop_ident = None
        self._ring = collections.deque()
        self._open = None      # the stalled lap this thread is following
        self._judged = -1      # the newest lap that has ended and been judged
        self._tick_s = os.sysconf("SC_CLK_TCK")
        self._absent = set()   # per-thread files this kernel does not keep
        pressure = self._paths["pressure"]
        self._files = {
            "stat": _File(self._paths["stat"]),
            "cpu.stat": _File(_cgroup_cpu_stat(self._paths)),
            **{f"psi_{what}": _File(os.path.join(pressure, what))
               for what in ("cpu", "memory", "io")}}
        self._log = get_logger()
        self._stop = threading.Event()
        self._closed = False
        self._thread = threading.Thread(target=self._run,
                                        name="hvd-step-watch", daemon=True)
        _LIVE.add(self)
        self._thread.start()

    # -- the loop's side ----------------------------------------------------

    @property
    def laps(self) -> int:
        """Laps begun so far: the index the next ``lap()`` gives."""
        return self._opened

    def lap(self) -> None:
        """The loop passed here: the open lap ends and the next begins."""
        now = time.perf_counter_ns()
        index = self._opened
        self._laps.append((index, now))
        self._opened = index + 1
        if self._loop_ident is None:
            self._loop_ident = threading.get_ident()
        # One span a lap on the profiler's clock; outside a profiler
        # session each of the two is a flag test.
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span = TraceAnnotation("hvd_step", lap=index)
        self._span.__enter__()

    def pause(self) -> None:
        """The loop stops stepping (an evaluation, a checkpoint, the end of
        a window): no lap is open until the next ``lap()``."""
        self._laps.append((None, time.perf_counter_ns()))
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def close(self) -> None:
        """Stop the thread, end a stall still open (its record says
        ``"open": true``) and append the records not yet written to the
        file.  A second call does nothing."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=2.0)
        if self._open is not None:
            self._finish(self._open, time.perf_counter_ns(), still_open=True)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        for f in self._files.values():
            f.close()
        self._write()
        _LIVE.discard(self)

    def _write(self) -> None:
        fresh = self.stalls[self._written:]
        if not self._file or not fresh:
            return
        with open(self._file, "a") as f:
            for record in fresh:
                f.write(json.dumps(record) + "\n")
        self._written = len(self.stalls)

    # -- the watch thread ---------------------------------------------------

    def _run(self) -> None:
        asked = time.perf_counter_ns() + PERIOD_NS
        while not self._stop.wait(
                max(0.0, (asked - time.perf_counter_ns()) / 1e9)):
            woke = time.perf_counter_ns()
            sample = {}
            try:
                sample.update(self._sample(woke, woke - asked))
                self._ring.append(sample)
                while self._ring[0]["t_ns"] < woke - RING_S * 1e9:
                    self._ring.popleft()
                self._check(woke)
            except Exception:  # the thread's boundary: say so, keep watching
                self._log.exception("step watch: a wake failed")
            # What a wake costs is not lateness of the next, and is kept: a
            # host that stands still while this thread reads its counters
            # shows here and not there.
            done = time.perf_counter_ns()
            sample["work_ns"] = done - woke
            asked = done + PERIOD_NS

    def _sample(self, woke: int, late: int) -> dict:
        """The cheap sample of every wake: counters that only grow, of the
        process (``getrusage``), the machine (``/proc/stat``, pressure
        stalls), its cgroup and the native core's loop."""
        usage = resource.getrusage(resource.RUSAGE_SELF)
        sample = {"t_ns": woke, "unix_ns": time.time_ns(), "late_ns": late,
                  "ru_majflt": usage.ru_majflt, "ru_nivcsw": usage.ru_nivcsw,
                  "ru_utime_s": usage.ru_utime, "ru_stime_s": usage.ru_stime}
        stat = self._files["stat"].read()
        if stat:
            cpu = stat.split(b"\n", 1)[0].split()
            if len(cpu) > 8 and cpu[0] == b"cpu":
                sample["iowait_s"] = int(cpu[5]) / self._tick_s
                sample["steal_s"] = int(cpu[8]) / self._tick_s
        for what in ("cpu", "memory", "io"):
            for row in (self._files[f"psi_{what}"].read() or b"").split(b"\n"):
                kind, _, rest = row.partition(b" ")
                total = rest.rpartition(b"total=")[2]
                if kind in (b"some", b"full") and total.isdigit():
                    sample[f"psi_{what}_{kind.decode()}_us"] = int(total)
        for row in (self._files["cpu.stat"].read() or b"").split(b"\n"):
            key, _, value = row.partition(b" ")
            if key in (b"nr_throttled", b"throttled_usec") and value.isdigit():
                sample[key.decode()] = int(value)
            elif key == b"throttled_time" and value.isdigit():  # v1: ns
                sample["throttled_usec"] = int(value) // 1000
        cycles = _native_cycles()
        if cycles is not None:
            sample["native_cycles"] = cycles
        return sample

    def _check(self, woke: int) -> None:
        laps = list(self._laps)
        if not laps:
            return
        median = _median_lap_ns(laps)
        stall = self._open
        # The laps that have ended since the last wake: the stalled one this
        # thread was following, and any that began and ended between two
        # wakes (a host that stood still kept this thread from waking too).
        for (lap, start), (_, end) in zip(laps, laps[1:]):
            if lap is None or lap <= self._judged:
                continue
            self._judged = lap
            if stall is not None and lap == stall["lap"]:
                self._finish(stall, end)
                stall = None
            elif median is not None and _stalled(end - start, median):
                self._finish(self._begin(lap, start, median, woke,
                                         live=False), end)
        index, start = laps[-1]
        if stall is not None and index != stall["lap"]:
            self._finish(stall, woke, end_seen=False)  # its stamp had gone
            stall = None
        if index is None:
            return
        if stall is None:
            if median is None or not _stalled(woke - start, median):
                return
            stall = self._open = self._begin(index, start, median, woke)
        self._stalled_wake(stall, woke)

    def _begin(self, index: int, start: int, median: float, woke: int,
               live: bool = True) -> dict:
        """What is kept of a stalled lap while it lasts.  ``live`` is false
        for one noticed only after it ended: no stack and no thread of it
        can be read any more, and nobody is told."""
        stacks = {}
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items() if live else ():
            name = names.get(ident, str(ident))
            if ident == self._loop_ident:
                stacks["loop"] = _stack(frame)
            elif name == "MainThread" or (name.startswith("hvd-")
                                          and name != self._thread.name):
                stacks[name] = _stack(frame)
        stall = {"lap": index, "start_ns": start, "median_ns": median,
                 "live": live, "noticed_ns": woke, "wakes": 0, "sweeps": 0,
                 "next_sweep_ns": 0, "turn": 0, "threads": {},
                 "stacks": stacks,
                 "loop_top_frames": collections.Counter()}
        if live and self._on_stall is not None:
            so_far = {"lap": index, "start_ns": start,
                      "median_ms": median / 1e6,
                      "so_far_ms": (woke - start) / 1e6, "stacks": stacks}
            try:
                self._on_stall(so_far)
            except Exception:  # the caller's code, on this thread
                self._log.exception("step watch: on_stall raised")
        return stall

    def _stalled_wake(self, stall: dict, woke: int) -> None:
        """What cannot be read afterwards, at every wake of a stalled lap."""
        with TraceAnnotation("hvd_stall_sample", lap=stall["lap"]):
            pass
        stall["wakes"] += 1
        frame = sys._current_frames().get(self._loop_ident)
        if frame is not None:
            stall["loop_top_frames"][_stack(frame)[0]] += 1
        if woke < stall["next_sweep_ns"]:
            return
        task = self._paths["task"]
        try:
            tids = os.listdir(task)
        except OSError:
            return
        names = {str(t.native_id): t.name for t in threading.enumerate()}
        stall["sweeps"] += 1
        self._ring[-1]["swept"] = True   # this wake's work is the sweep's
        # The threads Python started (the program's own first) at every
        # sweep, the rest in turn, as many as SWEEP_NS pays for: where a
        # thread holds the interpreter each read below waits for it, and a
        # sweep of every thread would outlast the stall.
        ours = sorted((tid for tid in tids if tid in names),
                      key=lambda tid: not names[tid].startswith("hvd-"))
        rest = sorted(tid for tid in tids if tid not in names)
        turn = stall["turn"] % max(len(rest), 1)
        for n, tid in enumerate(ours + rest[turn:] + rest[:turn]):
            if n >= len(ours) and time.perf_counter_ns() - woke > SWEEP_NS:
                break
            stall["turn"] += n >= len(ours)
            self._sweep_thread(stall, tid, names.get(tid))
        # The watch spends a quarter of its time on sweeps at most.
        cost = time.perf_counter_ns() - woke
        stall["next_sweep_ns"] = woke + 4 * cost - PERIOD_NS // 2

    def _sweep_thread(self, stall: dict, tid: str, name: Optional[str]) -> None:
        task = self._paths["task"]
        stat = _read(os.path.join(task, tid, "stat"))
        if not stat:   # the thread ended
            return
        comm = stat[stat.index(b"(") + 1:stat.rindex(b")")].decode(
            "utf-8", "replace")
        rest = stat[stat.rindex(b")") + 2:].split()
        utime, stime = int(rest[11]), int(rest[12])
        sched, wchan = [], ""
        if "schedstat" not in self._absent:
            sched = (_read(os.path.join(task, tid, "schedstat"))
                     or b"").split()
            if len(sched) < 2:   # this kernel keeps none: asked once
                self._absent.add("schedstat")
        if len(sched) < 2:       # CPU time stands in, by the tick
            sched = [(utime + stime) * 1_000_000_000 // self._tick_s, 0]
        if "wchan" not in self._absent:
            wchan = _read(os.path.join(task, tid, "wchan"))
            if wchan is None:
                self._absent.add("wchan")
            wchan = (wchan or b"").decode("ascii", "replace").strip()
        now = (utime, stime, int(sched[0]), int(sched[1]))
        seen = stall["threads"].get(tid)
        if seen is None:
            seen = stall["threads"][tid] = {
                "comm": comm, "first": now,
                "states": collections.Counter(),
                "wchans": collections.Counter()}
        seen["last"] = now
        if name is not None:
            seen["name"] = name
        seen["states"][rest[0].decode()] += 1
        seen["wchans"][wchan if wchan not in ("", "0") else "-"] += 1

    def _finish(self, stall: dict, end: int, end_seen: bool = True,
                still_open: bool = False) -> None:
        if stall is self._open:
            self._open = None
        record = self._record(stall, end)
        if not end_seen:
            record["end_approximate"] = True  # the lap's stamp had gone
        if still_open:
            record["open"] = True
        record["reading"], record["evidence"] = reading(record)
        if record["loop_top_frames"]:
            record["evidence"] += "; the loop stood at " + max(
                record["loop_top_frames"],
                key=record["loop_top_frames"].get).split("/")[-1]
        self.stalls.append(record)
        self._log.warning(
            "step watch: lap %d %s %.1f ms against a median of %.1f ms "
            "(%d wakes inside it, the latest by %.1f ms): %s, %s",
            record["lap"], "has taken" if still_open else "took",
            record["ms"], record["median_ms"], record["wakes"],
            record["wake_late_max_ms"], record["reading"], record["evidence"])

    def _record(self, stall: dict, end: int) -> dict:
        start = stall["start_ns"]
        ring = list(self._ring)
        upto = max(end + PERIOD_NS, stall["noticed_ns"])

        def within(a, b):
            """How much of a..b lies in the lap (and the wake after it)."""
            return max(0, min(b, upto) - max(a, start))

        # Of each wake, the part of its lateness and of its own work (a
        # sweep of every thread apart) that lies inside the lap.
        late = max((within(s["t_ns"] - s["late_ns"], s["t_ns"])
                    for s in ring), default=0)
        work = max((within(s["t_ns"], s["t_ns"] + s.get("work_ns", 0))
                    for s in ring if "swept" not in s), default=0)

        def at(t):
            """The newest sample taken by ``t`` (the oldest, before any)."""
            found = ring[0]
            for s in ring:
                if s["t_ns"] > t:
                    break
                found = s
            return found

        first, last, before = at(start), at(end + PERIOD_NS), \
            at(start - (end - start))
        counters = {}
        for key in last:
            if key in ("t_ns", "unix_ns", "late_ns", "work_ns",
                       "swept") or key not in first:
                continue
            counters[key] = {"during": last[key] - first[key]}
            if key in before:
                counters[key]["before"] = first[key] - before[key]
        threads, asleep = [], collections.Counter()
        ticks = self._tick_s
        for tid, seen in stall["threads"].items():
            (u0, s0, run0, wait0), (u1, s1, run1, wait1) = (seen["first"],
                                                            seen["last"])
            row = {"tid": int(tid), "comm": seen["comm"],
                   "states": dict(seen["states"]),
                   "utime_s": (u1 - u0) / ticks, "stime_s": (s1 - s0) / ticks,
                   "run_ms": (run1 - run0) / 1e6,
                   "wait_ms": (wait1 - wait0) / 1e6,
                   "wchan": seen["wchans"].most_common(1)[0][0]}
            if "name" in seen:   # a thread Python started
                row["name"] = seen["name"]
            # Kept whole: a thread that ran, waited for a core or slept
            # where it cannot be woken, and every thread of the program.
            # The rest is counted by where it slept.
            if (set(row["states"]) - {"S"} or row["run_ms"] > 0
                    or row["wait_ms"] > 0 or "name" in row):
                threads.append(row)
            else:
                asleep[row["wchan"]] += 1
        threads.sort(key=lambda r: -r["run_ms"])
        return {
            "lap": stall["lap"], "live": stall["live"],
            "ms": (end - start) / 1e6,
            "median_ms": stall["median_ns"] / 1e6,
            "start_ns": start, "end_ns": end,
            # time.time_ns() at the same instants, by the difference of the
            # two clocks at the nearest wake: the profiler's clock.
            "start_unix_ns": start + first["unix_ns"] - first["t_ns"],
            "end_unix_ns": end + last["unix_ns"] - last["t_ns"],
            "noticed_after_ms": (stall["noticed_ns"] - start) / 1e6,
            "sampled_ms": max(0, min(end, last["t_ns"])
                              - stall["noticed_ns"]) / 1e6,
            "wakes": stall["wakes"], "sweeps": stall["sweeps"],
            "wake_late_max_ms": late / 1e6, "wake_work_max_ms": work / 1e6,
            "before_ms": (first["t_ns"] - before["t_ns"]) / 1e6,
            "counters": counters,
            "threads_seen": len(stall["threads"]), "threads": threads,
            "asleep_by_wchan": dict(asleep),
            "stacks": stall["stacks"],
            "loop_top_frames": dict(stall["loop_top_frames"])}


def _median_lap_ns(laps: list) -> Optional[float]:
    """The median of the laps that have ended among ``laps``."""
    done = [b[1] - a[1] for a, b in zip(laps, laps[1:]) if a[0] is not None]
    return statistics.median(done) if len(done) >= LEAST_LAPS else None


def _stalled(lasted_ns: float, median_ns: float) -> bool:
    """143 ms on a step of 98.6 is a stall; 399 ms on one of 398 is none."""
    return lasted_ns > max(STALL_FACTOR * median_ns,
                           median_ns + STALL_SLACK_NS)


def _native_cycles() -> Optional[int]:
    """The native core's loop count, where the library is initialized: a
    second clock, in a C++ thread."""
    from ..context import HorovodContext

    try:
        return HorovodContext.instance().core.cycle_count()
    except ValueError:   # not initialized (or shut down since)
        return None


def reading(record: dict) -> tuple:
    """``("H1".."H4", the evidence in one line)`` of a stall's record, by
    the table of docs/observability.md, "Stalls"."""
    excess = record["ms"] - record["median_ms"]
    late = record["wake_late_max_ms"]
    work = record.get("wake_work_max_ms", 0.0)
    counters = record["counters"]

    def moved(key):
        c = counters.get(key, {})
        return c.get("during", 0) - c.get("before", 0)

    if max(late, work) >= 0.5 * excess:
        also = [f"{key} +{moved(key):g}" for key in (
            "steal_s", "throttled_usec", "nr_throttled", "ru_nivcsw")
            if moved(key) > 0]
        stood = (f"the watch woke {late:.0f} ms late" if late >= work else
                 f"a wake of the watch stood {work:.0f} ms over its few reads")
        cycles = counters.get("native_cycles")
        if cycles and cycles.get("before"):
            also.append(f"native cycles {cycles['during']} against "
                        f"{cycles['before']} before")
            # The second clock.  A thread that holds the interpreter makes
            # this one wake as late as a host that stood still does, and the
            # native loop, which needs no interpreter, tells them apart
            # (where it turns often enough in a lap to count by).
            a_ms = cycles["before"] / max(record.get("before_ms")
                                          or record["ms"], 1e-3)
            if (a_ms * record["ms"] >= LEAST_CYCLES and cycles["during"]
                    > a_ms * (record["ms"] - 0.5 * max(late, work))):
                return "H?", (
                    f"{stood} in a lap {excess:.0f} ms over its median, but "
                    f"the native loop ran on ({also[-1]}): the interpreter "
                    f"was held, or this thread alone was woken late")
        return "H1", (f"{stood} in a lap {excess:.0f} ms over its median"
                      + ("; " + ", ".join(also) if also else ""))
    if not record["live"]:
        return "H?", (f"it ended between two wakes that came on time (latest "
                      f"{late:.1f} ms): nothing of it was sampled")
    sampled = max(record["sampled_ms"], 1e-3)
    ours = [t for t in record["threads"]
            if t["comm"].startswith("hvd")
            or (t.get("name", "").startswith("hvd-")
                and t["name"] != "hvd-step-watch")]
    # Nearly a whole core: the native loop's standing cost (half a core
    # where a sleep of 1 ms is a sandbox's) is no stall's cause.
    burning = [t for t in ours if t["run_ms"] >= 0.8 * sampled]
    if burning:
        t = burning[0]
        return "H4", (f"{t.get('name', t['comm'])} ran {t['run_ms']:.0f} of "
                      f"the {sampled:.0f} ms sampled (utime "
                      f"{t['utime_s']:.2f} s)")
    blocked = [t for t in record["threads"] if t not in ours
               and t["states"].get("D", 0)
               >= max(1, 0.25 * sum(t["states"].values()))]
    pressure = [key for key in ("psi_memory_some_us", "psi_io_some_us")
                if moved(key) >= 100 * excess]  # a tenth of the excess, in us
    if blocked or moved("ru_majflt") > 0 or pressure:
        what = [f"{t['comm']} in D at {t['wchan']} "
                f"({t['states']['D']} of {sum(t['states'].values())} readings)"
                for t in blocked[:2]]
        what += [f"{key} +{moved(key):g}" for key in ["ru_majflt"] + pressure
                 if moved(key) > 0]
        return "H2", f"wakes on time (latest {late:.1f} ms); " + ", ".join(what)
    ran = [t for t in record["threads"] if t["run_ms"] >= 0.1 * sampled]
    return "H3", (
        f"wakes on time (latest {late:.1f} ms), {record['threads_seen']} "
        f"threads seen, {len(ran)} ran a tenth of the time or more"
        + ("".join(f", {t.get('name', t['comm'])} {t['run_ms']:.0f} ms"
                   for t in ran[:3]))
        + f"; asleep by wchan {record['asleep_by_wchan']}")
