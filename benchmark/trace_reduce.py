"""From a profiler trace to per-layer metrics.

The core is pure Python over ``Event`` tuples ``(name, start_ns, dur_ns,
line, scope)`` so that tests feed it hand-made events; ``read_xplane`` is the
thin adapter that fills a ``Trace`` from an ``.xplane.pb``: names and times
with ``jax.profiler.ProfileData``, each op's scope with ``xplane_raw`` (nothing
but JAX and the standard library is needed to read one).

The readers at the bottom are what ``layer_metrics/<name>.json`` files name
as ``"reducer": "trace_reduce.<function>"``.  A reader takes the ``Trace``,
the run's context (``run.py:reader_context``: the traced run, the cell, its
configuration and traffic, the chip's peaks) and the file's ``params``; it
returns a number, or None when there is nothing to read (the harness then
leaves the metric out).
"""

from __future__ import annotations

import re
from typing import Iterable, NamedTuple, Optional


class Event(NamedTuple):
    """``name``: a device op's whole HLO instruction, a host span's name.
    ``scope``: JAX's ``op_name`` of a device op, the scopes it was traced
    under (``jit(step)/transpose(jvp(GPT))/h_0/attn/dot_general:``).  A
    fusion has one, its root's; copies and layout changes have none."""

    name: str
    start_ns: float
    dur_ns: float
    line: str
    scope: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


SYNC_LINE = "XLA Ops"          # what the core executes, one op at a time
ASYNC_LINE = "Async XLA Ops"   # copies and collectives in flight beside it


class Trace(NamedTuple):
    """One traced window: per-device op events of both lines, the host
    spans of the benchmark (``bench_*`` TraceAnnotations) and of the program
    (``hvd_*``), the window and its step count."""

    devices: dict          # device index -> [Event] (any order)
    host: list             # [Event] of the bench_* and hvd_* host spans
    window: tuple          # (start_ns, end_ns)
    steps: int


WINDOW_SPAN = "bench_window"


def from_example(example: dict, shift_ns: float = 0.0) -> Trace:
    """The ``Trace`` a metric file's ``example`` (or its ``nothing`` case)
    describes: ``events`` are one device's ops, ``[name, start_ns, dur_ns,
    line]`` or with a scope five, ``host`` the spans alike; ``window`` and
    ``steps`` as they are.  ``shift_ns`` moves all of it in time, so that
    several examples fit one trace."""
    def events(rows):
        return [Event(*row)._replace(start_ns=row[1] + shift_ns)
                for row in rows]

    ops = events(example.get("events", []))
    lo, hi = example["window"]
    return Trace({0: ops} if ops else {}, events(example.get("host", [])),
                 (lo + shift_ns, hi + shift_ns), example["steps"])


def examples_of(meta: dict) -> list:
    """The examples of a metric file: its one ``example``, or the
    ``examples`` of a name that several families share, each with the
    ``cell`` it is of.  Empty where the file has neither."""
    if "example" in meta:
        return [meta["example"]]
    return list(meta.get("examples", ()))


# ---------------------------------------------------------------------------
# Core: intervals
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[tuple]) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(events: Iterable[Event], window: tuple) -> list:
    """The events' intervals cut to ``window``."""
    lo, hi = window
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def length(merged: list) -> float:
    return sum(end - start for start, end in merged)


def subtract(merged: list, cover: list) -> list:
    """The parts of ``merged`` that ``cover`` (merged too) does not touch."""
    out, j = [], 0
    for start, end in merged:
        while j < len(cover) and cover[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def classify(events: Iterable[Event], pattern: str = "",
             scope: Optional[str] = None, scope_not: Optional[str] = None,
             line: Optional[str] = None) -> tuple:
    """(matching, others).  An event matches when every condition given
    agrees: ``pattern`` is found in its name, ``scope`` is found in its
    scope, ``scope_not`` is not (an op without a scope passes), and with
    ``line="sync"`` it is one of the ops the core itself executes."""
    if line not in (None, "sync"):
        raise ValueError(f"line={line!r}: \"sync\" or nothing")
    name_rx = re.compile(pattern)
    scope_rx = re.compile(scope) if scope is not None else None
    not_rx = re.compile(scope_not) if scope_not is not None else None
    hit, miss = [], []
    for e in events:
        ok = (name_rx.search(e.name)
              and (scope_rx is None or scope_rx.search(e.scope))
              and (not_rx is None or not not_rx.search(e.scope))
              and (line is None or e.line != ASYNC_LINE))
        (hit if ok else miss).append(e)
    return hit, miss


def sync_ops(events: Iterable[Event]) -> list:
    """The ops the core itself executes (not the async line beside them)."""
    return [e for e in events if e.line != ASYNC_LINE]


def busy_ns(events: Iterable[Event], window: tuple) -> float:
    """Time in ``window`` during which at least one event runs."""
    return length(merge(clip(events, window)))


def idle_share(events: Iterable[Event], window: tuple) -> float:
    """Share of ``window`` in which the core executes no op."""
    return 1.0 - busy_ns(sync_ops(events), window) / (window[1] - window[0])


def exposed_length(hit: Iterable[Event], miss: Iterable[Event],
                   window: tuple) -> float:
    """Time during which an op of ``hit`` runs (on either line) and the core
    executes no op of ``miss``."""
    return length(subtract(merge(clip(hit, window)),
                           merge(clip(sync_ops(miss), window))))


def exposed_ns(events: Iterable[Event], pattern: str, window: tuple,
               **select) -> float:
    """Time during which an op matching ``pattern`` (and ``select``, as
    ``classify`` takes it) runs (on either line) and the core executes no
    other op: the part of a collective that compute does not hide."""
    return exposed_length(*classify(events, pattern, **select), window)


def per_step(total_ns: float, steps: int) -> float:
    """Nanoseconds over a window of ``steps`` steps -> milliseconds a step."""
    return total_ns / steps / 1e6


def short_name(name: str) -> str:
    """``%fusion.45 = (f32[256]{...}, ...) fusion(...)`` -> ``fusion.45``:
    the trace names a TPU op by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_group(name: str) -> str:
    """``fusion.45`` -> ``fusion``, ``attn.99`` -> ``attn``,
    ``fusion.4106.remat`` -> ``fusion``: the instruction's name without its
    index.  XLA names a fusion for what it computes and a kernel call for
    the module scope that made it, so the groups mean something; single
    instructions (3,669 a step in ResNet-50) do not fit ten rows."""
    return re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", short_name(name))


def top_ops(events: Iterable[Event], window: tuple, n: int = 10) -> list:
    """``[[group, seconds], ...]``: the groups of ops (``op_group``) the
    core spent most time in."""
    total = {}
    for e in sync_ops(events):
        for start, end in clip([e], window):
            name = op_group(e.name)
            total[name] = total.get(name, 0.0) + (end - start)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(events: Iterable[Event], host: Iterable[Event], window: tuple,
              n: int = 10) -> list:
    """``[[what the host was doing, seconds], ...]``: the longest stretches
    of ``window`` with no device op, each named by the innermost host span
    that covers most of it (the shortest of those overlapping more than half
    of it, so a program's ``hvd_*`` span inside ``bench_dispatch`` is what is
    named); where none does, by the span that overlaps it most
    (``(no host span)`` if none does)."""
    gaps = subtract([tuple(window)], merge(clip(sync_ops(events), window)))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [h for h in host if h.name != WINDOW_SPAN]
    out = []
    for start, end in gaps:
        best, best_overlap, inner = "(no host span)", 0.0, None
        for h in host:
            overlap = min(end, h.end_ns) - max(start, h.start_ns)
            if overlap > best_overlap:
                best, best_overlap = h.name, overlap
            if 2 * overlap > end - start and (inner is None
                                              or h.dur_ns < inner.dur_ns):
                inner = h
        out.append([inner.name if inner else best, (end - start) / 1e9])
    return out


def mean_over_devices(trace: Trace, fn) -> Optional[float]:
    values = [fn(events) for _, events in sorted(trace.devices.items())]
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------------------
# Adapter: .xplane.pb -> Trace
# ---------------------------------------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The benchmark's own spans, and the program's: a TraceAnnotation inside
# horovod_tpu/ is named hvd_<what>.
HOST_SPAN_PREFIXES = ("bench_", "hvd_")


def read_xplane(path: str, steps: int) -> Trace:
    """Op events (both lines) of every device plane, each with its scope
    (``xplane_raw.op_scopes``: by the op's name, from the file's own
    metadata), the ``bench_*`` and ``hvd_*`` host spans, and the window the
    ``bench_window`` span marks (or, without one, first device op start to
    last device op end)."""
    import gzip

    from jax.profiler import ProfileData

    from benchmark import xplane_raw

    # .gz: the recorded traces of the tests
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    scopes = xplane_raw.op_scopes(raw, DEVICE_PLANE.pattern)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            scope_of = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name in (SYNC_LINE, ASYNC_LINE):
                    ops.extend(Event(e.name, e.start_ns, e.duration_ns,
                                     line.name, scope_of.get(e.name, ""))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns,
                                  line.name) for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIXES))
    devices = {d: ops for d, ops in devices.items() if ops}
    marks = [h for h in host if h.name == WINDOW_SPAN]
    if marks:
        window = (marks[0].start_ns, marks[0].end_ns)
    elif devices:
        every = [e for ops in devices.values() for e in ops]
        window = (min(e.start_ns for e in every), max(e.end_ns for e in every))
    else:
        window = (0.0, 0.0)
    return Trace(devices, host, window, steps)


# ---------------------------------------------------------------------------
# Readers named by layer_metrics/*.json
# ---------------------------------------------------------------------------


def host_dispatch_ms(trace: Trace, ctx: dict, **_) -> Optional[float]:
    """Host-clock time inside the ``step(...)`` call, mean per step of the
    traced window (the loop's own ``perf_counter`` stamps, not the trace)."""
    spans = (ctx.get("run") or {}).get("dispatch_s") or []
    return sum(spans) / len(spans) * 1e3 if spans else None


def device_idle_pct(trace: Trace, ctx: dict, **_) -> Optional[float]:
    share = mean_over_devices(trace, lambda ev: idle_share(ev, trace.window))
    return None if share is None else 100.0 * share


def op_time_ms(trace: Trace, ctx: dict, pattern: str = "",
               exposed: bool = False, **select) -> Optional[float]:
    """Device time a step spends in ops whose name matches ``pattern`` and
    which ``select`` admits (``scope``, ``scope_not``: regular expressions on
    the op's scope; ``line="sync"``: the core's own line only, so that
    disjoint classes add up to ``busy_s``; see ``classify``), on either line
    otherwise (union of their intervals, so an async collective and its
    start/done ops count once); with ``exposed``, only the part during which
    the core executes no other op.  None when no op of any device matches."""
    split = [classify(events, pattern, **select)
             for _, events in sorted(trace.devices.items())]
    if not any(hit for hit, _ in split):
        return None
    total = sum(exposed_length(hit, miss, trace.window) if exposed
                else busy_ns(hit, trace.window) for hit, miss in split)
    return per_step(total / len(split), trace.steps)


def roofline_pct(trace: Trace, ctx: dict, pattern: str, least: str,
                 least_key: Optional[str] = None,
                 **select) -> Optional[float]:
    """Least time the chip could take for a kernel group in one step over
    the device time of the ops matching ``pattern`` (and ``select``, as
    ``op_time_ms`` takes it).  ``least`` names the function
    (``<module>.<function>`` under ``benchmark/``) that computes the least
    time from the context's shapes and peaks: it returns a dict with
    ``seconds``, and lives beside the operations and bytes it counts.
    ``least_key`` (``"kernels.dq"``) is the path to the one kernel's dict
    inside it, for a function that returns several.  ``{family}`` in
    ``least`` stands for the configuration's ``family``: one metric over
    the families that each count a least time of their own
    (``"{family}_flops.experts_step_least"``)."""
    from benchmark import common

    took = op_time_ms(trace, ctx, pattern, **select)
    if not took:
        return None
    if "{family}" in least:
        least = least.replace("{family}", ctx["cfg"]["family"])
    bound = common.load_function(least)(ctx)
    for key in least_key.split(".") if least_key else ():
        bound = bound[key]
    return 100.0 * bound["seconds"] * 1e3 / took


def host_span_ms(trace: Trace, ctx: dict, pattern: str,
                 self_time: bool = False, **_) -> Optional[float]:
    """Host time a step spends inside the ``bench_*`` / ``hvd_*`` spans
    whose name matches ``pattern``, within the window: the union of their
    intervals, or with ``self_time`` each span's own time, without what the
    spans nested in it on its thread cover.  None when no span matches."""
    rx = re.compile(pattern)
    spans = [h for h in trace.host if rx.search(h.name)]
    if not spans:
        return None
    if not self_time:
        return per_step(busy_ns(spans, trace.window), trace.steps)
    total = 0.0
    for s in spans:
        children = [h for h in trace.host if h is not s and h.line == s.line
                    and s.start_ns <= h.start_ns and h.end_ns <= s.end_ns
                    and h.dur_ns < s.dur_ns]
        total += length(subtract(merge(clip([s], trace.window)),
                                 merge(clip(children, trace.window))))
    return per_step(total, trace.steps)
