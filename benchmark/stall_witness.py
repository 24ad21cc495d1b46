"""Readers of the two witnesses of a stalled step that every traced window
holds (docs/observability.md, "Stalls"): how long the device stood still at
a stretch, and whether the host was alive meanwhile.  Read beside each other
they part a stall in which the whole host stood still (both read its length)
from one beneath a host that ran on (``host_alive_gap_max_ms`` stays at the
dispatcher's 50 ms).

Named by ``layer_metrics/device_gap_max_ms.json`` and
``layer_metrics/host_alive_gap_max_ms.json``; signature and context as
``trace_reduce``'s readers.
"""

from __future__ import annotations

from typing import Optional

from benchmark import trace_reduce

ALIVE_MARK = "hvd_alive"   # horovod_tpu/context.py:_executor_loop


def device_gap_max_ms(trace: trace_reduce.Trace, ctx: dict,
                      **_) -> Optional[float]:
    """The longest stretch of the window in which the first chip's core
    executes no op: the first of the gaps ``trace_reduce.idle_gaps`` ranks,
    as a number.  None without a device plane."""
    if not trace.devices:
        return None
    longest = trace_reduce.idle_gaps(trace.devices[min(trace.devices)], (),
                                     trace.window, n=1)
    return 1e3 * longest[0][1] if longest else 0.0


def host_alive_gap_max_ms(trace: trace_reduce.Trace, ctx: dict,
                          **_) -> Optional[float]:
    """The longest interval between consecutive ``hvd_alive`` marks inside
    the window, its two edges counted as marks.  None without a mark."""
    lo, hi = trace.window
    marks = sorted(h.start_ns for h in trace.host
                   if h.name == ALIVE_MARK and lo <= h.start_ns <= hi)
    if not marks:
        return None
    edges = [lo, *marks, hi]
    return max(b - a for a, b in zip(edges, edges[1:])) / 1e6


def profile_start_unix_ns(xplane: str) -> Optional[int]:
    """``time.time_ns()`` at the instant the file's times count from.  The
    profiler writes every plane's events in nanoseconds since the session
    began and states that beginning, on the Unix clock, as
    ``profile_start_time`` of the ``Task Environment`` plane: an instant a
    program stamped with ``time.time_ns()`` (a ``hvd.StepWatch`` record's
    ``start_unix_ns``) lies at that less this on the file's clock."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "Task Environment":
            return dict(plane.stats).get("profile_start_time")
    return None
