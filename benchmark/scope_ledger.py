"""A traced step read by the program's layer scopes, at self time.

The program names its layers where it traces them (``jax.named_scope``
``hvd_<what>``; docs/observability.md, "Names on the profiler's clock"), and
a device op's scope (JAX's ``op_name``) holds every scope it was traced
under: **the innermost ``hvd_*`` one is the op's layer**.  An op that holds
others on the core's own line (a ``while``, a ``cond`` or a ``call`` and its
body's ops) is counted for what its body leaves of it, so the rows of one
device add up to its busy time and to nothing more.

Pure Python over ``trace_reduce``'s events, as that module is.  The readers
are what ``layer_metrics/<name>.json`` files name as ``"reducer":
"scope_ledger.<function>"``; ``table`` is what ``tools/step_ledger.py``
prints, so that the tool and the metrics cannot disagree.
"""

from __future__ import annotations

import functools
import re
from typing import Iterable, Optional

from benchmark import trace_reduce
from benchmark.trace_reduce import Event, Trace

# A scope as it stands in an ``op_name``: a segment of its own, or the
# innermost of a ``transpose(jvp(hvd_attn_gate))`` that a ``custom_vjp`` or a
# checkpoint wraps it in.
LAYER = re.compile(r"(?:^|[/(])(hvd_[a-z0-9_]+)(?=[/)]|$)")
UNATTRIBUTED = "(unattributed)"


@functools.lru_cache(maxsize=None)    # a step's scopes repeat every step
def layer_of_scope(scope: str) -> Optional[str]:
    """The innermost ``hvd_*`` scope of an ``op_name`` (a trace's, with its
    closing ``:``, or a compiled text's), None where it has none.  The last
    segment is the operation itself (``dot_general``, ``pallas_call``), no
    scope.  A kernel with a name is a scope of its own
    (``.../attn/hvd_flash_fwd/pallas_call``) and so a layer of its own; a
    plain call belongs to the layer that makes it."""
    path = scope.rpartition(":")[0] if ":" in scope else scope
    found = LAYER.findall(path.rpartition("/")[0])
    return found[-1] if found else None


def layer_of(event: Event) -> Optional[str]:
    """The layer of a device op: ``layer_of_scope`` of its scope.  The
    instruction's own name is not read."""
    return layer_of_scope(event.scope)


def pass_of(event: Event) -> str:
    """``forward``: under ``jvp(`` and no ``transpose(``; ``backward``: under
    ``transpose(`` (a checkpointed block's second forward with it);
    ``neither``: the optimizer, the exchange, what has no scope."""
    if "transpose(" in event.scope:
        return "backward"
    return "forward" if "jvp(" in event.scope else "neither"


_memo: dict = {}   # id(events) -> (events, window, rows): a trace's readers


def self_times(events: Iterable[Event], window: tuple) -> list:
    """``[(event, self_ns), ...]`` for the ops of one device that the core
    itself executes, inside ``window``: each op's interval minus the union
    of the ops that lie inside it (the body of a ``while``, a ``cond`` or a
    ``call`` is on the same line, within its op's interval).  The self times
    add up to ``trace_reduce.busy_ns`` of the same events."""
    kept = _memo.get(id(events))
    if kept is not None and kept[0] is events and kept[1] == window:
        return kept[2]
    lo, hi = window                # ``trace_reduce.clip``, an op at a time
    spans = sorted(((max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi), e)
                    for e in trace_reduce.sync_ops(events)
                    if e.start_ns + e.dur_ns > lo and e.start_ns < hi),
                   key=lambda s: (s[0], -s[1]))
    inside = [[] for _ in spans]   # the intervals of each op's own children
    open_ops = []                  # indices of the ops still running
    for i, (start, end, _) in enumerate(spans):
        while open_ops and spans[open_ops[-1]][1] <= start:
            open_ops.pop()
        if open_ops:
            holder = spans[open_ops[-1]]
            inside[open_ops[-1]].append((start, min(end, holder[1])))
        open_ops.append(i)
    rows = [(e, trace_reduce.length(trace_reduce.subtract(
        [(start, end)], trace_reduce.merge(inside[i])))
        if inside[i] else end - start)      # most ops hold no other
        for i, (start, end, e) in enumerate(spans) if end > start]
    if isinstance(events, list):
        if len(_memo) >= 8:        # two traces of four devices
            _memo.clear()
        _memo[id(events)] = (events, window, rows)
    return rows


def self_time_ms(trace: Trace, ctx: dict, layer: str, **_) -> Optional[float]:
    """Device time a step spends, at self time, in the ops whose layer
    (``layer_of``) matches the regular expression ``layer``, mean over the
    cell's devices.  None when no op of any device matches."""
    rx, total, found = re.compile(layer), 0.0, False
    for _, events in sorted(trace.devices.items()):
        for e, own in self_times(events, trace.window):
            name = layer_of(e)
            if name is not None and rx.search(name):
                total, found = total + own, True
    if not found:
        return None
    return trace_reduce.per_step(total / len(trace.devices), trace.steps)


def unattributed_pct(trace: Trace, ctx: dict, **_) -> Optional[float]:
    """100 x the self time of the ops under no ``hvd_*`` scope over the
    device's busy time, mean over the cell's devices: ``apply_updates`` and
    the loss's ``psum`` (the user's lines), the copies and layout changes
    XLA made without a scope, and whatever of the program is not named yet.
    None without a device op."""
    def share(events):
        rows = self_times(events, trace.window)
        busy = sum(own for _, own in rows)
        bare = sum(own for e, own in rows if layer_of(e) is None)
        return bare / busy if busy else 0.0

    mean = trace_reduce.mean_over_devices(trace, share)
    return None if mean is None else 100.0 * mean


KEYS = {"layer": lambda e: layer_of(e) or UNATTRIBUTED, "pass": pass_of,
        "op": lambda e: trace_reduce.op_group(e.name)}


def table(trace: Trace, by: tuple = ("layer", "pass", "op"),
          stats: Optional[dict] = None) -> list:
    """The step by ``by`` (of ``layer``, ``pass``, ``op``), one dict a row:
    the keys' values, ``ms`` a step of self time and ``calls`` a step, both
    means over the devices, and with ``stats`` (``{op's instruction text:
    {"flops", "bytes_accessed"}}``, XLA's own figures of one execution:
    ``xplane_raw.event_stats`` of a device plane) ``flops`` and ``bytes`` a
    step.  An op under no layer is a row by its ``scope`` too: what it was
    traced as is all that names it.  Layers by their time, the unattributed
    last; a layer's rows by theirs.  The rows' ``ms`` add up to ``busy_s /
    steps``."""
    unknown = [k for k in by if k not in KEYS]
    if unknown or not by:
        raise ValueError(f"by={by!r}: some of {sorted(KEYS)}")
    rows, over = {}, len(trace.devices) * trace.steps
    for _, events in sorted(trace.devices.items()):
        for e, own in self_times(events, trace.window):
            key = tuple(KEYS[k](e) for k in by)
            if UNATTRIBUTED in key:
                key += (e.scope,)
            row = rows.setdefault(key, {"ns": 0.0, "calls": 0, "flops": 0.0,
                                        "bytes": 0.0})
            row["ns"] += own
            row["calls"] += 1
            of_op = (stats or {}).get(e.name, {})
            row["flops"] += of_op.get("flops") or 0
            row["bytes"] += of_op.get("bytes_accessed") or 0
    first = {}   # the first key's totals: how the groups are ordered
    for key, row in rows.items():
        first[key[0]] = first.get(key[0], 0.0) + row["ns"]
    ranked = sorted(rows.items(), key=lambda kv: (
        kv[0][0] == UNATTRIBUTED, -first[kv[0][0]], kv[0][0], -kv[1]["ns"]))
    out = []
    for key, row in ranked:
        line = dict(zip(by + ("scope",), key), ms=row["ns"] / over / 1e6,
                    calls=row["calls"] / over)
        if stats is not None:
            line.update(flops=row["flops"] / over, bytes=row["bytes"] / over)
        out.append(line)
    return out


def busy_ms(trace: Trace) -> Optional[float]:
    """``busy_s / steps`` in milliseconds, as ``run.py`` reports ``busy_s``:
    what a table's rows add up to."""
    busy = trace_reduce.mean_over_devices(
        trace, lambda ev: trace_reduce.busy_ns(trace_reduce.sync_ops(ev),
                                               trace.window))
    return None if busy is None else trace_reduce.per_step(busy, trace.steps)
