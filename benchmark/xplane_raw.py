"""What ``jax.profiler.ProfileData`` does not surface of an ``.xplane.pb``:
the stats XLA attaches to each op's *metadata* (``tf_op``, JAX's ``op_name``
with its scopes; ``hlo_category``, ``flops``, ``bytes_accessed``, ``source``).
``ProfileData`` gives an event's name, times and its own three stats only.

A protobuf wire reader over the few messages needed, standard library only
(``tensorflow``, ``xprof`` and ``tensorboard_plugin_profile`` are not on the
chip machine).  Field numbers, from ``tsl/profiler/protobuf/xplane.proto``:

    XSpace          planes=1
    XPlane          name=2  lines=3  event_metadata=4  stat_metadata=5
                    (both maps: entry key=1, value=2)
    XEventMetadata  id=1  name=2  stats=5
    XStatMetadata   id=1  name=2
    XStat           metadata_id=1  double=2  uint64=3  int64=4  str_value=5
                    bytes=6  ref_value=7 (the id of a stat_metadata whose
                    name is the value)

Lines and events, the bulk of a file, are skipped by their length.
"""

from __future__ import annotations

import re
import struct
from typing import Iterator

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def varint(buf, pos: int) -> tuple:
    """(value, position after it) of the varint at ``pos``."""
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf) -> Iterator[tuple]:
    """``(field number, wire type, value)`` of each field of one message: an
    int for a varint, the raw bytes (a memoryview, no copy) otherwise."""
    buf, pos = memoryview(buf), 0
    while pos < len(buf):
        key, pos = varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, pos = varint(buf, pos)
        elif wire == BYTES:
            size, pos = varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire in (FIXED64, FIXED32):
            size = 8 if wire == FIXED64 else 4
            value, pos = buf[pos:pos + size], pos + size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an "
                             "xplane.pb, or a group field")
        yield number, wire, value


def _text(raw) -> str:
    return bytes(raw).decode("utf-8", "replace")


def _map_entry(raw) -> tuple:
    """(key, value bytes) of one entry of a ``map<int64, message>``."""
    key, value = 0, b""
    for number, _, v in fields(raw):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat_value(stat: dict, stat_names: dict):
    """The value of one decoded XStat (``{field number: raw}``)."""
    if 5 in stat:
        return _text(stat[5])
    if 7 in stat:
        return stat_names.get(stat[7], "")
    if 2 in stat:
        return struct.unpack("<d", stat[2])[0]
    if 4 in stat:  # int64: two's complement in 64 bits
        return stat[4] - (1 << 64) if stat[4] >> 63 else stat[4]
    if 3 in stat:
        return stat[3]
    return bytes(stat[6]) if 6 in stat else None


def event_stats(data: bytes, planes: str = "") -> dict:
    """``{plane name: {event-metadata name: {stat name: value}}}`` for the
    planes of a serialized XSpace whose name matches ``planes``.  A line and
    its async twin name one op in two metadata entries: their stats are
    merged."""
    rx, out = re.compile(planes), {}
    for number, wire, plane in fields(data):
        if number != 1 or wire != BYTES:
            continue
        name, metas, stat_names = "", [], {}
        for n, _, value in fields(plane):
            if n == 2:
                name = _text(value)
            elif n == 4:
                metas.append(_map_entry(value)[1])
            elif n == 5:
                key, meta = _map_entry(value)
                stat_names[key] = next(
                    (_text(v) for m, _, v in fields(meta) if m == 2), "")
        if not rx.search(name):
            continue
        events = out.setdefault(name, {})
        for meta in metas:
            event_name, stats = "", []
            for n, _, v in fields(meta):
                if n == 2:
                    event_name = _text(v)
                elif n == 5:
                    stats.append({fn: fv for fn, _, fv in fields(v)})
            events.setdefault(event_name, {}).update(
                (stat_names.get(s.get(1), str(s.get(1))),
                 _stat_value(s, stat_names)) for s in stats)
    return out


def op_scopes(data: bytes, planes: str = "") -> dict:
    """``{plane name: {op's instruction text: tf_op}}``: JAX's ``op_name`` of
    each device op (``jit(step)/jvp(GPT)/h_0/attn/dot_general:``), the scope
    of a fusion's root.  Ops without one (copies, layout changes) are left
    out; a file without the stat at all (a CPU run) gives empty maps."""
    return {plane: {event: stats["tf_op"] for event, stats in events.items()
                    if isinstance(stats.get("tf_op"), str)}
            for plane, events in event_stats(data, planes).items()}
