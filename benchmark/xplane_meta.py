"""What a profiler trace (``.xplane.pb``) says about each operation apart
from when it ran: the stats of its **event metadata**.

On a TPU every HLO operation of a device plane has one ``XEventMetadata``
whose stats hold, among others, ``tf_op`` (the JAX name stack the
operation was traced under, e.g.
``jit(work)/while/body/closed_call/dot_general:``), ``flops`` and
``bytes_accessed``.  ``jax.profiler.ProfileData`` (what ``trace_reduce.py``
reads with) yields only the stats of the events themselves, so this module
walks the protobuf's wire format for the few fields it needs; it imports
nothing outside the standard library.  The field numbers are those of
``tsl/profiler/protobuf/xplane.proto``: ``XSpace.planes`` = 1; ``XPlane``:
``name`` = 2, ``lines`` = 3 (skipped whole: the events are
``ProfileData``'s to read), ``event_metadata`` = 4, ``stat_metadata`` = 5;
``XEventMetadata``: ``id`` = 1, ``name`` = 2, ``stats`` = 5;
``XStatMetadata``: ``id`` = 1, ``name`` = 2; ``XStat``: ``metadata_id`` = 1,
``double_value`` = 2, ``uint64_value`` = 3, ``int64_value`` = 4,
``str_value`` = 5, ``bytes_value`` = 6, ``ref_value`` = 7.

    python -m benchmark.xplane_meta <trace-dir-or-file>     # describe it
"""

from __future__ import annotations

import struct
import sys

from benchmark.trace_reduce import find_xplane

KEPT = ("tf_op", "flops", "bytes_accessed")
VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed-width field (raw bits), a memoryview for a
    length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, at = _varint(buf, at)
        elif wire == BYTES:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == FIXED64:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == FIXED32:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an xplane")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map_value(entry):
    """The value message of one ``map<int64, Message>`` entry."""
    return next((v for n, w, v in fields(entry) if n == 2 and w == BYTES),
                memoryview(b""))


def _stat(buf, stat_names: dict[int, str]):
    """``(stat name, value)`` of one ``XStat``."""
    name, value = None, None
    for number, wire, v in fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf, keep) -> tuple[str, dict[str, dict]]:
    name, metadata, stat_names = "", [], {}
    for number, wire, v in fields(buf):
        if wire != BYTES:
            continue
        if number == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif number == 4:
            metadata.append(_map_value(v))
        elif number == 5:
            sid, sname = 0, ""
            for n, w, x in fields(_map_value(v)):
                if n == 1:
                    sid = x
                elif n == 2 and w == BYTES:
                    sname = bytes(x).decode("utf-8", "replace")
            stat_names[sid] = sname
    out = {}
    for m in metadata:      # after the loop: stat names may follow them
        event_name, stats = "", {}
        for n, w, x in fields(m):
            if n == 2 and w == BYTES:
                event_name = bytes(x).decode("utf-8", "replace")
            elif n == 5 and w == BYTES:
                key, value = _stat(x, stat_names)
                if key in keep:
                    stats[key] = value
        out[event_name] = stats
    return name, out


def event_metadata(path: str, keep=KEPT) -> dict[str, dict[str, dict]]:
    """``{plane name: {event name: {stat: value}}}`` for the stats named in
    ``keep``, of every plane of the ``.xplane.pb`` at (or newest under)
    ``path``.  The event name is the one ``ProfileData`` gives the events
    of that metadata (on a device plane, the operation's HLO text)."""
    with open(find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, wire, v in fields(space):
        if number == 1 and wire == BYTES:
            name, meta = _plane(v, keep)
            planes[name] = meta
    return planes


if __name__ == "__main__":
    for plane, meta in event_metadata(sys.argv[1]).items():
        with_stats = {k: v for k, v in meta.items() if v}
        print(f"plane {plane!r}: {len(meta)} event names, "
              f"{len(with_stats)} with {KEPT}")
        for event, stats in list(with_stats.items())[:12]:
            print(f"    {event[:60]!r}: {stats}")
