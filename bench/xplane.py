"""A reader of the few fields of a profiler trace (``.xplane.pb``, an
``XSpace`` protobuf) that ``jax.profiler.ProfileData`` leaves out: the
stats of an event's metadata, such as an XLA op's ``tf_op`` (its JAX name
stack), merged with the event's own stats.

It decodes the protobuf wire format directly, so it needs no schema
module. The fields read (``tsl/profiler/protobuf/xplane.proto``)::

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map), stat_metadata = 5 (map)
    XLine           name = 2, events = 4
    XEvent          metadata_id = 1, stats = 4
    XEventMetadata  id = 1, name = 2, stats = 5
    XStatMetadata   id = 1, name = 2
    XStat           metadata_id = 1, double 2, uint64 3, int64 4, str 5, bytes 6, ref 7

Events come back in file order, plane by plane and line by line, which is
the order ``ProfileData`` walks them in too.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Dict, Iterator, List, Tuple, Union

Value = Union[int, float, str, bytes]


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[str, Dict[str, Value]]]   # (metadata name, stats) in file order


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Union[int, bytes]]]:
    """(field number, wire type, value) of each field of a message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, names: Dict[int, str]) -> Tuple[int, Value]:
    mid, val = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 6:
            val = bytes(v)
        elif f == 7:                       # a string kept once, as a stat name
            val = names.get(v, "")
    return mid, val


def _stats(bufs: List[bytes], names: Dict[int, str]) -> Dict[str, Value]:
    out = {}
    for b in bufs:
        mid, val = _stat(b, names)
        out[names.get(mid, str(mid))] = val
    return out


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf: bytes) -> Plane:
    name, lines, ev_meta, stat_meta = "", [], [], {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(_map_entry(v)[1])
        elif f == 5:
            key, val = _map_entry(v)
            stat_meta[key] = next((bytes(x).decode() for g, _, x in _fields(val) if g == 2), "")
    meta: Dict[int, Tuple[str, Dict[str, Value]]] = {}
    for m in ev_meta:
        mid, mname, mstats = 0, "", []
        for f, _, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = bytes(v).decode("utf-8", "replace")
            elif f == 5:
                mstats.append(v)
        meta[mid] = (mname, _stats(mstats, stat_meta))
    out = []
    for lb in lines:
        lname, events = "", []
        for f, _, v in _fields(lb):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 4:
                mid, own = 0, []
                for g, _, x in _fields(v):
                    if g == 1:
                        mid = x
                    elif g == 4:
                        own.append(x)
                mname, mstats = meta.get(mid, ("", {}))
                events.append((mname, {**mstats, **_stats(own, stat_meta)}))
        out.append(Line(lname, events))
    return Plane(name, out)


def read(data: bytes) -> List[Plane]:
    """The planes of a serialized ``XSpace``."""
    return [_plane(v) for f, _, v in _fields(memoryview(data)) if f == 1]
