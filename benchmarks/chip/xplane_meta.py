"""The ``tf_op`` stat of each device operation in a profiler trace.

A device plane of an ``.xplane.pb`` names each operation's event by its
metadata, and that metadata carries the HLO instruction's ``op_name`` as
the ``tf_op`` stat: the path of JAX transforms and ``jax.named_scope``
names the operation was traced under (``jit(<lambda>)/dot_general:``).
``jax.profiler.ProfileData`` does not expose metadata stats, so this reads
them from the file's protobuf wire format, with the standard library only:

    XSpace.planes (1) -> XPlane: name (2), event_metadata (4),
                                 stat_metadata (5)
    XEventMetadata: name (2), stats (5);  XStatMetadata: name (2)
    XStat: metadata_id (1), str_value (5), ref_value (7)
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

STAT = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_values(entry: bytes) -> bytes:
    """The value (field 2) of a map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _plane(buf: bytes) -> Tuple[str, Dict[str, str]]:
    name, stat_names, events = "", {}, []
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 4:
            events.append(_map_values(v))
        elif f == 5:
            meta = dict(_fields(_map_values(v)))
            stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
    ops: Dict[str, str] = {}
    for ev in events:
        ev_name, tf_op = "", None
        for f, v in _fields(ev):
            if f == 2:
                ev_name = v.decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != STAT:
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode()
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        if tf_op is not None:
            ops[ev_name] = tf_op
    return name, ops


def tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """{plane name: {event metadata name: tf_op}} for every plane whose
    events carry the stat."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, v in _fields(buf):
        if field == 1:
            name, ops = _plane(v)
            if ops:
                out[name] = ops
    return out
