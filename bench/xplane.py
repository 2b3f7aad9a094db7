"""What a device op was scoped as, read from the ``.xplane.pb`` itself.

``jax.profiler.ProfileData`` gives each event its name and times but not
the stats of its event metadata, and those hold the op's ``tf_op``: the
op-name path JAX gave it, ``jit(<program>)/.../<scope>/.../<primitive>``,
which carries the program's ``jax.named_scope``s (the recorded probe's
shuffle kernel reads ``jit(bucketed_shuffle_pallas)/pallas_call:``).  This
is a small reader of the protobuf wire format for just that part of an
XSpace: for each device plane, each op's metadata name with its
``program_id`` and ``tf_op``.  The plane's lines, which hold the events,
are skipped whole.

The fields read (``tsl/profiler/protobuf/xplane.proto``)::

    XSpace          planes = 1
    XPlane          name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    XEventMetadata  name = 2, stats = 5
    XStat           metadata_id = 1, uint64 = 3, int64 = 4, str = 5, ref = 7
    XStatMetadata   name = 2
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of each field of the message in ``buf[lo:hi]``:
    an int for varints, ``(start, end)`` for length-delimited fields, the
    raw bytes for fixed-width ones."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield field, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span: Tuple[int, int]) -> Tuple[int, int]:
    """The value of one map entry (key = 1, value = 2)."""
    for field, value in _fields(buf, *span):
        if field == 2:
            return value
    return (span[1], span[1])


@dataclasses.dataclass(frozen=True)
class OpInfo:
    program_id: int   # 0 where the metadata names none
    tf_op: str        # "" where it carries none


def _plane(buf: bytes, span: Tuple[int, int]):
    name, metas, stat_names = "", [], {}
    for field, value in _fields(buf, *span):
        if field == 2:
            name = _text(buf, value)
        elif field == 4:
            metas.append(_map_values(buf, value))
        elif field == 5:
            sid, sname = None, ""
            for f, v in _fields(buf, *_map_values(buf, value)):
                if f == 1:
                    sid = v
                elif f == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
    return name, metas, stat_names


def _op_info(buf: bytes, span: Tuple[int, int], stat_names: Dict
             ) -> Tuple[str, OpInfo]:
    name, program_id, tf_op = "", 0, ""
    for field, value in _fields(buf, *span):
        if field == 2:
            name = _text(buf, value)
        elif field == 5:
            stat = dict(_fields(buf, *value))
            key = stat_names.get(stat.get(1))
            if key == "program_id":
                program_id = stat.get(3, stat.get(4, 0))
            elif key == "tf_op":
                if 5 in stat:
                    tf_op = _text(buf, stat[5])
                elif 7 in stat:   # a reference to a stat name
                    tf_op = stat_names.get(stat[7], "")
    return name, OpInfo(program_id, tf_op)


def op_scopes(path: str) -> Dict[int, Dict[str, List[OpInfo]]]:
    """For each TPU chip of the trace at ``path``: each op name (the HLO
    text the trace names its events by) with what its metadata records,
    one entry per program that ran an op of that name."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[int, Dict[str, List[OpInfo]]] = {}
    for field, value in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, stat_names = _plane(buf, value)
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        ops: Dict[str, List[OpInfo]] = {}
        for span in metas:
            op_name, info = _op_info(buf, span, stat_names)
            ops.setdefault(op_name, []).append(info)
        out[int(m.group(1))] = ops
    return out


def scope_of(tf_op: str) -> str:
    """The scope path of a ``tf_op``: without the op type after ``:``."""
    return tf_op.split(":", 1)[0]
