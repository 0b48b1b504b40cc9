"""What ``jax.profiler.ProfileData`` does not expose of an ``.xplane.pb``:
the stats that a plane's *event metadata* holds.

On a TPU a device operation's event carries only its times; its
framework op (``tf_op``: JAX's name stack, such as
``jit(decode_step)/while/body/closed_call/q_proj/adapted_dense/...``)
lives in the metadata the event points to.  This module decodes the
protobuf wire format itself, for the few fields it needs, so the
benchmark reads it wherever JAX runs (no xplane ``*_pb2`` module is
assumed).  ``bench/tests/test_bench_scopes.py`` reads the program's
recorded scopes with it; ``bench/trace.py`` does not call it yet.

Fields read (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes (1);
XPlane.name (2), lines (3), event_metadata (4, a map), stat_metadata (5,
a map); XLine.name (2), events (4); XEvent.metadata_id (1);
XEventMetadata.name (2), stats (5); XStat.metadata_id (1),
uint64_value (3), int64_value (4), str_value (5), ref_value (7);
XStatMetadata.name (2).
"""

from __future__ import annotations

import re
from typing import Iterator

# a program's metadata name on a device plane: "<module>(<program id>)"
_MODULE = re.compile(r"(.+)\((\d+)\)")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[tuple[int, object]]:
    """(field number, value) of each field of one message: an int for
    varint and fixed-width fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = bytes(buf[i:i + size]), i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _text(v: bytes) -> str:
    return v.decode("utf-8", "replace")


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _metadata(buf: bytes, stat_names: dict) -> tuple[str, dict]:
    """An event metadata's name and its stats by name."""
    name, stats = "", {}
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 5:
            sid, value = None, None
            for sn, sv in _fields(v):
                if sn == 1:
                    sid = sv
                elif sn in (3, 4):
                    value = sv
                elif sn == 5:
                    value = _text(sv)
                elif sn == 7:
                    value = stat_names.get(sv, "")
            stats[stat_names.get(sid, "")] = value
    return name, stats


def _name_stack(tf_op: str) -> str:
    """``tf_op`` without its ``:<type>`` suffix (empty for JAX ops)."""
    if ":" in tf_op.rsplit("/", 1)[-1]:
        return tf_op[:tf_op.rindex(":")]
    return tf_op


def op_scopes(path: str, plane_filter) -> dict:
    """The scope of each event of each line of the planes whose name
    passes ``plane_filter``, in file order: ``{(plane name, line name):
    [scope, ...]}``.  An operation's scope is its name stack (its
    metadata's ``tf_op``); where it has none (an asynchronous copy, a
    program's own event) it is the name of the program it belongs to
    (its ``program_id``), else ""."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for n, v in fields if n == 2), "")
        if not plane_filter(name):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                k, m = _map_entry(v)
                stat_names[k] = _text(dict(_fields(m)).get(2, b""))
        meta = {k: _metadata(m, stat_names)
                for k, m in (_map_entry(v) for n, v in fields if n == 4)}
        lines = [v for n, v in fields if n == 3]
        modules = {}
        for mname, _ in meta.values():
            m = _MODULE.fullmatch(mname)
            if m:
                modules[int(m.group(2))] = m.group(1)
        scope = {}
        for k, (_, stats) in meta.items():
            tf_op = stats.get("tf_op")
            scope[k] = (_name_stack(tf_op) if tf_op
                        else modules.get(stats.get("program_id"), ""))
        for line in lines:
            lname, scopes = "", []
            for ln, lv in _fields(line):
                if ln == 2:
                    lname = _text(lv)
                elif ln == 4:
                    mid = next((v for n, v in _fields(lv) if n == 1), 0)
                    scopes.append(scope.get(mid, ""))
            out[(name, lname)] = scopes
    return out
