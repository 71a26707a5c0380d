"""The source scope of each device operation in a profiler trace.

`xplane.py` reads the trace through `jax.profiler.ProfileData`, whose
events carry the operation's HLO text but not the metadata XLA keeps
beside it. That metadata holds `tf_op`, the JAX name stack of the
operation's source, `jit(<program>)/.../<scope>/<primitive>`, in which
every `jax.named_scope` the operation was traced under appears.
`op_scopes` reads it from the `.xplane.pb` file itself, a protobuf
`XSpace` (tsl/profiler/protobuf/xplane.proto), decoding only the fields it
needs:

    XSpace.planes = 1
    XPlane: name = 2, event_metadata = 4 (map int64 -> XEventMetadata),
            stat_metadata = 5 (map int64 -> XStatMetadata)
    XEventMetadata: name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, str_value = 5, ref_value = 7

An event of the "XLA Ops" line is named after its metadata, so the names
`op_scopes` returns are the names `xplane.reduce_trace` keys its op times
by (after the program's name and a slash, where the event carries one).
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, Tuple

from xplane import DEVICE_PLANE, find_xplane

TF_OP = "tf_op"


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview of the bytes for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield number, value


def _map_entry(buf) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane_scopes(plane) -> Dict[str, str]:
    name, events, stats = "", [], {}
    for number, v in fields(plane):
        if number == 2:
            name = bytes(v).decode()
        elif number == 4:
            events.append(_map_entry(v)[1])
        elif number == 5:
            for n, sv in fields(_map_entry(v)[1]):
                if n == 1:
                    sid = sv
                elif n == 2:
                    stats[bytes(sv).decode()] = sid
    if not DEVICE_PLANE.match(name) or TF_OP not in stats:
        return {}
    tf_op = stats[TF_OP]
    ref_names = {sid: n for n, sid in stats.items()}
    out = {}
    for ev in events:
        op_name, scope = None, None
        for number, v in fields(ev):
            if number == 2:
                op_name = bytes(v).decode(errors="replace")
            elif number == 5:
                stat = dict(fields(v))
                if stat.get(1) == tf_op:
                    if 5 in stat:
                        scope = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        scope = ref_names.get(stat[7])
        if op_name and scope:
            out[op_name] = scope
    return out


def op_scopes(trace_dir) -> Dict[str, str]:
    """{operation name: its `tf_op` name stack} over the device planes of
    the trace under `trace_dir`; operations without one are left out."""
    out: Dict[str, str] = {}
    for number, plane in fields(find_xplane(trace_dir).read_bytes()):
        if number == 1:
            out.update(_plane_scopes(plane))
    return out


def time_in_scope(trace, scopes: Dict[str, str], scope: str) -> Tuple[float, int]:
    """(seconds, calls) of the reduced trace's operations whose name stack
    holds `scope` as a whole component."""
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    secs = calls = 0
    for key, s in trace.op_s.items():
        stack = scopes.get(key) or scopes.get(key.split("/", 1)[-1], "")
        if rx.search(stack):
            secs += s
            calls += trace.op_calls[key]
    return secs, calls


def busy_share(f, scope: str):
    """Percent of the traced window's busy time spent in operations under
    `scope`, from a reader's facts (`trace`, `op_scopes`); None where the
    trace has no device time or no operation under it."""
    scopes = getattr(f, "op_scopes", None)
    if not scopes or not f.trace.devices or not f.trace.busy_s:
        return None
    secs, calls = time_in_scope(f.trace, scopes, scope)
    return secs / f.trace.busy_s * 100.0 if calls else None
