"""Device time by ``jax.named_scope``, from a ``*.xplane.pb`` capture.

The scope path of a device op (``jit(fwd)/.../mamba_0/ssd_scan/...``)
is the ``tf_op`` stat of the op's event METADATA, which
``jax.profiler.ProfileData`` does not expose and ``harness/xplane.py``
therefore never sees. This module reads the file's protobuf wire format
itself (the seven messages of ``xplane.proto``, field numbers below), so
it needs nothing that is not installed with JAX.

Only leaf ops count: a ``while`` (a scanned run of layers), a
``conditional`` or a ``call`` is an event of its own AROUND its body's
events, and adding it would count the body twice.
"""

from __future__ import annotations

import re
from functools import lru_cache
from typing import Dict, Iterator, List, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
_CONTAINER = re.compile(r" (while|conditional|call)\(")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: varints as
    ints, length-delimited fields as bytes, fixed 32/64 as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf: bytes):
    """``(name, {stat id: stat name}, {event metadata id: (name, [stat
    (id, str, ref)])}, [(line name, [(metadata id, duration ps)])])``."""
    name, stat_names, metadata, lines = "", {}, {}, []
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode()
        elif field == 5:      # map<int64, XStatMetadata>: id=1, name=2
            key, sm = _map_entry(v)
            stat_names[key] = next(
                (x.decode() for f, _, x in _fields(sm) if f == 2), "")
        elif field == 4:      # map<int64, XEventMetadata>: name=2, stats=5
            key, em = _map_entry(v)
            em_name, stats = "", []
            for f, _, x in _fields(em):
                if f == 2:
                    em_name = x.decode(errors="replace")
                elif f == 5:  # XStat: metadata_id=1, str_value=5, ref=7
                    sid, text, ref = 0, None, None
                    for sf, _, sx in _fields(x):
                        if sf == 1:
                            sid = sx
                        elif sf == 5:
                            text = sx.decode(errors="replace")
                        elif sf == 7:
                            ref = sx
                    stats.append((sid, text, ref))
            metadata[key] = (em_name, stats)
        elif field == 3:      # XLine: name=2, events=4
            line_name, events = "", []
            for f, _, x in _fields(v):
                if f == 2:
                    line_name = x.decode()
                elif f == 4:  # XEvent: metadata_id=1, duration_ps=3
                    mid = dur = 0
                    for ef, _, ex in _fields(x):
                        if ef == 1:
                            mid = ex
                        elif ef == 3:
                            dur = ex
                    events.append((mid, dur))
            lines.append((line_name, events))
    return name, stat_names, metadata, lines


@lru_cache(maxsize=2)
def op_seconds(path: str) -> Tuple[Tuple[str, float], ...]:
    """``(scope path, seconds)`` of every leaf op event on the device
    planes' op lines; the path is ``""`` where an op carries none."""
    with open(path, "rb") as f:
        space = f.read()
    out: List[Tuple[str, float]] = []
    for field, _, v in _fields(space):
        if field != 1:
            continue
        name, stat_names, metadata, lines = _plane(v)
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        scope_of: Dict[int, str] = {}
        for mid, (em_name, stats) in metadata.items():
            if _CONTAINER.search(em_name):
                continue
            scope = ""
            for sid, text, ref in stats:
                if stat_names.get(sid) == "tf_op":
                    scope = text if text is not None \
                        else stat_names.get(ref, "")
            scope_of[mid] = scope
        for line_name, events in lines:
            if line_name != OP_LINE:
                continue
            out += [(scope_of[mid], dur * 1e-12) for mid, dur in events
                    if mid in scope_of]
    return tuple(out)


def seconds_under(path: str, patterns) -> float:
    """Device seconds of the ops whose scope path matches ANY of the
    regular expressions ``patterns``."""
    regs = [re.compile(p) for p in patterns]
    return sum(s for scope, s in op_seconds(path)
               if any(r.search(scope) for r in regs))


def by_named_part(path: str, parts: str) -> Dict[str, float]:
    """Device seconds by the named parts of each op's scope path: the
    path's components that match the regular expression ``parts``, their
    layer numbers dropped (``mamba_6/ssd_scan`` -> ``mamba/ssd_scan``);
    ops under none of them come under ``"(other)"``."""
    part = re.compile(parts)
    out: Dict[str, float] = {}
    for scope, s in op_seconds(path):
        names = [re.sub(r"_\d+$", "", c) for c in scope.split("/")
                 if part.fullmatch(c)]
        key = "/".join(names) or "(other)"
        out[key] = out.get(key, 0.0) + s
    return out
