"""Minimal, dependency-free XPlane (.xplane.pb) reader.

jax.profiler writes device traces as XSpace protobufs
(tensorflow/tsl/profiler/protobuf/xplane.proto).  The stock readers need
the TensorFlow proto stubs — a multi-GB dependency this framework refuses
to require just to open its own trace files — so this module decodes the
wire format directly: the XSpace schema is tiny (planes > lines > events,
plus an id->name event-metadata map) and protobuf wire encoding is four
primitives (varint, fixed32/64, length-delimited).

Only the fields the profiler tooling consumes are decoded; unknown fields
are skipped by wire type, so schema growth upstream stays compatible.

    spaces = [parse_xspace_file(p) for p in find_xplane_files(trace_dir)]
    for plane in spaces[0].planes:
        for line in plane.lines:            # one device stream / host thread
            for ev in line.events:          # name, offset_ps, duration_ps
                ...
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List


# -- protobuf wire primitives -----------------------------------------------


def _varint(buf: bytes, i: int):
    """Returns (value, next_index).  Unsigned; int64 fields that need sign
    are reinterpreted by the caller."""
    shift = 0
    out = 0
    n = len(buf)
    while True:
        if i >= n:
            # a run killed mid-trace-write leaves a truncated file — the
            # postmortem input this parser exists for; name the condition
            raise ValueError("truncated varint (corrupt/truncated "
                             "xplane file)")
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7
        if shift > 70:
            raise ValueError("varint overflow (corrupt xplane file)")


def _signed(v: int) -> int:
    """Two's-complement reinterpretation of a 64-bit varint."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes.
    Length-delimited values come back as memoryview-compatible bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if wt == 0:  # varint
            v, i = _varint(buf, i)
        elif wt == 1:  # fixed64
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:  # length-delimited
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
            if len(v) != ln:
                raise ValueError("truncated field (corrupt/truncated "
                                 "xplane file)")
        elif wt == 5:  # fixed32
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} "
                             "(corrupt xplane file)")
        if i > n:
            raise ValueError("truncated field (corrupt/truncated "
                             "xplane file)")
        yield field, wt, v


# -- schema (the slice of xplane.proto we read) ------------------------------


class XEvent:
    __slots__ = ("name", "metadata_id", "offset_ps", "duration_ps",
                 "raw_stats", "stats", "meta_stats")

    def __init__(self):
        self.name = ""
        self.metadata_id = 0
        self.offset_ps = 0
        self.duration_ps = 0
        # (stat_metadata_id, value, is_ref) triples, resolved into
        # `stats` once the owning plane's stat-metadata map is known
        self.raw_stats: List[tuple] = []
        self.stats: Dict[str, object] = {}
        # the stats of the event's METADATA, shared by every event of
        # that metadata (read, never written): a device op's `tf_op`
        # (the HLO op_name: jit(..)/while/body/<op type>/..), `source`,
        # `hlo_category`, `flops`, `bytes_accessed` live here
        self.meta_stats: Dict[str, object] = {}


class XLine:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self):
        self.name = ""
        self.timestamp_ns = 0
        self.events: List[XEvent] = []


class XPlane:
    __slots__ = ("name", "lines", "warnings")

    def __init__(self):
        self.name = ""
        self.lines: List[XLine] = []
        # named skip-with-warning notes from tolerant parsing (newer
        # libtpu dumps: unknown plane content, missing stat metadata)
        self.warnings: List[str] = []


class XSpace:
    __slots__ = ("planes", "warnings")

    def __init__(self):
        self.planes: List[XPlane] = []
        self.warnings: List[str] = []


def _parse_stat(buf: bytes):
    """XStat: returns (metadata_id, value, is_ref) — value oneof double/
    uint64/int64/str/bytes/ref (a ref indexes the plane's stat-metadata
    name table)."""
    import struct

    mid, val, is_ref = 0, None, False
    for f, wt, v in _fields(buf):
        if f == 1 and wt == 0:
            mid = _signed(v)
        elif f == 2 and wt == 1:  # double_value
            val = struct.unpack("<d", v)[0]
        elif f == 3 and wt == 0:  # uint64_value
            val = v
        elif f == 4 and wt == 0:  # int64_value
            val = _signed(v)
        elif f == 5 and wt == 2:  # str_value
            val = v.decode("utf-8", "replace")
        elif f == 6 and wt == 2:  # bytes_value
            val = bytes(v)
        elif f == 7 and wt == 0:  # ref_value
            val, is_ref = v, True
    return mid, val, is_ref


def _parse_event(buf: bytes) -> XEvent:
    ev = XEvent()
    for f, wt, v in _fields(buf):
        if f == 1 and wt == 0:
            ev.metadata_id = v
        elif f == 2 and wt == 0:  # offset_ps (oneof data)
            ev.offset_ps = _signed(v)
        elif f == 3 and wt == 0:
            ev.duration_ps = _signed(v)
        elif f == 4 and wt == 2:  # stats
            ev.raw_stats.append(_parse_stat(v))
    return ev


def _parse_line(buf: bytes) -> XLine:
    ln = XLine()
    for f, wt, v in _fields(buf):
        if f == 2 and wt == 2:
            ln.name = v.decode("utf-8", "replace")
        elif f == 3 and wt == 0:
            ln.timestamp_ns = _signed(v)
        elif f == 4 and wt == 2:
            ln.events.append(_parse_event(v))
        elif f == 11 and wt == 2 and not ln.name:  # display_name fallback
            ln.name = v.decode("utf-8", "replace")
    return ln


def _parse_event_metadata(buf: bytes):
    """XEventMetadata: returns (id, name, raw stats)."""
    mid, name, display, raw = 0, "", "", []
    for f, wt, v in _fields(buf):
        if f == 1 and wt == 0:
            mid = _signed(v)
        elif f == 2 and wt == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3 and wt == 2:
            display = v.decode("utf-8", "replace")
        elif f == 5 and wt == 2:  # stats
            raw.append(_parse_stat(v))
    return mid, (display or name), raw


def _parse_stat_metadata(buf: bytes):
    """XStatMetadata: returns (id, name)."""
    mid, name = 0, ""
    for f, wt, v in _fields(buf):
        if f == 1 and wt == 0:
            mid = _signed(v)
        elif f == 2 and wt == 2:
            name = v.decode("utf-8", "replace")
    return mid, name


def _map_entry(buf: bytes):
    """One map<int64, Msg> entry: returns (key, value_bytes)."""
    key, val = 0, None
    for mf, mwt, mv in _fields(buf):
        if mf == 1 and mwt == 0:
            key = _signed(mv)
        elif mf == 2 and mwt == 2:
            val = mv
    return key, val


def _parse_plane(buf: bytes) -> XPlane:
    plane = XPlane()
    meta: Dict[int, str] = {}
    meta_raw: Dict[int, list] = {}
    stat_meta: Dict[int, str] = {}
    for f, wt, v in _fields(buf):
        if f == 2 and wt == 2:
            plane.name = v.decode("utf-8", "replace")
        elif f == 3 and wt == 2:
            # newer dumps may carry line/event content this reader does
            # not model: skip THE LINE with a named warning, keep the
            # plane (postmortem traces must not die on one bad stream)
            try:
                plane.lines.append(_parse_line(v))
            except ValueError as e:
                plane.warnings.append(
                    f"plane {plane.name or '?'}: skipping unparseable "
                    f"line #{len(plane.lines)}: {e}")
        elif f == 4 and wt == 2:
            # map<int64, XEventMetadata>: entries are {1: key, 2: value}
            key, val = _map_entry(v)
            if val is not None:
                mid, name, raw = _parse_event_metadata(val)
                meta[key or mid] = name
                if raw:
                    meta_raw[key or mid] = raw
        elif f == 5 and wt == 2:
            # map<int64, XStatMetadata> — stat name table
            key, val = _map_entry(v)
            if val is not None:
                mid, name = _parse_stat_metadata(val)
                stat_meta[key or mid] = name
    missing_stats = set()

    def resolve(raw_stats, into):
        for mid, val, is_ref in raw_stats:
            # a stat (or ref target) whose metadata entry is absent
            # from this dump is SKIPPED by name, never a KeyError —
            # newer libtpu versions add stat types freely
            sname = stat_meta.get(mid)
            if sname is None:
                missing_stats.add(mid)
                continue
            if is_ref:
                if val not in stat_meta:
                    missing_stats.add(val)
                    continue
                val = stat_meta[val]
            into[sname] = val
        return into

    meta_stats = {mid: resolve(raw, {}) for mid, raw in meta_raw.items()}
    no_stats: Dict[str, object] = {}
    for line in plane.lines:
        for ev in line.events:
            ev.name = meta.get(ev.metadata_id, f"op#{ev.metadata_id}")
            ev.meta_stats = meta_stats.get(ev.metadata_id, no_stats)
            resolve(ev.raw_stats, ev.stats)
    for mid in sorted(missing_stats):
        plane.warnings.append(
            f"plane {plane.name or '?'}: skipping stat(s) with missing "
            f"stat-metadata entry #{mid}")
    return plane


def parse_xspace(buf: bytes) -> XSpace:
    """Decode one XSpace.  Tolerant by construction: unknown fields skip
    by wire type, and a plane whose contents this reader cannot decode
    (an unknown plane type from a newer libtpu) is dropped with a NAMED
    warning on `space.warnings` (+ one log line) instead of poisoning
    the whole trace."""
    space = XSpace()
    idx = 0
    for f, wt, v in _fields(buf):
        if f == 1 and wt == 2:
            try:
                plane = _parse_plane(v)
            except ValueError as e:
                msg = f"skipping unparseable plane #{idx}: {e}"
                space.warnings.append(msg)
                from .log import warning

                warning("xplane: %s", msg)
                idx += 1
                continue
            space.planes.append(plane)
            space.warnings.extend(plane.warnings)
            idx += 1
    return space


def parse_xspace_file(path: str) -> XSpace:
    with open(path, "rb") as f:
        return parse_xspace(f.read())


def find_xplane_files(trace_dir: str) -> List[str]:
    """The .xplane.pb files of a jax.profiler trace directory (tensorboard
    layout: <dir>/plugins/profile/<run>/<host>.xplane.pb)."""
    return sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True))


def is_device_plane(name: str) -> bool:
    """Device planes hold per-chip op streams ('/device:TPU:0' etc.);
    everything else ('/host:CPU', 'Task Environment', ...) is host-side."""
    return name.startswith("/device:")
