"""Device time by the program's named scopes, and idle gaps by its spans.

A ``jax.named_scope`` reaches a profiler trace as the XLA op's ``op_name``
metadata, which the profiler keeps as a ``tf_op`` stat on the op's event
metadata (``jit(step)/chunk/while/body/attn/dot_general:``).
``jax.profiler.ProfileData`` does not expose event-metadata stats, so this
module decodes the few XSpace fields it needs from the protobuf wire format
itself, with no ``tensorflow`` or ``protobuf`` import: planes, their event
and stat metadata, and the stats on metadata and planes. It maps each
device op's full name to its ``tf_op``; a name that two ops share with
different scopes counts as unscoped (``ambiguous``).

``scope_split`` sums device self time (``trace_reduce.self_times``) over
the traced window at two levels: the step part (``chunk``, ``decode``) and
the layer part inside it (``chunk/attn``, ``chunk/ffn``, ...). ``other``
holds the ops under neither. ``idle_by_span`` charges the idle gaps to the
innermost host span of the benchmark (``bench.*``) or of the program
(``serve.*``, ``control.*``, ``engine.*``). ``profile_start_ns`` reads the
profile's start on the Unix epoch, the clock of the program's own spans
(``repro.obs.wall_us``).
"""
from __future__ import annotations

import collections
import struct
from pathlib import Path

import numpy as np

import trace_reduce

STEP_SCOPES = ("chunk", "decode")
LAYER_SCOPES = ("attn", "ffn")
SPAN_PREFIXES = ("bench.", "serve.", "control.", "engine.")


# ------------------------------------------------------------- wire format
def _varint(buf: bytes, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes):
    """(field number, value) of each field of one message; a varint is an
    int, a fixed64 its 8 bytes, a length-delimited field its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _stat(buf: bytes, names: dict) -> tuple:
    """XStat -> (name, value): double 2, uint64 3, int64 4, str 5, bytes 6,
    ref 7 (a ref points into the stat-metadata names)."""
    sid, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            sid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = names.get(v)
    return names.get(sid, str(sid)), value


def _map_entry(buf: bytes) -> tuple:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def planes(data: bytes) -> list:
    """[{"name", "stats", "events"}] of an XSpace, where ``events`` maps
    each event-metadata name to the list of its metadata's stats dicts."""
    out = []
    for f, plane in _fields(data):
        if f != 1:                       # XSpace.planes
            continue
        name, raw_meta, raw_stats, stat_names = "", [], [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = v.decode("utf-8", "replace")
            elif pf == 4:                # event_metadata map
                raw_meta.append(_map_entry(v)[1])
            elif pf == 5:                # stat_metadata map
                sid, meta = _map_entry(v)
                for mf, mv in _fields(meta):
                    if mf == 2:
                        stat_names[sid] = mv.decode("utf-8", "replace")
            elif pf == 6:
                raw_stats.append(v)
        events = collections.defaultdict(list)
        for meta in raw_meta:
            ev_name, stats = "", {}
            for mf, mv in _fields(meta):
                if mf == 2:
                    ev_name = mv.decode("utf-8", "replace")
                elif mf == 5:
                    k, val = _stat(mv, stat_names)
                    stats[k] = val
            events[ev_name].append(stats)
        out.append({"name": name, "events": dict(events),
                    "stats": dict(_stat(s, stat_names) for s in raw_stats)})
    return out


def read(path) -> list:
    return planes(Path(path).read_bytes())


# ------------------------------------------------------------------ scopes
def op_scopes(xplanes: list) -> tuple:
    """({full op name: tf_op path}, ambiguous) over the device planes. The
    path drops ``tf_op``'s trailing ``:type``; an op with no ``tf_op`` maps
    to "". A name whose metadata disagree on the path maps to None and is
    counted in ``ambiguous``."""
    seen = collections.defaultdict(set)
    for p in xplanes:
        if not trace_reduce._is_device(p["name"]):
            continue
        for name, metas in p["events"].items():
            for stats in metas:
                seen[name].add(str(stats.get("tf_op", "")).rsplit(":", 1)[0])
    out = {n: next(iter(paths)) if len(paths) == 1 else None
           for n, paths in seen.items()}
    return out, sum(v is None for v in out.values())


def scope_of(path) -> str:
    """``jit(f)/chunk/while/body/attn/dot_general`` -> ``chunk/attn``: the
    first step scope on the path, then the first layer scope after it;
    ``other`` when the path has no step scope (or is unknown)."""
    parts = (path or "").split("/")
    for i, p in enumerate(parts):
        if p in STEP_SCOPES:
            inner = next((q for q in parts[i + 1:] if q in LAYER_SCOPES), None)
            return f"{p}/{inner}" if inner else p
    return "other"


def profile_start_ns(xplanes: list) -> int | None:
    """The profile's start (``profile_start_time`` of the ``Task
    Environment`` plane) in nanoseconds on the Unix epoch."""
    for p in xplanes:
        if "profile_start_time" in p["stats"]:
            return int(p["stats"]["profile_start_time"])
    return None


def _window(pd) -> tuple:
    win = [(s, e) for s, e, n in trace_reduce.host_spans(pd)
           if n == trace_reduce.WINDOW_SPAN]
    if win:
        return win[0]
    ops = [o for v in device_events(pd).values() for o in v]
    return min(s for s, _, _ in ops), max(e for _, e, _ in ops)


def device_events(pd) -> dict:
    """{plane: [(start_ns, end_ns, full op name)]}, as ``trace_reduce``
    finds them but with XLA's whole op text as the name."""
    out = {}
    for plane in pd.planes:
        if not trace_reduce._is_device(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if line is not None:
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if ops:
                out[plane.name] = ops
    return out


def scope_split(pd, scopes: dict) -> dict:
    """Device self seconds in the traced window by scope, averaged over the
    devices: ``chunk`` and ``decode`` (each with its ``/attn`` and ``/ffn``
    parts), and ``other``. An op counts where ``trace_reduce.reduce``
    counts it: its start lies in the window."""
    lo, hi = _window(pd)
    ops = device_events(pd)
    total = collections.Counter({k: 0.0 for k in (*STEP_SCOPES, "other")})
    for plane_ops in ops.values():
        for s, _, name, own in trace_reduce.self_times(plane_ops):
            if lo <= s < hi:
                key = scope_of(scopes.get(name))
                sec = max(own, 0) * 1e-9
                total[key] += sec
                if "/" in key:
                    total[key.split("/")[0]] += sec
    n = max(len(ops), 1)
    return {k: v / n for k, v in sorted(total.items())}


def idle_by_span(pd, prefixes=SPAN_PREFIXES, top: int = 10) -> list:
    """[[span name, idle seconds]]: each gap between busy intervals in the
    traced window charged to the innermost (shortest) host span with one of
    ``prefixes`` that holds the gap's midpoint, else ``host.other``."""
    lo, hi = _window(pd)
    spans = [(s, e, n) for p in prefixes
             for s, e, n in trace_reduce.host_spans(pd, p)
             if n != trace_reduce.WINDOW_SPAN]
    starts = np.asarray([s for s, _, _ in spans], np.float64)
    ends = np.asarray([e for _, e, _ in spans], np.float64)
    gaps = collections.Counter()
    ops = device_events(pd)
    for plane_ops in ops.values():
        busy = trace_reduce._clip(
            trace_reduce.merge([(s, e) for s, e, _ in plane_ops]), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2], strict=True):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            dur = np.where((starts <= mid) & (mid <= ends), ends - starts, np.inf)
            i = int(np.argmin(dur)) if dur.size else -1
            name = spans[i][2] if i >= 0 and np.isfinite(dur[i]) else "host.other"
            gaps[name] += (g1 - g0) * 1e-9
    n = max(len(ops), 1)
    return [[k, v / n] for k, v in gaps.most_common(top)]
