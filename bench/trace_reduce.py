"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy: the union of the intervals in which an XLA op ran on a device,
  clipped to the traced window and averaged over the devices;
* the device ops that took the most self time (an op's time less the ops
  nested in it, such as a layer scan's body), under XLA's op names with
  their result types;
* the idle gaps between busy intervals, each charged to the innermost
  benchmark host span (``bench.*``, from ``jax.profiler.TraceAnnotation``)
  that holds the gap's midpoint, or to ``host.other``.

The window is the host span ``bench.traced`` when the trace has one, else
the extent of the device ops.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name \
        and "NON_CORE" not in plane_name


def op_name(text: str, width: int = 96) -> str:
    """``%fusion.12 = bf16[16,2048]{...} fusion(...)`` -> the op's name and
    result type, without layouts, cut to ``width`` characters."""
    name, _, rest = text.partition(" = ")
    kind = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0] if rest else "")
    return f"{name} {kind}".strip()[:width]


def device_ops(pd) -> dict:
    """{plane name: [(start_ns, end_ns, name), ...]} of XLA ops."""
    out = {}
    for plane in pd.planes:
        if not _is_device(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if line is None:
            continue
        ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
               for e in line.events]
        if ops:
            out[plane.name] = ops
    return out


def self_times(ops: list) -> list:
    """[(start, end, name, self_ns)]: each op's time less that of the ops
    nested inside it (XLA lists a while loop and its body's ops alike)."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out, stack = [], []
    for s, e, n in ops:
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, n, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append(rec)
        out.append(rec)
    return [tuple(r) for r in out]


def host_spans(pd, prefix: str = SPAN_PREFIX) -> list:
    """[(start_ns, end_ns, name)] of host events whose name has ``prefix``."""
    spans = []
    for plane in pd.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return spans


def merge(intervals: list) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(pd, top: int = 10) -> dict:
    """Busy and window seconds, top device ops and idle gaps by host span."""
    ops = device_ops(pd)
    if not ops:
        raise ValueError("the trace holds no device ops")
    spans = host_spans(pd)
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for v in ops.values() for s, _, _ in v)
        hi = max(e for v in ops.values() for _, e, _ in v)
    inner = sorted((s, e, n) for s, e, n in spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in inner]

    def holder(t) -> str:
        # the benchmark's spans sit side by side (one per call into a
        # layer): look back over the few that start before ``t``
        i = bisect.bisect_right(starts, t)
        held = [(e - s, n) for s, e, n in inner[max(0, i - 8):i] if e >= t]
        return min(held)[1] if held else "host.other"

    busy_ns, per_op = [], collections.Counter()
    gaps = collections.Counter()
    for plane_ops in ops.values():
        for s, e, name, own in self_times(plane_ops):
            if lo <= s < hi:
                per_op[name] += max(own, 0) * 1e-9
        busy = _clip(merge([(s, e) for s, e, _ in plane_ops]), lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2], strict=True):
            if g1 <= g0:
                continue
            gaps[holder((g0 + g1) / 2)] += (g1 - g0) * 1e-9
    n_dev = len(ops)
    return {
        "busy_s": sum(busy_ns) * 1e-9 / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_dev,
        "device_ops": [[k, v / n_dev] for k, v in per_op.most_common(top)],
        "idle_gaps": [[k, v / n_dev] for k, v in gaps.most_common(top)],
    }
