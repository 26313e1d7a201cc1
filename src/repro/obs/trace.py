"""Request-lifecycle tracing: bounded ring buffer -> Chrome-trace JSON.

``TraceRecorder`` collects structured events from the serving hot paths:
lifecycle points (arrival, admission, chunk, activation, preemption,
requeue, raced_hit, route, retirement) keyed by request id and control
slot, and host spans (``span()``) at the serve-loop, scheduler and engine
boundaries. Events are stored as tuples in a preallocated ring (oldest
dropped on overflow, counted), so steady-state recording is an index bump
plus a tuple build.

One clock: ``now()`` is microseconds on the Unix epoch (``time.time_ns``),
the clock the profiler's ``TraceMe`` stamps host events with. Each span also
opens a ``jax.profiler.TraceAnnotation`` of its kind, so inside an active
``jax.profiler`` trace it lands beside the device ops; a ring timestamp
minus the profile's ``profile_start_time`` is the profile's own offset.
Spans nest in time: the enclosing span is the parent.

Disabled tracing must be free: engines hold a ``NullRecorder`` by default;
its ``span()`` hands back one shared no-op context and every ``emit`` site
is guarded by ``if recorder.enabled``, so the hot path pays one branch or
one ``with`` on a constant (the overhead budget tests/test_observability.py
asserts).

``chrome_trace()`` exports the Chrome trace event format (Perfetto opens
it directly): ``pid`` = replica, ``tid`` = engine row (so each slot/row is
one timeline lane), complete events ("X") for spans, instant events ("i")
for lifecycle points. Timestamps are the epoch microseconds as recorded;
``slot`` (control-slot index) rides in ``args`` — the timeline-reading
guide is DESIGN.md §11.
"""
from __future__ import annotations

import json
import time

from jax.profiler import TraceAnnotation

# Event kinds the runtime emits (DESIGN.md §11 event schema). Not enforced
# at emit time — the recorder is generic — but tests pin the runtime to it.
EVENT_KINDS = (
    "arrival",       # request entered an engine's pending queue
    "admission",     # engine claimed a row for the request
    "chunk",         # one prompt chunk entered the mixed dispatch
    "activation",    # final chunk shipped; first token computed on device
    "preemption",    # active/mid-prefill request bounced back to pending
    "requeue",       # fleet moved the request off a failed/drained replica
    "raced_hit",     # prefix-cache hit degraded by a concurrent eviction
    "route",         # fleet router picked a replica for the request
    "retirement",    # request finished; row freed
    # host spans (``span()``), each a complete event with ``dur``
    "serve.slot",        # one iteration of the serve loop
    "control.decide",    # Algorithm 1's decision for the slot
    "control.admit",     # the scheduler hands polled requests to the engine
    "engine.consume",    # one readback packet consumed (retirements)
    "engine.wait",       # inside engine.consume: the host waits on the device
    "engine.admit",      # chunked admission: rows claimed for pending requests
    "engine.plan",       # the slot's chunk plan (with its preempt-and-replan)
    "engine.pages",      # page growth and COW fork flushes
    "engine.upload",     # the step's host->device arguments
    "engine.dispatch",   # enqueue of one jitted dispatch
    "engine.readback",   # the async device->host copy is initiated
)


def wall_us() -> float:
    """Microseconds on the Unix epoch: the profiler's host clock."""
    return time.time_ns() / 1e3


class _Span:
    """One live span: a ``TraceAnnotation`` while open, one complete event
    in the ring when closed. ``set(**args)`` adds args known only at the
    end (a consume's retirement count)."""

    __slots__ = ("_rec", "_kind", "_ids", "_args", "_ts", "_ann")

    def __init__(self, rec, kind: str, ids: tuple, args: dict):
        self._rec, self._kind, self._ids, self._args = rec, kind, ids, args

    def set(self, **args) -> None:
        self._args.update(args)

    def __enter__(self):
        self._ann = TraceAnnotation(self._kind)
        self._ann.__enter__()
        self._ts = wall_us()
        return self

    def __exit__(self, *exc):
        end = wall_us()
        self._ann.__exit__(*exc)
        slot, rid, row, pid = self._ids
        self._rec.emit(self._kind, slot=slot, rid=rid, row=row, pid=pid,
                       ts=self._ts, dur=end - self._ts, **self._args)
        return False


class _NullSpan:
    """The disabled recorder's one shared span: does nothing."""

    __slots__ = ()

    def set(self, **args) -> None:
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class TraceRecorder:
    """Bounded ring buffer of (kind, slot, rid, row, pid, ts, dur, args)."""

    enabled = True

    def __init__(self, capacity: int = 65536):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._buf: list = [None] * capacity
        self._head = 0          # next write index
        self._count = 0         # live events (<= capacity)
        self.dropped = 0        # overwritten events

    def now(self) -> float:
        """Microseconds on the Unix epoch (see ``wall_us``)."""
        return wall_us()

    def span(self, kind: str, *, slot=None, rid=None, row=None, pid: int = 0,
             **args):
        """Context manager timing one host span of ``kind`` (ring + profiler
        annotation); ``slot``/``rid``/``row``/``pid`` as ``emit`` takes them."""
        return _Span(self, kind, (slot, rid, row, pid), args)

    def emit(self, kind: str, *, slot=None, rid=None, row=None, pid: int = 0,
             ts=None, dur=None, **args) -> None:
        if ts is None:
            ts = self.now()
        i = self._head
        if self._buf[i] is not None:
            self.dropped += 1
        else:
            self._count += 1
        self._buf[i] = (kind, slot, rid, row, pid, ts, dur,
                        args if args else None)
        self._head = (i + 1) % self.capacity

    def __len__(self) -> int:
        return self._count

    def events(self) -> list:
        """Live events as dicts, oldest first."""
        out = []
        start = (self._head - self._count) % self.capacity
        for j in range(self._count):
            kind, slot, rid, row, pid, ts, dur, args = (
                self._buf[(start + j) % self.capacity])
            e = {"kind": kind, "slot": slot, "rid": rid, "row": row,
                 "pid": pid, "ts": ts}
            if dur is not None:
                e["dur"] = dur
            if args:
                e.update(args)
            out.append(e)
        return out

    def clear(self) -> None:
        self._buf = [None] * self.capacity
        self._head = self._count = 0
        self.dropped = 0

    # --------------------------------------------------------- exports
    def chrome_trace(self) -> dict:
        """Chrome trace event format (Perfetto/about:tracing-compatible)."""
        events = []
        pids, lanes = set(), set()
        for e in self.events():
            pid = int(e["pid"] or 0)
            tid = int(e["row"]) if e.get("row") is not None else 0
            pids.add(pid)
            lanes.add((pid, tid))
            args = {k: v for k, v in e.items()
                    if k not in ("kind", "pid", "ts", "dur") and v is not None}
            name = e["kind"]
            if e.get("what"):
                name = f"{name}:{e['what']}"
            ev = {"name": name, "cat": e["kind"], "pid": pid, "tid": tid,
                  "ts": float(e["ts"]), "args": args}
            if "dur" in e:
                ev["ph"] = "X"
                ev["dur"] = float(e["dur"])
            else:
                ev["ph"] = "i"
                ev["s"] = "t"   # instant scoped to its thread lane
            events.append(ev)
        meta = [{"name": "process_name", "ph": "M", "pid": p, "tid": 0,
                 "args": {"name": f"replica {p}"}} for p in sorted(pids)]
        meta += [{"name": "thread_name", "ph": "M", "pid": p, "tid": t,
                  "args": {"name": f"row {t}"}} for p, t in sorted(lanes)]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


class NullRecorder(TraceRecorder):
    """Disabled recorder: ``enabled`` is False, every emit is a no-op and
    every span is the one shared no-op context.

    Hot paths check ``enabled`` before building event kwargs, so a disabled
    engine pays one branch per site; ``emit`` still being callable keeps
    unguarded cold-path sites (shutdown, drains) safe.
    """

    enabled = False

    def __init__(self):
        super().__init__(capacity=1)

    def emit(self, kind: str, **kw) -> None:  # noqa: ARG002 — deliberate no-op
        return None

    def span(self, kind: str, **kw):  # noqa: ARG002 — deliberate no-op
        return _NULL_SPAN


NULL_TRACE = NullRecorder()
