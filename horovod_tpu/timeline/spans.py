"""Cross-rank span layer: tagged timing spans + per-step summaries.

Every host-side timing region in the exchange path funnels through the
process-wide :class:`SpanRecorder`: eager collective dispatch and fence
waits (``collectives/eager.py``), fused deferred-flush buckets, and the
jitted step's dispatch / dispatch-gap (``training._InstrumentedStep``).
Each span is tagged ``(rank, step, bucket_id, fuse_key, leg)`` and, when
a :class:`~horovod_tpu.timeline.Timeline` is attached, mirrored into the
Chrome-trace file so one rank's file already carries the attribution the
cross-rank merge needs.

In-jit exchange legs (``collectives/ops.py``, ``optim/zero.py``,
``optim/distributed.py``) cannot be host-timed span-by-span -- XLA owns
their schedule.  They register themselves at *trace time* via
:func:`note_leg` instead (the same host-side-effect idiom as
``optim/distributed._note_compression_ratio``: fires once per trace, so
retraces refresh it and cached executions cost nothing).  The registered
byte counts let the straggler report attribute a compiled step's
exchange time across legs proportionally.

Per step, the recorder folds its spans into a compact summary dict::

    {"rank": r, "step": s, "t0_us": <unix epoch us at dispatch start>,
     "wall_s": ..., "spans": {"dispatch": ..., "dispatch_gap": ...,
     "exchange": ..., "fence": ..., "bucket": ...}, "legs": {...}}

which feeds the :class:`~horovod_tpu.timeline.straggler.StragglerMonitor`
locally and, under ``HOROVOD_TRACE_SYNC=1``, the KV trace plane
(``timeline/sync.py``) for rank 0 to merge.

One funnel, three sinks.  :meth:`SpanRecorder.span` also KEEPS each span
-- name, start and end on ``time.perf_counter_ns``, its own id, the id
of the span that was open on the same thread when it began, and its
attributes -- in a bounded ring (:meth:`SpanRecorder.records`), and
enters a ``jax.profiler.TraceAnnotation("hvd." + name, **attrs)`` for
the same interval: while a profiler trace is on, the span lies in the
trace's host plane on the clock the device's operations are on, so a
device gap can be laid against what the host was doing.

The recorder also keeps what the ring cannot once it has wrapped: by
name, how many spans closed, their time and their SELF time (a span's
duration less its children's on the same thread), whole since
:meth:`SpanRecorder.reset` (:meth:`SpanRecorder.totals`); and how many
records it filed and how many of those the ring has pushed out
(``filed``, ``dropped``).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional

#: Span kinds a step decomposes into.  "dispatch" is the jitted-step
#: dispatch call; "dispatch_gap" the host time between consecutive
#: dispatches (input pipeline, Python glue, injected host delays);
#: "exchange" an eager collective execution; "fence" a blocking
#: device->host wait; "bucket" one fused deferred-flush unit;
#: "negotiate" trace+compile on an executable-cache miss.
SPAN_KINDS = ("dispatch", "dispatch_gap", "exchange", "fence", "bucket",
              "negotiate", "compute")

#: A phase of a host loop (the serve loop, a decode round and their
#: parts).  Phases nest -- a round holds its own dispatch -- so they are
#: kept in the record ring and the profiler's trace and left out of the
#: per-step sums, which would count the same time twice.
PHASE = "phase"

#: Per-step summaries kept in the ring buffer.
SUMMARY_RING = 64

#: Span records kept in the ring: a 30 s serve run files some 7,000.
RECORD_RING = 32768


class SpanRecord(NamedTuple):
    """One kept span.  ``parent`` is the id of the span that was open on
    the same thread when this one began (None for a root).  A record
    made by :meth:`SpanRecorder.file` is a point: ``start_ns ==
    end_ns``."""

    name: str
    start_ns: int            # time.perf_counter_ns
    end_ns: int
    id: int
    parent: Optional[int]
    attrs: dict


class OpenSpan:
    """A span that is open: a frame of its thread's stack, and what
    :meth:`SpanRecorder.span` yields.  ``child_ns`` is the time of its
    children that have closed so far."""

    __slots__ = ("id", "name", "start_ns", "child_ns")

    def __init__(self, id: int, name: str):
        self.id = id
        self.name = name
        self.start_ns = 0
        self.child_ns = 0

    def elapsed_ns(self) -> int:
        return time.perf_counter_ns() - self.start_ns


_trace_annotation = None


def _annotation(name: str, attrs: dict):
    """``jax.profiler.TraceAnnotation``, imported at the first span: a
    ``Timeline`` must be able to open before jax is imported at all."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation(name, **attrs)


class SpanRecorder:
    """Process-wide span sink; cheap enough to call per collective."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rank = 0
        self.timeline = None  # Optional[Timeline]
        self._step = 0
        # step -> {"spans": {kind: secs}, "tags": [...]}  (ring)
        self._acc: "OrderedDict[int, dict]" = OrderedDict()
        self.summaries: "OrderedDict[int, dict]" = OrderedDict()
        # trace-time leg registry: leg -> {"nbytes": n, "buckets": k}
        self.legs: Dict[str, dict] = {}
        self._listeners = []
        self._ring: "deque[SpanRecord]" = deque(maxlen=RECORD_RING)
        self._ids = itertools.count(1)
        self._open = threading.local()   # .stack: [OpenSpan, ...]
        # name -> [count, total_ns, self_ns] of the spans that closed
        self._totals: Dict[str, list] = {}
        #: Records filed, and those of them the ring has pushed out.
        self.filed = 0
        self.dropped = 0

    # -- wiring -----------------------------------------------------------
    def configure(self, rank: Optional[int] = None,
                  timeline=None) -> "SpanRecorder":
        with self._lock:
            if rank is not None:
                self.rank = int(rank)
            if timeline is not None:
                self.timeline = timeline
        return self

    def add_listener(self, fn) -> None:
        """``fn(summary_dict)`` called after every step boundary.
        Idempotent by identity (re-init must not double-feed)."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    # -- step clock -------------------------------------------------------
    def set_step(self, step: int) -> None:
        self._step = int(step)

    @property
    def step(self) -> int:
        return self._step

    def _bucket(self, step: int) -> dict:
        acc = self._acc.get(step)
        if acc is None:
            acc = self._acc[step] = {"spans": {}, "legs": {}}
            while len(self._acc) > SUMMARY_RING:
                self._acc.popitem(last=False)
        return acc

    # -- span emission ----------------------------------------------------
    def add(self, kind: str, dur_s: float, leg: Optional[str] = None,
            bucket_id: Optional[int] = None,
            fuse_key: Optional[str] = None, emit: bool = False) -> None:
        """Record a completed span of ``dur_s`` seconds at the current
        step (the non-contextmanager form, for callers that already
        timed the region themselves).  ``emit=True`` mirrors it into the
        attached timeline as a retroactive "X" event ending now -- used
        for regions with no begin/end pair of their own (the dispatch
        gap); callers whose region already has a timeline range must
        leave it False or the merge would double-count."""
        with self._lock:
            acc = self._bucket(self._step)
            acc["spans"][kind] = acc["spans"].get(kind, 0.0) + float(dur_s)
            if leg:
                lg = acc["legs"].setdefault(leg, {"secs": 0.0, "count": 0})
                lg["secs"] += float(dur_s)
                lg["count"] += 1
        if emit:
            tl = self.timeline
            if tl is not None:
                args = {"rank": self.rank, "step": self._step}
                if leg is not None:
                    args["leg"] = leg
                if bucket_id is not None:
                    args["bucket_id"] = int(bucket_id)
                if fuse_key is not None:
                    args["fuse_key"] = str(fuse_key)
                try:
                    tl.complete("spans", kind, dur_s, args=args)
                except Exception:
                    pass

    @contextlib.contextmanager
    def span(self, kind: str, name: str = "", leg: Optional[str] = None,
             bucket_id: Optional[int] = None,
             fuse_key: Optional[str] = None, **attrs):
        """Time a host region and tag it ``(rank, step, bucket_id,
        fuse_key, leg)``.  Three sinks: the per-step sum of ``kind``
        (not for :data:`PHASE`), the Chrome-trace timeline when one is
        attached (args carry the tags), and the record ring together
        with a ``TraceAnnotation("hvd." + (name or kind))`` in the
        profiler's trace.  ``attrs`` (``rid``, ``round``, ``slot``...:
        numbers or strings) go to the record and the annotation.  At
        its close the span adds to its name's :meth:`totals`.  Yields
        the :class:`OpenSpan`."""
        label = name or kind
        if leg is not None:
            attrs["leg"] = leg
        tl = self.timeline
        track = "phases" if kind == PHASE else (name or "spans")
        event = label if kind == PHASE else kind
        if tl is not None:
            args = dict(attrs, rank=self.rank, step=self._step)
            if bucket_id is not None:
                args["bucket_id"] = int(bucket_id)
            if fuse_key is not None:
                args["fuse_key"] = str(fuse_key)
            tl.begin(track, event, args=args)
        stack = self._stack()
        frame = OpenSpan(next(self._ids), label)
        parent = stack[-1] if stack else None
        stack.append(frame)
        t0 = frame.start_ns = time.perf_counter_ns()
        try:
            with _annotation("hvd." + label, attrs):
                yield frame
        finally:
            t1 = time.perf_counter_ns()
            stack.pop()
            if tl is not None:
                tl.end(track, event)
            if parent is not None:
                parent.child_ns += t1 - t0
            with self._lock:
                self._keep(SpanRecord(
                    label, t0, t1, frame.id,
                    None if parent is None else parent.id, attrs))
                total = self._totals.get(label)
                if total is None:
                    total = self._totals[label] = [0, 0, 0]
                total[0] += 1
                total[1] += t1 - t0
                total[2] += t1 - t0 - frame.child_ns
            if kind != PHASE:
                self.add(kind, (t1 - t0) / 1e9, leg=leg,
                         bucket_id=bucket_id, fuse_key=fuse_key)

    def phase(self, name: str, **attrs):
        """``span(PHASE, name=name, **attrs)``: a phase of a host loop."""
        return self.span(PHASE, name=name, **attrs)

    # -- the record ring --------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _keep(self, rec: SpanRecord) -> None:
        """Into the ring, under the lock."""
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
        self.filed += 1
        self._ring.append(rec)

    def file(self, name: str, under: Optional[str] = None,
             **attrs) -> SpanRecord:
        """File a point record (no interval, no annotation): what a
        layer knows only once something is over, such as a finished
        request's timestamps.  Its parent is the innermost span named
        ``under`` that is open on this thread (None where there is
        none), else the innermost open span."""
        stack = self._stack()
        if under is None:
            parent = stack[-1].id if stack else None
        else:
            parent = next((frame.id for frame in reversed(stack)
                           if frame.name == under), None)
        now = time.perf_counter_ns()
        rec = SpanRecord(name, now, now, next(self._ids), parent, attrs)
        with self._lock:
            self._keep(rec)
        return rec

    def records(self, name: Optional[str] = None,
                since_ns: Optional[int] = None) -> List[SpanRecord]:
        """The kept records, oldest first (a span is filed when it
        closes, so a parent follows its children): those named ``name``
        and begun at or after ``since_ns``, where given."""
        with self._lock:
            out = list(self._ring)
        return [r for r in out
                if (name is None or r.name == name)
                and (since_ns is None or r.start_ns >= since_ns)]

    def totals(self) -> Dict[str, tuple]:
        """``{name: (count, total_ns, self_ns)}`` over every span that
        has closed since :meth:`reset`, the ring's length regardless (a
        copy).  The self times of a closed span and all it held add up
        to its duration."""
        with self._lock:
            return {name: tuple(t) for name, t in self._totals.items()}

    # -- trace-time leg registry ------------------------------------------
    def note_leg(self, leg, nbytes: Optional[int] = None,
                 bucket_id: Optional[int] = None,
                 fuse_key: Optional[str] = None) -> None:
        """Register an in-jit exchange leg (called at TRACE time from
        inside jitted code -- a host side effect that fires once per
        trace, like ``_note_compression_ratio``).  The byte totals let
        the offline report split compiled-step exchange time across
        legs; they are per-trace wire payloads, not per-step timings.

        ``leg`` is either a plan-IR ``ExchangeLeg`` row (preferred: the
        tag AND byte count come from the plan, so the registry renders
        the IR verbatim) or a bare tag string.  All entry points -- this
        method and the module-level :func:`note_leg` -- normalize
        through :func:`_normalize_leg`, the single tag/byte derivation
        path."""
        leg, nbytes = _normalize_leg(leg, nbytes)
        with self._lock:
            lg = self.legs.setdefault(leg, {"nbytes": 0, "buckets": 0})
            lg["nbytes"] += int(nbytes)
            lg["buckets"] += 1
        tl = self.timeline
        if tl is not None:
            try:
                tl.counter(f"leg_bytes/{leg}", float(nbytes))
            except Exception:
                pass

    # -- step boundary ----------------------------------------------------
    def step_boundary(self, step: int, wall_s: float,
                      t0_unix_us: Optional[float] = None) -> dict:
        """Close step ``step``: fold accumulated spans into a summary,
        push it through the listeners (straggler monitor, KV publisher)
        and return it.  ``wall_s`` is the full step wall including the
        dispatch gap; ``t0_unix_us`` anchors the step on the wall clock
        for the cross-rank merge."""
        with self._lock:
            acc = self._acc.pop(step, {"spans": {}, "legs": {}})
            summary = {
                "rank": self.rank,
                "step": int(step),
                "t0_us": float(t0_unix_us if t0_unix_us is not None
                               else time.time() * 1e6),
                "wall_s": float(wall_s),
                "spans": {k: round(v, 9)
                          for k, v in sorted(acc["spans"].items())},
                "legs": {k: {"secs": round(v["secs"], 9),
                             "count": v["count"]}
                         for k, v in sorted(acc["legs"].items())},
            }
            self.summaries[step] = summary
            while len(self.summaries) > SUMMARY_RING:
                self.summaries.popitem(last=False)
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(summary)
            except Exception:  # observers must never break training
                pass
        return summary

    def reset(self) -> None:
        """Forget accumulated state (tests / re-init)."""
        with self._lock:
            self._step = 0
            self._acc.clear()
            self.summaries.clear()
            self.legs.clear()
            self._ring.clear()
            self._totals.clear()
            self.filed = self.dropped = 0
            self._listeners = []
            self.timeline = None
            self.rank = 0


def dominant_span(summary: dict) -> str:
    """The span kind that ate the most host time in a step summary
    (``"compute"`` when the dispatch dominates and nothing else is
    recorded -- on the scan-loop path the device work hides behind one
    dispatch)."""
    spans = summary.get("spans") or {}
    if not spans:
        return "compute"
    return max(spans.items(), key=lambda kv: kv[1])[0]


_recorder = SpanRecorder()


def recorder() -> SpanRecorder:
    """The process-wide :class:`SpanRecorder` singleton."""
    return _recorder


def _normalize_leg(leg, nbytes: Optional[int] = None):
    """THE tag-normalization path for leg registration.

    Accepts a plan-IR leg row (anything with ``.tag``/``.nbytes`` --
    ``controller.fusion.ExchangeLeg``) or a bare tag string.  When the
    caller passes an IR row and no byte override, the leg's planned wire
    bytes are recorded -- the registry then renders the IR verbatim and
    executor-emitted tags cannot drift from plan-rendered tags.  Both
    ``SpanRecorder.note_leg`` and the module-level :func:`note_leg`
    funnel through here (there is no second derivation)."""
    tag = getattr(leg, "tag", None)
    if tag is not None:
        if nbytes is None:
            nbytes = getattr(leg, "nbytes", 0)
        return str(tag), int(nbytes)
    return str(leg), int(nbytes if nbytes is not None else 0)


def note_leg(leg, nbytes: Optional[int] = None,
             bucket_id: Optional[int] = None,
             fuse_key: Optional[str] = None) -> None:
    """Module-level convenience for in-jit call sites (keeps the traced
    code's import surface to one function).  Delegates to the recorder
    method; tag normalization happens exactly once, in
    :func:`_normalize_leg`."""
    _recorder.note_leg(leg, nbytes=nbytes, bucket_id=bucket_id,
                       fuse_key=fuse_key)
