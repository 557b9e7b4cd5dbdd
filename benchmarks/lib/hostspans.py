"""The program's own host spans in a traced run, on the device's clock.

The program enters a ``jax.profiler.TraceAnnotation("hvd.<name>", ...)``
for every span it records (``horovod_tpu/timeline/spans.py``), so while
the profiler is on its spans lie in the xplane file's host plane beside
the device's operations.  This module loads them, with their stats, and
lays the first chip's idle time against them: every idle nanosecond goes
to the innermost ``hvd.`` span that covers it on the host thread that
recorded the most span time (the loop that drives the device), or to
``None`` where no span does.  ``xplane.HOST_PREFIX`` stays ``"bench."``:
the result line's breakdown reads the benchmark's own annotations.
"""

import dataclasses
import functools
import os
from typing import Dict, Iterable, List, Optional, Tuple

from . import xplane

PREFIX = "hvd."
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class Span(xplane.Event):
    """A host event of the program's: ``name`` without the prefix, and
    the annotation's attributes."""

    stats: dict = dataclasses.field(default_factory=dict)


def trace_path(cell: str) -> str:
    """The traced run's xplane file, where ``run.py`` writes and finds it
    (it is still there while the readers run)."""
    return xplane.find_xplane(os.path.join(ROOT, ".bench_trace", cell))


@functools.lru_cache(maxsize=2)     # several readers read one run's file
def load(path: str) -> List[List[Span]]:
    """The ``hvd.`` events of each host thread that has any, by start,
    an enclosing span before what it holds."""
    from jax.profiler import ProfileData
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(str(e.name)[len(PREFIX):], int(e.start_ns),
                          int(e.start_ns) + int(e.duration_ns),
                          dict(e.stats))
                     for e in line.events
                     if str(e.name).startswith(PREFIX)]
            if spans:
                spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
                threads.append(spans)
    return threads


def of_run(ctx) -> List[List[Span]]:
    """The spans of the run a reader is called for: from
    ``ctx.xplane_path`` where the caller gives it (the tests do;
    ``run.py`` gives readers the reduced trace only), else from where
    ``run.py`` keeps the file."""
    path = getattr(ctx, "xplane_path", None) or trace_path(ctx.cell["name"])
    return load(path)


def named(threads: Iterable[List[Span]], name: str) -> List[Span]:
    return sorted((s for spans in threads for s in spans if s.name == name),
                  key=lambda s: s.start_ns)


def main_thread(threads: List[List[Span]]) -> List[Span]:
    """The thread whose outermost spans cover the most time."""
    def covered(spans):
        return xplane.length((s.start_ns, s.end_ns) for s in spans)
    return max(threads, key=covered, default=[])


def shifted(spans: List[Span], ns: int) -> List[Span]:
    """``spans`` moved by ``ns`` (negative: earlier)."""
    return [dataclasses.replace(s, start_ns=s.start_ns + ns,
                                end_ns=s.end_ns + ns) for s in spans]


def innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` pieces of one thread's spans: each
    instant belongs to the innermost span that covers it.  Spans of one
    thread nest; one that does not is cut where the next begins."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    at = 0

    def emit(upto: int):
        nonlocal at
        if stack and upto > at:
            out.append((at, upto, stack[-1].name))
        at = max(at, upto)

    for s in spans:
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(s.start_ns)
        stack.append(s)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    return out


def idle_by_span(trace: xplane.Trace,
                 spans: List[Span]) -> Dict[Optional[str], int]:
    """Idle nanoseconds of the first chip inside its window, by the
    innermost span of ``spans`` covering them; ``None`` holds what no
    span covers.  The values add up to window minus busy."""
    dev = trace.devices[0]
    gaps = xplane.subtract([xplane.window_of(dev)], xplane.spans(dev.ops))
    total: Dict[Optional[str], int] = {}
    covered, i = 0, 0
    for start, end, name in innermost(spans):    # both sorted, disjoint
        while i < len(gaps) and gaps[i][1] <= start:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < end:
            ns = min(end, gaps[j][1]) - max(start, gaps[j][0])
            total[name] = total.get(name, 0) + ns
            covered += ns
            j += 1
    rest = xplane.length(gaps) - covered
    if rest:
        total[None] = rest
    return total
