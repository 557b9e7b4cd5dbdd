"""Reduction of a jax profiler trace (``*.xplane.pb``) to numbers.

Device planes -> op intervals -> busy union, per-name sums, collective
intervals and their part not covered by compute.  Read with nothing but
``jax.profiler.ProfileData``.  One rule throughout: a time is taken from
ONE line of ONE device plane (the ops line), never summed over lines --
steps, modules and ops overlap -- and never summed over chips: several
chips give the mean of the per-chip figures.  An empty or host-only trace
raises ``TraceError``; nothing here returns 0 or NaN for "found nothing".
"""

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?$")
# A Pallas (Mosaic) kernel on the ops line: the program gives its kernels
# no name, so the call's target is all there is to go by.
MOSAIC_KERNEL = r'custom_call_target="tpu_custom_call"'
HOST_PREFIX = "bench."

Interval = Tuple[int, int]   # [start_ns, end_ns)


class TraceError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    end_ns: int

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class DevicePlane:
    ordinal: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DevicePlane]
    host: List[Event]         # the benchmark's own annotations, by start


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no *.xplane.pb under {logdir}")
    return paths[-1]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(str(e.name), start, start + int(e.duration_ns)))
    out.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return out


def load_trace(path: str) -> Trace:
    """Read one xplane file.  Raises ``TraceError`` where no device plane
    has an operation on its ops line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = _events(lines[OPS_LINE])
            if not ops:
                continue
            modules = (_events(lines[MODULES_LINE])
                       if MODULES_LINE in lines else [])
            devices.append(DevicePlane(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(HOST_PREFIX))
    if not devices:
        raise TraceError(
            f"{path}: no device plane with an operation on its "
            f"{OPS_LINE!r} line (an empty or host-only trace)")
    devices.sort(key=lambda d: d.ordinal)
    host.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return Trace(devices, host)


# -- interval arithmetic ----------------------------------------------------

def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``a`` that ``b`` does not cover."""
    out = []
    cover = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in cover:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


# -- per-device figures -------------------------------------------------------

def window_of(dev: DevicePlane) -> Interval:
    """The traced window as the device saw it: first operation's start to
    last operation's end."""
    return (min(e.start_ns for e in dev.ops),
            max(e.end_ns for e in dev.ops))


def busy_ns(dev: DevicePlane, window: Interval = None) -> int:
    """Union of the operation intervals on the ops line, clipped to the
    window."""
    lo, hi = window or window_of(dev)
    return length(clip(spans(dev.ops), lo, hi))


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Per-name sums of SELF time: an operation that holds others (a
    while loop, a conditional) is charged only what its children do not
    cover, so the sums add up to the busy union where nothing runs side
    by side."""
    sums: Dict[str, int] = {}
    stack: List[List] = []      # [event, self_ns]

    def close(upto: int):
        while stack and stack[-1][0].end_ns <= upto:
            ev, self_ns = stack.pop()
            sums[ev.name] = sums.get(ev.name, 0) + max(self_ns, 0)

    for ev in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        close(ev.start_ns)
        if stack:
            parent = stack[-1]
            parent[1] -= min(ev.end_ns, parent[0].end_ns) - ev.start_ns
        stack.append([ev, ev.dur_ns])
    close(max((e.end_ns for e in events), default=0) + 1)
    return sums


def name_sums(events: Iterable[Event], pattern: str) -> Tuple[int, int]:
    """``(count, total ns)`` of the events whose name matches ``pattern``
    (a regular expression, searched)."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e.name)]
    return len(hits), sum(e.dur_ns for e in hits)


def opcode(name: str) -> str:
    """The HLO opcode of an ops-line event.  On a TPU the event's name is
    the instruction's text, ``%lhs = shape opcode(operands), attributes``
    (the shape may be a tuple); a name of another form is its own
    opcode."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return name
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.partition("(")[0].strip()


def short_name(name: str, limit: int = 100) -> str:
    """``opcode %lhs shape`` of an instruction's text, for the breakdown."""
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{opcode(name)} {lhs} {shape}"[:limit]


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(name)))


def ops_within(dev: DevicePlane, module_pattern: str) -> List[Event]:
    """The operations that ran inside an event of the modules line whose
    name matches ``module_pattern``."""
    rx = re.compile(module_pattern)
    mods = [(m.start_ns, m.end_ns) for m in dev.modules
            if rx.search(m.name)]
    out, j = [], 0
    for e in dev.ops:                       # both sorted by start
        while j < len(mods) and mods[j][1] <= e.start_ns:
            j += 1
        if j < len(mods) and mods[j][0] <= e.start_ns \
                and e.end_ns <= mods[j][1]:
            out.append(e)
    return out


def collective_exposed_ns(dev: DevicePlane) -> Tuple[int, int]:
    """``(collective ns, exposed ns)`` on one device: the union of the
    collective operations' intervals, and the part of it during which no
    other operation runs there."""
    coll = [e for e in dev.ops if is_collective(e.name)]
    comp = [e for e in dev.ops if not is_collective(e.name)]
    # A control-flow operation that merely holds a collective is not
    # compute covering it.
    starts = [c.start_ns for c in coll]          # dev.ops is sorted
    comp = [e for e in comp if not any(
        c.end_ns <= e.end_ns for c in coll[
            bisect.bisect_left(starts, e.start_ns):
            bisect.bisect_left(starts, e.end_ns)])]
    cu = union(spans(coll))
    return length(cu), length(subtract(cu, spans(comp)))


def mean_over_devices(trace: Trace, fn) -> float:
    vals = [fn(d) for d in trace.devices]
    return sum(vals) / len(vals)


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """``(busy_s, window_s)``: per chip the busy union and the window,
    then the mean over the chips.  ``0 < busy <= window`` holds by
    construction; a trace that breaks it raises."""
    busy = mean_over_devices(trace, busy_ns) / 1e9
    window = mean_over_devices(
        trace, lambda d: window_of(d)[1] - window_of(d)[0]) / 1e9
    if not 0.0 < busy <= window:
        raise TraceError(f"busy {busy} s is not in (0, window {window} s]")
    return busy, window


# -- breakdown ------------------------------------------------------------------

def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time (self time, mean over
    the chips), as ``[[name, seconds], ...]``."""
    total: Dict[str, float] = {}
    for dev in trace.devices:
        for name, ns in self_times(dev.ops).items():
            total[name] = total.get(name, 0.0) + ns / len(trace.devices)
    short: Dict[str, float] = {}
    for name, ns in total.items():
        key = short_name(name)
        short[key] = short.get(key, 0.0) + ns
    rows = sorted(short.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows if ns > 0]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The idle time of the first chip, summed by what the host was doing:
    each gap between busy stretches goes to the innermost of the
    benchmark's annotations that covers its midpoint."""
    dev = trace.devices[0]
    busy = union(spans(dev.ops))
    total: Dict[str, int] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        mid = (e0 + s1) // 2
        label, best = "host:unannotated", None
        for h in trace.host:
            if h.start_ns > mid:
                break
            if h.end_ns >= mid and (best is None or h.dur_ns < best):
                label, best = h.name, h.dur_ns
        total[label] = total.get(label, 0) + (s1 - e0)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows if ns > 0]
