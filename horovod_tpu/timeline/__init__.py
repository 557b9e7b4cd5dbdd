"""Chrome-tracing timeline (``HOROVOD_TIMELINE`` parity).

Analogue of the reference's ``horovod/common/timeline.cc``: a JSON writer
producing ``chrome://tracing`` / Perfetto-loadable output with per-tensor
phase events.  The reference's phases (NEGOTIATE_ALLREDUCE, QUEUE,
MEMCPY_IN_FUSION_BUFFER, NCCL_ALLREDUCE, MEMCPY_OUT_FUSION_BUFFER) map to
this runtime's phases: NEGOTIATE_* = trace+compile (executable-cache miss),
CACHE_HIT, and the collective execution itself.  Device-side timing is the
profiler's job (``jax.profiler`` emits XPlane/Perfetto); this timeline
captures the *semantic* host-side lifecycle, as SURVEY.md section 5.1
prescribes.

Events are buffered and flushed by a writer thread like the reference's,
so the hot path only appends to a deque.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, Optional


class Timeline:
    """Append-only Chrome-trace event stream with a background writer."""

    def __init__(self, path: str, mark_cycles: bool = False,
                 flush_interval: float = 1.0, rank: Optional[int] = None,
                 hostname: Optional[str] = None):
        self.path = path
        self.mark_cycles = mark_cycles
        self._events: Deque[dict] = deque()
        self._pids: Dict[str, int] = {}
        self._next_pid = 1
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._close_lock = threading.Lock()
        self._closed = False
        self._t0 = time.perf_counter()
        # Wall-clock anchor captured at the SAME instant as the
        # perf_counter epoch: offline merge aligns files via
        # wall_us = epoch_unix_us + ts, so n ranks' traces become
        # mergeable without the live KV offset handshake.  rank falls
        # back to the launcher-provided env identity (no jax import:
        # the timeline must open before backends initialize).
        self.epoch_unix_us = time.time() * 1e6
        if rank is None:
            for var in ("HVD_TPU_RANK", "HOROVOD_RANK"):
                v = os.environ.get(var, "")
                if v.lstrip("-").isdigit():
                    rank = int(v)
                    break
        self.rank = int(rank) if rank is not None else 0
        if hostname is None:
            import socket
            try:
                hostname = socket.gethostname()
            except OSError:
                hostname = "unknown"
        self.hostname = hostname
        self._events.append({
            "name": "clock_anchor", "ph": "M", "pid": 0,
            "args": {"epoch_unix_us": self.epoch_unix_us,
                     "rank": self.rank, "hostname": self.hostname}})
        self._file = open(path, "w")
        self._file.write("[\n")
        self._wrote_any = False
        self._flush_interval = flush_interval
        self._writer = threading.Thread(target=self._writer_loop,
                                        name="hvd-tpu-timeline", daemon=True)
        self._writer.start()
        atexit.register(self.close)

    # -- event emission ---------------------------------------------------
    def _us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _pid(self, track: str) -> int:
        pid = self._pids.get(track)
        if pid is None:
            pid = self._next_pid
            self._next_pid += 1
            self._pids[track] = pid
            self._events.append({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": track}})
        return pid

    def begin(self, tensor: str, phase: str,
              args: Optional[dict] = None) -> None:
        with self._lock:
            ev = {"name": phase, "ph": "B",
                  "pid": self._pid(tensor), "tid": 0,
                  "ts": self._us()}
            if args:
                ev["args"] = args
            self._events.append(ev)

    def end(self, tensor: str, phase: str,
            args: Optional[dict] = None) -> None:
        with self._lock:
            ev = {"name": phase, "ph": "E",
                  "pid": self._pid(tensor), "tid": 0,
                  "ts": self._us()}
            if args:
                ev["args"] = args
            self._events.append(ev)

    def complete(self, tensor: str, phase: str, dur_s: float,
                 args: Optional[dict] = None) -> None:
        """Retroactive Chrome "X" complete event spanning the PAST
        ``dur_s`` seconds and ending now -- for regions only measurable
        after the fact (the inter-dispatch gap: its start is known only
        once the next dispatch begins)."""
        with self._lock:
            ev = {"name": phase, "ph": "X",
                  "pid": self._pid(tensor), "tid": 0,
                  # Clamp to the trace epoch: a gap can predate open()
                  # (the first window of a freshly attached timeline).
                  "ts": max(0.0, self._us() - float(dur_s) * 1e6),
                  "dur": float(dur_s) * 1e6}
            if args:
                ev["args"] = args
            self._events.append(ev)

    def instant(self, name: str, track: str = "cycle") -> None:
        with self._lock:
            self._events.append({"name": name, "ph": "i", "s": "g",
                                 "pid": self._pid(track), "tid": 0,
                                 "ts": self._us()})

    def counter(self, name: str, value: float,
                track: str = "counters") -> None:
        """Chrome-trace counter sample ("C" event) -- renders as a
        stacked-area track (the reference plots tensor bytes this way)."""
        with self._lock:
            self._events.append({"name": name, "ph": "C",
                                 "pid": self._pid(track), "tid": 0,
                                 "ts": self._us(),
                                 "args": {name: float(value)}})

    def counters(self, values: Dict[str, float],
                 track: str = "counters") -> None:
        """Several counter samples at ONE timestamp (a single "C" event
        with multiple args renders as one stacked area).  Used by the
        fused deferred flush to emit its ``deferred_fused_buckets`` /
        fused-vs-singleton op counts as an atomic snapshot -- separate
        :meth:`counter` calls would get distinct timestamps and make the
        per-flush ratios unreadable in the trace viewer."""
        with self._lock:
            self._events.append({"name": "|".join(sorted(values)),
                                 "ph": "C",
                                 "pid": self._pid(track), "tid": 0,
                                 "ts": self._us(),
                                 "args": {k: float(v)
                                          for k, v in values.items()}})

    def mark_cycle(self) -> None:
        if self.mark_cycles:
            self.instant("CYCLE")

    @contextlib.contextmanager
    def range(self, tensor: str, phase: str, args: Optional[dict] = None):
        self.begin(tensor, phase, args=args)
        try:
            yield
        finally:
            self.end(tensor, phase)

    # -- writer thread ----------------------------------------------------
    def _drain(self) -> None:
        batch = []
        with self._lock:
            while self._events:
                batch.append(self._events.popleft())
        if not batch or self._file.closed:
            return
        chunks = []
        for ev in batch:
            prefix = ",\n" if self._wrote_any else ""
            self._wrote_any = True
            chunks.append(prefix + json.dumps(ev))
        self._file.write("".join(chunks))
        self._file.flush()

    def _writer_loop(self) -> None:
        while not self._stop.wait(self._flush_interval):
            try:
                self._drain()
            except ValueError:  # file closed under us at exit
                return

    def close(self) -> None:
        """Idempotent and exception-safe: ``hvd.shutdown()`` closes the
        timeline AND atexit fires the registration made in ``__init__``,
        so the double-close path is the normal path.  The writer thread
        is joined exactly once and the file closed exactly once, even if
        draining or the closing ``]`` write raises (e.g. a full disk) --
        a failed close must never wedge interpreter shutdown."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        self._writer.join(timeout=5)
        try:
            if not self._file.closed:
                self._drain()
                self._file.write("\n]\n")
        finally:
            try:
                self._file.close()
            except OSError:
                pass
            atexit.unregister(self.close)
