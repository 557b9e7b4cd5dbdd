"""Native cycle-time gradient micro-batching for the torch shim.

The reference's hot path (``horovod/common/operations.cc::RunLoopOnce``):
framework hooks enqueue gradients to a C++ queue; a background thread wakes
every ``HOROVOD_CYCLE_TIME`` ms, fuses whatever is ready (up to
``HOROVOD_FUSION_THRESHOLD`` bytes per bucket), and runs ONE collective per
bucket.  Without this, the eager torch path dispatches one XLA program per
gradient -- exactly the per-tensor launch overhead the fusion buffer
exists to kill.

This module wires the native C++ scheduler (``horovod_tpu._core``) into the
torch ``DistributedOptimizer``: hooks enqueue (tensor, handle) payloads;
the native cycle thread groups them by (dtype, op, compression,
process-set) and its callback dispatches a single fused
``grouped_allreduce`` per group, copies results into the grads in place,
and completes the native handles.  ``synchronize`` = flush + wait.

Falls back transparently when the native lib can't build
(``HVD_TPU_NATIVE_CORE=0`` or no compiler): callers check
:func:`batcher` for None.
"""

from __future__ import annotations

import atexit
import threading
from typing import Dict, List, Optional, Tuple

import torch

from .. import _core
from ..core.exceptions import HorovodInternalError
from ..core.state import global_state

_lock = threading.Lock()
_batcher: Optional["GradBatcher"] = None


class GradBatcher:
    def __init__(self, cycle_ms: float, fusion_bytes: int,
                 stall_warn_s: float, deterministic: bool = False):
        self.handles = _core.NativeHandles()
        self._group_codes: Dict[Tuple, int] = {}
        self._sched = _core.NativeScheduler(
            self._on_batch, cycle_ms=cycle_ms, fusion_bytes=fusion_bytes,
            stall_warn_s=stall_warn_s, deterministic=deterministic)

    def _code(self, key: Tuple) -> int:
        # The native scheduler groups by an int "dtype" code; fold every
        # attribute that must be uniform within a fused dispatch into it.
        with _lock:
            return self._group_codes.setdefault(key, len(self._group_codes))

    def enqueue(self, tensor: torch.Tensor, name: str, op, compression,
                process_set, prescale_factor: float = 1.0,
                postscale_factor: float = 1.0) -> int:
        h = self.handles.create()
        code = self._code((str(tensor.dtype), id(op), id(compression),
                           id(process_set), prescale_factor,
                           postscale_factor))
        payload = (h, tensor, op, compression, process_set,
                   prescale_factor, postscale_factor)
        self._sched.enqueue(payload, name=name, dtype_code=code,
                            nbytes=tensor.numel() * tensor.element_size(),
                            handle=h)
        return h

    def _on_batch(self, payloads: List) -> None:
        # Runs on the native cycle thread (ctypes holds the GIL here).
        try:
            from . import grouped_allreduce
            tensors = [p[1] for p in payloads]
            _, _, op, compression, process_set, pre, post = payloads[0]
            outs = grouped_allreduce(tensors, op=op,
                                     compression=compression,
                                     process_set=process_set,
                                     prescale_factor=pre,
                                     postscale_factor=post,
                                     name="cycle_fused")
            for (h, t, *_), o in zip(payloads, outs):
                t.copy_(o)
                self.handles.done(h, 0)
        except Exception as e:  # noqa: BLE001 - propagate via handles
            for p in payloads:
                self.handles.done(p[0], 1, f"{type(e).__name__}: {e}")

    def wait(self, h: int, timeout_s: float = 300.0) -> None:
        self._sched.flush()
        status = self.handles.wait(h, timeout_s)
        err = self.handles.error(h) if status not in (0, -2, -3) else ""
        self.handles.release(h)  # always: a leaked entry trips the
        # stall inspector forever and inflates pending() counts
        if status == -2:
            raise HorovodInternalError(
                f"allreduce handle {h} timed out after {timeout_s}s")
        if status not in (0, -3):
            raise HorovodInternalError(
                f"fused allreduce failed: {err or status}")

    def poll(self, h: int) -> bool:
        return self.handles.poll(h) != 0

    def stop(self) -> None:
        self._sched.stop()


def batcher() -> Optional[GradBatcher]:
    """The process-wide batcher, started lazily; None if native core is
    unavailable."""
    global _batcher
    with _lock:
        if _batcher is not None:
            return _batcher
        if not _core.available():
            return None
        cfg = global_state().config
        cycle_ms = getattr(cfg, "cycle_time", 1.0)
        stall = 0.0 if cfg.stall_check_disable else cfg.stall_check_time
        # Multi-controller SPMD: every process must cut identical fused
        # batches (they jointly launch each XLA program), so the scheduler
        # runs in deterministic mode UNCONDITIONALLY there -- it is a
        # correctness requirement, not a knob.  Also deterministic on
        # accelerator backends even single-process: timing-based cutting
        # produces DIFFERENT fused shapes each cycle, and every new shape
        # is a fresh XLA compile -- seconds on a TPU vs. ms on CPU.
        # HOROVOD_DETERMINISTIC=0/1 overrides only the
        # single-process backend heuristic.
        import os

        import jax
        from ..core.config import _env_bool
        if ("HOROVOD_DETERMINISTIC" in os.environ
                or "HVD_TPU_DETERMINISTIC" in os.environ):
            single_proc_det = _env_bool("DETERMINISTIC", False)
        else:
            single_proc_det = jax.default_backend() != "cpu"
        deterministic = jax.process_count() > 1 or single_proc_det
        _batcher = GradBatcher(cycle_ms, cfg.fusion_threshold, stall,
                               deterministic=deterministic)
        atexit.register(shutdown_batcher)
        return _batcher


def shutdown_batcher() -> None:
    global _batcher
    with _lock:
        b, _batcher = _batcher, None
    if b is not None:
        b.stop()
