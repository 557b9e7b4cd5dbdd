"""Host-level (eager) collective API with async handles.

This is the analogue of the reference's enqueue surface
(``horovod/common/operations.cc::EnqueueTensorAllreduce`` + the
``handle``/``synchronize``/``poll`` machinery of
``horovod/torch/mpi_ops.py``) for code running *outside* a traced step --
parameter broadcasts, metric averaging, tests.

Data model ("rank-stacked" arrays):

* single process: the input carries a leading axis of length
  ``process_set.size()`` -- element ``i`` is rank ``i``'s tensor.  The
  result has the same shape (every rank's post-collective value).
* multi-process: each process passes its *local* stack of shape
  ``[local_ranks_in_set, ...]`` and receives its local stack back; the
  global array is assembled with ``jax.make_array_from_process_local_data``.

Dispatch path: the request signature (op kind, name, shape, dtype, reduce
op, process set -- exactly the reference's ``Request`` wire fields) keys the
:class:`~horovod_tpu.controller.cache.ExecutableCache`; a hit reuses the
compiled ``shard_map`` program (ResponseCache bitvector fast path
analogue), a miss traces + compiles one.  JAX dispatch is asynchronous, so
``*_async`` returns a handle immediately and ``synchronize`` blocks --
matching the reference's semantics without a background thread.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ops as _ops
from .compression import Compression
from .reduce_op import ReduceOp, Average, Sum
from ..controller.cache import signature
from ..core import process_sets as _ps
from ..core import stall as _stall
from ..core.state import global_state
from ..parallel.mesh import HVD_AXIS


def _is_multiprocess(mesh: Mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


def local_rank_count(ps=None) -> int:
    """Number of this process's devices in the set (= rows this process
    contributes to a rank-stacked eager input in multi-process mode).

    Returns 0 when this process owns NO member device -- including the
    case where every member device belongs to one OTHER process (a
    "single-process" member mesh seen from a non-member)."""
    ps = _ps.get_process_set(ps)
    mesh = ps.flat_mesh()
    me = jax.process_index()
    if not _is_multiprocess(mesh):  # all devices owned by ONE process
        owner = mesh.devices.flat[0].process_index
        return int(mesh.devices.size) if owner == me else 0
    return sum(1 for d in mesh.devices.flat if d.process_index == me)


def replicated_stack(leaf, ps=None) -> np.ndarray:
    """Stack one host value into the correctly-sized rank-stacked input for
    the current mode (all ranks in single-process; local ranks otherwise)."""
    x = np.asarray(leaf)
    k = local_rank_count(ps)
    return np.broadcast_to(x[None], (k,) + x.shape)


def _to_global(x, mesh: Mesh):
    """Assemble the rank-stacked global array on the eager mesh."""
    n = int(mesh.devices.size)
    sharding = NamedSharding(mesh, P(HVD_AXIS))
    if _is_multiprocess(mesh):
        local = np.asarray(x)
        me = jax.process_index()
        k = sum(1 for d in mesh.devices.flat if d.process_index == me)
        if local.ndim == 0 or local.shape[0] != k:
            raise ValueError(
                f"multi-process eager collectives take this process's local "
                f"rank stack: expected leading axis {k}, got shape "
                f"{local.shape} (use horovod_tpu.replicated_stack for "
                f"replicated host values)")
        global_shape = (n,) + local.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, local, global_shape)
    x = jnp.asarray(x)
    if x.ndim == 0 or x.shape[0] != n:
        raise ValueError(
            f"eager collectives take rank-stacked input: expected leading "
            f"axis {n} (process-set size), got shape {x.shape}")
    return jax.device_put(x, sharding)


def _run(kind: str, x, name: Optional[str], ps, per_rank_fn, op_label: str,
         out_rank_stacked: bool = True, publish_meta: Optional[dict] = None):
    """Shared eager dispatch: cache lookup -> shard_map program -> run.

    ``publish_meta``: replay metadata for joined ranks (join mode only) --
    published to the coordination KV store under this op's fence sequence
    number before dispatch, so drained ranks can mirror the collective.
    """
    from . import joinop as _join
    st = global_state()
    ps = _ps.get_process_set(ps)
    mesh = ps.flat_mesh()
    def _publish_abort(e: Exception) -> None:
        _join.publish(mesh, {"kind": "abort",
                             "message": f"{type(e).__name__}: {e}"})

    if publish_meta is None:
        arr = _to_global(x, mesh)
    else:
        # Join phase: drained ranks are already blocked on this op's
        # sequence slot (their presence round matched ours).  Validate
        # BEFORE publishing so a bad input publishes an abort record --
        # not op metadata they would replay against a never-dispatched
        # collective -- and publish an abort for any later dispatch
        # failure too (best effort: a drained rank that fetched the op
        # metadata before the overwrite lands surfaces the failure as a
        # transport error/timeout instead).
        try:
            arr = _to_global(x, mesh)
        except Exception as e:
            _publish_abort(e)
            raise
        _join.publish(mesh, publish_meta)
    key = signature(kind, name, (tuple(arr.shape), str(arr.dtype)), op_label,
                    ps.name)
    timeline = st.timeline

    def build():
        def spmd(block):
            # block: [1, ...] -- this device's rank tensor.
            y = per_rank_fn(block[0])
            return y[None]
        f = jax.shard_map(spmd, mesh=mesh, in_specs=P(HVD_AXIS),
                          out_specs=P(HVD_AXIS))
        return jax.jit(f)

    from ..timeline import spans as _spans
    rec = _spans.recorder()
    tags = {"rank": rec.rank, "step": rec.step, "leg": kind}
    try:
        t_neg = time.perf_counter()
        if timeline:
            with timeline.range(name or kind, "NEGOTIATE_" + kind.upper(),
                                args=tags):
                fn = st.cache.get_or_build(key, build)
            t_exec = time.perf_counter()
            with timeline.range(name or kind, kind.upper(), args=tags):
                out = fn(arr)
        else:
            fn = st.cache.get_or_build(key, build)
            t_exec = time.perf_counter()
            out = fn(arr)
    except Exception as e:
        if publish_meta is not None:
            _publish_abort(e)
        raise
    t_done = time.perf_counter()
    rec.add("negotiate", t_exec - t_neg, leg=kind)
    rec.add("exchange", t_done - t_exec, leg=kind)
    with _eager_stats_lock:
        _eager_stats["ops"] += 1
    if timeline:
        with timeline.range(name or kind, "FENCE", args=tags):
            _eager_fence(mesh, out)
    else:
        _eager_fence(mesh, out)
    rec.add("fence", time.perf_counter() - t_done, leg=kind)
    return out


def _mesh_platform(mesh: Mesh) -> str:
    """Hardware platform backing the eager mesh ("cpu"/"tpu"/"gpu")."""
    return getattr(mesh.devices.flat[0], "platform", "cpu")


def _transport_needs_fence(mesh: Mesh) -> bool:
    """Does this mesh's collective transport need post-dispatch
    serialization?  The two hazards fenced below are properties of the
    multi-process CPU (Gloo-style) transport; TPU/GPU collectives run on
    compiler-scheduled dedicated channels and never interleave."""
    return _mesh_platform(mesh) == "cpu"


def _eager_fence(mesh: Mesh, out) -> None:
    """Serialize cross-process eager collectives (backend-scoped).

    Two hazards on the multi-process CPU (Gloo) backend, both observed
    as "op.preamble.length <= op.nbytes ... distributed collective
    mismatch" aborts:
     1. separately-compiled programs reuse the same collective channel
        tags, so two programs in flight at once interleave their Gloo
        messages across processes;
     2. consecutive executions of even the SAME program reuse slots, and
        local completion on one rank does not imply the peer drained its
        tail messages -- the next dispatch can race them.
    block_until_ready closes (1) locally; the coordination-service
    barrier (gRPC, independent of the Gloo transport) closes (2) by
    ensuring every participant fully finished before anyone starts the
    next collective.  In-step fused collectives (one program per step)
    are unaffected; single-process paths skip this entirely, and a
    TPU/GPU-backed mesh skips the block + barrier (its channels cannot
    interleave) while still advancing the fence SEQUENCE -- join replay
    keys op metadata on that counter, so it must tick identically on
    every backend (see :func:`_coordination_fence`).
    """
    if not _is_multiprocess(mesh):
        return
    if _transport_needs_fence(mesh):
        jax.block_until_ready(out)
    _coordination_fence(mesh)


_fence_lock = threading.Lock()
_fence_seq: Dict[tuple, int] = {}

_eager_stats_lock = threading.Lock()
_eager_stats = {"ops": 0}


def eager_op_stats() -> dict:
    """Cumulative eager-plane accounting since the last reset:
    ``ops`` = collective dispatches through the shared ``_run`` path,
    ``fences`` = coordination-fence sequence advances summed over every
    participant set.  Feeds the ``horovod_eager_*`` metric families."""
    with _eager_stats_lock:
        ops = _eager_stats["ops"]
    with _fence_lock:
        fences = sum(_fence_seq.values())
    return {"ops": ops, "fences": fences}


def reset_fences() -> None:
    """Reset barrier sequence numbers.  Called by ``hvd.shutdown()``: after
    an elastic re-init, a restarted worker starts counting from zero, so a
    survivor carrying the old counts would wait at differently-named
    barriers forever."""
    from . import joinop as _join
    reset_deferred()
    with _fence_lock:
        _fence_seq.clear()
    with _eager_stats_lock:
        _eager_stats["ops"] = 0
    _join.reset()


def _peek_next_seq(procs: tuple) -> int:
    """The fence sequence number the NEXT collective on ``procs`` will use
    (the key joined ranks watch for replay metadata)."""
    with _fence_lock:
        return _fence_seq.get(procs, 0) + 1


def _coordination_fence(mesh: Mesh) -> None:
    """Cross-process happens-before via the JAX coordination service.

    Every process whose devices appear in ``mesh`` joins a named barrier;
    the name carries a per-participant-set sequence number, which matches
    across processes because SPMD requires them to issue eager collectives
    in the same order.

    The sequence number advances on EVERY backend (it keys join-replay
    metadata slots, so active and drained ranks must count identically);
    the barrier WAIT itself is scoped to the CPU/Gloo transport that
    needs it (:func:`_transport_needs_fence`).
    """
    procs = tuple(sorted({d.process_index for d in mesh.devices.flat}))
    with _fence_lock:
        seq = _fence_seq[procs] = _fence_seq.get(procs, 0) + 1
    client = getattr(jax._src.distributed.global_state, "client", None)
    if client is None:  # pragma: no cover - not under jax.distributed
        return
    if not _transport_needs_fence(mesh):
        return
    name = "hvd_eager_fence_" + "_".join(map(str, procs)) + f"_{seq}"
    client.wait_at_barrier(name, 60_000, process_ids=list(procs))


def local_result(out) -> np.ndarray:
    """This process's portion of a rank-stacked result (multi-process), or
    the whole stack (single process)."""
    shards = sorted(out.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


def one_row(out) -> np.ndarray:
    """One locally-addressable rank's row of a rank-stacked result.

    After a broadcast/allreduce every row is identical, so any local
    shard serves; used by the framework shims and the broadcast helpers
    (works multi-process, where the global array spans non-addressable
    devices)."""
    return np.array(np.asarray(out.addressable_shards[0].data)[0])


# ---------------------------------------------------------------------------
# Handle table (HandleManager analogue, horovod/torch/handle_manager.cc).
# ---------------------------------------------------------------------------

_handle_lock = threading.Lock()
_handle_counter = itertools.count(1)
_handles: Dict[int, Any] = {}

_PENDING = object()  # handle value: enqueued in _deferred, not yet dispatched
_ABSENT = object()   # pop default: distinguishes "no such handle" from pending


def _alloc_handle(value) -> int:
    with _handle_lock:
        h = next(_handle_counter)
        _handles[h] = value
        return h


def synchronize(handle: int):
    """Block until the async op completes and return its result.

    A deferred op whose flush failed raises its error here, ONCE -- the
    entry is consumed either way (retrying a consumed handle is a
    KeyError, matching an unknown handle).
    """
    flush_error = None
    try:
        flush_deferred()
    except Exception as e:  # KeyboardInterrupt/SystemExit propagate
        # The flush error was written into every affected handle; deliver
        # THIS handle's outcome (its op may have dispatched fine before a
        # later op failed).  A handle the failed flush never touched
        # propagates the flush error itself.
        flush_error = e
    if flush_error is None:
        with _handle_lock:
            value = _handles.pop(handle)   # KeyError: unknown/consumed
    else:
        with _handle_lock:
            value = _handles.pop(handle, _ABSENT)
        if value is _ABSENT:
            # Unknown/already-consumed handles stay a KeyError even when
            # the flush failed: the flush error belongs to the ops it
            # aborted, not to a caller retrying a spent handle.
            raise KeyError(handle)
        if value is _PENDING:
            raise flush_error
    if isinstance(value, BaseException):
        raise value
    with _stall.watched(f"synchronize(handle={handle})"):
        from ..elastic import chaos as _chaos
        _chaos.raise_if_armed()  # injected at=sync comm fault
        return jax.block_until_ready(value)


def poll(handle: int) -> bool:
    """True when the async op has finished (result ready to fetch).

    Polling a still-deferred op dispatches the pending batch first (the
    reference's PollHandle likewise guarantees progress -- a caller
    spinning on poll() must not livelock on an op that was never
    submitted to the cycle).  A flush failure reports True: the error is
    stored in the handle and raises at synchronize()."""
    with _handle_lock:
        pending = _handles.get(handle) is _PENDING
    if pending:
        try:
            flush_deferred()
        except Exception:  # delivered via synchronize; interrupts raise
            return True
    with _handle_lock:
        value = _handles.get(handle)
    if value is None:
        return True
    if isinstance(value, BaseException):
        return True
    try:
        return all(not a.is_deleted() and a.is_ready()
                   for a in jax.tree.leaves(value))
    except AttributeError:  # pragma: no cover - older jax
        jax.block_until_ready(value)
        return True


# ---------------------------------------------------------------------------
# Deferred async dispatch (cycle batching for the presence protocol).
#
# Reference analogue: EnqueueTensorAllreduce puts the request on the
# background loop's queue and RunLoopOnce negotiates EVERYTHING pending in
# one controller round per cycle.  Here the control-plane cost is the join
# presence round (``examples/eager_latency_probe.py`` times it on a
# localhost Gloo mesh), and the grouped/fused entry points already
# amortize it via joinop.flush -- but a loop of ungrouped ``*_async`` ops
# paid one round each.  Deferring the dispatch until a flush point
# (synchronize/poll, any sync collective, hvd.join, or the capacity cap)
# lets ONE presence round cover every op enqueued since the last flush,
# exactly the reference's async contract: an async op is only guaranteed
# to have run after its synchronize().
#
# Only ops the presence protocol applies to are deferred (multi-process,
# global set, join enabled): everywhere else JAX dispatch is already
# async and immediate dispatch is strictly better.  Flush points are
# program-order-deterministic (SPMD processes enqueue identical op
# sequences), so every process cuts identical batches -- a requirement,
# since the batch size is published to drained ranks via the flush-size
# protocol.
# ---------------------------------------------------------------------------

_deferred_lock = threading.Lock()
_deferred: List[tuple] = []          # (handle, entry) in issue order
_MAX_DEFERRED = 512                  # capacity flush (deterministic: count)
_flush_lock = threading.RLock()      # serializes flushes across threads
_flush_tls = threading.local()       # .active: THIS thread is mid-flush
_fused_meta_tls = threading.local()  # .extra: in-flight fused dispatch meta

_fuse_stats_lock = threading.Lock()
_fuse_stats = {"flushes": 0, "fused_buckets": 0, "fused_ops": 0,
               "singleton_ops": 0}


def deferred_fuse_stats() -> dict:
    """Cumulative fused-flush accounting since the last reset: flushes
    run, fused buckets dispatched, ops that rode a fused bucket, ops
    dispatched per-op (singletons).  Mirrors the ``deferred_fused_*``
    timeline counters for callers without a timeline."""
    with _fuse_stats_lock:
        return dict(_fuse_stats)


@dataclasses.dataclass
class _DeferredAllreduce:
    """Structured deferred entry.

    Round-6: carries the request fields instead of an opaque thunk, so
    ``flush_deferred`` can group compatible pending ops through the
    fusion planner (the reference's fusion-buffer cycle groups on the
    same Request fields).  ``dispatch`` reproduces the exact per-op call
    for the unfused/fallback path."""
    x: Any
    op: Any
    name: Optional[str]
    process_set: Any          # resolved ProcessSet
    prescale: float
    postscale: float
    compression: Any

    def fuse_key(self) -> tuple:
        """Ops fuse only when every program-changing parameter matches
        (kind, dtype, reduce op, scale factors, codec, process set) --
        the bucket then compiles, publishes, and replays as ONE
        collective."""
        return ("allreduce", str(jnp.dtype(self.x.dtype)), str(self.op),
                float(self.prescale), float(self.postscale),
                self.compression.__name__, self.process_set.name)

    def dispatch(self):
        return allreduce(self.x, self.op, name=self.name,
                         process_set=self.process_set,
                         prescale_factor=self.prescale,
                         postscale_factor=self.postscale,
                         compression=self.compression)


def _deferred_fuse_enabled() -> bool:
    st = global_state()
    if st.config is not None:
        return st.config.deferred_fuse
    from ..core.config import _env_bool
    return _env_bool("DEFERRED_FUSE", True)


def _deferred_fuse_threshold() -> int:
    """Per-rank bucket byte cap for the fused flush
    (HOROVOD_DEFERRED_FUSE_THRESHOLD; 0 = follow the fusion threshold,
    autotuner included)."""
    st = global_state()
    if st.config is not None and st.config.deferred_fuse_threshold > 0:
        return st.config.deferred_fuse_threshold
    from ..controller import fusion as _fusion
    return _fusion._threshold()


def _defer_applies(ps) -> bool:
    """Should an ``*_async`` op on ``ps`` defer to the batched flush?
    Exactly when the presence protocol applies (multi-process, global
    set, join enabled): everywhere else JAX dispatch is already async
    and immediate dispatch is strictly better.  Separate seam so tests
    can force the deferred path on a single-process mesh."""
    from . import joinop as _join
    return _join._applies(ps)


def _in_flush() -> bool:
    """True on the thread currently executing flush_deferred's dispatch
    loop.  Must be thread-local: a CONCURRENT thread's collective is not
    reentrant -- it must block on the flush lock, not skip the flush."""
    return getattr(_flush_tls, "active", False)


def _defer(entry) -> int:
    """Enqueue a deferred op: a :class:`_DeferredAllreduce` record
    (fusable at flush) or a bare thunk (always per-op)."""
    h = _alloc_handle(_PENDING)
    with _deferred_lock:
        _deferred.append((h, entry))
        full = len(_deferred) >= _MAX_DEFERRED
    if full:
        flush_deferred()
    return h


def deferred_count() -> int:
    with _deferred_lock:
        return len(_deferred)


def reset_deferred() -> None:
    """Drop undispatched async ops (``hvd.shutdown()``): an async op is
    only guaranteed dispatched after synchronize/poll, and flushing here
    could hang against peers that already shut down."""
    with _deferred_lock:
        dropped = list(_deferred)
        _deferred.clear()
    with _handle_lock:
        for h, _ in dropped:
            _handles.pop(h, None)
    with _fuse_stats_lock:
        for key in _fuse_stats:
            _fuse_stats[key] = 0


def _deferred_error(handle: int, cause: BaseException,
                    reason: str) -> RuntimeError:
    """Fresh per-handle error for a failed flush.

    Every affected handle gets its OWN exception object (chained to the
    shared cause) -- raising one shared instance from several
    ``synchronize()`` calls would accrete conflicting tracebacks and make
    each raise look like a re-raise of the previous one.
    """
    err = RuntimeError(
        f"deferred async op (handle {handle}) {reason}: {cause!r}")
    err.__cause__ = cause
    return err


@dataclasses.dataclass
class _FlushUnit:
    """One collective dispatch within a flush: a fused bucket of
    compatible ops, or a single op on the per-op path.  ``leg`` is the
    unit's exchange-plan IR row (fused buckets only) -- the scheduler
    orders units by its cost model under the default bandwidth mode."""
    pos: int                       # issue position of the first member
    handles: List[int]
    dispatch: Callable[[], Dict[int, Any]]
    fused: bool = False
    leg: Any = None                # Optional[fusion.ExchangeLeg]


def _single_unit(pos: int, h: int, entry) -> _FlushUnit:
    d = entry.dispatch if isinstance(entry, _DeferredAllreduce) else entry
    return _FlushUnit(pos, [h], lambda h=h, d=d: {h: d()})


def _fused_unit(bucket, widths, k: int) -> _FlushUnit:
    """ONE collective for a planner bucket of compatible deferred ops.

    The member rank-stacks reshape to ``[k, width]`` rows and concatenate
    into one ``[k, sum(widths)]`` payload; a single :func:`allreduce`
    carries it (one presence slot, one fence).  Results slice back per
    handle through a jitted unfuse program (eager slicing of a
    multi-process global array is not allowed outside jit) memoized in
    the shared executable cache.  The bucket name is derived from the
    first member's issue position -- deterministic across SPMD processes,
    stable across identical flushes so the compiled program and unfuse
    slicer both cache-hit.
    """
    pos = min(p for p, _, _ in bucket)
    handles = [h for _, h, _ in bucket]
    recs = [r for _, _, r in bucket]
    r0 = recs[0]
    name = f"deferred_fused.{jnp.dtype(r0.x.dtype).name}.{pos}"
    widths = [int(w) for w in widths]
    tails = [tuple(int(d) for d in r.x.shape[1:]) for r in recs]

    def dispatch():
        host = all(isinstance(r.x, np.ndarray) for r in recs)
        cat = np.concatenate if host else jnp.concatenate
        flats = [(r.x if host else jnp.asarray(r.x)).reshape(k, -1)
                 for r in recs]
        fused = cat(flats, axis=1)
        # Publish the fused layout with the op metadata: a drained rank
        # replays the bucket-level collective bitwise from kind + fused
        # shape (joinop._replay also cross-checks the widths).
        _fused_meta_tls.extra = {"fused_ops": len(recs),
                                 "fused_widths": widths}
        try:
            red = allreduce(fused, r0.op, name=name,
                            process_set=r0.process_set,
                            prescale_factor=r0.prescale,
                            postscale_factor=r0.postscale,
                            compression=r0.compression)
        finally:
            _fused_meta_tls.extra = None
        st = global_state()
        key = signature("deferred_unfuse", name,
                        (tuple(red.shape), str(red.dtype)),
                        f"{widths}|{tails}", r0.process_set.name)

        def build():
            def unfuse(buf):
                out, off = [], 0
                for w, tail in zip(widths, tails):
                    out.append(buf[:, off:off + w].reshape(
                        (buf.shape[0],) + tail))
                    off += w
                return out
            return jax.jit(unfuse)

        vals = st.cache.get_or_build(key, build)(red)
        return dict(zip(handles, vals))

    # Plan-IR row for the fused payload: one flat allreduce of the
    # [k, sum(widths)] concat at this bucket's wire dtype.  Pure in the
    # member shapes/codec, so every SPMD process derives the same row.
    from ..controller import fusion as _fusion
    leg = _fusion.plan_exchange(
        "flat", size=k * sum(widths),
        dtype=jnp.dtype(r0.x.dtype).name,
        compression=r0.compression).legs[0]
    return _FlushUnit(pos, handles, dispatch, fused=True, leg=leg)


def _plan_flush_units(pending, fuse: bool) -> List[_FlushUnit]:
    """Group pending deferred entries into dispatch units.

    Compatible structured ops (same :meth:`_DeferredAllreduce.fuse_key`)
    route through the shared fusion planner
    (:func:`~horovod_tpu.controller.fusion.plan_eager_flush`) and pack
    into per-rank buckets of at most the deferred-fuse threshold: one
    fused collective + one fence per bucket.  Everything else -- opaque
    thunks, mismatched keys, inputs that are not a well-formed local rank
    stack -- keeps the per-op path, as does any bucket with a single
    member (no concat/slice overhead for the trivial case).  The grouping
    is pure in issue order + op signatures, so every SPMD process cuts
    identical units -- required, since the unit count is published to
    drained ranks as the flush size.  Units dispatch in the issue order
    of their first member.
    """
    from ..controller import fusion as _fusion
    units: List[_FlushUnit] = []
    groups: Dict[tuple, List[tuple]] = {}
    for pos, (h, entry) in enumerate(pending):
        if not (fuse and isinstance(entry, _DeferredAllreduce)):
            units.append(_single_unit(pos, h, entry))
            continue
        k = local_rank_count(entry.process_set)
        shape = getattr(entry.x, "shape", ())
        if k < 1 or len(shape) < 1 or shape[0] != k:
            # Not a local rank stack: the per-op path raises the same
            # error immediate dispatch would have.
            units.append(_single_unit(pos, h, entry))
            continue
        groups.setdefault(entry.fuse_key(), []).append((pos, h, entry))
    threshold = _deferred_fuse_threshold()
    for members in groups.values():
        if len(members) == 1:
            units.append(_single_unit(*members[0]))
            continue
        recs = [entry for _, _, entry in members]
        k = local_rank_count(recs[0].process_set)
        spec = _fusion.plan_eager_flush(
            [r.x for r in recs], k, threshold,
            extra=(recs[0].process_set.name,))
        for _dt, lspecs in spec.buffers:
            if len(lspecs) == 1:
                units.append(_single_unit(*members[lspecs[0].index]))
                continue
            units.append(_fused_unit([members[s.index] for s in lspecs],
                                     [s.size for s in lspecs], k))
    if _fusion.exchange_schedule_mode() == "bandwidth":
        # Bandwidth-ordered issue (HOROVOD_EXCHANGE_SCHEDULE=program
        # restores pure issue order): costliest planned legs dispatch
        # first so their wire time overlaps the cheaper units' host
        # glue.  Pure in the plan rows + issue order -- every SPMD
        # process cuts the identical sequence, which the drained-rank
        # protocol requires.  Payloads are untouched; only issue order
        # moves.
        units.sort(key=lambda u: (
            -_fusion.leg_cost_seconds(u.leg) if u.leg is not None
            else 0.0, u.pos))
    else:
        units.sort(key=lambda u: u.pos)
    return units


def _note_flush(units: List[_FlushUnit]) -> None:
    """Account the flush plan (module stats + timeline counters)."""
    fused = [u for u in units if u.fused]
    n_fused_ops = sum(len(u.handles) for u in fused)
    n_single = len(units) - len(fused)
    with _fuse_stats_lock:
        _fuse_stats["flushes"] += 1
        _fuse_stats["fused_buckets"] += len(fused)
        _fuse_stats["fused_ops"] += n_fused_ops
        _fuse_stats["singleton_ops"] += n_single
    tl = global_state().timeline
    if tl:
        tl.counters({"deferred_fused_buckets": len(fused),
                     "deferred_fused_ops": n_fused_ops,
                     "deferred_singleton_ops": n_single})


def flush_deferred() -> None:
    """Dispatch every deferred async op behind ONE presence round.

    Serialized under an RLock: a REENTRANT call (a unit's own dispatch
    re-entering via ``_join_sync``/``joinop.flush`` on the flushing
    thread) sees the thread-local flag and returns; a CONCURRENT thread's
    ``synchronize``/``poll``/collective blocks here until the in-flight
    flush lands its results -- returning early would let it pop the raw
    ``_PENDING`` sentinel as the op's value, or corrupt the in-flight
    joinop flush accounting.

    Round-6: compatible pending ops FUSE (see :func:`_plan_flush_units`);
    the published flush size is the number of dispatch UNITS, and each
    fused unit publishes bucket-level metadata so drained ranks replay
    one identical fused collective per bucket.  Results scatter back per
    handle under the existing error-stamping protocol: every handle in a
    failed unit gets its own error chained to the cause, handles in later
    units get "aborted" errors.
    """
    with _flush_lock:
        if _in_flush():
            return
        with _deferred_lock:
            pending = list(_deferred)
            _deferred.clear()
        if not pending:
            return
        from . import joinop as _join
        _flush_tls.active = True
        try:
            ps = _ps.get_process_set(None)
            units = _plan_flush_units(pending, _deferred_fuse_enabled())
            _note_flush(units)
            from ..timeline import spans as _spans
            rec = _spans.recorder()
            with _join.flush(ps, len(units)):
                err = None
                for i, unit in enumerate(units):
                    if err is None:
                        try:
                            fuse_key = (f"fused@{unit.pos}" if unit.fused
                                        else f"single@{unit.pos}")
                            with rec.span("bucket", name="deferred_flush",
                                          leg="deferred_flush",
                                          bucket_id=i, fuse_key=fuse_key):
                                values = unit.dispatch()
                        except BaseException as e:  # noqa: BLE001
                            err = e
                            values = {
                                h: _deferred_error(h, e,
                                                   "failed during flush")
                                for h in unit.handles}
                    else:
                        # Units after a failure never dispatch (the flush
                        # context publishes an abort for their slots);
                        # their synchronize() raises a fresh error chained
                        # to the op that sank the batch.
                        values = {
                            h: _deferred_error(
                                h, err, "aborted: an earlier op in the "
                                "flushed batch failed")
                            for h in unit.handles}
                    with _handle_lock:
                        for h, value in values.items():
                            if h in _handles:
                                _handles[h] = value
                if err is not None:
                    raise err
        except BaseException as e:
            # Context-entry failures (presence-round timeout, process-set
            # lookup during shutdown) reach here before the loop ran:
            # stamp the error into every handle still at the sentinel so
            # no synchronize() can return _PENDING as a "result".
            with _handle_lock:
                for h, _ in pending:
                    if _handles.get(h) is _PENDING:
                        _handles[h] = _deferred_error(
                            h, e, "aborted: flush failed before dispatch")
            raise
        finally:
            _flush_tls.active = False


# ---------------------------------------------------------------------------
# Public eager collectives.
# ---------------------------------------------------------------------------

def _join_sync(ps, kind: str, x, name: Optional[str], extra: dict = None):
    """Presence round + replay-metadata for join mode (JoinOp draining).

    Returns ``(k_active, meta, mask)``: ``k_active``/``mask`` are None
    when join handling does not apply (single process, replaying,
    non-global set); ``meta`` is None unless some rank has joined
    (k < set size), in which case it is the dict to publish for drained
    ranks to replay.
    """
    from . import joinop as _join
    if not _in_flush():
        # A sync collective is a flush point: pending deferred async ops
        # must dispatch first (program order; same point on every SPMD
        # process) so their presence round precedes this op's.
        flush_deferred()
    ps = _ps.get_process_set(ps)
    mask = _join.sync(ps)
    if mask is None:
        return None, None, None
    k = int(mask.sum())
    if k >= ps.size():
        return k, None, mask
    xa = np.asarray(x)
    meta = {"kind": kind, "name": name,
            "shape": (ps.size(),) + tuple(xa.shape[1:]),
            "dtype": str(xa.dtype)}
    if extra:
        meta.update(extra)
    fused_extra = getattr(_fused_meta_tls, "extra", None)
    if fused_extra:
        # A fused deferred-flush bucket is in flight on this thread:
        # publish its layout (op count + per-rank widths) with the op
        # metadata so drained ranks replay the bucket-level collective.
        meta.update(fused_extra)
    return k, meta, mask


def _join_abort(ps, message: str):
    """Raise after a presence round without leaving drained ranks hanging.

    A post-presence error on the active side must still publish SOMETHING
    at the op's sequence slot -- drained ranks are already blocked on the
    metadata key and would otherwise stall until HOROVOD_JOIN_TIMEOUT and
    then desync.  Publish an abort record (they re-raise it) and raise
    locally; every active rank does the same (SPMD), overwrites benign.
    """
    from . import joinop as _join
    _join.publish(_ps.get_process_set(ps).flat_mesh(),
                  {"kind": "abort", "message": message})
    raise RuntimeError(message)


def allreduce(x, op: ReduceOp = Average, *, name: Optional[str] = None,
              process_set=None, prescale_factor: float = 1.0,
              postscale_factor: float = 1.0, compression=Compression.none):
    ps = _ps.get_process_set(process_set)
    k, jmeta, _mask = _join_sync(ps, "allreduce", x, name)
    if jmeta is not None:
        if op is Average:
            # Mean over the ranks that actually contributed (reference
            # JoinOp behavior): the traced op divides by the full size n,
            # so rescale by n/k.  Ill-defined for truncating int division.
            if np.issubdtype(np.asarray(x).dtype, np.integer):
                _join_abort(ps, "integer-dtype Average while ranks are "
                                "joined is unsupported (truncating rescale "
                                "is ill-defined)")
            postscale_factor *= ps.size() / k
        jmeta.update(op=str(op), pre=prescale_factor,
                     post=postscale_factor,
                     compression=compression.__name__)
        from .compression import is_powersgd, powersgd_factor_widths
        if is_powersgd(compression):
            # Replay metadata for the low-rank codec: a drained rank
            # re-traces the factor exchange from shape alone, so publish
            # the factor widths (rank x matricized dims) for the replay
            # cross-check in joinop._replay.
            row = int(np.prod(np.asarray(x).shape[1:], dtype=np.int64))
            jmeta.update(factor_widths=list(
                powersgd_factor_widths(max(row, 1), compression.rank)))

    def per_rank(t):
        from .compression import is_fp8, is_powersgd, is_topk
        from .reduce_op import Adasum as _Adasum
        if is_powersgd(compression) or is_topk(compression):
            if op is _Adasum:
                raise NotImplementedError(
                    "error-feedback codecs do not compose with Adasum")
            # Stateless form: the eager control plane has nowhere to
            # thread residual state, so the residual is dropped (same
            # one-shot semantics the autotuner's probe samples use).
            if is_powersgd(compression):
                out, _ = _ops.powersgd_allreduce(
                    t, op, rank=compression.rank, axes=(HVD_AXIS,),
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
            else:
                out, _ = _ops.topk_allreduce(
                    t, op, fraction=compression.fraction, axes=(HVD_AXIS,),
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
            return out
        if is_fp8(compression):
            if op is _Adasum:
                return _ops.allreduce(t, op, axes=(HVD_AXIS,),
                                      prescale_factor=prescale_factor,
                                      postscale_factor=postscale_factor,
                                      wire_codec="fp8")
            return _ops.fp8_allreduce(t, op, axes=(HVD_AXIS,),
                                      prescale_factor=prescale_factor,
                                      postscale_factor=postscale_factor)
        c, ctx = compression.compress(t)
        r = _ops.allreduce(c, op, axes=(HVD_AXIS,),
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor)
        return compression.decompress(r, ctx)
    # Every parameter that changes the compiled program must be in the
    # cache key (the reference's Request carries the same distinctions).
    label = (f"{op}|pre={prescale_factor}|post={postscale_factor}|"
             f"{compression.__name__}")
    return _run("allreduce", x, name, ps, per_rank, label,
                publish_meta=jmeta)


def allreduce_async(x, op: ReduceOp = Average, *, name=None, process_set=None,
                    prescale_factor=1.0, postscale_factor=1.0,
                    compression=Compression.none) -> int:
    ps_ = _ps.get_process_set(process_set)
    if not _in_flush() and _defer_applies(ps_):
        # Snapshot host inputs: the caller may mutate the buffer between
        # enqueue and flush (jax arrays are immutable; no copy needed).
        x_snap = x if isinstance(x, jax.Array) else np.array(x, copy=True)
        return _defer(_DeferredAllreduce(
            x_snap, op, name, ps_, prescale_factor, postscale_factor,
            compression))
    out = allreduce(x, op, name=name, process_set=process_set,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, compression=compression)
    return _alloc_handle(out)


def grouped_allreduce(xs: Sequence, op: ReduceOp = Average, *, name=None,
                      process_set=None, compression=Compression.none,
                      to_host: bool = False, prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    """Fused multi-tensor eager allreduce (grouped_allreduce parity).

    Tensors are fused per dtype (concatenating mixed dtypes would silently
    promote); each dtype bucket dispatches one collective.  NumPy inputs
    fuse on the HOST (one staging transfer per bucket instead of one per
    tensor -- every host->device transfer is its own dispatch, and a
    ResNet-50 has ~160 gradient tensors).

    ``to_host=True`` additionally fetches each bucket's result once and
    returns per-tensor numpy views of this process's LOCAL rank-stack --
    the framework-shim path, where slicing the fused device array per
    tensor would cost one device->host round-trip each.
    """
    xs = list(xs)
    if not xs:
        return []
    reds, spec = _grouped_allreduce_buckets(
        xs, op, name=name, process_set=process_set, compression=compression,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor)
    return _unfuse_buckets(reds, spec, to_host=to_host)


def _grouped_allreduce_buckets(xs, op: ReduceOp = Average, *, name=None,
                               process_set=None,
                               compression=Compression.none,
                               prescale_factor: float = 1.0,
                               postscale_factor: float = 1.0):
    """Dispatch the per-dtype fused allreduces WITHOUT fetching: returns
    ``(bucket_results, spec)`` for :func:`_unfuse_buckets` -- the async
    framework-shim path keeps the device arrays in its handle and unfuses
    (one fetch per bucket) only at synchronize."""
    ps = _ps.get_process_set(process_set)
    # Inputs are rank-stacked: ALL ranks single-process, this process's
    # local ranks in multi-process mode -- flatten per leading row.
    k = local_rank_count(ps)
    host_in = all(isinstance(x, np.ndarray) for x in xs)
    if not host_in:
        xs = [jnp.asarray(x) for x in xs]
    plan = _bucket_layout(xs, k, ps)
    cat = np.concatenate if host_in else jnp.concatenate
    reds, spec = [], []
    from . import joinop as _join
    with _join.flush(ps, len(plan)):  # ONE presence round per flush
        for dt, idxs, widths, tails in plan:
            flats = [xs[i].reshape(k, -1) for i in idxs]
            fused = flats[0] if len(flats) == 1 else cat(flats, axis=1)
            reds.append(allreduce(
                fused, op, name=f"{name or 'grouped_allreduce'}.{dt.name}",
                process_set=process_set, compression=compression,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor))
            spec.append((idxs, widths, tails))
    return reds, (spec, len(xs))


def _bucket_layout(xs, k: int, ps):
    """Memoized dtype-bucket layout for the per-step eager hot path.

    The grouping (and every width/tail it implies) is pure in the input
    shapes/dtypes, the local rank count and the process set, yet was
    recomputed on every grouped call.  The plan lives in the shared fusion
    plan cache (``controller.fusion``'s ``ExecutableCache``), keyed on
    (shapes, dtypes, threshold, process set); hit/miss counters surface
    through :func:`horovod_tpu.controller.fusion.plan_cache_stats`.
    """
    from ..controller import fusion as _fusion
    cache = _fusion._get_plan_cache()
    key = _fusion.plan_key(xs, _fusion._threshold(),
                           extra=("eager_grouped", k, ps.name))

    def build():
        by_dtype: Dict[Any, List[int]] = {}
        for i, x in enumerate(xs):
            by_dtype.setdefault(jnp.dtype(x.dtype), []).append(i)
        return tuple(
            (dt, tuple(idxs),
             # width == reshape(k, -1).shape[1], computed without touching
             # array data
             tuple(int(np.prod(xs[i].shape, dtype=np.int64)) // k
                   for i in idxs),
             tuple(tuple(xs[i].shape[1:]) for i in idxs))
            for dt, idxs in by_dtype.items())

    return cache.get_or_build(key, build)


def _unfuse_buckets(reds, spec, to_host: bool = False):
    """Split fused bucket results back into per-tensor arrays.

    ``to_host=True`` fetches each bucket ONCE (``local_result``) and
    returns numpy local-rank stacks -- slicing the fused device array per
    tensor would cost one device->host fetch each (~160 for a
    ResNet-50).
    """
    buckets, n = spec
    out: List[Any] = [None] * n
    for red, (idxs, widths, tails) in zip(reds, buckets):
        if to_host:
            red = local_result(red)             # ONE fetch per bucket
        off = 0
        for i, w, tail in zip(idxs, widths, tails):
            # Device path: ``red`` is rank-stacked over the GLOBAL set
            # (leading axis ps.size()); host path: the LOCAL stack.
            out[i] = red[:, off:off + w].reshape((red.shape[0],) + tail)
            off += w
    return out


def broadcast_fused(arrays, root_rank: int = 0, *, name=None,
                    process_set=None):
    """Fused-per-dtype eager broadcast of replicated host arrays.

    Returns the root-rank value of each input as a host numpy array.  One
    collective (and one staging round-trip) per dtype instead of one per
    array -- a per-array loop compiles one XLA program per distinct shape
    and pays one transfer per array; this is the backing for every
    framework shim's ``broadcast_parameters`` / ``broadcast_variables``.
    """
    ps = _ps.get_process_set(process_set)
    arrays = [np.asarray(a) for a in arrays]
    out: List[Any] = [None] * len(arrays)
    by_dtype: Dict[Any, List[int]] = {}
    for i, a in enumerate(arrays):
        by_dtype.setdefault(a.dtype, []).append(i)
    from . import joinop as _join
    with _join.flush(ps, len(by_dtype)):
        for dt, idxs in sorted(by_dtype.items(), key=lambda kv: str(kv[0])):
            flat = np.concatenate([arrays[i].ravel() for i in idxs])
            res = broadcast(replicated_stack(flat, ps), root_rank,
                            name=f"{name or 'broadcast_fused'}.{dt}",
                            process_set=ps)
            row = one_row(res)
            off = 0
            for i in idxs:
                cnt = arrays[i].size
                out[i] = row[off:off + cnt].reshape(arrays[i].shape)
                off += cnt
    return out


def grouped_allgather(xs: Sequence, *, name=None, process_set=None):
    """Fused multi-tensor allgather (reference ``hvd.grouped_allgather``).

    Per-rank tensors are flattened and concatenated into one buffer, ONE
    collective gathers it, and each tensor's dim-0 concatenation is sliced
    back out -- the fusion-buffer treatment upstream gives grouped ops.

    The fused buffer is static-shape: every rank must pass the SAME
    per-tensor shapes (the reference's grouped gather also negotiates
    ragged dims -- here ragged first dims go through per-tensor
    :func:`allgatherv` instead).
    """
    xs = _as_stacks(xs)
    if not xs:
        return []
    ps = _ps.get_process_set(process_set)
    k = local_rank_count(ps)
    n = ps.size()
    _check_rank_stacked(xs, k, "grouped_allgather")
    out: List[Any] = [None] * len(xs)
    cat = np.concatenate if isinstance(xs[0], np.ndarray) \
        else jnp.concatenate
    from . import joinop as _join
    buckets = _dtype_buckets(xs)
    with _join.flush(ps, len(buckets)):
        for dt, idxs in buckets.items():
            flats = [xs[i].reshape(k, -1) for i in idxs]
            widths = [f.shape[1] for f in flats]
            fused = flats[0] if len(flats) == 1 else cat(flats, axis=1)
            g = allgather(fused,
                          name=f"{name or 'grouped_allgather'}.{dt.name}",
                          process_set=ps)            # [k, n*S]
            S = sum(widths)
            rows = g.reshape(g.shape[0], n, S)
            off = 0
            for i, w in zip(idxs, widths):
                piece = rows[:, :, off:off + w]      # [k, n, w]
                out[i] = piece.reshape(
                    (g.shape[0], n * xs[i].shape[1]) + xs[i].shape[2:])
                off += w
    return out


def _as_stacks(xs) -> List[Any]:
    """Normalize inputs: keep all-numpy lists on the host (fusing there
    costs one staging transfer per BUCKET instead of one per tensor)."""
    xs = list(xs)
    if all(isinstance(x, np.ndarray) for x in xs):
        return xs
    return [jnp.asarray(x) for x in xs]


def _dtype_buckets(xs) -> Dict[Any, List[int]]:
    """Indices grouped per dtype (concatenating mixed dtypes would
    silently promote)."""
    by_dtype: Dict[Any, List[int]] = {}
    for i, x in enumerate(xs):
        by_dtype.setdefault(jnp.dtype(x.dtype), []).append(i)
    return by_dtype


def grouped_reducescatter(xs: Sequence, op: ReduceOp = Average, *,
                          name=None, process_set=None):
    """Fused multi-tensor reducescatter (``hvd.grouped_reducescatter``).

    Each tensor's dim 0 must divide by the set size.  Tensors reshape to
    ``[k, n, d0/n * tail]`` and concatenate on the last axis, so ONE
    scatter leaves every rank a contiguous fused shard that slices back
    into per-tensor shards.
    """
    xs = _as_stacks(xs)
    if not xs:
        return []
    ps = _ps.get_process_set(process_set)
    k = local_rank_count(ps)
    n = ps.size()
    _check_rank_stacked(xs, k, "grouped_reducescatter")
    out: List[Any] = [None] * len(xs)
    for x in xs:
        if x.shape[1] % n:
            raise ValueError(
                f"grouped_reducescatter needs dim 0 divisible by the set "
                f"size {n}, got {x.shape[1:]}")
    cat = np.concatenate if isinstance(xs[0], np.ndarray) \
        else jnp.concatenate
    from . import joinop as _join
    buckets = _dtype_buckets(xs)
    with _join.flush(ps, len(buckets)):
        for dt, idxs in buckets.items():
            parts = [xs[i].reshape(k, n, -1) for i in idxs]
            widths = [p.shape[2] for p in parts]
            fused = parts[0] if len(parts) == 1 else cat(parts, axis=2)
            red = reducescatter(
                fused, op,
                name=f"{name or 'grouped_reducescatter'}.{dt.name}",
                process_set=ps)                      # [k, 1, S] shards
            red = red.reshape(red.shape[0], -1)
            off = 0
            for i, w in zip(idxs, widths):
                shard = red[:, off:off + w]
                out[i] = shard.reshape(
                    (red.shape[0], xs[i].shape[1] // n) + xs[i].shape[2:])
                off += w
    return out


def _check_rank_stacked(xs, k: int, what: str) -> None:
    for x in xs:
        if x.ndim < 2 or x.shape[0] != k:
            raise ValueError(
                f"{what} takes rank-stacked inputs with leading axis {k} "
                f"(this process's local ranks); got shape {x.shape}")


def allgather(x, *, name=None, process_set=None):
    """Each rank contributes its slice; all receive the concatenation.

    Rank-stacked input ``[n, d0, ...]`` -> output ``[n, n*d0, ...]``.
    First dimensions must match; ragged inputs go through
    :func:`allgatherv` (the reference's ``hvd.allgather`` supports both
    through one entry point because its negotiation already exchanges
    sizes; here the ragged path is explicit).

    During a join phase, drained ranks contribute ZERO rows of sizes via
    :func:`allgatherv` (reference zero-size gather contribution); through
    this static-shape entry point they contribute zeros."""
    ps = _ps.get_process_set(process_set)
    _, jmeta, _mask = _join_sync(ps, "allgather", x, name)

    def per_rank(t):
        return _ops.allgather(t, axes=(HVD_AXIS,), axis=0)
    return _run("allgather", x, name, ps, per_rank, "gather",
                publish_meta=jmeta)


def allgather_value(a, *, name=None, process_set=None) -> np.ndarray:
    """Framework-shim helper: gather ONE per-process value (replicated
    across this process's local ranks) with ragged first dims allowed.
    Single-controller mode treats every rank as holding ``a``."""
    k = local_rank_count(process_set)
    return allgatherv([np.asarray(a)] * k, name=name,
                      process_set=process_set)


def allgatherv(arrs, *, name=None, process_set=None) -> np.ndarray:
    """Ragged allgather: per-rank arrays whose FIRST dims differ.

    Reference semantics (``MPIAllgather``/``NCCLAllgather`` with unequal
    first dims -- the reference gathers sizes during negotiation, then
    runs a gatherv): sizes are exchanged first, data is padded to the max
    and gathered, and every rank receives the dim-0 concatenation in rank
    order as a HOST array (ragged shapes cannot live on-device under
    XLA's static shapes).

    ``arrs``: single process -- a sequence of per-rank arrays (length =
    set size); multi-process -- this process's local per-rank sequence
    (usually one array, which may be passed bare).
    """
    ps = _ps.get_process_set(process_set)
    if hasattr(arrs, "shape"):  # a bare array (ndarray / jax.Array)
        arrs = [arrs]
    arrs = [np.asarray(a) for a in arrs]
    k = local_rank_count(ps)
    if len(arrs) != k:
        raise ValueError(
            f"allgatherv takes one array per local rank: expected {k}, "
            f"got {len(arrs)}")
    tail_shapes = {a.shape[1:] for a in arrs}
    dtypes = {a.dtype for a in arrs}
    if len(tail_shapes) > 1 or len(dtypes) > 1:
        raise ValueError("allgatherv arrays may differ only in dim 0; got "
                         f"shapes {[a.shape for a in arrs]}, "
                         f"dtypes {sorted(map(str, dtypes))}")
    from . import joinop as _join
    with _join.flush(ps, 2):  # sizes + data: one presence round
        # Phase 1: exchange sizes (the reference's negotiation does this).
        sizes = np.asarray([[a.shape[0]] for a in arrs], np.int32)
        all_sizes = local_result(
            allgather(sizes, name=f"{name or 'allgatherv'}.sizes",
                      process_set=ps))[0].ravel()
        max_len = int(all_sizes.max())
        # Phase 2: pad to the max and gather (one static-shape collective).
        tail = arrs[0].shape[1:]
        padded = np.zeros((k, max_len) + tail, arrs[0].dtype)
        for i, a in enumerate(arrs):
            padded[i, :a.shape[0]] = a
        g = allgather(padded, name=f"{name or 'allgatherv'}.data",
                      process_set=ps)
    rows = local_result(g)[0].reshape((ps.size(), max_len) + tail)
    return np.concatenate([rows[r, :all_sizes[r]]
                           for r in range(ps.size())], axis=0)


def broadcast(x, root_rank: int = 0, *, name=None, process_set=None):
    ps = _ps.get_process_set(process_set)
    # root_rank is a global rank (reference semantics); on the member-only
    # eager mesh it maps to the root's position within the set.
    if ps.is_global():
        root_pos = root_rank
        if not 0 <= root_rank < ps.size():
            raise ValueError(f"broadcast root_rank {root_rank} out of range "
                             f"for world size {ps.size()}")
    else:
        if root_rank not in ps.ranks:
            raise ValueError(f"broadcast root_rank {root_rank} is not a "
                             f"member of process set {ps.name!r} "
                             f"(ranks {ps.ranks})")
        root_pos = ps.ranks.index(root_rank)

    _, jmeta, mask = _join_sync(ps, "broadcast", x, name,
                                {"root": root_rank})
    if jmeta is not None and not mask[root_rank]:
        # A drained root would replay zeros; error like the reference (a
        # joined rank cannot be the source of new data).
        _join_abort(ps, f"broadcast root_rank {root_rank} has joined and "
                        "cannot source a broadcast")

    def per_rank(t):
        return _ops.broadcast(t, root_pos, axes=(HVD_AXIS,))
    return _run("broadcast", x, name, ps, per_rank, f"root{root_rank}",
                publish_meta=jmeta)


def reducescatter(x, op: ReduceOp = Average, *, name=None, process_set=None,
                  _join_k: Optional[int] = None):
    """``_join_k`` (internal): active-rank count during a join phase --
    Average then divides by the contributing ranks, not the full size."""
    ps = _ps.get_process_set(process_set)
    if _join_k is None:
        k, jmeta, _mask = _join_sync(ps, "reducescatter", x, name)
        if jmeta is not None:
            if op is Average:
                if np.issubdtype(np.asarray(x).dtype, np.integer):
                    _join_abort(ps, "integer-dtype Average while ranks "
                                    "are joined is unsupported")
                _join_k = k
            jmeta.update(op=str(op), jk=_join_k)
    else:
        jmeta = None  # replaying a drained rank's mirror call

    def per_rank(t):
        if _join_k:
            y = _ops.reducescatter(t, Sum, axes=(HVD_AXIS,))
            return y / jnp.asarray(_join_k, y.dtype)
        return _ops.reducescatter(t, op, axes=(HVD_AXIS,))
    return _run("reducescatter", x, name, ps, per_rank,
                f"{op}|jk={_join_k}", publish_meta=jmeta)


def alltoall(x, *, name=None, process_set=None):
    ps = _ps.get_process_set(process_set)
    _, jmeta, _mask = _join_sync(ps, "alltoall", x, name)

    def per_rank(t):
        return _ops.alltoall(t, axes=(HVD_AXIS,))
    return _run("alltoall", x, name, ps, per_rank, "a2a",
                publish_meta=jmeta)


def alltoallv(arrs, splits, *, name=None, process_set=None):
    """Uneven alltoall (reference ``hvd.alltoall(tensor, splits=...)``).

    Reference semantics (NCCLAlltoall with ``splits`` -- the negotiation
    exchanges counts, then a ragged exchange runs): split counts are
    allgathered first, data is padded to the global max split and exchanged
    with one static-shape alltoall, and each rank receives the rank-order
    concatenation of the splits addressed to it, plus the per-sender counts.

    Args:
      arrs: single process -- per-rank data arrays (length = set size);
        multi-process -- this process's local per-rank list.  Each is
        ``[total_r, ...]`` rows, the rank-order concatenation of splits.
      splits: matching per-rank int arrays ``[size]``; ``splits[r][i]``
        rows of ``arrs[r]`` go to global rank ``i``.

    Returns:
      ``(datas, recv_splits)``: per local rank ``r``, ``datas[r]`` is the
      HOST array concatenating what rank ``r`` received (in sender rank
      order) and ``recv_splits[r][j]`` says how many rows came from global
      rank ``j``.
    """
    ps = _ps.get_process_set(process_set)
    if hasattr(arrs, "shape"):
        arrs = [arrs]
    arrs = [np.asarray(a) for a in arrs]
    if hasattr(splits, "shape") and np.asarray(splits).ndim == 1:
        splits = [splits]
    splits = [np.asarray(s, np.int32) for s in splits]
    k = local_rank_count(ps)
    n = ps.size()
    if k == 0:  # non-member process: no sub-mesh participation
        if arrs or splits:
            raise ValueError("this process owns no member device; pass "
                             "empty arrs/splits")
        return [], []
    if len(arrs) != k or len(splits) != k:
        raise ValueError(
            f"alltoallv takes one array and one splits vector per local "
            f"rank: expected {k}, got {len(arrs)} arrays / {len(splits)} "
            f"splits")
    for a, s in zip(arrs, splits):
        if s.shape != (n,):
            raise ValueError(f"splits must have shape ({n},), got {s.shape}")
        if s.sum() != a.shape[0]:
            raise ValueError(
                f"splits must sum to the data rows (the rank-order "
                f"concatenation of splits): sum {int(s.sum())} != "
                f"{a.shape[0]} rows")
    tail_shapes = {a.shape[1:] for a in arrs}
    dtypes = {a.dtype for a in arrs}
    if len(tail_shapes) > 1 or len(dtypes) > 1:
        raise ValueError("alltoallv arrays may differ only in dim 0; got "
                         f"shapes {[a.shape for a in arrs]}, "
                         f"dtypes {sorted(map(str, dtypes))}")
    from . import joinop as _join
    with _join.flush(ps, 2):  # split matrix + exchange: one presence round
        # Phase 1: exchange the split matrix (negotiation analogue).  Row
        # r of ``all_splits`` is global rank r's splits vector.
        stacked = np.stack(splits)                  # [k, n]
        all_splits = local_result(
            allgather(stacked, name=f"{name or 'alltoallv'}.splits",
                      process_set=ps))[0].reshape(n, n)
        max_len = max(int(all_splits.max()), 1)
        tail = arrs[0].shape[1:]
        # Phase 2: pad each split to the max and exchange (one
        # static-shape alltoall).  Send layout per rank: [n, max_len, ...].
        padded = np.zeros((k, n, max_len) + tail, arrs[0].dtype)
        for r, (a, s) in enumerate(zip(arrs, splits)):
            off = 0
            for i, c in enumerate(s):
                padded[r, i, :c] = a[off:off + c]
                off += int(c)

        # Join phase: drained ranks replay this as a plain alltoall of
        # zeros on the padded shape (identical traced program) -- their
        # zero split rows in ``all_splits`` already make receivers take 0
        # rows from them.
        _, jmeta, _mask = _join_sync(ps, "alltoall", padded, name)

        def per_rank(t):
            return _ops.alltoall(t, axes=(HVD_AXIS,))
        out = _run("alltoallv", padded, name, ps, per_rank, "a2av",
                   publish_meta=jmeta)
    rows = local_result(out)                        # [k, n, max_len, ...]
    local_global_ranks = _local_member_positions(ps)
    datas, recv_splits = [], []
    for r in range(k):
        g = local_global_ranks[r]
        counts = all_splits[:, g]                   # what each sender sent me
        datas.append(np.concatenate(
            [rows[r, j, :counts[j]] for j in range(n)], axis=0))
        recv_splits.append(counts.copy())
    return datas, recv_splits


def alltoallv_row(data, splits, *, name=None, process_set=None):
    """Framework-shim helper: uneven alltoall of ONE per-process value
    (replicated across this process's local ranks, like
    :func:`replicated_stack` for the even collectives).

    Returns host arrays ``(received, received_splits)`` for this process's
    first local rank -- the single-controller row the torch/TF/mxnet
    wrappers hand back.
    """
    data = np.asarray(data)
    sp = np.asarray(splits, np.int32)
    k = local_rank_count(process_set)
    if k == 0:
        raise RuntimeError(
            "alltoall(splits=...) called on a process owning no member "
            "device of the process set (in the reference's per-rank model "
            "a non-member never calls the op)")
    datas, rsplits = alltoallv([data] * k, [sp] * k, name=name,
                               process_set=process_set)
    return datas[0], rsplits[0]


def _local_member_positions(ps) -> List[int]:
    """Positions within the set (0..size-1) of this process's local ranks,
    in the same order their rows appear in rank-stacked eager arrays."""
    mesh = ps.flat_mesh()
    me = jax.process_index()
    if not _is_multiprocess(mesh):
        return list(range(int(mesh.devices.size)))
    return [i for i, d in enumerate(mesh.devices.flat)
            if d.process_index == me]


def barrier(*, process_set=None) -> None:
    """Block until every member device reaches the barrier."""
    ps = _ps.get_process_set(process_set)
    ones = replicated_stack(np.ones((1,), np.int32), ps)
    _, jmeta, _mask = _join_sync(ps, "barrier", ones, "barrier")
    out = _run("barrier", ones, "barrier", ps,
               lambda t: _ops.barrier(axes=(HVD_AXIS,)) * t, "barrier",
               publish_meta=jmeta)
    with _stall.watched("barrier"):
        from ..elastic import chaos as _chaos
        _chaos.raise_if_armed()  # injected at=sync comm fault
        jax.block_until_ready(out)


def join() -> int:
    """``hvd.join()`` (reference JoinOp, SURVEY.md 3.2).

    Multi-process mode: this process stops contributing and DRAINS -- it
    keeps participating in the survivors' collectives with identity
    payloads (zeros / +-inf / ones) until every process has joined, then
    returns the last rank to join.  Ranks with fewer batches can therefore
    stop early while the rest keep allreducing, without deadlock.

    Single-controller SPMD mode: every rank executes every step by
    construction, so there are no stragglers; join degenerates to a
    barrier and returns -1 ("no rank joined last"), the reference's
    convention when ranks are indistinguishable.
    """
    from . import joinop as _join
    flush_deferred()
    ps = _ps.get_process_set(None)
    mesh = ps.flat_mesh()
    if not _is_multiprocess(mesh) or _join.client() is None:
        barrier()
        return -1
    return _join.join_drain(mesh)
