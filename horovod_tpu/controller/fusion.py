"""Tensor fusion: the HBM-resident fusion-buffer analogue.

The reference's ``fusion_buffer_manager.cc`` keeps a persistent 64 MiB
device buffer; the background thread memcpys ready gradients in (batched
D2D CUDA kernels), runs ONE collective, and memcpys out: the buffer is its
answer to the launch latency of one NCCL call a tensor.  Under XLA the
same idea is expressed functionally at trace time: leaves are raveled and
concatenated into flat per-dtype buffers no larger than the fusion
threshold (:func:`plan_buckets`, :func:`pack`), one collective is emitted
per buffer, and the results are sliced back out (:func:`unpack`).

Only an exchange that needs a bucket as ONE contiguous vector pays for
that (:func:`exchange_needs_vector`: error-feedback residuals, a scale a
bucket, a reduce-scatter that splits the vector, Adasum's dot products).
On a TPU the ravel of a tiled matrix is a relayout copy, so the buffer
moves every gradient byte through HBM several times.  The elementwise
exchange (``Sum``/``Average`` under none/fp16/bf16) builds none:
``allreduce_gradients`` emits one psum A LEAF and XLA's all-reduce
combiner groups them into many-operand all-reduces, each operand in its
own tiled layout.  There the combiner answers the launch latency, at its
own threshold, and the plan below is not part of the compiled step.

``HOROVOD_FUSION_THRESHOLD`` (default 64 MiB) controls bucket size, exactly
as in the reference (SURVEY.md section 5.6), wherever buckets are built:
the packed exchanges, ZeRO-1's arenas, the microbatched step and the eager
grouped collectives.  It does not reach the leaf-wise exchange.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.state import global_state
from .cache import ExecutableCache


@dataclasses.dataclass(frozen=True)
class _LeafSpec:
    index: int            # position in the original leaf list
    shape: Tuple[int, ...]
    size: int


@dataclasses.dataclass(frozen=True)
class FusionSpec:
    """Static description of how leaves were packed into flat buffers."""
    buffers: Tuple[Tuple[Any, Tuple[_LeafSpec, ...]], ...]  # (dtype, leaves)
    num_leaves: int


def _threshold() -> int:
    st = global_state()
    if st.config is not None:
        if st.autotuner is not None:
            return st.autotuner.fusion_threshold()
        return st.config.fusion_threshold
    return 64 * 1024 * 1024


def exchange_chunk_bytes() -> int:
    """Resolved chunk size for the chunked gradient exchange (0 = off).

    Reads ``HOROVOD_EXCHANGE_CHUNK_MB`` through the parsed config; when the
    autotuner is active its chunk-size axis wins (like ``_threshold``).
    """
    st = global_state()
    if st.config is not None:
        if st.autotuner is not None:
            return st.autotuner.exchange_chunk_bytes()
        return st.config.exchange_chunk_bytes
    return 0


# Bucket-plan memoization (ResponseCache spirit): the eager path replans
# identical gradient lists every step, and plan_buckets is pure in
# (shapes, dtypes, threshold).  Bounded LRU so shape-polymorphic callers
# cannot grow it without bound; capacity follows HOROVOD_CACHE_CAPACITY.
_plan_cache: Optional[ExecutableCache] = None


def _get_plan_cache() -> ExecutableCache:
    global _plan_cache
    st = global_state()
    cap = st.config.cache_capacity if st.config is not None else 1024
    if _plan_cache is None or _plan_cache.capacity != cap:
        _plan_cache = ExecutableCache(capacity=cap)
    return _plan_cache


def plan_cache_stats() -> dict:
    """Hit/miss/eviction counters for the memoized bucket planner."""
    c = _get_plan_cache()
    return {"hits": c.hits, "misses": c.misses, "evictions": c.evictions,
            "size": len(c)}


def clear_plan_cache() -> None:
    global _plan_cache
    _plan_cache = None


def plan_cache_enabled() -> bool:
    """Whether plan memoization is on (``HOROVOD_PLAN_CACHE``, default 1).

    ``0`` / ``false`` / ``off`` disables the shared plan cache: every
    planner call rebuilds from scratch.  Diagnostic knob -- the replan counts
    the consistency tests assert assume the cache is on.
    """
    return os.environ.get("HOROVOD_PLAN_CACHE", "1").strip().lower() \
        not in ("0", "false", "off")


def exchange_schedule_mode() -> str:
    """Leg-issue order policy (``HOROVOD_EXCHANGE_SCHEDULE``).

    ``bandwidth`` (default): :func:`schedule_legs` issues ready legs in
    bandwidth order -- contended-DCN legs before independent ICI legs,
    ties broken by modeled leg cost then program order.  ``program``:
    legs issue exactly in plan order (the pre-IR behaviour).
    """
    mode = os.environ.get("HOROVOD_EXCHANGE_SCHEDULE", "bandwidth")
    mode = mode.strip().lower()
    return mode if mode in ("bandwidth", "program") else "bandwidth"


def _memo(key: Tuple, build):
    """Route a planner memoization through the shared plan cache
    (identity when ``HOROVOD_PLAN_CACHE`` disables it)."""
    if not plan_cache_enabled():
        return build()
    return _get_plan_cache().get_or_build(key, build)


def plan_key(leaves: Sequence[Any], threshold_bytes: int,
             extra: Tuple = ()) -> Tuple:
    """Hashable memoization key for a bucket plan: per-leaf (shape, dtype)
    plus the threshold and any caller context (e.g. process-set name)."""
    return (tuple((tuple(x.shape), str(jnp.dtype(x.dtype))) for x in leaves),
            int(threshold_bytes)) + tuple(extra)


def plan_buckets(leaves: Sequence[Any],
                 threshold_bytes: Optional[int] = None,
                 reverse: bool = False,
                 extra: Tuple = ()) -> FusionSpec:
    """Greedily pack leaves into per-dtype buckets of <= threshold bytes.

    Order within a dtype follows leaf order (gradients arrive in reverse
    topological order, which keeps adjacent-layer gradients adjacent in the
    buffer -- same locality the reference's cycle batching produces).

    ``reverse=True`` walks the leaves last-to-first instead: the
    bucket-READY ordering for the backward-overlap exchange.  Flax/optax
    trees flatten in parameter (forward) order, so the LAST leaves are the
    layers whose gradients the backward pass finishes FIRST -- emitting
    their buckets first matches upstream Horovod's fusion-cycle behaviour
    (ready gradients go on the wire while earlier layers still compute).
    Unpack is index-addressed, so leaf recovery is order-independent.

    Leaves may be concrete arrays OR abstract ``jax.ShapeDtypeStruct``s
    (anything with ``.shape``/``.dtype``): the plan depends only on shapes
    and dtypes, so the scan-loop runner can plan its exchange ahead of data.
    Plans are memoized in a bounded LRU (see :func:`plan_cache_stats`).

    ``extra`` is folded into the memo key for caller context that changes
    what a bucket MEANS without changing its packing -- e.g. the exchange
    codec name, so an error-feedback plan (whose bucket sizes fix the
    residual-state shapes) never aliases a plain plan of the same leaves.
    """
    if threshold_bytes is None:
        threshold_bytes = _threshold()
    leaves = [x if hasattr(x, "dtype") else jnp.asarray(x) for x in leaves]
    key = plan_key(leaves, threshold_bytes,
                   extra=(("rev",) if reverse else ()) + tuple(extra))
    return _memo(
        key, lambda: _plan_buckets_uncached(leaves, threshold_bytes, reverse))


def _plan_buckets_uncached(leaves: Sequence[Any],
                           threshold_bytes: int,
                           reverse: bool = False) -> FusionSpec:
    by_dtype: dict = {}
    indexed = list(enumerate(leaves))
    if reverse:
        indexed.reverse()
    for i, x in indexed:
        by_dtype.setdefault(jnp.dtype(x.dtype), []).append(
            _LeafSpec(i, tuple(x.shape), int(np.prod(x.shape, dtype=np.int64))))
    buffers: List[Tuple[Any, Tuple[_LeafSpec, ...]]] = []
    for dt, specs in by_dtype.items():
        itemsize = jnp.dtype(dt).itemsize
        cur: List[_LeafSpec] = []
        cur_bytes = 0
        for s in specs:
            nbytes = s.size * itemsize
            if cur and cur_bytes + nbytes > threshold_bytes:
                buffers.append((dt, tuple(cur)))
                cur, cur_bytes = [], 0
            cur.append(s)
            cur_bytes += nbytes
        if cur:
            buffers.append((dt, tuple(cur)))
    return FusionSpec(buffers=tuple(buffers), num_leaves=len(leaves))


def plan_eager_flush(leaves: Sequence[Any], k: int,
                     threshold_bytes: Optional[int] = None,
                     extra: Tuple = ()) -> FusionSpec:
    """Bucket plan for the fused deferred-async flush (eager path).

    Same greedy per-dtype packing as :func:`plan_buckets`, but the eager
    layout is RANK-STACKED (``[k, ...]`` with ``k`` local ranks), so
    bucket sizes are counted over each op's per-rank row -- the payload a
    rank actually puts on the wire -- not over the whole stack.  Each
    returned ``_LeafSpec``'s shape/size describe that flat row
    (``size == prod(shape) // k``); ``index`` addresses the caller's leaf
    list as usual.  Memoized in the shared plan cache under an
    eager-flush-scoped key (``extra`` carries caller context such as the
    process-set name).
    """
    if threshold_bytes is None:
        threshold_bytes = _threshold()
    leaves = [x if hasattr(x, "dtype") else jnp.asarray(x) for x in leaves]
    k = max(int(k), 1)
    key = plan_key(leaves, threshold_bytes,
                   extra=("eager_flush", k) + tuple(extra))

    def build():
        rows = [jax.ShapeDtypeStruct(
            (int(np.prod(x.shape, dtype=np.int64)) // k,), x.dtype)
            for x in leaves]
        return _plan_buckets_uncached(rows, threshold_bytes)

    return _memo(key, build)


def pack(leaves: Sequence[jax.Array], spec: FusionSpec) -> List[jax.Array]:
    """Ravel+concat leaves into flat buffers per the spec."""
    out = []
    for dt, lspecs in spec.buffers:
        if len(lspecs) == 1:
            s = lspecs[0]
            out.append(jnp.ravel(leaves[s.index]))
        else:
            out.append(jnp.concatenate(
                [jnp.ravel(leaves[s.index]) for s in lspecs]))
    return out


def unpack(buffers: Sequence[jax.Array], spec: FusionSpec) -> List[jax.Array]:
    """Slice flat buffers back into the original leaf list order."""
    leaves: List[Optional[jax.Array]] = [None] * spec.num_leaves
    for buf, (dt, lspecs) in zip(buffers, spec.buffers):
        off = 0
        for s in lspecs:
            leaves[s.index] = buf[off:off + s.size].reshape(s.shape)
            off += s.size
    assert all(l is not None for l in leaves)
    return leaves  # type: ignore[return-value]


def fuse_flat(xs: Sequence[jax.Array],
              threshold_bytes: Optional[int] = None
              ) -> Tuple[List[jax.Array], FusionSpec]:
    spec = plan_buckets(xs, threshold_bytes)
    return pack(xs, spec), spec


def unfuse_flat(buffers: Sequence[jax.Array], spec: FusionSpec
                ) -> List[jax.Array]:
    return unpack(buffers, spec)


def exchange_needs_vector(compression, op, *, two_level: bool = False,
                          chunked: bool = False) -> bool:
    """Whether a gradient exchange needs each fusion bucket as ONE
    contiguous vector (so it packs), or is elementwise and reduces leaf by
    leaf with no buffer.  Read from what the exchange is, never from a
    knob: only ``Sum``/``Average`` under a codec that acts on every
    element alone (none and the casts) on the flat exchange is
    elementwise.  The error-feedback codecs keep a residual a bucket, fp8
    a scale a bucket, the two-level (``two_level``) and the chunked
    (``chunked``) exchanges split the vector, Adasum takes dot products
    over it.  ``allreduce_gradients`` routes on this; the auditor's
    contract, ``explain_plan``'s ``packed`` column and the step report's
    ``packed_bytes`` ask here."""
    from ..collectives.compression import is_elementwise
    from ..collectives.reduce_op import Average, Sum
    return (op not in (Sum, Average) or not is_elementwise(compression)
            or two_level or chunked)


def fused_tree_collective(tree, collective_fn,
                          threshold_bytes: Optional[int] = None,
                          extra: Tuple = ()):
    """Apply ``collective_fn(flat_buffer) -> flat_buffer`` to a whole pytree
    through the fusion buffers.  This is the gradient path of every
    exchange that needs a bucket as one vector
    (:func:`exchange_needs_vector`; the others reduce leaf by leaf and
    never come here).  ``extra`` is caller
    context for the plan memo key (see :func:`plan_buckets`).
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    spec = plan_buckets(leaves, threshold_bytes, extra=extra)
    with jax.named_scope("hvd_exchange/pack"):
        buffers = pack(leaves, spec)
    reduced = [collective_fn(b) for b in buffers]
    with jax.named_scope("hvd_exchange/unpack"):
        return jax.tree.unflatten(treedef, unpack(reduced, spec))


# -- explicit leg planning (two-level exchange) ----------------------------

@dataclasses.dataclass(frozen=True)
class ExchangeLeg:
    """One typed row of the exchange-plan IR: which mesh axis the leg
    moves over, which collective it emits, the codec riding that hop,
    and the closed-form operand/wire accounting the spans and the
    auditor both read.

    ``elements`` is the collective's first-operand element count (what
    the jaxpr auditor records); ``nbytes`` the wire payload bytes the
    matching ``spans.note_leg`` call reports for the leg.  ``audit`` is
    the leg's contract with ``analysis.stepmodel``: the exact
    ``(kind, dtype, elements, label)`` collective rows the traced step
    must contain for this leg (label is a suffix the model prefixes with
    its bucket tag).  ``kind`` indexes :data:`LEG_KINDS` (bandwidth
    class for the scheduler); ``fence`` records the eager fence policy
    in force when the plan was built; ``kernel`` names a Pallas kernel
    family when the leg is a kernel contract rather than a collective.
    """
    tag: str          # span tag: hier/ici_rs | zero_rs | moe/a2a_* | ...
    axis: str         # mesh axis name(s) the leg moves over
    collective: str   # reduce_scatter | psum | all_gather | fp8_gather |
                      # powersgd | topk | all_to_all | none
    codec: str        # codec name applied on this leg
    wire_dtype: str
    elements: int
    nbytes: int
    kind: str = ""    # LEG_KINDS key: flat_ar | ici_rs | dcn_ar | ...
    bucket: int = 0   # bucket / arena / layer index within the plan
    leaves: int = 0   # leaf count packed into the leg's bucket (0 = n/a)
    fence: str = ""   # eager fence policy snapshot (see _fence_policy)
    audit: Tuple[Tuple[str, str, int, str], ...] = ()
    kernel: str = ""  # Pallas kernel family for kind="kernel" legs


def hier_mesh_shape() -> Optional[Tuple[int, int]]:
    """``(n_dcn, n_ici)`` when the world mesh is the two-level
    ``(dcn, ici)`` communicator, else ``None``."""
    st = global_state()
    m = st.mesh
    if m is None:
        return None
    names = tuple(m.axis_names)
    if len(names) != 2:
        return None
    return (int(m.shape[names[0]]), int(m.shape[names[1]]))


def hier_requested(compression=None) -> bool:
    """Whether the two-level exchange is in effect for the gradient path:
    a per-leg codec always requests it; otherwise the config flag /
    topology spec or the autotuner's hierarchical axis."""
    from ..collectives.compression import is_hier_legs
    if compression is not None and is_hier_legs(compression):
        return True
    st = global_state()
    cfg = st.config
    if cfg is not None and cfg.hierarchical_allreduce:
        return True
    if cfg is not None and getattr(cfg, "hierarchical", None):
        from ..parallel.mesh import parse_topology_spec
        try:
            if parse_topology_spec(cfg.hierarchical)[0]:
                return True
        except ValueError:
            pass
    if st.autotuner is not None:
        return bool(st.autotuner.hierarchical_explicit())
    return False


def plan_hier_legs(size: int, dtype, *, n_dcn: int, n_ici: int,
                   compression=None, dcn_axis: str = "dcn",
                   ici_axis: str = "ici", ici_codec=None,
                   dcn_codec=None) -> List[ExchangeLeg]:
    """Closed-form leg plan for one bucket of the two-level exchange.

    Thin wrapper over ``plan_exchange("hier", ...)`` -- the memoized IR
    planner mirrors ``ops.hierarchical_allreduce`` exactly (padding
    quantum, per-leg wire dtypes, ``note_leg`` byte accounting), so the
    auditor's ``stepmodel`` and the op itself consume the SAME plan
    object.  ``compression`` may be ``None``,
    a cast codec (the bucket is cast before the exchange: every leg
    rides the wire dtype), or a per-leg ``ici:...,dcn:...`` codec;
    alternatively pass resolved ``ici_codec``/``dcn_codec`` classes
    directly (the executor's calling convention).
    """
    return list(plan_exchange(
        "hier", size=int(size), dtype=str(jnp.dtype(dtype)),
        n_dcn=int(n_dcn), n_ici=int(n_ici), compression=compression,
        ici_codec=ici_codec, dcn_codec=dcn_codec,
        dcn_axis=dcn_axis, ici_axis=ici_axis).legs)


def plan_moe_alltoall(n_experts: int, capacity: int, d_model: int, *,
                      dtype=jnp.float32, compression=None,
                      axis: str = "model") -> List[ExchangeLeg]:
    """Closed-form leg plan for one MoE layer's all_to_all pair.

    Thin wrapper over ``plan_exchange("moe", ...)``; mirrors
    ``parallel.moe.moe_ffn`` exactly: the dispatch leg moves the
    f32 ``(E, C, d)`` slot tensor (split experts, concat slots), the
    combine leg moves the same payload back, and ``compression`` (the
    ``HOROVOD_MOE_COMPRESSION`` / autotuner-MoE-axis codec) narrows both
    legs' wire dtype.  ``elements`` is the per-device operand element
    count the jaxpr auditor records for each ``all_to_all``; ``nbytes``
    matches the ``moe/a2a_*`` ``note_leg`` accounting byte-for-byte.
    """
    return list(plan_exchange(
        "moe", n_experts=int(n_experts), capacity=int(capacity),
        d_model=int(d_model), dtype=dtype, compression=compression,
        axis=axis).legs)


# -- plan introspection ----------------------------------------------------

def _fence_policy() -> str:
    """Human-readable fence policy the eager plane would apply to a
    collective dispatched right now (compiled steps never fence: XLA
    schedules their collectives)."""
    st = global_state()
    if st.mesh is None:
        return "unfenced(no-mesh)"
    from ..collectives.eager import _mesh_platform, _transport_needs_fence
    platform = _mesh_platform(st.mesh)
    if _transport_needs_fence(st.mesh):
        return f"barrier+block({platform})"
    return f"compiler-scheduled({platform})"


def explain_plan(params, threshold_bytes: Optional[int] = None,
                 compression=None, reverse: bool = False,
                 extra: Tuple = (), register: bool = True,
                 moe: Optional[dict] = None) -> List[dict]:
    """Render the planner's decision for ``params`` as structured rows.

    One dict per bucket: ``bucket`` index, ``dtype``, ``leaves`` count,
    ``elements``, raw ``bytes``, ``wire_bytes`` under ``compression``
    (a spec string or codec class; None = uncompressed), the ``codec``
    name, ``packed``, the eager ``fence`` policy, and the ``fuse_key``
    the plan memoizes under.  Like ``wire_bytes``, ``packed`` prices the
    ``Sum``/``Average`` exchange over the whole mesh
    (:func:`exchange_needs_vector`, with the two-level and chunked
    settings in force): ``True`` rows are buffers the step builds, one
    collective each, from the SAME :func:`plan_buckets` call the exchange
    makes -- error-feedback codecs fold the ``("ef", codec)`` context
    exactly like ``ef_bucket_plan`` -- so bucket count and per-bucket
    bytes match the emitted exchange by construction (asserted in
    tests/test_metrics.py).  ``False`` rows are accounting only: the
    leaf-wise exchange emits one psum a leaf, XLA's combiner draws the
    all-reduces, and the threshold changes the rows but not the step
    (the bytes add up the same).

    ``register=True`` also publishes the rows as ``horovod_plan_*``
    gauges so ``/metrics`` exposes the current plan.  Printable via
    ``python -m horovod_tpu.run --explain-plan`` (:func:`render_plan`).

    ``moe`` prices a model's MoE all_to_all traffic alongside the
    gradient buckets: a dict with ``n_experts``, ``capacity`` and
    ``d_model`` (optional ``layers`` -- MoE layer count, default 1 --
    plus ``compression`` and ``axis``) appends one extra row whose legs
    come from :func:`plan_moe_alltoall`, one dispatch/combine pair per
    layer.
    """
    from ..collectives.compression import (is_error_feedback,
                                           parse_compression,
                                           wire_payload_bytes)
    leaves = jax.tree.leaves(params)
    comp = parse_compression(compression) if compression is not None \
        else None
    if threshold_bytes is None:
        threshold_bytes = _threshold()
    plan_extra = tuple(extra)
    if comp is not None and is_error_feedback(comp):
        # Mirror optim.distributed.ef_bucket_plan's memo context so the
        # explained plan IS the exchange's plan (same cache entry).
        plan_extra = ("ef", comp.__name__) + plan_extra
    spec = plan_buckets(leaves, threshold_bytes, reverse=reverse,
                        extra=plan_extra)
    codec = comp.__name__ if comp is not None else "none"
    fence = _fence_policy()
    hier_shape = hier_mesh_shape() if hier_requested(comp) else None
    from ..collectives.reduce_op import Average
    # The reverse (bucket-ready) order is the microbatched step's plan:
    # its reduce-scatters split a vector, so it packs whatever the codec.
    packed = reverse or exchange_needs_vector(
        comp, Average, two_level=hier_shape is not None,
        chunked=exchange_chunk_bytes() > 0)
    rows = []
    for i, (dt, lspecs) in enumerate(spec.buffers):
        dtype = str(jnp.dtype(dt))
        size = sum(s.size for s in lspecs)
        itemsize = jnp.dtype(dt).itemsize
        raw = size * itemsize
        legs = None
        if hier_shape is not None:
            try:
                legs = plan_hier_legs(size, dt, n_dcn=hier_shape[0],
                                      n_ici=hier_shape[1], compression=comp)
            except ValueError:
                legs = None  # codec the two-level path doesn't route
        if legs is not None:
            wire = sum(l.nbytes for l in legs)
        elif comp is not None:
            wire = wire_payload_bytes(comp, size, itemsize)
        else:
            wire = raw
        rows.append({
            "bucket": i, "dtype": dtype, "leaves": len(lspecs),
            "elements": int(size), "bytes": int(raw),
            "wire_bytes": int(wire), "codec": codec, "packed": packed,
            "fence": fence,
            "fuse_key": "|".join(
                [dtype, f"thr={int(threshold_bytes)}", codec]
                + (["rev"] if reverse else [])),
            "legs": [dataclasses.asdict(l) for l in legs]
            if legs is not None else None,
        })
    if moe is not None:
        layers = int(moe.get("layers", 1))
        pair = plan_moe_alltoall(
            moe["n_experts"], moe["capacity"], moe["d_model"],
            dtype=moe.get("dtype", jnp.float32),
            compression=moe.get("compression"),
            axis=moe.get("axis", "model"))
        moe_legs = pair * layers
        elements = sum(l.elements for l in moe_legs)
        raw = elements * jnp.dtype(moe.get("dtype", jnp.float32)).itemsize
        rows.append({
            "bucket": len(rows), "dtype": pair[0].wire_dtype,
            "leaves": 0, "elements": int(elements), "bytes": int(raw),
            "wire_bytes": int(sum(l.nbytes for l in moe_legs)),
            "codec": pair[0].codec, "packed": False, "fence": fence,
            "fuse_key": "|".join(
                ["moe", f"E={int(moe['n_experts'])}",
                 f"C={int(moe['capacity'])}", f"d={int(moe['d_model'])}",
                 f"L={layers}", pair[0].codec]),
            "legs": [dataclasses.asdict(l) for l in moe_legs],
        })
    if register:
        register_plan_gauges(rows)
    return rows


def register_plan_gauges(rows: List[dict]) -> None:
    """Publish explain_plan rows into the metrics registry."""
    from ..timeline import metrics as _metrics
    reg = _metrics.registry()
    reg.gauge("horovod_plan_buckets",
              "Bucket count of the most recently explained exchange plan"
              ).set(len(rows))
    by_bytes = reg.gauge(
        "horovod_plan_bucket_bytes",
        "Raw bytes per bucket of the explained plan",
        labelnames=("bucket", "dtype"))
    by_wire = reg.gauge(
        "horovod_plan_bucket_wire_bytes",
        "Wire bytes per bucket of the explained plan",
        labelnames=("bucket", "dtype"))
    for r in rows:
        labels = {"bucket": str(r["bucket"]), "dtype": r["dtype"]}
        by_bytes.labels(**labels).set(r["bytes"])
        by_wire.labels(**labels).set(r["wire_bytes"])


def render_plan(rows: List[dict]) -> str:
    """Fixed-width table rendering of :func:`explain_plan` rows."""
    if not rows:
        return "(empty plan: no leaves)"
    cols = ("bucket", "dtype", "leaves", "elements", "bytes",
            "wire_bytes", "codec", "packed", "fence", "fuse_key")
    table = [cols] + [tuple(str(r[c]) for c in cols) for r in rows]
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = ["  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
             for row in table]
    lines.insert(1, "  ".join("-" * w for w in widths))
    for r in rows:
        for leg in r.get("legs") or ():
            lines.append(
                f"    bucket {r['bucket']} leg {leg['tag']}: "
                f"{leg['collective']}@{leg['axis']} codec={leg['codec']} "
                f"{leg['wire_dtype']} {leg['elements']}el {leg['nbytes']}B")
    total_raw = sum(r["bytes"] for r in rows)
    total_wire = sum(r["wire_bytes"] for r in rows)
    ratio = f" (ratio {total_raw / total_wire:.1f}x)" \
        if 0 < total_wire < total_raw else ""
    lines.append(f"total: {len(rows)} bucket(s), {total_raw} bytes raw, "
                 f"{total_wire} bytes wire{ratio}")
    return "\n".join(lines)


# -- exchange-plan IR ------------------------------------------------------
#
# One typed plan object for EVERY exchange the framework emits.  Each
# consumer (flat/chunked/hierarchical/compressed allreduce, eager flush,
# ZeRO arena, EF exchange, microbatch pipe, guard screen, serving decode,
# MoE all_to_all) asks ``plan_exchange(family, **spec)`` for its legs and
# then (a) notes each leg into the span ledger verbatim and (b) emits the
# collectives the legs describe.  ``analysis.stepmodel`` rebuilds its
# expected-collective multiset from the SAME memoized plan (the ``audit``
# rows), so expectation and emission can only diverge if an executor
# diverges from its own plan.  Adding a new leg kind = register a kind +
# a family here, consume the legs in ONE executor; spans and auditor
# pick it up with zero new code (the ROADMAP success test; exercised in
# tests/test_plan_ir.py).

#: Registry of leg kinds -> {"bandwidth": dcn|ici|local, "doc": ...}.
#: The scheduler uses the bandwidth class to order ready legs (DCN
#: before ICI before local) and to price them (see leg_cost_seconds).
LEG_KINDS: Dict[str, dict] = {}


def register_leg_kind(kind: str, *, bandwidth: str = "ici",
                      doc: str = "") -> None:
    """Register (or re-register) a leg kind with its bandwidth class."""
    if bandwidth not in ("dcn", "ici", "local"):
        raise ValueError(f"bandwidth class must be dcn|ici|local, "
                         f"got {bandwidth!r}")
    LEG_KINDS[kind] = {"bandwidth": bandwidth, "doc": doc}


register_leg_kind("flat_ar", bandwidth="ici",
                  doc="flat fused-bucket allreduce (single psum)")
register_leg_kind("ici_rs", bandwidth="ici",
                  doc="two-level exchange: intra-slice reduce-scatter")
register_leg_kind("dcn_ar", bandwidth="dcn",
                  doc="two-level exchange: cross-slice hop under DCN codec")
register_leg_kind("ici_ag", bandwidth="ici",
                  doc="two-level exchange: intra-slice allgather")
register_leg_kind("chunked", bandwidth="ici",
                  doc="chunked RS+AG sweep over the wire buffer")
register_leg_kind("zero_rs", bandwidth="ici",
                  doc="ZeRO arena reduce-scatter (or psum fallback)")
register_leg_kind("zero_ag", bandwidth="ici",
                  doc="ZeRO arena shard allgather")
register_leg_kind("ef", bandwidth="ici",
                  doc="error-feedback exchange (ledger + factored legs)")
register_leg_kind("fp8", bandwidth="ici",
                  doc="quantized fp8 gather-sum allreduce")
register_leg_kind("mb_rs", bandwidth="ici",
                  doc="microbatch pipe per-microbatch reduce-scatter")
register_leg_kind("mb_ag", bandwidth="ici",
                  doc="microbatch pipe closing allgather")
register_leg_kind("guard", bandwidth="ici",
                  doc="SDC guard screen vector psum")
register_leg_kind("serving_psum", bandwidth="ici",
                  doc="serving TP decode row-parallel activation psum")
register_leg_kind("serving_verify", bandwidth="ici",
                  doc="speculative-verify row-parallel activation psum")
register_leg_kind("moe_a2a", bandwidth="ici",
                  doc="MoE dispatch/combine all_to_all")
register_leg_kind("kernel", bandwidth="local",
                  doc="Pallas kernel contract: no wire traffic")


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """A full exchange plan: an ordered tuple of typed legs.

    Hashable and memoized by :func:`plan_exchange`; ``fingerprint`` is a
    process-stable key for whole-plan executable memoization (see
    :func:`plan_executable`)."""
    family: str
    legs: Tuple[ExchangeLeg, ...]

    @property
    def fingerprint(self) -> str:
        digest = hashlib.sha1(
            repr((self.family, self.legs)).encode()).hexdigest()[:16]
        return f"{self.family}:{len(self.legs)}:{digest}"

    def by_tag(self, tag: str) -> Tuple[ExchangeLeg, ...]:
        return tuple(l for l in self.legs if l.tag == tag)

    def by_kind(self, kind: str) -> Tuple[ExchangeLeg, ...]:
        return tuple(l for l in self.legs if l.kind == kind)

    def wire_bytes(self) -> int:
        return int(sum(l.nbytes for l in self.legs))

    def ops(self) -> List[Tuple[str, str, int, str]]:
        return ops_from_legs(self.legs)


def ops_from_legs(legs: Sequence[ExchangeLeg],
                  tag: Optional[str] = None
                  ) -> List[Tuple[str, str, int, str]]:
    """Flatten legs' audit contracts into ``(kind, dtype, elements,
    label)`` rows -- the stepmodel's ExpectedOp tuples.  ``tag`` prefixes
    each row's label (default: the leg's span tag; pass ``""`` for
    families whose audit rows carry complete labels)."""
    out: List[Tuple[str, str, int, str]] = []
    for leg in legs:
        prefix = leg.tag if tag is None else tag
        for kind, dt, elements, suffix in leg.audit:
            label = f"{prefix}/{suffix}" if prefix else suffix
            out.append((kind, dt, int(elements), label))
    return out


def _wire_cast_dtype(comp, dtype) -> "jnp.dtype":
    """Dtype a cast codec puts on the wire for a ``dtype`` bucket
    (identical condition to ``stepmodel._wire_dtype``)."""
    dt = jnp.dtype(dtype)
    wd = getattr(comp, "wire_dtype", None)
    if (wd is not None and jnp.issubdtype(dt, jnp.floating)
            and dt.itemsize > jnp.dtype(wd).itemsize):
        return jnp.dtype(wd)
    return dt


_XPLAN_BUILDERS: Dict[str, Any] = {}
_XPLAN_CANON: Dict[str, Any] = {}


def register_plan_family(family: str, builder, canon=None) -> None:
    """Register an exchange-plan family.

    ``builder(spec) -> List[ExchangeLeg]`` produces the legs from a
    CANONICAL spec dict; ``canon(spec) -> spec`` normalizes caller
    arguments into that canonical, hashable form (so an executor call
    and a stepmodel call that mean the same exchange share one cache
    entry).  This is the only extension point new leg kinds need."""
    _XPLAN_BUILDERS[family] = builder
    if canon is not None:
        _XPLAN_CANON[family] = canon


def plan_exchange(family: str, **spec) -> ExchangePlan:
    """THE planner: one memoized entry point for every exchange family.

    Canonicalizes ``spec``, folds the eager fence policy into the memo
    key (plans are mesh-platform-scoped), and builds the leg list at
    most once per distinct exchange shape.  All executors and the
    read-only consumers (``stepmodel``/``explain_plan``/spans) call
    through here, so replans are shared across train, eager and serving
    steps (see ``plan_cache_stats``)."""
    if family not in _XPLAN_BUILDERS:
        raise ValueError(
            f"unknown exchange-plan family {family!r} "
            f"(registered: {sorted(_XPLAN_BUILDERS)})")
    canon = _XPLAN_CANON.get(family)
    cspec = canon(spec) if canon is not None \
        else {k: spec[k] for k in sorted(spec)}
    fence = _fence_policy()
    key = ("xplan", family, fence) + tuple(sorted(cspec.items()))

    def build() -> ExchangePlan:
        legs = tuple(dataclasses.replace(l, fence=fence)
                     for l in _XPLAN_BUILDERS[family](cspec))
        return ExchangePlan(family=family, legs=legs)

    return _memo(key, build)


# -- family canons + builders ----------------------------------------------

def _parse_comp(comp):
    from ..collectives.compression import Compression, parse_compression
    return parse_compression(comp) if comp is not None else Compression.none


def _canon_flat(spec: dict) -> dict:
    comp = _parse_comp(spec.get("compression"))
    dt = _wire_cast_dtype(comp, spec.get("dtype", "float32"))
    return {"size": int(spec["size"]), "wire_dtype": str(dt),
            "axis": str(spec.get("axis", ""))}


def _build_flat(spec: dict) -> List[ExchangeLeg]:
    dt = jnp.dtype(spec["wire_dtype"])
    size = spec["size"]
    return [ExchangeLeg(
        tag="flat_ar", axis=spec["axis"], collective="psum", codec="none",
        wire_dtype=str(dt), elements=size, nbytes=size * dt.itemsize,
        kind="flat_ar", audit=(("psum", str(dt), size, "allreduce"),))]


def _canon_hier(spec: dict) -> dict:
    from ..collectives.compression import Compression, is_hier_legs
    dt = jnp.dtype(spec.get("dtype", "float32"))
    floating = jnp.issubdtype(dt, jnp.floating)
    ici_c = spec.get("ici_codec")
    dcn_c = spec.get("dcn_codec")
    if ici_c is None and dcn_c is None:
        comp = _parse_comp(spec.get("compression"))
        if is_hier_legs(comp):
            ici_c, dcn_c = comp.ici, comp.dcn
        elif getattr(comp, "wire_format", ""):
            raise ValueError(
                f"{comp.__name__} is an exchange-level codec; the "
                f"two-level path takes it per leg (ici:...,dcn:...)")
        else:
            # A flat cast codec compresses the bucket BEFORE the
            # exchange: the op sees the already-cast buffer, so every
            # leg (padding, shard, wire accounting) lives in the wire
            # domain.
            wd = getattr(comp, "wire_dtype", None)
            if (floating and wd is not None
                    and jnp.dtype(wd).itemsize < dt.itemsize):
                dt = jnp.dtype(wd)
            ici_c = dcn_c = Compression.none
    else:
        ici_c = ici_c if ici_c is not None else Compression.none
        dcn_c = dcn_c if dcn_c is not None else Compression.none
    if not floating:
        ici_c = dcn_c = Compression.none
    return {"size": int(spec["size"]), "dtype": str(dt),
            "n_dcn": int(spec["n_dcn"]), "n_ici": int(spec["n_ici"]),
            "ici": ici_c, "dcn": dcn_c,
            "dcn_axis": str(spec.get("dcn_axis", "dcn")),
            "ici_axis": str(spec.get("ici_axis", "ici"))}


def _build_hier(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.compression import (is_error_feedback, is_fp8,
                                           is_powersgd,
                                           powersgd_factor_widths,
                                           topk_count, wire_payload_bytes)
    from ..collectives.ops import microbatch_pad_quantum
    size = spec["size"]
    dt = jnp.dtype(spec["dtype"])
    floating = jnp.issubdtype(dt, jnp.floating)
    n_dcn, n_ici = spec["n_dcn"], spec["n_ici"]
    ici_c, dcn_c = spec["ici"], spec["dcn"]
    dcn_axis, ici_axis = spec["dcn_axis"], spec["ici_axis"]
    if n_dcn <= 1:
        # Single slice: the op statically falls back to the flat psum.
        return [ExchangeLeg(
            tag="flat_ar", axis=f"{dcn_axis},{ici_axis}",
            collective="psum", codec="none", wire_dtype=str(dt),
            elements=size, nbytes=size * dt.itemsize, kind="flat_ar",
            audit=(("psum", str(dt), size, "flat-ar"),))]
    quantum = microbatch_pad_quantum(n_ici)
    padded = size + (-size) % quantum
    shard = padded // n_ici
    itemsize = dt.itemsize
    ici_itemsize = itemsize
    ici_dt = str(dt)
    wd = getattr(ici_c, "wire_dtype", None)
    if floating and wd is not None and jnp.dtype(wd).itemsize < itemsize:
        ici_itemsize = jnp.dtype(wd).itemsize
        ici_dt = str(jnp.dtype(wd))
    if floating and is_powersgd(dcn_c):
        dcn_coll, dcn_dt = "powersgd", "float32"
        pw, qw = powersgd_factor_widths(shard, dcn_c.rank)
        dcn_audit = (("psum", "float32", pw, "dcn-psum-P"),
                     ("psum", "float32", qw, "dcn-psum-Q"))
    elif floating and is_error_feedback(dcn_c):
        dcn_coll, dcn_dt = "topk", "float32"
        k = min(topk_count(shard, dcn_c.fraction), shard)
        dcn_audit = (("all_gather", "float32", k, "dcn-gather-values"),
                     ("all_gather", "int32", k, "dcn-gather-indices"))
    elif floating and is_fp8(dcn_c):
        # Quantized gather-sum: e4m3 shards + one f32 scale per slice.
        dcn_coll, dcn_dt = "fp8_gather", "float8_e4m3fn"
        dcn_audit = (("all_gather", "float8_e4m3fn", shard,
                      "dcn-gather-q"),
                     ("all_gather", "float32", 1, "dcn-gather-scale"))
    else:
        dcn_coll = "psum"
        dwd = getattr(dcn_c, "wire_dtype", None)
        dcn_dt = str(jnp.dtype(dwd)) if floating and dwd is not None \
            and jnp.dtype(dwd).itemsize < itemsize else str(dt)
        dcn_audit = (("psum", dcn_dt, shard, "dcn-ar"),)
    return [
        ExchangeLeg(tag="hier/ici_rs", axis=ici_axis,
                    collective="reduce_scatter", codec=ici_c.__name__,
                    wire_dtype=ici_dt, elements=padded,
                    nbytes=padded * ici_itemsize, kind="ici_rs",
                    audit=(("reduce_scatter", ici_dt, padded, "ici-rs"),)),
        ExchangeLeg(tag="hier/dcn_ar", axis=dcn_axis, collective=dcn_coll,
                    codec=dcn_c.__name__, wire_dtype=dcn_dt,
                    elements=shard,
                    nbytes=wire_payload_bytes(dcn_c, shard, itemsize),
                    kind="dcn_ar", audit=dcn_audit),
        ExchangeLeg(tag="hier/ici_ag", axis=ici_axis,
                    collective="all_gather", codec=ici_c.__name__,
                    wire_dtype=ici_dt, elements=shard,
                    nbytes=padded * ici_itemsize, kind="ici_ag",
                    audit=(("all_gather", ici_dt, shard, "ici-ag"),)),
    ]


def _canon_chunked(spec: dict) -> dict:
    comp = _parse_comp(spec.get("compression"))
    dt = _wire_cast_dtype(comp, spec.get("dtype", "float32"))
    return {"size": int(spec["size"]), "wire_dtype": str(dt),
            "chunk_bytes": int(spec["chunk_bytes"]),
            "world": int(spec["world"])}


def _build_chunked(spec: dict) -> List[ExchangeLeg]:
    dt = jnp.dtype(spec["wire_dtype"])
    size, world = spec["size"], spec["world"]
    item = dt.itemsize
    chunk_elems = max(1, spec["chunk_bytes"] // item)
    chunk_elems += (-chunk_elems) % world
    audit: List[Tuple[str, str, int, str]] = []
    for j, off in enumerate(range(0, size, chunk_elems)):
        piece = min(chunk_elems, size - off)
        padded = piece + (-piece) % world
        audit.append(("reduce_scatter", str(dt), padded, f"chunk{j}-rs"))
        audit.append(("all_gather", str(dt), padded // world,
                      f"chunk{j}-ag"))
    return [ExchangeLeg(
        tag="chunked_rs_ag", axis="", collective="reduce_scatter",
        codec="none", wire_dtype=str(dt), elements=size,
        nbytes=size * item, kind="chunked", audit=tuple(audit))]


def _canon_powersgd(spec: dict) -> dict:
    return {"size": int(spec["size"]), "rank": int(spec["rank"])}


def _build_powersgd(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.compression import (powersgd_compressor,
                                           powersgd_factor_widths,
                                           powersgd_matrix_shape)
    size, rank = spec["size"], spec["rank"]
    m, c = powersgd_matrix_shape(size)
    r = max(1, min(rank, m, c))
    pw, qw = powersgd_factor_widths(size, rank)
    return [ExchangeLeg(
        tag="powersgd_allreduce", axis="", collective="powersgd",
        codec=powersgd_compressor(rank).__name__, wire_dtype="float32",
        elements=size, nbytes=2 * r * (m + c) * 4, kind="ef",
        audit=(("psum", "float32", pw, "psum-P"),
               ("psum", "float32", qw, "psum-Q")))]


def _canon_topk(spec: dict) -> dict:
    return {"size": int(spec["size"]), "fraction": float(spec["fraction"])}


def _build_topk(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.compression import topk_compressor, topk_count
    size = spec["size"]
    k = min(topk_count(size, spec["fraction"]), size)
    return [ExchangeLeg(
        tag="topk_allreduce", axis="", collective="topk",
        codec=topk_compressor(spec["fraction"]).__name__,
        wire_dtype="float32", elements=size, nbytes=8 * k, kind="ef",
        audit=(("all_gather", "float32", k, "gather-values"),
               ("all_gather", "int32", k, "gather-indices")))]


def _canon_fp8(spec: dict) -> dict:
    return {"size": int(spec["size"]), "world": int(spec["world"])}


def _build_fp8(spec: dict) -> List[ExchangeLeg]:
    size, world = spec["size"], spec["world"]
    padded = size + (-size) % world
    # stepmodel declines the flat fp8 path (unmodeled), so no audit rows.
    return [ExchangeLeg(
        tag="fp8_allreduce", axis="", collective="fp8_gather",
        codec="fp8", wire_dtype="float8_e4m3fn", elements=padded,
        nbytes=2 * padded, kind="fp8", audit=())]


def _canon_ef(spec: dict) -> dict:
    comp = _parse_comp(spec["compression"])
    return {"size": int(spec["size"]),
            "dtype": str(jnp.dtype(spec["dtype"])), "comp": comp}


def _build_ef(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.compression import is_powersgd, wire_payload_bytes
    comp = spec["comp"]
    size = spec["size"]
    dt = jnp.dtype(spec["dtype"])
    ledger_nbytes = wire_payload_bytes(comp, size, dt.itemsize)
    if not jnp.issubdtype(dt, jnp.floating):
        # Non-float buckets ride the plain flat psum; the ledger leg IS
        # the exchange.
        return [ExchangeLeg(
            tag="ef_exchange", axis="", collective="psum",
            codec=comp.__name__, wire_dtype=str(dt), elements=size,
            nbytes=ledger_nbytes, kind="ef",
            audit=(("psum", str(dt), size, "allreduce"),))]
    # Floating buckets: the ledger leg accounts the factored wire payload
    # once (audit-free), and the nested powersgd/topk leg carries the
    # collective contract (its own note fires inside the op).
    ledger = ExchangeLeg(
        tag="ef_exchange", axis="", collective="ledger",
        codec=comp.__name__, wire_dtype="float32", elements=size,
        nbytes=ledger_nbytes, kind="ef", audit=())
    if is_powersgd(comp):
        nested = _build_powersgd({"size": size, "rank": int(comp.rank)})
    else:
        nested = _build_topk({"size": size,
                              "fraction": float(comp.fraction)})
    return [ledger] + nested


def _canon_zero(spec: dict) -> dict:
    comp = _parse_comp(spec.get("compression"))
    ax_shape = spec.get("axes_shape")
    ax_shape = tuple(int(a) for a in ax_shape) \
        if ax_shape and len(ax_shape) == 2 else None
    axes = spec.get("axes") or ()
    axes = tuple(str(a) for a in axes) if ax_shape is not None else ()
    return {"buffers": tuple(
                (str(jnp.dtype(d)), int(s), int(p), int(sh))
                for d, s, p, sh in spec["buffers"]),
            "world": int(spec["world"]), "comp": comp,
            "axes_shape": ax_shape, "axes": axes,
            "use_rs": bool(spec["use_rs"])}


def _build_zero(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.compression import is_hier_legs
    comp = spec["comp"]
    use_rs = spec["use_rs"]
    two_level = spec["axes_shape"]
    hier = is_hier_legs(comp) and two_level is not None
    axis = ",".join(spec["axes"])
    if two_level is not None:
        n_dcn, n_ici = two_level
        # Axis extents in the order the RS loop scatters over them: a
        # per-leg codec flips to (ici, dcn) so only the 1/n_ici shard
        # crosses DCN.
        rs_order = (n_ici, n_dcn) if hier else (n_dcn, n_ici)
    rs_legs: List[ExchangeLeg] = []
    ag_legs: List[ExchangeLeg] = []
    for i, (dts, size, padded, shard) in enumerate(spec["buffers"]):
        item = jnp.dtype(dts).itemsize
        rs_audit: Tuple = ()
        ag_audit: Tuple = ()
        if size >= 1:
            if use_rs and two_level is not None:
                rows = []
                running = padded
                for j, n_a in enumerate(rs_order):
                    rows.append(("reduce_scatter", dts, running,
                                 f"reduce-scatter-ax{j}"))
                    running //= n_a
                rs_audit = tuple(rows)
            elif use_rs:
                rs_audit = (("reduce_scatter", dts, padded,
                             "reduce-scatter"),)
            else:
                rs_audit = (("psum", dts, padded, "allreduce"),)
            if hier:
                # compressed_allgather over (dcn,) then (ici,), each hop
                # at its leg codec's wire dtype.
                ag_audit = (
                    ("all_gather", str(_wire_cast_dtype(comp.dcn, dts)),
                     shard, "allgather-dcn"),
                    ("all_gather", str(_wire_cast_dtype(comp.ici, dts)),
                     shard * n_dcn, "allgather-ici"))
            elif two_level is not None:
                # ops.allgather gathers reversed(axes): ici first.
                wire = str(_wire_cast_dtype(comp, dts))
                ag_audit = (("all_gather", wire, shard, "allgather-ici"),
                            ("all_gather", wire, shard * n_ici,
                             "allgather-dcn"))
            else:
                ag_audit = (("all_gather",
                             str(_wire_cast_dtype(comp, dts)), shard,
                             "allgather"),)
        rs_legs.append(ExchangeLeg(
            tag="zero_rs" if use_rs else "zero_allreduce", axis=axis,
            collective="reduce_scatter" if use_rs else "psum",
            codec="none", wire_dtype=dts, elements=padded,
            nbytes=padded * item, kind="zero_rs", bucket=i,
            audit=rs_audit))
        ag_legs.append(ExchangeLeg(
            tag="zero_ag", axis=axis, collective="all_gather",
            codec=comp.__name__, wire_dtype=dts, elements=shard,
            nbytes=shard * item, kind="zero_ag", bucket=i,
            audit=ag_audit))
    # RS legs for every arena, then AG legs: the executor's note order.
    return rs_legs + ag_legs


def _canon_microbatch(spec: dict) -> dict:
    comp = _parse_comp(spec.get("compression"))
    return {"buffers": tuple((str(jnp.dtype(d)), int(s))
                             for d, s in spec["buffers"]),
            "k": int(spec["k"]), "world": int(spec["world"]),
            "comp": comp}


def _build_microbatch(spec: dict) -> List[ExchangeLeg]:
    from ..collectives.ops import microbatch_pad_quantum
    comp = spec["comp"]
    k, world = spec["k"], spec["world"]
    q = microbatch_pad_quantum(world)
    rs_legs: List[ExchangeLeg] = []
    ag_legs: List[ExchangeLeg] = []
    for i, (dts, size) in enumerate(spec["buffers"]):
        padded = size + (-size) % q
        wire = _wire_cast_dtype(comp, dts)
        rs_legs.append(ExchangeLeg(
            tag="microbatch_rs", axis="", collective="reduce_scatter",
            codec=comp.__name__, wire_dtype=str(wire), elements=padded,
            nbytes=size * wire.itemsize, kind="mb_rs", bucket=i,
            audit=tuple(("reduce_scatter", str(wire), padded,
                         f"scatter-mb{j}") for j in range(k))))
        ag_legs.append(ExchangeLeg(
            tag="microbatch_ag", axis="", collective="all_gather",
            codec=comp.__name__, wire_dtype=str(wire),
            elements=padded // world,
            nbytes=(padded // world) * wire.itemsize, kind="mb_ag",
            bucket=i,
            audit=(("all_gather", str(wire), padded // world,
                    "allgather"),)))
    return rs_legs + ag_legs


def _canon_serving(spec: dict) -> dict:
    return {"kind": str(spec.get("kind", "serving_decode")),
            "layers": int(spec["layers"]), "slots": int(spec["slots"]),
            "width": int(spec.get("width", 1)),
            "d_model": int(spec["d_model"]),
            "dtype": str(jnp.dtype(spec.get("dtype", "float32"))),
            "axis": str(spec.get("axis", "tp"))}


def _build_serving(spec: dict) -> List[ExchangeLeg]:
    kind = spec["kind"]
    leg_kind = "serving_verify" if kind == "serving_verify" \
        else "serving_psum"
    dt = jnp.dtype(spec["dtype"])
    elements = spec["slots"] * spec["width"] * spec["d_model"]
    nbytes = elements * dt.itemsize
    legs = []
    for li in range(spec["layers"]):
        for part in ("attn_wo", "mlp_down"):
            legs.append(ExchangeLeg(
                tag=f"{kind}/layer{li}/{part}", axis=spec["axis"],
                collective="psum", codec="none", wire_dtype=str(dt),
                elements=elements, nbytes=nbytes, kind=leg_kind,
                bucket=li,
                audit=(("psum", str(dt), elements,
                        f"layer{li}/{part}/allreduce"),)))
    return legs


def _build_guard(spec: dict) -> List[ExchangeLeg]:
    # The 2-wide screen vector psum the SDC guard prepends to the step.
    return [ExchangeLeg(
        tag="guard/screen", axis="", collective="psum", codec="none",
        wire_dtype="float32", elements=2, nbytes=8, kind="guard",
        audit=(("psum", "float32", 2, "guard/screen"),))]


def _canon_moe(spec: dict) -> dict:
    from ..parallel.moe import resolve_moe_compression
    return {"n_experts": int(spec["n_experts"]),
            "capacity": int(spec["capacity"]),
            "d_model": int(spec["d_model"]),
            "dtype": str(jnp.dtype(spec.get("dtype", jnp.float32))),
            "codec": resolve_moe_compression(spec.get("compression")),
            "axis": str(spec.get("axis", "model"))}


def _build_moe(spec: dict) -> List[ExchangeLeg]:
    from ..parallel.moe import _MOE_CODECS
    wire = _MOE_CODECS[spec["codec"]]
    dt = jnp.dtype(spec["dtype"])
    wire_dt = jnp.dtype(wire) if wire is not None else dt
    elements = spec["n_experts"] * spec["capacity"] * spec["d_model"]
    nbytes = elements * wire_dt.itemsize
    return [ExchangeLeg(
        tag=f"moe/a2a_{name}", axis=spec["axis"],
        collective="all_to_all", codec=spec["codec"],
        wire_dtype=str(wire_dt), elements=elements, nbytes=nbytes,
        kind="moe_a2a",
        audit=(("all_to_all", str(wire_dt), elements, f"a2a-{name}"),))
        for name in ("dispatch", "combine")]


def _canon_kernel(spec: dict) -> dict:
    return {"kernel": str(spec["kernel"]), "nbytes": int(spec["nbytes"])}


def _build_kernel(spec: dict) -> List[ExchangeLeg]:
    # Kernel contract: HBM traffic accounting only, no wire collective.
    return [ExchangeLeg(
        tag=f"pallas/{spec['kernel']}", axis="", collective="none",
        codec="none", wire_dtype="", elements=0, nbytes=spec["nbytes"],
        kind="kernel", kernel=spec["kernel"], audit=())]


register_plan_family("flat", _build_flat, _canon_flat)
register_plan_family("hier", _build_hier, _canon_hier)
register_plan_family("chunked", _build_chunked, _canon_chunked)
register_plan_family("powersgd", _build_powersgd, _canon_powersgd)
register_plan_family("topk", _build_topk, _canon_topk)
register_plan_family("fp8", _build_fp8, _canon_fp8)
register_plan_family("ef", _build_ef, _canon_ef)
register_plan_family("zero", _build_zero, _canon_zero)
register_plan_family("microbatch", _build_microbatch, _canon_microbatch)
register_plan_family("serving", _build_serving, _canon_serving)
register_plan_family("guard", _build_guard)
register_plan_family("moe", _build_moe, _canon_moe)
register_plan_family("kernel", _build_kernel, _canon_kernel)


def hier_mesh_axes() -> Optional[Tuple[str, str]]:
    """``(dcn_axis, ici_axis)`` names of the two-level world mesh, else
    ``None`` -- so read-only consumers canonicalize hier plans with the
    SAME axis names the executor uses (one cache entry, not two)."""
    st = global_state()
    m = st.mesh
    if m is None:
        return None
    names = tuple(m.axis_names)
    if len(names) != 2:
        return None
    return (str(names[0]), str(names[1]))


# -- overlap-aware leg scheduler -------------------------------------------

_BW_RANK = {"dcn": 2, "ici": 1, "local": 0}


def leg_bandwidth(leg: ExchangeLeg) -> str:
    """Bandwidth class a leg occupies: its kind's registered class,
    promoted to ``dcn`` when the leg's axis list names the DCN axis
    (e.g. a ZeRO allgather whose outer hop crosses slices)."""
    cls = LEG_KINDS.get(leg.kind, {}).get("bandwidth", "ici")
    if cls == "local":
        return "local"
    axes = tuple(a.strip() for a in leg.axis.split(",") if a.strip())
    if cls == "dcn" or "dcn" in axes:
        return "dcn"
    return cls


def leg_cost_seconds(leg: ExchangeLeg, chip=None) -> float:
    """Modeled issue cost: leg wire bytes over the bandwidth class's
    effective allreduce rate (the autotuner's contended-DCN ChipSpec
    model; defaults to v5e)."""
    bw = leg_bandwidth(leg)
    if bw == "local":
        return 0.0
    if chip is None:
        from ..utils.scaling import V5E
        chip = V5E
    rate = chip.dcn_allreduce_bytes_per_s if bw == "dcn" \
        else chip.ici_allreduce_bytes_per_s
    return float(leg.nbytes) / max(float(rate), 1.0)


def schedule_legs(legs: Sequence[ExchangeLeg], mode: Optional[str] = None,
                  chip=None) -> List[ExchangeLeg]:
    """Order legs for issue: bandwidth-aware greedy list scheduling.

    Legs sharing a ``bucket`` form an ordered dependency chain (RS ->
    hop -> AG must stay in plan order); across chains the scheduler
    replays the two-link contention model :func:`simulate_issue` prices
    and repeatedly issues the chain head that can START earliest --
    breaking ties by slowest bandwidth class (DCN before ICI before
    local), then modeled cost, then plan order.  A chain's downstream
    leg (an AG waiting on its DCN hop) therefore never head-of-line
    blocks its link while an independent chain's leg is ready: the idle
    window the hop leaves on the ICI link is filled with the next
    bucket's RS.  ``mode="program"`` (or
    ``HOROVOD_EXCHANGE_SCHEDULE=program``) returns plan order.
    Deterministic in its inputs: safe to call at trace time under SPMD.
    """
    mode = exchange_schedule_mode() if mode is None else str(mode)
    ordered = list(legs)
    if mode != "bandwidth" or len(ordered) <= 1:
        return ordered
    chains: Dict[int, List[int]] = {}
    for idx, leg in enumerate(ordered):
        chains.setdefault(int(leg.bucket), []).append(idx)
    heads = {b: 0 for b in chains}
    free = {"dcn": 0.0, "ici": 0.0}
    done: Dict[int, float] = {}
    out: List[ExchangeLeg] = []
    while len(out) < len(ordered):
        best = None
        for b in chains:
            pos = heads[b]
            if pos >= len(chains[b]):
                continue
            idx = chains[b][pos]
            leg = ordered[idx]
            bw = leg_bandwidth(leg)
            start = max(free.get(bw, 0.0), done.get(b, 0.0))
            score = (start, -_BW_RANK.get(bw, 1),
                     -leg_cost_seconds(leg, chip), idx)
            if best is None or score < best[0]:
                best = (score, b, idx)
        assert best is not None
        _, b, idx = best
        heads[b] += 1
        leg = ordered[idx]
        bw = leg_bandwidth(leg)
        start = max(free.get(bw, 0.0), done.get(b, 0.0))
        end = start + leg_cost_seconds(leg, chip)
        if bw in free:
            free[bw] = end
        done[b] = end
        out.append(leg)
    return out


def overlap_phases(legs: Sequence[ExchangeLeg], k: int,
                   mode: Optional[str] = None,
                   chip=None) -> List[List[ExchangeLeg]]:
    """Partition scheduled legs into ``k`` issue phases, one per
    backward microbatch: the generalization of the ``microbatches=k``
    overlap to arbitrary leg DAGs.  Phase ``j`` holds the legs that go
    on the wire while microbatch ``j``'s backward still computes;
    round-robin over the scheduled order keeps every phase's class mix
    balanced (each phase leads with the most-contended ready leg)."""
    k = max(int(k), 1)
    ordered = schedule_legs(legs, mode=mode, chip=chip)
    phases: List[List[ExchangeLeg]] = [[] for _ in range(k)]
    for i, leg in enumerate(ordered):
        phases[i % k].append(leg)
    return phases


def simulate_issue(legs: Sequence[ExchangeLeg], chip=None) -> dict:
    """Price an issue order on the two-link contention model.

    Each bandwidth class is one link; a leg starts when its link is free
    AND its bucket's previous leg finished (the RS->hop->AG chain).
    Returns the modeled makespan, per-class busy seconds, and the
    dispatch-gap fraction: how much of the makespan the critical link
    sits idle waiting on dispatch order.  Purely a host-side model -- it
    never touches the wire."""
    free = {"dcn": 0.0, "ici": 0.0}
    busy = {"dcn": 0.0, "ici": 0.0}
    done: Dict[int, float] = {}
    makespan = 0.0
    for leg in legs:
        bw = leg_bandwidth(leg)
        cost = leg_cost_seconds(leg, chip)
        start = max(free.get(bw, 0.0), done.get(int(leg.bucket), 0.0))
        end = start + cost
        if bw in free:
            free[bw] = end
            busy[bw] += cost
        done[int(leg.bucket)] = end
        makespan = max(makespan, end)
    crit = max(busy.values()) if any(busy.values()) else 0.0
    gap = max(0.0, 1.0 - crit / makespan) if makespan > 0 else 0.0
    return {"makespan_s": makespan, "busy_s": dict(busy),
            "dispatch_gap_fraction": gap}


def plan_executable(plan: ExchangePlan, build, extra: Tuple = ()):
    """Memoize a whole-plan executable by plan fingerprint.

    Steps that share exchange structure (an eager flush, a serving
    decode step, a train step replayed under a new closure) share one
    compiled executable through the session ``ExecutableCache`` --
    ``build()`` runs at most once per (fingerprint, extra).  Falls back
    to the plan cache before ``hvd.init`` wires the session cache."""
    if not plan_cache_enabled():
        return build()
    st = global_state()
    cache = st.cache if st.cache is not None else _get_plan_cache()
    return cache.get_or_build(
        ("plan_exec", plan.fingerprint) + tuple(extra), build)
