"""DistributedOptimizer: gradient allreduce fused into the update.

JAX-native analogue of ``horovod/torch/optimizer.py::DistributedOptimizer``
(grad-hook allreduce + ``synchronize()`` before ``step()``) and
``horovod/tensorflow/__init__.py::DistributedGradientTape``.  Because the
whole step is traced, the "hook + background negotiation + synchronize"
machinery collapses into a pure function: gradients are bucketed through
the fusion planner, one ``psum`` per bucket is emitted inside the step, and
XLA overlaps those collectives with the backward pass automatically (the
latency-hiding the reference needs its async enqueue machinery for).

Supports the reference's knobs: reduce op (Average/Sum/Adasum), fp16/bf16
compression, process sets, prescale/postscale,
``backward_passes_per_step`` (local gradient accumulation: N-1 steps
accumulate locally, the Nth allreduces the running sum -- same traffic
saving as the reference's ``backward_passes_per_step``).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..collectives import ops as _ops
from ..collectives.compression import (Compression, is_error_feedback,
                                       is_hier_legs, is_powersgd,
                                       parse_compression,
                                       wire_payload_bytes)
from ..collectives.reduce_op import ReduceOp, Average
from ..controller.fusion import fused_tree_collective


def _resolve_compression(compression):
    """``None`` defers to ``HOROVOD_COMPRESSION`` (a spec string resolved
    through :func:`parse_compression`); an explicit codec or spec string is
    taken as-is.  Passing ``Compression.none`` explicitly disables the env
    default."""
    if compression is None:
        from ..core.state import global_state
        cfg = global_state().config
        spec = cfg.compression if cfg is not None else None
        return parse_compression(spec)
    return parse_compression(compression)


def _ef_enabled() -> bool:
    """``HOROVOD_EF_RESIDUAL`` (default on): whether the EF codecs carry
    residual state across steps.  Off means the compression error is
    dropped every step -- useful only for ablations."""
    from ..core.state import global_state
    cfg = global_state().config
    return cfg.ef_residual if cfg is not None else True


def _hier_axes(axes):
    """Resolve ``axes`` to the two-level ``(dcn, ici)`` pair, or ``None``
    when the effective mesh is flat (single axis)."""
    from ..core.state import global_state
    if axes is None:
        mesh = global_state().mesh
        ax = tuple(mesh.axis_names) if mesh is not None else ()
    else:
        ax = tuple((axes,) if isinstance(axes, str) else axes)
    return ax if len(ax) == 2 else None


def _stateless_ef_collective(buf, compression, op, axes,
                             prescale_factor, postscale_factor):
    """One EF-codec exchange with no residual (autotune sampling, direct
    ``allreduce_gradients`` calls, the eager path).  Non-floating buckets
    fall back to the plain allreduce -- the codecs are float-only."""
    if not jnp.issubdtype(buf.dtype, jnp.floating):
        return _ops.allreduce(buf, op, axes=axes,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor)
    if is_hier_legs(compression):
        pair = _hier_axes(axes)
        if pair is None:
            # Flat mesh: the DCN hop degenerates; run the EF codec over
            # the whole (single-axis) world instead.
            compression = compression.dcn
        else:
            out, _ = _ops.hierarchical_allreduce(
                buf, op, dcn_axis=pair[0], ici_axis=pair[1],
                dcn_codec=compression.dcn, ici_codec=compression.ici,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
            return out
    if is_powersgd(compression):
        out, _ = _ops.powersgd_allreduce(
            buf, op, rank=compression.rank, axes=axes,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    else:
        out, _ = _ops.topk_allreduce(
            buf, op, fraction=compression.fraction, axes=axes,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    return out


class _Route(NamedTuple):
    """What :func:`allreduce_gradients` dispatches on, resolved once."""
    compression: Any      # the codec in force (the tuner may override)
    explicit_hier: bool   # the two-level exchange is asked for
    chunk_bytes: int      # > 0: the chunked RS+AG decomposition
    axes: tuple           # the reduce axes' names
    packed: bool          # fusion.exchange_needs_vector: buckets are built


def _exchange_route(compression, op, axes, process_set) -> _Route:
    from ..collectives.compression import is_fp8
    from ..collectives.reduce_op import Adasum as _Adasum
    from ..controller.fusion import (exchange_chunk_bytes,
                                     exchange_needs_vector)
    from ..core.state import global_state
    st = global_state()
    tuner = st.autotuner
    if tuner is not None:
        override = tuner.compression_override(compression)
        if (is_fp8(override) and not is_fp8(compression)
                and process_set is not None):
            # The tuner's fp8 axis cannot serve subset reductions (the
            # quantized exchange has no masked identity); keep the
            # configured codec for this sample instead of failing it.
            override = compression
        if (is_error_feedback(override)
                and not is_error_feedback(compression)
                and (process_set is not None or op is _Adasum)):
            # Same escape hatch for the tuner's EF-codec axis: the factored/
            # sparse exchanges serve full-mesh Sum/Average only.
            override = compression
        compression = override
        explicit_hier = tuner.hierarchical_explicit()
    else:
        explicit_hier = bool(st.config and st.config.hierarchical_allreduce)
        if not explicit_hier and st.config is not None \
                and st.config.hierarchical:
            # HOROVOD_HIERARCHICAL topology spec implies the two-level
            # exchange (not just the two-level mesh).
            from ..parallel.mesh import parse_topology_spec
            try:
                explicit_hier = parse_topology_spec(st.config.hierarchical)[0]
            except ValueError:
                pass
    if axes is not None:
        ax = tuple((axes,) if isinstance(axes, str) else axes)
    else:
        ax = tuple(st.mesh.axis_names) if st.mesh is not None else ()
    chunk_bytes = exchange_chunk_bytes()
    whole = process_set is None     # a subset rides the flat exchange
    packed = exchange_needs_vector(
        compression, op, two_level=whole and explicit_hier and len(ax) == 2,
        chunked=whole and chunk_bytes > 0)
    return _Route(compression, explicit_hier, chunk_bytes, ax, packed)


def exchange_packs(compression, op: ReduceOp = Average, *, axes=None,
                   process_set=None) -> bool:
    """Whether :func:`allreduce_gradients` would build fusion buffers for
    this exchange at a world above one, under the tuner's and the
    configuration's settings in force: its own route
    (``fusion.exchange_needs_vector`` is the rule)."""
    return _exchange_route(parse_compression(compression), op, axes,
                           process_set).packed


def allreduce_gradients(grads,
                        op: ReduceOp = Average,
                        *,
                        compression=Compression.none,
                        fusion_threshold: Optional[int] = None,
                        axes=None,
                        process_set=None,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0):
    """Fused in-step allreduce of a gradient pytree (the hot path).

    Two further knobs resolve at TRACE time (the reference's
    ParameterManager tunes both; ours does too under
    ``HOROVOD_AUTOTUNE=1``): the hierarchical-allreduce algorithm choice
    on (dcn, ici) meshes (``HOROVOD_HIERARCHICAL_ALLREDUCE`` /
    autotuned) and -- opt-in, it changes wire numerics
    (``HOROVOD_AUTOTUNE_COMPRESSION=1``) -- the compression codec.

    ``Sum``/``Average`` under ``none``/``fp16``/``bf16`` on the flat
    exchange is elementwise, so it builds no fusion buffer: every leaf is
    cast, reduced by a psum of its own and cast back in the layout it has,
    and XLA's all-reduce combiner groups the psums into many-operand
    all-reduces at its own threshold (``fusion_threshold`` does not reach
    this path).  Every exchange that needs a bucket as one contiguous
    vector (``fusion.exchange_needs_vector``) goes through the planner's
    buckets as before.
    """
    from ..collectives.compression import is_fp8
    route = _exchange_route(parse_compression(compression), op, axes,
                            process_set)
    compression, explicit_hier, chunk_bytes, ax, packed = route

    def collective(buf):
        # The stages carry hvd_exchange/ scopes into the HLO text and the
        # trace viewer; an exchange-level codec (EF, fp8, per-leg) is all
        # "collective".
        with jax.named_scope("hvd_exchange/collective"):
            if is_error_feedback(compression):
                # Exchange-level EF codec WITHOUT residual state: the stateful
                # path lives in the DistributedOptimizer wrap (it owns the
                # residual carry); this surface serves tuner samples and
                # direct calls, where dropping the error is acceptable.
                if process_set is not None:
                    raise NotImplementedError(
                        "powersgd/topk do not support process-set reductions "
                        "(no masked identity for a factored/sparse exchange); "
                        "use fp16/bf16 there")
                return _stateless_ef_collective(
                    buf, compression, op, axes, prescale_factor,
                    postscale_factor)
            if is_fp8(compression):
                # Exchange-level codec: the collective itself changes (a psum
                # cannot carry fp8 -- compression.py module docstring).
                from ..collectives.reduce_op import Adasum
                if op is Adasum:
                    return _ops.allreduce(
                        buf, op, axes=axes, process_set=process_set,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor, wire_codec="fp8")
                if process_set is not None:
                    raise NotImplementedError(
                        "Compression.fp8 does not support process-set "
                        "Sum/Average reductions (no masked identity for a "
                        "quantized exchange); use fp16/bf16 there")
                return _ops.fp8_allreduce(
                    buf, op, axes=axes, prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
        with jax.named_scope("hvd_exchange/compress"):
            c, ctx = compression.compress(buf)
        with jax.named_scope("hvd_exchange/collective"):
            hier_ok = (process_set is None and len(ax) == 2
                       and op in (_ops.Sum, Average))
            if is_hier_legs(compression):
                # Per-leg codec (ici:...,dcn:...): the exchange itself is the
                # two-level decomposition with each hop's codec applied on
                # that hop only.  On a flat mesh the DCN hop degenerates, so
                # ride the psum-compatible ICI codec on the flat exchange.
                if hier_ok:
                    r = _ops.hierarchical_allreduce(
                        c, op, dcn_axis=ax[0], ici_axis=ax[1],
                        dcn_codec=compression.dcn, ici_codec=compression.ici,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor)
                    return r
                _note_flat_leg(c, compression.ici)
                ci, ictx = compression.ici.compress(c)
                r = _ops.allreduce(ci, op, axes=axes, process_set=process_set,
                                   prescale_factor=prescale_factor,
                                   postscale_factor=postscale_factor)
                return compression.ici.decompress(r, ictx)
            if explicit_hier and hier_ok:
                r = _ops.hierarchical_allreduce(
                    c, op, dcn_axis=ax[0], ici_axis=ax[1],
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
            elif (chunk_bytes > 0 and process_set is None
                  and op in (_ops.Sum, Average)):
                # HOROVOD_EXCHANGE_CHUNK_MB (or the tuner's chunk axis):
                # decompose the bucket into overlap-friendly RS+AG chunks.
                # Chunking acts on the compressed wire buffer, so it composes
                # with fp16/bf16 codecs.
                r = _ops.chunked_allreduce(
                    c, op, chunk_bytes=chunk_bytes, axes=ax,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
            else:
                _note_flat_leg(buf, compression)
                r = _ops.allreduce(c, op, axes=axes, process_set=process_set,
                                   prescale_factor=prescale_factor,
                                   postscale_factor=postscale_factor)
        with jax.named_scope("hvd_exchange/decompress"):
            return compression.decompress(r, ctx)

    def _note_flat_leg(buf, comp):
        # Flat exchange of one buffer (a packed bucket, or a leaf on the
        # leaf-wise path): register the plan-IR row at trace time (the
        # hier/chunked/fp8/EF paths note inside their ops; a world-1
        # "reduction" is the identity and moves no bytes).
        if world == 1:
            return
        from ..controller import fusion as _fusion
        from ..timeline import spans as _spans
        _spans.note_leg(_fusion.plan_exchange(
            "flat", size=int(buf.size), dtype=str(buf.dtype),
            compression=comp).legs[0])

    # Axis sizes are static at trace time: a one-device reduction is the
    # identity (every reduce op over a single member returns its input), so
    # skip the pack/unpack copies and apply the collective leaf-wise -- XLA
    # deletes the size-1 psum and fuses the scale/compression casts into
    # the surrounding update.  The reference pays its fusion-buffer memcpys
    # even at np=1; knowing the world size at trace time is exactly what
    # lets the TPU build not to.  An elementwise exchange goes the same
    # way at any world: the casts fuse into the backward and the update,
    # and the combiner makes the many-operand all-reduces.
    try:
        world = _ops.axis_size(axes)
    except Exception:  # outside a traced mesh context: keep the fused path
        world = None
    if world == 1 or not packed:
        return jax.tree.map(collective, grads)

    # The codec name rides the plan memo key: an EF-codec plan pins the
    # residual-state shapes, so it must never alias a plain plan of the
    # same leaf list at the same threshold.
    return fused_tree_collective(grads, collective, fusion_threshold,
                                 extra=(compression.__name__,))


class _AccumState(NamedTuple):
    counter: jnp.ndarray          # int32 scalar
    accum: Any                    # gradient-shaped pytree
    inner: Any                    # wrapped optimizer state


class _EFState(NamedTuple):
    """Optimizer-state carry for the error-feedback codecs.

    ``residuals`` is one flat f32 array PER FUSION BUCKET with a leading
    world axis (``[world, bucket_size]`` globally, ``[1, bucket_size]``
    inside the shard-mapped step) -- residuals are PER-RANK state (each
    rank's compression error differs), so ``make_train_step`` shards them
    ``P(axes)`` like ZeRO state while ``inner`` stays replicated.
    """
    residuals: Any                # tuple of [world, bucket_size] f32
    inner: Any                    # wrapped optimizer state


def _ef_threshold(fusion_threshold: Optional[int]) -> int:
    """Bucket threshold for EF plans, resolved ONCE and pinned: residual
    shapes live in the optimizer state, so the autotuner's threshold axis
    must not re-plan under them (config value, never the tuner's)."""
    if fusion_threshold is not None:
        return int(fusion_threshold)
    from ..core.state import global_state
    cfg = global_state().config
    return cfg.fusion_threshold if cfg is not None else 64 * 1024 * 1024


def _ef_world() -> int:
    """Leading residual axis: the FULL mesh size (``make_train_step``
    shards optimizer state over every mesh axis)."""
    from ..core.state import global_state
    mesh = global_state().mesh
    return int(mesh.devices.size) if mesh is not None else 1


def ef_bucket_plan(leaves, fusion_threshold: Optional[int], compression):
    from ..controller.fusion import plan_buckets
    return plan_buckets(leaves, _ef_threshold(fusion_threshold),
                        extra=("ef", compression.__name__))


def ef_residual_shape(size: int, compression) -> tuple:
    """Per-bucket residual row shape (no leading world axis).

    Flat EF codecs carry ``(size,)`` -- the whole bucket's unsent error.
    Per-leg codecs carry ``(2, shard)`` -- one row per leg of the
    two-level exchange, where ``shard`` is the DCN hop's operand width
    (``padded / n_ici``).  The ICI legs are exact reduce-scatter /
    allgather, so leg 0 stays identically zero; leg 1 holds the DCN
    codec's unsent residual.  The leg axis keeps the state
    self-describing for join replay and elastic resize.
    """
    if is_hier_legs(compression):
        from ..core.state import global_state
        mesh = global_state().mesh
        names = tuple(mesh.axis_names) if mesh is not None else ()
        n_ici = int(mesh.shape[names[-1]]) if len(names) == 2 else 1
        quantum = _ops.microbatch_pad_quantum(n_ici)
        padded = size + (-size) % quantum
        return (2, padded // n_ici)
    return (int(size),)


def ef_init_residuals(params, fusion_threshold: Optional[int], compression):
    """Zero residual carry matching the EF bucket plan of ``params``-shaped
    gradients: one ``[world, *ef_residual_shape]`` f32 array per bucket."""
    leaves = jax.tree.leaves(params)
    spec = ef_bucket_plan(leaves, fusion_threshold, compression)
    world = _ef_world()
    return tuple(
        jnp.zeros((world,) + ef_residual_shape(
            sum(s.size for s in lspecs), compression), jnp.float32)
        for _dt, lspecs in spec.buffers)


def _note_compression_ratio(spec, compression) -> None:
    """Host-side ``compression_ratio`` accounting (trace-time: the ratio
    is a pure function of the static bucket shapes).  Feeds the timeline
    counter track when one is active AND the metrics-registry gauges
    unconditionally -- the gauges are set (not incremented) because this
    fires once per trace, not per step; per-step totals come from the
    StepReport instrumentation."""
    from ..controller.fusion import hier_mesh_shape, plan_hier_legs
    from ..core.state import global_state
    hier_shape = hier_mesh_shape() if is_hier_legs(compression) else None
    raw = wire = 0
    for dt, lspecs in spec.buffers:
        size = sum(s.size for s in lspecs)
        itemsize = jnp.dtype(dt).itemsize
        raw += size * itemsize
        if hier_shape is not None:
            wire += sum(l.nbytes for l in plan_hier_legs(
                size, dt, n_dcn=hier_shape[0], n_ici=hier_shape[1],
                compression=compression))
        else:
            wire += wire_payload_bytes(compression, size, itemsize)
    if wire <= 0:
        return
    tl = global_state().timeline
    if tl is not None:
        tl.counters({"compression_ratio": raw / wire,
                     "wire_bytes_per_step": wire,
                     "uncompressed_bytes_per_step": raw})
    from ..timeline import metrics as _metrics
    reg = _metrics.registry()
    reg.gauge("horovod_compression_ratio",
              "uncompressed / wire bytes of the gradient exchange"
              ).set(raw / wire)
    reg.gauge("horovod_wire_bytes_per_step",
              "Per-chip exchange wire bytes per optimizer step").set(wire)
    reg.gauge("horovod_uncompressed_bytes_per_step",
              "Equivalent uncompressed exchange bytes per optimizer step"
              ).set(raw)


def ef_exchange(grads, residuals, *, compression, op=Average,
                fusion_threshold: Optional[int] = None, axes=None,
                prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Error-feedback fused gradient exchange: the stateful hot path.

    ``residuals`` is the per-bucket tuple of flat f32 arrays from the
    PREVIOUS step (local view, no leading world axis).  Returns
    ``(reduced_grads, new_residuals)``.  With ``HOROVOD_EF_RESIDUAL=0``
    the residual input is ignored (zeros) and the carry is returned
    unchanged, so the state shape stays stable across the flag.
    """
    from ..controller.fusion import pack, unpack
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads, residuals
    spec = ef_bucket_plan(leaves, fusion_threshold, compression)
    if len(residuals) != len(spec.buffers):
        raise ValueError(
            f"EF residual carry has {len(residuals)} buckets but the plan "
            f"has {len(spec.buffers)} -- optimizer state initialized under "
            f"a different fusion threshold or codec?")
    buffers = pack(leaves, spec)
    feed = _ef_enabled()
    # Trace-time leg registration for the straggler report (fires once
    # per trace, exactly like _note_compression_ratio below).
    from ..timeline import spans as _spans
    hier = is_hier_legs(compression)
    hier_pair = _hier_axes(axes) if hier else None
    if hier and hier_pair is None:
        raise NotImplementedError(
            "per-leg error-feedback compression (ici:...,dcn:powersgd/topk)"
            " needs the two-level (dcn, ici) mesh; set HOROVOD_HIERARCHICAL"
            " or use the flat codec spec instead")
    out_bufs, new_res = [], []
    for i, (buf, res, (dt, _ls)) in enumerate(
            zip(buffers, residuals, spec.buffers)):
        if not hier:
            # The two-level path notes its own hier/* legs per hop.  The
            # ledger row (wire payload accounting) comes from the shared
            # exchange-plan IR; the nested powersgd/topk collective rows
            # fire from inside the op itself.
            from ..controller import fusion as _fusion
            _spans.note_leg(
                _fusion.plan_exchange(
                    "ef", size=int(buf.size), dtype=str(buf.dtype),
                    compression=compression).legs[0],
                bucket_id=i)
        if not jnp.issubdtype(buf.dtype, jnp.floating):
            out_bufs.append(_ops.allreduce(
                buf, op, axes=axes, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor))
            new_res.append(res)
            continue
        if hier:
            # Residual row is [2, shard]: leg 0 (ICI) is exact and stays
            # zero, leg 1 carries the DCN hop's unsent error.
            r_in = res[1] if feed else None
            out, r_out = _ops.hierarchical_allreduce(
                buf, op, dcn_axis=hier_pair[0], ici_axis=hier_pair[1],
                dcn_codec=compression.dcn, ici_codec=compression.ici,
                dcn_residual=r_in,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
            out_bufs.append(out)
            new_res.append(jnp.stack([jnp.zeros_like(r_out), r_out])
                           if feed else res)
            continue
        r_in = res if feed else None
        if is_powersgd(compression):
            out, r_out = _ops.powersgd_allreduce(
                buf, op, rank=compression.rank, axes=axes, residual=r_in,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
        else:
            out, r_out = _ops.topk_allreduce(
                buf, op, fraction=compression.fraction, axes=axes,
                residual=r_in, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
        out_bufs.append(out)
        new_res.append(r_out if feed else res)
    _note_compression_ratio(spec, compression)
    return (jax.tree.unflatten(treedef, unpack(out_bufs, spec)),
            tuple(new_res))


def is_ef_optimizer(optimizer) -> bool:
    """True when ``optimizer`` is a DistributedOptimizer wrap whose codec
    needs the error-feedback state carry (its state is an :class:`_EFState`
    and must be sharded ``P(axes)`` on the residual leaves)."""
    ex = getattr(optimizer.update, "_hvd_exchange", None)
    return ex is not None and is_error_feedback(ex["compression"])


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *,
                         op: ReduceOp = Average,
                         compression=None,
                         fusion_threshold: Optional[int] = None,
                         axes=None,
                         process_set=None,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         backward_passes_per_step: int = 1
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally-reduced gradients.

    Use inside a step traced over the mesh (``shard_map`` or the
    :func:`horovod_tpu.training.train_step` helper)::

        opt = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                       compression=hvd.Compression.bf16)

    ``compression`` accepts a codec class, a spec string
    (``"powersgd:2"``, ``"topk:0.01"``, ``"bf16"``, ...), or ``None`` to
    follow ``HOROVOD_COMPRESSION``.  The error-feedback codecs
    (``Compression.powersgd(r)`` / ``Compression.topk(f)``) make the
    optimizer STATEFUL beyond the inner state: ``init`` returns an
    :class:`_EFState` carrying one per-rank residual array per fusion
    bucket, and each ``update`` runs the factored/sparse exchange with the
    residual fed back (``HOROVOD_EF_RESIDUAL``).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    compression = _resolve_compression(compression)

    if is_error_feedback(compression):
        if process_set is not None:
            raise NotImplementedError(
                "powersgd/topk do not support process-set reductions; use "
                "fp16/bf16 compression there")
        from ..collectives.reduce_op import Adasum as _Adasum
        if op is _Adasum:
            raise NotImplementedError(
                "powersgd/topk support Sum/Average reductions only")
        if backward_passes_per_step != 1:
            raise NotImplementedError(
                "error-feedback compression with backward_passes_per_step"
                " > 1 is not supported; use microbatches=k instead "
                "(residual applied once per optimizer step)")

        def ef_init(params):
            return _EFState(
                residuals=ef_init_residuals(params, fusion_threshold,
                                            compression),
                inner=optimizer.init(params))

        def ef_update(grads, state, params=None, **extra):
            if not isinstance(state, _EFState):
                # Checkpoint restore may rebuild the carry as a plain
                # 2-tuple; the layout is positional either way.
                state = _EFState(*state)
            local_res = tuple(r[0] for r in state.residuals)
            reduced, new_res = ef_exchange(
                grads, local_res, compression=compression, op=op,
                fusion_threshold=fusion_threshold, axes=axes,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
            updates, inner = optimizer.update(reduced, state.inner, params,
                                              **extra)
            return updates, _EFState(tuple(r[None] for r in new_res), inner)

        ef_update._hvd_allreduce = True
        ef_update._hvd_inner = optimizer
        ef_update._hvd_exchange = dict(
            op=op, compression=compression, fusion_threshold=fusion_threshold,
            axes=axes, process_set=process_set,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
        return optax.GradientTransformation(ef_init, ef_update)

    def _reduce(grads):
        return allreduce_gradients(
            grads, op, compression=compression,
            fusion_threshold=fusion_threshold, axes=axes,
            process_set=process_set, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)

    if backward_passes_per_step == 1:
        def init(params):
            return optimizer.init(params)

        def update(grads, state, params=None, **extra):
            return optimizer.update(_reduce(grads), state, params, **extra)

        # zero_stage=1 replaces this allreduce with a reduce-scatter; the
        # zero path detects the wrap through this marker and rejects it.
        update._hvd_allreduce = True
        # The microbatched step (training.py, microbatches=k>1) unwraps the
        # optimizer and runs the exchange itself (per-microbatch shard
        # reduce-scatter + one allgather), so it needs the inner optimizer
        # and the exchange parameters this wrap would have applied.  Only
        # the plain (non-accumulating) wrap exposes them: combining k>1
        # with backward_passes_per_step>1 is rejected at build time.
        update._hvd_inner = optimizer
        update._hvd_exchange = dict(
            op=op, compression=compression, fusion_threshold=fusion_threshold,
            axes=axes, process_set=process_set,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
        return optax.GradientTransformation(init, update)

    n = backward_passes_per_step

    def init(params):
        return _AccumState(
            counter=jnp.zeros((), jnp.int32),
            accum=jax.tree.map(jnp.zeros_like, params),
            inner=optimizer.init(params))

    def update(grads, state, params=None, **extra):
        accum = jax.tree.map(lambda a, g: a + g, state.accum, grads)
        is_sync = state.counter == n - 1

        def do_sync(_):
            mean_grads = jax.tree.map(lambda a: a / n, accum)
            reduced = _reduce(mean_grads)
            updates, inner = optimizer.update(reduced, state.inner, params,
                                              **extra)
            zeroed = jax.tree.map(jnp.zeros_like, accum)
            return updates, _AccumState(jnp.zeros((), jnp.int32), zeroed,
                                        inner)

        def skip(_):
            updates = jax.tree.map(jnp.zeros_like, grads)
            return updates, _AccumState(state.counter + 1, accum, state.inner)

        return jax.lax.cond(is_sync, do_sync, skip, None)

    update._hvd_allreduce = True
    return optax.GradientTransformation(init, update)


def DistributedAdasumOptimizer(optimizer: optax.GradientTransformation,
                               **kwargs) -> optax.GradientTransformation:
    """Adasum variant (``_DistributedAdasumOptimizer`` parity)."""
    from ..collectives.reduce_op import Adasum
    kwargs["op"] = Adasum
    return DistributedOptimizer(optimizer, **kwargs)


# --- elastic resize -------------------------------------------------------

def ef_resize_residuals(residuals, params, old_world: int, new_world: int,
                        *, fusion_threshold: Optional[int] = None,
                        compression=None):
    """Re-bucket an ``_EFState`` residual carry for a new world size.

    EF bucket shapes depend only on the fusion threshold (world-
    independent), so a rank change only changes the leading world axis.
    The dropped ranks' pending correction mass is NOT lost: with the
    exchange averaging over ``world``, the carried quantity is
    ``sum(residuals) / world``, so the kept rows are rescaled by
    ``new/old`` and each dropped row's mass is spread uniformly::

        res'_i = (new/old) * res_i + sum(dropped) / old

    which preserves ``sum(res') / new == sum(res) / old`` exactly (same
    algebra when growing: the existing rows are rescaled and new rows
    start at zero).  Residuals are zeroed -- with a counted warning --
    only when the bucket plan itself is irreconcilable (different bucket
    count or sizes, e.g. the fusion threshold changed across the
    restart).

    Returns ``(new_residuals, report)``.
    """
    import logging
    import numpy as np
    logger = logging.getLogger("horovod_tpu.optim")
    old_world, new_world = int(old_world), int(new_world)
    report = {"carried_bytes": 0, "zeroed_buckets": 0}
    expected = None
    if params is not None:
        comp = _resolve_compression(compression)
        spec = ef_bucket_plan(jax.tree.leaves(params), fusion_threshold,
                              comp)
        # Row shape under the NEW mesh: flat codecs (size,), per-leg
        # codecs (2, shard) -- a slice-boundary resize that changes the
        # shard width shows up here as an irreconcilable shape and the
        # residual is zeroed (counted) rather than silently misaligned.
        expected = [ef_residual_shape(sum(s.size for s in lspecs), comp)
                    for _dt, lspecs in spec.buffers]

    def _zeroed(shape):
        from ..optim.zero import _count_zeroed_residual
        _count_zeroed_residual()
        report["zeroed_buckets"] += 1
        return jnp.zeros((new_world,) + tuple(shape), jnp.float32)

    res_list = list(residuals)
    if expected is not None and len(res_list) != len(expected):
        logger.warning(
            "ef_resize_residuals: carry has %d bucket(s) but the plan "
            "for the new world has %d -- zeroing all residuals",
            len(res_list), len(expected))
        return tuple(_zeroed(s) for s in expected), report

    out = []
    for i, r in enumerate(res_list):
        arr = np.asarray(jax.device_get(r), dtype=np.float32)
        shape = tuple(expected[i]) if expected is not None else (
            arr.shape[1:] if arr.ndim >= 2 else None)
        if arr.ndim < 2 or shape is None or arr.shape[1:] != shape:
            logger.warning(
                "ef_resize_residuals: bucket %d shape %s irreconcilable "
                "with planned row shape %s -- zeroing it", i,
                getattr(arr, "shape", None), shape)
            out.append(_zeroed(shape if shape is not None else (0,)))
            continue
        rows = arr.shape[0]
        keep = min(rows, new_world)
        newr = np.zeros((new_world,) + shape, np.float32)
        newr[:keep] = arr[:keep] * (new_world / rows)
        if rows > new_world:
            newr += arr[new_world:].sum(axis=0) / rows
        out.append(jnp.asarray(newr))
        report["carried_bytes"] += int(arr.nbytes)
    return tuple(out), report
