"""High-level data-parallel training-step builder.

The reference's end-user recipe (wrap optimizer, hook gradients, launch one
process per accelerator) becomes, TPU-natively: trace ONE step function
over the mesh with ``jax.shard_map``; the batch is sharded over the mesh
axes, parameters are replicated, and the wrapped optimizer emits fused
``psum`` collectives that XLA overlaps with the backward pass.

This module is the "DistributedOptimizer user experience" glue: given a
loss function and a (Distributed)optax optimizer it returns a jitted step
with donated params/opt-state (in-place HBM update, fusion-buffer style).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import math

from .collectives import ops as _ops
from .collectives.reduce_op import Average, Sum
from .core import basics as _basics
from .optim import distributed as _dist
from .optim import zero as _zero


def _opt_state_spec(optimizer, zero_stage: int, axes, override=None):
    """Partition spec (pytree prefix) for the optimizer-state carry.

    ZeRO-1 state is arena-sharded ``P(axes)``.  An error-feedback wrap's
    state mixes specs: the per-rank residual leaves (leading world axis)
    shard ``P(axes)`` while the inner optimizer state stays replicated --
    expressed as an ``_EFState``-shaped spec prefix.  ``override`` (the
    builders' ``opt_state_specs=``) wins for everything else -- the TP
    case, where a stateful optimizer's param-shaped moments must shard
    like the params (:func:`mirror_opt_state_specs`).  Default:
    replicated."""
    if zero_stage:
        return P(axes)
    if _dist.is_ef_optimizer(optimizer):
        return _dist._EFState(residuals=P(axes), inner=P())
    if override is not None:
        return override
    return P()


def mirror_opt_state_specs(optimizer, params, param_specs):
    """Optimizer-state spec tree mirroring TP/pipeline ``param_specs``.

    A stateful optimizer (Adam moments, SGD momentum) carries param-tree-
    shaped subtrees in its state; on a model-parallel mesh those must
    shard exactly like the params or the shard_map in_specs try to place
    a full-shaped moment next to a sharded param.  This walks
    ``jax.eval_shape(optimizer.init, params)`` and substitutes
    ``param_specs`` for every subtree structurally equal to ``params``
    (scalars such as the Adam step count stay replicated).  Pass the
    result as ``make_train_step(..., opt_state_specs=...)``.
    """
    state = jax.eval_shape(optimizer.init, params)
    pstruct = jax.tree.structure(params)

    def is_param_tree(node):
        try:
            return jax.tree.structure(node) == pstruct
        except Exception:  # noqa: BLE001 - non-pytree node
            return False

    def leaf(node):
        return is_param_tree(node) or not jax.tree.leaves(node) \
            or isinstance(node, jax.ShapeDtypeStruct)

    return jax.tree.map(
        lambda n: param_specs if is_param_tree(n) else P(),
        state, is_leaf=leaf)


def batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding that splits the leading (batch) dim over the mesh's DATA
    axes (all axes on a pure-DP mesh; the batch is replicated over the
    ``model``/``pipe`` axes of a :func:`~horovod_tpu.parallel.build_3d_mesh`
    mesh -- every TP rank and pipeline stage sees its DP shard whole)."""
    from .parallel.mesh import data_axes as _data_axes
    mesh = mesh or _basics.mesh()
    return NamedSharding(mesh, P(_data_axes(mesh)))


def shard_batch(batch: Any, mesh: Optional[Mesh] = None) -> Any:
    """Place a host-global batch onto the mesh, sharded along dim 0."""
    sharding = batch_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def stacked_batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding for :func:`stack_steps` output: dim 0 is the (unsharded)
    steps axis the scan loop consumes, dim 1 the global batch split over
    the mesh's data axes."""
    from .parallel.mesh import data_axes as _data_axes
    mesh = mesh or _basics.mesh()
    return NamedSharding(mesh, P(None, _data_axes(mesh)))


def shard_steps(stacked: Any, mesh: Optional[Mesh] = None) -> Any:
    """Place a k-step stacked batch (``[k, global_batch, ...]`` leaves --
    :func:`stack_steps`) onto the mesh for :func:`make_train_loop`."""
    sharding = stacked_batch_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)


def shard_batch_from_local(local_batch: Any,
                           mesh: Optional[Mesh] = None) -> Any:
    """Assemble the global batch from each process's local rows.

    The reference's data model: every rank loads its own shard (Petastorm
    per-rank readers, ``ElasticSampler``).  Each process passes the rows it
    owns; the global array is stitched with
    ``jax.make_array_from_process_local_data``.  Single-process, this is
    :func:`shard_batch`.
    """
    import numpy as np

    mesh = mesh or _basics.mesh()
    mesh_procs = {d.process_index for d in mesh.devices.flat}
    if len(mesh_procs) == 1:
        return shard_batch(local_batch, mesh)
    sharding = batch_sharding(mesh)

    def put(x):
        x = np.asarray(x)
        # Multiply by the processes IN THIS MESH (a process-set sub-mesh
        # may span fewer than jax.process_count()).
        global_shape = (x.shape[0] * len(mesh_procs),) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x,
                                                      global_shape)

    return jax.tree.map(put, local_batch)


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or _basics.mesh()
    return NamedSharding(mesh, P())


def replicate(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Replicate parameters/optimizer state across the mesh."""
    sharding = replicated_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def sync_batch_norm(axes=None, **kwargs):
    """Flax BatchNorm whose batch statistics span the mesh.

    Reference parity: ``horovod/torch/sync_batch_norm.py`` (the torch shim
    equivalent lives at ``horovod_tpu.torch.SyncBatchNorm``).  On TPU the
    stat exchange is just ``lax.pmean`` over the mesh axes, which flax's
    BatchNorm emits natively via ``axis_name`` -- XLA fuses it with the
    surrounding reduction, so sync BN costs one small fused collective.

    Use inside a step built by :func:`make_train_step` /
    :func:`make_flax_train_step` (the mesh axes are bound by shard_map
    there).  ``axes`` defaults to the initialized mesh's axis names.
    """
    import flax.linen as nn
    axes = tuple(axes) if axes is not None else tuple(
        _basics.mesh().axis_names)
    return nn.BatchNorm(axis_name=axes if len(axes) > 1 else axes[0],
                        **kwargs)


def _resolve_zero_stage(zero_stage: Optional[int]) -> int:
    """``None`` defers to the configured default (``HOROVOD_ZERO``)."""
    if zero_stage is None:
        from .core.state import global_state
        cfg = global_state().config
        zero_stage = cfg.zero_stage if cfg is not None else 0
    if zero_stage not in (0, 1):
        raise ValueError(f"zero_stage must be 0 or 1, got {zero_stage!r}")
    return zero_stage


def steps_per_execution(default: int = 1) -> int:
    """Resolved steps-per-execution k (``HOROVOD_STEPS_PER_EXEC``).

    The keras/torch shims read this to pick up the env knob (pass it to
    ``model.compile(steps_per_execution=...)`` / use it as the torch
    micro-loop length); :func:`make_train_loop` calls it when built
    without an explicit ``steps_per_execution``.  When the autotuner's
    opt-in steps axis is active, the current sample's value wins.
    """
    from .core.state import global_state
    st = global_state()
    if st.autotuner is not None:
        return max(1, st.autotuner.steps_per_exec())
    if st.config is not None:
        return max(1, st.config.steps_per_exec)
    return max(1, default)


def _resolve_steps(k: Optional[int]) -> int:
    """``None`` defers to :func:`steps_per_execution` (env/tuner)."""
    k = steps_per_execution() if k is None else int(k)
    if k < 1:
        raise ValueError(f"steps_per_execution must be >= 1, got {k}")
    return k


def microbatches(default: int = 1) -> int:
    """Resolved microbatch count k (``HOROVOD_MICROBATCHES``).

    :func:`make_train_step` / :func:`make_flax_train_step` (and the loop
    builders) call this when built without an explicit ``microbatches``
    argument.  When the autotuner's opt-in microbatch axis is active
    (``HOROVOD_AUTOTUNE_MICROBATCH=1``) the current sample's value wins.
    k > 1 selects the backward-overlap exchange: the per-step batch splits
    into k sub-batches inside one executable and each sub-batch's gradient
    buckets reduce-scatter while the next sub-batch's backward pass runs.
    """
    from .core.state import global_state
    st = global_state()
    if st.autotuner is not None:
        return max(1, st.autotuner.microbatches())
    if st.config is not None:
        return max(1, st.config.microbatches)
    return max(1, default)


def _resolve_microbatches(k: Optional[int]) -> int:
    """``None`` defers to :func:`microbatches` (env/tuner)."""
    k = microbatches() if k is None else int(k)
    if k < 1:
        raise ValueError(f"microbatches must be >= 1, got {k}")
    return k


def _resolve_tp(tp: Optional[int]) -> int:
    """``None`` defers to the configured default (``HOROVOD_TP``)."""
    if tp is None:
        from .core.state import global_state
        cfg = global_state().config
        tp = cfg.tp if cfg is not None else 1
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    return tp


def _resolve_pipeline_stages(pipeline_stages: Optional[int]) -> int:
    """``None`` defers to the configured default
    (``HOROVOD_PIPELINE_STAGES``)."""
    if pipeline_stages is None:
        from .core.state import global_state
        cfg = global_state().config
        pipeline_stages = cfg.pipeline_stages if cfg is not None else 1
    pipeline_stages = int(pipeline_stages)
    if pipeline_stages < 1:
        raise ValueError(
            f"pipeline_stages must be >= 1, got {pipeline_stages}")
    return pipeline_stages


def _resolve_model_axes(mesh: Mesh, tp: int, pipeline_stages: int
                        ) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """``(data_axes, model_axes)`` of ``mesh`` for a step built with
    ``tp``/``pipeline_stages``, with the declared extents validated
    against the mesh shape.

    The data axes are the gradient-exchange domain: every collective the
    step emits on its own behalf (gradient allreduce, ZeRO arena
    reduce-scatter/allgather, microbatch overlap, loss average) runs over
    them ONLY, so TP's in-forward collectives on the ``model`` axis and
    the pipeline's ``ppermute`` on ``pipe`` never mix with the DP leg.
    On a pure-DP mesh the data axes are all axes -- bitwise-identical
    wiring to the pre-3D builder.
    """
    from .parallel import mesh as _pmesh
    names = tuple(mesh.axis_names)

    def check(extent: int, axis: str, knob: str) -> None:
        have = int(mesh.shape[axis]) if axis in names else 1
        if extent > 1 and have != extent:
            raise ValueError(
                f"{knob}={extent} needs a mesh {axis!r} axis of extent "
                f"{extent} (build_3d_mesh); mesh axes are "
                f"{dict(mesh.shape)}")
        if extent == 1 and have > 1:
            raise ValueError(
                f"mesh has a {axis!r} axis of extent {have} but the step "
                f"was built with {knob}={extent}; pass {knob}={have}")

    check(tp, _pmesh.MODEL_AXIS, "tp")
    check(pipeline_stages, _pmesh.PIPE_AXIS, "pipeline_stages")
    d_ax = _pmesh.data_axes(mesh)
    m_ax = tuple(a for a in names if a not in d_ax)
    return d_ax, m_ax


def _check_model_parallel_exchange(optimizer, d_ax, m_ax) -> None:
    """Reject optimizer wraps whose gradient exchange would reduce over
    the model axes.  A :func:`~horovod_tpu.DistributedOptimizer` built
    without explicit ``axes`` resolves them to ALL mesh axes at trace
    time, which on a TP/pipeline mesh would sum gradients of DIFFERENT
    parameter shards -- silently wrong math, so it fails the build."""
    if not m_ax:
        return
    upd = getattr(optimizer, "update", None)
    if not getattr(upd, "_hvd_allreduce", False):
        return  # bare optimizer: the step emits no exchange for it
    if _dist.is_ef_optimizer(optimizer):
        raise NotImplementedError(
            "error-feedback codecs (powersgd/topk) do not yet compose "
            "with tp/pipeline_stages: the residual carry is planned from "
            "the global parameter shapes, not the TP-local shards.  Use "
            "fp16/bf16 (or per-leg ici:...,dcn:fp16) compression on the "
            "DP leg instead")
    ex = getattr(upd, "_hvd_exchange", None)
    ax = ex.get("axes") if ex is not None else None
    ax = tuple((ax,) if isinstance(ax, str) else ax) if ax is not None \
        else None
    if ax != tuple(d_ax):
        raise ValueError(
            f"DistributedOptimizer on a model-parallel mesh must be built "
            f"with axes={tuple(d_ax)} (the data axes) so the gradient "
            f"exchange never reduces over the model axes {tuple(m_ax)}; "
            f"got axes={ax!r}")


def _microbatch_unwrap(optimizer):
    """Decompose an optimizer for the microbatched exchange.

    Returns ``(inner, exchange)``: the unwrapped optax optimizer plus the
    exchange parameters a :func:`~horovod_tpu.DistributedOptimizer` wrap
    would have applied (``None`` for a bare optimizer -- local microbatch
    accumulation only, no collective, matching what the bare single-shot
    step does).  The microbatched step must run the exchange itself --
    per-microbatch bucket reduce-scatter, one closing allgather -- so a
    wrapped optimizer's in-update allreduce cannot be reused: it would
    exchange every microbatch's full gradient (k times the wire traffic)
    with no overlap ordering.
    """
    upd = optimizer.update
    if not getattr(upd, "_hvd_allreduce", False):
        return optimizer, None
    if not hasattr(upd, "_hvd_inner"):
        raise ValueError(
            "microbatches > 1 cannot combine with "
            "backward_passes_per_step > 1 (both are gradient-accumulation "
            "schemes; pick one)")
    exchange = dict(upd._hvd_exchange)
    if exchange["process_set"] is not None:
        raise NotImplementedError(
            "microbatches > 1 does not support process-set reductions "
            "(the scatter-based exchange has no masked identity)")
    if exchange["op"] not in (Sum, Average):
        raise ValueError(
            "microbatches > 1 supports Sum/Average reductions only, got "
            f"{exchange['op']!r} (Adasum composes through "
            "DistributedAdasumOptimizer without microbatching)")
    from .collectives.compression import is_fp8
    if is_fp8(exchange["compression"]):
        raise NotImplementedError(
            "microbatches > 1 does not support Compression.fp8 (the "
            "quantized exchange owns its own collective); use fp16/bf16")
    # Error-feedback codecs (powersgd/topk) DO compose: the microbatched
    # step accumulates sub-batch gradients locally in f32 and runs ONE
    # residual-fed exchange per step (_build_microbatch_local_step), so
    # the residual is applied once per optimizer step, never per
    # microbatch.
    return upd._hvd_inner, exchange


def _is_ef_exchange(exchange) -> bool:
    """True when a microbatch exchange dict carries an error-feedback codec
    (powersgd/topk): the builders then accumulate locally and run ONE
    residual-fed exchange per step instead of the per-microbatch
    reduce-scatter pipe."""
    from .collectives.compression import is_error_feedback
    return is_error_feedback(exchange["compression"])


def _resolve_guard() -> Tuple[bool, float]:
    """``(guard_on, norm_limit)`` from ``HOROVOD_GUARD`` (core/guard.py).

    Resolved at step-BUILD time: the screen is part of the traced
    program.  ``auto`` (default) arms only when chaos injection or the
    desync/snapshot planes are active, so default builds stay bitwise
    identical to the unguarded trace."""
    from .core import guard as _guard
    return _guard.step_guard()


def _note_guard_leg():
    """Trace-time registration of the SDC screen's one extra psum: the
    leg row comes from the shared exchange-plan IR ("guard" family)."""
    from .controller import fusion as _fusion
    from .timeline import spans as _spans
    _spans.note_leg(_fusion.plan_exchange("guard").legs[0])


def _guard_screen_vec(grads):
    """Local half of the SDC screen: ``[nonfinite_count, sq_sum]`` f32[2].

    Summed across ranks with ONE extra psum (float32 on purpose: the
    audit fence flags scalar int32 psums as barrier-shaped).  The norm
    half is a magnitude SCREEN (sqrt of the global sum of local squared
    norms), not the exact norm of the averaged gradient -- it saturates
    to inf for |g| beyond ~1e19, which the policy treats as poisoned."""
    nonf = jnp.zeros((), jnp.float32)
    sq = jnp.zeros((), jnp.float32)
    for g in jax.tree.leaves(grads):
        if jnp.issubdtype(g.dtype, jnp.inexact):
            g32 = g.astype(jnp.float32)
            nonf = nonf + jnp.sum(~jnp.isfinite(g32)).astype(jnp.float32)
            sq = sq + jnp.sum(jnp.square(g32))
        # Integer leaves are always finite and carry no norm.
    return jnp.stack([nonf, sq])


def _guard_verdict(gvec, norm_limit):
    """``(nonfinite, norm, bad)`` from the psum'd screen vector."""
    nonfinite = gvec[0]
    norm = jnp.sqrt(gvec[1])
    bad = (nonfinite > 0) | ~jnp.isfinite(norm)
    if norm_limit and norm_limit > 0:
        bad = bad | (norm > norm_limit)
    return nonfinite, norm, bad


def _guard_select(bad, old_tree, new_tree):
    """Poisoned step -> keep the OLD tree wholesale (bitwise: params and
    EF residuals provably untouched -- the whole old carry is selected,
    not recomputed)."""
    return jax.tree.map(lambda o, n: jnp.where(bad, o, n),
                        old_tree, new_tree)


def stack_steps(batches) -> Any:
    """Stack k per-step batches into the scanned layout ``make_train_loop``
    consumes: each leaf gains a leading steps axis ``[k, batch, ...]``."""
    batches = list(batches)
    if not batches:
        raise ValueError("stack_steps needs at least one batch")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def make_train_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    loss_has_aux: bool = False,
    aux_mode: str = "stacked",
    with_frozen: bool = False,
    zero_stage: Optional[int] = None,
    zero_compression=None,
    microbatches: Optional[int] = None,
    tp: Optional[int] = None,
    pipeline_stages: Optional[int] = None,
    param_specs=None,
    opt_state_specs=None,
) -> Callable[[Any, Any, Any], Tuple[Any, Any, jnp.ndarray]]:
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss)``.

    ``loss_fn(params, local_batch)`` is evaluated on each device's batch
    shard; gradients flow through ``optimizer`` (wrap it with
    :func:`horovod_tpu.DistributedOptimizer` for the fused allreduce) and
    the returned loss is the global mean.

    With ``loss_has_aux``, ``loss_fn`` returns ``(loss, aux)``.
    ``aux_mode`` controls how aux crosses the mesh: ``"stacked"`` returns
    the per-device values stacked on a leading axis; ``"averaged"``
    mean-allreduces every aux leaf and returns it replicated -- use this
    for mutated model state such as BatchNorm running statistics (the
    cross-device averaging mirrors the reference's SyncBatchNorm stats
    exchange, ``horovod/torch/sync_batch_norm.py``).

    With ``with_frozen``, ``loss_fn(params, frozen, local_batch)`` and the
    step takes a fourth argument: ``step(params, opt_state, batch,
    frozen)``.  The frozen tree is replicated, NOT donated, and never
    differentiated -- gradients, the fused allreduce, and optimizer state
    span only ``params``.  This is the LoRA/adapter layout (e.g. an int8
    frozen Llama base with trainable adapters, ``models.split_frozen``).

    With ``zero_stage=1`` (default from ``HOROVOD_ZERO``) the optimizer
    state is sharded across the mesh (ZeRO-1,
    :mod:`horovod_tpu.optim.zero`): gradients are reduce-scattered, each
    chip updates its 1/n arena slice, and updated params ride an
    allgather optionally compressed via ``zero_compression``
    (``hvd.Compression.{fp16,bf16,fp8}``).  Pass the BARE optax optimizer
    (no :func:`~horovod_tpu.DistributedOptimizer` wrap) and build
    ``opt_state`` with :func:`horovod_tpu.zero_init`.

    With ``microbatches=k > 1`` (default from ``HOROVOD_MICROBATCHES``)
    the per-step batch splits into k sub-batches inside ONE executable:
    each sub-batch's gradient buckets reduce-scatter the moment its
    backward segment finishes, overlapping wire time with the next
    sub-batch's backward compute (the reference's headline
    backward-overlap, expressed as schedulable HLO).  Same optimizer
    trajectory as single-shot at the same global batch, up to documented
    accumulation-order tolerance (f32 cross-microbatch sum; bitwise at
    k=1, which is exactly the single-shot path).  Requires a
    per-example-mean loss, a local batch divisible by k, and is
    incompatible with ``zero_stage=1``, Adasum, fp8 compression, process
    sets, and ``backward_passes_per_step > 1``.

    With ``tp=t > 1`` / ``pipeline_stages=s > 1`` (defaults from
    ``HOROVOD_TP`` / ``HOROVOD_PIPELINE_STAGES``) the step runs 3-D
    parallel over a :func:`~horovod_tpu.parallel.build_3d_mesh` mesh:
    the gradient exchange, ZeRO-1 arena, microbatch overlap and loss
    average all run over the mesh's DATA axes only (``("dcn", "data")``
    when DCN splits the data axis -- the DP leg then rides the
    hierarchical ICI x DCN exchange -- else ``("data",)``), while
    ``loss_fn`` computes with TP collectives on the ``model`` axis
    (:mod:`horovod_tpu.parallel.tp`) and pipeline ``ppermute`` on
    ``pipe`` (:func:`~horovod_tpu.parallel.pipeline_apply`).  Pass
    ``param_specs``: a pytree (prefix) of ``PartitionSpec``s placing the
    stacked TP/stage parameter leaves, e.g. ``P("model")`` on a
    ``[tp, d, f/tp]`` column-stacked kernel or ``P("pipe")`` on
    ``[s, ...]`` stage-stacked leaves (each leaf arrives in ``loss_fn``
    with those leading axes of LOCAL extent 1).  A
    :func:`~horovod_tpu.DistributedOptimizer` must then be built with
    ``axes=<data axes>``; ``zero_stage=1`` needs ``zero_init(...,
    param_specs=...)`` so each device's arena holds its own TP shard.
    """
    if aux_mode not in ("stacked", "averaged"):
        raise ValueError(f"unknown aux_mode {aux_mode!r}")
    zero_stage = _resolve_zero_stage(zero_stage)
    k_micro = _resolve_microbatches(microbatches)
    if zero_stage:
        if k_micro > 1:
            raise ValueError(
                "microbatches > 1 is incompatible with zero_stage=1 (the "
                "ZeRO-1 arena reduce-scatter is already shard-based; "
                "overlap it via HOROVOD_EXCHANGE_CHUNK_MB instead)")
        _zero._reject_distributed(optimizer)
    mesh = mesh or _basics.mesh()
    tp = _resolve_tp(tp)
    pipeline_stages = _resolve_pipeline_stages(pipeline_stages)
    axes, model_ax = _resolve_model_axes(mesh, tp, pipeline_stages)
    _check_model_parallel_exchange(optimizer, axes, model_ax)
    guard_on, guard_limit = _resolve_guard()
    if k_micro > 1:
        inner, exchange = _microbatch_unwrap(optimizer)
        local_step = _build_microbatch_local_step(
            loss_fn, inner, exchange, axes, loss_has_aux, aux_mode,
            with_frozen, k_micro, guard=guard_on,
            guard_norm_limit=guard_limit,
            guard_axes=tuple(mesh.axis_names))
    else:
        local_step = _build_local_step(loss_fn, optimizer, axes,
                                       loss_has_aux, aux_mode, with_frozen,
                                       zero_stage, zero_compression,
                                       guard=guard_on,
                                       guard_norm_limit=guard_limit,
                                       guard_axes=tuple(mesh.axis_names))

    aux_spec = () if not loss_has_aux else \
        ((P(),) if aux_mode == "averaged" else (P(axes),))
    guard_spec = (P(),) if guard_on else ()
    frozen_spec = (P(),) if with_frozen else ()
    p_spec = param_specs if param_specs is not None else P()
    opt_spec = _opt_state_spec(optimizer, zero_stage,
                               tuple(mesh.axis_names),
                               override=opt_state_specs)
    shard = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_spec, opt_spec, P(axes)) + frozen_spec,
        out_specs=(p_spec, opt_spec, P()) + aux_spec + guard_spec,
        check_vma=False)
    donate_argnums = (0, 1) if donate else ()

    meta = {"optimizer": optimizer,
            "zero_stage": zero_stage,
            "zero_compression": zero_compression,
            "microbatches": k_micro,
            "guard": guard_on,
            "tp": tp,
            "pipeline_stages": pipeline_stages,
            "data_mesh": tuple(int(mesh.shape[a]) for a in axes),
            "data_axes": tuple(str(a) for a in axes),
            "mesh_shape": tuple((a, int(mesh.shape[a]))
                                for a in mesh.axis_names),
            "param_specs": param_specs,
            "world": int(math.prod(mesh.shape[a] for a in axes))}
    step = _maybe_tuned(shard, donate_argnums, loss_index=2, meta=meta)
    return _GuardedStep(step, meta) if guard_on else step


def _build_local_step(loss_fn, optimizer, axes, loss_has_aux, aux_mode,
                      with_frozen, zero_stage, zero_compression,
                      guard=False, guard_norm_limit=0.0, guard_axes=None):
    """The per-device step body shared by :func:`make_train_step` (one
    shard_map call) and :func:`make_train_loop` (the ``lax.scan`` body).
    Sharing the exact closure is what makes the k-step loop bitwise
    identical to k sequential step calls.

    With ``guard`` the SDC screen psums the raw LOCAL gradients' nonfinite
    count and squared norm (one extra f32[2] psum, before any exchange or
    update) and a poisoned step selects the OLD params/opt-state carry
    wholesale; the step then emits a trailing replicated ``f32[3]``
    ``[nonfinite, grad_norm, skipped]`` vector for the host policy.

    ``guard_axes`` (default ``axes``) is the screen's psum domain: on a
    model-parallel mesh it spans ALL mesh axes -- TP shards partition the
    gradient, so only the full-mesh sum gives every rank the same verdict
    (a data-axes-only sum would diverge across TP ranks and fork the
    carry)."""
    g_axes = tuple(guard_axes) if guard_axes is not None else axes

    def local_step(params, opt_state, batch, *frozen):
        lf = (lambda p, b: loss_fn(p, frozen[0], b)) if with_frozen \
            else loss_fn
        if loss_has_aux:
            (loss, aux), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch)
        else:
            loss, grads = jax.value_and_grad(lf)(params, batch)
            aux = None
        if guard:
            old_params, old_opt = params, opt_state
            _note_guard_leg()
            gvec = _ops.allreduce(_guard_screen_vec(grads), Sum,
                                  axes=g_axes)
        if zero_stage:
            params, opt_state = _zero.zero_apply(
                optimizer, grads, opt_state, params, axes=axes,
                compression=zero_compression)
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if guard:
            nonfinite, norm, bad = _guard_verdict(gvec, guard_norm_limit)
            params = _guard_select(bad, old_params, params)
            opt_state = _guard_select(bad, old_opt, opt_state)
            guard_out = jnp.stack([nonfinite, norm,
                                   bad.astype(jnp.float32)])
        loss = _ops.allreduce(loss, Average, axes=axes)
        out = (params, opt_state, loss)
        if loss_has_aux:
            if aux_mode == "averaged":
                aux = jax.tree.map(
                    lambda v: _ops.allreduce(v, Average, axes=axes)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            out = out + (aux,)
        if guard:
            out = out + (guard_out,)
        return out

    return local_step


def _microbatch_grad_pipe(exchange, axes, k=1):
    """Build ``(accumulate, finalize)`` for the backward-overlap exchange.

    ``accumulate(grads, state)`` is called once per microbatch, right after
    that microbatch's backward pass: it packs the gradients into fusion
    buckets in READY order (``plan_buckets(reverse=True)`` -- last layers'
    gradients finish first) and emits one tiled ``psum_scatter`` per bucket
    IMMEDIATELY, so the collective for microbatch i is independent of (and
    schedulable under) the backward compute of microbatch i+1.  Shards
    accumulate in float32 across microbatches.  ``finalize(state, k,
    grads)`` scales the accumulated shards (1/k; 1/n for Average;
    postscale) and closes with ONE allgather per bucket.

    Wire accounting: k reduce-scatters + 1 allgather of the
    ``lcm(n, 256)``-padded bucket move an equivalent-allreduce payload of
    ``(k+1)/2`` buckets -- the overlap costs extra bytes but each piece
    rides under compute.  Numerics: the cross-rank reduce runs in the wire dtype like
    the single-shot path, but the cross-MICROBATCH sum runs in f32 and the
    Average divide happens once at the end, so k>1 matches single-shot to
    accumulation-order tolerance, not bitwise (see ``make_train_step``).

    ``exchange=None`` (bare optimizer, no DistributedOptimizer wrap) does
    local f32 accumulation only -- no collective, matching the bare
    single-shot step.
    """
    from .controller.fusion import pack, plan_buckets, unpack

    if exchange is None:
        def accumulate(grads, state):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if state is None:
                return g32
            return jax.tree.map(jnp.add, state, g32)

        def finalize(state, k, grads_like):
            return jax.tree.map(lambda a, g: (a / k).astype(g.dtype),
                                state, grads_like)

        return accumulate, finalize

    compression = exchange["compression"]
    threshold = exchange["fusion_threshold"]
    pre = exchange["prescale_factor"]
    post = exchange["postscale_factor"]

    def _plan(bufspec, n):
        # One memoized plan-IR lookup shared by accumulate/finalize (and
        # by stepmodel's expected multiset): rs rows first, then ag.
        from .controller import fusion as _fusion
        legs = _fusion.plan_exchange(
            "microbatch",
            buffers=tuple((dt, sum(s.size for s in lspecs))
                          for dt, lspecs in bufspec),
            k=int(k), world=int(n), compression=compression).legs
        return legs[:len(bufspec)], legs[len(bufspec):]

    def accumulate(grads, state):
        leaves = jax.tree.leaves(grads)
        spec = plan_buckets(leaves, threshold, reverse=True)
        bufs = pack(leaves, spec)
        n = _ops.axis_size(axes)
        q = _ops.microbatch_pad_quantum(n)
        from .timeline import spans as _spans
        rs_legs, _ag = _plan(spec.buffers, n)
        shards = []
        for i, buf in enumerate(bufs):
            c, ctx = compression.compress(buf)
            if pre != 1.0:
                c = c * jnp.asarray(pre, dtype=c.dtype)
            # Trace-time leg registration (once per trace): the overlap
            # RS leg's planned wire bytes per bucket, for straggler
            # attribution (noted once per microbatch).
            _spans.note_leg(rs_legs[i], bucket_id=i)
            shard = _ops.psum_scatter_bucket(c, axes=axes, quantum=q)
            shards.append(
                compression.decompress(shard, ctx).astype(jnp.float32))
        if state is None:
            return shards
        return [a + s for a, s in zip(state, shards)]

    def finalize(state, k, grads_like):
        leaves, treedef = jax.tree.flatten(grads_like)
        spec = plan_buckets(leaves, threshold, reverse=True)
        n = _ops.axis_size(axes)
        scale = 1.0 / k
        if exchange["op"] is Average:
            scale = scale / n
        from .timeline import spans as _spans
        _rs, ag_legs = _plan(spec.buffers, n)
        out = []
        for i, (shard, (dt, lspecs)) in enumerate(
                zip(state, spec.buffers)):
            shard = shard * scale
            if post != 1.0:
                shard = shard * post
            shard = shard.astype(dt)
            c2, ctx2 = compression.compress(shard)
            size = sum(s.size for s in lspecs)
            _spans.note_leg(ag_legs[i], bucket_id=i)
            full = _ops.allgather_bucket(c2, size, axes=axes)
            out.append(compression.decompress(full, ctx2))
        return jax.tree.unflatten(treedef, unpack(out, spec))

    return accumulate, finalize


def _split_microbatches(tree, k):
    """Reshape each leaf's leading (local-batch) dim into ``[k, b/k, ...]``
    contiguous sub-batches.  Shapes are static at trace time, so a
    non-divisible batch fails the build, not the run."""
    def split(leaf):
        b0 = leaf.shape[0] if leaf.ndim else 0
        if b0 % k:
            raise ValueError(
                f"microbatches={k} must divide the per-device batch "
                f"(got leading dim {b0}); pad or resize the batch")
        return leaf.reshape((k, b0 // k) + leaf.shape[1:])

    return jax.tree.map(split, tree)


def _build_microbatch_local_step(loss_fn, inner, exchange, axes,
                                 loss_has_aux, aux_mode, with_frozen, k,
                                 guard=False, guard_norm_limit=0.0,
                                 guard_axes=None):
    """Per-device step body for ``microbatches=k > 1``: an UNROLLED loop
    over k sub-batches whose trace interleaves each microbatch's bucket
    reduce-scatters between backward segments (the HLO-structure the
    overlap test asserts), one optimizer update on the merged gradients.

    Equivalence contract: with a per-example-MEAN loss (the usual
    ``.mean()`` losses; what the parity tests use), the mean of the k
    sub-batch gradients equals the full-batch gradient, so k>1 matches the
    single-shot step at the same global batch to accumulation-order
    tolerance.  A per-example-SUM loss would need ``prescale_factor=k`` --
    same caveat as any gradient-accumulation scheme.  ``aux_mode
    "stacked"`` gains a leading ``[k]`` axis per device; ``"averaged"``
    averages floating aux leaves over microbatches before the allreduce.
    """
    ef = exchange is not None and _is_ef_exchange(exchange)
    accumulate, finalize = _microbatch_grad_pipe(
        None if ef else exchange, axes, k=k)
    g_axes = tuple(guard_axes) if guard_axes is not None else axes

    def local_step(params, opt_state, batch, *frozen):
        lf = (lambda p, b: loss_fn(p, frozen[0], b)) if with_frozen \
            else loss_fn
        micro = _split_microbatches(batch, k)
        if ef:
            if not isinstance(opt_state, _dist._EFState):
                opt_state = _dist._EFState(*opt_state)
            residuals = tuple(r[0] for r in opt_state.residuals)
            inner_state = opt_state.inner
        else:
            inner_state = opt_state
        state, losses, auxes, grads = None, [], [], None
        for i in range(k):
            mb = jax.tree.map(lambda a: a[i], micro)
            if loss_has_aux:
                (loss_i, aux_i), grads = jax.value_and_grad(
                    lf, has_aux=True)(params, mb)
                auxes.append(aux_i)
            else:
                loss_i, grads = jax.value_and_grad(lf)(params, mb)
            losses.append(loss_i)
            state = accumulate(grads, state)
        reduced = finalize(state, k, grads)
        if guard:
            # Screen the merged gradient (already cross-rank for a wrapped
            # exchange): nonfinite sub-batch contributions have propagated
            # into it by now, and screening BEFORE ef_exchange/update means
            # the skip select below discards the residuals a poisoned
            # exchange would have produced.
            # opt_state here is still the incoming carry (normalized to
            # _EFState on the ef path), structure-matched to the new one.
            old_params, old_opt = params, opt_state
            _note_guard_leg()
            gvec = _ops.allreduce(_guard_screen_vec(reduced), Sum,
                                  axes=g_axes)
        if ef:
            reduced, new_res = _dist.ef_exchange(
                reduced, residuals, compression=exchange["compression"],
                op=exchange["op"],
                fusion_threshold=exchange["fusion_threshold"], axes=axes,
                prescale_factor=exchange["prescale_factor"],
                postscale_factor=exchange["postscale_factor"])
        updates, inner_state = inner.update(reduced, inner_state, params)
        opt_state = _dist._EFState(
            tuple(r[None] for r in new_res), inner_state) if ef \
            else inner_state
        params = optax.apply_updates(params, updates)
        if guard:
            nonfinite, norm, bad = _guard_verdict(gvec, guard_norm_limit)
            params = _guard_select(bad, old_params, params)
            opt_state = _guard_select(bad, old_opt, opt_state)
            guard_out = jnp.stack([nonfinite, norm,
                                   bad.astype(jnp.float32)])
        loss = _ops.allreduce(jnp.mean(jnp.stack(losses)), Average,
                              axes=axes)
        out = (params, opt_state, loss)
        if loss_has_aux:
            if aux_mode == "averaged":
                aux = jax.tree.map(
                    lambda *xs: jnp.mean(jnp.stack(xs), axis=0)
                    if jnp.issubdtype(xs[0].dtype, jnp.floating)
                    else xs[-1], *auxes)
                aux = jax.tree.map(
                    lambda v: _ops.allreduce(v, Average, axes=axes)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            else:
                aux = jax.tree.map(lambda *xs: jnp.stack(xs), *auxes)
            out = out + (aux,)
        if guard:
            out = out + (guard_out,)
        return out

    return local_step


def _build_flax_microbatch_local_step(apply_fn, inner, exchange, loss_fn,
                                      axes, k, guard=False,
                                      guard_norm_limit=0.0,
                                      guard_axes=None):
    """Flax counterpart of :func:`_build_microbatch_local_step`.

    BatchNorm note: batch statistics CHAIN through the k microbatches
    (microbatch i normalizes with the stats microbatch i-1 produced, and
    the running EMA advances k times per step) -- a real semantic
    difference from the single-shot step's one full-batch normalization,
    inherent to any microbatched BN.  Stats-free models match the
    single-shot step to accumulation tolerance; the final stats cross the
    mesh in the same one-allreduce-per-leaf exchange as single-shot.
    """
    if loss_fn is None:
        def loss_fn(logits, y):
            return _softmax_xent(logits, y)
    ef = exchange is not None and _is_ef_exchange(exchange)
    accumulate, finalize = _microbatch_grad_pipe(
        None if ef else exchange, axes, k=k)
    g_axes = tuple(guard_axes) if guard_axes is not None else axes

    def local_step(params, batch_stats, opt_state, batch):
        x, y = batch
        xs = _split_microbatches(x, k)
        ys = _split_microbatches(y, k)
        stats = batch_stats
        if ef:
            if not isinstance(opt_state, _dist._EFState):
                opt_state = _dist._EFState(*opt_state)
            residuals = tuple(r[0] for r in opt_state.residuals)
            inner_state = opt_state.inner
        else:
            inner_state = opt_state
        state, losses, grads = None, [], None
        for i in range(k):
            xi = jax.tree.map(lambda a: a[i], xs)
            yi = jax.tree.map(lambda a: a[i], ys)

            def lf(p, stats=stats, xi=xi, yi=yi):
                variables = {"params": p}
                if stats:
                    variables["batch_stats"] = stats
                    logits, mutated = apply_fn(variables, xi, train=True,
                                               mutable=["batch_stats"])
                    return (loss_fn(logits, yi),
                            mutated.get("batch_stats", {}))
                logits = apply_fn(variables, xi, train=True)
                return loss_fn(logits, yi), {}

            (loss_i, stats), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            losses.append(loss_i)
            state = accumulate(grads, state)
        reduced = finalize(state, k, grads)
        if guard:
            old_params, old_opt = params, opt_state
            _note_guard_leg()
            gvec = _ops.allreduce(_guard_screen_vec(reduced), Sum,
                                  axes=g_axes)
        if ef:
            reduced, new_res = _dist.ef_exchange(
                reduced, residuals, compression=exchange["compression"],
                op=exchange["op"],
                fusion_threshold=exchange["fusion_threshold"], axes=axes,
                prescale_factor=exchange["prescale_factor"],
                postscale_factor=exchange["postscale_factor"])
        updates, inner_state = inner.update(reduced, inner_state, params)
        opt_state = _dist._EFState(
            tuple(r[None] for r in new_res), inner_state) if ef \
            else inner_state
        params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(
            lambda v: _ops.allreduce(v, Average, axes=axes), stats)
        loss = _ops.allreduce(jnp.mean(jnp.stack(losses)), Average,
                              axes=axes)
        if guard:
            nonfinite, norm, bad = _guard_verdict(gvec, guard_norm_limit)
            params = _guard_select(bad, old_params, params)
            opt_state = _guard_select(bad, old_opt, opt_state)
            new_stats = _guard_select(bad, batch_stats, new_stats)
            guard_out = jnp.stack([nonfinite, norm,
                                   bad.astype(jnp.float32)])
            return params, new_stats, opt_state, loss, guard_out
        return params, new_stats, opt_state, loss

    return local_step


def make_train_loop(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    steps_per_execution: Optional[int] = None,
    donate: bool = True,
    loss_has_aux: bool = False,
    aux_mode: str = "stacked",
    with_frozen: bool = False,
    zero_stage: Optional[int] = None,
    zero_compression=None,
    microbatches: Optional[int] = None,
    tp: Optional[int] = None,
    pipeline_stages: Optional[int] = None,
    param_specs=None,
    opt_state_specs=None,
) -> Callable[[Any, Any, Any], Tuple[Any, Any, jnp.ndarray]]:
    """Steps-per-execution runner: k train steps as ONE executable.

    Builds ``loop(params, opt_state, batches) -> (params, opt_state,
    losses)`` where ``batches`` stacks k per-step batches on a leading
    axis (``[k, global_batch, ...]`` per leaf -- :func:`stack_steps`, or
    :class:`horovod_tpu.data.DevicePrefetcher` with ``stack_steps=k``)
    and ``losses`` is the ``[k]`` per-step global-mean loss history.

    The k steps run inside one ``jax.lax.scan`` with the params/opt-state
    carry donated, so a whole window costs ONE host dispatch and ONE
    device->host fence instead of k of each -- the reference hides that
    host overhead behind its background thread; under XLA the loop simply
    never returns to the host.  The step body is byte-for-byte the
    :func:`make_train_step` body, so k scanned steps match k sequential
    step calls bitwise.

    ``steps_per_execution=None`` reads ``HOROVOD_STEPS_PER_EXEC``
    (autotuner steps axis wins when active -- see
    :func:`steps_per_execution`).  All other knobs (``loss_has_aux``,
    ``aux_mode``, ``with_frozen``, ``zero_stage``,  ``microbatches``...)
    behave as in :func:`make_train_step`; stacked aux gains a leading k
    axis.  ``microbatches > 1`` microbatches EACH scanned step (the two
    k's compose: steps_per_execution batches dispatches, microbatches
    overlaps the exchange inside every step).
    """
    if aux_mode not in ("stacked", "averaged"):
        raise ValueError(f"unknown aux_mode {aux_mode!r}")
    zero_stage = _resolve_zero_stage(zero_stage)
    k_micro = _resolve_microbatches(microbatches)
    if zero_stage:
        if k_micro > 1:
            raise ValueError(
                "microbatches > 1 is incompatible with zero_stage=1 (the "
                "ZeRO-1 arena reduce-scatter is already shard-based; "
                "overlap it via HOROVOD_EXCHANGE_CHUNK_MB instead)")
        _zero._reject_distributed(optimizer)
    mesh = mesh or _basics.mesh()
    tp = _resolve_tp(tp)
    pipeline_stages = _resolve_pipeline_stages(pipeline_stages)
    axes, model_ax = _resolve_model_axes(mesh, tp, pipeline_stages)
    _check_model_parallel_exchange(optimizer, axes, model_ax)
    k = _resolve_steps(steps_per_execution)
    guard_on, guard_limit = _resolve_guard()
    if k_micro > 1:
        inner, exchange = _microbatch_unwrap(optimizer)
        local_step = _build_microbatch_local_step(
            loss_fn, inner, exchange, axes, loss_has_aux, aux_mode,
            with_frozen, k_micro, guard=guard_on,
            guard_norm_limit=guard_limit,
            guard_axes=tuple(mesh.axis_names))
    else:
        local_step = _build_local_step(loss_fn, optimizer, axes,
                                       loss_has_aux, aux_mode, with_frozen,
                                       zero_stage, zero_compression,
                                       guard=guard_on,
                                       guard_norm_limit=guard_limit,
                                       guard_axes=tuple(mesh.axis_names))

    def local_loop(params, opt_state, batches, *frozen):
        def body(carry, batch):
            out = local_step(carry[0], carry[1], batch, *frozen)
            # Trailing outputs (loss[, aux][, guard]) stack on a leading
            # [k] axis; with guard the history is [k, 3] so the host
            # policy sees every scanned step, not just the last.
            return (out[0], out[1]), tuple(out[2:])

        (params, opt_state), ys = jax.lax.scan(
            body, (params, opt_state), batches, length=k)
        return (params, opt_state) + tuple(ys)

    # Batch leaves carry a leading steps axis: dim 0 scans, dim 1 shards.
    aux_spec = () if not loss_has_aux else \
        ((P(),) if aux_mode == "averaged" else (P(None, axes),))
    guard_spec = (P(),) if guard_on else ()
    frozen_spec = (P(),) if with_frozen else ()
    p_spec = param_specs if param_specs is not None else P()
    opt_spec = _opt_state_spec(optimizer, zero_stage,
                               tuple(mesh.axis_names),
                               override=opt_state_specs)
    shard = jax.shard_map(
        local_loop, mesh=mesh,
        in_specs=(p_spec, opt_spec, P(None, axes)) + frozen_spec,
        out_specs=(p_spec, opt_spec, P()) + aux_spec + guard_spec,
        check_vma=False)
    donate_argnums = (0, 1) if donate else ()

    meta = {"optimizer": optimizer,
            "zero_stage": zero_stage,
            "zero_compression": zero_compression,
            "microbatches": k_micro,
            "guard": guard_on,
            "tp": tp,
            "pipeline_stages": pipeline_stages,
            "data_mesh": tuple(int(mesh.shape[a]) for a in axes),
            "data_axes": tuple(str(a) for a in axes),
            "mesh_shape": tuple((a, int(mesh.shape[a]))
                                for a in mesh.axis_names),
            "param_specs": param_specs,
            "world": int(math.prod(mesh.shape[a] for a in axes))}
    step = _maybe_tuned(shard, donate_argnums, loss_index=2, steps=k,
                        meta=meta)
    return _GuardedStep(step, meta) if guard_on else step


def _maybe_tuned(shard, donate_argnums, loss_index: int, steps: int = 1,
                 meta: Optional[dict] = None):
    """jit the sharded step; under HOROVOD_AUTOTUNE=1 wrap it in the
    ParameterManager score loop.

    The fusion threshold is read at trace time, so each candidate needs
    its own trace (where the step builds buckets at all:
    :func:`_step_builds_buckets`) -- one compiled step per trace key,
    observed step time fed back to the tuner (the reference's score loop,
    minus the background thread).  The timing fence is a value fetch of the loss;
    it adds a constant per-step latency that cancels in the per-config
    ranking.

    ``steps`` is the scan-loop steps-per-execution: one call of a k-step
    loop moves k steps' worth of gradient bytes, so the bytes/sec score
    stays comparable across loop shapes.

    ``meta`` is the builder's exchange description consumed by the
    StepReport instrumentation (optimizer, zero stage/codec, microbatch
    count, mesh size); the jitted step comes back wrapped in
    :class:`_InstrumentedStep` unless metrics are disabled.
    """
    from .core.state import global_state
    from .timeline import metrics as _metrics
    tuner = global_state().autotuner
    if tuner is None:
        fn = jax.jit(shard, donate_argnums=donate_argnums)
    else:
        import time as _time
        compiled = {}
        grad_nbytes = [0]

        def tuned_step(params, *rest):
            key = tuner.trace_key()  # every trace-time knob of this sample
            # A step that builds no fusion bucket is the same program at
            # every threshold: one compile, one sample for all of them.
            inert = meta is not None and not _step_builds_buckets(meta)
            if inert:
                key = key[1:]
            fn = compiled.get(key)
            if fn is None:
                fn = jax.jit(shard, donate_argnums=donate_argnums)
                compiled[key] = fn
            if tuner.done:
                return fn(params, *rest)
            if not grad_nbytes[0]:
                grad_nbytes[0] = sum(
                    x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(params))
            t0 = _time.perf_counter()
            out = fn(params, *rest)
            float(jnp.asarray(out[loss_index]).ravel()[0])  # honest fence
            tuner.record_step(_time.perf_counter() - t0,
                              grad_nbytes[0] * steps, threshold_inert=inert)
            return out

        fn = tuned_step

    if not _metrics.registry().enabled:
        return fn
    return _InstrumentedStep(fn, steps, meta or {})


class _InstrumentedStep:
    """Host-side StepReport sampler around the jitted step.

    Times the DISPATCH of the underlying callable (no extra fence, no
    device work) and feeds the process-wide metrics registry a
    :class:`~horovod_tpu.timeline.metrics.StepReport` per call.  Every
    other attribute (``.lower``, AOT paths) delegates to the wrapped
    ``jax.jit`` object, and nothing is added INSIDE the traced program,
    so buffer donation and scan-loop bitwise parity are untouched.

    Exchange accounting is computed lazily from the first call's params
    (shape/dtype reads only -- before the donated buffers are consumed)
    and must match the existing bookkeeping byte-for-byte: the ZeRO-1
    path reuses ``zero_report`` and the compressed path reuses
    ``wire_payload_bytes`` over the exchange's own bucket plan.  A failure in the accounting degrades to
    zeros -- it must never break training.
    """

    def __init__(self, fn, steps: int, meta: dict):
        self._fn = fn
        self._steps = max(int(steps), 1)
        self._meta = meta
        self._accounting: Optional[Tuple[str, int, int, int]] = None
        self._step_count = 0
        # perf_counter at the previous call's return: the time until the
        # next call is the host dispatch gap (input pipeline, Python
        # glue, injected chaos delays) the span layer attributes.
        self._last_end: Optional[float] = None

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def _account(self, params) -> Tuple[str, int, int, int]:
        if self._accounting is None:
            try:
                self._accounting = _step_exchange_accounting(
                    params, self._meta)
            except Exception:
                self._accounting = ("unknown", 0, 0, 0)
        return self._accounting

    def __call__(self, params, *rest):
        from .timeline import metrics as _metrics
        from .timeline import spans as _spans
        import time as _time
        reg = _metrics.registry()
        if not reg.enabled:
            return self._fn(params, *rest)
        codec, wire, raw, packed = self._account(params)
        rec = _spans.recorder()
        step = self._step_count + self._steps
        rec.set_step(step)
        t0 = _time.perf_counter()
        t0_unix_us = _time.time() * 1e6
        gap = (t0 - self._last_end) if self._last_end is not None else 0.0
        if gap > 0:
            rec.add("dispatch_gap", gap, emit=True)
        # StepTraceAnnotation: the profiler's trace groups the device's
        # work by step number; the span beside it is the same interval
        # in the program's own records.
        with jax.profiler.StepTraceAnnotation("hvd.train_step",
                                              step_num=step), \
                rec.span("dispatch", name="step", step=step):
            out = self._fn(params, *rest)
        t1 = _time.perf_counter()
        wall = t1 - t0
        self._last_end = t1
        self._step_count += self._steps
        try:
            _metrics.record_step_report(_metrics.StepReport(
                step=self._step_count,
                wall_time_s=wall,
                steps_per_exec=self._steps,
                microbatches=int(self._meta.get("microbatches", 1)),
                zero_stage=int(self._meta.get("zero_stage", 0)),
                codec=codec,
                exchanged_bytes=wire,
                uncompressed_bytes=raw,
                packed_bytes=packed))
        except Exception:
            pass
        try:
            # Step summary wall INCLUDES the dispatch gap (a late host
            # is a late rank); the wall-clock anchor backs up to the
            # gap's start so merged traces show the full step extent.
            rec.step_boundary(step, wall + gap,
                              t0_unix_us=t0_unix_us - gap * 1e6)
        except Exception:
            pass
        return out


class _GuardedStep:
    """Host-side SDC policy around a guarded step.

    The guarded trace appends a trailing replicated ``f32[3]`` guard
    vector (``[k, 3]`` for a scan loop); this wrapper strips it from the
    outputs -- callers see exactly the unguarded signature -- and feeds
    it to :func:`horovod_tpu.core.guard.policy`, which counts the
    ``horovod_guard_*`` metrics and raises
    :class:`~horovod_tpu.core.exceptions.SustainedAnomalyError` when a
    skip streak reaches ``HOROVOD_GUARD_STREAK``.  The fetch of the tiny
    guard vector is the guard's only host cost (it does fence the step;
    that is the price of a same-step verdict).  Attribute access
    delegates to the wrapped step (``.lower``, ``._meta``, AOT paths).
    """

    def __init__(self, fn, meta: dict):
        self._fn = fn
        self._meta = meta

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *args):
        out = self._fn(*args)
        from .core import guard as _guard
        import numpy as np
        _guard.policy().observe(np.asarray(out[-1]))
        return out[:-1]


def _step_builds_buckets(meta) -> bool:
    """Whether a step built with ``meta`` copies its gradients into flat
    fusion buffers, DERIVED from the route its exchange takes under the
    settings in force (not read off the lowered program).  ZeRO-1's
    arenas do; the stateful error-feedback exchange is the wrap's own and
    keeps a residual a bucket at any world.  Otherwise world 1 maps the
    collective over the leaves whatever the exchange, the microbatched
    step reduce-scatters vectors whatever the codec, and the wrap's
    allreduce says for itself
    (:func:`~horovod_tpu.optim.distributed.exchange_packs`)."""
    if meta.get("zero_stage"):
        return True
    exchange = getattr(getattr(meta.get("optimizer"), "update", None),
                       "_hvd_exchange", None)
    if exchange is None:
        return False
    from .collectives.compression import is_error_feedback
    comp = exchange["compression"]
    return is_error_feedback(comp) or int(meta.get("world", 1)) > 1 and (
        int(meta.get("microbatches", 1)) > 1 or _dist.exchange_packs(
            comp, exchange["op"], axes=exchange["axes"],
            process_set=exchange["process_set"]))


def _step_exchange_accounting(params, meta) -> Tuple[str, int, int, int]:
    """``(codec, wire_bytes_per_step, uncompressed_bytes_per_step,
    packed_bytes_per_step)`` for the exchange a step built with ``meta``
    emits, per chip per optimizer step.

    ZeRO-1: ``zero_report``'s ``zero1_exchanged_bytes_per_chip`` against
    its ``replicated_allreduce_bytes_per_chip`` equivalent.
    DistributedOptimizer wrap: ``wire_payload_bytes`` summed over the
    exchange's own bucket plan (``ef_bucket_plan`` for error-feedback
    codecs, ``plan_buckets`` otherwise) against the raw gradient bytes.
    Bare optimizer: no collective, wire 0.  The microbatch overlap factor
    is NOT folded in -- the figure is the equivalent single-exchange
    payload (see :class:`~horovod_tpu.timeline.metrics.StepReport`).

    Packed bytes are the gradient bytes the step copies into flat fusion
    buffers, DERIVED from the route the exchange takes (not read off the
    lowered program): every one where the exchange needs a contiguous
    vector (ZeRO-1's arenas, the error-feedback wrap's residual
    buckets, the microbatched reduce-scatters, and whatever
    :func:`~horovod_tpu.optim.distributed.exchange_packs` says of the
    wrap's allreduce above world 1), none on the leaf-wise exchange.
    """
    leaves = jax.tree.leaves(params)
    raw = sum(int(x.size) * jnp.dtype(x.dtype).itemsize for x in leaves)
    packed = raw if _step_builds_buckets(meta) else 0
    optimizer = meta.get("optimizer")
    if meta.get("zero_stage"):
        rep = _zero.zero_report(optimizer, params,
                                int(meta.get("world", 1)),
                                compression=meta.get("zero_compression"))
        comp = meta.get("zero_compression")
        codec = getattr(comp, "__name__", None) or \
            (str(comp) if comp else "none")
        return (codec, int(rep["zero1_exchanged_bytes_per_chip"]),
                int(rep["replicated_allreduce_bytes_per_chip"]), packed)
    exchange = getattr(getattr(optimizer, "update", None),
                       "_hvd_exchange", None)
    if exchange is None:
        return ("none", 0, raw, packed)
    from .collectives.compression import (is_error_feedback,
                                          wire_payload_bytes)
    comp = exchange["compression"]
    if is_error_feedback(comp):
        spec = _dist.ef_bucket_plan(leaves, exchange["fusion_threshold"],
                                    comp)
    else:
        from .controller.fusion import plan_buckets
        spec = plan_buckets(leaves, exchange["fusion_threshold"])
    wire = 0
    for dt, lspecs in spec.buffers:
        size = sum(s.size for s in lspecs)
        wire += wire_payload_bytes(comp, size, jnp.dtype(dt).itemsize)
    return (getattr(comp, "__name__", type(comp).__name__), int(wire), raw,
            packed)


def make_flax_train_step(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    loss_fn: Optional[Callable] = None,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    zero_stage: Optional[int] = None,
    zero_compression=None,
    microbatches: Optional[int] = None,
    tp: Optional[int] = None,
    pipeline_stages: Optional[int] = None,
    param_specs=None,
    opt_state_specs=None,
):
    """Data-parallel train step for flax modules with mutable batch stats.

    Returns ``step(params, batch_stats, opt_state, (x, y)) ->
    (params, batch_stats, opt_state, loss)``.  BatchNorm running statistics
    are mean-allreduced each step (the reference's SyncBatchNorm stats
    exchange); gradients flow through ``optimizer`` (wrap with
    :func:`DistributedOptimizer`).  ``loss_fn(logits, y)`` defaults to
    softmax cross-entropy with integer labels.

    ``zero_stage=1`` shards the optimizer state as in
    :func:`make_train_step` (bare optax optimizer +
    :func:`horovod_tpu.zero_init` state); batch stats stay replicated.

    ``microbatches=k > 1`` (``HOROVOD_MICROBATCHES``) runs the
    backward-overlap exchange as in :func:`make_train_step`.  BatchNorm
    statistics chain through the k sub-batches (see
    :func:`_build_flax_microbatch_local_step` for the semantics).

    ``tp``/``pipeline_stages``/``param_specs`` behave as in
    :func:`make_train_step` (3-D parallelism over a ``build_3d_mesh``
    mesh; batch stats stay replicated).
    """
    zero_stage = _resolve_zero_stage(zero_stage)
    k_micro = _resolve_microbatches(microbatches)
    if zero_stage:
        if k_micro > 1:
            raise ValueError(
                "microbatches > 1 is incompatible with zero_stage=1 (the "
                "ZeRO-1 arena reduce-scatter is already shard-based; "
                "overlap it via HOROVOD_EXCHANGE_CHUNK_MB instead)")
        _zero._reject_distributed(optimizer)
    mesh = mesh or _basics.mesh()
    tp = _resolve_tp(tp)
    pipeline_stages = _resolve_pipeline_stages(pipeline_stages)
    axes, model_ax = _resolve_model_axes(mesh, tp, pipeline_stages)
    _check_model_parallel_exchange(optimizer, axes, model_ax)
    guard_on, guard_limit = _resolve_guard()
    if k_micro > 1:
        inner, exchange = _microbatch_unwrap(optimizer)
        local_step = _build_flax_microbatch_local_step(
            apply_fn, inner, exchange, loss_fn, axes, k_micro,
            guard=guard_on, guard_norm_limit=guard_limit,
            guard_axes=tuple(mesh.axis_names))
    else:
        local_step = _build_flax_local_step(apply_fn, optimizer, loss_fn,
                                            axes, zero_stage,
                                            zero_compression,
                                            guard=guard_on,
                                            guard_norm_limit=guard_limit,
                                            guard_axes=tuple(
                                                mesh.axis_names))

    guard_spec = (P(),) if guard_on else ()
    p_spec = param_specs if param_specs is not None else P()
    opt_spec = _opt_state_spec(optimizer, zero_stage,
                               tuple(mesh.axis_names),
                               override=opt_state_specs)
    shard = jax.shard_map(local_step, mesh=mesh,
                          in_specs=(p_spec, P(), opt_spec, P(axes)),
                          out_specs=(p_spec, P(), opt_spec, P())
                          + guard_spec,
                          check_vma=False)
    donate_argnums = (0, 1, 2) if donate else ()
    # Autotune applies here too (HOROVOD_AUTOTUNE=1): loss is element 3.
    meta = {"optimizer": optimizer,
            "zero_stage": zero_stage,
            "zero_compression": zero_compression,
            "microbatches": k_micro,
            "guard": guard_on,
            "tp": tp,
            "pipeline_stages": pipeline_stages,
            "data_mesh": tuple(int(mesh.shape[a]) for a in axes),
            "data_axes": tuple(str(a) for a in axes),
            "mesh_shape": tuple((a, int(mesh.shape[a]))
                                for a in mesh.axis_names),
            "param_specs": param_specs,
            "world": int(math.prod(mesh.shape[a] for a in axes))}
    step = _maybe_tuned(shard, donate_argnums, loss_index=3, meta=meta)
    return _GuardedStep(step, meta) if guard_on else step


def _build_flax_local_step(apply_fn, optimizer, loss_fn, axes, zero_stage,
                           zero_compression, guard=False,
                           guard_norm_limit=0.0, guard_axes=None):
    """Per-device flax step body shared by :func:`make_flax_train_step`
    and :func:`make_flax_train_loop` (bitwise parity, as with
    :func:`_build_local_step`).  The guard additionally pins the OLD
    batch stats on a poisoned step -- a NaN batch pollutes the BN running
    statistics as surely as it pollutes the gradients."""
    if loss_fn is None:
        def loss_fn(logits, y):
            return _softmax_xent(logits, y)
    g_axes = tuple(guard_axes) if guard_axes is not None else axes

    def local_step(params, batch_stats, opt_state, batch):
        x, y = batch

        def lf(p):
            variables = {"params": p}
            if batch_stats:
                variables["batch_stats"] = batch_stats
                logits, mutated = apply_fn(variables, x, train=True,
                                           mutable=["batch_stats"])
                return loss_fn(logits, y), mutated.get("batch_stats", {})
            logits = apply_fn(variables, x, train=True)
            return loss_fn(logits, y), {}

        (loss, new_stats), grads = jax.value_and_grad(lf, has_aux=True)(params)
        if guard:
            old_params, old_opt = params, opt_state
            _note_guard_leg()
            gvec = _ops.allreduce(_guard_screen_vec(grads), Sum,
                                  axes=g_axes)
        if zero_stage:
            params, opt_state = _zero.zero_apply(
                optimizer, grads, opt_state, params, axes=axes,
                compression=zero_compression)
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(
            lambda v: _ops.allreduce(v, Average, axes=axes), new_stats)
        loss = _ops.allreduce(loss, Average, axes=axes)
        if guard:
            nonfinite, norm, bad = _guard_verdict(gvec, guard_norm_limit)
            params = _guard_select(bad, old_params, params)
            opt_state = _guard_select(bad, old_opt, opt_state)
            new_stats = _guard_select(bad, batch_stats, new_stats)
            guard_out = jnp.stack([nonfinite, norm,
                                   bad.astype(jnp.float32)])
            return params, new_stats, opt_state, loss, guard_out
        return params, new_stats, opt_state, loss

    return local_step


def make_flax_train_loop(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    loss_fn: Optional[Callable] = None,
    mesh: Optional[Mesh] = None,
    steps_per_execution: Optional[int] = None,
    donate: bool = True,
    zero_stage: Optional[int] = None,
    zero_compression=None,
    microbatches: Optional[int] = None,
    tp: Optional[int] = None,
    pipeline_stages: Optional[int] = None,
    param_specs=None,
    opt_state_specs=None,
):
    """Steps-per-execution runner for flax modules with batch stats.

    Returns ``loop(params, batch_stats, opt_state, batches) -> (params,
    batch_stats, opt_state, losses)``: the :func:`make_flax_train_step`
    body scanned k times in one executable (one dispatch, one fence),
    with the params/stats/opt-state carry donated.  ``batches`` stacks k
    ``(x, y)`` pairs on a leading axis (:func:`stack_steps`); ``losses``
    is the ``[k]`` per-step loss history.  See :func:`make_train_loop`.

    Note the flax carry includes batch stats only when non-empty: an
    empty-stats model scans the same body with an empty-dict carry leaf,
    exactly as the single step does.
    """
    zero_stage = _resolve_zero_stage(zero_stage)
    k_micro = _resolve_microbatches(microbatches)
    if zero_stage:
        if k_micro > 1:
            raise ValueError(
                "microbatches > 1 is incompatible with zero_stage=1 (the "
                "ZeRO-1 arena reduce-scatter is already shard-based; "
                "overlap it via HOROVOD_EXCHANGE_CHUNK_MB instead)")
        _zero._reject_distributed(optimizer)
    mesh = mesh or _basics.mesh()
    tp = _resolve_tp(tp)
    pipeline_stages = _resolve_pipeline_stages(pipeline_stages)
    axes, model_ax = _resolve_model_axes(mesh, tp, pipeline_stages)
    _check_model_parallel_exchange(optimizer, axes, model_ax)
    k = _resolve_steps(steps_per_execution)
    guard_on, guard_limit = _resolve_guard()
    if k_micro > 1:
        inner, exchange = _microbatch_unwrap(optimizer)
        local_step = _build_flax_microbatch_local_step(
            apply_fn, inner, exchange, loss_fn, axes, k_micro,
            guard=guard_on, guard_norm_limit=guard_limit,
            guard_axes=tuple(mesh.axis_names))
    else:
        local_step = _build_flax_local_step(apply_fn, optimizer, loss_fn,
                                            axes, zero_stage,
                                            zero_compression,
                                            guard=guard_on,
                                            guard_norm_limit=guard_limit,
                                            guard_axes=tuple(
                                                mesh.axis_names))

    def local_loop(params, batch_stats, opt_state, batches):
        def body(carry, batch):
            out = local_step(*carry, batch)
            return (out[0], out[1], out[2]), tuple(out[3:])

        (params, batch_stats, opt_state), ys = jax.lax.scan(
            body, (params, batch_stats, opt_state), batches, length=k)
        return (params, batch_stats, opt_state) + tuple(ys)

    guard_spec = (P(),) if guard_on else ()
    p_spec = param_specs if param_specs is not None else P()
    opt_spec = _opt_state_spec(optimizer, zero_stage,
                               tuple(mesh.axis_names),
                               override=opt_state_specs)
    shard = jax.shard_map(local_loop, mesh=mesh,
                          in_specs=(p_spec, P(), opt_spec, P(None, axes)),
                          out_specs=(p_spec, P(), opt_spec, P())
                          + guard_spec,
                          check_vma=False)
    donate_argnums = (0, 1, 2) if donate else ()
    meta = {"optimizer": optimizer,
            "zero_stage": zero_stage,
            "zero_compression": zero_compression,
            "microbatches": k_micro,
            "guard": guard_on,
            "tp": tp,
            "pipeline_stages": pipeline_stages,
            "data_mesh": tuple(int(mesh.shape[a]) for a in axes),
            "data_axes": tuple(str(a) for a in axes),
            "mesh_shape": tuple((a, int(mesh.shape[a]))
                                for a in mesh.axis_names),
            "param_specs": param_specs,
            "world": int(math.prod(mesh.shape[a] for a in axes))}
    step = _maybe_tuned(shard, donate_argnums, loss_index=3, steps=k,
                        meta=meta)
    return _GuardedStep(step, meta) if guard_on else step


def _softmax_xent(logits, y):
    import optax as _optax
    return _optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def make_eval_step(metric_fn: Callable[[Any, Any], Any],
                   mesh: Optional[Mesh] = None):
    """Build an eval step that averages ``metric_fn`` over the mesh."""
    mesh = mesh or _basics.mesh()
    axes = tuple(mesh.axis_names)

    def local_eval(params, batch):
        m = metric_fn(params, batch)
        return jax.tree.map(
            lambda v: _ops.allreduce(v, Average, axes=axes), m)

    shard = jax.shard_map(local_eval, mesh=mesh, in_specs=(P(), P(axes)),
                          out_specs=P(), check_vma=False)
    return jax.jit(shard)
