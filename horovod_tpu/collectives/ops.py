"""In-step collective ops: the XLA-collective re-implementation of the
reference's op layer.

This is the TPU-native replacement for ``horovod/common/ops/nccl_operations.cc``
(``NCCLAllreduce``, ``NCCLAllgather``, ``NCCLBroadcast``, ``NCCLAlltoall``,
``NCCLReducescatter``) and ``mpi_operations.cc``: every collective is a
``jax.lax`` primitive emitted *inside* a ``jax.shard_map``-traced program
over the ICI/DCN mesh, so XLA schedules the DMA over the physical links --
there is no user-level comm library, no streams, no fusion-buffer memcpy
kernels.  Pre/post-scaling (the reference's CUDA ``ScaleBuffer`` kernels)
become fused elementwise multiplies.

All functions here must be called inside a traced context that binds the
mesh axis names (``shard_map`` over ``hvd.mesh()``); the eager wrappers in
``horovod_tpu.collectives.eager`` do that wrapping for host-level use.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reduce_op import ReduceOp, Average, Sum, Min, Max, Product, Adasum
from ..core.state import global_state
from ..core import process_sets as _ps

AxisSpec = Union[str, Tuple[str, ...]]


def _default_axes() -> Tuple[str, ...]:
    st = global_state()
    if st.mesh is None:
        raise RuntimeError("horovod_tpu.init() must run before collectives")
    return tuple(st.mesh.axis_names)


def _resolve(axes: Optional[AxisSpec],
             process_set=None) -> Tuple[Tuple[str, ...], Optional[Tuple[int, ...]]]:
    """Resolve (axis names, member ranks) for a collective.

    ``members`` is ``None`` for the global set.  In-step process-set
    collectives are implemented with *masked* full-mesh collectives
    (non-members contribute the op's identity and keep their own value):
    JAX 0.9's shard_map does not lower ``axis_index_groups``, and on the
    ICI torus a full-ring reduction is usually as fast as a subgroup one
    anyway -- the masking costs one fused elementwise select.
    """
    if axes is None:
        axes = _default_axes()
    elif isinstance(axes, str):
        axes = (axes,)
    members = None
    if process_set is not None:
        ps = _ps.get_process_set(process_set)
        if not ps.is_global():
            members = ps.ranks
    return tuple(axes), members


def _member_mask(axes: Tuple[str, ...], members: Tuple[int, ...]):
    return jnp.isin(axis_index(axes), jnp.asarray(members))


def _member_pos(axes: Tuple[str, ...], members: Tuple[int, ...]):
    """This device's position within ``members`` (0 for non-members).

    ``members`` is static, so the rank->position table is baked into the
    program as a constant gather.
    """
    size = math.prod(lax.axis_size(a) for a in axes)
    table = np.zeros((size,), np.int32)
    table[list(members)] = np.arange(len(members), dtype=np.int32)
    return jnp.asarray(table)[axis_index(axes)]


def _gather_rows(x, axes: Tuple[str, ...]):
    """Stack every mesh member's ``x`` along a new leading axis, ordered by
    the row-major flattened index (matching :func:`axis_index`)."""
    g = x[None]
    for a in reversed(axes):
        g = lax.all_gather(g, a, axis=0, tiled=True)
    return g


def axis_size(axes: Optional[AxisSpec] = None) -> int:
    axes, _ = _resolve(axes)
    return math.prod(lax.axis_size(a) for a in axes)


def axis_index(axes: Optional[AxisSpec] = None):
    """Flattened device index along the reduce axes (row-major)."""
    axes, _ = _resolve(axes)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _divide_in_dtype(y, n: int):
    """Average's division, in the tensor's own dtype.

    Integer tensors use lax.div (C-style truncation toward zero -- the
    reference reduces in the tensor's dtype); true division would promote
    to float and change the output dtype.  // is NOT equivalent: it
    floors, so negative sums would round away from zero.
    """
    if jnp.issubdtype(y.dtype, jnp.integer):
        return lax.div(y, jnp.asarray(n, dtype=y.dtype))
    return y / jnp.asarray(n, dtype=y.dtype)


def allreduce(x,
              op: ReduceOp = Average,
              *,
              axes: Optional[AxisSpec] = None,
              process_set=None,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0,
              wire_codec=None):
    """Allreduce one array across the mesh (NCCLAllreduce analogue).

    With a process set, members reduce among themselves and non-members
    receive their input unchanged (they would not have called the op in
    the reference's per-rank model).

    ``wire_codec="fp8"`` (Adasum only): quantize the VHDD exchanges to
    e4m3 on the wire -- see ``adasum/xla.py``.  Sum/Average fp8 goes
    through :func:`fp8_allreduce` instead (a psum cannot carry it).
    """
    if wire_codec is not None and op is not Adasum:
        raise ValueError(
            f"wire_codec={wire_codec!r} applies to Adasum only; use "
            f"fp8_allreduce for {op}")
    axes, members = _resolve(axes, process_set)
    x_orig = x
    mask = None
    if members is not None:
        mask = _member_mask(axes, members)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)

    if op in (Sum, Average):
        contrib = x if mask is None else jnp.where(mask, x,
                                                   jnp.zeros((), x.dtype))
        y = lax.psum(contrib, axes)
        if op is Average:
            n = len(members) if members is not None else \
                math.prod(lax.axis_size(a) for a in axes)
            y = _divide_in_dtype(y, n)
    elif op in (Min, Max):
        if mask is not None:
            if jnp.issubdtype(x.dtype, jnp.integer):
                info = jnp.iinfo(x.dtype)
                ident = info.max if op is Min else info.min
            else:
                ident = jnp.inf if op is Min else -jnp.inf
            x = jnp.where(mask, x, jnp.asarray(ident, x.dtype))
        y = lax.pmin(x, axes) if op is Min else lax.pmax(x, axes)
    elif op is Product:
        # No pprod primitive: gather then reduce (small tensors only; XLA
        # fuses the reduction with the gather output).
        if mask is not None:
            x = jnp.where(mask, x, jnp.ones((), x.dtype))
        g = lax.all_gather(x, axes, axis=0)
        # dtype= keeps the input dtype: jnp.prod would promote small ints
        # to a 32-bit accumulator (reference collectives reduce in the
        # tensor's own dtype, wraparound included).
        y = jnp.prod(g, axis=0, dtype=g.dtype)
    elif op is Adasum:
        from ..adasum.xla import (adasum_allreduce,
                                  adasum_allreduce_hierarchical,
                                  adasum_local_tree)
        if members is not None:
            if len(members) & (len(members) - 1) != 0:
                raise ValueError(
                    f"Adasum requires a power-of-two member count, got "
                    f"{len(members)}")
            if len(axes) == 1:
                # Masked VHDD over the full flat mesh: the same
                # vector-halving schedule paired by member POSITION, so
                # subset Adasum moves O(n) bytes per member like the
                # global path (was: gather O(mesh * n) everywhere + a
                # replicated local tree).
                y = adasum_allreduce(x, axis=axes[0], members=members,
                                     wire_codec=wire_codec)
            else:
                # Hierarchical (multi-axis) mesh: ppermute needs a flat
                # axis, so the subset falls back to gather + replicated
                # binary tree -- O(mesh * n) bytes, fine for the small
                # sets this path serves.
                if wire_codec is not None:
                    raise NotImplementedError(
                        "fp8 wire is not supported for process-set Adasum "
                        "on multi-axis meshes (the gather fallback has no "
                        "quantized exchange)")
                sel = _gather_rows(x, axes)[np.asarray(members)]
                y = adasum_local_tree([sel[i]
                                       for i in range(len(members))])
        elif len(axes) == 1:
            y = adasum_allreduce(x, axis=axes[0], wire_codec=wire_codec)
        elif len(axes) == 2:
            # Hierarchical (dcn, ici) mesh: the reference's hybrid Adasum
            # (intra-node ReduceScatter -> cross-node Adasum -> Allgather,
            # adasum_gpu_operations.cc).
            y = adasum_allreduce_hierarchical(x, dcn_axis=axes[0],
                                              ici_axis=axes[1],
                                              wire_codec=wire_codec)
        else:
            raise NotImplementedError(
                "Adasum supports flat or 2-level (dcn, ici) meshes")
    else:
        raise ValueError(f"unknown reduce op {op}")
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, dtype=y.dtype)
    if mask is not None:
        y = jnp.where(mask, y, x_orig)
    return y


def hierarchical_allreduce(x,
                           op: ReduceOp = Average,
                           *,
                           dcn_axis: str,
                           ici_axis: str,
                           dcn_codec=None,
                           ici_codec=None,
                           dcn_residual=None,
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0):
    """Explicit two-level allreduce on a ``(dcn, ici)`` mesh
    (HOROVOD_HIERARCHICAL_ALLREDUCE parity, ``NCCLHierarchicalAllreduce``):
    intra-slice reduce-scatter over ICI, cross-slice allreduce of the
    1/n_ici shard over DCN, intra-slice allgather.

    A plain ``psum`` over both axes leaves the schedule to XLA (usually
    right on ICI-only meshes); this explicit form moves only the shard
    over the slow DCN links -- the reference's hierarchical algorithm --
    and is what the autotuner's ``hierarchical`` knob selects.  Sum and
    Average only (min/max/product don't scatter).

    Codecs apply PER LEG.  ``ici_codec`` (none/fp16/bf16 cast codecs
    only) sets the wire dtype of the intra-slice reduce-scatter and
    allgather; ``dcn_codec`` touches only the cross-slice hop of the
    1/n_ici shard and may additionally be fp8 (quantized gather-sum, f32
    accumulation) or an error-feedback codec (powersgd/topk over the DCN
    axis).  With an EF ``dcn_codec`` the return is
    ``(out, new_dcn_residual)`` -- ``dcn_residual`` is the previous
    step's unsent shard-domain f32 mass (``None`` = zeros), exactly the
    :func:`powersgd_allreduce` contract scoped to the DCN leg.

    The flat bucket is zero-padded to a multiple of
    ``microbatch_pad_quantum(n_ici)`` so the per-leg wire payload is
    mesh-invariant across every ``n_ici`` dividing 256 (held by
    ``tests/test_hierarchical.py``).  When the DCN axis has extent 1 (single slice) the
    two-level decomposition would only add reduction-order noise, so the
    op statically falls back to the flat ``psum`` over both axes --
    bitwise identical to :func:`allreduce` on the same mesh.
    """
    from .compression import (Compression, fp8_quantize, is_error_feedback,
                              is_fp8, is_powersgd, is_topk)
    if op not in (Sum, Average):
        raise ValueError(
            f"hierarchical_allreduce supports Sum/Average, got {op}")
    ici_codec = ici_codec or Compression.none
    dcn_codec = dcn_codec or Compression.none
    if getattr(ici_codec, "wire_format", ""):
        raise ValueError(
            f"ICI leg codec must be psum-compatible (none|fp16|bf16), "
            f"got {ici_codec.__name__}")
    n_ici = lax.axis_size(ici_axis)
    n_dcn = lax.axis_size(dcn_axis)
    n = n_ici * n_dcn
    ef = is_error_feedback(dcn_codec)
    floating = jnp.issubdtype(x.dtype, jnp.floating)
    if not floating:
        # Non-float buckets ride uncompressed (both legs).
        ici_codec = Compression.none
        dcn_codec = Compression.none
    quantum = microbatch_pad_quantum(n_ici)
    shard_len = (x.size + (-x.size) % quantum) // n_ici

    if n_dcn == 1:
        # Single slice: the DCN hop is an identity; the flat psum is both
        # cheaper and bitwise identical to allreduce() on this mesh.
        y = allreduce(x, op, axes=(dcn_axis, ici_axis),
                      prescale_factor=prescale_factor,
                      postscale_factor=postscale_factor)
        if ef:
            res = dcn_residual if dcn_residual is not None else \
                jnp.zeros((shard_len,), jnp.float32)
            return y, res
        return y

    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    shape, dtype = x.shape, x.dtype
    flat = x.ravel()
    pad = (-flat.size) % quantum
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    padded = flat.size
    itemsize = jnp.dtype(dtype).itemsize
    ici_wire, ici_ctx = ici_codec.compress(flat)
    ici_itemsize = jnp.dtype(ici_wire.dtype).itemsize
    # Trace-time per-leg registration (fires once per trace): the legs
    # come from the shared exchange-plan IR -- the SAME plan object the
    # auditor and explain_plan consume -- and each row carries the wire
    # byte accounting (RS/AG move the full padded bucket at the ICI wire
    # width, the DCN hop only the 1/n_ici shard at the DCN codec's
    # payload).
    from ..controller import fusion as _fusion
    from ..timeline import spans as _spans
    for _leg in _fusion.plan_exchange(
            "hier", size=int(x.size), dtype=str(dtype),
            n_dcn=int(n_dcn), n_ici=int(n_ici),
            ici_codec=ici_codec, dcn_codec=dcn_codec,
            dcn_axis=dcn_axis, ici_axis=ici_axis).legs:
        _spans.note_leg(_leg)

    shard = lax.psum_scatter(ici_wire, ici_axis, scatter_dimension=0,
                             tiled=True)
    shard = ici_codec.decompress(shard, ici_ctx)

    new_residual = None
    if ef and floating:
        # Compressed leader exchange: powersgd/topk of the shard over the
        # DCN axis only; the residual lives in the shard domain.
        if is_powersgd(dcn_codec):
            shard, new_residual = powersgd_allreduce(
                shard, Sum, rank=dcn_codec.rank, axes=(dcn_axis,),
                residual=dcn_residual, note=False)
        else:
            shard, new_residual = topk_allreduce(
                shard, Sum, fraction=dcn_codec.fraction, axes=(dcn_axis,),
                residual=dcn_residual, note=False)
    elif is_fp8(dcn_codec):
        # Quantized gather-sum: e4m3 on the DCN wire, exact f32
        # accumulation on chip (a psum would reduce IN fp8).
        q, scale = fp8_quantize(shard.astype(jnp.float32))
        gq = lax.all_gather(q[None], dcn_axis, axis=0, tiled=True)
        gs = lax.all_gather(scale.reshape(1), dcn_axis, axis=0,
                            tiled=True)
        shard = jnp.sum(gq.reshape(n_dcn, -1).astype(jnp.float32)
                        * gs[:, None], axis=0).astype(dtype)
    else:
        dcn_wire, dcn_ctx = dcn_codec.compress(shard)
        dcn_wire = lax.psum(dcn_wire, dcn_axis)
        shard = dcn_codec.decompress(dcn_wire, dcn_ctx)
    if op is Average:
        shard = _divide_in_dtype(shard, n)
    ag_wire, ag_ctx = ici_codec.compress(shard)
    y = lax.all_gather(ag_wire, ici_axis, axis=0, tiled=True)
    y = ici_codec.decompress(y, ag_ctx)
    if pad:
        y = y[:-pad]
    y = y.reshape(shape)
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, dtype=y.dtype)
    if ef:
        if new_residual is None:  # non-float bucket: nothing was unsent
            new_residual = dcn_residual if dcn_residual is not None else \
                jnp.zeros((shard_len,), jnp.float32)
        return y, new_residual
    return y


def chunked_allreduce(x,
                      op: ReduceOp = Average,
                      *,
                      chunk_bytes: int,
                      axes: Optional[AxisSpec] = None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    """Allreduce decomposed into chunk-sized reduce-scatter + all-gather
    pairs (``HOROVOD_EXCHANGE_CHUNK_MB``; Sum/Average, full mesh only).

    This XLA toolchain emits all-gather (and collective-permute) with async
    start/done pairs but keeps all-reduce and reduce-scatter synchronous
    (see ``utils/scaling.py``), so one monolithic bucket allreduce gives the
    latency-hiding scheduler nothing to overlap.  Splitting the bucket into
    chunk-sized ``psum_scatter`` + ``all_gather`` pieces moves the same
    total link payload -- RS(B) + AG(B) == 2*(n-1)/n*B == AR(B) -- while
    handing the scheduler independent pieces to interleave with the
    remaining backward compute.  Each chunk is zero-padded to a multiple of
    the mesh size (at most ``n-1`` elements per chunk, same trick as
    :func:`hierarchical_allreduce`).

    The reduction ORDER differs from a single ``psum`` (scatter-reduce
    semantics), so results are close but not bitwise identical to
    :func:`allreduce`; the knob is therefore opt-in (0 = off).
    """
    if op not in (Sum, Average):
        raise ValueError(f"chunked_allreduce supports Sum/Average, got {op}")
    axes, members = _resolve(axes, None)
    n = math.prod(lax.axis_size(a) for a in axes)
    if n == 1 or int(chunk_bytes) <= 0:
        return allreduce(x, op, axes=axes, prescale_factor=prescale_factor,
                         postscale_factor=postscale_factor)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    shape, dtype = x.shape, x.dtype
    flat = x.ravel()
    itemsize = jnp.dtype(dtype).itemsize
    # A chunk holds chunk_bytes, rounded up to a multiple of n elements so
    # every chunk scatters evenly across the mesh.
    chunk_elems = max(1, int(chunk_bytes) // itemsize)
    chunk_elems += (-chunk_elems) % n
    # Trace-time leg registration for straggler attribution (fires once
    # per trace; RS(B)+AG(B) moves an equivalent-allreduce payload).
    # The leg row comes from the shared plan IR: chunking acts on the
    # already-compressed wire buffer, so the plan sees the wire dtype.
    from ..controller import fusion as _fusion
    from ..timeline import spans as _spans
    _spans.note_leg(_fusion.plan_exchange(
        "chunked", size=int(flat.size), dtype=str(dtype),
        chunk_bytes=int(chunk_bytes), world=int(n)).legs[0])
    pieces = []
    for off in range(0, flat.size, chunk_elems):
        piece = flat[off:off + chunk_elems]
        pad = (-piece.size) % n
        if pad:
            piece = jnp.concatenate([piece, jnp.zeros((pad,), dtype)])
        shard = lax.psum_scatter(piece, axes, scatter_dimension=0,
                                 tiled=True)
        if op is Average:
            shard = _divide_in_dtype(shard, n)
        full = lax.all_gather(shard, axes, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        pieces.append(full)
    y = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    y = y.reshape(shape)
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, dtype=y.dtype)
    return y


def microbatch_pad_quantum(n: int, base: int = 256) -> int:
    """Padding quantum for the microbatched exchange: ``lcm(n, base)``.

    Buckets are zero-padded to a multiple of this before the per-microbatch
    reduce-scatter.  Padding to a multiple of ``n`` alone would make the
    padded byte count (and hence the wire payload) depend on the mesh
    size; padding to ``lcm(n, base)`` keeps it mesh-invariant across
    every ``n`` dividing ``base`` (256 covers the v5e/v5p pod sizes), so
    the planner's bytes are the payload's at every such ``n``.
    """
    return base * n // math.gcd(base, n)


def psum_scatter_bucket(flat, *, axes: Tuple[str, ...], quantum: int):
    """Zero-pad ``flat`` to a multiple of ``quantum`` and reduce-scatter
    it (Sum) over ``axes``; returns this rank's ``padded/n`` shard.

    The building block of the backward-overlap exchange: each microbatch's
    gradient bucket goes on the wire as one tiled ``psum_scatter`` the
    moment its backward segment produces it, while later microbatches are
    still computing.  The caller accumulates shards across microbatches and
    closes with one :func:`allgather_bucket`.
    """
    pad = (-flat.size) % quantum
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return lax.psum_scatter(flat, axes, scatter_dimension=0, tiled=True)


def allgather_bucket(shard, size: int, *, axes: Tuple[str, ...]):
    """All-gather a :func:`psum_scatter_bucket` shard back to the full
    bucket and strip the padding down to ``size`` elements."""
    full = lax.all_gather(shard, axes, axis=0, tiled=True)
    return full[:size] if full.size != size else full


def grouped_allreduce(xs: Sequence,
                      op: ReduceOp = Average,
                      *,
                      axes: Optional[AxisSpec] = None,
                      process_set=None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    """Allreduce a list of arrays as one fused unit (GroupTable analogue).

    The arrays are flattened into a single buffer (the HBM-resident
    fusion-buffer analogue -- reference ``fusion_buffer_manager.cc``), one
    collective is emitted, and the results are split back out.  Mixed dtypes
    are grouped per dtype.
    """
    from ..controller.fusion import fuse_flat, unfuse_flat
    xs = list(xs)
    if not xs:
        return []
    fused, spec = fuse_flat(xs)
    reduced = [
        allreduce(buf, op, axes=axes, process_set=process_set,
                  prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor)
        for buf in fused
    ]
    return unfuse_flat(reduced, spec)


def allgather(x,
              *,
              axes: Optional[AxisSpec] = None,
              process_set=None,
              axis: int = 0,
              tiled: bool = True):
    """Concatenate each worker's array along ``axis`` (NCCLAllgather).

    Like the reference, workers may differ only in dimension ``axis`` --
    but XLA requires static equal shapes, so unequal first dims must go
    through :func:`allgatherv` (padding-based) instead.

    With a process set, every device (SPMD traces one program) computes the
    gather of the MEMBER values -- shape ``[len(set) * d_axis, ...]`` when
    tiled.  Non-members receive the member gather too (in the reference's
    per-rank model they would never have called the op).
    """
    axes, members = _resolve(axes, process_set)
    if members is not None:
        sel = _gather_rows(x, axes)[np.asarray(members)]  # [m, ...]
        if tiled:
            return jnp.concatenate([sel[i] for i in range(len(members))],
                                   axis=axis)
        return jnp.moveaxis(sel, 0, axis)
    y = x
    for a in reversed(axes):
        y = lax.all_gather(y, a, axis=axis, tiled=tiled)
    return y


def broadcast(x,
              root_rank: int = 0,
              *,
              axes: Optional[AxisSpec] = None,
              process_set=None):
    """Every worker receives root's value (NCCLBroadcast analogue).

    Implemented as a masked psum: ``sum_i (i == root ? x_i : 0)``.  XLA
    lowers this to the same ring traffic a broadcast would use, and it
    composes with axis_index_groups for process sets.
    """
    axes, members = _resolve(axes, process_set)
    idx = axis_index(axes)
    member_mask = None
    if members is not None:
        # root_rank is a *global* rank; it must be a member of the set.
        if root_rank not in members:
            raise ValueError(
                f"broadcast root_rank {root_rank} is not a member of the "
                f"process set (ranks {tuple(members)})")
        # Non-members keep their own value (identity).
        member_mask = _member_mask(axes, members)
    mask = (idx == root_rank)
    if jnp.issubdtype(x.dtype, jnp.bool_):
        xi = jnp.where(mask, x, False).astype(jnp.int8)
        out = lax.psum(xi, axes).astype(jnp.bool_)
    else:
        masked = jnp.where(mask, x, jnp.zeros((), x.dtype))
        out = lax.psum(masked, axes)
    if member_mask is not None:
        out = jnp.where(member_mask, out, x)
    return out


def reducescatter(x,
                  op: ReduceOp = Average,
                  *,
                  axes: Optional[AxisSpec] = None,
                  process_set=None,
                  scatter_axis: int = 0):
    """Reduce then scatter shards along ``scatter_axis`` (NCCLReducescatter).

    With a process set, members reduce among themselves (masked full-mesh
    psum, or the masked allreduce for min/max/product) and each member
    takes the shard at its position within the set;
    ``x.shape[scatter_axis]`` must divide by the set size.  Non-members
    receive an UNSPECIFIED value (shard 0 of the member reduction on the
    sum path, their own shard 0 on the min/max/product path -- in the
    reference's per-rank model a non-member never calls the op).
    """
    axes, members = _resolve(axes, process_set)
    if op is Adasum:
        raise NotImplementedError(
            "reducescatter does not support Adasum (the reference's Adasum "
            "is an allreduce-shaped op); use allreduce(op=Adasum)")
    if op not in (Sum, Average, Min, Max, Product):
        raise ValueError(f"unknown reduce op {op}")
    if members is not None:
        m = len(members)
        d = x.shape[scatter_axis]
        if d % m:
            raise ValueError(
                f"reducescatter over a {m}-member process set needs "
                f"dim {scatter_axis} divisible by {m}, got {d}")
        if op in (Min, Max, Product):
            y = allreduce(x, op, axes=axes, process_set=process_set)
        else:
            mask = _member_mask(axes, members)
            contrib = jnp.where(mask, x, jnp.zeros((), x.dtype))
            y = lax.psum(contrib, axes)
            if op is Average:
                y = _divide_in_dtype(y, m)
        shard = d // m
        pos = _member_pos(axes, members)
        return lax.dynamic_slice_in_dim(y, pos * shard, shard, scatter_axis)
    if op in (Min, Max, Product):
        # No min/max/prod scatter primitive: reduce the full vector
        # (pmin/pmax, or the gathered product the allreduce path uses)
        # and take this rank's shard.  Bytes are O(n) like an allreduce
        # rather than the ring-scatter's O(n/p) -- matching the
        # reference, whose NCCL reducescatter supports these ops and is
        # the parity point.
        n = math.prod(lax.axis_size(a) for a in axes)
        d = x.shape[scatter_axis]
        if d % n:
            raise ValueError(
                f"reducescatter needs dim {scatter_axis} divisible by the "
                f"mesh size {n}, got {d}")
        y = allreduce(x, op, axes=axes)
        return lax.dynamic_slice_in_dim(
            y, axis_index(axes) * (d // n), d // n, scatter_axis)
    y = x
    for a in axes:
        y = lax.psum_scatter(y, a, scatter_dimension=scatter_axis, tiled=True)
    if op is Average:
        n = math.prod(lax.axis_size(a) for a in axes)
        y = _divide_in_dtype(y, n)
    return y


def alltoall(x,
             *,
             axes: Optional[AxisSpec] = None,
             process_set=None,
             split_axis: int = 0,
             concat_axis: int = 0):
    """Exchange equal splits with every worker (NCCLAlltoall analogue).

    The reference supports uneven ``splits``; XLA's static shapes require
    equal splits -- uneven exchange is provided by ``alltoallv`` (padded).
    This is the expert-parallel / Ulysses building block (SURVEY.md 5.7).

    With a process set, members exchange their ``len(set)`` splits through
    a masked full-mesh alltoall (non-member slots carry zeros; non-members
    receive zeros).  ``x.shape[split_axis]`` must divide by the set size.

    Works on flat AND hierarchical meshes: a multi-axis exchange uses the
    row-major flattened rank order (matching :func:`axis_index`).
    """
    axes, members = _resolve(axes, process_set)
    a = axes[0] if len(axes) == 1 else axes
    if members is None:
        return lax.all_to_all(x, a, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    m = len(members)
    size = math.prod(lax.axis_size(ax) for ax in axes)
    d = x.shape[split_axis]
    if d % m:
        raise ValueError(
            f"alltoall over a {m}-member process set needs dim "
            f"{split_axis} divisible by {m}, got {d}")
    chunk = d // m
    # [m, chunk, rest...]: split i is this member's payload for member i.
    xs = jnp.moveaxis(x, split_axis, 0).reshape(
        (m, chunk) + tuple(np.delete(np.array(x.shape), split_axis)))
    send = jnp.zeros((size,) + xs.shape[1:], x.dtype)
    send = send.at[np.asarray(members)].set(xs)
    recv = lax.all_to_all(send, a, split_axis=0, concat_axis=0, tiled=True)
    sel = recv[np.asarray(members)]          # [m, chunk, rest...]
    # Match the global tiled semantics: split_axis shrinks to ``chunk``,
    # concat_axis grows by ``m``.
    pieces = jnp.moveaxis(sel, 1, split_axis + 1)   # [m] + x-like shape
    return jnp.concatenate([pieces[i] for i in range(m)], axis=concat_axis)


def alltoallv(x, send_counts, *, axes: Optional[AxisSpec] = None,
              process_set=None, max_count: int,
              return_overflow: bool = False,
              strict: Optional[bool] = None):
    """Uneven alltoall (padded alltoallv; NCCLAlltoall with ``splits``).

    The reference exchanges ragged splits directly (its negotiation shares
    the counts); XLA needs static shapes, so each split is padded to the
    static bound ``max_count`` and receivers get the valid lengths
    alongside.  ``send_counts`` may be a traced per-device value -- the
    padding/masking is dynamic-slice based, so routing decisions computed
    inside the step (e.g. MoE dispatch) stay on device.

    Args:
      x: ``[total, ...]`` local rows; the split for peer ``i`` occupies
        rows ``[sum(send_counts[:i]), sum(send_counts[:i+1]))`` (rank-order
        concatenation, the reference's layout).
      send_counts: int array ``[size]``; ``send_counts[i]`` rows go to
        global rank ``i``.
      max_count: static upper bound on any single split.  A traced count
        exceeding it is truncated: only the first ``max_count`` rows of
        that split transfer and the receiver's count reports the clamped
        value (size your bound for the worst case, like an MoE capacity
        factor).  The reference ERRORS on inconsistent splits and never
        drops rows; request ``return_overflow=True`` to detect truncation
        (dropped tokens in an MoE exchange are otherwise invisible).
      return_overflow: also return the per-sender count of rows DROPPED by
        clamping.  Costs nothing extra: the original counts ride the same
        counts collective as the clamped ones.
      strict: loud mode (default: the ``HOROVOD_ALLTOALLV_STRICT`` env
        var).  Emits a ``jax.experimental.checkify.check`` that fails the
        step when ANY row is dropped, reporting the per-sender dropped
        counts -- the reference errors on inconsistent splits and never
        silently drops rows; this is the TPU-compiled equivalent (the
        error is functionalized, so the step needs no host callback).
        The enclosing jit/shard_map step must be wrapped in
        ``checkify.checkify(...)`` and the returned error thrown
        (``err.throw()``); an unwrapped strict step fails at TRACE time
        with checkify's "not functionalized" error, which is still loud,
        never silent.  Uses the same already-computed overflow counts as
        ``return_overflow`` -- zero extra communication.  The env var is
        read at TRACE time: set it before the step is first traced --
        executables already compiled with strict off stay off (jit cache
        keys do not include the environment).

    Returns:
      ``(recv, recv_counts)``: ``recv[j]`` is ``[max_count, ...]`` holding
      the split received from rank ``j`` (zero-padded past
      ``recv_counts[j]``); ``recv_counts`` is ``[size]``, every entry
      ``<= max_count``.  With ``return_overflow=True``, a third element
      ``overflow`` ([size] int32): ``overflow[j]`` rows addressed to this
      device by rank ``j`` were dropped (0 everywhere means the exchange
      was lossless).

    With a process set, ``send_counts`` is indexed by SET position (one
    count per member, splits concatenated in member order) and the
    results cover members only: ``recv`` is ``[len(set), max_count, ...]``
    and ``recv_counts``/``overflow`` are ``[len(set)]``.  Non-member
    devices exchange nothing (their results are all-zero).
    """
    axes, members = _resolve(axes, process_set)
    if members is not None:
        # Subset ragged exchange over the full mesh: member counts
        # (indexed by SET position) scatter into global slots, non-member
        # devices' counts are masked to zero (they send nothing and, by
        # construction, receive zero rows from every member).
        m = len(members)
        send_counts = jnp.asarray(send_counts, jnp.int32)
        if send_counts.shape != (m,):
            raise ValueError(
                f"send_counts must have shape ({m},) (one count per set "
                f"member), got {send_counts.shape}")
        size = math.prod(lax.axis_size(ax) for ax in axes)
        full = jnp.zeros((size,), jnp.int32).at[
            np.asarray(members)].set(send_counts)
        full = jnp.where(_member_mask(axes, members), full, 0)
        sel = np.asarray(members)
        out = alltoallv(x, full, axes=axes, max_count=max_count,
                        return_overflow=return_overflow, strict=strict)
        return tuple(o[sel] for o in out)
    a = axes[0] if len(axes) == 1 else axes
    size = math.prod(lax.axis_size(ax) for ax in axes)
    send_counts = jnp.asarray(send_counts, jnp.int32)
    if send_counts.shape != (size,):
        raise ValueError(
            f"send_counts must have shape ({size},) (one count per mesh "
            f"member), got {send_counts.shape}")
    # Offsets follow the caller's layout (the ORIGINAL counts); a split
    # larger than max_count is truncated to max_count rows, and the clamped
    # count is what the receiver sees -- overflow loses the tail but stays
    # internally consistent (recv_counts[j] <= max_count always), and is
    # reported via ``return_overflow``.
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(send_counts)[:-1]])
    clamped = jnp.minimum(send_counts, max_count)
    # Tail padding keeps every dynamic slice in bounds (XLA clamps
    # out-of-bounds starts, which would otherwise duplicate trailing rows).
    pad = jnp.zeros((max_count,) + x.shape[1:], x.dtype)
    xp = jnp.concatenate([x, pad], axis=0)
    pieces = jax.vmap(
        lambda off: lax.dynamic_slice_in_dim(xp, off, max_count, axis=0)
    )(offsets)                                # [size, max_count, ...]
    valid = (jnp.arange(max_count, dtype=jnp.int32)[None, :]
             < clamped[:, None])              # [size, max_count]
    valid = valid.reshape(valid.shape + (1,) * (x.ndim - 1))
    pieces = jnp.where(valid, pieces, jnp.zeros((), x.dtype))
    recv = lax.all_to_all(pieces, a, split_axis=0, concat_axis=0, tiled=True)
    # One counts collective carries BOTH the clamped and the original
    # counts ([size, 2] rows), so overflow detection is free.
    pair = lax.all_to_all(jnp.stack([clamped, send_counts], axis=1), a,
                          split_axis=0, concat_axis=0, tiled=True)
    recv_counts = pair[:, 0]
    if strict is None:
        from ..core.config import _env_bool
        strict = _env_bool("ALLTOALLV_STRICT")
    if strict:
        from jax.experimental import checkify
        overflow = pair[:, 1] - pair[:, 0]
        checkify.check(
            jnp.logical_not(jnp.any(overflow > 0)),
            "alltoallv dropped rows (HOROVOD_ALLTOALLV_STRICT): per-sender "
            "dropped counts {ov} at max_count=" + str(int(max_count))
            + " -- raise max_count or fix the split computation",
            ov=overflow)
    if return_overflow:
        return recv, recv_counts, pair[:, 1] - pair[:, 0]
    return recv, recv_counts


def fp8_allreduce(x,
                  op: ReduceOp = Average,
                  *,
                  axes: Optional[AxisSpec] = None,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0):
    """Allreduce with an e4m3 wire and f32 on-chip accumulation.

    ``Compression.fp8``'s exchange (see ``compression.py``): a plain psum
    would ACCUMULATE in the wire dtype (3 mantissa bits, overflow at 448),
    so the reduction is decomposed TPU-natively instead:

    1. shard the flat bucket ``n`` ways; quantize each destination row
       with its own max-abs scale (``n`` f32 scalars);
    2. ``all_to_all`` the fp8 rows (the scale matrix rides a tiny
       ``all_gather``);
    3. dequantize and reduce THIS rank's shard in f32;
    4. re-quantize the result shard and ``all_gather`` it back -- the one
       collective this toolchain emits ASYNC for (scaling.py round-4
       capability matrix), so the rebuild can hide behind compute.

    Wire cost: 2 * B/4 * (n-1)/n link bytes -- 4x less than fp32 psum,
    2x less than fp16.  Numerics: two e4m3 roundings end-to-end
    (~2^-4 relative each); the REDUCTION itself is exact f32, unlike
    what summing in any wire dtype would give.  Floating-point inputs
    only; process sets are not supported (no masked identity exists for
    a quantized exchange) -- use fp16/bf16 compression there.
    """
    axes, members = _resolve(axes)
    if members is not None:
        raise NotImplementedError(
            "fp8_allreduce does not support process sets; use fp16/bf16 "
            "compression for subset reductions")
    if op not in (Sum, Average):
        raise ValueError(f"fp8_allreduce supports Sum/Average, got {op}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(f"fp8 wire needs a floating dtype, got {x.dtype}")
    from .compression import fp8_quantize, fp8_dequantize

    a = axes[0] if len(axes) == 1 else axes
    n = math.prod(lax.axis_size(ax) for ax in axes)
    shape, dtype = x.shape, x.dtype
    x32 = x.astype(jnp.float32)
    if prescale_factor != 1.0:
        x32 = x32 * prescale_factor
    flat = x32.ravel()
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    rows = flat.reshape(n, -1)                     # row j -> rank j
    # Trace-time leg registration: fp8 all_to_all + result allgather,
    # one wire byte per e4m3 element in each direction (plan-IR row).
    from ..controller import fusion as _fusion
    from ..timeline import spans as _spans
    _spans.note_leg(_fusion.plan_exchange(
        "fp8", size=int(x.size), world=int(n)).legs[0])
    q, scales = fp8_quantize(rows, axis=0)         # per-destination scales
    recv = lax.all_to_all(q, a, split_axis=0, concat_axis=0, tiled=True)
    # scale matrix: S[src, dst]; my column is the scale each sender used
    # for the row now in ``recv[src]``.
    smat = _gather_rows(scales, axes)              # [n, n]
    my = axis_index(axes)
    my_scales = smat[:, my] if len(axes) > 1 else \
        jnp.take(smat, my, axis=1)
    acc = jnp.sum(recv.astype(jnp.float32) * my_scales[:, None], axis=0)
    if op is Average:
        acc = acc / n
    if postscale_factor != 1.0:
        acc = acc * postscale_factor
    qr, s2 = fp8_quantize(acc)
    gathered = _gather_rows(qr, axes)              # [n, chunk]
    s2_all = _gather_rows(s2, axes)                # [n]
    out = (gathered.astype(jnp.float32) * s2_all[:, None]).ravel()
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def _powersgd_seed_matrix(cols: int, rank: int):
    """Deterministic, RNG-free right-factor init ``Q0`` of shape
    ``[cols, rank]``.

    Every rank must start the power iteration from the SAME Q0 (the P
    allreduce assumes it), and the eager join-replay path re-traces the
    exchange on drained ranks, so the init must be a pure function of the
    shape -- no PRNG key threading.  Incommensurate cosine phases give
    columns that are linearly independent in practice (orthogonalization
    downstream cleans up conditioning).
    """
    i = jnp.arange(cols, dtype=jnp.float32)[:, None]
    j = jnp.arange(rank, dtype=jnp.float32)[None, :]
    return jnp.cos(i * (j + 1.0) * 0.9182736 + (j + 1.0) * 0.3717)


def _orthonormalize_columns(p):
    """Modified Gram-Schmidt over the (few) columns of ``p`` -- the one
    orthogonalization round of the PowerSGD exchange.  Unrolled Python loop:
    rank is small and static, so XLA sees straight-line code."""
    cols = []
    for k in range(p.shape[1]):
        v = p[:, k]
        for u in cols:
            v = v - jnp.dot(u, v) * u
        norm = jnp.sqrt(jnp.sum(v * v))
        cols.append(v / jnp.maximum(norm, 1e-12))
    return jnp.stack(cols, axis=1)


def powersgd_allreduce(x,
                       op: ReduceOp = Average,
                       *,
                       rank: int,
                       axes: Optional[AxisSpec] = None,
                       residual=None,
                       prescale_factor: float = 1.0,
                       postscale_factor: float = 1.0,
                       note: bool = True):
    """Rank-``rank`` PowerSGD allreduce (Vogels et al., 2019): low-rank
    factor exchange with f32 on-chip arithmetic.

    The flat bucket is matricized near-square (``m x c``, zero-padded);
    one power-iteration round runs THROUGH the collective:

    1. ``P = M @ Q0`` with a deterministic shared ``Q0`` -- allreduce
       (mean) the ``[m, r]`` left factor;
    2. orthonormalize ``P`` locally (identical on every rank: one
       Gram-Schmidt round, f32);
    3. ``Q = M^T @ P`` -- allreduce (mean) the ``[c, r]`` right factor;
    4. rebuild ``P @ Q^T ~= mean(M)`` (the projection of the mean gradient
       onto span(P)).

    Wire bytes: two allreduces of ``r * (m + c)`` f32 elements vs one of
    ``m * c`` -- for a B-element bucket the reduction factor is
    ``B / (2 r (m + c)) ~= sqrt(B) / (4 r)``.

    The approximation is biased, so callers that train through it must use
    error feedback: pass the previous step's ``residual`` (flat f32, same
    element count as ``x``) and the return is ``(out, new_residual)`` where
    ``new_residual = (x + residual) - P @ Q_local^T`` -- the part of THIS
    rank's contribution the averaged factors did not carry.  ``residual``
    of ``None`` means zeros (stateless use: autotune sampling, the eager
    path).  Floating inputs, Sum/Average, full mesh only (no masked
    identity exists for a factored exchange).
    """
    axes, members = _resolve(axes)
    if members is not None:
        raise NotImplementedError(
            "powersgd_allreduce does not support process sets; use "
            "fp16/bf16 compression for subset reductions")
    if op not in (Sum, Average):
        raise ValueError(f"powersgd_allreduce supports Sum/Average, got {op}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"powersgd wire needs a floating dtype, got {x.dtype}")
    from .compression import powersgd_matrix_shape

    n = math.prod(lax.axis_size(ax) for ax in axes)
    shape, dtype = x.shape, x.dtype
    size = x.size
    m, c = powersgd_matrix_shape(size)
    pad = m * c - size
    r = max(1, min(int(rank), m, c))
    if note:
        # Trace-time leg registration: two f32 factor allreduces
        # (plan-IR row).
        from ..controller import fusion as _fusion
        from ..timeline import spans as _spans
        _spans.note_leg(_fusion.plan_exchange(
            "powersgd", size=int(size), rank=int(rank)).legs[0])

    from ..ops import pallas as _pallas
    if _pallas.pallas_enabled("fused_update"):
        # Fused path (PR 13): the three HBM passes between the factor
        # psums run as Pallas kernels (ops.fused_update); the psums
        # themselves stay HERE in XLA, so the wire contract -- two f32
        # allreduces of r*m and r*c elements -- and the _EFState carry
        # are identical to the unfused path below.
        from ..ops import fused_update as _fused
        if note:
            from ..controller import fusion as _fusion
            from ..timeline import spans as _spans
            _spans.note_leg(_fusion.plan_exchange(
                "kernel", kernel="fused_update", nbytes=int(size) * 4
            ).legs[0])
        xf = x.ravel()
        xp = jnp.concatenate([xf, jnp.zeros((pad,), xf.dtype)]) \
            if pad else xf
        res_mat = None
        if residual is not None:
            rf = residual.astype(jnp.float32).ravel()
            rp = jnp.concatenate([rf, jnp.zeros((pad,), jnp.float32)]) \
                if pad else rf
            res_mat = rp.reshape(m, c)
        acc_mat, p_local = _fused.matricize_p(
            xp.reshape(m, c), res_mat, _powersgd_seed_matrix(c, r),
            prescale=prescale_factor)
        p = lax.psum(p_local, axes if len(axes) > 1 else axes[0]) / n
        p_orth, q_local = _fused.orthonormalize_q(acc_mat, p)
        q = lax.psum(q_local, axes if len(axes) > 1 else axes[0]) / n
        out_mat, res_out = _fused.reconstruct_residual(
            acc_mat, p_orth, q, q_local,
            n_scale=float(n) if op is Sum else 1.0,
            postscale=postscale_factor)
        return (out_mat.ravel()[:size].reshape(shape).astype(dtype),
                res_out.ravel()[:size])

    acc = x.astype(jnp.float32).ravel()
    if prescale_factor != 1.0:
        acc = acc * prescale_factor
    if residual is not None:
        acc = acc + residual.astype(jnp.float32).ravel()
    flat = jnp.concatenate([acc, jnp.zeros((pad,), jnp.float32)]) \
        if pad else acc
    mat = flat.reshape(m, c)

    p = mat @ _powersgd_seed_matrix(c, r)          # [m, r]
    p = lax.psum(p, axes if len(axes) > 1 else axes[0]) / n
    p = _orthonormalize_columns(p)
    q_local = mat.T @ p                            # [c, r]
    q = lax.psum(q_local, axes if len(axes) > 1 else axes[0]) / n

    approx = (p @ q.T).ravel()[:size]              # ~= mean over ranks
    own = (p @ q_local.T).ravel()[:size]           # this rank's share
    new_residual = acc - own
    out = approx * n if op is Sum else approx
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out.reshape(shape).astype(dtype), new_residual


def topk_allreduce(x,
                   op: ReduceOp = Average,
                   *,
                   fraction: float,
                   axes: Optional[AxisSpec] = None,
                   residual=None,
                   prescale_factor: float = 1.0,
                   postscale_factor: float = 1.0,
                   note: bool = True):
    """Top-``fraction`` sparsified allreduce (DGC-style, Lin et al., 2018).

    Each rank keeps its ``k = ceil(fraction * size)`` largest-magnitude
    elements and allgathers ``(value f32, index int32)`` pairs; every rank
    scatter-adds all ``n * k`` pairs into a dense f32 bucket -- duplicate
    indices across ranks accumulate correctly, and the reduction is exact
    f32 over what was sent.  Wire bytes: ``8k`` per rank vs ``4 * size``
    (a ``1 / (2 * fraction)`` reduction before allgather-vs-allreduce
    link accounting).

    Error feedback mirrors :func:`powersgd_allreduce`: returns
    ``(out, new_residual)`` with ``new_residual = acc - own_sparse`` (the
    elements this rank did NOT send).  Floating inputs, Sum/Average, full
    mesh only.
    """
    axes, members = _resolve(axes)
    if members is not None:
        raise NotImplementedError(
            "topk_allreduce does not support process sets; use fp16/bf16 "
            "compression for subset reductions")
    if op not in (Sum, Average):
        raise ValueError(f"topk_allreduce supports Sum/Average, got {op}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(f"topk wire needs a floating dtype, got {x.dtype}")
    from .compression import topk_count

    n = math.prod(lax.axis_size(ax) for ax in axes)
    shape, dtype = x.shape, x.dtype
    acc = x.astype(jnp.float32).ravel()
    if prescale_factor != 1.0:
        acc = acc * prescale_factor
    if residual is not None:
        acc = acc + residual.astype(jnp.float32).ravel()
    size = acc.size
    k = min(topk_count(size, fraction), size)
    if note:
        # Trace-time leg registration: (value f32, index int32) pairs
        # (plan-IR row).
        from ..controller import fusion as _fusion
        from ..timeline import spans as _spans
        _spans.note_leg(_fusion.plan_exchange(
            "topk", size=int(size), fraction=float(fraction)).legs[0])

    _, idx = lax.top_k(jnp.abs(acc), k)            # int32 indices
    vals = jnp.take(acc, idx)
    gv = _gather_rows(vals, axes)                  # [n, k]
    gi = _gather_rows(idx, axes)                   # [n, k]
    dense = jnp.zeros((size,), jnp.float32).at[gi.ravel()].add(gv.ravel())
    if op is Average:
        dense = dense / n
    if postscale_factor != 1.0:
        dense = dense * postscale_factor
    own = jnp.zeros((size,), jnp.float32).at[idx].set(vals)
    new_residual = acc - own
    return dense.reshape(shape).astype(dtype), new_residual


def barrier(*, axes: Optional[AxisSpec] = None, process_set=None):
    """Synchronization barrier (BarrierOp analogue).

    Returns a scalar that data-depends on every worker having reached this
    point; consume it (e.g. ``jax.block_until_ready``) to enforce ordering.
    Under SPMD every device executes the program, so a process-set barrier
    synchronizes the full mesh.
    """
    axes, _ = _resolve(axes, process_set)
    return lax.psum(jnp.ones((), jnp.int32), axes)


def ppermute(x, perm, *, axes: Optional[AxisSpec] = None):
    """Point-to-point permutation over the flat axis (ring building block)."""
    axes, _ = _resolve(axes)
    if len(axes) != 1:
        raise NotImplementedError("ppermute requires a flat mesh axis")
    return lax.ppermute(x, axes[0], perm)


def desync_check(x, *, axes: Optional[AxisSpec] = None):
    """In-step desync probe: scalar bool, True when ``x`` is NOT
    bit-identical on every mesh member.

    Debug-mode companion of :func:`horovod_tpu.core.desync.check_desync`
    (SURVEY.md 5.2's "psum of hashes"): an integer bit-sum of the local
    array compared via pmax/pmin -- two cheap scalar collectives, so it can
    run every step under ``HOROVOD_CHECK_DESYNC=1`` without moving data.
    """
    axes, _ = _resolve(axes)
    x = jnp.asarray(x)
    nbits = x.dtype.itemsize * 8
    if x.dtype == jnp.bool_:
        bits = x.astype(jnp.int32)
    elif nbits >= 32:
        # Wide elements bitcast to int32 words (64-bit dtypes gain a
        # trailing length-2 dim), so no high bits are dropped.
        bits = lax.bitcast_convert_type(x, jnp.int32)
    elif jnp.issubdtype(x.dtype, jnp.floating):
        bits = lax.bitcast_convert_type(
            x, jnp.dtype(f"int{nbits}")).astype(jnp.int32)
    else:
        bits = x.astype(jnp.int32)
    # Wrapping uint32 sum of position-weighted words: exact (associative)
    # regardless of reduction order, unlike a float checksum, and the
    # per-position odd multiplier (Knuth hash constant; bijective mod 2^32)
    # makes permutations of the same values visible -- a plain bit-sum
    # would pass rank 0 holding [a, b] against rank 1 holding [b, a].
    flat = bits.ravel()
    if flat.size:
        u = lax.bitcast_convert_type(flat, jnp.uint32)
        # |1 keeps every weight ODD (hence invertible mod 2^32): i*K+1 is
        # even at odd i, which would zero out top-bit-only differences.
        w = (jnp.arange(flat.size, dtype=jnp.uint32)
             * jnp.uint32(2654435761)) | jnp.uint32(1)
        c = jnp.sum(u * w, dtype=jnp.uint32)
    else:
        c = jnp.zeros((), jnp.uint32)
    hi, lo = c, c
    for a in axes:
        hi = lax.pmax(hi, a)
        lo = lax.pmin(lo, a)
    return hi != lo
