"""ZeRO-1 sharded optimizer state (Rajbhandari et al., 2020).

The replicated step moves the whole gradient payload through one fused
allreduce and every chip runs the full optimizer update on a full copy of
the optimizer state.  ZeRO stage 1 splits that work across the
data-parallel mesh:

* gradients are packed into flat per-dtype **arenas** (the fusion-buffer
  idea, but padded so the mesh size divides each arena) and exchanged with
  one ``reduce-scatter`` per arena -- each chip receives the fully-reduced
  mean of its own 1/n slice only;
* each chip runs ``optimizer.update`` on its slice of the param/opt-state
  arena, so optimizer-update FLOPs and optimizer-state HBM both shrink by
  the mesh size;
* the updated param shards are broadcast back with one ``all-gather`` per
  arena, optionally compressed through the existing
  :mod:`~horovod_tpu.collectives.compression` codecs (fp16/bf16 cast the
  wire; fp8 quantizes per shard and gathers e4m3 bytes + one f32 scale per
  shard).  Every chip dequantizes the SAME wire bytes -- its own shard
  included -- so replicas stay bit-identical.

Wire math: an uncompressed reduce-scatter + all-gather moves exactly the
bytes of one ring allreduce (2B(n-1)/n per chip); the ZeRO win is the /n
optimizer FLOPs + HBM and the *compressible* allgather leg (fp16 gather:
0.75x the replicated wire; fp8: 0.625x).

Layout contract: the sharded optimizer state is the inner optimizer's
state over the list of arena shards, with every leaf carrying a leading
``[n, ...]`` axis that shards over the mesh (``PartitionSpec(axes)``).
Plain pytree of arrays, so it round-trips through
:func:`horovod_tpu.save_checkpoint` / ``restore_checkpoint`` unchanged;
re-place a restored (replicated) state onto the mesh with
:func:`shard_zero_state`.

Use the BARE optax optimizer with ``zero_stage=1`` -- the reduce-scatter
replaces :func:`~horovod_tpu.optim.distributed.DistributedOptimizer`'s
allreduce, and wrapping would re-reduce already-disjoint shard gradients
(detected and rejected).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..collectives import ops as _ops
from ..collectives.compression import (Compression, fp8_quantize, is_fp8,
                                       is_error_feedback, is_hier_legs,
                                       is_powersgd, parse_compression,
                                       powersgd_factor_widths,
                                       powersgd_matrix_shape, topk_count)
from ..collectives.reduce_op import Average
from ..controller.fusion import _LeafSpec


class _ZeroEFState(NamedTuple):
    """ZeRO-1 state carry when ``zero_compression`` is an error-feedback
    codec: the shard-owner residuals ride NEXT TO the inner state with the
    same leading ``[n, ...]`` sharded axis ("residuals live on the shard
    owner" -- each rank's residual covers only the arena slice it
    allgathers, 1/n of the replicated EF footprint)."""
    residuals: Any                # tuple of [n, shard] f32, one per arena
    inner: Any                    # sharded inner optimizer state


@dataclasses.dataclass(frozen=True)
class _ArenaBuffer:
    """One flat per-dtype buffer of the ZeRO arena."""
    dtype: Any
    leaves: Tuple[_LeafSpec, ...]
    size: int      # unpadded element count
    padded: int    # padded so ``world`` divides it
    shard: int     # padded // world


@dataclasses.dataclass(frozen=True)
class ZeroSpec:
    """Static flatten/partition plan: how a pytree maps onto the arenas.

    Deterministic in (tree structure, leaf shapes/dtypes, world size), so
    the plan computed at ``zero_init`` time and the one recomputed inside
    the traced step agree without being carried through the state.
    """
    buffers: Tuple[_ArenaBuffer, ...]
    num_leaves: int
    world: int


def plan_arena(leaves: Sequence[Any], world: int) -> ZeroSpec:
    """One arena per dtype (leaf order preserved), padded to ``world``."""
    by_dtype: dict = {}
    for i, x in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(x.dtype), []).append(
            _LeafSpec(i, tuple(x.shape),
                      int(np.prod(x.shape, dtype=np.int64))))
    buffers = []
    for dt, specs in by_dtype.items():
        size = sum(s.size for s in specs)
        padded = int(math.ceil(size / world)) * world if size else 0
        buffers.append(_ArenaBuffer(dtype=dt, leaves=tuple(specs),
                                    size=size, padded=padded,
                                    shard=padded // world))
    return ZeroSpec(buffers=tuple(buffers), num_leaves=len(leaves),
                    world=world)


def arena_pack(leaves: Sequence[jax.Array], spec: ZeroSpec
               ) -> List[jax.Array]:
    """Ravel+concat leaves into the padded flat arenas."""
    out = []
    for buf in spec.buffers:
        parts = [jnp.ravel(leaves[s.index]) for s in buf.leaves]
        pad = buf.padded - buf.size
        if pad:
            parts.append(jnp.zeros((pad,), buf.dtype))
        out.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return out


def arena_unpack(arenas: Sequence[jax.Array], spec: ZeroSpec
                 ) -> List[jax.Array]:
    """Slice the (padding dropped) arenas back into the leaf list."""
    leaves: List[Optional[jax.Array]] = [None] * spec.num_leaves
    for arena, buf in zip(arenas, spec.buffers):
        off = 0
        for s in buf.leaves:
            leaves[s.index] = arena[off:off + s.size].reshape(s.shape)
            off += s.size
    assert all(l is not None for l in leaves)
    return leaves  # type: ignore[return-value]


def _reject_distributed(optimizer) -> None:
    if getattr(optimizer.update, "_hvd_allreduce", False):
        raise ValueError(
            "zero_stage=1 replaces the gradient allreduce with a "
            "reduce-scatter; pass the bare optax optimizer, not "
            "DistributedOptimizer (which would re-reduce disjoint shard "
            "gradients)")


def compressed_allgather(x, *, axes, compression=None):
    """All-gather ``x`` (each worker's shard) with an optional wire codec.

    fp16/bf16 cast the shard down for the wire and back up after; fp8
    quantizes per shard (e4m3 + one f32 scale each) and dequantizes every
    gathered shard from the wire bytes -- the sender's own shard included,
    so all replicas reconstruct identical values.  Non-floating or
    already-narrow shards gather uncompressed.
    """
    comp = compression or Compression.none
    if is_fp8(comp):
        if (not jnp.issubdtype(x.dtype, jnp.floating)
                or jnp.dtype(x.dtype).itemsize <= 1):
            return _ops.allgather(x, axes=axes)
        q, scale = fp8_quantize(x)
        full_q = _ops.allgather(q, axes=axes)            # [n * shard] e4m3
        scales = _ops.allgather(scale.reshape(1), axes=axes)  # [n] f32
        n = scales.shape[0]
        full = full_q.astype(jnp.float32).reshape(n, -1) * scales[:, None]
        return full.reshape(-1).astype(x.dtype)
    wire, ctx = comp.compress(x)
    return comp.decompress(_ops.allgather(wire, axes=axes), ctx)


def ef_delta_allgather(delta, *, axes, compression):
    """Compressed allgather of each shard owner's param DELTA (the EF
    composition of the ZeRO allgather leg).

    ``delta`` is this rank's flat f32 update (new shard - old shard, plus
    the fed-back residual).  Each rank compresses its OWN delta locally --
    PowerSGD here is a plain local low-rank factorization (one
    orthogonalization round, no inner collective: there is nothing to
    reduce, each shard has one owner) and top-k keeps the largest
    magnitudes -- then ONE allgather moves the compressed payloads and
    EVERY rank reconstructs EVERY shard's delta from the same wire bytes
    (sender included), so replicas stay bit-identical, exactly the
    ``compressed_allgather`` fp8 contract.

    Returns ``(full, own)``: ``full`` is the ``[n, shard]`` f32
    reconstruction of all shards' deltas, ``own`` this rank's row (what
    the mesh actually applied for it -- the EF residual is
    ``delta - own``).
    """
    n = _ops.axis_size(axes)
    my = _ops.axis_index(axes)
    shard = delta.shape[0]
    if is_powersgd(compression):
        m, c = powersgd_matrix_shape(shard)
        pad = m * c - shard
        flat = jnp.concatenate([delta, jnp.zeros((pad,), jnp.float32)]) \
            if pad else delta
        mat = flat.reshape(m, c)
        r = max(1, min(int(compression.rank), m, c))
        p = _ops._orthonormalize_columns(mat @ _ops._powersgd_seed_matrix(c, r))
        q = mat.T @ p                                  # [c, r]
        wire = jnp.concatenate([p.ravel(), q.ravel()])  # [r*(m+c)]
        gw = _ops._gather_rows(wire, axes)             # [n, r*(m+c)]
        ps = gw[:, :r * m].reshape(n, m, r)
        qs = gw[:, r * m:].reshape(n, c, r)
        full = jnp.einsum("nmr,ncr->nmc", ps, qs).reshape(n, -1)[:, :shard]
    else:
        k = min(topk_count(shard, compression.fraction), shard)
        _, idx = lax.top_k(jnp.abs(delta), k)
        vals = jnp.take(delta, idx)
        gv = _ops._gather_rows(vals, axes)             # [n, k]
        gi = _ops._gather_rows(idx, axes)              # [n, k]
        pos = gi + (jnp.arange(n, dtype=gi.dtype) * shard)[:, None]
        full = jnp.zeros((n * shard,), jnp.float32).at[
            pos.ravel()].set(gv.ravel()).reshape(n, shard)
    own = jnp.take(full, my, axis=0)
    return full, own


def _use_reducescatter() -> bool:
    """Trace-time exchange choice.  Default: reduce-scatter.  When the
    autotuner's zero axis is being searched (``HOROVOD_AUTOTUNE_ZERO=1``
    on a zero-configured run), the sample's axis value picks between the
    reduce-scatter exchange (1) and the allreduce exchange (0) over the
    same sharded arena -- the score loop measures both wire profiles and
    locks the winner per model."""
    from ..core.state import global_state
    tuner = global_state().autotuner
    if tuner is not None and getattr(tuner, "tunes_zero", False):
        return bool(tuner.zero_stage())
    return True


def _resolve_compression(compression):
    comp = parse_compression(compression) if compression else Compression.none
    from ..core.state import global_state
    tuner = global_state().autotuner
    if tuner is not None:
        override = tuner.compression_override(comp)
        # The tuner may not flip EF-ness mid-run: the ZeRO state layout
        # (whether residuals ride next to the inner state) was fixed at
        # zero_init time.
        if is_error_feedback(override) == is_error_feedback(comp):
            comp = override
    return comp


def zero_apply(optimizer, grads, zero_state, params, *, axes,
               compression=None):
    """Sharded exchange + shard-local update (call inside ``shard_map``).

    Returns ``(new_params, new_zero_state)``; ``new_params`` is the full
    (replicated) tree reassembled from the compressed allgather,
    ``new_zero_state`` keeps the leading ``[1, ...]`` local axis that
    shards over the mesh.

    With an error-feedback ``compression`` (powersgd/topk) the allgather
    leg moves each owner's compressed param DELTA instead of the raw
    shard (:func:`ef_delta_allgather`); ``zero_state`` must then be the
    :class:`_ZeroEFState` built by ``zero_init(..., compression=...)``.
    """
    _reject_distributed(optimizer)
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return params, zero_state
    comp = _resolve_compression(compression)
    ef = is_error_feedback(comp)
    if ef:
        if not isinstance(zero_state, _ZeroEFState):
            if (isinstance(zero_state, (tuple, list))
                    and len(zero_state) == 2):
                zero_state = _ZeroEFState(*zero_state)  # restored carry
            else:
                raise ValueError(
                    "zero_compression=powersgd/topk needs the residual-"
                    "carrying state from zero_init(..., compression=...); "
                    f"got {type(zero_state).__name__}")
        residuals = tuple(r[0] for r in zero_state.residuals)
        inner_full = zero_state.inner
    else:
        inner_full = zero_state
    p_leaves = jax.tree.leaves(params)
    n = _ops.axis_size(axes)
    spec = plan_arena(leaves, n)
    g_arenas = arena_pack(leaves, spec)
    p_arenas = arena_pack(p_leaves, spec)
    idx = _ops.axis_index(axes)
    use_rs = _use_reducescatter()
    ax = tuple((axes,) if isinstance(axes, str) else axes)
    hier = is_hier_legs(comp) and len(ax) == 2
    if hier:
        # Per-leg codec on the two-level mesh: intra-slice RS FIRST so
        # only the 1/n_ici shard ever crosses DCN, compressed leader
        # exchange over the slice axis, allgather back in the inverse
        # order.  The rank->shard bijection becomes (ici, dcn)-major to
        # match that scatter order -- a bijection either way, so pack/
        # unpack stay consistent as long as the same index is used
        # throughout (zero_init mirrors it).
        dcn_ax, ici_ax = ax
        rs_axes = (ici_ax, dcn_ax)
        idx = (lax.axis_index(ici_ax) * lax.axis_size(dcn_ax)
               + lax.axis_index(dcn_ax))
    else:
        rs_axes = axes
    # Trace-time leg registration (fires once per trace, like
    # _note_compression_ratio): attributes the compiled step's exchange
    # bytes to the ZeRO RS/AG legs for the cross-rank straggler report.
    # The RS/AG rows come from the shared exchange-plan IR -- this
    # executor only picks which collective to run per row.
    from ..controller import fusion as _fusion
    from ..timeline import spans as _spans
    zplan = _fusion.plan_exchange(
        "zero",
        buffers=tuple((str(jnp.dtype(b.dtype)), int(b.size),
                       int(b.padded), int(b.shard)) for b in spec.buffers),
        world=int(n), compression=comp,
        axes_shape=(tuple(int(lax.axis_size(a)) for a in ax)
                    if len(ax) == 2 else None),
        axes=(ax if len(ax) == 2 else ()), use_rs=use_rs)
    rs_legs = zplan.legs[:len(spec.buffers)]
    ag_legs = zplan.legs[len(spec.buffers):]
    g_shards, p_shards = [], []
    for i, (g, p, buf) in enumerate(zip(g_arenas, p_arenas, spec.buffers)):
        _spans.note_leg(rs_legs[i], bucket_id=i)
        if use_rs:
            gs = _ops.reducescatter(g, Average, axes=rs_axes)
        else:
            red = _ops.allreduce(g, Average, axes=axes)
            gs = lax.dynamic_slice_in_dim(red, idx * buf.shard, buf.shard, 0)
        g_shards.append(gs)
        p_shards.append(
            lax.dynamic_slice_in_dim(p, idx * buf.shard, buf.shard, 0))
    inner = jax.tree.map(lambda v: v[0], inner_full)
    old_shards = p_shards
    updates, inner = optimizer.update(g_shards, inner, p_shards)
    import optax
    p_shards = optax.apply_updates(p_shards, updates)
    if ef:
        from .distributed import _ef_enabled
        feed = _ef_enabled()
        full, new_res = [], []
        for i, (old, new, res, arena, buf) in enumerate(zip(
                old_shards, p_shards, residuals, p_arenas, spec.buffers)):
            _spans.note_leg(ag_legs[i], bucket_id=i)
            if (not jnp.issubdtype(buf.dtype, jnp.floating)
                    or buf.shard < 1):
                full.append(_ops.allgather(new, axes=rs_axes))
                new_res.append(res)
                continue
            delta = (new.astype(jnp.float32) - old.astype(jnp.float32))
            if feed:
                delta = delta + res
            recon, own = ef_delta_allgather(
                delta, axes=rs_axes,
                compression=comp.dcn if hier else comp)
            full.append(
                (arena.astype(jnp.float32) + recon.ravel())
                .astype(buf.dtype))
            new_res.append(delta - own if feed else res)
        new_params = jax.tree.unflatten(treedef, arena_unpack(full, spec))
        return new_params, _ZeroEFState(
            tuple(r[None] for r in new_res),
            jax.tree.map(lambda v: v[None], inner))
    full = []
    for i, s in enumerate(p_shards):
        _spans.note_leg(ag_legs[i], bucket_id=i)
        if hier:
            # Leader exchange over the slice axis rides the DCN codec;
            # the intra-slice reassembly rides the (psum-compatible) ICI
            # codec.
            block = compressed_allgather(s, axes=(dcn_ax,),
                                         compression=comp.dcn)
            full.append(compressed_allgather(block, axes=(ici_ax,),
                                             compression=comp.ici))
        else:
            full.append(compressed_allgather(s, axes=axes, compression=comp))
    new_params = jax.tree.unflatten(treedef, arena_unpack(full, spec))
    return new_params, jax.tree.map(lambda v: v[None], inner)


def zero_init(optimizer, params, mesh: Optional[Mesh] = None,
              compression=None, param_specs=None):
    """Build the sharded optimizer state for ``zero_stage=1``.

    Each device runs ``optimizer.init`` on its own arena shard; the
    result's leaves carry a leading ``[n, ...]`` axis sharded over the
    mesh, so the state occupies 1/n of the replicated state's HBM per
    chip.  Pass the result as the ``opt_state`` of a step built with
    ``make_train_step(..., zero_stage=1)``.

    ``compression`` must name the step's ``zero_compression`` when that is
    an error-feedback codec (powersgd/topk): the returned carry is then a
    :class:`_ZeroEFState` with one zero f32 residual per arena shard,
    sharded like the inner state.  Dtype codecs (fp16/bf16/fp8) carry no
    state and may be omitted here.

    On a model-parallel mesh (``build_3d_mesh`` with ``model``/``pipe``
    axes) pass ``param_specs`` -- the same pytree of ``PartitionSpec``s
    the train step was built with.  The arena is then planned over each
    device's LOCAL (TP/stage-sharded) parameter leaves and sharded over
    the DATA axes only: every (tp, pipe) group owns an independent ZeRO
    arena for its own shard of the model, the state still occupies
    ``1/n_data`` of that group's replicated state per chip, and the
    returned leaves carry a leading axis of the FULL mesh extent (one
    arena row per device, sharded over every mesh axis).
    """
    from ..core import basics as _basics
    from ..parallel.mesh import data_axes as _data_axes
    _reject_distributed(optimizer)
    comp = parse_compression(compression) if compression else Compression.none
    ef = is_error_feedback(comp)
    mesh = mesh or _basics.mesh()
    axes = _data_axes(mesh)
    world = int(np.prod([mesh.shape[a] for a in axes]))

    def local_init(params):
        leaves = jax.tree.leaves(params)
        spec = plan_arena(leaves, world)
        arenas = arena_pack(leaves, spec)
        if is_hier_legs(comp) and len(axes) == 2:
            # Match zero_apply's (ici, dcn)-major shard bijection.
            idx = (lax.axis_index(axes[1]) * lax.axis_size(axes[0])
                   + lax.axis_index(axes[0]))
        else:
            idx = _ops.axis_index(axes)
        shards = [lax.dynamic_slice_in_dim(a, idx * b.shard, b.shard, 0)
                  for a, b in zip(arenas, spec.buffers)]
        inner = optimizer.init(shards)
        out = jax.tree.map(lambda v: jnp.asarray(v)[None], inner)
        if ef:
            return _ZeroEFState(
                residuals=tuple(jnp.zeros((1, b.shard), jnp.float32)
                                for b in spec.buffers),
                inner=out)
        return out

    p_spec = param_specs if param_specs is not None else P()
    fn = jax.shard_map(local_init, mesh=mesh, in_specs=(p_spec,),
                       out_specs=P(tuple(mesh.axis_names)),
                       check_vma=False)
    return jax.jit(fn)(params)


def zero_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """The sharding of every zero-state leaf (leading axis over the mesh)."""
    from ..core import basics as _basics
    mesh = mesh or _basics.mesh()
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def shard_zero_state(state, mesh: Optional[Mesh] = None):
    """Place a (restored, host/replicated) zero state onto the mesh.

    ``restore_checkpoint`` returns replicated leaves; the step expects
    them sharded on the leading axis -- this re-places every leaf.
    """
    sh = zero_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), state)


def zero_report(optimizer, params, world: int, compression=None) -> dict:
    """Static wire/HBM accounting for the zero1 config.

    Returns per-chip link bytes per step for the gradient reduce-scatter
    and the (possibly compressed) param allgather, the replicated
    allreduce equivalent, and optimizer-state HBM per chip for both
    layouts.  Pure shape arithmetic -- nothing is materialized.
    """
    leaves = jax.tree.leaves(params)
    spec = plan_arena(leaves, world)
    comp = parse_compression(compression) if compression else Compression.none

    def wire_itemsize(dt) -> int:
        dt = jnp.dtype(dt)
        if not jnp.issubdtype(dt, jnp.floating):
            return dt.itemsize
        if is_fp8(comp):
            return 1 if dt.itemsize > 1 else dt.itemsize
        wd = getattr(comp, "wire_dtype", None)
        if wd is not None and dt.itemsize > jnp.dtype(wd).itemsize:
            return jnp.dtype(wd).itemsize
        return dt.itemsize

    rs = sum(b.padded * jnp.dtype(b.dtype).itemsize
             for b in spec.buffers) * (world - 1) // max(world, 1)
    if is_error_feedback(comp):
        # EF delta allgather: each owner's wire is the compressed delta of
        # its shard (factor pair / top-k value+index pairs), not the shard.
        ag = 0
        for b in spec.buffers:
            if (not jnp.issubdtype(jnp.dtype(b.dtype), jnp.floating)
                    or b.shard < 1):
                wire = b.shard * jnp.dtype(b.dtype).itemsize
            elif is_powersgd(comp):
                pw, qw = powersgd_factor_widths(b.shard, comp.rank)
                wire = 4 * (pw + qw)
            else:
                wire = 8 * topk_count(b.shard, comp.fraction)
            ag += wire * world * (world - 1) // max(world, 1)
    else:
        ag = sum(b.padded * wire_itemsize(b.dtype)
                 for b in spec.buffers) * (world - 1) // max(world, 1)
        if is_fp8(comp):
            ag += 4 * world * len(spec.buffers)  # one f32 scale per shard
    full_bytes = sum(b.padded * jnp.dtype(b.dtype).itemsize
                     for b in spec.buffers)
    allreduce_eq = 2 * full_bytes * (world - 1) // max(world, 1)
    shards = [jax.ShapeDtypeStruct((b.shard,), b.dtype)
              for b in spec.buffers]
    state = jax.eval_shape(optimizer.init, shards)
    opt_shard_bytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                          for l in jax.tree.leaves(state))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            tuple(getattr(x, "shape", np.shape(x))),
            jnp.dtype(getattr(x, "dtype", None) or np.asarray(x).dtype)),
        params)
    full_state = jax.eval_shape(optimizer.init, abstract)
    opt_full_bytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                         for l in jax.tree.leaves(full_state))
    return {
        "world": world,
        "reducescatter_bytes_per_chip": int(rs),
        "allgather_bytes_per_chip": int(ag),
        "zero1_exchanged_bytes_per_chip": int(rs + ag),
        "replicated_allreduce_bytes_per_chip": int(allreduce_eq),
        "opt_state_bytes_per_chip_zero1": int(opt_shard_bytes),
        "opt_state_bytes_per_chip_replicated": int(opt_full_bytes),
    }


# --- elastic resize -------------------------------------------------------

def zero_resize(state, params, old_world: int, new_world: int):
    """Re-lay a ZeRO-1 optimizer state out for a new world size.

    Checkpointless elastic recovery: after a rank loss (or join), the
    flat arenas are re-planned for ``new_world`` and every sharded leaf
    (leading ``[old_world, ...]`` axis) is re-sliced so each survivor
    owns the correct 1/``new_world`` of the SAME flat content -- nothing
    is re-derived, the bytes just move.  ``_ZeroEFState`` residual
    carries index flat arena positions, so re-slicing carries the unsent
    compression mass exactly (only the arena *padding* region, zero for
    top-k and near-zero for powersgd, is dropped when the pad width
    changes).  Per-shard replicated leaves (e.g. an adam step count of
    shape ``[old_world]``) are broadcast from row 0.

    Returns ``(new_state, report)`` with
    ``report = {"carried_bytes", "zeroed_buckets", "resharded",
    "replicated"}``.  Raises ``ValueError`` when a sharded leaf cannot
    be matched to any arena (caller falls back to a full re-derivation).
    """
    import logging
    logger = logging.getLogger("horovod_tpu.optim")
    if params is None:
        raise ValueError("zero_resize needs the params tree to re-plan "
                         "the flat arenas")
    old_world, new_world = int(old_world), int(new_world)
    leaves = jax.tree.leaves(params)
    old_spec = plan_arena(leaves, old_world)
    new_spec = plan_arena(leaves, new_world)
    report = {"carried_bytes": 0, "zeroed_buckets": 0, "resharded": 0,
              "replicated": 0}

    def relayout(arr: np.ndarray, ob: _ArenaBuffer, nb: _ArenaBuffer
                 ) -> np.ndarray:
        flat = arr.reshape(-1)[:ob.size]
        pad = nb.padded - ob.size
        if pad:
            flat = np.concatenate(
                [flat, np.zeros((pad,), dtype=arr.dtype)])
        return flat.reshape(new_world, nb.shard)

    def match_buffer(arr: np.ndarray) -> Optional[int]:
        cands = [i for i, b in enumerate(old_spec.buffers)
                 if b.shard == arr.shape[1]]
        if len(cands) > 1:
            same_dt = [i for i in cands
                       if jnp.dtype(old_spec.buffers[i].dtype)
                       == arr.dtype]
            cands = same_dt or cands
        return cands[0] if len(cands) == 1 else None

    residuals = None
    inner = state
    if isinstance(state, _ZeroEFState):
        inner = state.inner
        res_out = []
        for r, ob, nb in zip(state.residuals, old_spec.buffers,
                             new_spec.buffers):
            arr = np.asarray(jax.device_get(r), dtype=np.float32)
            if arr.ndim == 2 and arr.shape == (old_world, ob.shard):
                res_out.append(jnp.asarray(relayout(arr, ob, nb)))
                report["carried_bytes"] += int(ob.size * 4)
            else:
                logger.warning(
                    "zero_resize: residual carry of shape %s is "
                    "irreconcilable with arena %s/%s -- zeroing it",
                    getattr(arr, "shape", None), ob, nb)
                _count_zeroed_residual()
                res_out.append(
                    jnp.zeros((new_world, nb.shard), jnp.float32))
                report["zeroed_buckets"] += 1
        if len(res_out) < len(new_spec.buffers):
            for nb in new_spec.buffers[len(res_out):]:
                _count_zeroed_residual()
                res_out.append(
                    jnp.zeros((new_world, nb.shard), jnp.float32))
                report["zeroed_buckets"] += 1
        residuals = tuple(res_out)

    def fix_leaf(x):
        arr = np.asarray(jax.device_get(x))
        if arr.ndim >= 1 and arr.shape[0] == old_world:
            if arr.ndim >= 2:
                i = match_buffer(arr)
                if i is not None:
                    report["resharded"] += 1
                    out = relayout(arr, old_spec.buffers[i],
                                   new_spec.buffers[i])
                    report["carried_bytes"] += int(
                        old_spec.buffers[i].size * arr.dtype.itemsize)
                    return jnp.asarray(out)
                raise ValueError(
                    f"zero_resize: sharded leaf of shape {arr.shape} "
                    f"dtype {arr.dtype} matches no arena of the "
                    f"old plan")
            # [old_world] leaf: per-shard replicated content (e.g. the
            # adam step count) -- broadcast row 0 to the new world.
            if not np.all(arr == arr[0]):
                logger.warning(
                    "zero_resize: per-shard scalar rows disagree "
                    "(%s); adopting shard 0's value", arr)
            report["replicated"] += 1
            return jnp.asarray(
                np.repeat(arr[:1], new_world, axis=0))
        return x  # replicated leaf: untouched

    new_inner = jax.tree.map(fix_leaf, inner)
    if residuals is not None:
        return _ZeroEFState(residuals, new_inner), report
    return new_inner, report


def _count_zeroed_residual() -> None:
    try:
        from ..timeline import metrics as _metrics
        _metrics.registry().counter(
            "horovod_ef_residual_zeroed_total",
            "EF residual buckets dropped (zeroed) during an elastic "
            "resize because shapes were irreconcilable").inc()
    except Exception:
        pass
