"""Expected-collective model: what a train step SHOULD emit.

Given the builder metadata a :func:`horovod_tpu.make_train_step` /
``make_flax_train_step`` step carries (optimizer wrap, zero stage,
microbatch count, world size), derive the exact multiset of collectives
the exchange is contracted to put on the wire -- op kind, dtype, and
element count per leg -- from the SAME planner calls the exchange makes
(``fusion.plan_buckets`` / ``ef_bucket_plan`` / ``zero.plan_arena``), so
the expectation and the emission can only diverge if the exchange code
itself diverges from its plan.

Width references:

- cast codecs: one ``psum`` per LEAF at the wire dtype (f32 leaves cast
  down, narrow/int leaves ride as-is): the elementwise exchange builds no
  bucket (``fusion.exchange_needs_vector``); where the flat exchange
  packs (per-leg codec on a flat mesh), one ``psum`` of the bucket;
- powersgd(r): two f32 ``psum`` legs per floating bucket of
  ``powersgd_factor_widths(size, r)`` elements -- the P/Q factor widths
  ``joinop._replay`` replays bitwise;
- topk(f): two ``all_gather`` legs per floating bucket of
  ``k = min(topk_count(size, f), size)`` elements (f32 values + int32
  indices);
- ZeRO-1: per dtype arena, one ``reduce_scatter`` of the padded arena
  plus one ``all_gather`` of the shard at the allgather codec's wire
  dtype;
- microbatches=k: per reverse-planned bucket, k ``reduce_scatter`` legs
  of the ``lcm(256, n)``-padded bucket plus one closing ``all_gather``
  of ``padded / n`` elements, all at the wire dtype;
- hierarchical (two-level ``(dcn, ici)`` mesh): per bucket one
  ``reduce_scatter`` of the ``lcm(256, n_ici)``-padded bucket over ICI,
  the DCN hop of the ``padded / n_ici`` shard under the DCN-leg codec
  (psum / powersgd P+Q / topk gathers / fp8 quantized gather), and one
  closing ICI ``all_gather`` of the shard;
- chunked: per wire-buffer chunk (``chunk_bytes / wire_itemsize``
  elements, rounded up to a multiple of n) one ``reduce_scatter`` of the
  padded piece plus one ``all_gather`` of ``piece / n`` elements.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..collectives import ops as _ops
from ..collectives.compression import (Compression, is_error_feedback,
                                       is_fp8, is_powersgd,
                                       parse_compression,
                                       powersgd_factor_widths, topk_count)


@dataclasses.dataclass(frozen=True)
class ExpectedOp:
    """One collective leg the exchange contract requires."""
    kind: str
    dtype: str
    elements: int
    label: str    # e.g. "bucket0(f32)/psum-P"

    def sig(self) -> Tuple[str, str, int]:
        return (self.kind, self.dtype, self.elements)


@dataclasses.dataclass
class ExpectedExchange:
    """The derived contract plus the plan rows it was derived from.

    ``supported=False`` means the config uses an exchange the model does
    not price (chunked/hierarchical/fp8/process-set/Adasum paths); the
    auditor then skips plan matching and reports a warning instead of
    guessing."""
    ops: List[ExpectedOp]
    plan_rows: List[dict]
    supported: bool = True
    notes: Tuple[str, ...] = ()
    # Pallas kernel families active while the step traces (from
    # ``ops.pallas.active_kernels()``).  Informational: every registered
    # contract is collective-free with zero wire delta, so the exchange
    # contract above is identical with kernels on or off; a future
    # family that DID declare collective legs would have them appended
    # to ``ops`` (priced, not declined) by ``_attach_kernel_contracts``.
    kernels: Tuple[str, ...] = ()


def _wire_dtype(comp, dtype) -> str:
    """Dtype a cast codec puts on the wire for a ``dtype`` bucket."""
    dt = jnp.dtype(dtype)
    wd = getattr(comp, "wire_dtype", None)
    if (wd is not None and jnp.issubdtype(dt, jnp.floating)
            and dt.itemsize > jnp.dtype(wd).itemsize):
        return str(jnp.dtype(wd))
    return str(dt)


def _unsupported(notes) -> ExpectedExchange:
    return ExpectedExchange(ops=[], plan_rows=[], supported=False,
                            notes=tuple(notes))


def _expected_world1(params, meta: dict) -> ExpectedExchange:
    """The single-device exchange: ``allreduce_gradients`` skips the
    fusion planner at ``axis_size == 1`` and maps the collective over the
    leaves -- one identity psum per leaf, at the codec's wire dtype
    (compress/decompress still wrap the size-1 psum).  ZeRO / microbatch /
    EF configurations never hit this path in practice; at world=1 their
    degenerate shapes are not worth modeling."""
    if meta.get("zero_stage") or int(meta.get("microbatches", 1)) > 1:
        return _unsupported(("world=1 zero/microbatch step: unmodeled "
                             "degenerate exchange",))
    optimizer = meta.get("optimizer")
    exchange = getattr(getattr(optimizer, "update", None),
                       "_hvd_exchange", None)
    if exchange is None:
        return ExpectedExchange(ops=[], plan_rows=[], notes=(
            "bare optimizer at world=1: no gradient exchange",))
    comp = parse_compression(exchange["compression"])
    if is_error_feedback(comp) or is_fp8(comp):
        return _unsupported((f"world=1 {comp.__name__} exchange: unmodeled "
                             "degenerate codec path",))
    from ..controller.fusion import exchange_chunk_bytes, hier_requested
    if hier_requested(comp) or exchange_chunk_bytes() > 0:
        return _unsupported(("world=1 chunked/hierarchical exchange: "
                             "unmodeled degenerate decomposition",))
    leaves = jax.tree.leaves(params)
    ops = [ExpectedOp("psum", _wire_dtype(comp, leaf.dtype),
                      int(leaf.size),
                      f"leaf{i}({jnp.dtype(leaf.dtype)})")
           for i, leaf in enumerate(leaves)]
    rows = [{"bucket": 0, "dtype": "per-leaf", "leaves": len(leaves),
             "elements": sum(int(l.size) for l in leaves),
             "kind": "leafwise-world1"}]
    return ExpectedExchange(ops=ops, plan_rows=rows, notes=(
        "world=1: leaf-wise identity psums (planner bypassed)",))


def meta_from_step(step) -> Optional[dict]:
    """The builder metadata riding an ``_InstrumentedStep`` wrapper (None
    for a bare jitted step -- pass ``meta=`` to ``audit_step`` then)."""
    meta = getattr(step, "_meta", None)
    return dict(meta) if isinstance(meta, dict) else None


# The decode-attention family a serving step does NOT call, by how it
# reads the cache (``ServingDecodeStep.meta["attention"]``): a step that
# walks the page table runs ``mla_decode``'s kernel and never the
# split-KV one; one that gathers slot views (verify, fp8) the reverse.
_OTHER_DECODE_FAMILY = {"walk": "flash_decode", "view": "mla_decode"}


def _attach_kernel_contracts(expected: ExpectedExchange, meta: dict
                             ) -> ExpectedExchange:
    """Make the expectation kernel-aware instead of declining.

    Active Pallas families are recorded on ``expected.kernels`` (for a
    serving step, less the decode-attention family it does not call);
    any collective legs a family's contract registers are appended to
    the priced ops (today every contract is collective-free with zero
    wire delta, so this only annotates).  ``trace_audit`` separately
    enforces the collective-free claim by walking ``pallas_call``
    sub-jaxprs.
    """
    from ..ops import pallas as _pallas
    other = _OTHER_DECODE_FAMILY.get(meta.get("attention"))
    active = tuple(k for k in _pallas.active_kernels() if k != other)
    if not active or not expected.supported:
        return expected
    expected.kernels = active
    for family in active:
        contract = _pallas.kernel_contract(family)
        for kind, dtype, elements in contract["collectives"]:
            expected.ops.append(ExpectedOp(
                kind, str(dtype), int(elements),
                f"kernel:{family}/{kind}"))
    return expected


def expected_exchange(params, meta: dict) -> ExpectedExchange:
    """Derive the collective contract for a step built with ``meta``
    (kernel-aware: see :func:`_attach_kernel_contracts`)."""
    expected = _attach_kernel_contracts(_expected_exchange(params, meta),
                                        meta)
    if meta.get("guard") and expected.supported:
        # The SDC guard screen: one f32[2] psum (nonfinite count +
        # grad-norm square) riding beside the gradient exchange,
        # identical on every modeled path including world=1.  Priced
        # from the SAME plan row the step notes (audit label is
        # complete), NOT absorbed by the scalar-aux allowance --
        # elements==2 is deliberate so an unmodeled auditor flags it.
        from ..controller import fusion as _fusion
        expected.ops.extend(
            _plan_ops(_fusion.plan_exchange("guard").legs, tag=""))
    return expected


def _expected_exchange(params, meta: dict) -> ExpectedExchange:
    from ..controller.fusion import (exchange_chunk_bytes,
                                     exchange_needs_vector, explain_plan)
    from ..core.state import global_state
    from ..optim import distributed as _dist
    from ..optim import zero as _zero

    if meta.get("kind") in ("serving_decode", "serving_verify"):
        return _expected_serving_decode(meta)
    if (int(meta.get("tp", 1) or 1) > 1
            or int(meta.get("pipeline_stages", 1) or 1) > 1):
        # Model-parallel step on a build_3d_mesh: the DP leg prices over
        # the LOCAL (model-sharded) leaves and the data axes only.
        return _expected_3d(params, meta)
    world = int(meta.get("world", 1))
    if world <= 1:
        return _expected_world1(params, meta)
    leaves = jax.tree.leaves(params)
    if not leaves:
        return ExpectedExchange(ops=[], plan_rows=[])

    if meta.get("zero_stage"):
        return _expected_zero(leaves, meta, world)

    optimizer = meta.get("optimizer")
    exchange = getattr(getattr(optimizer, "update", None),
                       "_hvd_exchange", None)
    k_micro = int(meta.get("microbatches", 1))
    if k_micro > 1:
        # Mirror _microbatch_unwrap: the wrapped exchange dict moves into
        # the microbatch pipe (or EF-once), the wrap's own allreduce is
        # never traced.
        return _expected_microbatch(leaves, exchange, k_micro, world)
    if exchange is None:
        return ExpectedExchange(ops=[], plan_rows=[], notes=(
            "bare optimizer: no gradient exchange",))

    comp = parse_compression(exchange["compression"])
    notes = []
    if exchange.get("process_set") is not None:
        notes.append("process-set reduction")
    from ..collectives.reduce_op import Adasum, Average, Sum
    from ..collectives.compression import is_hier_legs
    from ..controller.fusion import hier_mesh_shape, hier_requested
    op = exchange.get("op") or Average
    if op is Adasum:
        notes.append("Adasum exchange")
    if notes:
        return _unsupported(f"unmodeled exchange path: {n}" for n in notes)

    hier_shape = hier_mesh_shape()
    hier = (hier_requested(comp) and hier_shape is not None
            and op in (Sum, Average))
    thr = exchange["fusion_threshold"]
    if is_error_feedback(comp):
        if is_hier_legs(comp) and hier_shape is None:
            return _unsupported(("per-leg EF codec on a flat mesh: the "
                                 "runtime raises (needs the (dcn, ici) "
                                 "communicator)",))
        rows = explain_plan(params, threshold_bytes=_dist._ef_threshold(thr),
                            compression=comp, register=False)
        ops = _ef_ops(rows, comp,
                      hier_shape=hier_shape if is_hier_legs(comp) else None)
        return ExpectedExchange(ops=ops, plan_rows=rows)
    if is_fp8(comp):
        return _unsupported(("unmodeled exchange path: fp8 exchange",))
    rows = explain_plan(params, threshold_bytes=thr, compression=comp,
                        register=False)
    if hier:
        n_dcn, n_ici = hier_shape
        ops = []
        for r in rows:
            ops += _hier_bucket_ops(
                f"bucket{r['bucket']}({r['dtype']})", r["elements"],
                r["dtype"], comp, n_dcn, n_ici)
        return ExpectedExchange(ops=ops, plan_rows=rows, notes=(
            f"two-level exchange on the ({n_dcn}, {n_ici}) mesh",))
    if is_hier_legs(comp):
        # Flat-mesh degrade: the DCN hop is vacuous, the psum-compatible
        # ICI codec rides the flat exchange (collective() parity).
        ops = _flat_bucket_ops(rows, comp.ici)
        return ExpectedExchange(ops=ops, plan_rows=rows, notes=(
            "per-leg codec on a flat mesh: ICI codec on the flat psum",))
    chunk = exchange_chunk_bytes()
    if chunk > 0 and op in (Sum, Average):
        return ExpectedExchange(ops=_chunked_ops(rows, comp, chunk, world),
                                plan_rows=rows,
                                notes=(f"chunked exchange ({chunk}B chunks "
                                       "of the wire buffer)",))
    if not exchange_needs_vector(comp, op):
        return ExpectedExchange(
            ops=_flat_leaf_ops(leaves, comp), plan_rows=rows, notes=(
                "elementwise exchange: one psum a leaf, no fusion buffer "
                "(XLA's combiner groups them; the rows are accounting)",))
    return ExpectedExchange(ops=_flat_bucket_ops(rows, comp),
                            plan_rows=rows)


def _flat_bucket_ops(rows: List[dict], comp) -> List[ExpectedOp]:
    """One flat psum per bucket at the codec's wire dtype, rendered from
    the memoized ``plan_exchange("flat", ...)`` rows."""
    from ..controller import fusion as _fusion
    ops = []
    for r in rows:
        plan = _fusion.plan_exchange(
            "flat", size=int(r["elements"]), dtype=str(r["dtype"]),
            compression=comp)
        ops += _plan_ops(plan.legs,
                         tag=f"bucket{r['bucket']}({r['dtype']})")
    return ops


def _flat_leaf_ops(leaves, comp) -> List[ExpectedOp]:
    """The elementwise exchange: one flat psum per leaf at the codec's
    wire dtype, from the same ``plan_exchange("flat", ...)`` row the step
    notes for the leaf."""
    from ..controller import fusion as _fusion
    ops = []
    for i, leaf in enumerate(leaves):
        plan = _fusion.plan_exchange(
            "flat", size=int(leaf.size), dtype=str(jnp.dtype(leaf.dtype)),
            compression=comp)
        ops += _plan_ops(plan.legs,
                         tag=f"leaf{i}({jnp.dtype(leaf.dtype)})")
    return ops


def _plan_ops(legs, tag=None) -> List[ExpectedOp]:
    """Render plan-IR legs' audit contracts as ExpectedOp rows -- the
    expectation IS the plan, flattened by ``fusion.ops_from_legs``."""
    from ..controller import fusion as _fusion
    return [ExpectedOp(kind, dt, elements, label)
            for kind, dt, elements, label
            in _fusion.ops_from_legs(legs, tag=tag)]


def _hier_bucket_ops(tag: str, size: int, dtype, comp, n_dcn: int,
                     n_ici: int, axes=None) -> List[ExpectedOp]:
    """The collective legs one bucket of ``ops.hierarchical_allreduce``
    emits -- the SAME memoized ``plan_exchange("hier", ...)`` rows the
    executor notes, rendered in first-operand element counts (what the
    jaxpr auditor records).  ``axes`` overrides the ``(dcn, ici)`` axis
    names for exchanges over a mesh subset (the 3-D data pair); the
    default asks the world mesh so the plan-cache entry is shared with
    the executor."""
    from ..controller import fusion as _fusion
    if axes is None:
        axes = _fusion.hier_mesh_axes() or ("dcn", "ici")
    plan = _fusion.plan_exchange(
        "hier", size=int(size), dtype=str(jnp.dtype(dtype)),
        n_dcn=int(n_dcn), n_ici=int(n_ici), compression=comp,
        dcn_axis=str(axes[0]), ici_axis=str(axes[1]))
    return _plan_ops(plan.legs, tag=tag)


def _chunked_ops(rows: List[dict], comp, chunk_bytes: int,
                 world: int) -> List[ExpectedOp]:
    """The RS+AG pieces ``ops.chunked_allreduce`` emits per bucket.

    Chunking acts on the COMPRESSED wire buffer (collective() compresses
    first), so each bucket's plan is keyed on the wire dtype/size -- the
    SAME ``plan_exchange("chunked", ...)`` entry the executor notes."""
    from ..controller import fusion as _fusion
    ops = []
    for r in rows:
        wire = _wire_dtype(comp, r["dtype"])
        tag = f"bucket{r['bucket']}({r['dtype']})"
        plan = _fusion.plan_exchange(
            "chunked", size=int(r["elements"]), dtype=wire,
            chunk_bytes=int(chunk_bytes), world=int(world))
        ops += _plan_ops(plan.legs, tag=tag)
    return ops


def _expected_serving_decode(meta: dict) -> ExpectedExchange:
    """The serving TP decode / speculative verify activation contract.

    Two row-parallel closures per decoder layer (``wo`` after attention,
    ``w_down`` after the SwiGLU), each one ``collectives.ops.allreduce``
    == one ``psum`` of the full residual activation -- ``slots * width *
    d_model`` elements at the compute dtype, where ``width`` is 1 for
    plain decode and ``k + 1`` for the speculative verify step
    (``kind=serving_verify``): the SAME two-psums-per-layer multiset,
    just wider.  Size-1-axis psums are NOT elided at trace time, so the
    contract holds at tp=1.  fp8 KV compression is wire-neutral here:
    the dequant blend is local gather arithmetic, no new collectives.
    So is how the step reads its cache (``meta["attention"]``): the
    decode step's page walk (kernel family ``mla_decode``) and the
    verify step's split-KV kernel over a gathered view
    (``flash_decode``) are both local to a ``tp`` shard's heads.

    Per-slot LoRA banks are declined, not guessed: the adapter gather is
    an indexing pattern the pricing model does not cover, and a wrong
    expectation is worse than an honest unsupported warning.
    """
    if meta.get("lora"):
        return _unsupported(("serving TP decode with per-slot LoRA banks: "
                             "unmodeled adapter exchange",))
    missing = [k for k in ("num_layers", "d_model", "slots")
               if not meta.get(k)]
    if missing:
        return _unsupported(
            (f"serving decode meta missing {'/'.join(missing)}: "
             "cannot derive activation widths",))
    from ..controller import fusion as _fusion
    layers = int(meta["num_layers"])
    width = int(meta.get("width", 1))
    elements = int(meta["slots"]) * width * int(meta["d_model"])
    dtype = str(jnp.dtype(meta.get("dtype", "float32")))
    kind_tag = ("serving-tp-verify" if meta.get("kind") == "serving_verify"
                else "serving-tp-decode")
    # The SAME memoized plan the decode step builder notes; audit labels
    # are complete, so no tag prefix.
    plan = _fusion.plan_exchange(
        "serving", kind=str(meta.get("kind", "serving_decode")),
        layers=layers, slots=int(meta["slots"]), width=width,
        d_model=int(meta["d_model"]), dtype=dtype,
        axis=str(meta.get("tp_axis", "tp")))
    ops: List[ExpectedOp] = _plan_ops(plan.legs, tag="")
    rows = [{"bucket": 0, "dtype": dtype, "leaves": 2 * layers,
             "elements": 2 * layers * elements,
             "kind": kind_tag}]
    notes = [f"serving decode: 2 row-parallel allreduces/layer x {layers} "
             f"layer(s), {elements} elements each (width {width})"]
    # A rebuilt step after an elastic resize carries provenance; the
    # contract is mesh-size invariant (the psum payload is the full
    # residual activation regardless of how many ranks reduce it), so
    # the SAME expected ops must match on the post-shrink mesh.
    if meta.get("resized_from"):
        notes.append(
            f"resized decode mesh: tp {meta['resized_from']} -> "
            f"{meta.get('tp', meta.get('world'))}; activation contract "
            "is mesh-size invariant")
    return ExpectedExchange(ops=ops, plan_rows=rows, notes=tuple(notes))


def _ef_ops(rows: List[dict], comp,
            hier_shape: Optional[Tuple[int, int]] = None) -> List[ExpectedOp]:
    """The two-leg EF exchange per floating bucket (ef_exchange).

    With ``hier_shape`` (a per-leg ``ici:...,dcn:powersgd/topk`` codec on
    the two-level mesh) each floating bucket routes through
    ``hierarchical_allreduce`` with the EF codec scoped to the DCN hop;
    non-float buckets still ride the plain flat psum.  Both shapes come
    from the memoized plan IR -- the flat path from the SAME
    ``plan_exchange("ef", ...)`` entry ``ef_exchange`` notes."""
    from ..controller import fusion as _fusion
    ops = []
    for r in rows:
        tag = f"bucket{r['bucket']}({r['dtype']})"
        floating = jnp.issubdtype(jnp.dtype(r["dtype"]), jnp.floating)
        if floating and hier_shape is not None:
            ops += _hier_bucket_ops(tag, r["elements"], r["dtype"], comp,
                                    *hier_shape)
            continue
        plan = _fusion.plan_exchange(
            "ef", size=int(r["elements"]), dtype=str(r["dtype"]),
            compression=comp)
        ops += _plan_ops(plan.legs, tag=tag)
    return ops


def _expected_microbatch(leaves, exchange, k: int, world: int
                         ) -> ExpectedExchange:
    """The backward-overlap pipe: k reduce-scatters + 1 allgather per
    reverse-planned bucket (or the EF-once path for powersgd/topk)."""
    from ..controller.fusion import explain_plan, plan_buckets
    from ..optim import distributed as _dist

    if exchange is None:
        return ExpectedExchange(ops=[], plan_rows=[], notes=(
            "bare optimizer: local microbatch accumulation only",))
    comp = parse_compression(exchange["compression"])
    if is_error_feedback(comp):
        # EF composes as ONE residual-fed exchange per step over the
        # NON-reversed ef plan (_build_microbatch_local_step).
        params_like = leaves
        rows = explain_plan(
            params_like,
            threshold_bytes=_dist._ef_threshold(
                exchange["fusion_threshold"]),
            compression=comp, register=False)
        return ExpectedExchange(ops=_ef_ops(rows, comp), plan_rows=rows,
                                notes=("EF-once-per-step microbatch pipe",))

    from ..controller import fusion as _fusion
    spec = plan_buckets(leaves, exchange["fusion_threshold"], reverse=True)
    plan = _fusion.plan_exchange(
        "microbatch",
        buffers=tuple((str(jnp.dtype(dt)), sum(s.size for s in lspecs))
                      for dt, lspecs in spec.buffers),
        k=int(k), world=int(world), compression=comp)
    nb = len(spec.buffers)
    ops, rows = [], []
    for i, (dt, lspecs) in enumerate(spec.buffers):
        rs, ag = plan.legs[i], plan.legs[nb + i]
        tag = f"bucket{i}({jnp.dtype(dt)})"
        ops += _plan_ops([rs, ag], tag=tag)
        rows.append({"bucket": i, "dtype": str(jnp.dtype(dt)),
                     "leaves": len(lspecs),
                     "elements": sum(s.size for s in lspecs),
                     "padded": rs.elements, "wire_dtype": rs.wire_dtype,
                     "codec": comp.__name__, "kind": "microbatch-pipe"})
    return ExpectedExchange(ops=ops, plan_rows=rows)


def _expected_zero(leaves, meta: dict, world: int,
                   axes_shape: Optional[Tuple[int, ...]] = None
                   ) -> ExpectedExchange:
    """ZeRO-1 arena exchange: reduce-scatter + compressed allgather.

    On the two-level ``(dcn, ici)`` mesh the multi-axis collectives
    decompose per axis (``ops.reducescatter`` loops ``psum_scatter`` in
    axis order; ``ops.allgather`` gathers in reverse order), and a
    per-leg ``ici:...,dcn:...`` codec additionally flips the scatter to
    (ici, dcn) order so only the 1/n_ici shard crosses DCN, with each
    allgather hop riding its own leg codec (``zero_apply`` parity).

    ``axes_shape`` overrides the axis decomposition for steps whose
    exchange runs over a SUBSET of the mesh (the 3-D path's data axes):
    a 2-tuple prices the per-axis decomposition over that outer/inner
    pair, any other length forces the single-axis exchange -- ``None``
    keeps the global-mesh ``hier_mesh_shape()`` probe."""
    from ..collectives.compression import is_hier_legs
    from ..controller.fusion import hier_mesh_shape
    from ..optim import zero as _zero

    comp = meta.get("zero_compression")
    comp = parse_compression(comp) if comp else Compression.none
    if is_error_feedback(comp) or is_fp8(comp):
        return _unsupported(
            (f"unmodeled zero allgather codec: {comp.__name__}",))
    from ..controller import fusion as _fusion
    spec = _zero.plan_arena(leaves, world)
    use_rs = _zero._use_reducescatter()
    if axes_shape is None:
        two_level = hier_mesh_shape()
        ax_names = _fusion.hier_mesh_axes() or ()
    else:
        two_level = tuple(int(n) for n in axes_shape) \
            if len(axes_shape) == 2 else None
        ax_names = tuple(meta.get("data_axes") or ()) \
            if two_level is not None else ()
    hier = is_hier_legs(comp) and two_level is not None
    if hier and is_fp8(comp.dcn):
        return _unsupported(("unmodeled zero DCN-leg codec: fp8 "
                             "(quantized leader gather)",))
    plan = _fusion.plan_exchange(
        "zero",
        buffers=tuple((str(jnp.dtype(b.dtype)), int(b.size),
                       int(b.padded), int(b.shard)) for b in spec.buffers),
        world=int(world), compression=comp, axes_shape=two_level,
        axes=ax_names, use_rs=use_rs)
    nb = len(spec.buffers)
    ops, rows = [], []
    notes = []
    if two_level is not None:
        n_dcn, n_ici = two_level
        notes.append(f"per-axis zero exchange on the ({n_dcn}, {n_ici}) "
                     f"mesh{' (per-leg codec)' if hier else ''}")
    for i, buf in enumerate(spec.buffers):
        if buf.size < 1:
            continue
        dt = str(jnp.dtype(buf.dtype))
        tag = f"arena{i}({dt})"
        ops += _plan_ops([plan.legs[i], plan.legs[nb + i]], tag=tag)
        rows.append({"bucket": i, "dtype": dt, "leaves": len(buf.leaves),
                     "elements": buf.size, "padded": buf.padded,
                     "shard": buf.shard, "codec": comp.__name__,
                     "kind": "zero-arena"})
    return ExpectedExchange(ops=ops, plan_rows=rows, notes=tuple(notes))


def _local_leaves(params, meta: dict):
    """Per-device leaf shapes under the step's ``param_specs``: each
    spec-named dim divided by that mesh axis's extent.  The gradient
    exchange inside ``shard_map`` plans its buckets/arena from these
    LOCAL shards, so the expectation must too.  Returns ``None`` when
    the meta carries no specs or a spec does not divide its dim."""
    from jax.sharding import PartitionSpec as P
    specs = meta.get("param_specs")
    if specs is None:
        return None
    mesh_shape = dict(meta.get("mesh_shape") or ())
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.flatten(
        specs, is_leaf=lambda x: x is None or isinstance(x, P))[0]
    if len(spec_leaves) != len(leaves):
        return None
    out = []
    for leaf, sp in zip(leaves, spec_leaves):
        shape = list(leaf.shape)
        if isinstance(sp, P):
            for i, entry in enumerate(sp):
                if entry is None:
                    continue
                names = entry if isinstance(entry, tuple) else (entry,)
                for nm in names:
                    ext = int(mesh_shape.get(nm, 1))
                    if ext <= 1:
                        continue
                    if i >= len(shape) or shape[i] % ext:
                        return None
                    shape[i] //= ext
        out.append(jax.ShapeDtypeStruct(tuple(shape),
                                        jnp.dtype(leaf.dtype)))
    return out


def _expected_3d(params, meta: dict) -> ExpectedExchange:
    """DP x TP x pipeline step on a ``build_3d_mesh`` (PR 18).

    Two contributions:

    - the DP gradient leg, priced with the SAME planner calls as the
      flat model but over each device's LOCAL (model-sharded) parameter
      leaves (``_local_leaves``) and the DATA-axes world only -- plain
      per-bucket psums, the two-level decomposition when the data axes
      are the ``(dcn, data)`` pair and hier is requested, the ZeRO-1
      per-axis arena exchange, or the microbatch RS+AG pipe;
    - the model-parallel activation legs of the REFERENCE 3-D configs,
      declared via ``meta["model_parallel"]`` (``d_model``, ``act_rows``
      = rows entering the loss per call, optional ``pipe_microbatches``
      and ``dtype``): per loss call, tensor parallelism contributes one
      forward + one backward row-parallel psum of the full activation;
      a pipeline stage shifts activations with one forward + one
      backward ppermute (recorded once per scan) and closes with the
      stage-select allreduce pair.  Arbitrary TP/pipeline losses carry
      no declaration and are declined, not guessed.
    """
    from ..collectives.compression import is_hier_legs
    from ..collectives.reduce_op import Average, Sum
    from ..controller.fusion import (exchange_chunk_bytes,
                                     exchange_needs_vector, explain_plan,
                                     hier_requested)

    tp = int(meta.get("tp", 1) or 1)
    pipe = int(meta.get("pipeline_stages", 1) or 1)
    data_mesh = tuple(int(n) for n in (meta.get("data_mesh") or ()))
    world = int(meta.get("world", 1))
    k_micro = int(meta.get("microbatches", 1))
    local = _local_leaves(params, meta)
    if local is None:
        return _unsupported((
            "model-parallel step without param_specs meta: cannot derive "
            "the local leaf shapes the exchange plans over",))
    if world <= 1:
        return _unsupported((
            "3-D step with data world 1: unmodeled degenerate exchange",))
    mp = meta.get("model_parallel")
    if not (isinstance(mp, dict) and "d_model" in mp and "act_rows" in mp):
        return _unsupported((
            "model-parallel step without a declared activation contract "
            "(meta['model_parallel'] with d_model/act_rows): the 3-D "
            "reference configs declare theirs, arbitrary TP/pipeline "
            "losses are not priced",))

    # -- the DP gradient leg over the data axes --------------------------
    if meta.get("zero_stage"):
        base = _expected_zero(
            local, meta, world,
            axes_shape=data_mesh if len(data_mesh) == 2 else ())
    else:
        optimizer = meta.get("optimizer")
        exchange = getattr(getattr(optimizer, "update", None),
                           "_hvd_exchange", None)
        if k_micro > 1:
            base = _expected_microbatch(local, exchange, k_micro, world)
        elif exchange is None:
            base = ExpectedExchange(ops=[], plan_rows=[], notes=(
                "bare optimizer: no gradient exchange",))
        else:
            comp = parse_compression(exchange["compression"])
            op = exchange.get("op") or Average
            if (is_error_feedback(comp) or is_fp8(comp)
                    or op not in (Sum, Average)
                    or exchange.get("process_set") is not None):
                return _unsupported((
                    "unmodeled 3-D DP exchange (EF/fp8 codec, non-sum op "
                    "or process set)",))
            if exchange_chunk_bytes() > 0:
                return _unsupported((
                    "unmodeled 3-D chunked DP exchange",))
            rows = explain_plan(local,
                                threshold_bytes=exchange["fusion_threshold"],
                                compression=comp, register=False)
            hier = ((hier_requested(comp) or is_hier_legs(comp))
                    and len(data_mesh) == 2)
            if hier:
                d_axes = tuple(meta.get("data_axes") or ()) or None
                hops = []
                for r in rows:
                    hops += _hier_bucket_ops(
                        f"bucket{r['bucket']}({r['dtype']})", r["elements"],
                        r["dtype"], comp, *data_mesh, axes=d_axes)
                base = ExpectedExchange(ops=hops, plan_rows=rows, notes=(
                    f"two-level DP leg on the {data_mesh} data axes",))
            elif is_hier_legs(comp):
                return _unsupported((
                    "per-leg codec without the (dcn, data) pair: the "
                    "runtime raises",))
            elif exchange_needs_vector(comp, op):
                base = ExpectedExchange(ops=_flat_bucket_ops(rows, comp),
                                        plan_rows=rows)
            else:
                base = ExpectedExchange(
                    ops=_flat_leaf_ops(jax.tree.leaves(local), comp),
                    plan_rows=rows)
    if not base.supported:
        return base

    # -- the declared model-parallel activation legs ---------------------
    d = int(mp["d_model"])
    act_rows = int(mp["act_rows"])
    act_dt = str(jnp.dtype(mp.get("dtype", "float32")))
    m_pipe = max(1, int(mp.get("pipe_microbatches", 1)))
    ops = list(base.ops)
    for mb in range(k_micro):
        tag = f"mb{mb}" if k_micro > 1 else "act"
        if pipe > 1:
            rp = act_rows // m_pipe
            # One ppermute per scan direction (jaxpr_walk records a
            # scan-body collective once), stage-select psum pair on the
            # stacked outputs.
            ops.append(ExpectedOp("ppermute", act_dt, rp * d,
                                  f"{tag}/pipe-shift-fwd"))
            ops.append(ExpectedOp("ppermute", act_dt, rp * d,
                                  f"{tag}/pipe-shift-bwd"))
            ops.append(ExpectedOp("psum", act_dt, act_rows * d,
                                  f"{tag}/pipe-out-fwd"))
            ops.append(ExpectedOp("psum", act_dt, act_rows * d,
                                  f"{tag}/pipe-out-bwd"))
            if tp > 1:
                ops.append(ExpectedOp("psum", act_dt, rp * d,
                                      f"{tag}/tp-row-fwd"))
                ops.append(ExpectedOp("psum", act_dt, rp * d,
                                      f"{tag}/tp-row-bwd"))
        elif tp > 1:
            ops.append(ExpectedOp("psum", act_dt, act_rows * d,
                                  f"{tag}/tp-row-fwd"))
            ops.append(ExpectedOp("psum", act_dt, act_rows * d,
                                  f"{tag}/tp-row-bwd"))
    notes = tuple(base.notes) + (
        f"3-D config: tp={tp} pipe={pipe} data={data_mesh or (world,)}",)
    return ExpectedExchange(ops=ops, plan_rows=base.plan_rows, notes=notes)
