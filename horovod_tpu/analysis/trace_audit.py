"""Layer-1 static step auditor: trace, walk, cross-check -- never execute.

``audit_step`` traces a training step with ``jax.make_jaxpr`` (no device
execution, no donation side effects), extracts its collective graph with
:mod:`.jaxpr_walk`, derives the planner contract with :mod:`.stepmodel`,
and reports :class:`~horovod_tpu.analysis.findings.Finding` rows for:

- ``audit-plan-missing`` (error): a planned collective leg the trace
  never emits -- the exchange silently dropped a bucket;
- ``audit-plan-unaccounted`` (error): an emitted collective no plan row
  (nor the scalar loss/metric allowance) accounts for -- untracked wire
  traffic, the static form of the reference's mismatch stall;
- ``audit-desync-branch`` (error): ``cond``/``while`` control flow whose
  predicate is data-dependent on ``axis_index`` guarding a collective --
  ranks can disagree on whether the collective runs;
- ``audit-donation`` (error): a donated input leaf whose aval matches no
  output, so its buffer is freed with the caller still holding the
  array;
- ``audit-fence`` (error): a TPU-backed mesh whose eager fence policy
  degrades to CPU-style barrier+block, or a barrier-signature collective
  (scalar int32 psum) traced into a TPU step body;
- ``audit-collective-in-kernel`` (error): a collective primitive traced
  inside a ``pallas_call`` kernel body -- every registered kernel family
  (``ops.pallas.KERNEL_CONTRACTS``) contracts to keep its exchanges in
  XLA, where the fusion planner, this auditor, and the span recorder can
  see them.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import jaxpr_walk as _walk
from .findings import ERROR, WARNING, Finding
from .stepmodel import ExpectedExchange, expected_exchange, meta_from_step

# Scalar reductions (loss mean, metric max/min, desync probes) ride beside
# any exchange; they are matched after plan legs so a planned scalar leg
# still claims its record first.
_AUX_KINDS = frozenset({"psum", "pmax", "pmin"})


@dataclasses.dataclass
class AuditReport:
    """Outcome of one ``audit_step`` call."""
    name: str
    findings: List[Finding]
    collectives: List[_walk.CollectiveRecord]
    expected: Optional[ExpectedExchange]
    summary: Dict[str, int]

    def ok(self) -> bool:
        return not any(f.severity == ERROR for f in self.findings)

    def render(self) -> str:
        s = self.summary
        head = (f"audit {self.name}: "
                f"{s['planned_buckets']} planned bucket(s), "
                f"{s['expected_ops']} planned collective leg(s), "
                f"{s['emitted_ops']} emitted, {s['matched_ops']} matched, "
                f"{s['aux_ops']} scalar-aux -- "
                f"{'OK' if self.ok() else 'FINDINGS'}")
        lines = [head]
        lines += [f"  {f.render()}" for f in self.findings]
        return "\n".join(lines)


def _mesh_platform() -> Optional[str]:
    from ..core.state import global_state
    st = global_state()
    if st.mesh is None:
        return None
    from ..collectives.eager import _mesh_platform as mp
    return mp(st.mesh)


def _fence_findings(name: str,
                    records: Sequence[_walk.CollectiveRecord]
                    ) -> List[Finding]:
    from ..controller.fusion import _fence_policy
    findings = []
    policy = _fence_policy()
    platform = _mesh_platform()
    if platform == "tpu" and policy.startswith("barrier+block"):
        findings.append(Finding(
            rule="audit-fence", severity=ERROR, path=name,
            ident="eager-policy",
            message=f"TPU mesh resolves eager fence policy {policy!r}; "
                    "TPU transports must be compiler-scheduled"))
    if platform == "tpu":
        for r in records:
            if (r.kind == "psum" and r.elements == 1
                    and r.dtype == "int32"):
                findings.append(Finding(
                    rule="audit-fence", severity=ERROR, path=name,
                    ident=r.path,
                    message="barrier-signature collective (scalar int32 "
                            "psum) traced into a TPU step body; XLA "
                            "schedules TPU collectives -- CPU-style "
                            "barriers only serialize"))
    return findings


def _match_plan(name: str, expected: ExpectedExchange,
                records: Sequence[_walk.CollectiveRecord],
                stats_allowance: Counter) -> Tuple[List[Finding],
                                                   Dict[str, int]]:
    want = Counter(op.sig() for op in expected.ops)
    labels: Dict[Tuple[str, str, int], List[str]] = {}
    for op in expected.ops:
        labels.setdefault(op.sig(), []).append(op.label)
    matched = aux = stats = 0
    unaccounted: List[_walk.CollectiveRecord] = []
    for r in records:
        sig = r.sig()
        if want.get(sig, 0) > 0:
            want[sig] -= 1
            matched += 1
        elif stats_allowance.get(sig, 0) > 0:
            stats_allowance[sig] -= 1
            stats += 1
        elif r.kind in _AUX_KINDS and r.elements == 1:
            aux += 1
        else:
            unaccounted.append(r)

    findings = []
    for sig, n in want.items():
        if n <= 0:
            continue
        for label in labels[sig][-n:]:
            findings.append(Finding(
                rule="audit-plan-missing", severity=ERROR, path=name,
                ident=label,
                message=f"planned collective leg never emitted: "
                        f"{sig[0]} {sig[1]}[{sig[2]}] ({label})"))
    for r in unaccounted:
        findings.append(Finding(
            rule="audit-plan-unaccounted", severity=ERROR, path=name,
            ident=r.path,
            message=f"emitted collective not in the plan: {r.kind} "
                    f"{r.dtype}[{r.elements}] at {r.path}"))
    stats_left = sum(stats_allowance.values())
    counts = {"matched_ops": matched, "aux_ops": aux,
              "stats_ops": stats, "stats_unused": stats_left,
              "unaccounted_ops": len(unaccounted),
              "missing_ops": sum(n for n in want.values() if n > 0)}
    return findings, counts


def audit_step(fn, *args,
               meta: Optional[dict] = None,
               donate_argnums: Optional[Sequence[int]] = None,
               batch_stats: Any = None,
               name: str = "step") -> AuditReport:
    """Statically audit a training step against its exchange plan.

    ``fn`` is the step as the builder returned it (the
    ``_InstrumentedStep`` wrapper is unwrapped and its builder ``meta``
    picked up automatically) or any jit/shard_map callable; ``args`` are
    example arguments of the real shapes (traced, never executed, so
    donation does not consume them).  ``meta`` overrides/provides the
    builder metadata for plan matching (omit it to skip plan matching on
    unknown callables).  ``donate_argnums`` enables the donation-safety
    check; ``batch_stats`` declares a flax mutable-stats tree whose
    per-leaf averaging psums are accounted to the stats exchange.
    """
    # Builders may stack wrappers (_GuardedStep over _InstrumentedStep):
    # unwrap every layer to reach the traceable callable.
    inner = fn
    while hasattr(inner, "_fn"):
        inner = inner._fn
    if meta is None:
        meta = meta_from_step(fn)
    closed = jax.make_jaxpr(inner)(*args)

    records = _walk.collect_collectives(closed)
    findings: List[Finding] = []
    summary: Dict[str, int] = {
        "emitted_ops": len(records), "planned_buckets": 0,
        "expected_ops": 0, "matched_ops": 0, "aux_ops": 0,
        "stats_ops": 0, "unaccounted_ops": 0, "missing_ops": 0,
    }

    expected = None
    if meta is not None:
        expected = expected_exchange(args[0], meta)
        for note in expected.notes:
            findings.append(Finding(
                rule="audit-plan-unsupported" if not expected.supported
                else "audit-plan-note", severity=WARNING, path=name,
                ident="model", message=note))
        if expected.supported:
            stats_allow: Counter = Counter()
            if batch_stats is not None:
                for leaf in jax.tree.leaves(batch_stats):
                    if jnp.issubdtype(leaf.dtype, jnp.floating):
                        stats_allow[("psum", str(jnp.dtype(leaf.dtype)),
                                     int(leaf.size))] += 1
            plan_findings, counts = _match_plan(name, expected, records,
                                                stats_allow)
            findings += plan_findings
            summary.update(counts)
            summary["planned_buckets"] = len(expected.plan_rows)
            summary["expected_ops"] = len(expected.ops)

    for r in _walk.collectives_in_kernels(closed):
        findings.append(Finding(
            rule="audit-collective-in-kernel", severity=ERROR, path=name,
            ident=r.path,
            message=f"collective {r.kind} {r.dtype}[{r.elements}] traced "
                    "inside a pallas_call kernel body; kernel contracts "
                    "declare every family collective-free (in-kernel "
                    "collectives are invisible to XLA's scheduler and the "
                    "planner's wire accounting)"))

    for d in _walk.find_rank_dependent_branches(closed):
        findings.append(Finding(
            rule="audit-desync-branch", severity=ERROR, path=name,
            ident=d.path,
            message=f"rank-dependent {d.primitive} predicate guards "
                    f"collective(s) {', '.join(d.collectives)}: ranks can "
                    "diverge on whether the collective executes (desync "
                    "stall)"))

    if donate_argnums:
        for rec in _walk.check_donation(closed, args, donate_argnums):
            findings.append(Finding(
                rule="audit-donation", severity=ERROR, path=name,
                ident=f"arg{rec.argnum}.leaf{rec.leaf_index}",
                message=f"donated leaf {rec.dtype}{list(rec.shape)} of "
                        f"argument {rec.argnum} matches no output aval: "
                        "its buffer is freed while the caller still holds "
                        "the array (read-after-donate)"))

    findings += _fence_findings(name, records)
    summary["desync"] = sum(1 for f in findings
                            if f.rule == "audit-desync-branch")
    summary["donation"] = sum(1 for f in findings
                              if f.rule == "audit-donation")
    return AuditReport(name=name, findings=findings,
                       collectives=records, expected=expected,
                       summary=summary)


# -- the four reference configurations --------------------------------------

STANDARD_CONFIGS = ("plain", "zero1", "powersgd_ef", "microbatch2")

# Two-level reference configurations: same tiny tree, but the exchange
# decomposes over the (dcn, ici) communicator -- plain per-leg hier,
# hier composed with the ZeRO-1 arena, and hier with the EF codec scoped
# to the DCN hop.  They require init() on a two-level mesh
# (``build_mesh(devices, hierarchical=True, dcn_size=...)``).
HIER_CONFIGS = ("hier", "hier_zero1", "hier_powersgd_ef")

# Serving decode configurations: the tensor-parallel decode step on the
# full tp ladder and on the post-shrink mesh the elastic control plane
# leaves behind, so the exchange contract (2 row-parallel psums per
# layer of slots*d_model at the activation dtype) is gated across
# resizes, not only at the size serving happened to start at.
# ``serving_verify`` gates the speculative-decoding verify step: the
# same multiset widened by k+1 (slots*width*d_model per psum).
SERVING_CONFIGS = ("serving_decode", "serving_decode_resized",
                   "serving_verify")

# 3-D parallelism reference configurations (PR 18): the DP gradient leg
# priced over LOCAL (model-sharded) leaves and the data axes only, plus
# the declared TP/pipeline activation legs.  ``tp2`` runs TP=2 with the
# fp16 DP exchange on the hierarchical (dcn, data) pair; ``tp2_zero1``
# shards the optimizer arena over the same data axes; ``tp2_pipe_micro``
# stacks TP=2 x pipe=2 x microbatches=2 on a flat data axis.  All three
# build their own mesh over the first 8 devices.
PARALLEL3D_CONFIGS = ("tp2", "tp2_zero1", "tp2_pipe_micro")

# Threshold chosen so the tiny parameter tree below splits into TWO f32
# buckets (256 + 192 elements), exercising multi-bucket matching.
_TINY_THRESHOLD = 1024


def _tiny_params():
    a = jnp.linspace(-1.0, 1.0, 256, dtype=jnp.float32).reshape(16, 16)
    b = jnp.linspace(0.5, 1.5, 128, dtype=jnp.float32)
    c = jnp.linspace(-0.5, 0.5, 64, dtype=jnp.float32)
    return {"a": a, "b": b, "c": c}


def _tiny_loss(params, batch):
    # Per-example-mean loss touching every leaf (nonzero grads all over).
    x = batch
    s = (jnp.sum(params["a"] ** 2) + jnp.sum(params["b"] ** 2)
         + jnp.sum(params["c"] ** 2))
    return jnp.mean(x) * s


def build_standard_config(config: str):
    """Build ``(step, args, donate_argnums, name)`` for one of the four
    reference configurations (requires an initialized mesh)."""
    import optax

    from .. import training as _training
    from ..collectives.compression import Compression
    from ..core import basics as _basics
    from ..optim import distributed as _dist
    from ..optim import zero as _zero

    mesh = _basics.mesh()
    world = int(mesh.devices.size)
    params = _tiny_params()
    batch = jnp.ones((world * 2, 4), jnp.float32)

    if config == "plain":
        opt = _dist.DistributedOptimizer(
            optax.sgd(0.01), compression=Compression.fp16,
            fusion_threshold=_TINY_THRESHOLD)
        step = _training.make_train_step(_tiny_loss, opt, mesh=mesh)
        opt_state = opt.init(params)
    elif config == "zero1":
        opt = optax.sgd(0.01)
        step = _training.make_train_step(_tiny_loss, opt, mesh=mesh,
                                         zero_stage=1)
        opt_state = _zero.zero_init(opt, params, mesh=mesh)
    elif config == "powersgd_ef":
        opt = _dist.DistributedOptimizer(
            optax.sgd(0.01), compression="powersgd:2",
            fusion_threshold=_TINY_THRESHOLD)
        step = _training.make_train_step(_tiny_loss, opt, mesh=mesh)
        opt_state = opt.init(params)
    elif config == "microbatch2":
        opt = _dist.DistributedOptimizer(
            optax.sgd(0.01), compression=Compression.fp16,
            fusion_threshold=_TINY_THRESHOLD)
        step = _training.make_train_step(_tiny_loss, opt, mesh=mesh,
                                         microbatches=2)
        opt_state = opt.init(params)
    elif config in HIER_CONFIGS:
        if len(mesh.axis_names) != 2:
            raise ValueError(
                f"config {config!r} needs the two-level (dcn, ici) mesh; "
                f"init() with build_mesh(..., hierarchical=True, "
                f"dcn_size=...) first (got axes {mesh.axis_names})")
        if config == "hier":
            opt = _dist.DistributedOptimizer(
                optax.sgd(0.01), compression="ici:none,dcn:none",
                fusion_threshold=_TINY_THRESHOLD)
            step = _training.make_train_step(_tiny_loss, opt, mesh=mesh)
            opt_state = opt.init(params)
        elif config == "hier_zero1":
            opt = optax.sgd(0.01)
            step = _training.make_train_step(
                _tiny_loss, opt, mesh=mesh, zero_stage=1,
                zero_compression="ici:none,dcn:none")
            opt_state = _zero.zero_init(opt, params, mesh=mesh,
                                        compression="ici:none,dcn:none")
        else:  # hier_powersgd_ef
            opt = _dist.DistributedOptimizer(
                optax.sgd(0.01), compression="ici:none,dcn:powersgd:2",
                fusion_threshold=_TINY_THRESHOLD)
            step = _training.make_train_step(_tiny_loss, opt, mesh=mesh)
            opt_state = opt.init(params)
    elif config in SERVING_CONFIGS:
        return _build_serving_config(config)
    elif config in PARALLEL3D_CONFIGS:
        return _build_3d_config(config)
    else:
        known = (STANDARD_CONFIGS + HIER_CONFIGS + SERVING_CONFIGS
                 + PARALLEL3D_CONFIGS)
        raise ValueError(
            f"unknown standard config {config!r}; pick from {known}")
    # donate_argnums mirrors make_train_step's own (0, 1) donation.
    return step, (params, opt_state, batch), (0, 1), f"step:{config}"


def _build_3d_config(config: str):
    """``(step, args, donate, name)`` for the 3-D parallelism audits.

    Tiny TP=2 MLP (d_model=16, d_ff=32) with stacked-leading-dim sharded
    weights: ``param_specs`` put the TP shards on the ``model`` axis (and
    stage shards on ``pipe``), so the DP exchange plans over each
    device's local slices.  Each builder declares its activation contract
    in ``step._meta["model_parallel"]`` (d_model, rows per loss call,
    pipeline microbatches) -- the quantities :func:`stepmodel._expected_3d`
    prices the TP row-parallel psums and pipeline ppermute/select legs
    from.  Requires >= 8 devices; each config builds its own mesh.
    """
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from .. import training as _training
    from ..collectives.compression import Compression
    from ..optim import distributed as _dist
    from ..optim import zero as _zero
    from ..parallel import build_3d_mesh, data_axes, tp_mlp

    if len(jax.devices()) < 8:
        raise ValueError(
            f"config {config!r} needs 8 devices for the 2x2x2 meshes "
            f"(got {len(jax.devices())})")

    d_model, d_ff, tp = 16, 32, 2
    rng = np.random.default_rng(0)

    def tp_params():
        return {
            "w_up": jnp.asarray(rng.normal(size=(tp, d_model, d_ff // tp)),
                                jnp.float32),
            "w_down": jnp.asarray(rng.normal(size=(tp, d_ff // tp, d_model)),
                                  jnp.float32),
            "bias": jnp.linspace(0.5, 1.5, d_model, dtype=jnp.float32),
        }

    tp_specs = {"w_up": P("model"), "w_down": P("model"), "bias": P()}

    def tp_loss(params, batch):
        y = tp_mlp(batch + params["bias"], params["w_up"][0],
                   params["w_down"][0], axis="model")
        return jnp.mean(y * y)

    if config in ("tp2", "tp2_zero1"):
        mesh = build_3d_mesh(jax.devices()[:8], data=2, model=2,
                             dcn_size=2)
        params = tp_params()
        batch = jnp.ones((4 * 2, d_model), jnp.float32)
        if config == "tp2":
            opt = _dist.DistributedOptimizer(
                optax.sgd(0.01), compression=Compression.fp16,
                fusion_threshold=_TINY_THRESHOLD, axes=data_axes(mesh))
            step = _training.make_train_step(tp_loss, opt, mesh=mesh,
                                             tp=tp, param_specs=tp_specs)
            opt_state = opt.init(params)
        else:
            opt = optax.sgd(0.01)
            step = _training.make_train_step(tp_loss, opt, mesh=mesh,
                                             tp=tp, zero_stage=1,
                                             param_specs=tp_specs)
            opt_state = _zero.zero_init(opt, params, mesh=mesh,
                                        param_specs=tp_specs)
        # 8 global rows / 4 data-parallel devices = 2 rows per loss call.
        step._meta["model_parallel"] = {"d_model": d_model, "act_rows": 2}
    else:  # tp2_pipe_micro
        from ..parallel import pipeline_apply, split_microbatches
        mesh = build_3d_mesh(jax.devices()[:8], data=2, pipe=2, model=2)
        params = {
            "w_up": jnp.asarray(
                rng.normal(size=(2, tp, d_model, d_ff // tp)), jnp.float32),
            "w_down": jnp.asarray(
                rng.normal(size=(2, tp, d_ff // tp, d_model)), jnp.float32),
        }
        pp_specs = {"w_up": P("pipe", "model"),
                    "w_down": P("pipe", "model")}

        def pipe_loss(sp, batch):
            mb = split_microbatches(batch, 2)

            def stage_fn(stage_params, x):
                return tp_mlp(x, stage_params["w_up"][0],
                              stage_params["w_down"][0], axis="model")

            out = pipeline_apply(stage_fn, sp, mb, axis="pipe")
            y = jnp.concatenate(list(out), axis=0)
            return jnp.mean(y * y)

        opt = _dist.DistributedOptimizer(
            optax.sgd(0.01), compression=Compression.fp16,
            fusion_threshold=_TINY_THRESHOLD, axes=data_axes(mesh))
        step = _training.make_train_step(
            pipe_loss, opt, mesh=mesh, tp=tp, pipeline_stages=2,
            microbatches=2, param_specs=pp_specs)
        opt_state = opt.init(params)
        batch = jnp.ones((2 * 8, d_model), jnp.float32)
        # 16 global rows / 2 data devices / 2 train microbatches = 4 rows
        # per loss call, halved again by the 2 pipeline microbatches.
        step._meta["model_parallel"] = {"d_model": d_model, "act_rows": 4,
                                        "pipe_microbatches": 2}
    return step, (params, opt_state, batch), (0, 1), f"step:{config}"


def _build_serving_config(config: str):
    """``(step, args, (1, 2), name)`` for the serving decode audits.

    ``serving_decode`` builds on the largest valid tp size the device
    pool allows; ``serving_decode_resized`` on the next size down --
    the mesh the control plane's shrink path lands on -- with
    ``resized_from`` provenance in the step meta so the expected model
    notes the transition.  ``serving_verify`` is the width-5 (k=4)
    speculative verify step on the full tp size: the audit must match
    the widened multiset exactly, no new declines.  ``(1, 2)`` mirrors
    the step's own donation of ``k_pool``/``v_pool``: each pool leaf
    must be matched by an output (its in-place successor).
    """
    import numpy as np
    from jax.sharding import Mesh

    from ..models.transformer import LLAMA_SERVE, LlamaLM
    from ..serving import (CacheConfig, PagedKVCache, build_decode_step,
                           build_verify_step, cache_sharding)
    from ..serving.decode import no_round
    from ..serving.policy import valid_tp_sizes

    cfg = LLAMA_SERVE
    sizes = valid_tp_sizes(cfg, len(jax.devices()))
    tp = sizes[-1]
    resized_from = None
    if config == "serving_decode_resized" and len(sizes) > 1:
        resized_from, tp = sizes[-1], sizes[-2]
    mesh = Mesh(np.asarray(jax.devices()[:tp], dtype=object).reshape(tp),
                ("tp",))
    ccfg = CacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, slots=4, page_size=8, max_len=64)
    cache = PagedKVCache(ccfg, cache_sharding(mesh))
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))
    if config == "serving_verify":
        width = 5
        step = build_verify_step(cfg, mesh, slots=ccfg.slots, width=width,
                                 page_size=ccfg.page_size,
                                 pages_per_slot=ccfg.pages_per_slot)
        tokens = jnp.zeros((ccfg.slots, width), jnp.int32)
    else:
        step = build_decode_step(cfg, mesh, slots=ccfg.slots,
                                 page_size=ccfg.page_size,
                                 pages_per_slot=ccfg.pages_per_slot)
        tokens = jnp.zeros((ccfg.slots,), jnp.int32)
    if resized_from is not None:
        step._meta["resized_from"] = resized_from
    args = (params, cache.k, cache.v, tokens, cache.lengths_device(),
            cache.table_device(), jnp.zeros((ccfg.slots,), bool))
    if config != "serving_verify":
        args += (no_round(ccfg.slots),)
    return step, args, (1, 2), f"step:{config}"


def audit_standard_configs(configs: Optional[Sequence[str]] = None
                           ) -> Dict[str, AuditReport]:
    """Audit the reference configurations (plain DP, ZeRO-1, powersgd+EF,
    microbatches=2) against their plans.  Requires ``horovod_tpu.init()``
    to have built a mesh."""
    reports = {}
    for config in (configs or STANDARD_CONFIGS):
        step, args, donate, name = build_standard_config(config)
        reports[config] = audit_step(step, *args, donate_argnums=donate,
                                     name=name)
    return reports
