"""Static-analysis plane: trace-audit fixtures (known-bad and clean),
lint rule units, baseline semantics, and the CLI gate (PR 8)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu
from horovod_tpu.analysis import (apply_baseline, audit_standard_configs,
                                  audit_step, build_standard_config,
                                  errors, load_baseline)
from horovod_tpu.analysis.findings import Finding
from horovod_tpu.analysis.lints.base import LintContext
from horovod_tpu.analysis.lints.locks import UnlockedSharedStateRule
from horovod_tpu.analysis.lints.nondeterminism import \
    NondeterminismInStepRule
from horovod_tpu.analysis.lints.planner import CollectiveOutsidePlannerRule
from horovod_tpu.collectives import ops as _ops
from horovod_tpu.collectives.reduce_op import Sum
from horovod_tpu.core import basics as _basics
from horovod_tpu.optim import distributed as _dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- known-bad fixtures -----------------------------------------------------

def test_rank_dependent_branch_before_psum_is_flagged(hvd):
    """The canonical desync: only rank 0 enters the branch that reduces."""
    mesh = _basics.mesh()
    axes = tuple(mesh.axis_names)

    def local(x):
        idx = _ops.axis_index(axes)
        return jax.lax.cond(
            idx == 0,
            lambda v: _ops.allreduce(v, Sum, axes=axes),
            lambda v: v,
            x)

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(axes),
                       out_specs=P(axes), check_vma=False)
    report = audit_step(fn, jnp.ones((8, 4)), name="fixture:desync")
    assert not report.ok()
    desync = [f for f in report.findings
              if f.rule == "audit-desync-branch"]
    assert desync, report.render()
    assert "psum" in desync[0].message


def test_rank_masked_data_into_psum_is_not_flagged(hvd):
    """axis_index feeding DATA into a collective (rank masks, broadcast)
    is legitimate; only divergent control flow is a hazard."""
    mesh = _basics.mesh()
    axes = tuple(mesh.axis_names)

    def local(x):
        idx = _ops.axis_index(axes)
        masked = jnp.where(idx == 0, x, jnp.zeros_like(x))
        return _ops.allreduce(masked, Sum, axes=axes)

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(axes),
                       out_specs=P(), check_vma=False)
    report = audit_step(fn, jnp.ones((8, 4)), name="fixture:mask")
    assert not [f for f in report.findings
                if f.rule == "audit-desync-branch"], report.render()


def test_plan_emitted_width_mismatch_is_flagged(hvd):
    """Auditing the fp16 step against the plan of a bf16 wire must produce
    BOTH mismatch rules: the planned bfloat16 legs are never emitted, and
    the real float16 psums are unaccounted.  (The elementwise exchange
    builds no bucket, one psum a leaf, so where the threshold draws the
    plan's buckets does not show in the emitted collectives; the codec
    does.)"""
    step, args, donate, _ = build_standard_config("plain")
    from horovod_tpu.collectives.compression import Compression
    wrong = _dist.DistributedOptimizer(
        optax.sgd(0.01), compression=Compression.bf16,
        fusion_threshold=4096)
    meta = dict(step._meta, optimizer=wrong)
    report = audit_step(step, *args, meta=meta, donate_argnums=donate,
                        name="fixture:mismatch")
    assert not report.ok()
    assert "audit-plan-missing" in _rules(report.findings)
    assert "audit-plan-unaccounted" in _rules(report.findings)
    missing = [f for f in report.findings
               if f.rule == "audit-plan-missing"]
    assert len(missing) == 3
    assert "bfloat16[256]" in missing[0].message


def test_donated_leaf_without_output_is_flagged(hvd):
    """A donated argument whose aval matches no output is freed while the
    caller still holds it."""
    def fn(params, scratch):
        return jax.tree.map(lambda x: x + 1.0, params)

    params = {"w": jnp.ones((4, 4))}
    scratch = jnp.ones((7,))
    report = audit_step(fn, params, scratch, donate_argnums=(0, 1),
                        name="fixture:donation")
    donation = [f for f in report.findings if f.rule == "audit-donation"]
    assert len(donation) == 1, report.render()
    assert donation[0].ident == "arg1.leaf0"
    # The same shapes WITH a matching output audit clean.
    ok = audit_step(lambda p, s: (jax.tree.map(lambda x: x + 1.0, p), s),
                    params, scratch, donate_argnums=(0, 1),
                    name="fixture:donation-ok")
    assert not [f for f in ok.findings if f.rule == "audit-donation"]


def test_barrier_in_tpu_step_is_flagged(hvd, monkeypatch):
    """A CPU-style barrier (scalar int32 psum) traced into a step body is
    an error when the mesh platform is TPU, and fine on CPU."""
    from horovod_tpu.analysis import trace_audit as _ta
    mesh = _basics.mesh()
    axes = tuple(mesh.axis_names)

    def local(x):
        b = _ops.barrier(axes=axes)
        return x + b.astype(x.dtype)

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(axes),
                       out_specs=P(axes), check_vma=False)
    x = jnp.ones((8, 4))
    cpu_report = audit_step(fn, x, name="fixture:barrier-cpu")
    assert not [f for f in cpu_report.findings
                if f.rule == "audit-fence"]
    monkeypatch.setattr(_ta, "_mesh_platform", lambda: "tpu")
    tpu_report = audit_step(fn, x, name="fixture:barrier-tpu")
    fence = [f for f in tpu_report.findings if f.rule == "audit-fence"]
    assert any("barrier-signature" in f.message for f in fence), \
        tpu_report.render()


# -- clean reference configurations ----------------------------------------

def test_standard_configs_audit_green(hvd):
    reports = audit_standard_configs()
    assert set(reports) == {"plain", "zero1", "powersgd_ef", "microbatch2"}
    for name, report in reports.items():
        assert report.ok(), report.render()
        s = report.summary
        assert s["unaccounted_ops"] == 0 and s["missing_ops"] == 0, \
            report.render()
        # Every planned leg was emitted and matched exactly.
        assert s["matched_ops"] == s["expected_ops"] > 0


def test_standard_config_expected_leg_counts(hvd):
    """The audit matches the documented exchange shapes: 1 psum a leaf
    (plain: the elementwise exchange), RS+AG per arena (zero1), 2
    psums/bucket (powersgd), k RS + 1 AG per bucket (microbatch2)."""
    reports = audit_standard_configs()
    assert reports["plain"].summary["expected_ops"] == 3        # 3 leaves
    assert reports["zero1"].summary["expected_ops"] == 2        # RS + AG
    assert reports["powersgd_ef"].summary["expected_ops"] == 4  # P+Q x 2
    assert reports["microbatch2"].summary["expected_ops"] == 6  # (2RS+AG) x 2
    plain = reports["plain"]
    # fp16 wire: the emitted psums carry the three float16 leaves, each
    # in its own shape; the plan's two rows (256 | 128 + 64) are
    # accounting and say so.
    sigs = sorted(r.sig() for r in plain.collectives
                  if r.sig() in {op.sig() for op in plain.expected.ops})
    assert sigs == [("psum", "float16", 64), ("psum", "float16", 128),
                    ("psum", "float16", 256)]
    assert [r["elements"] for r in plain.expected.plan_rows] == [256, 192]
    assert [r["packed"] for r in plain.expected.plan_rows] == [False, False]


def test_train_loop_scan_carry_audits_green(hvd):
    """The k-step scan loop: per-step collectives inside the scan body
    match the plan once (the body is traced once), and the donated
    params/opt-state carry aliases the loop outputs."""
    from horovod_tpu import training as _training
    from horovod_tpu.analysis.trace_audit import (_tiny_loss, _tiny_params,
                                                  _TINY_THRESHOLD)
    from horovod_tpu.collectives.compression import Compression
    mesh = _basics.mesh()
    world = int(mesh.devices.size)
    opt = _dist.DistributedOptimizer(
        optax.sgd(0.01), compression=Compression.fp16,
        fusion_threshold=_TINY_THRESHOLD)
    loop = _training.make_train_loop(_tiny_loss, opt, mesh=mesh,
                                     steps_per_execution=3)
    params = _tiny_params()
    batches = jnp.ones((3, world * 2, 4), jnp.float32)
    report = audit_step(loop, params, opt.init(params), batches,
                        donate_argnums=(0, 1), name="step:loop")
    assert report.ok(), report.render()
    assert report.summary["matched_ops"] == 3     # one psum a leaf
    assert all(r.in_loop for r in report.collectives)


# -- lint rule units --------------------------------------------------------

def _ctx_for(tmp_path, source, fname="mod.py"):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / fname).write_text(textwrap.dedent(source))
    return LintContext(pkg_dir=str(pkg), repo_root=str(tmp_path))


def test_lock_rule_flags_unlocked_counter(tmp_path):
    ctx = _ctx_for(tmp_path, """
        import threading

        class Worker:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                self._count += 1

            def locked(self):
                with self._lock:
                    self._count += 1
        """)
    findings = list(UnlockedSharedStateRule().run(ctx))
    assert [f.ident for f in findings] == ["Worker._run:_count"]


def test_lock_rule_ignores_threadless_classes(tmp_path):
    ctx = _ctx_for(tmp_path, """
        import threading

        class Plain:
            def bump(self):
                self._count += 1
        """)
    assert not list(UnlockedSharedStateRule().run(ctx))


def test_nondeterminism_rule_flags_clock_in_traced_fn(tmp_path):
    ctx = _ctx_for(tmp_path, """
        import time
        import jax

        def local_step(x):
            t = time.time()
            return x + t

        def host_wrapper(x):
            return time.perf_counter()

        step = jax.jit(local_step)
        """)
    findings = list(NondeterminismInStepRule().run(ctx))
    assert len(findings) == 1
    assert findings[0].ident.startswith("local_step:")
    assert "wall-clock" in findings[0].message


def test_planner_rule_flags_raw_lax_collective(tmp_path):
    ctx = _ctx_for(tmp_path, """
        import jax

        def reduce_it(x, axis):
            return jax.lax.psum(x, axis)
        """)
    findings = list(CollectiveOutsidePlannerRule().run(ctx))
    assert len(findings) == 1
    assert findings[0].rule == "lint-collective-outside-planner"
    assert "lax.psum" in findings[0].ident


def test_planner_rule_exempts_exchange_layer(tmp_path):
    pkg = tmp_path / "horovod_tpu"
    (pkg / "collectives").mkdir(parents=True)
    (pkg / "collectives" / "ops.py").write_text(
        "import jax\n\ndef ar(x, a):\n    return jax.lax.psum(x, a)\n")
    ctx = LintContext(pkg_dir=str(pkg), repo_root=str(tmp_path))
    assert not list(CollectiveOutsidePlannerRule().run(ctx))


def test_repo_tree_lints_clean_under_baseline():
    """The committed tree plus the committed baseline has zero errors."""
    from horovod_tpu.analysis.lints import run_lints
    findings = run_lints()
    kept, suppressed = apply_baseline(findings, load_baseline())
    assert not errors(kept), "\n".join(f.render() for f in kept)
    assert suppressed, "baseline entries should be exercised"


# -- baseline semantics -----------------------------------------------------

def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("some-rule some/path some-ident\n")
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(p))


def test_baseline_suppresses_and_reports_stale(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text(
        "rule-a pkg/a.py ident-1  # accepted because reasons\n"
        "rule-b pkg/b.py *  # never matches anything\n")
    f = Finding(rule="rule-a", severity="error", path="pkg/a.py",
                ident="ident-1", message="m")
    kept, suppressed = apply_baseline([f], load_baseline(str(p)))
    assert suppressed == [f]
    stale = [k for k in kept if k.rule == "analysis-stale-baseline"]
    assert len(stale) == 1 and "rule-b" in stale[0].ident


# -- CLI gate ---------------------------------------------------------------

@pytest.mark.analysis
def test_cli_all_gate_exits_zero_on_repo():
    """The tier-1 CI gate: both layers over the real codebase, justified
    baseline applied, exit 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--all"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=480)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


@pytest.mark.analysis
def test_cli_lint_flags_exit_code(tmp_path):
    """--lint against a doctored baseline (suppressing nothing) must exit
    1 while the real baseline exits 0 -- the gate bites."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    empty = tmp_path / "empty_baseline.txt"
    empty.write_text("")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--lint",
         "--baseline", str(empty)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "lint-" in proc.stdout


# -- Pallas kernel awareness (PR 13) ----------------------------------------

def test_collectives_in_kernels_flags_in_kernel_psum(hvd):
    """A psum smuggled into a pallas_call body is caught by the kernel
    walk and surfaces as audit-collective-in-kernel (the contract every
    registered family declares it keeps)."""
    from jax.experimental import pallas as pl
    from horovod_tpu.analysis import jaxpr_walk as _walk

    mesh = _basics.mesh()
    axes = tuple(mesh.axis_names)

    def bad_kernel(x_ref, o_ref):
        o_ref[...] = jax.lax.psum(x_ref[...], axes[0])

    def local(x):
        return pl.pallas_call(
            bad_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(axes),
                       out_specs=P(axes), check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.ones((8, 4)))
    hits = _walk.collectives_in_kernels(closed)
    assert hits and hits[0].kind == "psum"
    assert "pallas_call" in hits[0].path

    report = audit_step(fn, jnp.ones((8, 4)), name="fixture:in-kernel")
    assert not report.ok()
    assert "audit-collective-in-kernel" in _rules(report.findings)


def test_expected_exchange_kernel_aware(hvd, monkeypatch):
    """With HOROVOD_PALLAS=1 the model annotates active families on
    ExpectedExchange.kernels (no notes -> no warnings) and the audited
    contract still matches -- the fused kernels keep the wire identical."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    step, args, donate, name = build_standard_config("powersgd_ef")
    report = audit_step(step, *args, donate_argnums=donate, name=name)
    assert report.ok(), report.render()
    assert report.expected.kernels == ("bn_bwd", "flash", "flash_decode",
                                       "fused_update", "mla_decode",
                                       "moe_gmm", "ssm_decode")
    assert not report.expected.notes
    assert report.summary["unaccounted_ops"] == 0

    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    step, args, donate, name = build_standard_config("powersgd_ef")
    report_off = audit_step(step, *args, donate_argnums=donate, name=name)
    assert report_off.ok(), report_off.render()
    assert report_off.expected.kernels == ()
    # Same contract either way: op multiset is unchanged by the kernels.
    assert sorted(op.sig() for op in report.expected.ops) == \
        sorted(op.sig() for op in report_off.expected.ops)


def test_pallas_lint_needs_interpret_test(tmp_path):
    from horovod_tpu.analysis.lints.pallas_tests import \
        PallasInterpretTestRule
    pkg = tmp_path / "horovod_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "mykern.py").write_text(textwrap.dedent("""
        from jax.experimental import pallas as pl

        def f(x):
            return pl.pallas_call(lambda x_ref, o_ref: None,
                                  out_shape=x)(x)
        """))
    ctx = LintContext(pkg_dir=str(tmp_path / "horovod_tpu"),
                      repo_root=str(tmp_path))
    findings = list(PallasInterpretTestRule().run(ctx))
    assert len(findings) == 1
    assert findings[0].rule == "lint-pallas-needs-interpret-test"
    assert findings[0].ident == "mykern"

    # A tests/test_*<stem>*.py importing the module clears it...
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_ops_mykern.py").write_text(
        "from horovod_tpu.ops import mykern\n")
    assert not list(PallasInterpretTestRule().run(ctx))

    # ...but a name-matching file that never imports it does not.
    (tests / "test_ops_mykern.py").write_text("x = 1\n")
    assert list(PallasInterpretTestRule().run(ctx))


def test_pallas_lint_clean_on_repo_tree():
    """Every committed pallas_call module ships its interpreter-mode
    test (the lint this PR adds must hold on the tree that adds it)."""
    from horovod_tpu.analysis.lints.pallas_tests import \
        PallasInterpretTestRule
    assert not list(PallasInterpretTestRule().run(LintContext()))


def test_parallel3d_configs_audit_green(hvd):
    """The 3-D trio (TP, TP+ZeRO-1, TP+pipeline+micro) audits at zero
    errors: the DP leg priced over LOCAL leaves and data axes only, the
    declared TP/pipeline activation legs matched exactly."""
    from horovod_tpu.analysis.trace_audit import PARALLEL3D_CONFIGS
    reports = audit_standard_configs(PARALLEL3D_CONFIGS)
    assert set(reports) == {"tp2", "tp2_zero1", "tp2_pipe_micro"}
    for name, report in reports.items():
        assert report.ok(), report.render()
        s = report.summary
        assert s["unaccounted_ops"] == 0 and s["missing_ops"] == 0, \
            report.render()
        assert s["matched_ops"] == s["expected_ops"] > 0


def test_parallel3d_expected_leg_counts(hvd):
    """Documented 3-D exchange shapes: tp2 = 3 DP buckets (over local
    shards) + 2 TP row psums; tp2_zero1 = per-axis RS+AG (4 legs) + 2 TP
    psums; tp2_pipe_micro = (2RS+AG) x 2 buckets + per-microbatch
    (2 ppermute + 2 stage-select + 2 TP) x 2."""
    from horovod_tpu.analysis.trace_audit import PARALLEL3D_CONFIGS
    reports = audit_standard_configs(PARALLEL3D_CONFIGS)
    assert reports["tp2"].summary["expected_ops"] == 5
    assert reports["tp2_zero1"].summary["expected_ops"] == 6
    assert reports["tp2_pipe_micro"].summary["expected_ops"] == 18
    tp2 = reports["tp2"]
    # The DP buckets plan over the LOCAL (TP-sharded) leaves: fp16 wire
    # over 16 + 256 + 256 elements, and the TP activation legs ride at
    # f32 (2 rows x d_model=16 per loss call, forward + backward).
    sigs = sorted(op.sig() for op in tp2.expected.ops)
    assert sigs == [("psum", "float16", 16), ("psum", "float16", 256),
                    ("psum", "float16", 256), ("psum", "float32", 32),
                    ("psum", "float32", 32)]


def test_expected_3d_declines_without_specs_or_contract(hvd):
    """A model-parallel meta without param_specs (or without the
    activation contract) is declined, not guessed."""
    from horovod_tpu.analysis.stepmodel import expected_exchange
    from horovod_tpu.analysis.trace_audit import (PARALLEL3D_CONFIGS,
                                                  build_standard_config)
    step, args, _, _ = build_standard_config(PARALLEL3D_CONFIGS[0])
    meta = dict(step._meta)
    no_specs = dict(meta, param_specs=None)
    exp = expected_exchange(args[0], no_specs)
    assert not exp.supported
    assert any("param_specs" in n for n in exp.notes)
    no_contract = dict(meta)
    no_contract.pop("model_parallel")
    exp = expected_exchange(args[0], no_contract)
    assert not exp.supported
    assert any("model_parallel" in n for n in exp.notes)
