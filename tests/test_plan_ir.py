"""Exchange-plan IR cross-consumer consistency (PR 19).

One plan to rule them all: for every reference configuration the
executors, the span recorder and the auditor's ``stepmodel`` must agree
because they all consume the SAME :class:`ExchangePlan` rows from
``plan_exchange``.  Gated here:

* every ``note_leg`` call during a reference trace carries an IR leg
  row (never an ad-hoc string tag), and the recorded tag set is exactly
  the tags of those rows;
* the executed collective multiset matches the IR-rebuilt
  ``expected_exchange`` exactly (0 unaccounted, 0 missing);
* ``stepmodel``/``explain_plan`` resolve against the executors' plan
  cache entries -- cache hits only, no second planning pass;
* the ROADMAP drill: a synthetic leg kind + plan family added through
  the two registry calls is priced, scheduled, audited and span-recorded
  with ZERO new consumer code.
"""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.analysis.stepmodel import expected_exchange
from horovod_tpu.analysis.trace_audit import (HIER_CONFIGS,
                                              PARALLEL3D_CONFIGS,
                                              SERVING_CONFIGS,
                                              STANDARD_CONFIGS, audit_step,
                                              build_standard_config)
from horovod_tpu.controller import fusion as _fusion
from horovod_tpu.timeline import spans as _spans


@pytest.fixture()
def captured_legs(monkeypatch):
    """Record every value handed to the leg normalizer (the single entry
    point both ``note_leg`` paths share)."""
    captured = []
    orig = _spans._normalize_leg

    def wrapper(leg, nbytes=None):
        captured.append(leg)
        return orig(leg, nbytes)

    monkeypatch.setattr(_spans, "_normalize_leg", wrapper)
    return captured


def _unwrap(step):
    inner = step
    while hasattr(inner, "_fn"):
        inner = inner._fn
    return inner


def _check_config(config, captured):
    rec = _spans.recorder()
    rec.reset()
    del captured[:]
    step, args, donate, name = build_standard_config(config)
    report = audit_step(step, *args, donate_argnums=donate, name=name)
    assert report.ok(), report.render()
    s = report.summary
    assert s["unaccounted_ops"] == 0 and s["missing_ops"] == 0, \
        report.render()
    assert s["matched_ops"] == s["expected_ops"] > 0

    # Every leg the trace registered is an IR row, and the recorder's
    # registry renders those rows verbatim (tag-for-tag).
    strings = [l for l in captured if isinstance(l, str)]
    assert not strings, f"{config}: string leg tags {strings}"
    rows = [l for l in captured if l is not None]
    assert rows, f"{config}: no legs registered"
    assert all(isinstance(l, _fusion.ExchangeLeg) for l in rows)
    assert {l.tag for l in rows} == set(rec.legs), config
    for leg in rows:
        if leg.nbytes:
            assert rec.legs[leg.tag]["nbytes"] > 0, leg.tag
    return report, rows


def _audit_sigs(rows):
    return {(kind, dt, int(n)) for leg in rows
            for kind, dt, n, _ in leg.audit}


@pytest.mark.parametrize("config", STANDARD_CONFIGS)
def test_standard_config_consumers_agree(hvd, captured_legs, config):
    report, rows = _check_config(config, captured_legs)
    # The auditor's expected multiset is derivable from the very audit
    # contracts the executors' noted legs carry: same IR, two readers.
    expected_sigs = {op.sig() for op in report.expected.ops}
    assert expected_sigs <= _audit_sigs(rows), config


@pytest.mark.parametrize("config", SERVING_CONFIGS)
def test_serving_config_consumers_agree(hvd, captured_legs, config):
    report, rows = _check_config(config, captured_legs)
    expected_sigs = {op.sig() for op in report.expected.ops}
    assert expected_sigs <= _audit_sigs(rows), config


@pytest.mark.parametrize("config", PARALLEL3D_CONFIGS)
def test_3d_config_consumers_agree(hvd, captured_legs, config):
    # TP/pipeline activation collectives are declared contracts (not
    # noted legs), so only the audit-green + IR-rows-only gates apply.
    _check_config(config, captured_legs)


@pytest.mark.parametrize("config", HIER_CONFIGS)
def test_hier_config_consumers_agree(captured_legs, config):
    import horovod_tpu as hvd_mod
    from horovod_tpu.parallel.mesh import build_mesh
    hvd_mod.shutdown()
    hvd_mod.init(mesh=build_mesh(jax.devices()[:8], hierarchical=True,
                                 dcn_size=2))
    try:
        report, rows = _check_config(config, captured_legs)
        expected_sigs = {op.sig() for op in report.expected.ops}
        assert expected_sigs <= _audit_sigs(rows), config
    finally:
        hvd_mod.shutdown()


def test_guard_config_consumers_agree(captured_legs, monkeypatch):
    # The guard mode is snapshotted into the config at init time.
    monkeypatch.setenv("HOROVOD_GUARD", "1")
    import horovod_tpu as hvd_mod
    hvd_mod.shutdown()
    hvd_mod.init()
    try:
        report, rows = _check_config("plain", captured_legs)
        # The SDC screen's extra psum rides the same IR: planner row in
        # the expected multiset, executor row in the span registry.
        guard = _fusion.plan_exchange("guard").legs[0]
        assert guard.tag in {l.tag for l in rows}
        assert any(op.sig() == ("psum", "float32", 2)
                   for op in report.expected.ops)
    finally:
        hvd_mod.shutdown()


@pytest.mark.parametrize("config", ("plain", "zero1", "microbatch2"))
def test_stepmodel_reuses_executor_plan_entries(hvd, config):
    """``expected_exchange`` rebuilds its multiset FROM the cached plans
    the executors made at trace time: hits only, zero new planning."""
    step, args, _, _ = build_standard_config(config)
    jax.make_jaxpr(_unwrap(step))(*args)
    before = _fusion.plan_cache_stats()
    expected = expected_exchange(args[0], step._meta)
    after = _fusion.plan_cache_stats()
    assert expected.supported
    assert after["misses"] == before["misses"], config
    assert after["hits"] > before["hits"], config


def test_explain_plan_reuses_executor_plan_entries(hvd):
    from horovod_tpu.analysis.trace_audit import _TINY_THRESHOLD
    from horovod_tpu.collectives.compression import Compression
    step, args, _, _ = build_standard_config("plain")
    jax.make_jaxpr(_unwrap(step))(*args)
    before = _fusion.plan_cache_stats()
    rows = _fusion.explain_plan(args[0], threshold_bytes=_TINY_THRESHOLD,
                                compression=Compression.fp16,
                                register=False)
    after = _fusion.plan_cache_stats()
    assert len(rows) == 2  # the two reference buckets
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]


# -- the ROADMAP drill: a new leg kind touches planner + one executor only --

def _syn_build(spec):
    return [_fusion.ExchangeLeg(
        tag="syn/probe", axis="dcn", collective="psum", codec="none",
        wire_dtype="float32", elements=spec["n"], nbytes=spec["n"] * 4,
        kind="syn_probe",
        audit=(("psum", "float32", spec["n"], "probe"),))]


def test_new_leg_kind_needs_zero_consumer_code(hvd):
    _fusion.register_leg_kind("syn_probe", bandwidth="dcn",
                              doc="synthetic drill kind (tests only)")
    _fusion.register_plan_family("syn", _syn_build,
                                 lambda s: {"n": int(s["n"])})
    plan = _fusion.plan_exchange("syn", n=32)
    leg = plan.legs[0]
    # Scheduler: priced and classed from the kind registry alone.
    assert _fusion.leg_bandwidth(leg) == "dcn"
    assert _fusion.leg_cost_seconds(leg) > 0.0
    # Auditor: expected rows come straight off the IR.
    assert _fusion.ops_from_legs(plan.legs) == \
        [("psum", "float32", 32, "syn/probe/probe")]
    # Spans: the registry renders the row verbatim.
    rec = _spans.recorder()
    rec.reset()
    _spans.note_leg(leg)
    assert rec.legs["syn/probe"] == {"nbytes": 128, "buckets": 1}
    # Planner: memoized like every built-in family.
    before = _fusion.plan_cache_stats()
    again = _fusion.plan_exchange("syn", n=32)
    after = _fusion.plan_cache_stats()
    assert again is plan
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    # Scheduler integration: the DCN probe leg is issued ahead of an
    # independent ICI leg it does not depend on.
    ici = _fusion.plan_exchange("flat", size=64, dtype="float32",
                                compression=None).legs[0]
    import dataclasses
    ici = dataclasses.replace(ici, bucket=1)
    ordered = _fusion.schedule_legs([ici, leg], mode="bandwidth")
    assert ordered[0] is leg


def test_schedule_legs_orders_bandwidth_and_respects_chains(hvd):
    """DCN-first across independent chains; plan order within a bucket's
    RS -> hop -> AG chain; ``program`` mode restores plan order."""
    legs = _fusion.plan_exchange(
        "hier", size=4096, dtype="float32", n_dcn=2, n_ici=4,
        compression=None, dcn_axis="dcn", ici_axis="ici").legs
    flat = _fusion.plan_exchange("flat", size=64, dtype="float32",
                                 compression=None).legs[0]
    import dataclasses
    flat = dataclasses.replace(flat, bucket=7)
    program = [flat] + list(legs)
    ordered = _fusion.schedule_legs(program, mode="bandwidth")
    # Intra-bucket chain order is preserved...
    pos = {id(l): i for i, l in enumerate(ordered)}
    chain = [l for l in ordered if l.bucket == legs[0].bucket]
    assert [l.tag for l in chain] == [l.tag for l in legs]
    # ...and the contended-DCN hop cannot be issued later than in
    # program order (the cheap flat ICI leg no longer blocks it).
    dcn = next(l for l in legs if _fusion.leg_bandwidth(l) == "dcn")
    assert pos[id(dcn)] <= 1 + list(legs).index(dcn)
    assert _fusion.schedule_legs(program, mode="program") == program
    # Either order carries the same legs: a byte-identical wire payload.
    assert sorted(map(id, ordered)) == sorted(map(id, program))
    assert sum(l.nbytes for l in ordered) == sum(l.nbytes for l in program)
    sim_sched = _fusion.simulate_issue(ordered)
    sim_prog = _fusion.simulate_issue(program)
    assert sim_sched["makespan_s"] <= sim_prog["makespan_s"] + 1e-12
    assert 0.0 <= sim_sched["dispatch_gap_fraction"] <= 1.0


def test_overlap_phases_round_robins_scheduled_order(hvd):
    legs = []
    import dataclasses
    base = _fusion.plan_exchange("flat", size=256, dtype="float32",
                                 compression=None).legs[0]
    for b in range(4):
        legs.append(dataclasses.replace(base, bucket=b))
    phases = _fusion.overlap_phases(legs, 2, mode="program")
    assert [len(p) for p in phases] == [2, 2]
    assert [l.bucket for l in phases[0]] == [0, 2]
    assert [l.bucket for l in phases[1]] == [1, 3]
