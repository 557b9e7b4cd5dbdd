"""SLO-driven elastic serving control plane: policy, drain, evict, audit.

The contract under test, per layer:

* **policy** -- pure-python decision function: dead-rank mandatory
  shrink beats everything, straggler eviction beats voluntary moves,
  voluntary moves need ``hysteresis`` consecutive breaches plus an
  elapsed cooldown, and every target stays on the valid tp ladder.
* **drain** -- a request mid-decode at shrink time either finishes on
  the old mesh with bitwise-identical tokens (completion path) or is
  suspended, re-prefilled on the post-resize mesh from prompt + emitted
  tokens, and continues within sampling tolerance (re-prefill path);
  either way suspension frees its KV pages exactly.
* **eviction** -- the StragglerMonitor hook fires once (latched) only
  for a SUSTAINED over-threshold straggler, and ``evict`` forgets the
  rank so attribution tracks the survivors.
* **closed loop** -- a chaos drill (kill@ + slow@) ends with the dead
  rank resized away, the slow rank auto-evicted, zero lost requests,
  zero leaked pages, and every decision visible as ``horovod_ctl_*``
  metrics and ``ctl/*`` span-recorder legs.
* **audit** -- the serving-tp-decode trace audit still matches its plan
  on the post-shrink mesh (``serving_decode_resized``).
"""

import jax
import numpy as np
import pytest

from horovod_tpu.analysis.trace_audit import audit_standard_configs
from horovod_tpu.elastic import run_loop as _run_loop
from horovod_tpu.elastic.run_loop import apply_resize
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import (CacheConfig, ContinuousBatchScheduler,
                                 Decision, PagedKVCache, PolicyConfig,
                                 Request, ScalePolicy, ServingControlPlane,
                                 ServingEngine, SLOSample, valid_tp_sizes)
from horovod_tpu.timeline import spans
from horovod_tpu.timeline.metrics import (histogram_quantile,
                                          histogram_window, registry,
                                          render_prometheus)
from horovod_tpu.timeline.straggler import StragglerMonitor

import jax.numpy as jnp

CFG = LLAMA_SERVE


@pytest.fixture(scope="module")
def base_params():
    model = LlamaLM(CFG, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))


def _req(rid, plen=4, out=4, arrival=0.0):
    return Request(rid=rid, prompt=np.full((plen,), rid % 7, np.int32),
                   max_new_tokens=out, arrival_s=arrival)


def _sample(now_s=0.0, queue=0, p99=None, occ=0.5, mesh=(0, 1),
            healthy=(0, 1, 2, 3, 4, 5, 6, 7), dead=(), evict=None):
    return SLOSample(now_s=now_s, queue_depth=queue, ttft_p99_s=p99,
                     occupancy=occ, mesh_size=len(mesh),
                     mesh_ranks=tuple(mesh), healthy=tuple(healthy),
                     dead_ranks=tuple(dead), evict_candidate=evict)


# ---------------------------------------------------------------------------
# Policy: ladder, hysteresis, cooldown, precedence
# ---------------------------------------------------------------------------


def test_valid_tp_sizes_ladder():
    assert valid_tp_sizes(CFG, 8) == [1, 2, 4, 8]
    assert valid_tp_sizes(CFG, 5) == [1, 2, 4]

    class _Odd:
        num_heads, num_kv_heads, ffn_hidden = 6, 2, 24

    # 4 does not divide num_heads=6: the ladder skips it.
    assert valid_tp_sizes(_Odd, 8) == [1, 2]


def test_policy_config_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_CTL_QUEUE_HIGH", "3")
    monkeypatch.setenv("HOROVOD_CTL_TTFT_SLO_S", "2.5")
    monkeypatch.setenv("HOROVOD_CTL_MAX_TP", "4")
    cfg = PolicyConfig.from_env()
    assert cfg.queue_high == 3
    assert cfg.ttft_slo_s == 2.5
    assert cfg.max_tp == 4
    assert cfg.hysteresis == PolicyConfig().hysteresis  # untouched default


def test_policy_grow_needs_hysteresis_then_cooldown():
    cfg = PolicyConfig(hysteresis=2, cooldown_s=1.0, queue_high=8)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    assert pol.decide(_sample(now_s=0.0, queue=10)).is_hold  # breach 1/2
    d = pol.decide(_sample(now_s=0.1, queue=10))             # breach 2/2
    assert (d.action, d.target_size) == ("grow", 4)
    pol.mark_applied(d, 0.1)
    # Still overloaded, but inside the cooldown: hold.
    assert pol.decide(_sample(now_s=0.3, queue=10)).is_hold
    assert pol.decide(_sample(now_s=0.5, queue=10)).is_hold
    d = pol.decide(_sample(now_s=1.2, queue=10))
    assert (d.action, d.target_size) == ("grow", 4)


def test_policy_grow_capped_by_healthy_and_ladder_top():
    cfg = PolicyConfig(hysteresis=1, cooldown_s=0.0)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    # Only 3 healthy devices: no valid size above 2 fits.
    assert pol.decide(_sample(queue=10, mesh=(0, 1),
                              healthy=(0, 1, 2))).is_hold
    # Already at the top of the ladder: nothing to grow into.
    assert pol.decide(_sample(queue=10,
                              mesh=tuple(range(8)))).is_hold


def test_policy_shrink_on_underload():
    cfg = PolicyConfig(hysteresis=2, cooldown_s=0.0, occupancy_low=0.25)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    assert pol.decide(_sample(occ=0.1, mesh=(0, 1, 2, 3))).is_hold
    d = pol.decide(_sample(occ=0.1, mesh=(0, 1, 2, 3)))
    assert (d.action, d.target_size) == ("shrink", 2)
    # A queued request means the low occupancy is transient: no shrink.
    pol2 = ScalePolicy(cfg, [1, 2, 4, 8])
    for t in range(4):
        assert pol2.decide(_sample(now_s=t, occ=0.1, queue=1,
                                   mesh=(0, 1, 2, 3))).is_hold


def test_policy_ttft_breach_counts_as_overload():
    cfg = PolicyConfig(hysteresis=1, cooldown_s=0.0, ttft_slo_s=0.5)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    d = pol.decide(_sample(p99=0.9))
    assert (d.action, d.target_size) == ("grow", 4)
    # None p99 (empty window) is not a breach.
    pol2 = ScalePolicy(cfg, [1, 2, 4, 8])
    assert pol2.decide(_sample(p99=None)).is_hold


def test_policy_dead_rank_bypasses_debounce():
    cfg = PolicyConfig(hysteresis=99, cooldown_s=1e9)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    d = pol.decide(_sample(mesh=(0, 1, 2, 3, 4, 5, 6, 7),
                           healthy=(0, 1, 2, 3, 4, 5, 6), dead=(7,)))
    assert (d.action, d.reason, d.target_size) == ("shrink", "rank-dead", 4)
    # No healthy device left that fits any valid size: hold, not crash.
    d = pol.decide(_sample(mesh=(0,), healthy=(), dead=(0,)))
    assert d.is_hold and "no-viable-size" in d.reason


def test_policy_evict_precedence_and_latch():
    cfg = PolicyConfig(hysteresis=99, cooldown_s=1e9)
    pol = ScalePolicy(cfg, [1, 2, 4, 8])
    s = _sample(mesh=(0, 1, 2, 3), healthy=(0, 1, 2, 3, 4),
                evict=(2, 0.4))
    d = pol.decide(s)
    assert (d.action, d.evict_rank, d.target_size) == ("evict", 2, 4)
    assert "straggler-lateness" in d.reason
    # Same candidate again: already evicted, never re-issued.
    assert pol.decide(s).is_hold
    # A candidate that already left the mesh is ignored.
    assert pol.decide(_sample(mesh=(0, 1), evict=(5, 0.4))).is_hold


# ---------------------------------------------------------------------------
# Histogram window/quantile arithmetic (the controller's TTFT p99 sensor)
# ---------------------------------------------------------------------------


def test_histogram_quantile_interpolation():
    snap = {"buckets": {"0.1": 5, "1.0": 10, "+Inf": 10},
            "sum": 4.0, "count": 10}
    assert histogram_quantile(snap, 0.5) == pytest.approx(0.1)
    assert histogram_quantile(snap, 0.99) == pytest.approx(0.982)
    # Overflow observations clamp to the highest finite bound.
    over = {"buckets": {"0.25": 0, "+Inf": 4}, "sum": 9.0, "count": 4}
    assert histogram_quantile(over, 0.5) == pytest.approx(0.25)
    assert histogram_quantile({"buckets": {}, "count": 0}, 0.5) is None


def test_histogram_window_diffs_cumulative_snapshots():
    h = registry().histogram("test_ctl_ttft_window", "test histogram",
                             buckets=(0.1, 1.0))
    for _ in range(5):
        h.observe(0.05)
    base = h.snapshot()
    for _ in range(5):
        h.observe(0.5)
    win = histogram_window(h.snapshot(), base)
    assert win["count"] == 5
    # All 5 windowed observations sit in the (0.1, 1.0] bucket.
    assert histogram_quantile(win, 0.5) == pytest.approx(0.55)
    # No baseline: the window is the whole snapshot.
    assert histogram_window(base, None) is base


# ---------------------------------------------------------------------------
# apply_resize: the shared training/serving reset sequence
# ---------------------------------------------------------------------------


class _FakeElasticState:
    """Training-shaped carrier recording the reset call sequence."""

    def __init__(self, fail=False):
        self.fail = fail
        self.calls = []

    def resize(self, old_size, new_size):
        self.calls.append(("resize", old_size, new_size))
        if self.fail:
            raise RuntimeError("repartition failed")
        return "ok"

    def on_reset(self):
        self.calls.append(("on_reset",))


class _SyncOnlyState:
    def __init__(self):
        self.calls = []

    def on_reset(self):
        self.calls.append(("on_reset",))


def _ranks_lost():
    return registry().counter("horovod_elastic_ranks_lost",
                              "Ranks lost across elastic recoveries").value


def test_apply_resize_shrink_order_and_counter():
    st = _FakeElasticState()
    before = _ranks_lost()
    apply_resize(st, 8, 4)
    assert st.calls == [("resize", 8, 4), ("on_reset",)]
    assert _ranks_lost() - before == 4


def test_apply_resize_grow_and_noop_paths():
    st = _FakeElasticState()
    before = _ranks_lost()
    apply_resize(st, 2, 4)
    assert st.calls == [("resize", 2, 4), ("on_reset",)]
    assert _ranks_lost() == before        # growth loses nothing
    st = _FakeElasticState()
    apply_resize(st, 4, 4)                # same size: reset only
    assert st.calls == [("on_reset",)]
    st = _FakeElasticState()
    apply_resize(st, None, 4)             # first rendezvous
    assert st.calls == [("on_reset",)]


def test_apply_resize_falls_back_to_plain_sync():
    st = _FakeElasticState(fail=True)
    apply_resize(st, 4, 2)                # must not raise
    assert st.calls == [("resize", 4, 2), ("on_reset",)]
    st = _SyncOnlyState()
    before = _ranks_lost()
    apply_resize(st, 4, 2)
    assert st.calls == [("on_reset",)]
    assert _ranks_lost() - before == 2


def test_training_loop_uses_extracted_apply_resize():
    # The elastic training loop's reset block is exactly the extracted
    # hook -- the serving control plane and the training loop share one
    # resize sequence (covered behaviorally by tests/test_elastic.py).
    assert "apply_resize" in _run_loop._elastic_loop.__code__.co_names


# ---------------------------------------------------------------------------
# Straggler eviction hook: sustained streak, latch, evict-forgets
# ---------------------------------------------------------------------------


def _obs(rank, step, wall):
    return {"rank": rank, "step": step, "t0_us": 0.0, "wall_s": wall,
            "spans": {}, "legs": {}}


def test_eviction_hook_fires_once_for_sustained_straggler():
    mon = StragglerMonitor(world=3, stall_check_time=0)
    fired = []
    mon.add_eviction_hook(0.1, lambda r, l: fired.append((r, l)))
    for rnd in range(4):
        for r in range(3):
            mon.observe(_obs(r, rnd, 0.5 if r == 2 else 0.01))
    assert len(fired) == 1                # latched after the first fire
    rank, lateness = fired[0]
    assert rank == 2 and lateness >= 0.1
    mon.evict(2)
    rep = mon.report()
    assert 2 not in rep["per_rank_wall_s"]
    assert rep["straggler_rank"] != 2


def test_eviction_streak_resets_when_lateness_recovers():
    # High alpha so one fast report pulls the EWMA back under the
    # threshold: a recovered rank must restart the sustained streak.
    mon = StragglerMonitor(world=3, alpha=0.9, stall_check_time=0)
    fired = []
    mon.add_eviction_hook(0.1, lambda r, l: fired.append(r))
    mon.observe(_obs(0, 0, 0.2))
    mon.observe(_obs(1, 0, 0.01))
    mon.observe(_obs(2, 0, 0.01))         # streak 2 for rank 0
    mon.observe(_obs(0, 1, 0.01))         # recovers: lateness < 0.1
    mon.observe(_obs(1, 1, 0.01))
    mon.observe(_obs(2, 1, 0.01))
    assert fired == []                    # never 3 consecutive
    mon.observe(_obs(0, 2, 0.2))          # slow again: streak restarts
    mon.observe(_obs(1, 2, 0.01))
    assert fired == []                    # streak 2 < world
    mon.observe(_obs(2, 2, 0.01))
    assert fired == [0]


# ---------------------------------------------------------------------------
# Scheduler drain lifecycle: draining label, suspend frees pages exactly
# ---------------------------------------------------------------------------


def test_scheduler_drain_suspend_restore_cycle():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    sched = ContinuousBatchScheduler(2, cache)
    for i in range(2):
        sched.submit(_req(i, plen=6))
    for slot, req in sched.admit(0.0):
        cache.reserve(slot, req.prompt_len + 1)
    assert cache.allocated_pages == 4     # 2 slots x 2 pages
    sched.pause_admission()
    sched.submit(_req(9))
    assert sched.admit(0.1) == []         # admission gate closed
    for slot in list(sched.active):
        assert sched.mark_draining(slot).state == "draining"
    assert sched.draining_slots == [0, 1]
    assert sched._m_slot_states.labels(state="draining").value == 2
    suspended = [sched.suspend(slot) for slot in sorted(sched.active)]
    assert [r.state for r in suspended] == ["suspended", "suspended"]
    assert all(r.slot == -1 for r in suspended)
    # Suspension released every page: the sweep recovers nothing.
    assert cache.allocated_pages == 0
    assert cache.release_all() == 0
    slot = sched.restore(suspended[0])
    assert suspended[0].state == "decode" and suspended[0].slot == slot
    sched.resume_admission()
    assert [r.rid for _, r in sched.admit(0.2)] == [9]


# ---------------------------------------------------------------------------
# Drain paths on the real engine
# ---------------------------------------------------------------------------


class ScriptedPolicy:
    """Deterministic decision source: ``script`` maps decide-call index
    to a Decision; everything else holds."""

    def __init__(self, script):
        self.script = dict(script)
        self.calls = 0
        self.applied = []

    def decide(self, sample):
        d = self.script.pop(self.calls, None)
        self.calls += 1
        return d if d is not None else Decision("hold", "scripted")

    def mark_applied(self, decision, now_s):
        self.applied.append(decision.action)


_ENGINE_KW = dict(slots=2, page_size=8, max_len=64)


def _mesh2():
    from jax.sharding import Mesh
    devs = jax.devices()[:2]
    return Mesh(np.asarray(devs, dtype=object).reshape(2), ("tp",))


@pytest.fixture(scope="module")
def baseline_tokens(base_params):
    """Undisturbed tp=2 serve of the reference request."""
    _, params = base_params
    eng = ServingEngine(CFG, params, mesh=_mesh2(), **_ENGINE_KW)
    req = _req(0, plen=8, out=12)
    eng.serve([req])
    return list(req.tokens)


def test_drain_completion_path_bitwise(base_params, baseline_tokens):
    _, params = base_params
    # Shrink scripted mid-decode, but the drain budget is large enough
    # for the request to finish on the mesh it started on: tokens must
    # be bitwise identical to the undisturbed run.
    plane = ServingControlPlane(
        CFG, params, devices=jax.devices()[:2], initial_tp=2,
        policy=ScriptedPolicy({2: Decision("shrink", "scripted",
                                           target_size=1)}),
        policy_config=PolicyConfig(interval_s=0.0, drain_steps=64),
        **_ENGINE_KW)
    req = _req(0, plen=8, out=12)
    rep = plane.serve([req])
    assert list(req.tokens) == baseline_tokens
    assert rep.drained_completed == 1 and rep.drained_reprefilled == 0
    assert rep.drain_leaked_pages == 0 and rep.lost_requests == 0
    assert rep.mesh_size_final == 1 and rep.resizes == 1
    assert plane.engine.cache.allocated_pages == 0


@pytest.mark.parametrize("how", ["undisturbed", "same_mesh_reprefill",
                                 "chunked_across_a_drain"])
def test_the_planes_loop_serves_what_serve_serves(base_params, how):
    """The control plane's loop goes through the engine's one join and
    one round: ten requests over six slots (the first admission brings
    four of one length, one prefill program) are served the tokens
    ``serve`` serves them, bitwise, every first token left on the chip
    -- undisturbed, across a suspend-and-re-prefill onto the same mesh,
    and where the drain comes while prompts still go in chunk by chunk
    (they go in whole first: a request is suspended by its tokens)."""
    _, params = base_params
    chunked = how == "chunked_across_a_drain"
    kw = dict(slots=6, page_size=8, max_len=32,
              prefill_chunk=4 if chunked else 0)

    def requests():
        rng = np.random.RandomState(2)
        return [Request(rid=i, prompt=rng.randint(0, 60, size=n)
                        .astype(np.int32), max_new_tokens=o, arrival_s=0.0)
                for i, (n, o) in enumerate(zip(
                    [6, 6, 9, 6, 6, 5, 6, 9, 6, 6],
                    [5, 3, 4, 6, 1, 4, 3, 5, 2, 4]))]

    want = requests()
    ServingEngine(CFG, params, **kw).serve(want)
    swap = Decision("shrink", "scripted-swap", target_size=1)
    script = {"undisturbed": {}, "same_mesh_reprefill": {2: swap},
              "chunked_across_a_drain": {0: swap}}[how]
    plane = ServingControlPlane(
        CFG, params, devices=jax.devices()[:1], initial_tp=1,
        policy=ScriptedPolicy(script),
        policy_config=PolicyConfig(interval_s=0.0, drain_steps=0), **kw)
    spans.recorder().reset()
    got = requests()
    rep = plane.serve(got)
    assert rep.lost_requests == 0 and rep.drain_leaked_pages == 0
    assert rep.resizes == (how != "undisturbed")
    assert [r.tokens for r in got] == [r.tokens for r in want]
    st = plane._stats
    assert st["first_tokens_deferred"] == st["prefills"] == len(got)
    assert st["in_flight"] is None and not st["joins"]
    assert st["prefills_grouped"] >= (0 if chunked else 4)
    if chunked:
        # The six of the first admission were one chunk in when the
        # drain came: one was done with its first token, five were
        # suspended with theirs.
        assert rep.drained_reprefilled == 5
    prefills = spans.recorder().records(name="serve.prefill")
    assert {p.attrs["deferred"] for p in prefills} <= {True}
    assert {p.attrs["behind"] for p in prefills} <= {-1}
    assert bool(prefills) == (not chunked)
    assert plane.engine.cache.allocated_pages == 0


def test_drain_reprefill_path_across_shrink(base_params, baseline_tokens):
    _, params = base_params
    # Zero drain budget: the mid-decode request is suspended and
    # re-prefilled on the tp=1 mesh.  The prefix emitted before the
    # shrink is bitwise identical; the continuation after re-prefill is
    # within decode-step sampling tolerance (greedy over logits that
    # agree to ~1e-4 across mesh sizes), and the request still runs to
    # its full token budget with every page accounted for.
    plane = ServingControlPlane(
        CFG, params, devices=jax.devices()[:2], initial_tp=2,
        policy=ScriptedPolicy({2: Decision("shrink", "scripted",
                                           target_size=1)}),
        policy_config=PolicyConfig(interval_s=0.0, drain_steps=0),
        **_ENGINE_KW)
    req = _req(0, plen=8, out=12)
    rep = plane.serve([req])
    assert rep.drained_reprefilled == 1 and rep.drained_completed == 0
    assert rep.drain_leaked_pages == 0 and rep.lost_requests == 0
    assert rep.mesh_size_final == 1
    # Decide-call 2 fires after the 2nd decode step: prefill token +
    # 3 decode tokens are already out and must match the baseline.
    assert list(req.tokens[:4]) == baseline_tokens[:4]
    assert len(req.tokens) == 12          # ran to completion post-resize
    assert plane.engine.cache.allocated_pages == 0


def test_drain_reprefill_same_mesh_is_bitwise(base_params, baseline_tokens):
    _, params = base_params
    # Same-size scripted transition (a spare swap with no spare: the
    # surviving ranks ARE the old ranks).  Re-prefill back onto an
    # identical mesh must reproduce the undisturbed tokens bitwise --
    # the resume state (prompt + emitted tokens) carries everything.
    plane = ServingControlPlane(
        CFG, params, devices=jax.devices()[:2], initial_tp=2,
        policy=ScriptedPolicy({2: Decision("shrink", "scripted-swap",
                                           target_size=2)}),
        policy_config=PolicyConfig(interval_s=0.0, drain_steps=0),
        **_ENGINE_KW)
    req = _req(0, plen=8, out=12)
    rep = plane.serve([req])
    assert rep.drained_reprefilled == 1
    assert rep.drain_leaked_pages == 0 and rep.lost_requests == 0
    assert rep.mesh_size_final == 2 and rep.resizes == 1
    assert list(req.tokens) == baseline_tokens


# ---------------------------------------------------------------------------
# The closed loop: kill@ + slow@ chaos drill
# ---------------------------------------------------------------------------


def test_closed_loop_chaos_drill(base_params):
    _, params = base_params
    spans.recorder().reset()
    plane = ServingControlPlane(
        CFG, params, devices=jax.devices()[:4], initial_tp=4,
        policy_config=PolicyConfig(
            interval_s=0.01, ttft_slo_s=10.0, queue_high=1000,
            occupancy_low=-1.0, hysteresis=2, cooldown_s=0.1,
            evict_lateness_s=0.05, drain_steps=4, max_tp=4),
        chaos_spec="kill@step=6,rank=3;slow@step=12,rank=1,secs=0.3",
        slots=4, page_size=8, max_len=64)
    reqs = [_req(i, plen=4, out=16) for i in range(12)]
    rep = plane.serve(reqs)

    # Nothing lost, nothing leaked: every admitted request completed
    # across two disruptive transitions.
    assert rep.lost_requests == 0
    assert rep.serving.completed == 12
    assert rep.drain_leaked_pages == 0
    assert plane.engine.cache.allocated_pages == 0

    # kill@rank=3 forced a mandatory shrink off the dead device...
    assert rep.dead_ranks == [3]
    assert any(d["action"] == "shrink" and d["reason"] == "rank-dead"
               for d in rep.decisions)
    assert 3 not in plane.mesh_ranks
    # ...and slow@rank=1 was evicted by the lateness EWMA closed loop.
    assert rep.evicted_ranks == [1]
    assert any(d["action"] == "evict" and d["evict_rank"] == 1
               for d in rep.decisions)
    assert 1 not in plane.mesh_ranks
    assert rep.resizes >= 2 and rep.mesh_size_final == 2
    assert rep.drained_completed + rep.drained_reprefilled >= 1

    # Every decision is visible to the observability plane: metric
    # families and span-recorder ctl legs.
    text = render_prometheus()
    for fam in ("horovod_ctl_decisions_total",
                "horovod_ctl_resizes_total",
                "horovod_ctl_evictions_total",
                "horovod_ctl_drained_requests_total",
                "horovod_ctl_mesh_size",
                "horovod_ctl_healthy_ranks"):
        assert fam in text, fam
    legs = set()
    for acc in spans.recorder()._acc.values():
        legs.update(acc["legs"])
    assert "ctl/fault/kill" in legs and "ctl/fault/slow" in legs
    assert "ctl/shrink/rank-dead" in legs
    assert any(l.startswith("ctl/evict/straggler-lateness") for l in legs)

    counts = rep.decision_counts
    assert counts.get("shrink", 0) >= 1 and counts.get("evict", 0) >= 1


# ---------------------------------------------------------------------------
# Post-shrink trace audit
# ---------------------------------------------------------------------------


def test_post_shrink_audit_matches_on_resized_mesh(hvd):
    reports = audit_standard_configs(("serving_decode_resized",))
    rep = reports["serving_decode_resized"]
    assert rep.ok(), rep.render()
    s = rep.summary
    # One activation psum per row-parallel closure: attn_wo + mlp_down
    # per layer, all matched against the plan on the resized mesh.
    assert s["matched_ops"] == s["expected_ops"] == 2 * CFG.num_layers
    assert s["unaccounted_ops"] == 0 and s["missing_ops"] == 0
    assert any("resized decode mesh" in n for n in rep.expected.notes)
