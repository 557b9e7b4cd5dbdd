"""Disaggregated serving fleet (PR 20): parity, router, policy, chaos.

The tentpole gate: a 1-prefill + 1-decode fleet streaming KV pages over
the rendezvous plane produces decode streams BITWISE equal to a
colocated engine on the same requests (f32 wire tier + per-slot logits
independence).  Around it: the ``handoff`` slot lifecycle, the fleet
router's hint/affinity/spill/least-loaded precedence, the add-only
fleet policy + scaler (grow under live traffic, queued-request
migration), the dead-prefill-worker local fallback with zero leaked
pages, and the fleet load-generator shapes' determinism contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import (ContinuousBatchScheduler, DecodeWorker,
                                 FleetPolicy, FleetPolicyConfig,
                                 FleetRouter, FleetSample, LoadSpec,
                                 PrefillWorker, Request, ServingEngine,
                                 ServingFleet, fleet_spec, generate)
from horovod_tpu.serving.policy import Decision
from horovod_tpu.run.http_kv import KVClient, RendezvousServer
from horovod_tpu.run.secret import make_secret_key
from horovod_tpu.timeline.metrics import render_prometheus

CFG = LLAMA_SERVE


def mesh_1d(n):
    return Mesh(np.asarray(jax.devices()[:n], dtype=object).reshape(n),
                ("tp",))


@pytest.fixture(scope="module")
def base_params():
    model = LlamaLM(CFG, dtype=jnp.float32)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


@pytest.fixture()
def kv_plane():
    secret = make_secret_key()
    srv = RendezvousServer(secret, host="127.0.0.1")
    try:
        yield KVClient("127.0.0.1", srv.port, secret)
    finally:
        srv.stop()


def _engine(params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 256)
    kw.setdefault("prefetch_depth", 1)
    kw.setdefault("prefill_chunk", 0)
    kw.setdefault("spec_decode", False)
    kw.setdefault("kv_compress", False)
    kw.setdefault("prefix_cache", False)
    return ServingEngine(CFG, params, mesh=mesh_1d(1), **kw)


# ---------------------------------------------------------------------------
# Tentpole: disaggregated decode streams == colocated, bitwise
# ---------------------------------------------------------------------------


def test_disaggregated_streams_bitwise_equal_colocated(base_params,
                                                       kv_plane):
    """1 prefill worker + 1 decode worker vs one colocated engine on
    identical request streams: every request's emitted tokens must be
    bit-for-bit equal (f32 wire tier is bitwise; per-slot decode
    logits are independent of batch composition)."""
    spec = LoadSpec(num_requests=10, rate_rps=50.0,
                    prompt_lens=(8, 13, 21), output_lens=(6, 9), seed=3)
    reqs_base = generate(spec)
    colo = _engine(base_params, max_len=64)
    rep = colo.serve(reqs_base)
    assert rep.completed == 10
    base_tokens = {r.rid: list(r.tokens) for r in reqs_base}

    reqs_fleet = generate(spec)
    fleet = ServingFleet(
        [PrefillWorker("p0", CFG, base_params, kv_plane, page_size=8)],
        [DecodeWorker("decode0", _engine(base_params, max_len=64),
                      kv_plane)],
        kv_plane)
    frep = fleet.serve(reqs_fleet)
    assert frep.completed == 10
    # Every handoff actually streamed over the KV plane.
    assert frep.handoffs_streamed == 10 and frep.handoffs_local == 0
    assert frep.kv_bytes_out > 0 and frep.kv_bytes_in == frep.kv_bytes_out
    assert {r.rid: list(r.tokens) for r in reqs_fleet} == base_tokens
    # Drain-time leak gate on the decode pool.
    assert frep.leaked_pages == {"decode0": 0}
    assert frep.refcounts_balanced


def test_handoff_state_gauge_and_decode_exclusion(base_params):
    """A handoff slot is occupied but not decodable: it shows in the
    slot-state gauge family under ``state="handoff"`` and is excluded
    from the engine's decode batch until the import lands."""
    eng = _engine(base_params)
    sched = eng.scheduler
    req = Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                  max_new_tokens=4)
    sched.submit(req)
    [(slot, r)] = sched.admit(0.0)
    sched.note_handoff(r)
    assert r.state == "handoff"
    assert sched.handoff_slots == [slot]
    assert eng._decode_slots() == []
    text = render_prometheus()
    assert 'horovod_serving_slot_states{state="handoff"} 1' in text
    assert 'horovod_serving_slot_states{state="active"} 0' in text
    # note_prefill completes the transition into the decode batch.
    sched.note_prefill(r, 0.1)
    assert eng._decode_slots() == [slot]
    assert 'state="handoff"} 0' in render_prometheus()


# ---------------------------------------------------------------------------
# Fleet router
# ---------------------------------------------------------------------------


def _sched(slots=4):
    return ContinuousBatchScheduler(slots)


def _req(rid, prompt, hint=None):
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=4, engine_hint=hint)


def test_router_hint_wins_and_bounds_checked():
    r = FleetRouter(affinity=True)
    r.register("e0", _sched())
    r.register("e1", _sched())
    assert r.route(_req(0, [1, 2, 3], hint=1)) == ("e1", "hint")
    assert r.route(_req(1, [1, 2, 3], hint=0)) == ("e0", "hint")
    # Out-of-range hint (engine not commissioned yet) falls through to
    # affinity instead of crashing.
    name, reason = r.route(_req(2, [1, 2, 3], hint=7))
    assert reason == "affinity" and name in ("e0", "e1")


def test_router_affinity_is_stable_and_spills_under_overload():
    r = FleetRouter(affinity=True, spill_factor=2.0)
    s0, s1 = _sched(), _sched()
    r.register("e0", s0)
    r.register("e1", s1)
    prompt = [5, 6, 7, 8]
    first, reason = r.route(_req(0, prompt))
    assert reason == "affinity"
    # Same prefix -> same engine, every time.
    for rid in range(1, 4):
        assert r.route(_req(rid, prompt)) == (first, "affinity")
    # Overload the affinity target far beyond the sibling: locality
    # loses to the queue and the request spills to the least loaded.
    target = s0 if first == "e0" else s1
    for i in range(12):
        target.submit(_req(100 + i, [9] * 4))
    name, reason = r.route(_req(200, prompt))
    assert reason == "spill" and name != first


def test_router_least_loaded_when_affinity_off():
    r = FleetRouter(affinity=False)
    s0, s1 = _sched(), _sched()
    r.register("e0", s0)
    r.register("e1", s1)
    s0.submit(_req(0, [1, 2]))
    assert r.route(_req(1, [1, 2])) == ("e1", "least-loaded")
    s1.submit(_req(2, [1, 2]))
    s1.submit(_req(3, [1, 2]))
    assert r.route(_req(4, [1, 2])) == ("e0", "least-loaded")
    # Registration order breaks ties deterministically.
    r2 = FleetRouter(affinity=False)
    r2.register("a", _sched())
    r2.register("b", _sched())
    assert r2.route(_req(5, [1, 2]))[0] == "a"


def test_router_env_affinity_default(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLEET_AFFINITY", "0")
    assert FleetRouter().affinity is False
    monkeypatch.delenv("HOROVOD_FLEET_AFFINITY")
    assert FleetRouter().affinity is True


# ---------------------------------------------------------------------------
# Fleet policy + scaler
# ---------------------------------------------------------------------------


def test_fleet_policy_hysteresis_cooldown_and_cap():
    cfg = FleetPolicyConfig(queue_high=8, ttft_slo_s=0.5, hysteresis=2,
                            cooldown_s=1.0, max_engines=3)
    pol = FleetPolicy(cfg)

    def s(now, queue=0, p99=None, engines=1):
        return FleetSample(now_s=now, queue_depth=queue, ttft_p99_s=p99,
                           occupancy=0.5, engines=engines)

    # One breach sample holds (hysteresis=2); the second adds.
    assert pol.decide(s(0.0, queue=10)).is_hold
    d = pol.decide(s(0.1, queue=10))
    assert d.action == "add-engine" and d.target_size == 2
    pol.mark_applied(d, 0.1)
    # Cooldown: immediate re-breach holds until 1.0s has elapsed.
    assert pol.decide(s(0.2, queue=10)).is_hold
    assert pol.decide(s(0.3, queue=10)).is_hold
    assert pol.decide(s(1.2, queue=10)).action == "add-engine"
    # TTFT breach counts like queue breach.
    pol2 = FleetPolicy(cfg)
    pol2.decide(s(0.0, p99=0.9))
    assert pol2.decide(s(0.1, p99=0.9)).action == "add-engine"
    # A healthy sample resets the streak.
    pol3 = FleetPolicy(cfg)
    pol3.decide(s(0.0, queue=10))
    pol3.decide(s(0.1, queue=0))
    assert pol3.decide(s(0.2, queue=10)).is_hold
    # max_engines caps growth.
    pol4 = FleetPolicy(cfg)
    pol4.decide(s(0.0, queue=10, engines=3))
    assert pol4.decide(s(0.1, queue=10, engines=3)).is_hold


def test_fleet_policy_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLEET_QUEUE_HIGH", "3")
    monkeypatch.setenv("HOROVOD_FLEET_TTFT_SLO_S", "0.25")
    monkeypatch.setenv("HOROVOD_FLEET_HYSTERESIS", "5")
    monkeypatch.setenv("HOROVOD_FLEET_COOLDOWN_S", "2.5")
    monkeypatch.setenv("HOROVOD_FLEET_MAX_ENGINES", "6")
    monkeypatch.setenv("HOROVOD_FLEET_INTERVAL_S", "0.125")
    cfg = FleetPolicyConfig.from_env()
    assert (cfg.queue_high, cfg.ttft_slo_s, cfg.hysteresis,
            cfg.cooldown_s, cfg.max_engines, cfg.interval_s) == \
        (3, 0.25, 5, 2.5, 6, 0.125)


def test_fleet_scaler_grows_under_surge(base_params, kv_plane):
    """Grow-by-adding-capacity under live traffic: a sustained queue
    breach commissions a second decode engine mid-run, migrates queued
    requests to it, and both pools drain leak-free."""
    spec = fleet_spec(num_requests=24, rate_rps=80.0, seed=1)
    reqs = generate(spec)
    pol = FleetPolicy(FleetPolicyConfig(
        interval_s=0.01, queue_high=4, hysteresis=2, cooldown_s=0.5,
        max_engines=2))
    fleet = ServingFleet(
        [PrefillWorker("p0", CFG, base_params, kv_plane, page_size=8)],
        [DecodeWorker("decode0", _engine(base_params), kv_plane)],
        kv_plane, scaler_policy=pol,
        engine_factory=lambda: _engine(base_params))
    frep = fleet.serve(reqs)
    assert frep.completed == 24
    assert frep.engines == 2            # the scaler grew the fleet
    assert frep.migrated > 0            # queued work re-homed
    assert fleet.scaler.decisions       # audit trail of the loop
    adds = [d for d in fleet.scaler.decisions
            if d["action"] == "add-engine"]
    assert len(adds) == 1 and adds[0]["reason"] == "fleet-slo-breach"
    assert frep.leaked_pages == {"decode0": 0, "decode1": 0}
    assert frep.refcounts_balanced
    assert frep.per_engine_completed["decode1"] > 0
    text = render_prometheus()
    assert "horovod_fleet_migrated_total" in text
    assert "horovod_fleet_engines 2" in text


@pytest.mark.parametrize("prefill_worker", ["killed", "none"])
def test_dead_prefill_worker_falls_back_local_zero_leaks(base_params,
                                                         kv_plane,
                                                         prefill_worker):
    """Killing the only prefill worker mid-run reaps its un-imported
    KV objects; affected requests re-prefill LOCALLY on the decode
    engine (all of them, where the fleet never had a worker) and the run
    completes with zero leaked pages.  A local prefill is the engine's
    one join: the streams are ``serve``'s, bitwise, and every first
    token it computed was left on the chip."""
    spec = LoadSpec(num_requests=16, rate_rps=60.0, prompt_lens=(8, 16),
                    output_lens=(6, 10), seed=5)
    want = generate(spec)
    assert _engine(base_params).serve(want).completed == 16
    reqs = generate(spec)
    killed = prefill_worker == "killed"
    worker = DecodeWorker("decode0", _engine(base_params), kv_plane)
    fleet = ServingFleet(
        [PrefillWorker("p0", CFG, base_params, kv_plane, page_size=8)]
        if killed else [], [worker], kv_plane)
    frep = fleet.serve(reqs, kill_prefill_at_step=2 if killed else None)
    assert frep.completed == 16
    # The kill forced at least one local fallback; nothing was lost.
    assert frep.handoffs_local >= (1 if killed else 16)
    assert frep.handoffs_streamed + frep.handoffs_local == 16
    assert frep.leaked_pages == {"decode0": 0}
    assert frep.refcounts_balanced
    assert not killed or not fleet.prefill_workers[0].alive
    assert {r.rid: list(r.tokens) for r in reqs} \
        == {r.rid: list(r.tokens) for r in want}
    assert worker.st["first_tokens_deferred"] == worker.st["prefills"] \
        == frep.handoffs_local
    assert worker.st["in_flight"] is None and not worker.st["joins"]


# ---------------------------------------------------------------------------
# Fleet load-generator shapes
# ---------------------------------------------------------------------------


def test_loadgen_fleet_defaults_byte_identical():
    """rate_double_at_s=0 and empty engine_skew must not perturb the
    stream: arrivals, prompts and hints match the PR 16 generator
    byte for byte."""
    base = LoadSpec(num_requests=24, rate_rps=20.0, seed=7)
    shaped = LoadSpec(num_requests=24, rate_rps=20.0, seed=7,
                      rate_double_at_s=0.0, engine_skew=())
    a, b = generate(base), generate(shaped)
    for ra, rb in zip(a, b):
        assert ra.arrival_s == rb.arrival_s
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new_tokens == rb.max_new_tokens
        assert ra.engine_hint is None and rb.engine_hint is None


def test_loadgen_rate_doubling_halves_gaps_post_boundary():
    """The doubling is a pure post-draw transform: pre-boundary
    arrivals are untouched, post-boundary gaps are exactly half the
    undoubled stream's."""
    plain = generate(LoadSpec(num_requests=40, rate_rps=10.0, seed=2))
    doubled = generate(LoadSpec(num_requests=40, rate_rps=10.0, seed=2,
                                rate_double_at_s=1.0))
    # Determinism: same spec twice -> identical streams.
    again = generate(LoadSpec(num_requests=40, rate_rps=10.0, seed=2,
                              rate_double_at_s=1.0))
    assert [r.arrival_s for r in doubled] == [r.arrival_s for r in again]
    gaps_p = np.diff([0.0] + [r.arrival_s for r in plain])
    gaps_d = np.diff([0.0] + [r.arrival_s for r in doubled])
    t = 0.0
    crossed = False
    for gp, gd in zip(gaps_p, gaps_d):
        if t >= 1.0:
            crossed = True
            assert abs(gd - gp / 2) < 1e-12
        else:
            assert gd == gp
        t += gd
    assert crossed  # the run actually reached the boundary
    # Prompts and outputs are untouched by the gap transform.
    for rp, rd in zip(plain, doubled):
        assert np.array_equal(rp.prompt, rd.prompt)
        assert rp.max_new_tokens == rd.max_new_tokens


def test_loadgen_engine_skew_deterministic_and_weighted():
    spec = LoadSpec(num_requests=400, rate_rps=50.0, seed=4,
                    engine_skew=(3.0, 1.0))
    a, b = generate(spec), generate(spec)
    assert [r.engine_hint for r in a] == [r.engine_hint for r in b]
    hints = np.asarray([r.engine_hint for r in a])
    assert set(hints) == {0, 1}
    share0 = float((hints == 0).mean())
    assert 0.65 < share0 < 0.85  # ~3:1 skew
    # The FIRST request's gap/prompt draws precede its hint draw, so
    # they match the unskewed spec exactly (later requests diverge
    # because the hint draw advances the shared stream -- by design,
    # one RandomState in one fixed order).
    plain = generate(LoadSpec(num_requests=400, rate_rps=50.0, seed=4))
    assert np.array_equal(plain[0].prompt, a[0].prompt)
    assert plain[0].arrival_s == a[0].arrival_s


def test_loadgen_shape_validation():
    with pytest.raises(ValueError, match="rate_double_at_s"):
        LoadSpec(rate_double_at_s=-1.0)
    with pytest.raises(ValueError, match="engine_skew"):
        LoadSpec(engine_skew=(1.0, -2.0))
    with pytest.raises(ValueError, match="positive mass"):
        LoadSpec(engine_skew=(0.0, 0.0))
    s = fleet_spec()
    assert s.rate_double_at_s > 0 and len(s.engine_skew) == 2
