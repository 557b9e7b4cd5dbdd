"""Silent-data-corruption defense (PR 15).

Covers the in-step numeric guard (``HOROVOD_GUARD``: screen psum +
skip-don't-poison policy, bitwise-untouched params/EF residuals on a
skipped step), the snapshot/rollback ledger (``HOROVOD_SNAPSHOT_STEPS``,
``JaxState.rollback``), the cross-rank corruption tripwire
(``HOROVOD_DESYNC_CHECK_STEPS``, majority-vote rank attribution,
quarantine via re-init on the survivor set), the serving engine's
nonfinite-logit quarantine (re-prefill instead of streaming garbage,
no KV page leak), and the canonical-repr checksum encoding that replaced
pickle in ``core/desync.py``.

Acceptance gates (ISSUE 15): a clean 30-step run activates the guard
zero times; a ``nan@`` chaos step is skipped with params and EF
residuals bitwise unchanged; a ``bitflip@`` is attributed to the victim
rank within one tripwire interval; the rollback drill converges to
<= 1.25x loss parity against the uninterrupted run.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hv
from horovod_tpu import elastic
from horovod_tpu.core import desync, guard
from horovod_tpu.core.exceptions import (CorruptRankError,
                                         SustainedAnomalyError)
from horovod_tpu.elastic import chaos
from horovod_tpu.timeline import metrics as tm


@pytest.fixture(autouse=True)
def _clean_guard():
    """Every test starts and ends with a fresh policy and no chaos."""
    guard.reset()
    chaos.reset()
    yield
    guard.reset()
    chaos.reset()


def _make_problem(seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(16, 4).astype(np.float32)
    x = rng.randn(64, 16).astype(np.float32)
    y = x @ w_true
    params = {"w1": rng.randn(16, 32).astype(np.float32) * 0.3,
              "b1": np.zeros((32,), np.float32),
              "w2": rng.randn(32, 4).astype(np.float32) * 0.3,
              "b2": np.zeros((4,), np.float32)}

    def loss_fn(p, batch):
        bx, by = batch
        h = jnp.tanh(bx @ p["w1"] + p["b1"])
        pred = h @ p["w2"] + p["b2"]
        return jnp.mean((pred - by) ** 2)

    return params, loss_fn, (x, y)


def _reinit(hvd_mod, monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    chaos.reset()  # clear the checked-env latch so init() re-reads it
    hvd_mod.shutdown()
    hvd_mod.init()
    guard.reset()


def _tree_bytes(tree):
    return [np.asarray(l).tobytes() for l in jax.tree.leaves(tree)]


# ---------------------------------------------------------------------------
# Mode resolution + policy unit behavior
# ---------------------------------------------------------------------------

def test_resolve_mode_forced_and_invalid(hvd):
    from horovod_tpu.core.state import global_state
    cfg = global_state().config

    class Cfg:
        guard = "1"
        check_desync = False
        desync_check_steps = 0
        snapshot_steps = 0
    assert guard.resolve_mode(Cfg()) is True
    Cfg.guard = "off"
    assert guard.resolve_mode(Cfg()) is False
    Cfg.guard = "banana"
    with pytest.raises(ValueError, match="HOROVOD_GUARD"):
        guard.resolve_mode(Cfg())
    # Repo default config: auto, nothing armed, no injector -> off.
    assert cfg.guard == "auto"
    assert guard.resolve_mode(cfg) is False


def test_auto_mode_arms_on_chaos_and_defense_knobs(hvd):
    class Cfg:
        guard = "auto"
        check_desync = False
        desync_check_steps = 0
        snapshot_steps = 0
    assert guard.resolve_mode(Cfg()) is False
    Cfg.snapshot_steps = 5
    assert guard.resolve_mode(Cfg()) is True
    Cfg.snapshot_steps = 0
    Cfg.desync_check_steps = 2
    assert guard.resolve_mode(Cfg()) is True
    Cfg.desync_check_steps = 0
    # Latency chaos must NOT arm the screen -- a slow rank corrupts no
    # numerics, and the straggler drill's attribution expects a step
    # without the guard leg's host sync.
    chaos.install("slow@step=99,rank=0,secs=0.1", rank=0, size=1)
    assert guard.resolve_mode(Cfg()) is False
    chaos.reset()
    chaos.install("nan@step=99", rank=0, size=1)
    assert guard.resolve_mode(Cfg()) is True


def test_guard_policy_streak_and_metrics(hvd):
    p = guard.GuardPolicy(streak_limit=3)
    skipped0 = tm.registry().counter("horovod_guard_skipped_total").value
    assert p.observe([0.0, 1.5, 0.0]) == 0
    assert p.streak == 0 and p.steps == 1
    assert p.observe([4.0, np.nan, 1.0]) == 1
    assert p.streak == 1
    assert tm.registry().gauge("horovod_guard_grad_norm").value == -1.0
    # A good step resets the streak; a [k, 3] stack is consumed row-wise.
    assert p.observe(np.array([[0.0, 2.0, 0.0], [1.0, np.inf, 1.0]])) == 1
    assert p.streak == 1 and p.steps == 4
    with pytest.raises(SustainedAnomalyError) as ei:
        p.observe(np.array([[1.0, np.nan, 1.0], [1.0, np.nan, 1.0]]))
    assert ei.value.streak == 3
    assert tm.registry().counter(
        "horovod_guard_skipped_total").value - skipped0 == 4


# ---------------------------------------------------------------------------
# Acceptance gate: clean run activates the guard zero times
# ---------------------------------------------------------------------------

def test_clean_run_zero_skips_and_aligned_metrics(hvd, monkeypatch):
    _reinit(hvd, monkeypatch, HOROVOD_GUARD="1")
    params0, loss_fn, data = _make_problem()
    opt = optax.adam(0.05)
    step = hvd.make_train_step(loss_fn, opt)
    assert step._meta["guard"] is True
    p = hvd.replicate(params0)
    st = opt.init(p)
    batch = hvd.shard_batch(data)
    steps0 = tm.registry().counter("horovod_guard_steps_total").value
    skip0 = tm.registry().counter("horovod_guard_skipped_total").value
    losses = []
    for _ in range(30):
        p, st, loss = step(p, st, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # actually trained
    assert tm.registry().counter(
        "horovod_guard_steps_total").value - steps0 == 30
    assert tm.registry().counter(
        "horovod_guard_skipped_total").value - skip0 == 0
    assert guard.policy().streak == 0
    assert tm.registry().gauge("horovod_guard_grad_norm").value > 0


def test_guard_off_step_has_no_guard_output(hvd):
    params0, loss_fn, data = _make_problem()
    opt = optax.adam(0.05)
    step = hvd.make_train_step(loss_fn, opt)
    assert step._meta["guard"] is False
    assert type(step).__name__ != "_GuardedStep"
    p = hvd.replicate(params0)
    st = opt.init(p)
    out = step(p, st, hvd.shard_batch(data))
    assert len(out) == 3  # (params, opt_state, loss), nothing appended


def test_scan_loop_guard_consumes_stacked_rows(hvd, monkeypatch):
    _reinit(hvd, monkeypatch, HOROVOD_GUARD="1")
    params0, loss_fn, data = _make_problem()
    opt = optax.adam(0.05)
    loop = hvd.make_train_loop(loss_fn, opt, steps_per_execution=4)
    p = hvd.replicate(params0)
    st = opt.init(p)
    batches = hvd.shard_steps(jax.tree.map(
        lambda a: jnp.stack([jnp.asarray(a)] * 4), data))
    steps0 = tm.registry().counter("horovod_guard_steps_total").value
    p, st, losses = loop(p, st, batches)
    assert losses.shape == (4,)
    assert tm.registry().counter(
        "horovod_guard_steps_total").value - steps0 == 4


# ---------------------------------------------------------------------------
# Acceptance gate: nan@ chaos -> exactly the poisoned step is skipped,
# params and EF residuals bitwise unchanged
# ---------------------------------------------------------------------------

def test_nan_chaos_skips_poisoned_step_bitwise(hvd, monkeypatch):
    _reinit(hvd, monkeypatch, HOROVOD_GUARD="auto",
            HOROVOD_CHAOS="nan@step=3,rank=0")
    inj = chaos.injector()
    assert inj is not None  # installed by init; also arms guard auto mode
    params0, loss_fn, data = _make_problem()
    opt = hv.DistributedOptimizer(optax.adam(0.05), compression="topk:0.25")
    step = hvd.make_train_step(loss_fn, opt)
    assert step._meta["guard"] is True  # auto armed by the injector
    p = hvd.replicate(params0)
    st = opt.init(p)
    clean_batch = hvd.shard_batch(data)
    skip0 = tm.registry().counter("horovod_guard_skipped_total").value
    skipped_at = []
    for i in range(1, 7):
        inj.on_step(i)
        victim = chaos.consume_nan_poison()
        if victim is not None:
            assert victim == 0
            batch = hvd.shard_batch(chaos.poison_batch(
                tuple(jnp.asarray(a) for a in data)))
        else:
            batch = clean_batch
        before_p = _tree_bytes(p)
        before_st = _tree_bytes(st)
        p, st, loss = step(p, st, batch)
        if victim is not None:
            skipped_at.append(i)
            # Skip, don't poison: params AND the EF residual carry are
            # bitwise identical to the pre-step values.
            assert _tree_bytes(p) == before_p
            assert _tree_bytes(st) == before_st
            assert guard.policy().streak == 1
        else:
            assert guard.policy().streak == 0
    assert skipped_at == [3]  # exactly the poisoned step, once
    assert tm.registry().counter(
        "horovod_guard_skipped_total").value - skip0 == 1
    assert float(loss) == float(loss)  # post-recovery loss is finite


# ---------------------------------------------------------------------------
# Acceptance gate: bitflip@ -> tripwire attribution within one interval
# ---------------------------------------------------------------------------

def test_bitflip_tripwire_attributes_victim_rank(hvd, monkeypatch,
                                                 n_devices):
    _reinit(hvd, monkeypatch, HOROVOD_DESYNC_CHECK_STEPS="2")
    victim = n_devices - 1
    params0, loss_fn, data = _make_problem()
    p = hvd.replicate(params0)
    state = elastic.JaxState(params=p, batch=0)  # commit 0: clean check
    checks0 = tm.registry().counter(
        "horovod_guard_tripwire_checks_total").value
    state.commit()  # commit 1: off-cadence, no check
    # A single flipped mantissa bit on ONE device's replica: finite,
    # invisible to the numeric guard, undetectable without the tripwire.
    state.params = desync.corrupt_replica(state.params, victim)
    with pytest.raises(CorruptRankError) as ei:
        state.commit()  # commit 2: tripwire samples -- one interval later
    assert ei.value.ranks == [victim]
    assert tm.registry().counter(
        "horovod_guard_tripwire_checks_total").value - checks0 >= 1
    assert tm.registry().counter(
        "horovod_guard_tripwire_trips_total").value >= 1
    # The check ran BEFORE the snapshot refresh: the last committed copy
    # is still the converged one, so quarantine + restore recovers on
    # the survivor set without the victim.
    survivors = [d for i, d in enumerate(jax.devices()) if i != victim][:4]
    hvd.shutdown()
    hvd.init(devices=survivors)
    state.restore()
    for leaf, ref in zip(jax.tree.leaves(state.params),
                         jax.tree.leaves(hv.replicate(params0))):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))


def test_tripwire_clean_tree_is_silent(hvd):
    p = hvd.replicate({"w": jnp.arange(16.0)})
    assert desync.tripwire_check(p, name="params") == []


def test_tripwire_skips_sharded_trees(hvd, monkeypatch):
    """ZeRO arenas differ across ranks by construction; the commit-path
    tripwire must not attribute that as corruption."""
    _reinit(hvd, monkeypatch, HOROVOD_DESYNC_CHECK_STEPS="1")
    params0, loss_fn, _ = _make_problem()
    p = hvd.replicate(params0)
    st = hvd.zero_init(optax.adam(0.05), p)
    state = elastic.JaxState(params=p, opt_state=st, batch=0)
    state.commit()  # every-commit cadence: raises if the arena is checked


# ---------------------------------------------------------------------------
# Acceptance gate: rollback drill converges to <= 1.25x parity
# ---------------------------------------------------------------------------

def test_sustained_anomaly_rollback_loss_parity(hvd, monkeypatch):
    _STEPS, _COMMIT_EVERY = 30, 3
    params0, loss_fn, data = _make_problem()

    def _build(hvd_mod):
        # DistributedOptimizer keeps every device in lockstep (grad
        # allreduce), so the host snapshot (device_get = device 0's
        # copy) IS the collective state and the rolled-back replay
        # retraces the reference run.  A bare optax optimizer follows
        # Horovod semantics -- no sync, per-device drift -- and the
        # ledger would capture only one replica's trajectory.
        opt = hvd_mod.DistributedOptimizer(optax.adam(0.05))
        p = hvd_mod.replicate(params0)
        st = opt.init(p)
        step = hvd_mod.make_train_step(loss_fn, opt)
        return p, st, step, hvd_mod.shard_batch(data)

    # Uninterrupted reference run.
    p, st, step, batch = _build(hvd)
    for _ in range(_STEPS):
        p, st, loss = step(p, st, batch)
    base_loss = float(loss)

    # Guarded run: a sustained anomaly (poisoned input shard) from step
    # 11 trips the streak limit; the ledger rolls back to the last good
    # snapshot and the replay -- with the shard healed -- converges.
    _reinit(hvd, monkeypatch, HOROVOD_GUARD="1", HOROVOD_GUARD_STREAK="3",
            HOROVOD_SNAPSHOT_STEPS="2")
    p, st, step, batch = _build(hvd)
    poisoned = hvd.shard_batch(chaos.poison_batch(
        tuple(jnp.asarray(a) for a in data)))
    state = elastic.JaxState(params=p, opt_state=st, batch=0)
    rb0 = tm.registry().counter("horovod_guard_rollbacks_total").value
    wedged = True
    rolled_back = False
    while state.batch < _STEPS:
        nxt = state.batch + 1
        try:
            use = poisoned if (wedged and nxt >= 11) else batch
            state.params, state.opt_state, loss = step(
                state.params, state.opt_state, use)
            state.batch = nxt
            if state.batch % _COMMIT_EVERY == 0:
                state.commit()
        except SustainedAnomalyError:
            assert not rolled_back, "anomaly survived the rollback"
            rolled_back = True
            wedged = False  # the rolled-back replay reads a healed shard
            # The streak dates the anomaly: it began at step 11, so the
            # last commit KNOWN good is the one at step 9 (commit #3).
            # Roll back past the whole window -- the newest ledger entry
            # alone may sit inside it.
            report = state.rollback(before_commit=(11 - 1) // _COMMIT_EVERY)
            assert report is not None and report["commit"] == 2
            # Sampler-offset awareness: the step counter rewound WITH
            # the params, so the replay re-covers the skipped ground
            # (steps 7..30 re-run on healed data -- no lost updates).
            assert state.batch == 6

    assert rolled_back, "sustained anomaly never tripped the streak"
    assert tm.registry().counter(
        "horovod_guard_rollbacks_total").value - rb0 == 1
    ratio = float(loss) / base_loss
    assert 0 < ratio <= 1.25, (float(loss), base_loss)


def test_ledger_rollback_drops_poisoned_entries(hvd, monkeypatch):
    _reinit(hvd, monkeypatch, HOROVOD_SNAPSHOT_STEPS="2")
    p = hvd.replicate({"w": jnp.arange(8.0)})
    state = elastic.JaxState(params=p, batch=0)
    for i in range(1, 7):
        state.params = jax.tree.map(lambda a: a + 1.0, state.params)
        state.batch = i
        state.commit()
    assert [e["commit"] for e in state._ledger] == [0, 2, 4, 6]
    report = state.rollback(before_commit=5)
    assert report["commit"] == 4
    assert state.batch == 4  # scalars rewound with the trees
    np.testing.assert_array_equal(
        np.asarray(state.params["w"]), np.arange(8.0) + 4.0)
    # Entries newer than the poison horizon were dropped, older kept.
    assert [e["commit"] for e in state._ledger] == [0, 2, 4]
    # No qualifying entry -> None (caller falls back to restore()).
    assert state.rollback(before_commit=-1) is None


def test_run_loop_rollback_helper_prefers_ledger(hvd, monkeypatch):
    from horovod_tpu.elastic.run_loop import _rollback_or_restore
    _reinit(hvd, monkeypatch, HOROVOD_SNAPSHOT_STEPS="1")
    state = elastic.JaxState(params=hvd.replicate({"w": jnp.zeros(4)}),
                             batch=0)
    state.params = jax.tree.map(lambda a: a + 7.0, state.params)
    _rollback_or_restore(state)
    assert not np.asarray(state.params["w"]).any()
    # ObjectState has no ledger: degrades to plain restore.
    s = elastic.ObjectState(x=5)
    s.x = 9
    _rollback_or_restore(s)
    assert s.x == 5


# ---------------------------------------------------------------------------
# Serving: nonfinite logits are quarantined, never streamed
# ---------------------------------------------------------------------------

def test_serving_nonfinite_logits_reprefill_no_page_leak(hvd):
    from jax.sharding import Mesh
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
    from horovod_tpu.serving import LoadSpec, ServingEngine, generate
    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    mesh = Mesh(np.asarray(jax.devices()[:1], dtype=object).reshape(1),
                ("tp",))
    eng = ServingEngine(cfg, params, mesh=mesh, slots=2, page_size=8,
                        max_len=64)
    total_pages = eng.cache.free_pages

    real_step = eng.step
    calls = {"n": 0}

    def poisoned_step(params, k, v, tokens, positions, table, active,
                      *rest):
        calls["n"] += 1
        if calls["n"] in (3, 4):  # two poisoned decode rounds
            # A resident row of every live slot goes bad: the logits
            # the step computes, samples from and screens are NaN.
            pages = np.asarray(table)[np.asarray(active), 0]
            k = k.at[:, pages, 0].set(jnp.nan)
            v = v.at[:, pages, 0].set(jnp.nan)
        return real_step(params, k, v, tokens, positions, table, active,
                         *rest)

    eng.step = poisoned_step
    reprefills0 = tm.registry().counter(
        "horovod_guard_serving_reprefills_total").value
    spec = LoadSpec(num_requests=6, rate_rps=100.0, prompt_lens=(4, 8),
                    output_lens=(3, 5), vocab_size=cfg.vocab_size, seed=2)
    report = eng.serve(generate(spec))
    # Every request still completes: the quarantined rounds cost time,
    # not correctness -- and no token from a poisoned distribution was
    # streamed (greedy over all-NaN logits would emit token 0 garbage).
    assert report.completed == 6 and report.rejected == 0
    assert tm.registry().counter(
        "horovod_guard_serving_reprefills_total").value - reprefills0 >= 1
    assert calls["n"] == report.decode_steps > 4
    # No page leak: every reserved page returned to the free pool.
    assert eng.cache.free_pages == total_pages
    assert all(int(x) == 0 for x in eng.cache.lengths)


# ---------------------------------------------------------------------------
# Canonical-repr checksum encoding (pickle removal regression)
# ---------------------------------------------------------------------------

def test_canonical_bytes_is_order_and_type_canonical():
    enc = desync._canonical_bytes
    # Dict insertion order must not change the encoding (pickle's
    # failure mode: {'a':1,'b':2} and {'b':2,'a':1} pickled differently
    # on some protocols/orders, flagging false desyncs).
    assert enc({"a": 1, "b": 2}) == enc({"b": 2, "a": 1})
    assert enc({1, 2, 3}) == enc({3, 1, 2})
    # Type tags keep distinct values distinct.
    assert enc((1, 2)) != enc([1, 2])
    assert enc(1) != enc(1.0)
    assert enc(True) != enc(1)
    assert enc("1") != enc(b"1")
    assert enc(None) != enc("None")
    assert enc(0.0) != enc(-0.0)
    # Floats encode via repr: equal values encode equal.
    assert enc(0.1 + 0.2) == enc(0.30000000000000004)
    # Nesting recurses with tags.
    assert enc({"k": [1, (2, 3)]}) == enc({"k": [1, (2, 3)]})
    assert enc({"k": [1, (2, 3)]}) != enc({"k": [1, [2, 3]]})


def test_canonical_bytes_depth_cap_and_fallback():
    deep = []
    node = deep
    for _ in range(100):
        inner = []
        node.append(inner)
        node = inner
    with pytest.raises(TypeError, match="nests too deeply"):
        desync._canonical_bytes(deep)

    class Opaque:
        __slots__ = ()  # no __dict__: nothing value-like to encode
    with pytest.raises(TypeError):
        desync._canonical_bytes(Opaque())
    # _leaf_checksum survives both cases via the type-name fallback.
    assert isinstance(desync._leaf_checksum(Opaque()), int)
    # Objects WITH instance state encode by value, not by address.
    class Stateful:
        def __init__(self, v):
            self.v = v
    assert (desync._canonical_bytes(Stateful(7))
            == desync._canonical_bytes(Stateful(7)))
    assert (desync._canonical_bytes(Stateful(7))
            != desync._canonical_bytes(Stateful(8)))


def test_leaf_checksum_no_pickle_dependency():
    import inspect
    src = inspect.getsource(desync)
    assert "import pickle" not in src
    # Dict-order invariance end to end through the checksum.
    assert (desync._leaf_checksum({"a": 1, "b": 2})
            == desync._leaf_checksum({"b": 2, "a": 1}))
    assert (desync._leaf_checksum({"a": 1})
            != desync._leaf_checksum({"a": 2}))
