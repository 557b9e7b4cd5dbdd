"""Serving data plane: paged KV cache, TP decode parity, scheduler, audit.

The tentpole contract under test: incremental (KV-cached) decode matches
the full-context flax forward to float tolerance on meshes of 1 AND 8
virtual devices, with the decode step's activation collectives visible
to the observability stack (span-recorder legs), the cache layout
invariant across mesh sizes, and slot eviction/reuse leaving no stale
attention mass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.analysis.stepmodel import expected_exchange, meta_from_step
from horovod_tpu.analysis.trace_audit import audit_step
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.ops.attention import decode_attention
from horovod_tpu.serving import (CacheConfig, ContinuousBatchScheduler,
                                 LoadSpec, PagedKVCache, PrefixCache,
                                 Request, RequestPrefetcher, ServingEngine,
                                 TenantClass, build_decode_step,
                                 cache_sharding, generate, prefill_forward,
                                 prefix_spec, stack_adapters)
from horovod_tpu.serving.decode import no_round
from horovod_tpu.timeline import spans
from horovod_tpu.timeline.metrics import render_prometheus

CFG = LLAMA_SERVE


def mesh_1d(n):
    return Mesh(np.asarray(jax.devices()[:n], dtype=object).reshape(n),
                ("tp",))


@pytest.fixture(scope="module")
def base_params():
    model = LlamaLM(CFG, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))


def _make_cache(ndev, slots=4, page_size=8, max_len=64):
    mesh = mesh_1d(ndev)
    ccfg = CacheConfig(num_layers=CFG.num_layers,
                       num_kv_heads=CFG.num_kv_heads,
                       head_dim=CFG.head_dim, slots=slots,
                       page_size=page_size, max_len=max_len)
    return mesh, ccfg, PagedKVCache(ccfg, cache_sharding(mesh))


def _decode_sequence(params, step, cache, tokens, t0, T, slot=0):
    """Teacher-forced decode of tokens[t0:T] through the cached step."""
    out = []
    slots = cache.config.slots
    for i in range(t0, T):
        cache.reserve(slot, i + 1)
        tok = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[0, i])
        active = jnp.zeros((slots,), bool).at[slot].set(True)
        logits, cache.k, cache.v, _ = step(
            params, cache.k, cache.v, tok, cache.lengths_device(),
            cache.table_device(), active, no_round(slots))
        cache.lengths[slot] += 1
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


# ---------------------------------------------------------------------------
# Tentpole parity: incremental decode == full-context forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ndev", [1, 8])
def test_incremental_decode_matches_full_context(base_params, ndev):
    model, params = base_params
    spans.recorder().reset()
    T, t0 = 20, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0,
                                CFG.vocab_size)
    full = np.asarray(model.apply(params, tokens))

    mesh, ccfg, cache = _make_cache(ndev)
    logits_p, kl, vl = prefill_forward(params, CFG, tokens[:, :t0])
    np.testing.assert_allclose(np.asarray(logits_p[0]), full[0, :t0],
                               rtol=1e-4, atol=1e-4)
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot)
    got = _decode_sequence(params, step, cache, tokens, t0, T)
    np.testing.assert_allclose(got, full[0, t0:T], rtol=1e-4, atol=1e-4)

    # Acceptance: the decode step's activation collectives are visible
    # to the observability plane -- one span-recorder leg per
    # row-parallel closure, registered at trace time.
    legs = spans.recorder().legs
    for li in range(CFG.num_layers):
        assert f"serving_decode/layer{li}/attn_wo" in legs
        assert f"serving_decode/layer{li}/mlp_down" in legs


def test_cache_layout_invariant_across_mesh_sizes():
    layouts = []
    for ndev in (1, 2, 4, 8):
        _, ccfg, cache = _make_cache(ndev)
        assert cache.layout() == ccfg.layout()
        layouts.append(cache.layout())
    assert all(l == layouts[0] for l in layouts[1:])
    # Sharded pool global shape equals the declared layout regardless of
    # how many ranks split the row: one row a token, no head dim, a
    # shard holding its contiguous heads (here one of the eight).
    _, _, cache8 = _make_cache(8)
    assert list(cache8.k.shape) == layouts[0]["kv_shape"]
    assert layouts[0]["kv_shape"][3:] == [CFG.num_kv_heads * CFG.head_dim]
    assert cache8.k.sharding.shard_shape(cache8.k.shape)[3:] \
        == (CFG.head_dim,)


def test_slot_eviction_reuse_no_stale_attention_mass(base_params):
    model, params = base_params
    mesh, ccfg, cache = _make_cache(1)
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot)
    rng = np.random.RandomState(7)
    prompt_a = jnp.asarray(rng.randint(0, CFG.vocab_size, (1, 24)))
    prompt_b = jnp.asarray(rng.randint(0, CFG.vocab_size, (1, 8)))

    # Fill slot 0 with A (3 pages of history), decode a few tokens...
    _, kl, vl = prefill_forward(params, CFG, prompt_a)
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    _decode_sequence(params, step, cache,
                     jnp.concatenate([prompt_a, prompt_a[:, :4]], 1),
                     24, 28)
    # ...then evict and recycle the slot for the SHORTER prompt B.
    cache.free_slot(0)
    _, kl, vl = prefill_forward(params, CFG, prompt_b)
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    seq_b = jnp.concatenate([prompt_b, prompt_b[:, :6]], 1)
    got = _decode_sequence(params, step, cache, seq_b, 8, 14)

    # Bitwise identical to a fresh cache that never saw A: the masking
    # contract, not page zeroing, is what isolates recycled pages.
    _, _, fresh = _make_cache(1)
    _, kl, vl = prefill_forward(params, CFG, prompt_b)
    fresh.write_prefill(0, kl[:, 0], vl[:, 0])
    want = _decode_sequence(params, step, fresh, seq_b, 8, 14)
    np.testing.assert_array_equal(got, want)

    # And still parity-exact against the full-context forward.
    full = np.asarray(model.apply(params, seq_b))
    np.testing.assert_allclose(got, full[0, 8:14], rtol=1e-4, atol=1e-4)


def test_decode_attention_idle_rows_are_exactly_zero():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(3, 2, 1, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(3, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(3, 2, 16, 8).astype(np.float32))
    out = decode_attention(q, k, v, lengths=jnp.asarray([5, 0, 16]))
    assert np.abs(np.asarray(out[1])).max() == 0.0
    assert np.abs(np.asarray(out[0])).max() > 0.0


# ---------------------------------------------------------------------------
# Cache accounting
# ---------------------------------------------------------------------------


def test_paged_cache_accounting_and_exhaustion():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    assert cache.free_pages == ccfg.num_pages == 8
    # Scratch page sits past the allocatable pool.
    assert cache.k.shape[1] == ccfg.num_pages + 1
    assert ccfg.layout()["scratch_page"] == ccfg.num_pages

    cache.reserve(0, 9)  # 3 pages
    assert cache.free_pages == 5
    assert cache.can_admit(16) and not cache.can_admit(24)
    with pytest.raises(ValueError):
        cache.reserve(0, 17)  # > max_len
    cache.reserve(1, 16)  # 4 pages
    assert cache.free_pages == 1
    # Reserving is idempotent for already-covered lengths.
    cache.reserve(1, 12)
    assert cache.free_pages == 1
    # Defensive exhaustion path (the derived pool covers slots*pps, so
    # drain it white-box to simulate an overcommitted deployment).
    cache._free.clear()
    with pytest.raises(RuntimeError):
        cache.reserve(0, 16)
    cache.free_slot(1)
    assert cache.free_pages == 4
    cache.reserve(0, 16)
    assert cache.free_pages == 3


def test_write_prefill_sets_length_and_pages():
    ccfg = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    t = 6
    kl = jnp.arange(2 * t * 2 * 4, dtype=jnp.float32).reshape(2, t, 8)
    cache.write_prefill(1, kl, kl * 2)
    assert int(cache.lengths[1]) == t
    assert cache.free_pages == ccfg.num_pages - 2
    # Round-trip through the page table reproduces the token order.
    pages = cache.page_table[1][np.arange(t) // 4]
    offs = np.arange(t) % 4
    got = np.asarray(cache.k)[:, pages, offs]
    np.testing.assert_array_equal(got, np.asarray(kl))


# ---------------------------------------------------------------------------
# Scheduler + load generator
# ---------------------------------------------------------------------------


def _req(rid, plen=4, out=4, arrival=0.0):
    return Request(rid=rid, prompt=np.full((plen,), rid % 7, np.int32),
                   max_new_tokens=out, arrival_s=arrival)


def test_scheduler_fifo_admission_and_slot_recycling():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    sched = ContinuousBatchScheduler(2, PagedKVCache(ccfg))
    for i in range(4):
        sched.submit(_req(i))
    pairs = sched.admit(now_s=0.0)
    assert [(s, r.rid) for s, r in pairs] == [(0, 0), (1, 1)]
    assert sched.occupancy == 1.0 and len(sched.queue) == 2
    assert sched.admit(now_s=0.1) == []  # batch full
    freed = sched.release(0, now_s=0.2)
    assert freed.rid == 0 and freed.state == "done"
    pairs = sched.admit(now_s=0.3)
    assert [(s, r.rid) for s, r in pairs] == [(0, 2)]  # slot recycled


def test_scheduler_admission_gated_on_kv_pages():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=4,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)  # 16 pages
    sched = ContinuousBatchScheduler(4, cache)
    sched.submit(_req(0, plen=14))   # 15 tokens incl. headroom -> 4 pages
    sched.submit(_req(1, plen=14))
    for slot, req in sched.admit(0.0):
        cache.reserve(slot, req.prompt_len + 1)
    assert len(sched.active) == 2 and cache.free_pages == 8
    # Two slots are still free but the page pool is (simulated) dry:
    # FIFO head must block on can_admit, not grab a slot it can't fill.
    cache._free = cache._free[:2]
    sched.submit(_req(2, plen=14))
    assert sched.admit(0.1) == []
    assert len(sched.queue) == 1
    # Pages coming back (an eviction) unblocks the same head request.
    cache._free = list(range(8))
    admitted = sched.admit(0.2)
    assert [(s, r.rid) for s, r in admitted] == [(2, 2)]


def test_loadgen_deterministic_and_open_loop():
    spec = LoadSpec(num_requests=64, rate_rps=20.0, seed=5,
                    prompt_lens=(4, 8), output_lens=(2, 4),
                    num_adapters=3)
    a, b = generate(spec), generate(spec)
    assert all((x.prompt == y.prompt).all() and
               x.arrival_s == y.arrival_s and
               x.max_new_tokens == y.max_new_tokens and
               x.adapter_id == y.adapter_id for x, y in zip(a, b))
    assert [r.adapter_id for r in a[:6]] == [0, 1, 2, 0, 1, 2]
    arrivals = [r.arrival_s for r in a]
    assert all(t2 >= t1 for t1, t2 in zip(arrivals, arrivals[1:]))
    # Poisson-ish: mean inter-arrival within a loose factor of 1/rate.
    gaps = np.diff([0.0] + arrivals)
    assert 0.3 / spec.rate_rps < gaps.mean() < 3.0 / spec.rate_rps
    c = generate(LoadSpec(num_requests=64, rate_rps=20.0, seed=6))
    assert any((x.prompt.shape != y.prompt.shape or
                (x.prompt != y.prompt).any()) for x, y in zip(a, c))

    # The PR 16 prefix/session/tenant traffic shape is just as
    # seed-deterministic -- same spec, byte-identical stream including
    # the new fields.
    pspec = prefix_spec(num_requests=48, seed=9)
    p, q = generate(pspec), generate(pspec)
    assert all((x.prompt == y.prompt).all() and
               x.arrival_s == y.arrival_s and
               x.tenant == y.tenant and
               x.session_id == y.session_id for x, y in zip(p, q))
    # Structure: shared requests really share -- at most num_prefixes
    # distinct prefix_len-token heads among the long prompts.
    plen = pspec.prefix_lens[0]
    heads = {tuple(r.prompt[:plen]) for r in p
             if r.prompt_len > plen and r.session_id is None}
    assert 1 <= len(heads) <= pspec.num_prefixes
    # Sessions: a later turn EXTENDS an earlier turn's prompt.
    by_sid = {}
    for r in p:
        if r.session_id is not None:
            by_sid.setdefault(r.session_id, []).append(r)
    multi = [turns for turns in by_sid.values() if len(turns) > 1]
    assert multi
    for turns in multi:
        first, second = turns[0], turns[1]
        assert second.prompt_len > first.prompt_len
        assert (second.prompt[:first.prompt_len] == first.prompt).all()
    # Tenants drawn from the declared mix.
    assert {r.tenant for r in p} == {"gold", "bronze"}


def test_request_prefetcher_order_and_error():
    reqs = [_req(i) for i in range(5)]
    with RequestPrefetcher(reqs, depth=2) as feed:
        got = [r.rid for r, _ in feed]
    assert got == [0, 1, 2, 3, 4]

    class Boom(Exception):
        pass

    class BadList(list):
        def __iter__(self):
            raise Boom("producer died")

    with pytest.raises(Boom):
        list(RequestPrefetcher(BadList(reqs), depth=1))


# ---------------------------------------------------------------------------
# The decode step walks the page table; the fp8 and verify steps gather views
# ---------------------------------------------------------------------------


def _two_live_slots(params, cache, tokens):
    """Slots 0 and 2 hold 13 and 24 prompt tokens; slots 1 and 3 idle."""
    for slot, t in ((0, 13), (2, 24)):
        _, kl, vl = prefill_forward(params, CFG, tokens[slot:slot + 1, :t])
        cache.write_prefill(slot, kl[:, 0], vl[:, 0])
        cache.reserve(slot, t + 3)
    slots = cache.config.slots
    return jnp.zeros((slots,), bool).at[jnp.asarray([0, 2])].set(True)


@pytest.mark.parametrize("kernels", ["0", "1"])
def test_walk_step_and_view_step_agree_on_one_cache(base_params,
                                                    monkeypatch, kernels):
    """Width 1 over uncompressed pools the step WALKS the page table;
    built for the fp8 pool (with nothing compressed yet) it gathers slot
    views of the same rows.  Same logits, same tokens, same pools: bit
    for bit with the kernels off (one reference under both), to rounding
    with ``hvd_cca_decode`` and ``hvd_flash_decode`` interpreted."""
    monkeypatch.setenv("HOROVOD_PALLAS_DECODE", kernels)
    _, params = base_params
    mesh = mesh_1d(1)
    ccfg = CacheConfig(num_layers=CFG.num_layers,
                       num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim,
                       slots=4, page_size=8, max_len=64, compress=True)
    cache = PagedKVCache(ccfg, cache_sharding(mesh))
    kw = dict(slots=4, page_size=8, pages_per_slot=ccfg.pages_per_slot)
    walk = build_decode_step(CFG, mesh, **kw)
    view = build_decode_step(CFG, mesh, compress=True, **kw)
    assert walk.meta["attention"] == "walk"
    assert view.meta["attention"] == "view"
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0,
                                CFG.vocab_size)
    active = _two_live_slots(params, cache, tokens)
    table, base = cache.table_device(), cache.lengths_device()
    pools = {"walk": (jnp.copy(cache.k), jnp.copy(cache.v)),
             "view": (cache.k, cache.v)}
    told = {"walk": no_round(4), "view": no_round(4)}
    same = np.testing.assert_array_equal if kernels == "0" else \
        (lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5))
    for i in range(3):
        # Round 0 feeds the host's tokens; after it the step reads its
        # own from ``told`` (-1).
        tok = tokens[:, 31] if i == 0 else jnp.full((4,), -1, jnp.int32)
        out = {}
        for name, step, extra in (
                ("walk", walk, ()),
                ("view", view, cache.compress_operands())):
            logits, k, v, told[name] = step(
                params, *pools[name], tok, base + i, table, active, *extra,
                told[name])
            pools[name] = (k, v)
            out[name] = np.asarray(logits)
        same(out["walk"][[0, 2]], out["view"][[0, 2]])
        np.testing.assert_array_equal(np.asarray(told["walk"]),
                                      np.asarray(told["view"]))
    for a, b in zip(pools["walk"], pools["view"]):
        same(np.asarray(a), np.asarray(b))


def test_tp_shards_hold_contiguous_heads_of_head_less_rows(base_params):
    """One row a token, split over ``tp`` into contiguous heads: the
    8-device step writes and reads what the 1-device step does (the
    pools' global bytes agree), and device ``i`` holds head ``i``."""
    _, params = base_params
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0,
                                CFG.vocab_size)
    got = {}
    for ndev in (1, 8):
        mesh, ccfg, cache = _make_cache(ndev)
        active = _two_live_slots(params, cache, tokens)
        step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                                 page_size=ccfg.page_size,
                                 pages_per_slot=ccfg.pages_per_slot)
        assert step.meta["attention"] == "walk" and step.meta["tp"] == ndev
        logits, cache.k, cache.v, _ = step(
            params, cache.k, cache.v, tokens[:, 31], cache.lengths_device(),
            cache.table_device(), active, no_round(ccfg.slots))
        got[ndev] = (np.asarray(logits), np.asarray(cache.k),
                     np.asarray(cache.v), cache)
    np.testing.assert_allclose(got[1][0][[0, 2]], got[8][0][[0, 2]],
                               rtol=1e-4, atol=1e-4)
    # The rows the round wrote (token 13 of slot 0: page 1, offset 5).
    cache = got[8][3]
    page = int(cache.page_table[0, 1])
    for pool in (1, 2):
        np.testing.assert_allclose(got[1][pool][:, page, 5],
                                   got[8][pool][:, page, 5],
                                   rtol=1e-5, atol=1e-5)
        assert np.abs(got[8][pool][:, page, 5]).min() > 0
    hd = CFG.head_dim
    for i, shard in enumerate(sorted(cache.k.addressable_shards,
                                     key=lambda s: s.index[3].start)):
        assert shard.index[3] == slice(i * hd, (i + 1) * hd)
        np.testing.assert_array_equal(
            np.asarray(shard.data), got[8][1][..., i * hd:(i + 1) * hd])


def test_two_engines_of_one_process_each_rotate_by_their_own_theta(
        base_params):
    """The step is memoized for the process, and RoPE's base is a
    constant of its trace: it is part of the memo's key (it was not, and
    the second of two configurations that differed in nothing else
    decoded with the first's; PERF.md, PR 37).  Each decodes like its
    OWN full-context forward."""
    import dataclasses
    _, params = base_params
    T, t0 = 14, 8
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, T), 0,
                                CFG.vocab_size)
    fulls = []
    for theta in (5e5, 1e6):
        cfg = dataclasses.replace(CFG, rope_theta=theta)
        full = np.asarray(LlamaLM(cfg, dtype=jnp.float32).apply(
            params, tokens))
        fulls.append(full)
        mesh, ccfg, cache = _make_cache(1)
        _, kl, vl = prefill_forward(params, cfg, tokens[:, :t0])
        cache.write_prefill(0, kl[:, 0], vl[:, 0])
        step = build_decode_step(cfg, mesh, slots=ccfg.slots,
                                 page_size=ccfg.page_size,
                                 pages_per_slot=ccfg.pages_per_slot)
        got = _decode_sequence(params, step, cache, tokens, t0, T)
        np.testing.assert_allclose(got, full[0, t0:T], rtol=1e-4, atol=1e-4)
    # The two do differ by more than the tolerance they are held to.
    assert np.abs(fulls[0][0, t0:T] - fulls[1][0, t0:T]).max() > 1e-3


def test_auditor_names_the_attention_kernel_the_step_calls(base_params,
                                                           monkeypatch):
    """``ExpectedExchange.kernels``: the dense decode step walks
    (``mla_decode``'s kernel, never the split-KV one); the verify step
    and the fp8 path gather views (``flash_decode``, never the walk)."""
    from horovod_tpu.serving import build_verify_step
    _, params = base_params
    mesh, ccfg, _ = _make_cache(1)
    kw = dict(slots=ccfg.slots, page_size=ccfg.page_size,
              pages_per_slot=ccfg.pages_per_slot)
    steps = {"walk": build_decode_step(CFG, mesh, **kw),
             "view": build_decode_step(CFG, mesh, compress=True, **kw),
             "verify": build_verify_step(CFG, mesh, width=3, **kw)}
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    names = {name: expected_exchange(params, meta_from_step(step)).kernels
             for name, step in steps.items()}
    assert "mla_decode" in names["walk"]
    assert "flash_decode" not in names["walk"]
    for name in ("view", "verify"):
        assert meta_from_step(steps[name])["attention"] == "view"
        assert "flash_decode" in names[name]
        assert "mla_decode" not in names[name]
    assert {len(v) for v in names.values()} == {6}   # the other families
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    assert expected_exchange(
        params, meta_from_step(steps["walk"])).kernels == ()


def test_decode_round_span_says_whether_the_step_walks(base_params):
    """``decode.round`` carries ``walk``: 1 under the plain engine, 0
    where the engine's step is the fp8 path's."""
    _, params = base_params
    rec = spans.recorder()
    for kw, want in ((dict(), 1), (dict(kv_compress=True), 0)):
        eng = ServingEngine(CFG, params, mesh=mesh_1d(1), slots=4,
                            page_size=8, max_len=64, **kw)
        assert eng.step.meta["attention"] == ("walk" if want else "view")
        rec.reset()
        report = eng.serve(generate(LoadSpec(
            num_requests=4, rate_rps=200.0, prompt_lens=(4, 9),
            output_lens=(3, 5), vocab_size=CFG.vocab_size, seed=2)))
        assert report.completed == 4
        rounds = rec.records(name="decode.round")
        assert rounds and len(rounds) == report.decode_steps
        assert {r.attrs["walk"] for r in rounds} == {want}
        assert all(r.attrs["pages"] >= r.attrs["slots"] for r in rounds)


# ---------------------------------------------------------------------------
# Auditor: model the decode step or decline honestly
# ---------------------------------------------------------------------------


def _audit_args(cache, *banks):
    slots = cache.config.slots
    return (cache.k, cache.v, jnp.zeros((slots,), jnp.int32),
            cache.lengths_device(), cache.table_device(),
            jnp.zeros((slots,), bool), *banks, no_round(slots))


@pytest.mark.parametrize("ndev", [1, 8])
def test_audit_models_tp_decode_step(base_params, ndev):
    _, params = base_params
    mesh, ccfg, cache = _make_cache(ndev)
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot)
    meta = meta_from_step(step)
    assert meta["kind"] == "serving_decode" and meta["tp"] == ndev
    expected = expected_exchange(params, meta)
    assert expected.supported
    assert len(expected.ops) == 2 * CFG.num_layers
    assert all(op.kind == "psum" and
               op.elements == ccfg.slots * CFG.d_model
               for op in expected.ops)
    report = audit_step(step, params, *_audit_args(cache),
                        name=f"serving-decode-tp{ndev}")
    assert report.ok(), [f.message for f in report.findings]
    assert not [f for f in report.findings
                if f.rule.startswith("audit-plan-") and
                f.rule != "audit-plan-note"]


def test_audit_declines_lora_banks(base_params):
    mesh, ccfg, cache = _make_cache(1)
    model = LlamaLM(CFG, dtype=jnp.float32, lora_rank=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    banks = stack_adapters([params["params"], params["params"]])
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot,
                             with_lora=True)
    expected = expected_exchange(params, meta_from_step(step))
    assert not expected.supported
    report = audit_step(step, params, *_audit_args(
                            cache, {"params": banks},
                            jnp.zeros((ccfg.slots,), jnp.int32)),
                        name="serving-decode-lora")
    assert report.ok()
    assert any(f.rule == "audit-plan-unsupported" for f in report.findings)


def test_audit_catches_desynced_decode_branch():
    """Known-bad fixture: a decode variant where only rank 0 enters the
    row-parallel allreduce -- the static auditor must still flag it."""
    mesh = mesh_1d(8)

    def bad_decode(x, wo):
        idx = jax.lax.axis_index("tp")

        def synced(v):
            return jax.lax.psum(v @ wo, "tp")

        def desynced(v):
            return v @ wo

        return jax.lax.cond(idx == 0, synced, desynced, x)

    bad = jax.jit(jax.shard_map(
        bad_decode, mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
        out_specs=P(), check_vma=False))
    report = audit_step(bad, jnp.ones((4, 64)), jnp.ones((64, 64)),
                        name="desynced-decode")
    assert not report.ok()
    assert any(f.rule == "audit-desync-branch" and f.severity == "error"
               for f in report.findings)


# ---------------------------------------------------------------------------
# Multi-LoRA decode batch
# ---------------------------------------------------------------------------


def test_multi_lora_adapters_share_base_model():
    model = LlamaLM(CFG, dtype=jnp.float32, lora_rank=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))

    def randomize(tree, key):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            0.05 * jax.random.normal(kk, l.shape, l.dtype)
            for kk, l in zip(keys, leaves)])

    def adapter_tree(key):
        base = jax.tree.map(lambda x: x, params["params"])
        bank = stack_adapters([base])  # structure template
        rand = randomize(bank, key)
        return jax.tree.map(lambda x: x[0], rand)

    ad0 = adapter_tree(jax.random.PRNGKey(11))
    ad1 = adapter_tree(jax.random.PRNGKey(22))
    banks = stack_adapters([ad0, ad1])

    def merge(adapter):
        merged = jax.tree.map(lambda x: x, params)

        def walk(dst, src):
            for kk, vv in src.items():
                if kk in ("lora_a", "lora_b"):
                    dst[kk] = vv
                else:
                    walk(dst[kk], vv)
        walk(merged["params"], adapter)
        return merged

    T, t0 = 14, 6
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, T), 0,
                                CFG.vocab_size)
    mesh, ccfg, cache = _make_cache(1)
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot,
                             with_lora=True)
    # Two requests, one per adapter, decoding in the SAME batch.
    for slot in (0, 1):
        _, kl, vl = prefill_forward(params, CFG, tokens[slot:slot + 1, :t0],
                                    adapters=banks, adapter_id=slot)
        cache.write_prefill(slot, kl[:, 0], vl[:, 0])
    adapter_ids = jnp.asarray([0, 1, 0, 0], jnp.int32)
    got = {0: [], 1: []}
    for i in range(t0, T):
        for slot in (0, 1):
            cache.reserve(slot, i + 1)
        tok = jnp.zeros((ccfg.slots,), jnp.int32)
        tok = tok.at[0].set(tokens[0, i]).at[1].set(tokens[1, i])
        active = jnp.zeros((ccfg.slots,), bool).at[0].set(True).at[1].set(
            True)
        logits, cache.k, cache.v, _ = step(
            params, cache.k, cache.v, tok, cache.lengths_device(),
            cache.table_device(), active, {"params": banks}, adapter_ids,
            no_round(ccfg.slots))
        for slot in (0, 1):
            cache.lengths[slot] += 1
            got[slot].append(np.asarray(logits[slot]))
    # Each slot matches the flax forward with ITS adapter merged in.
    for slot, adapter in ((0, ad0), (1, ad1)):
        full = np.asarray(model.apply(merge(adapter),
                                      tokens[slot:slot + 1]))
        np.testing.assert_allclose(np.stack(got[slot]), full[0, t0:T],
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------


def test_engine_serves_load_to_completion(base_params):
    _, params = base_params
    spans.recorder().reset()
    eng = ServingEngine(CFG, params, mesh=mesh_1d(8), slots=4,
                        page_size=8, max_len=64)
    assert eng.cache.layout() == eng.cache_config.layout()
    spec = LoadSpec(num_requests=10, rate_rps=100.0,
                    prompt_lens=(4, 8), output_lens=(3, 5),
                    vocab_size=CFG.vocab_size, seed=2)
    report = eng.serve(generate(spec))
    assert report.completed == 10 and report.rejected == 0
    assert report.new_tokens > 0 and report.tokens_per_s > 0
    assert report.decode_steps > 0
    assert 0.0 < report.mean_occupancy <= 1.0
    assert report.ttft_p99_s >= report.ttft_p50_s >= 0
    d = report.as_dict()
    for key in ("tokens_per_s", "ttft_p50_s", "ttft_p99_s",
                "token_latency_p50_s", "token_latency_p99_s",
                "mean_occupancy"):
        assert isinstance(d[key], float)
    # Lifecycle landed in the metrics plane and the span layer.
    text = render_prometheus()
    for fam in ("horovod_serving_requests_total",
                "horovod_serving_tokens_total",
                "horovod_serving_queue_depth",
                "horovod_serving_batch_occupancy",
                "horovod_serving_ttft_seconds",
                "horovod_serving_token_latency_seconds"):
        assert fam in text
    assert "serving_decode/layer0/attn_wo" in spans.recorder().legs


def test_engine_rejects_oversize_requests(base_params):
    _, params = base_params
    eng = ServingEngine(CFG, params, mesh=mesh_1d(1), slots=2,
                        page_size=8, max_len=16)
    reqs = [_req(0, plen=4, out=4),
            _req(1, plen=14, out=8)]  # 22 > max_len 16
    report = eng.serve(reqs)
    assert report.completed == 1 and report.rejected == 1


def test_engine_env_defaults(base_params, monkeypatch):
    _, params = base_params
    monkeypatch.setenv("HOROVOD_SERVING_SLOTS", "3")
    monkeypatch.setenv("HOROVOD_SERVING_PAGE_SIZE", "4")
    monkeypatch.setenv("HOROVOD_SERVING_MAX_LEN", "32")
    monkeypatch.setenv("HOROVOD_SERVING_PREFETCH", "5")
    eng = ServingEngine(CFG, params, mesh=mesh_1d(1))
    assert (eng.slots, eng.page_size, eng.max_len,
            eng.prefetch_depth) == (3, 4, 32, 5)


# ---------------------------------------------------------------------------
# Prefix-shared KV cache (PR 16): radix matching, COW pages, tenants
# ---------------------------------------------------------------------------


def test_shared_prefix_page_read_bitwise_and_cow_isolation(base_params):
    """Extends the eviction/reuse proof to SHARED pages: a slot reading
    a shared prefix page decodes bitwise-identically to a private copy
    of the same bytes, and copy-on-write divergence never mutates the
    shared original."""
    model, params = base_params
    mesh, ccfg, cache = _make_cache(1, slots=4, page_size=8, max_len=64)
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot)
    pc = PrefixCache(cache)
    rng = np.random.RandomState(11)
    prefix = rng.randint(0, CFG.vocab_size, (1, 16))   # 2 full pages
    prompt1 = np.concatenate(
        [prefix, rng.randint(0, CFG.vocab_size, (1, 4))], 1)
    prompt2 = np.concatenate(
        [prefix, rng.randint(0, CFG.vocab_size, (1, 4))], 1)

    # Slot 0: whole-prompt prefill, then register the prefix pages.
    _, kl, vl = prefill_forward(params, CFG, jnp.asarray(prompt1))
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    assert pc.insert(prompt1[0], 0) == 2

    # Slot 1: radix hit -> attach the SHARED pages, prefill the tail
    # only (conditioned on the cached pages as past K/V).
    matched, entries = pc.match(prompt2[0])
    assert matched == 16 and [k for k, _ in entries] == ["f", "f"]
    cache.attach_pages(1, entries, matched)
    shared_pids = [int(p) for _, p in entries]
    np.testing.assert_array_equal(cache.page_table[1, :2],
                                  cache.page_table[0, :2])
    past = cache.gather_pages(entries)
    _, kl2, vl2 = prefill_forward(params, CFG,
                                  jnp.asarray(prompt2[:, 16:]), past=past)
    cache.write_prefill(1, kl2[:, 0, 16:], vl2[:, 0, 16:], start=16)

    # Slot 3: the UNSHARED control -- attach the same pages and the
    # same tail bytes, then force the copy-on-write clone so it reads
    # private pages holding identical bytes.
    cache.attach_pages(3, entries, matched)
    cache.write_prefill(3, kl2[:, 0, 16:], vl2[:, 0, 16:], start=16)
    cache.reserve(3, 20, writable_from=0)   # COW: clone pages 0..1
    assert all(int(cache.page_table[3, i]) not in shared_pids
               for i in range(2))

    # Slot 2: COW DIVERGENCE -- attach the shared pages, then rewrite
    # the whole context with different tokens from position 0.
    orig_bytes_k = np.asarray(cache.k)[:, shared_pids].copy()
    orig_bytes_v = np.asarray(cache.v)[:, shared_pids].copy()
    other = rng.randint(0, CFG.vocab_size, (1, 20))
    cache.attach_pages(2, entries, matched)
    _, klo, vlo = prefill_forward(params, CFG, jnp.asarray(other))
    cache.write_prefill(2, klo[:, 0], vlo[:, 0])   # start=0: full rewrite
    assert all(int(cache.page_table[2, i]) not in shared_pids
               for i in range(2))
    # The divergence landed in clones; the shared originals are
    # bit-for-bit untouched.
    np.testing.assert_array_equal(np.asarray(cache.k)[:, shared_pids],
                                  orig_bytes_k)
    np.testing.assert_array_equal(np.asarray(cache.v)[:, shared_pids],
                                  orig_bytes_v)

    # Shared read (slot 1) == private-copy read (slot 3), bitwise --
    # decoded AFTER the divergence next door.
    seq2 = jnp.asarray(np.concatenate([prompt2, prompt2[:, :6]], 1))
    got = _decode_sequence(params, step, cache, seq2, 20, 26, slot=1)
    want = _decode_sequence(params, step, cache, seq2, 20, 26, slot=3)
    np.testing.assert_array_equal(got, want)

    # Drain: slots + tree release every reference, zero leaks.
    for s in range(4):
        cache.free_slot(s)
    pc.drop_all()
    assert cache.live_pages == 0
    assert cache.free_pages == ccfg.num_pages
    assert cache.refcounts_balanced()


def test_prefix_cache_radix_match_insert_and_refcounts():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    pc = PrefixCache(cache, session_ttl_steps=4)
    prompt = np.arange(10, dtype=np.int32)   # 2 full pages + tail
    assert pc.match(prompt) == (0, [])       # cold tree
    kl = jnp.ones((1, 10, 8), jnp.float32)
    cache.write_prefill(0, kl, kl)
    assert pc.insert(prompt, 0) == 2
    assert pc.insert(prompt, 0) == 0         # idempotent

    # Same-prefix prompt hits both registered pages.
    p2 = np.concatenate([prompt[:8], np.asarray([9, 9], np.int32)])
    matched, entries = pc.match(p2)
    assert matched == 8 and len(entries) == 2
    # The cap: a prompt can never match ALL of itself (the tail
    # prefill must produce first-token logits), so an exact-page
    # prompt matches one page short.
    assert pc.match(prompt[:8])[0] == 4

    # Tree references outlive the slot: only the unregistered tail
    # page returns to the free list.
    free_before = cache.free_pages
    cache.free_slot(0)
    assert cache.free_pages == free_before + 1
    assert cache.live_pages == 2

    # Attaching bumps refcounts; detaching drops them; pressure evicts
    # the tree's own references; drain leaves the pool whole.
    cache.attach_pages(1, entries, 8)
    assert int(cache.lengths[1]) == 8 and cache.live_pages == 2
    cache.free_slot(1)
    assert pc.release_pages(2) == 2
    pc.drop_all()
    assert cache.live_pages == 0 and cache.refcounts_balanced()
    assert pc.stats()["hit_rate"] == pc.hit_rate > 0


def test_prefix_cache_session_pin_ttl_expiry():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    pc = PrefixCache(cache, session_ttl_steps=3)
    prompt = np.arange(8, dtype=np.int32)
    kl = jnp.ones((1, 8, 8), jnp.float32)
    cache.write_prefill(0, kl, kl)
    pc.insert(prompt, 0)
    cache.free_slot(0)

    pc.pin_session("s0", prompt)
    assert pc.sessions_live == 1 and pc.touch_session("s0")
    # Pinned nodes survive an eviction demand while unpinned ones
    # exist... here everything is pinned, so LRU takes them last but
    # WILL take them (a cache, not a lease).
    pc.tick(2)
    assert pc.touch_session("s0")            # reuse refreshes the TTL
    pc.tick(2)
    assert pc.sessions_live == 1             # within TTL again
    pc.tick(4)                               # idle past TTL -> expired
    assert pc.sessions_live == 0
    assert not pc.touch_session("s0")
    pc.drop_all()
    assert cache.live_pages == 0 and cache.refcounts_balanced()


def test_prefix_cache_demotes_to_fp8_then_stays_matchable():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16, compress=True)
    cache = PagedKVCache(ccfg)
    pc = PrefixCache(cache)
    rng = np.random.RandomState(3)
    prompt = np.arange(8, dtype=np.int32)
    kl = jnp.asarray(rng.randn(1, 8, 8).astype(np.float32))
    vl = jnp.asarray(rng.randn(1, 8, 8).astype(np.float32))
    cache.write_prefill(0, kl, vl)
    pc.insert(prompt, 0)
    cache.free_slot(0)
    assert cache.live_pages == 2

    # Page pressure: the demotion tier quantizes tree-only f32 pages
    # into the e4m3 pool -- the f32 pages come back, the prefix stays
    # matchable at fp8 cost.
    assert pc.release_pages(2) == 2
    assert cache.live_pages == 0             # f32 pool fully free
    matched, entries = pc.match(np.concatenate([prompt, prompt[:4]]))
    assert matched == 8 and all(k == "c" for k, _ in entries)

    # gather_pages dequantizes the demoted pages for the tail prefill.
    pk, pv = cache.gather_pages(entries)
    assert pk.shape == (1, 1, 8, 8)
    np.testing.assert_allclose(np.asarray(pk)[0, 0], np.asarray(kl)[0],
                               rtol=0.2, atol=0.1)
    pc.drop_all()
    assert cache.refcounts_balanced()


def _treq(rid, tenant, plen=4, out=4):
    return Request(rid=rid, prompt=np.full((plen,), rid % 7, np.int32),
                   max_new_tokens=out, arrival_s=0.0, tenant=tenant)


def test_scheduler_tenant_stride_admission_and_share_cap():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=3,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    tenants = {"gold": TenantClass("gold"),
               "bronze": TenantClass("bronze", max_share=0.25)}
    sched = ContinuousBatchScheduler(3, cache, tenants=tenants)
    for i in range(3):
        sched.submit(_treq(i, "bronze"))
    sched.submit(_treq(3, "gold"))
    sched.submit(_treq(4, "gold"))
    admitted = sched.admit(0.0)
    # Stride order: bronze leads (earliest queue position at equal
    # pass), then gold; bronze's max_share (ceil(0.25 * 3) = 1 slot)
    # caps it while gold still waits, so gold takes the third slot.
    assert [r.tenant for _, r in admitted] == ["bronze", "gold", "gold"]
    assert [r.rid for _, r in admitted] == [0, 3, 4]
    assert len(sched.queue) == 2             # bronze 1, 2 held back
    # When NOBODY else is queued the cap yields (work conservation).
    for slot, _ in admitted:
        sched.release(slot, 0.1)
    assert [r.tenant for _, r in sched.admit(0.2)] == ["bronze", "bronze"]


def test_scheduler_tenant_weights_skew_admission_share():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=4,
                       page_size=4, max_len=16)
    cache = PagedKVCache(ccfg)
    tenants = {"gold": TenantClass("gold", weight=3.0),
               "bronze": TenantClass("bronze", weight=1.0)}
    sched = ContinuousBatchScheduler(4, cache, tenants=tenants)
    for i in range(4):
        sched.submit(_treq(i, "bronze"))
    for i in range(4, 8):
        sched.submit(_treq(i, "gold"))
    admitted = [r.tenant for _, r in sched.admit(0.0)]
    # Equal passes admit bronze's head first; after that gold's 3x
    # weight advances its pass 3x slower, so gold fills the rest.
    assert admitted == ["bronze", "gold", "gold", "gold"]


def test_parse_tenant_classes_wire_format():
    from horovod_tpu.serving import parse_tenant_classes
    got = parse_tenant_classes("gold:4:0.5:0.75, bronze:1, free")
    assert set(got) == {"gold", "bronze", "free"}
    assert got["gold"] == TenantClass("gold", weight=4.0, ttft_slo_s=0.5,
                                      max_share=0.75)
    assert got["bronze"].weight == 1.0 and got["free"].max_share == 1.0
    with pytest.raises(ValueError):
        parse_tenant_classes("bad:-1")


def test_engine_prefix_cache_end_to_end(base_params):
    _, params = base_params
    eng = ServingEngine(CFG, params, mesh=mesh_1d(1), slots=4,
                        page_size=8, max_len=128, prefix_cache=True,
                        session_ttl_steps=64)
    spec = prefix_spec(num_requests=12, prompt_lens=(8,), output_lens=(4,),
                       prefix_lens=(32,), num_prefixes=2,
                       vocab_size=CFG.vocab_size)
    report = eng.serve(generate(spec))
    assert report.completed == 12 and report.rejected == 0
    assert report.prefix_queries == 12
    assert report.prefix_hits > 0
    assert 0.0 < report.prefix_hit_rate <= 1.0
    assert report.prefill_tokens_cached > 0
    assert 0.0 < report.prefill_flops_avoided < 1.0
    assert report.prefix_hit_rate == pytest.approx(
        report.prefix_hits / report.prefix_queries)
    # Drain-time leak proof: slots released during serve, the tree is
    # the only remaining holder; dropping it must empty the pool.
    eng._prefix.drop_all()
    assert eng.cache.live_pages == 0
    assert eng.cache.refcounts_balanced()
    # The prefix and per-tenant metric families are live alongside the
    # slot-state gauges (the control plane reads these).
    text = render_prometheus()
    for fam in ("horovod_serving_prefix_hit_rate",
                "horovod_serving_prefix_pages",
                "horovod_serving_sessions_live",
                "horovod_serving_prefix_tokens_total",
                "horovod_serving_ttft_by_tenant_seconds",
                "horovod_serving_tenant_occupancy",
                "horovod_serving_tenant_queue_depth"):
        assert fam in text


def test_engine_prefix_cache_with_chunked_tail(base_params):
    """A prefix hit whose tail still exceeds the chunk budget runs the
    PR 14 chunked path seeded from the cached pages."""
    _, params = base_params
    eng = ServingEngine(CFG, params, mesh=mesh_1d(1), slots=2,
                        page_size=8, max_len=128, prefix_cache=True,
                        prefill_chunk=8)
    spec = prefix_spec(num_requests=8, prompt_lens=(24,), output_lens=(3,),
                       prefix_lens=(32,), num_prefixes=1,
                       session_share=0.0, vocab_size=CFG.vocab_size)
    report = eng.serve(generate(spec))
    assert report.completed == 8
    assert report.prefix_hits > 0
    assert report.prefill_flops_avoided > 0.0


# ---------------------------------------------------------------------------
# 3D-training -> serving checkpoint roundtrip (PR 18 satellite)
# ---------------------------------------------------------------------------


def test_3d_checkpoint_roundtrip_into_serving(base_params, tmp_path):
    """A checkpoint saved from the TP-sharded 3D train step loads straight
    into the serving plane: the step's out_specs reassemble FULL kernels,
    so ``save_checkpoint`` writes the unsharded tree and the restored
    params drive ``prefill_forward``/``build_decode_step`` on the serving
    tp mesh with decode parity against the full-context forward.
    """
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.parallel import (build_3d_mesh, data_axes, tp_mlp,
                                      tp_param_specs)
    from horovod_tpu.utils.checkpoint import (restore_checkpoint,
                                              save_checkpoint)

    model, params0 = base_params
    specs = tp_param_specs(params0, axis="model")
    path = str(tmp_path / "ckpt_3d.npz")

    hvd.shutdown()
    hvd.init(mesh=build_3d_mesh(jax.devices()[:8], data=2, model=2,
                                dcn_size=2))
    try:
        mesh = hvd.mesh()

        def loss_fn(p, batch):
            # TP-consistent toy objective: drive the layer-0 SwiGLU MLP
            # (column/row shards) toward zero output; adamw's decay term
            # moves every other leaf too.
            mlp = p["params"]["layer_0"]["mlp"]
            y = tp_mlp(batch, mlp["w_up"]["kernel"],
                       mlp["w_down"]["kernel"], axis="model",
                       w_gate=mlp["w_gate"]["kernel"])
            return jnp.mean(y ** 2)

        opt = hvd.DistributedOptimizer(
            optax.adamw(1e-2), compression=hvd.Compression.fp16,
            axes=data_axes(mesh))
        oss = hvd.mirror_opt_state_specs(opt, params0, specs)
        step = hvd.make_train_step(loss_fn, opt, mesh=mesh, tp=2,
                                   param_specs=specs, opt_state_specs=oss)
        rng = np.random.RandomState(3)
        batch = jnp.asarray(rng.randn(8, CFG.d_model).astype(np.float32))
        # The step donates its inputs; train on a copy so the module
        # fixture's tree survives for the other tests.
        p = jax.tree.map(jnp.copy, params0)
        st = opt.init(p)
        for _ in range(3):
            p, st, _ = step(p, st, batch)

        # The step's donated-out tree is already FULL-shaped: the
        # checkpoint holds unsharded kernels, no unstack step needed.
        for got, want in zip(jax.tree.leaves(p), jax.tree.leaves(params0)):
            assert got.shape == want.shape
        w0 = params0["params"]["layer_0"]["mlp"]["w_up"]["kernel"]
        assert float(jnp.abs(p["params"]["layer_0"]["mlp"]["w_up"]["kernel"]
                             - w0).max()) > 1e-5

        save_checkpoint(path, p, step=3)
        restored, step_no = restore_checkpoint(path, params0)
        assert step_no == 3
        for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(p)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        hvd.shutdown()

    # Serving-plane load: full-context forward vs incremental decode on
    # the 8-way tp mesh, both on the RESTORED tree.
    T, t0 = 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, T), 0,
                                CFG.vocab_size)
    full = np.asarray(model.apply(restored, tokens))
    mesh, ccfg, cache = _make_cache(8)
    logits_p, kl, vl = prefill_forward(restored, CFG, tokens[:, :t0])
    np.testing.assert_allclose(np.asarray(logits_p[0]), full[0, :t0],
                               rtol=1e-4, atol=1e-4)
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    dstep = build_decode_step(CFG, mesh, slots=ccfg.slots,
                              page_size=ccfg.page_size,
                              pages_per_slot=ccfg.pages_per_slot)
    got = _decode_sequence(restored, dstep, cache, tokens, t0, T)
    np.testing.assert_allclose(got, full[0, t0:T], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# A prefill's first token stays on the chip (the look-ahead loop's join)
# ---------------------------------------------------------------------------

from horovod_tpu.serving import engine as engine_mod  # noqa: E402
from horovod_tpu.timeline import metrics  # noqa: E402
from benchmarks.lib.tracing import CompileCounter  # noqa: E402
from serving_families import FAMILIES, round_by_round  # noqa: E402

JOIN_SLOTS = 3
ONE_TOKEN = 3                  # the rid whose first token is its last


def _join_engine(family):
    cfg, params = FAMILIES[family]()
    return cfg, ServingEngine(cfg, params, slots=JOIN_SLOTS, page_size=8,
                              max_len=32, dtype=jnp.float32)


def _join_requests(cfg):
    """Seven requests over three slots, all there at t = 0: the first
    three are prefilled with no round in flight, the rest join behind a
    round as slots come free, and ``ONE_TOKEN`` is done with its first
    token."""
    rng = np.random.RandomState(5)
    lens = [5, 9, 12, 9, 5, 12, 9]
    outs = [4, 7, 3, 1, 6, 2, 5]
    assert outs[ONE_TOKEN] == 1
    return [Request(rid=i, prompt=rng.randint(0, min(90, cfg.vocab_size),
                                              size=n).astype(np.int32),
                    max_new_tokens=o, arrival_s=0.0)
            for i, (n, o) in enumerate(zip(lens, outs))]


def _synchronous_join(eng, reqs):
    """The reference: a loop that catches up behind every join's round,
    so that no round is dispatched before the host has every token of
    the one before (the control plane's form of the loop)."""
    st = round_by_round(eng, reqs)
    assert len(st["completed"]) == len(reqs)
    assert st["first_tokens_deferred"] == st["prefills"] == len(reqs)
    return [list(r.tokens) for r in reqs]


def _poison_prefills(eng, rids):
    """The prefill of each request of ``rids`` returns logits that are
    not finite, the first time it runs.  Returns the rids still to go."""
    real, do_prefill = eng._prefill, eng._do_prefill
    todo, current = set(rids), []

    def prefill(params, toks, *rest):
        out = real(params, toks, *rest)
        # (Outside a ``_do_prefill`` the engine is preparing a length's
        # group programs: no request's prefill.)
        if current and current[-1] in todo:
            todo.discard(current[-1])
            return (out[0] * jnp.nan,) + tuple(out[1:])
        return out

    def naming(slot, req, *args, **kwargs):
        current.append(req.rid)
        try:
            return do_prefill(slot, req, *args, **kwargs)
        finally:
            current.clear()

    eng._prefill, eng._do_prefill = prefill, naming
    return todo


@pytest.fixture(scope="module", params=list(FAMILIES))
def joined(request):
    """One family's tiny engine serving :func:`_join_requests` through
    the look-ahead loop, on the spans' clock, beside what the
    synchronous join serves."""
    import time
    cfg, ref = _join_engine(request.param)
    want = _synchronous_join(ref, _join_requests(cfg))
    cfg, eng = _join_engine(request.param)
    pages = eng.cache.free_pages
    readings = []

    class SpanClock:
        @staticmethod
        def monotonic():
            readings.append(time.perf_counter_ns())
            return readings[-1] / 1e9

    rec = spans.recorder()
    rec.reset()
    reqs = _join_requests(cfg)
    mp = pytest.MonkeyPatch()
    mp.setattr(engine_mod, "time", SpanClock)
    try:
        report = eng.serve(reqs)
    finally:
        mp.undo()
    serve, = rec.records(name="serve")
    records = rec.records(since_ns=serve.start_ns)
    assert report.completed == len(reqs) and eng.cache.free_pages == pages
    assert not any(r.in_flight for r in reqs)
    # The same traffic again, as a benchmark's window follows its
    # warm-up: what is lowered now would compile inside the window.
    # (Lowerings, not backend compiles: a persistent cache would hide
    # those.)
    lowerings = CompileCounter()
    lowerings.EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    again = _join_requests(cfg)
    with lowerings.counting():
        assert eng.serve(again).completed == len(reqs)
    relowered = lowerings.count
    assert [r.tokens for r in again] == [r.tokens for r in reqs]
    return dict(reqs=reqs, want=want, report=report, serve=serve,
                records=records, zero_ns=readings[0], relowered=relowered)


def test_a_deferred_join_serves_what_the_synchronous_join_serves(joined):
    reqs, want = joined["reqs"], joined["want"]
    assert [r.tokens for r in reqs] == want
    assert [len(r.tokens) for r in reqs] == [r.max_new_tokens for r in reqs]
    assert len(reqs[ONE_TOKEN].tokens) == 1
    prefills = [r for r in joined["records"] if r.name == "serve.prefill"]
    # Some joined with no round in flight and some behind one.
    behind = [p.attrs["behind"] for p in prefills]
    assert -1 in behind and max(behind) >= 0
    # One program a round whoever wrote ``told`` last, the step or a
    # join, and one hand-over whatever the prompt's length: a second
    # call of the same traffic lowered nothing.
    assert joined["relowered"] == 0


def test_a_join_waits_for_nothing_before_the_next_round(joined):
    records, reqs = joined["records"], joined["reqs"]
    by_id = {r.id: r for r in records}

    def named(name):
        return [r for r in records if r.name == name]

    prefills = named("serve.prefill")
    assert len(prefills) == len(reqs)
    assert all(p.attrs["deferred"] is True for p in prefills)
    ids = {p.id for p in prefills}
    kids = {r.name for r in records if r.parent in ids}
    assert "prefill.hand_over" in kids and "prefill.dispatch" in kids
    assert "prefill.sample_fetch" not in kids
    account, = named("serve.account")
    assert account.attrs["first_tokens_deferred"] \
        == account.attrs["prefills"] == len(reqs)
    rounds = named("decode.round")
    fetches = {r.attrs["round"]: r for r in named("decode.sample_fetch")}
    dispatches = {by_id[r.parent].attrs["round"]: r
                  for r in named("decode.dispatch")}
    for p in prefills:
        after = min((r for r in rounds if r.start_ns >= p.end_ns),
                    key=lambda r: r.start_ns, default=None)
        if p.attrs["behind"] < 0 or after is None:
            continue
        # The round dispatched after the join is on its way before the
        # host reads the round that was in flight, whose fetch is the
        # one that waits for the join's token.
        fetch = fetches[p.attrs["behind"]]
        assert fetch.parent == after.id
        assert after.start_ns <= dispatches[after.attrs["round"]].end_ns \
            <= fetch.start_ns
    assert any(p.attrs["behind"] >= 0 for p in prefills)


def test_a_first_token_is_stamped_when_the_host_has_it(joined):
    records, reqs = joined["records"], joined["reqs"]
    zero_ns = joined["zero_ns"]
    prefills = {r.attrs["rid"]: r for r in records
                if r.name == "serve.prefill"}
    fetches = {r.attrs["round"]: r for r in records
               if r.name == "decode.sample_fetch"}
    books = {r.attrs["round"]: r for r in records
             if r.name == "decode.bookkeep"}
    alone = sorted((r for r in records if r.name == "prefill.sample_fetch"),
                   key=lambda r: r.start_ns)
    after_alone = 0
    for req in reqs:
        p = prefills[req.rid]
        stamp_ns = zero_ns + req.first_token_s * 1e9
        assert req.first_token_s == req.token_times[0]
        if p.attrs["behind"] >= 0:
            # Read at the retire that follows the join: not before that
            # fetch has returned, and not a round later.
            n = p.attrs["behind"]
            assert fetches[n].end_ns - 1e3 <= stamp_ns \
                <= books[n].end_ns + 1e3
        else:
            fetch = next(r for r in alone if r.start_ns >= p.end_ns)
            later = [r.start_ns for r in records
                     if r.name == "decode.round"
                     and r.start_ns >= fetch.end_ns]
            assert fetch.end_ns - 1e3 <= stamp_ns
            assert not later or stamp_ns <= min(later) + 1e3
            after_alone += 1
    assert after_alone >= JOIN_SLOTS


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_first_token_that_is_not_finite_is_never_served(family):
    """A prefill whose logits are not finite: the flag travels with the
    token, the round dispatched behind it sits the slot out, and the
    host prefills the prompt again before it books anything."""
    cfg, ref = _join_engine(family)
    want = _synchronous_join(ref, _join_requests(cfg))
    cfg, eng = _join_engine(family)
    pages = eng.cache.free_pages
    # One of the first turn (none in flight) and one that joins behind a
    # round.
    todo = _poison_prefills(eng, {1, 4})
    counter = metrics.registry().counter(
        "horovod_guard_serving_reprefills_total")
    before = counter.value
    rec = spans.recorder()
    rec.reset()
    reqs = _join_requests(cfg)
    report = eng.serve(reqs)
    assert not todo and counter.value - before == 2
    assert report.completed == len(reqs) and eng.cache.free_pages == pages
    assert [r.tokens for r in reqs] == want
    assert not any(r.in_flight for r in reqs)
    # Each of the two was handed in a second time, like any join: its
    # token left on the chip again, and read with what the host read
    # next.
    prefills = rec.records(name="serve.prefill")
    assert all(p.attrs["deferred"] is True for p in prefills)
    rids = [p.attrs["rid"] for p in prefills]
    assert sorted(rid for rid in set(rids) if rids.count(rid) == 2) == [1, 4]
    assert len(rids) == len(reqs) + 2
    account, = rec.records(name="serve.account")
    assert account.attrs["first_tokens_deferred"] \
        == account.attrs["prefills"] == len(reqs) + 2
