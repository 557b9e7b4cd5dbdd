"""Round-15 serving overhaul: speculative decoding, chunked flash
prefill, fp8 KV-cache compression.

The tentpole contract under test: speculative decoding is an OPTIMISER,
not a sampler -- every emitted token is the target model's greedy argmax
(bitwise equal to plain decode on meshes of 1 AND 8 virtual devices, for
a strong self-draft drafter AND a weak ngram one); chunked prefill
produces the same logits and KV as the whole-prompt forward; and a
compressed cold page survives its donor f32 page being recycled and
poisoned (the blend reads the e4m3 pool, never the freed page).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.analysis.stepmodel import expected_exchange, meta_from_step
from horovod_tpu.analysis.trace_audit import audit_step
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import (CacheConfig, ContinuousBatchScheduler,
                                 LoadSpec, ModelDrafter, NgramDrafter,
                                 PagedKVCache, Request, ServingEngine,
                                 build_decode_step, build_verify_step,
                                 cache_sharding, generate, prefill_forward)
from horovod_tpu.serving.decode import no_round
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


def _make_cache(ndev, slots=4, page_size=8, max_len=64, compress=False):
    mesh = mesh_1d(ndev)
    ccfg = CacheConfig(num_layers=CFG.num_layers,
                       num_kv_heads=CFG.num_kv_heads,
                       head_dim=CFG.head_dim, slots=slots,
                       page_size=page_size, max_len=max_len,
                       compress=compress)
    return mesh, ccfg, PagedKVCache(ccfg, cache_sharding(mesh))


def _serve_streams(params, *, ndev, seed=3, n=8, **engine_kw):
    """Serve one seeded load and return {rid: emitted token tuple}."""
    eng = ServingEngine(CFG, params, mesh=mesh_1d(ndev), slots=4,
                        page_size=8, max_len=64, **engine_kw)
    reqs = generate(LoadSpec(num_requests=n, rate_rps=200.0,
                             prompt_lens=(4, 9, 16), output_lens=(5, 9),
                             vocab_size=CFG.vocab_size, seed=seed))
    report = eng.serve(reqs)
    assert report.completed == n, report
    return {r.rid: tuple(r.tokens) for r in reqs}, report


# ---------------------------------------------------------------------------
# Tentpole: speculative decode is bitwise greedy-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ndev", [1, 8])
def test_spec_decode_streams_bitwise_equal_plain(base_params, ndev):
    _, params = base_params
    plain, _ = _serve_streams(params, ndev=ndev)
    drafter = ModelDrafter(CFG, params, slots=4, page_size=8, max_len=64,
                           dtype=jnp.float32)
    spec, rep = _serve_streams(params, ndev=ndev, spec_decode=True,
                               spec_k=3, drafter=drafter)
    assert spec == plain
    # Self-draft runs the SAME weights, so near-total agreement: the
    # widened step must actually be amortising dispatches, not
    # degenerating into plain decode with extra baggage.
    assert rep.spec_rounds > 0
    assert rep.acceptance_rate > 0.5, rep


def test_spec_decode_exact_even_with_weak_drafter(base_params):
    """Greedy-exactness must not depend on drafter quality: the ngram
    drafter guesses mostly wrong on random prompts, which costs
    acceptance (wasted verify width) but never changes a token."""
    _, params = base_params
    plain, _ = _serve_streams(params, ndev=1)
    spec, rep = _serve_streams(params, ndev=1, spec_decode=True,
                               spec_k=4, drafter=NgramDrafter())
    assert spec == plain
    assert rep.spec_rounds > 0
    assert 0.0 <= rep.acceptance_rate < 0.5, rep


def test_spec_round_accounting_and_metric_family(base_params):
    from horovod_tpu.timeline import spans
    _, params = base_params
    drafter = ModelDrafter(CFG, params, slots=4, page_size=8, max_len=64,
                           dtype=jnp.float32)
    spans.recorder().reset()
    _, rep = _serve_streams(params, ndev=1, spec_decode=True, spec_k=3,
                            drafter=drafter)
    # A speculative round is dispatched through the verify step, which
    # gathers slot views: its ``decode.round`` says ``walk`` 0.
    rounds = spans.recorder().records(name="decode.round")
    assert len(rounds) >= rep.spec_rounds > 0
    assert sum(1 - r.attrs["walk"] for r in rounds) == rep.spec_rounds
    # k drafts per active slot per round, so proposed is a positive
    # multiple of k and at least one slot's worth per round.
    assert rep.proposed_tokens >= rep.spec_rounds * 3 > 0
    assert rep.proposed_tokens % 3 == 0
    assert 0 <= rep.accepted_tokens <= rep.proposed_tokens
    assert rep.acceptance_rate == pytest.approx(
        rep.accepted_tokens / rep.proposed_tokens)
    # Every round emits the target's own token on top of accepted
    # drafts, so the stream always outruns the draft count.
    assert rep.as_dict()["new_tokens"] > rep.accepted_tokens
    text = render_prometheus()
    assert 'horovod_serving_spec_tokens_total{outcome="proposed"}' in text
    assert 'horovod_serving_spec_tokens_total{outcome="accepted"}' in text


@pytest.mark.parametrize("drafter", ["ngram", "model"])
def test_a_speculative_round_catches_up_first(base_params, drafter):
    """``spec_round`` takes the engine's one join and its one catch-up:
    a joining prompt's first token stays on the chip like any, and the
    host reads it (alone: no round is ever in flight here) before the
    verify round that drafts from it is built."""
    from horovod_tpu.timeline import spans
    _, params = base_params
    drafter = NgramDrafter() if drafter == "ngram" else ModelDrafter(
        CFG, params, slots=4, page_size=8, max_len=64, dtype=jnp.float32)
    rec = spans.recorder()
    rec.reset()
    _, rep = _serve_streams(params, ndev=1, spec_decode=True, spec_k=3,
                            drafter=drafter)
    account, = rec.records(name="serve.account")
    assert account.attrs["first_tokens_deferred"] \
        == account.attrs["prefills"] == 8
    prefills = rec.records(name="serve.prefill")
    assert {p.attrs["deferred"] for p in prefills} == {True}
    assert {p.attrs["behind"] for p in prefills} == {-1}
    fetches = rec.records(name="prefill.sample_fetch")
    ids = {p.id for p in prefills}
    assert fetches and not any(f.parent in ids for f in fetches)
    assert sum(f.attrs["joins"] for f in fetches) == 8
    rounds = rec.records(name="decode.round")
    assert rep.rounds_ahead == 0 and not any(r.attrs["ahead"]
                                             for r in rounds)
    # Each fetch lies before the round that follows it, under none.
    for f in fetches:
        assert all(r.end_ns <= f.start_ns or f.end_ns <= r.start_ns
                   for r in rounds)


def test_spec_fields_zero_when_disabled(base_params):
    _, params = base_params
    _, rep = _serve_streams(params, ndev=1)
    assert (rep.spec_rounds, rep.proposed_tokens,
            rep.accepted_tokens, rep.acceptance_rate) == (0, 0, 0, 0.0)


def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(ngram=2)
    # Context repeats "7 8 9": after ...7 8 the continuation is 9.
    req = Request(rid=0, prompt=np.asarray([7, 8, 9, 4, 7, 8], np.int32),
                  max_new_tokens=8, arrival_s=0.0)
    drafts = d.propose({0: req}, 3, np.asarray([0, 0], np.int32))
    assert drafts.shape == (2, 3)   # sized by last_tokens, not dict
    assert drafts[0, 0] == 9        # lookup hit
    assert drafts[1].tolist() == [0, 0, 0]  # idle slot proposes nothing


# ---------------------------------------------------------------------------
# Verify step: one dispatch, width rows bitwise equal to sequential decode
# ---------------------------------------------------------------------------


def test_verify_step_rows_bitwise_match_sequential_decode(base_params):
    _, params = base_params
    t0, W = 8, 3
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, t0 + W), 0,
                                CFG.vocab_size)
    mesh, ccfg, cache = _make_cache(1)
    plain = build_decode_step(CFG, mesh, slots=ccfg.slots,
                              page_size=ccfg.page_size,
                              pages_per_slot=ccfg.pages_per_slot)
    verify = build_verify_step(CFG, mesh, slots=ccfg.slots, width=W,
                               page_size=ccfg.page_size,
                               pages_per_slot=ccfg.pages_per_slot)

    _, kl, vl = prefill_forward(params, CFG, tokens[:, :t0])
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    # Reserve the whole window up front so both runs share one page
    # table (reserving mid-run would grow the table between dispatches).
    cache.reserve(0, t0 + W)
    table = cache.table_device()
    base = cache.lengths_device()
    active = jnp.zeros((ccfg.slots,), bool).at[0].set(True)

    # Both steps consume the pools they are given, so the sequential
    # run decodes on copies and the verify step gets the originals.
    k0, v0 = cache.k, cache.v
    rows, k, v = [], jnp.copy(k0), jnp.copy(v0)
    for i in range(W):
        tok = jnp.zeros((ccfg.slots,), jnp.int32).at[0].set(tokens[0, t0 + i])
        logits, k, v, _ = plain(params, k, v, tok, base + i, table, active,
                                no_round(ccfg.slots))
        rows.append(np.asarray(logits[0]))

    tok2 = jnp.zeros((ccfg.slots, W), jnp.int32).at[0].set(tokens[0, t0:])
    wide, _, _ = verify(params, k0, v0, tok2, base, table, active)
    assert wide.shape == (ccfg.slots, W, CFG.vocab_size)
    for i in range(W):
        np.testing.assert_array_equal(np.asarray(wide[0, i]), rows[i])


@pytest.mark.parametrize("ndev", [1, 8])
def test_audit_models_widened_verify_step(base_params, ndev):
    """PR 8 auditor gate: the width-k verify step's two row-parallel
    psums per layer must match the widened multiset exactly -- same op
    count as plain decode, ``width`` times the elements, no declines."""
    _, params = base_params
    mesh, ccfg, cache = _make_cache(ndev)
    W = 4
    step = build_verify_step(CFG, mesh, slots=ccfg.slots, width=W,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot)
    meta = meta_from_step(step)
    assert meta["kind"] == "serving_verify" and meta["width"] == W
    expected = expected_exchange(params, meta)
    assert expected.supported
    assert len(expected.ops) == 2 * CFG.num_layers
    assert all(op.kind == "psum" and
               op.elements == ccfg.slots * W * CFG.d_model
               for op in expected.ops)
    report = audit_step(
        step, params, cache.k, cache.v,
        jnp.zeros((ccfg.slots, W), jnp.int32), cache.lengths_device(),
        cache.table_device(), jnp.zeros((ccfg.slots,), bool),
        name=f"serving-verify-tp{ndev}")
    assert report.ok(), [f.message for f in report.findings]
    assert not [f for f in report.findings
                if f.rule.startswith("audit-plan-") and
                f.rule != "audit-plan-note"]


# ---------------------------------------------------------------------------
# Chunked flash prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_whole_prompt(base_params):
    _, params = base_params
    T, chunk = 24, 8
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, T), 0,
                                CFG.vocab_size)
    want_logits, want_k, want_v = prefill_forward(params, CFG, tokens)

    past = None
    for lo in range(0, T, chunk):
        logits, kl, vl = prefill_forward(params, CFG,
                                         tokens[:, lo:lo + chunk],
                                         past=past)
        past = (kl, vl)
    # Each chunk call returns FULL-context KV (past ++ chunk), so the
    # last call's cache covers the whole prompt.
    np.testing.assert_allclose(np.asarray(kl), np.asarray(want_k),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vl), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(want_logits[:, -chunk:]),
                               rtol=1e-4, atol=1e-4)


def test_engine_chunked_prefill_streams_match_whole(base_params):
    """End-to-end: admissions sliced through the chunked path emit the
    SAME tokens as whole-prompt prefill, and the chunk leg is visible to
    the span layer."""
    from horovod_tpu.timeline import spans
    _, params = base_params

    def run(chunk):
        eng = ServingEngine(CFG, params, mesh=mesh_1d(1), slots=2,
                            page_size=8, max_len=64, prefill_chunk=chunk)
        reqs = generate(LoadSpec(num_requests=4, rate_rps=100.0,
                                 prompt_lens=(24, 40), output_lens=(4, 6),
                                 vocab_size=CFG.vocab_size, seed=13))
        rep = eng.serve(reqs)
        assert rep.completed == 4, rep
        return {r.rid: tuple(r.tokens) for r in reqs}

    spans.recorder().reset()
    whole = run(0)
    rec = spans.recorder()
    rec.reset()
    chunked = run(8)
    assert chunked == whole
    # Runtime legs land in the step summary (trace-time collective legs
    # live in rec.legs); every admission above must have chunked.
    summary = rec.step_boundary(rec.step, 1.0)
    got = summary["legs"].get("serving_prefill_chunk")
    assert got and got["count"] > 0, summary["legs"].keys()


# ---------------------------------------------------------------------------
# fp8 KV compression: poisoned-page isolation
# ---------------------------------------------------------------------------


def test_fp8_compressed_page_survives_donor_page_poisoning(base_params):
    """After ``compress_cold`` migrates a page to the e4m3 pool, its
    donor f32 page goes back to the free list.  Poisoning every free
    f32 page (as a recycling slot would overwrite them) must not change
    the compressed slot's logits by one bit: the gather blends the
    e4m3 page in wherever comp_mask is set."""
    _, params = base_params
    mesh, ccfg, cache = _make_cache(1, slots=2, page_size=4, max_len=32,
                                    compress=True)
    step = build_decode_step(CFG, mesh, slots=ccfg.slots,
                             page_size=ccfg.page_size,
                             pages_per_slot=ccfg.pages_per_slot,
                             compress=True)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                                CFG.vocab_size)
    _, kl, vl = prefill_forward(params, CFG, prompt)
    cache.write_prefill(0, kl[:, 0], vl[:, 0])
    moved = cache.compress_cold(0)
    assert moved == 2   # 3 full pages, 1 hot -> 2 cold migrated
    assert cache.comp_mask[0, :2].all()
    assert (cache.page_table[0, :2] == ccfg.scratch_page).all()

    cache.reserve(0, 13)
    args = (jnp.zeros((ccfg.slots,), jnp.int32).at[0].set(prompt[0, -1]),
            cache.lengths_device(), cache.table_device(),
            jnp.zeros((ccfg.slots,), bool).at[0].set(True),
            *cache.compress_operands(), no_round(ccfg.slots))
    # The step consumes its pools: the clean run gets copies so the
    # cache's own arrays are still there to poison.
    clean, *_ = step(params, jnp.copy(cache.k), jnp.copy(cache.v), *args)

    # Poison every free f32 page with FINITE garbage, as a recycling
    # slot would (the masking contract zeroes stale pages' attention
    # weight exactly, so finite junk cancels bitwise; NaN would not).
    bad = jnp.asarray(list(cache._free), jnp.int32)
    poisoned_k = cache.k.at[:, bad].set(1e9)
    poisoned_v = cache.v.at[:, bad].set(1e9)
    dirty, *_ = step(params, poisoned_k, poisoned_v, *args)
    np.testing.assert_array_equal(np.asarray(dirty[0]),
                                  np.asarray(clean[0]))


def test_engine_kv_compress_streams_match_plain(base_params):
    _, params = base_params
    plain, _ = _serve_streams(params, ndev=1)
    compressed, _ = _serve_streams(params, ndev=1, kv_compress=True)
    assert compressed == plain


# ---------------------------------------------------------------------------
# Scheduler: admission prices the speculative write window
# ---------------------------------------------------------------------------


def test_scheduler_token_budget_gates_admission():
    def make(budget):
        ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4,
                           slots=2, page_size=4, max_len=16)
        cache = PagedKVCache(ccfg)
        cache._free = cache._free[:3]   # 12 free tokens of budget
        return cache, ContinuousBatchScheduler(2, cache,
                                               token_budget=budget)

    req = Request(rid=0, prompt=np.zeros((11,), np.int32),
                  max_new_tokens=4, arrival_s=0.0)
    # Plain decode prices prompt + 1 = 12 tokens -> 3 pages: admitted.
    cache, sched = make(1)
    sched.submit(req)
    assert [(s, r.rid) for s, r in sched.admit(0.0)] == [(0, 0)]
    # A k=4 speculative round writes up to k+1 tokens past the prompt:
    # 16 tokens -> 4 pages > 3 free, so the same request must wait.
    cache, sched = make(5)
    sched.submit(Request(rid=0, prompt=np.zeros((11,), np.int32),
                         max_new_tokens=4, arrival_s=0.0))
    assert sched.admit(0.0) == []
    cache._free = list(range(4))
    assert len(sched.admit(0.1)) == 1


def test_scheduler_note_spec_validates_and_counts():
    ccfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=4, slots=2,
                       page_size=4, max_len=16)
    sched = ContinuousBatchScheduler(2, PagedKVCache(ccfg), token_budget=4)
    sched.note_spec(3, 2)
    with pytest.raises(ValueError):
        sched.note_spec(2, 3)
    with pytest.raises(ValueError):
        ContinuousBatchScheduler(2, PagedKVCache(ccfg), token_budget=0)
