"""Compressed convolutional attention with its slot state, and the top-1
MLP router over wide experts, through the NORMAL serving path, at a tiny
size on the CPU (3 layers; 4 query and 2 key/value heads of 16, 8 of
them rotated; 8 experts of 32, router stream 16; vocabulary 256, tied),
against the plain reference of ``benchmarks/families/zaya_cca_moe.py``
(float32, ``highest``, no cache and no slot state: every shift is a
shift of the whole context; every expert on every row).  Logits are
compared, never tokens; no assertion reads a clock."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import zaya_cca_moe as family
from horovod_tpu import serving
from horovod_tpu.serving import cca_moe
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.serving.layerspec import LayerSpec, layer_spec
from horovod_tpu.timeline import metrics, spans
from serving_families import TINY_CCA as TINY

CFG = family.program_config(TINY)
STATE = 2 * (4 + 2) * 16 + 16          # u, a, W_v2 h: 208 values a layer

# float32 against float32: what is left is the order of summation (flash
# blocks, the sorted expert runs, a state read back from the slot's row
# against a shift of the whole context) at logits of deviation 0.16,
# largest 0.58 (the tied head reads a 0.02 embedding).  Measured here:
# 2.7e-7 (prefill), 3.3e-7 (decode).  A vector left at its identity reads
# 0.005-0.2 (``test_a_vector_left_at_identity_fails``).
TOL = 5e-6


@pytest.fixture(scope="module")
def params():
    return cca_moe.init_params(CFG, jax.random.PRNGKey(0))


def _reference_logits(params, context, first, count):
    ref = family.Reference(TINY, params, pad_to=64)
    return np.asarray(ref.logits(np.asarray(context), first, count))


def _cache(slots=3, dtype="float32"):
    spec = layer_spec(CFG)
    return serving.PagedKVCache(serving.CacheConfig(
        num_layers=3, slots=slots, page_size=8, max_len=64, dtype=dtype,
        page=spec.page, slot_state=spec.slot_state))


def test_the_spec_describes_pages_and_a_slot_state():
    spec = layer_spec(CFG)
    assert spec.attention == "cca" and spec.tied_head
    assert spec.ffn == ("moe",) * 3
    assert spec.page == ((2 * 2 * 16,), None)
    assert spec.slot_state == STATE == CFG.slot_state_width
    assert spec.step_tells == ("experts_touched", "peak_expert_rows")
    cache = _cache()
    assert cache.v is None and cache.k.shape == (3, 25, 8, 64)
    assert cache.state.shape == (3, 3, STATE)


@pytest.mark.parametrize("field,value,match", [
    ("attention", "conv", "attention kind 'conv'"),
    ("slot_state", 0, "slot state 0 and what it holds"),
    ("slot_state_holds", None, "slot state 208 and what it holds None"),
    ("ffn", ("moe", "mlp"), "feed-forward kinds"),
    ("page_holds", (None, None), "pools")])
def test_layer_spec_refuses_what_it_cannot_describe(field, value, match):
    import dataclasses
    spec = layer_spec(CFG)
    fields = {f.name: getattr(spec, f.name)
              for f in dataclasses.fields(LayerSpec)}
    fields[field] = value
    with pytest.raises(ValueError, match=match):
        LayerSpec(**fields)


@pytest.mark.parametrize("t", [40, 33, 1])
def test_prefill_logits_match_the_reference(params, t):
    ctx = np.random.RandomState(1).randint(0, 256, size=t)
    got, rows, second, state = cca_moe.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None], last_only=False)
    assert second is None and rows.shape == (3, 1, t, CFG.page_width)
    assert state.shape == (3, 1, STATE)
    want = _reference_logits(params, ctx, 0, t)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0, atol=TOL)
    last = cca_moe.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None])[0]
    np.testing.assert_allclose(np.asarray(last[0, 0]), want[-1], rtol=0,
                               atol=TOL)


def _decode(params, cache, step, state, feeds):
    """One decode round a column of ``feeds`` (``{slot: tokens}``, all
    the same length); returns ``{slot: logits [rounds, vocab]}``."""
    slots = cache.config.slots
    out = {s: [] for s in feeds}
    for t in range(len(next(iter(feeds.values())))):
        tokens = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        for s, toks in feeds.items():
            n = int(cache.lengths[s])
            cache.reserve(s, n + 1, writable_from=n)
            tokens[s], active[s] = int(toks[t]), True
        logits, cache.k, cache.v, cache.state, *rest = step(
            params, cache.k, cache.v, jnp.asarray(tokens),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray(active), cache.state, *state, no_round(slots, 2))
        state = tuple(rest[:1])
        sampled, finite, told = read_told(rest[1], slots)
        # Top 1: each live slot touches one expert a layer, three layers.
        assert 1 <= told[0] <= 3 * len(feeds)
        assert 1 <= told[1] <= len(feeds)
        for s in feeds:
            cache.lengths[s] += 1
            out[s].append(np.asarray(logits[s]))
            # The step samples and screens its own logits.
            assert sampled[s] == np.argmax(out[s][-1]) and finite[s]
    return {s: np.stack(v) for s, v in out.items()}, state


def _prefill_into(params, cache, slot, prompt, dtype=jnp.float32):
    spec = layer_spec(CFG)
    _, rows, _, state = spec.prefill(
        params, jnp.asarray(prompt, jnp.int32)[None], dtype=dtype)
    cache.write_prefill(slot, rows[:, 0], None, state=state[:, 0])


def _step(slots=3, dtype=jnp.float32):
    spec = layer_spec(CFG)
    return spec.build_step(None, slots=slots, page_size=8, pages_per_slot=8,
                           dtype=dtype), spec.step_state()


# A prompt that ends ON a page boundary (16 = two pages of 8), one token
# past it, and in the middle of a page.
@pytest.mark.parametrize("prompt_len", [16, 17, 19])
def test_cached_decode_matches_the_references_full_forward(params,
                                                           prompt_len):
    """Prefill, then 20 tokens decoded through the page pool and the slot
    state: each round's logits against the reference's ONE full forward
    over prompt + fed tokens, which keeps no state at all."""
    rng = np.random.RandomState(prompt_len)
    prompt, feed = rng.randint(0, 256, prompt_len), rng.randint(0, 256, 20)
    cache = _cache()
    _prefill_into(params, cache, 1, prompt)
    step, state = _step()
    got, state = _decode(params, cache, step, state, {1: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]),
                             prompt_len, 20)
    np.testing.assert_allclose(got[1], want, rtol=0, atol=TOL)
    assert int(np.asarray(state[0]).sum()) == 20 * 3


def test_decode_with_interpreted_kernels_matches(params, monkeypatch):
    """The same rounds with ``hvd_cca_decode`` and ``hvd_moe_gmm`` run by
    the Pallas interpreter."""
    rng = np.random.RandomState(4)
    prompt, feed = rng.randint(0, 256, 11), rng.randint(0, 256, 6)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    cache = _cache()
    _prefill_into(params, cache, 2, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {2: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]), 11, 6)
    np.testing.assert_allclose(got[2], want, rtol=0, atol=TOL)


def test_two_slots_of_different_lengths_do_not_read_each_others_state(
        params):
    """Slots 0 and 2 decode side by side from prompts of 9 and 21 tokens;
    slot 1 idles between them with a row of garbage in its state, which
    stays as it is.  Each live slot reads what it would alone."""
    rng = np.random.RandomState(7)
    pa, pb = rng.randint(0, 256, 9), rng.randint(0, 256, 21)
    fa, fb = rng.randint(0, 256, 8), rng.randint(0, 256, 8)
    cache = _cache()
    _prefill_into(params, cache, 0, pa)
    _prefill_into(params, cache, 2, pb)
    junk = jnp.full((3, STATE), 1e3, jnp.float32)
    cache.write_state(1, junk)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {0: fa, 2: fb})
    np.testing.assert_allclose(
        got[0], _reference_logits(params, np.concatenate([pa, fa]), 9, 8),
        rtol=0, atol=TOL)
    np.testing.assert_allclose(
        got[2], _reference_logits(params, np.concatenate([pb, fb]), 21, 8),
        rtol=0, atol=TOL)
    np.testing.assert_array_equal(np.asarray(cache.state[:, 1]),
                                  np.asarray(junk))


def test_a_released_slot_starts_from_a_cleared_state(params):
    cache = _cache()
    _prefill_into(params, cache, 1, np.arange(12))
    assert np.any(np.asarray(cache.state[:, 1]))
    given = cache.state
    cache.free_slot(1)
    assert given.is_deleted()
    assert not np.any(np.asarray(cache.state))
    assert cache.live_pages == 0
    # Taken again: what the second sequence decodes owes nothing to the
    # first.
    rng = np.random.RandomState(8)
    prompt, feed = rng.randint(0, 256, 10), rng.randint(0, 256, 5)
    _prefill_into(params, cache, 1, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {1: feed})
    np.testing.assert_allclose(
        got[1], _reference_logits(params, np.concatenate([prompt, feed]),
                                  10, 5), rtol=0, atol=TOL)


def test_the_programs_consume_the_pool_and_the_state_they_write(params):
    """PR 25's rule for both kinds of state: the decode step (pool, slot
    state, routed histogram), ``write_prefill`` and ``write_state`` delete
    the arrays they are given and hand back successors."""
    cache = _cache(slots=2)
    pool, rows = cache.k, cache.state
    _prefill_into(params, cache, 0, np.arange(12))
    assert pool.is_deleted() and rows.is_deleted()
    step, (hist,) = _step(slots=2)
    cache.reserve(0, 13, writable_from=12)
    pool, rows = cache.k, cache.state
    args = (params, cache.k, None, jnp.ones((2,), jnp.int32),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray([True, False]))
    _, cache.k, _, cache.state, hist2, told = step(*args, cache.state, hist,
                                                   no_round(2, 2))
    assert pool.is_deleted() and rows.is_deleted() and hist.is_deleted()
    assert cache.state.shape == rows.shape and hist2.shape == (3, 8)
    assert told.shape == (2 + 2 + 2,) and not told.is_deleted()
    text = step._fn.lower(params, cache.k, None, *args[3:], cache.state,
                          hist2, told).as_text()
    assert text.count("tf.aliasing_output") == 3


# -- every assumed term is computed ------------------------------------------------

def _at_identity(params, name):
    """The tree with every leaf called ``name`` at its identity value."""
    def fix(path, leaf):
        if str(getattr(path[-1], "key", "")) == name:
            return jnp.full_like(leaf, cca_moe.IDENTITY[name])
        return leaf
    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.mark.parametrize("name", sorted(cca_moe.IDENTITY))
def test_a_vector_left_at_identity_fails(params, name):
    """The program computing as if it had forgotten one of the vectors
    ``config.json`` does not fix (the key temperature, the router's depth
    decay, a residual scale, a convolution's bias): outside the tolerance
    by two orders or more, prefill and cached decode alike (the depth
    decay moves only the router's scores: 0.005 where no expert flips; the
    others read 0.02-0.2)."""
    assert set(cca_moe.IDENTITY) == set(family.ASSUMED_VECTORS)
    rng = np.random.RandomState(9)
    prompt, feed = rng.randint(0, 256, 14), rng.randint(0, 256, 6)
    want = _reference_logits(params, np.concatenate([prompt, feed]), 13, 7)
    wrong = _at_identity(params, name)
    last = cca_moe.prefill_forward(
        wrong, CFG, jnp.asarray(prompt, jnp.int32)[None])[0]
    assert np.max(np.abs(np.asarray(last[0, 0]) - want[0])) > 100 * TOL
    cache = _cache()
    _prefill_into(wrong, cache, 0, prompt)
    step, state = _step()
    got, _ = _decode(wrong, cache, step, state, {0: feed})
    assert np.max(np.abs(got[0] - want[1:])) > 100 * TOL


def test_a_bfloat16_cache_fails_the_float32_tolerance(params):
    """The same comparison with the pages and the slot state kept in
    bfloat16 under a float32 program: what the rows lose to rounding is
    outside the tolerance by two orders."""
    rng = np.random.RandomState(3)
    prompt, feed = rng.randint(0, 256, 19), rng.randint(0, 256, 8)
    cache = _cache(dtype="bfloat16")
    assert cache.state.dtype == jnp.bfloat16
    _prefill_into(params, cache, 1, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {1: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]), 19, 8)
    assert np.max(np.abs(got[1] - want)) > 100 * TOL


def test_two_held_ranges_add_up_to_the_whole_layer_under_the_mlp_router(
        params):
    """The expert block run once for each of two ranges of four held
    experts (each routes over all 8 through the MLP router and computes
    its own four): the parts add up to the reference's uncut
    layer, and each part is the reference's part."""
    blk = params["params"]["layer_1"]
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    r_before = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    whole, r_ref, _ = family.ref_moe(x, r_before, blk, eps=1e-5)
    scaled = x * blk["moe_alpha"]
    total, routed = jnp.zeros_like(x), 0
    for first in (0, 4):
        part = dict(blk, moe=dict(blk["moe"], experts={
            k: v[first:first + 4]
            for k, v in blk["moe"]["experts"].items()}))
        y, r, counts = cca_moe._experts(x, r_before, part, CFG, jnp.float32,
                                        first_expert=first)
        ref_part, _, _ = family.ref_moe(x, r_before, part, eps=1e-5,
                                        first=first)
        np.testing.assert_allclose(np.asarray(scaled + y),
                                   np.asarray(ref_part), rtol=0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref),
                                   rtol=0, atol=2e-5)
        assert int(counts.sum()) == 24      # every share routes over all
        total = total + y
        routed += int(counts[first:first + 4].sum())
    assert routed == 24
    np.testing.assert_allclose(np.asarray(scaled + total), np.asarray(whole),
                               rtol=0, atol=5e-5)


# -- the engine ---------------------------------------------------------------------

def _engine(params, **kw):
    return serving.ServingEngine(CFG, params, slots=4, page_size=8,
                                 max_len=64, dtype=jnp.float32, **kw)


def _requests(lengths, new=6, seed=5):
    rng = np.random.RandomState(seed)
    return [serving.Request(rid=i, prompt=rng.randint(0, 256, size=n)
                            .astype(np.int32), max_new_tokens=new,
                            arrival_s=0.0)
            for i, n in enumerate(lengths)]


def test_engine_serves_it_through_the_scheduler_pages_and_slot_state(params):
    eng = _engine(params)
    assert eng.cache.state.shape == (3, 4, STATE)
    reqs = _requests([16, 24, 16, 8, 24, 9])
    routed = metrics.registry().counter(
        "moe.tokens_routed", labelnames=("layer", "expert"))
    before = sum(c.value for _, c in routed.samples())
    t0 = spans.recorder().records()[-1].end_ns if \
        spans.recorder().records() else 0
    report = eng.serve(reqs)
    assert report.completed == 6 and report.new_tokens == 36
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    # Every slot was released: every row of the slot state is cleared.
    assert not np.any(np.asarray(eng.cache.state))
    # Greedy tokens are the argmax of the plain full forward.
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = _reference_logits(params, ctx, len(r.prompt) - 1, 6)
        served = want[np.arange(6), np.asarray(r.tokens)]
        assert np.all(want.max(axis=-1) - served < TOL)
    recs = spans.recorder().records
    books = [r for r in recs(name="decode.bookkeep") if r.start_ns >= t0]
    assert len(books) == report.decode_steps
    assert all(1 <= r.attrs["experts_touched"] <= 3 * 4 for r in books)
    assert all(1 <= r.attrs["peak_expert_rows"] <= 4 for r in books)
    writes = [r for r in recs(name="prefill.write_state")
              if r.start_ns >= t0]
    assert len(writes) == 6
    assert all(r.attrs["state_bytes"] == 3 * STATE * 4 for r in writes)
    pairs = sum(c.value for _, c in routed.samples()) - before
    assert pairs == (report.new_tokens - 6) * 3


def test_re_prefill_after_a_preemption_reproduces_the_tokens(params):
    """A request decoded four tokens, suspended (its slot freed: pages
    gone, state cleared), rebuilt by ``re_prefill`` from prompt + emitted
    tokens in another slot: the next rounds' logits are those of the
    uninterrupted run."""
    rng = np.random.RandomState(12)
    prompt = rng.randint(0, 256, 13).astype(np.int32)
    eng = _engine(params)
    req = serving.Request(rid=0, prompt=prompt, max_new_tokens=10,
                          arrival_s=0.0)
    whole = eng.serve([req])
    assert whole.completed == 1
    tokens = list(req.tokens)
    # The same request again, cut after four tokens.
    again = serving.Request(rid=1, prompt=prompt, max_new_tokens=10,
                            arrival_s=0.0)
    again.tokens = tokens[:4]
    eng.cache.free_slot(2)
    nxt = eng.re_prefill(2, again)
    assert nxt == tokens[3] and int(eng.cache.lengths[2]) == 13 + 3
    step, state = eng.step, eng._step_state
    got, _ = _decode(params, eng.cache, step, state, {2: tokens[3:9]})
    ctx = np.concatenate([prompt, tokens[:9]])
    want = _reference_logits(params, ctx, 13 + 3, 6)
    np.testing.assert_allclose(got[2], want, rtol=0, atol=TOL)
    assert [int(np.argmax(row)) for row in got[2]] == tokens[4:10]


@pytest.mark.parametrize("kwargs,name", [
    ({"spec_decode": True}, "spec_decode"),
    ({"kv_compress": True}, "kv_compress"),
    ({"prefill_chunk": 8}, "prefill_chunk"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"adapters": {"params": {}}}, "lora"),
    ({"mesh": 2}, "tp")])
def test_what_this_model_does_not_do_raises_by_name(params, kwargs, name):
    if "mesh" in kwargs:
        from jax.sharding import Mesh
        kwargs = {"mesh": Mesh(np.asarray(jax.devices()[:2]), ("tp",))}
    with pytest.raises(NotImplementedError, match="^" + name + ":"):
        _engine(params, **kwargs)
