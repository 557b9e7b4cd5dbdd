"""A state-space mixer beside attention in every layer, its float32
recurrent state a slot's row beside the pages, through the NORMAL serving
path, at a tiny size on the CPU (2 layers; hidden 64; 10 query heads over
2 key/value heads of 8, five a group; 4 mixer heads of 8 columns, a state
of 16, 2 groups, four taps, chunks of 4 tokens; every one of the fourteen
multipliers off one; vocabulary 64, untied), against the plain reference
of ``benchmarks/families/falcon_h1_hybrid.py`` (float32, ``highest``, the
recurrence a plain scan over tokens, no chunk, no cache, no slot state).
Logits are compared, never tokens; no assertion reads a clock."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import falcon_h1_hybrid as family
from horovod_tpu import serving
from horovod_tpu.serving import ssm_hybrid
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.serving.layerspec import FEATURES, LayerSpec, layer_spec
from horovod_tpu.timeline import metrics, spans
from serving_families import TINY_SSM as TINY

CFG = family.program_config(TINY)
H_VALUES = 4 * 16 * 8                  # every head's H: 512 values a layer
CONV = 32 + 2 * 2 * 16                 # [x | B | C]: 96 columns
STATE = H_VALUES + 3 * CONV            # 800 float32 values a slot a layer

# float32 against float32: what is left is the order of summation (the
# chunked scan against the token-by-token one, flash blocks, a state read
# back from the slot's row against a scan of the whole context) at logits
# of deviation 0.12, largest 0.45.  Measured here: 3.8e-7 (prefill),
# 2.1e-7 (decode).  A multiplier left out reads 2.5e-3 (the one over C)
# to 3.5 (the head's); the three controls below hold each term to a
# hundred times the tolerance.
TOL = 1e-5


@pytest.fixture(scope="module")
def params():
    return ssm_hybrid.init_params(CFG, jax.random.PRNGKey(0))


def _reference_logits(params, context, first, count, config=TINY):
    ref = family.Reference(config, params, pad_to=64)
    return np.asarray(ref.logits(np.asarray(context), first, count))


def _cache(slots=3):
    spec = layer_spec(CFG)
    return serving.PagedKVCache(serving.CacheConfig(
        num_layers=2, slots=slots, page_size=8, max_len=64, dtype="float32",
        page=spec.page, slot_state=spec.slot_state,
        slot_state_dtype=spec.slot_state_dtype))


def test_the_spec_describes_two_pools_and_a_float32_slot_state():
    spec = layer_spec(CFG)
    assert spec.attention == "gqa" and not spec.tied_head
    assert spec.ffn == ("dense",) * 2 and spec.page == ((16,), (16,))
    assert spec.slot_state == STATE == CFG.slot_state_width
    assert spec.slot_state_dtype == "float32"
    assert spec.slot_state_step == H_VALUES == CFG.state_width
    assert spec.scan_chunk == 4 and spec.step_tells == ()
    assert set(spec.unsupported) == set(FEATURES)
    assert all(len(why) > 20 for why in spec.unsupported.values())
    # The published widths: 1,063,936 float32 values a slot a layer.
    big = dataclasses.replace(CFG, ssm_heads=32, ssm_head_dim=128,
                              ssm_state=256)
    assert big.state_width == 1_048_576 and big.conv_width == 5_120
    assert big.slot_state_width == 1_063_936 and big.in_width == 9_248


def test_the_slot_state_keeps_its_own_type_beside_bfloat16_pools():
    spec = layer_spec(CFG)
    cache = serving.PagedKVCache(serving.CacheConfig(
        num_layers=2, slots=3, page_size=8, max_len=64, dtype="bfloat16",
        page=spec.page, slot_state=spec.slot_state,
        slot_state_dtype=spec.slot_state_dtype))
    assert cache.k.dtype == cache.v.dtype == jnp.bfloat16
    assert cache.state.dtype == jnp.float32
    assert cache.state.shape == (2, 3, STATE)
    eng = serving.ServingEngine(CFG, None, slots=3, page_size=8, max_len=64,
                                dtype=jnp.bfloat16)
    assert eng.cache.state.dtype == jnp.float32
    assert eng.cache.k.dtype == jnp.bfloat16


@pytest.mark.parametrize("fields", [
    dict(slot_state_dtype="float32"), dict(slot_state_step=4),
    dict(slot_state=8, slot_state_holds="x", slot_state_step=9)])
def test_layer_spec_refuses_a_state_s_type_without_a_state(fields):
    with pytest.raises(ValueError, match="slot state"):
        LayerSpec(**dict(dict(
            attention="gqa", page=((16,), (16,)), page_holds=("k", "v"),
            ffn=("dense",), tied_head=False, max_seq_len=64,
            tp_page_dim=None, prefill=None, build_step=None,
            param_specs=None), **fields))


# A prompt of whole chunks, one that is no multiple of the chunk, one
# shorter than the convolution, and one token.
@pytest.mark.parametrize("t", [40, 33, 2, 1])
def test_prefill_logits_match_the_reference(params, t):
    ctx = np.random.RandomState(1).randint(0, 64, size=t)
    got, keys, values, state = ssm_hybrid.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None], last_only=False)
    assert keys.shape == values.shape == (2, 1, t, 16)
    assert state.shape == (2, 1, STATE) and state.dtype == jnp.float32
    want = _reference_logits(params, ctx, 0, t)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0, atol=TOL)
    last = ssm_hybrid.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None])[0]
    np.testing.assert_allclose(np.asarray(last[0, 0]), want[-1], rtol=0,
                               atol=TOL)


def _decode(params, cache, step, feeds):
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
        logits, cache.k, cache.v, cache.state, told = step(
            params, cache.k, cache.v, jnp.asarray(tokens),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray(active), cache.state, no_round(slots))
        sampled, finite, tells = read_told(told, slots)
        assert tells.size == 0
        for s in feeds:
            cache.lengths[s] += 1
            out[s].append(np.asarray(logits[s]))
            assert sampled[s] == np.argmax(out[s][-1]) and finite[s]
    return {s: np.stack(v) for s, v in out.items()}


def _prefill_into(params, cache, slot, prompt, cfg=CFG):
    spec = layer_spec(cfg)
    _, keys, values, state = spec.prefill(
        params, jnp.asarray(prompt, jnp.int32)[None], dtype=jnp.float32)
    cache.write_prefill(slot, keys[:, 0], values[:, 0], state=state[:, 0])


def _step(slots=3, cfg=CFG):
    return layer_spec(cfg).build_step(None, slots=slots, page_size=8,
                                      pages_per_slot=8, dtype=jnp.float32)


# A prompt that ends on a page boundary and on a chunk's (16), one token
# past both, in the middle of either, and one shorter than the
# convolution's three carried rows.
@pytest.mark.parametrize("prompt_len", [16, 17, 19, 2])
def test_cached_decode_matches_the_references_full_forward(params,
                                                           prompt_len):
    """Prefill (a chunked scan), then 20 tokens decoded through the page
    pools and the slot state (one step of the recurrence a round): each
    round's logits against the reference's ONE full forward over prompt +
    fed tokens, which keeps no state at all."""
    rng = np.random.RandomState(prompt_len)
    prompt, feed = rng.randint(0, 64, prompt_len), rng.randint(0, 64, 20)
    cache = _cache()
    _prefill_into(params, cache, 1, prompt)
    got = _decode(params, cache, _step(), {1: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]),
                             prompt_len, 20)
    np.testing.assert_allclose(got[1], want, rtol=0, atol=TOL)


def test_decode_with_interpreted_kernels_matches(params, monkeypatch):
    """The same rounds with ``hvd_cca_decode`` and ``hvd_ssm_decode`` run
    by the Pallas interpreter."""
    rng = np.random.RandomState(4)
    prompt, feed = rng.randint(0, 64, 11), rng.randint(0, 64, 6)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    cache = _cache()
    _prefill_into(params, cache, 2, prompt)
    step = _step()
    text = str(jax.make_jaxpr(step._fn)(
        params, cache.k, cache.v, jnp.zeros((3,), jnp.int32),
        cache.lengths_device(), cache.table_device(),
        jnp.zeros((3,), bool), cache.state, no_round(3)))
    assert text.count("hvd_ssm_decode") >= 2
    got = _decode(params, cache, step, {2: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]), 11, 6)
    np.testing.assert_allclose(got[2], want, rtol=0, atol=TOL)


def test_two_slots_of_different_lengths_do_not_read_each_others_state(
        params):
    """Slots 0 and 2 decode side by side from prompts of 9 and 21 tokens;
    slot 1 idles between them with a row of garbage in its state, which
    stays as it is.  Each live slot reads what it would alone."""
    rng = np.random.RandomState(7)
    pa, pb = rng.randint(0, 64, 9), rng.randint(0, 64, 21)
    fa, fb = rng.randint(0, 64, 8), rng.randint(0, 64, 8)
    cache = _cache()
    _prefill_into(params, cache, 0, pa)
    _prefill_into(params, cache, 2, pb)
    junk = jnp.full((2, STATE), 1e3, jnp.float32)
    cache.write_state(1, junk)
    got = _decode(params, cache, _step(), {0: fa, 2: fb})
    np.testing.assert_allclose(
        got[0], _reference_logits(params, np.concatenate([pa, fa]), 9, 8),
        rtol=0, atol=TOL)
    np.testing.assert_allclose(
        got[2], _reference_logits(params, np.concatenate([pb, fb]), 21, 8),
        rtol=0, atol=TOL)
    np.testing.assert_array_equal(np.asarray(cache.state[:, 1]),
                                  np.asarray(junk))


def _cleared() -> float:
    return metrics.registry().counter("kv.state_rows_cleared").value


def test_a_released_slot_starts_from_a_cleared_state(params):
    cache = _cache()
    _prefill_into(params, cache, 1, np.arange(12))
    assert np.any(np.asarray(cache.state[:, 1]))
    given, zeros, before = cache.state, cache._cleared, _cleared()
    cache.free_slot(1)
    # The row is cleared from the ONE row of zeros the cache keeps: the
    # release consumed the state and built nothing.
    assert given.is_deleted() and cache._cleared is zeros
    assert not zeros.is_deleted() and _cleared() == before + 1
    assert not np.any(np.asarray(cache.state))
    assert cache.live_pages == 0
    # An idle slot's release writes nothing.
    cache.free_slot(0)
    assert _cleared() == before + 1
    # Taken again: what the second sequence decodes owes nothing to the
    # first.
    rng = np.random.RandomState(8)
    prompt, feed = rng.randint(0, 64, 10), rng.randint(0, 64, 5)
    _prefill_into(params, cache, 1, prompt)
    got = _decode(params, cache, _step(), {1: feed})
    np.testing.assert_allclose(
        got[1], _reference_logits(params, np.concatenate([prompt, feed]),
                                  10, 5), rtol=0, atol=TOL)


def test_the_programs_consume_the_pools_and_the_state_they_write(params):
    cache = _cache(slots=2)
    keys, rows = cache.k, cache.state
    _prefill_into(params, cache, 0, np.arange(12))
    assert keys.is_deleted() and rows.is_deleted()
    step = _step(slots=2)
    cache.reserve(0, 13, writable_from=12)
    keys, values, rows = cache.k, cache.v, cache.state
    args = (params, cache.k, cache.v, jnp.ones((2,), jnp.int32),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray([True, False]))
    _, cache.k, cache.v, cache.state, told = step(*args, cache.state,
                                                  no_round(2))
    assert keys.is_deleted() and values.is_deleted() and rows.is_deleted()
    assert cache.state.shape == rows.shape
    assert told.shape == (2 + 2,) and not told.is_deleted()
    text = step._fn.lower(params, cache.k, cache.v, *args[3:], cache.state,
                          told).as_text()
    assert text.count("tf.aliasing_output") == 3


# -- every term of the mathematics is computed -------------------------------------

MULTIPLIERS = (
    ["embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
     "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
     "ssm_out_multiplier"]
    + [f"mlp_multipliers[{i}]" for i in range(2)]
    + [f"ssm_multipliers[{i}]" for i in range(5)])


def _without(name: str):
    """The program's config with the multiplier ``name`` left out (at
    one)."""
    if "[" not in name:
        return dataclasses.replace(CFG, **{name: 1.0})
    field, i = name[:-1].split("[")
    values = list(getattr(CFG, field))
    values[int(i)] = 1.0
    return dataclasses.replace(CFG, **{field: tuple(values)})


def _gaps_of(params, cfg):
    """The largest distance from the reference of ``cfg``'s prefill
    (every row of a 19-token prompt) and of 6 rounds decoded behind an
    11-token prompt."""
    rng = np.random.RandomState(21)
    ctx = rng.randint(0, 64, 19)
    got = ssm_hybrid.prefill_forward(
        params, cfg, jnp.asarray(ctx, jnp.int32)[None], last_only=False)[0]
    prefill = np.abs(np.asarray(got[0])
                     - _reference_logits(params, ctx, 0, 19)).max()
    prompt, feed = rng.randint(0, 64, 11), rng.randint(0, 64, 6)
    cache = _cache()
    _prefill_into(params, cache, 0, prompt, cfg)
    got = _decode(params, cache, _step(cfg=cfg), {0: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]), 11, 6)
    return prefill, np.abs(got[0] - want).max()


def test_the_fourteen_multipliers_are_fourteen():
    assert len(MULTIPLIERS) == 14
    assert all(m != 1.0 for m in (
        CFG.embedding_multiplier, CFG.lm_head_multiplier,
        CFG.attention_in_multiplier, CFG.attention_out_multiplier,
        CFG.key_multiplier, CFG.ssm_in_multiplier, CFG.ssm_out_multiplier,
        *CFG.mlp_multipliers, *CFG.ssm_multipliers))
    assert max(_gaps_of(ssm_hybrid.init_params(CFG, jax.random.PRNGKey(0)),
                        CFG)) < TOL


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_left_out_fails(params, name):
    prefill, decode = _gaps_of(params, _without(name))
    assert prefill > 100 * TOL and decode > 100 * TOL, (prefill, decode)


def test_the_gate_applied_after_the_norm_fails(params, monkeypatch):
    """``mamba_norm_before_gate`` false: the gate first.  A program that
    norms ``y`` and gates it afterwards is another model."""
    def norm_then_gate(y, z, ssm, cfg, dtype):
        lead = y.shape[:-1]
        g = y.reshape(*lead, cfg.ssm_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.rms_eps)
        g = g.reshape(*lead, cfg.d_ssm) * ssm["norm"]["scale"] \
            * jax.nn.silu(z)
        return ssm_hybrid._dense_out(g, ssm["w_out"], dtype) \
            * cfg.ssm_out_multiplier

    monkeypatch.setattr(ssm_hybrid, "_gated_out", norm_then_gate)
    prefill, decode = _gaps_of(params, CFG)
    assert prefill > 100 * TOL and decode > 100 * TOL, (prefill, decode)


@pytest.mark.parametrize("lost", ["everything", "the_recurrent_state",
                                  "the_convolution_s_rows"])
def test_a_state_not_carried_from_the_prefill_into_the_rounds_fails(
        params, lost):
    rng = np.random.RandomState(5)
    prompt, feed = rng.randint(0, 64, 13), rng.randint(0, 64, 6)
    cache = _cache()
    _prefill_into(params, cache, 1, prompt)
    part = {"everything": slice(None), "the_recurrent_state":
            slice(0, H_VALUES), "the_convolution_s_rows":
            slice(H_VALUES, None)}[lost]
    cache.state = cache.state.at[:, 1, part].set(0.0)
    got = _decode(params, cache, _step(), {1: feed})
    want = _reference_logits(params, np.concatenate([prompt, feed]), 13, 6)
    assert np.abs(got[1] - want).max() > 100 * TOL


def test_a_bfloat16_engine_stays_near_and_fails_the_float32_tolerance(
        params):
    """What bfloat16 operands cost at this size: far beyond ``TOL``
    (the comparison would see a lower precision), far under a term left
    out."""
    rng = np.random.RandomState(9)
    ctx = rng.randint(0, 64, 24)
    got = ssm_hybrid.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None], dtype=jnp.bfloat16,
        last_only=False)[0]
    gap = np.abs(np.asarray(got[0])
                 - _reference_logits(params, ctx, 0, 24)).max()
    assert 10 * TOL < gap < 0.05, gap


# -- through the engine --------------------------------------------------------------

def _engine(params, **kw):
    return serving.ServingEngine(CFG, params, slots=4, page_size=8,
                                 max_len=64, dtype=jnp.float32, **kw)


def _requests(lengths, new=6, seed=5):
    rng = np.random.RandomState(seed)
    return [serving.Request(rid=i, prompt=rng.randint(0, 64, size=n)
                            .astype(np.int32), max_new_tokens=new,
                            arrival_s=0.0)
            for i, n in enumerate(lengths)]


def test_engine_serves_it_through_the_scheduler_pages_and_slot_state(params):
    eng = _engine(params)
    assert eng.cache.state.shape == (2, 4, STATE)
    assert eng.step.meta["arch"] == "ssm_hybrid"
    reqs = _requests([16, 24, 16, 8, 24, 9])
    written = metrics.registry().counter("kv.state_bytes_written")
    before, cleared = written.value, _cleared()
    rec = spans.recorder()
    rec.reset()
    report = eng.serve(reqs)
    assert report.completed == 6 and report.new_tokens == 36
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    # Every slot was released: every row of the slot state is cleared.
    assert not np.any(np.asarray(eng.cache.state))
    assert _cleared() == cleared + 6
    assert written.value == before + 6 * 2 * STATE * 4
    # Greedy tokens are the argmax of the plain full forward.
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = _reference_logits(params, ctx, len(r.prompt) - 1, 6)
        served = want[np.arange(6), np.asarray(r.tokens)]
        assert np.all(want.max(axis=-1) - served < TOL)
    writes = rec.records(name="prefill.write_state")
    assert len(writes) == 6
    assert all(r.attrs["state_bytes"] == 2 * STATE * 4 for r in writes)
    rounds = rec.records(name="decode.round")
    assert len(rounds) == report.decode_steps
    # What a round's update must read and write again: H of every live
    # slot in every plane, float32; the convolution's rows are not in it.
    assert all(r.attrs["state_planes"] == 2 for r in rounds)
    assert all(r.attrs["state_bytes"]
               == r.attrs["slots"] * 2 * H_VALUES * 4 for r in rounds)
    prefills = rec.records(name="serve.prefill")
    assert all(p.attrs["scan_chunks"]
               == p.attrs["group"] * -(-p.attrs["prompt_len"] // 4)
               for p in prefills)
    assert sum(p.attrs["scan_chunks"] for p in prefills) \
        == sum(-(-n // 4) for n in (16, 24, 16, 8, 24, 9))


def test_the_other_blocks_rounds_say_nothing_of_a_state():
    from serving_families import dense
    cfg, p = dense()
    eng = serving.ServingEngine(cfg, p, slots=2, page_size=4, max_len=32)
    rec = spans.recorder()
    rec.reset()
    eng.serve([serving.Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                               max_new_tokens=3, arrival_s=0.0)])
    assert all("state_bytes" not in r.attrs and "state_planes" not in r.attrs
               for r in rec.records(name="decode.round"))
    assert all("scan_chunks" not in r.attrs
               for r in rec.records(name="serve.prefill"))


def test_a_slot_taken_again_after_a_release_starts_clean(params):
    """Two waves over ONE slot: the second request's tokens are those it
    is served alone in a fresh engine."""
    reqs = _requests([9, 14, 11], new=5, seed=3)
    eng = serving.ServingEngine(CFG, params, slots=1, page_size=8,
                                max_len=64, dtype=jnp.float32)
    assert eng.serve(reqs).completed == 3
    for r in reqs:
        alone = serving.Request(rid=9, prompt=r.prompt, max_new_tokens=5,
                                arrival_s=0.0)
        fresh = serving.ServingEngine(CFG, params, slots=1, page_size=8,
                                      max_len=64, dtype=jnp.float32)
        fresh.step, fresh._prefill = eng.step, eng._prefill
        assert fresh.serve([alone]).completed == 1
        assert alone.tokens == r.tokens


def test_re_prefill_after_a_preemption_rebuilds_the_state(params):
    """A request decoded four tokens, suspended (its slot freed: pages
    gone, state cleared), rebuilt by ``re_prefill`` from prompt + emitted
    tokens in another slot: the next rounds' logits are those of the
    uninterrupted run."""
    rng = np.random.RandomState(12)
    prompt = rng.randint(0, 64, 13).astype(np.int32)
    eng = _engine(params)
    req = serving.Request(rid=0, prompt=prompt, max_new_tokens=10,
                          arrival_s=0.0)
    assert eng.serve([req]).completed == 1
    tokens = list(req.tokens)
    again = serving.Request(rid=1, prompt=prompt, max_new_tokens=10,
                            arrival_s=0.0)
    again.tokens = tokens[:4]
    eng.cache.free_slot(2)
    nxt = eng.re_prefill(2, again)
    assert nxt == tokens[3] and int(eng.cache.lengths[2]) == 13 + 3
    got = _decode(params, eng.cache, eng.step, {2: tokens[3:9]})
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


def test_the_fleet_refuses_its_handoff_by_name(params):
    with pytest.raises(NotImplementedError, match="handoff|slot"):
        layer_spec(CFG).require(handoff=True)
