"""A dense block whose layers run several times a token over one set of
weights, through the NORMAL serving path, at a tiny size on the CPU (3
layers, 3 passes; 4 query and 4 key/value heads of 8; SwiGLU 48;
vocabulary 97, head untied), against the plain reference of
``benchmarks/families/ouro_loop.py`` (float32, ``highest``, no cache and
no planes: each pass recomputes its keys and values over the whole
context).  Logits are compared, never tokens; no assertion reads a
clock."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import ouro_loop as family
from horovod_tpu import serving
from horovod_tpu.ops import attention
from horovod_tpu.serving import kvwire, loop_dense, stepparts
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.serving.layerspec import layer_spec
from horovod_tpu.timeline import metrics, spans
from serving_families import TINY_LOOP as TINY

CFG = family.program_config(TINY)
LAYERS, PASSES, PLANES, ROW = 3, 3, 9, 2 * 4 * 8

# float32 against float32: what is left is the order of summation (flash
# blocks, a page walk against one softmax over the context) through nine
# block applications, at logits of deviation 0.95-0.99, largest 3.4 (an
# untied head drawn over its fan-in).  Measured here: 4.6e-6 and 8.8e-6
# (prefill), 2.0e-6 to 3.7e-6 (decode).  A pass that reads another pass's
# plane reads 2.5 (``test_planes_are_distinct``); a norm's scale left at
# one 1.2-3.9 and the gate's bias at zero 0.025 on the exit distribution;
# a bfloat16 cache 0.0038.
TOL = 5e-5


@pytest.fixture(scope="module")
def params():
    return loop_dense.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def reference(params):
    return family.Reference(TINY, params, pad_to=64)


def _cache(slots=3, dtype="float32"):
    spec = layer_spec(CFG)
    return serving.PagedKVCache(serving.CacheConfig(
        num_layers=spec.planes, slots=slots, page_size=8, max_len=64,
        dtype=dtype, page=spec.page, slot_state=spec.slot_state))


def _prefill_into(params, cache, slot, prompt, dtype=jnp.float32):
    _, rows, _ = layer_spec(CFG).prefill(
        params, jnp.asarray(prompt, jnp.int32)[None], dtype=dtype)
    cache.write_prefill(slot, rows[:, 0], None)


def _step(slots=3, dtype=jnp.float32):
    spec = layer_spec(CFG)
    return spec.build_step(None, slots=slots, page_size=8, pages_per_slot=8,
                           dtype=dtype), spec.step_state()


def _decode(params, cache, step, state, feeds):
    """One decode round a column of ``feeds`` (``{slot: tokens}``, all
    the same length); returns ``({slot: logits [rounds, vocab]}, state)``."""
    slots = cache.config.slots
    out = {s: [] for s in feeds}
    for t in range(len(next(iter(feeds.values())))):
        tokens = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        for s, toks in feeds.items():
            n = int(cache.lengths[s])
            cache.reserve(s, n + 1, writable_from=n)
            tokens[s], active[s] = int(toks[t]), True
        logits, cache.k, cache.v, *rest = step(
            params, cache.k, cache.v, jnp.asarray(tokens),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray(active), *state, no_round(slots))
        state = tuple(rest[:1])
        sampled, finite, told = read_told(rest[1], slots)
        assert len(told) == 0
        for s in feeds:
            cache.lengths[s] += 1
            out[s].append(np.asarray(logits[s]))
            assert sampled[s] == np.argmax(out[s][-1]) and finite[s]
    return {s: np.stack(v) for s, v in out.items()}, state


# -- the spec counts planes -----------------------------------------------------------

def test_the_spec_says_how_many_passes_and_the_pool_counts_planes(params):
    spec = layer_spec(CFG)
    assert spec.attention == "gqa" and not spec.tied_head
    assert spec.ffn == ("dense",) * LAYERS and spec.num_layers == LAYERS
    assert spec.passes == PASSES and spec.planes == PLANES
    assert spec.page == ((ROW,), None) and spec.slot_state is None
    eng = serving.ServingEngine(CFG, params, slots=3, page_size=8,
                                max_len=64)
    assert eng.cache_config.num_layers == PLANES
    assert eng.cache.v is None and eng.cache.k.shape == (PLANES, 25, 8, ROW)
    assert eng.step._meta["passes"] == PASSES
    assert eng.step._meta["num_layers"] == LAYERS
    # ONE set of weights, whatever the number of passes.
    assert sorted(k for k in params["params"] if k.startswith("layer_")) \
        == ["layer_0", "layer_1", "layer_2"]


def test_every_other_spec_makes_one_pass():
    from horovod_tpu.models.transformer import LLAMA_TINY
    from horovod_tpu.serving import cca_moe, mla_moe
    for spec in (layer_spec(LLAMA_TINY),):
        assert spec.passes == 1 and spec.planes == spec.num_layers
    for cls in (mla_moe.MlaMoeConfig, cca_moe.CcaMoeConfig):
        assert "passes" not in {f.name for f in dataclasses.fields(cls)}
    with pytest.raises(ValueError, match="passes 0"):
        dataclasses.replace(layer_spec(CFG), passes=0)


def test_a_threshold_below_one_is_refused_by_name(params):
    early = dataclasses.replace(CFG, exit_threshold=0.9)
    with pytest.raises(NotImplementedError,
                       match="^early_exit_threshold 0.9: leaving the loop"):
        serving.ServingEngine(early, params, slots=2, page_size=8,
                              max_len=32)


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
        serving.ServingEngine(CFG, params, slots=4, page_size=8, max_len=64,
                              **kwargs)


# -- against the reference -----------------------------------------------------------

@pytest.mark.parametrize("t", [8, 19])
def test_prefill_logits_and_exit_probabilities_match_the_reference(
        params, reference, t):
    prompt = np.random.RandomState(t).randint(0, 97, t)
    logits, rows, second, exits = loop_dense.prefill_forward(
        params, CFG, jnp.asarray(prompt)[None], last_only=False,
        with_exit=True)
    assert second is None and rows.shape == (PLANES, 1, t, ROW)
    want = np.asarray(reference.logits(prompt, 0, t))
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=0,
                               atol=TOL)
    _, p = reference.forward(prompt)
    assert exits.shape == (PASSES, 1, t)
    np.testing.assert_allclose(np.asarray(exits[:, 0]), p[:, :t], rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-12)
    # The gate says something: no pass has all of the mass or none.
    assert 0.02 < p[:, :t].mean(axis=1).min()
    # The last-row readout is that row.
    last = loop_dense.prefill_forward(params, CFG,
                                      jnp.asarray(prompt)[None])[0]
    np.testing.assert_allclose(np.asarray(last[0, 0]), want[-1], rtol=0,
                               atol=TOL)


# A prompt that ends ON a page boundary (16 = two pages of 8), one token
# past it, and in the middle of a page.
@pytest.mark.parametrize("prompt_len", [16, 17, 19])
def test_cached_decode_matches_the_references_full_forward(
        params, reference, prompt_len):
    """Prefill into pages, then decode through the cache, every pass out
    of its own planes: each round's logits against the reference's ONE
    full forward over prompt + fed tokens, which keeps no cache at all;
    and the exit mass the step carried against the reference's exit
    distribution summed over the decoded rows."""
    rng = np.random.RandomState(prompt_len)
    prompt, feed = rng.randint(0, 97, prompt_len), rng.randint(0, 97, 12)
    cache = _cache()
    _prefill_into(params, cache, 1, prompt)
    step, state = _step()
    got, (mass,) = _decode(params, cache, step, state, {1: feed})
    ctx = np.concatenate([prompt, feed])
    want = np.asarray(reference.logits(ctx, prompt_len, 12))
    np.testing.assert_allclose(got[1], want, rtol=0, atol=TOL)
    _, p = reference.forward(ctx)
    np.testing.assert_allclose(
        np.asarray(mass), p[:, prompt_len:prompt_len + 12].sum(axis=1),
        rtol=0, atol=1e-5)
    assert abs(float(np.asarray(mass).sum()) - 12.0) < 1e-5


def test_decode_with_the_interpreted_kernel_matches(params, reference,
                                                    monkeypatch):
    """The same rounds with ``hvd_cca_decode`` run by the Pallas
    interpreter: the plane is a traced scalar inside the loop over the
    passes."""
    rng = np.random.RandomState(4)
    prompt, feed = rng.randint(0, 97, 11), rng.randint(0, 97, 4)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    cache = _cache()
    _prefill_into(params, cache, 2, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {2: feed})
    want = np.asarray(reference.logits(np.concatenate([prompt, feed]),
                                       11, 4))
    np.testing.assert_allclose(got[2], want, rtol=0, atol=TOL)


def test_planes_are_distinct(params, reference, monkeypatch):
    """A variant that reads (and writes) the LAST pass's plane in every
    pass -- a quarter of the cache -- computes other logits, and the
    tolerance can tell."""
    rng = np.random.RandomState(3)
    prompt, feed = rng.randint(0, 97, 14), rng.randint(0, 97, 6)
    monkeypatch.setattr(loop_dense, "_plane",
                        lambda first, li: (PASSES - 1) * LAYERS + li)
    cache = _cache()
    _prefill_into(params, cache, 0, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {0: feed})
    want = np.asarray(reference.logits(np.concatenate([prompt, feed]),
                                       14, 6))
    assert np.max(np.abs(got[0] - want)) > 1000 * TOL


@pytest.mark.parametrize("name", loop_dense.NORMS + ("final_norm", "bias"))
def test_a_norm_or_the_bias_left_at_identity_fails(params, reference, name):
    """Every assumed term is computed: the four norms a layer, the final
    norm between passes and the gate's bias each move what is compared."""
    def fix(path, leaf):
        keys = [str(getattr(k, "key", "")) for k in path]
        if name == "bias" and keys[-1] == "bias":
            return jnp.zeros_like(leaf)
        if name in keys and keys[-1] == "scale":
            return jnp.ones_like(leaf)
        return leaf
    plain = jax.tree_util.tree_map_with_path(fix, params)
    prompt = np.random.RandomState(9).randint(0, 97, 12)
    logits, _, _, exits = loop_dense.prefill_forward(
        plain, CFG, jnp.asarray(prompt)[None], last_only=False,
        with_exit=True)
    want = np.asarray(reference.logits(prompt, 0, 12))
    _, p = reference.forward(prompt)
    off = max(np.max(np.abs(np.asarray(logits[0]) - want)),
              np.max(np.abs(np.asarray(exits[:, 0]) - p[:, :12])))
    assert off > 100 * TOL


def test_a_bfloat16_cache_fails_the_float32_tolerance(params, reference):
    rng = np.random.RandomState(6)
    prompt, feed = rng.randint(0, 97, 10), rng.randint(0, 97, 4)
    cache = _cache(dtype="bfloat16")
    _prefill_into(params, cache, 0, prompt)
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {0: feed})
    want = np.asarray(reference.logits(np.concatenate([prompt, feed]),
                                       10, 4))
    assert np.max(np.abs(got[0] - want)) > 50 * TOL


def test_the_exit_distribution_as_the_paper_writes_it():
    leave = np.random.RandomState(0).uniform(size=(4, 5))
    want = family.exit_distribution(leave)
    got = np.asarray(loop_dense.exit_distribution(jnp.asarray(leave)))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(want[-1], np.prod(1 - leave[:-1], axis=0))
    np.testing.assert_allclose(want[1], leave[1] * (1 - leave[0]))


# -- the shared parts under one pass, and the kernel's traced plane ---------------------

def test_one_pass_builds_the_step_there_was(params):
    """Without ``after_pass`` the builder makes no loop: the lowered step
    has no ``while``, the layers get plane 0 as a Python int, and its
    operands and results are those of before (JoyAI's and ZAYA's own test
    files hold their numbers)."""
    seen = []

    def layer(li, blk, x, pool, carried, local, rnd):
        seen.append(rnd.first_plane)
        pool = pool.at[rnd.first_plane + li, rnd.page, rnd.off].set(
            x[:, :4].astype(pool.dtype))
        return x + 1.0, pool, carried, local, None, None

    step = stepparts.build_one_chip_step(
        "plain_step", layer, num_layers=3, eps=1e-6, tied=False,
        page_size=8, scratch=16, dtype=jnp.float32, tells=(), carried=0,
        routed=False, meta={})
    pool = jnp.zeros((3, 17, 8, 4), jnp.float32)
    args = (params, pool, None, jnp.ones((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 8), jnp.int32),
            jnp.asarray([True, False]), no_round(2))
    text = step._fn.lower(*args).as_text()
    assert seen == [0, 0, 0] and all(isinstance(p, int) for p in seen)
    assert "while" not in text
    logits, pool2, second, told = step(*args)
    assert second is None and pool.is_deleted() and told.shape == (4,)
    assert step._meta["passes"] == 1
    with pytest.raises(ValueError, match="2 passes and no after_pass"):
        stepparts.build_one_chip_step(
            "x", layer, num_layers=3, eps=1e-6, tied=False, page_size=8,
            scratch=16, dtype=jnp.float32, tells=(), carried=0, meta={},
            passes=2)


def test_the_looped_step_is_one_rolled_loop(params):
    """The passes are ONE ``while`` around the layer bodies (traced once),
    the pool and the exit mass are donated and handed back."""
    step, (mass,) = _step(slots=2)
    cache = _cache(slots=2)
    _prefill_into(params, cache, 0, np.arange(12))
    cache.reserve(0, 13, writable_from=12)
    pool = cache.k
    args = (params, cache.k, None, jnp.ones((2,), jnp.int32),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray([True, False]))
    text = step._fn.lower(*args, mass, no_round(2)).as_text()
    assert text.count("stablehlo.while") == 1
    assert text.count("tf.aliasing_output") == 2
    _, cache.k, _, mass2, told = step(*args, mass, no_round(2))
    assert pool.is_deleted() and mass.is_deleted()
    assert mass2.shape == (PASSES,) and told.shape == (4,)
    assert abs(float(mass2.sum()) - 1.0) < 1e-6      # one live slot


@pytest.mark.parametrize("interpreted", [False, True])
def test_the_page_walk_with_a_traced_plane_equals_the_constant_plane(
        interpreted, monkeypatch):
    if interpreted:
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    rng = np.random.RandomState(1)
    pool = jnp.asarray(rng.normal(size=(5, 9, 8, 64)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 0], [2, 5, 0, 0]], jnp.int32)
    lengths = jnp.asarray([27, 9], jnp.int32)

    def call(plane):
        return attention.cca_decode_attention(
            q, pool, table, layer=plane, lengths=lengths, kv_heads=4,
            scale=8 ** -0.5)

    for plane in (0, 3):
        want = call(plane)
        got = jax.jit(lambda p: call(p))(jnp.int32(plane))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)
    if interpreted:
        # Either way the plane rides as the sixth prefetched scalar of
        # ONE jitted walk: every layer of a model shares its trace (a
        # kernel a layer cost a trace and a lowering a layer at start-up).
        def operands(fn, *args):
            walk, = [e for e in jax.make_jaxpr(fn)(*args).eqns
                     if "jaxpr" in e.params]
            eqn, = [e for e in walk.params["jaxpr"].eqns
                    if e.primitive.name == "pallas_call"]
            return walk.params["name"], len(eqn.invars)
        assert operands(lambda: call(3)) == operands(call, jnp.int32(3)) \
            == ("_mla_decode", 8)
    # Inside a rolled loop: plane t of 5, summed.
    looped = jax.jit(lambda: jax.lax.fori_loop(
        0, 5, lambda t, acc: acc + call(t), jnp.zeros((2, 4, 8))))()
    np.testing.assert_allclose(
        np.asarray(looped), sum(np.asarray(call(t)) for t in range(5)),
        rtol=0, atol=1e-5)
    # The latent form takes its plane the same way.
    lat = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
    want = attention.mla_decode_attention(
        lat, pool, table, layer=2, lengths=lengths, value_dim=48, scale=0.1)
    got = jax.jit(lambda p: attention.mla_decode_attention(
        lat, pool, table, layer=p, lengths=lengths, value_dim=48,
        scale=0.1))(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6)


# -- the engine, and everything that counts planes ------------------------------------------

def _engine(params, **kw):
    return serving.ServingEngine(CFG, params, slots=4, page_size=8,
                                 max_len=64, dtype=jnp.float32, **kw)


def _requests(lengths, new=6, seed=5):
    rng = np.random.RandomState(seed)
    return [serving.Request(rid=i, prompt=rng.randint(0, 97, size=n)
                            .astype(np.int32), max_new_tokens=new,
                            arrival_s=0.0)
            for i, n in enumerate(lengths)]


def test_engine_serves_it_through_the_scheduler_and_the_planes(
        params, reference):
    eng = _engine(params)
    reqs = _requests([16, 24, 16, 8, 24, 9])
    counter = metrics.registry().counter("loop.exit_mass",
                                         labelnames=("pass",))
    before = {k: c.value for k, c in counter.samples()}
    t0 = spans.recorder().records()[-1].end_ns if \
        spans.recorder().records() else 0
    report = eng.serve(reqs)
    assert report.completed == 6 and report.new_tokens == 36
    assert report.rounds_ahead >= report.decode_steps - 3
    # Release covers every plane: a page is one id over all of them.
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    # Greedy tokens are the argmax of the plain full forward.
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = np.asarray(reference.logits(ctx, len(r.prompt) - 1, 6))
        served = want[np.arange(6), np.asarray(r.tokens)]
        assert np.all(want.max(axis=-1) - served < TOL)
    recs = spans.recorder().records
    rounds = [r for r in recs(name="decode.round") if r.start_ns >= t0]
    assert len(rounds) == report.decode_steps
    assert all(r.attrs["passes"] == PASSES and r.attrs["planes"] == PLANES
               for r in rounds)
    fills = [r for r in recs(name="serve.prefill") if r.start_ns >= t0]
    assert len(fills) == 6
    assert all(r.attrs["passes"] == PASSES and r.attrs["planes"] == PLANES
               for r in fills)
    # The exit distribution's mass: published once a serve, one unit a
    # decoded token (the first token of a request is the prefill's), and
    # the carried array starts the next call from zero.
    after = {k: c.value for k, c in counter.samples()}
    grown = {k: after[k] - before.get(k, 0.0) for k in after}
    assert len(grown) == PASSES
    assert abs(sum(grown.values()) - (report.new_tokens - 6)) < 1e-3
    assert all(v > 0 for v in grown.values())
    assert not np.any(np.asarray(eng._step_state[0]))


def test_re_prefill_after_a_preemption_rebuilds_every_plane(params,
                                                            reference):
    """A request decoded four tokens, suspended (its slot freed), rebuilt
    by ``re_prefill`` from prompt + emitted tokens in another slot: the
    next rounds' logits are those of the uninterrupted run, so every
    plane of every pass is back."""
    rng = np.random.RandomState(12)
    prompt = rng.randint(0, 97, 13).astype(np.int32)
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
    pages = eng.cache.page_table[2, :2]
    filled = np.asarray(eng.cache.k[:, pages])         # [planes, 2, 8, row]
    assert all(np.any(filled[plane]) for plane in range(PLANES))
    got, _ = _decode(params, eng.cache, eng.step, eng._step_state,
                     {2: tokens[3:9]})
    ctx = np.concatenate([prompt, tokens[:9]])
    want = np.asarray(reference.logits(ctx, 13 + 3, 6))
    np.testing.assert_allclose(got[2], want, rtol=0, atol=TOL)
    assert [int(np.argmax(row)) for row in got[2]] == tokens[4:10]


@pytest.mark.parametrize("tier", ["f32", "fp8"])
def test_a_kvwire_round_trip_ships_every_plane(params, reference, tier):
    """A handoff: the prefill's ``[planes, t, row]`` rows of ONE pool,
    framed, decoded and landed in another cache (two full pages and a
    tail).  f32 is bitwise, and decoding there gives the reference's
    logits; fp8 carries every plane's pages and scales."""
    rng = np.random.RandomState(2)
    prompt, feed = rng.randint(0, 97, 19), rng.randint(0, 97, 5)
    _, rows, second = layer_spec(CFG).prefill(
        params, jnp.asarray(prompt, jnp.int32)[None], dtype=jnp.float32)
    assert second is None
    wp = kvwire.decode_kv(kvwire.encode_kv(np.asarray(rows[:, 0]),
                                           page_size=8, tier=tier))
    assert wp.length == 19 and wp.full_pages == 2 and wp.tail_tokens == 3
    assert wp.v_tail is None and wp.k_tail.shape == (PLANES, 3, ROW)
    np.testing.assert_array_equal(wp.k_tail, np.asarray(rows[:, 0, 16:]))
    if tier == "fp8":
        assert wp.kq.shape == (PLANES, 2, 8, ROW) and wp.vq is None
        assert wp.kscale.shape == (PLANES, 2, 8) and wp.vscale is None
        back = wp.kq.astype(np.float32) * wp.kscale[..., None]
        want = np.asarray(rows[:, 0, :16]).reshape(PLANES, 2, 8, ROW)
        assert np.max(np.abs(back - want)) < 0.07 * np.max(np.abs(want))
        assert all(np.any(back[plane]) for plane in range(PLANES))
        return
    assert wp.k_pages.shape == (PLANES, 2, 8, ROW) and wp.v_pages is None
    cache = _cache()
    assert kvwire.import_pages(cache, 1, wp) == 2
    assert int(cache.lengths[1]) == 19
    local = _cache()
    _prefill_into(params, local, 1, prompt)
    for c in (cache, local):
        c.reserve(1, 20, writable_from=19)
    got_pages = np.asarray(cache.k[:, cache.page_table[1, :3]])
    want_pages = np.asarray(local.k[:, local.page_table[1, :3]])
    np.testing.assert_array_equal(got_pages[:, :2], want_pages[:, :2])
    np.testing.assert_array_equal(got_pages[:, 2, :3], want_pages[:, 2, :3])
    step, state = _step()
    got, _ = _decode(params, cache, step, state, {1: feed})
    want = np.asarray(reference.logits(np.concatenate([prompt, feed]),
                                       19, 5))
    np.testing.assert_allclose(got[1], want, rtol=0, atol=TOL)
    cache.free_slot(1)
    assert cache.live_pages == 0 and cache.refcounts_balanced()


def test_the_fleets_prefill_worker_ships_the_specs_planes(params):
    """``PrefillWorker`` runs the prefill the spec names and frames what
    it hands back: every plane, one pool."""
    from horovod_tpu.serving.fleet import PrefillWorker

    class Plane:
        def put_large(self, scope, key, buf):
            self.buf = buf

    kv = Plane()
    worker = PrefillWorker("p0", CFG, params, kv, page_size=8)
    prompt = np.random.RandomState(8).randint(0, 97, 17).astype(np.int32)
    req = serving.Request(rid=3, prompt=prompt, max_new_tokens=4,
                          arrival_s=0.0)
    ticket = worker.run(req, jnp.asarray(prompt), 0.0)
    wp = kvwire.decode_kv(kv.buf)
    assert ticket.nbytes == len(kv.buf) and wp.length == 17
    assert wp.k_pages.shape == (PLANES, 2, 8, ROW) and wp.v_pages is None
    logits, rows, _ = loop_dense.prefill_forward(
        params, CFG, jnp.asarray(prompt)[None])
    assert ticket.first == int(np.argmax(np.asarray(logits[0, -1])))
    np.testing.assert_array_equal(
        wp.k_pages.reshape(PLANES, 16, ROW), np.asarray(rows[:, 0, :16]))
