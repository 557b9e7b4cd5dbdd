"""EVA attention -- an exact, aligned window beside one pooled key and
value a chunk of the windows behind it, one softmax over both -- through
the NORMAL serving path, at a tiny size on the CPU that keeps the
structure (2 layers; hidden 128; 4 query heads over 4 key/value heads of
32; a window of 64 bytes in chunks and pages of 16; a vocabulary of 320
under a head of 8 x 320 columns, untied; norms with a unit offset),
against the plain reference of ``benchmarks/families/eva_dense.py``
(float32, ``highest``, the whole sequence under one explicit mask: no
ring, no page, no window at a time, no round).  Logits of ALL eight heads
are compared, never tokens; no assertion reads a clock."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import eva_dense as family
from horovod_tpu import serving
from horovod_tpu.ops import attention as _attn
from horovod_tpu.serving import eva_dense
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.serving.layerspec import FEATURES, LayerSpec, layer_spec
from horovod_tpu.timeline import metrics, spans
from serving_families import TINY_EVA as TINY

CFG = family.program_config(TINY)
WINDOW, CHUNK, PAGE, RING = 64, 16, 16, 5
COLUMNS = 8 * 320
PAD = 256                      # the reference's one length, = max_len

# float32 against float32: what is left is the order of summation (a
# chunk pooled out of a ring page against the whole sequence's chunks at
# once, a window at a time against one mask over everything, flash blocks,
# a softmax over gathered pages) at logits of deviation 1.0, largest 4.9.
# Measured here: 2.1e-6 to 4.1e-6 (prefill), 3.6e-6 (decode).  The three
# controls below read 1.1 (pooled rows a window early), 2.6 (mean
# pooling) and 3.3 (two softmaxes averaged): each fifty thousand times
# the tolerance and more; a bfloat16 engine reads 0.05.
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return eva_dense.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def reference(params):
    return family.Reference(TINY, params, pad_to=PAD)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 320, size=n).astype(
        np.int32)


def _cache(slots=3, cfg=CFG, max_len=PAD, page=PAGE):
    spec = layer_spec(cfg)
    return serving.PagedKVCache(serving.CacheConfig(
        num_layers=spec.planes, slots=slots, page_size=page,
        max_len=max_len, dtype="float32", page=spec.page,
        window_layers=spec.window_planes, window=spec.window,
        row_tokens=spec.row_tokens))


def _step(cache, cfg=CFG):
    c = cache.config
    return eva_dense.build_decode_step(
        cfg, None, slots=c.slots, page_size=c.page_size,
        pages_per_slot=c.pages_per_slot, dtype=jnp.float32)


def _prefill_into(params, cache, slot, prompt, cfg=CFG):
    _, kbar, vbar, (kring, vring) = eva_dense.prefill_forward(
        params, cfg, jnp.asarray(prompt, jnp.int32)[None])
    cache.write_prefill(slot, kbar[:, 0], vbar[:, 0],
                        window_rows=(kring[:, 0], vring[:, 0]))


def _decode(params, cache, step, feeds, rounds):
    """``rounds`` decode rounds; slot ``s`` is live while ``feeds[s]`` has
    a token left.  Returns ``{slot: logits [fed, COLUMNS]}``."""
    slots = cache.config.slots
    out = {s: [] for s in feeds}
    for t in range(rounds):
        tokens = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        live = [s for s, toks in feeds.items() if t < len(toks)]
        for s in live:
            n = int(cache.lengths[s])
            cache.reserve(s, n + 1, writable_from=n)
            tokens[s], active[s] = int(feeds[s][t]), True
        logits, cache.k, cache.v, told = step(
            params, cache.k, cache.v, jnp.asarray(tokens),
            cache.lengths_device(), cache.table_device(),
            jnp.asarray(active), cache.window_table_device(),
            no_round(slots))
        sampled, finite, tells = read_told(told, slots)
        assert tells.size == 0
        for s in live:
            cache.lengths[s] += 1
            out[s].append(np.asarray(logits[s]))
            # The round's token is head 0's greedy one.
            assert sampled[s] == np.argmax(out[s][-1][:320]) and finite[s]
    return {s: np.stack(v) for s, v in out.items()}


# -- what the block says of itself ----------------------------------------------

def test_the_spec_describes_chunked_layers_with_a_plane_in_each_group():
    spec = layer_spec(CFG)
    assert isinstance(spec, LayerSpec) and spec.attention == "gqa"
    assert spec.attn_kinds == ("chunked", "chunked")
    assert spec.planes == 2 and spec.window_planes == 2
    assert (spec.window, spec.row_tokens, spec.window_aligned) == (
        64, 16, True)
    assert spec.page == ((128,), (128,)) and spec.slot_state is None
    assert set(spec.unsupported) == set(FEATURES)
    cache = _cache()
    c = cache.config
    # One pair of pools holds both groups: a slot's pooled pages (256 bytes
    # of context a page), the scratch page, then every slot's ring of five.
    assert (c.pages_per_slot, c.window_pages_per_slot) == (1, 5)
    assert cache.k.shape == cache.v.shape == (2, 3 * 1 + 1 + 3 * 5, 16, 128)
    assert cache.wk is None and cache.wv is None and cache.carried == ()
    assert c.window_first_page == 4 and cache.window_table.min() == 4
    assert c.layout()["kv_shape"] == [2, 19, 16, 128]
    assert "window_kv_shape" not in c.layout()


@pytest.mark.parametrize("fields", [
    dict(row_tokens=16),
    dict(attn_kinds=("chunked", "chunked"), window=64),
    dict(attn_kinds=("chunked", "full"), window=64, row_tokens=16),
    dict(attn_kinds=("chunked", "chunked"), window=60, row_tokens=16)])
def test_layer_spec_refuses_chunks_without_chunked_layers(fields):
    import dataclasses
    base = layer_spec(CFG)
    plain = dict(attn_kinds=None, window=None, row_tokens=1)
    with pytest.raises(ValueError, match="chunked layers|window"):
        dataclasses.replace(base, **dict(plain, **fields))


# -- prefill -----------------------------------------------------------------

# One window and a ragged chunk; a chunk's and a window's edge; three
# windows and a ragged chunk; shorter than a chunk.
@pytest.mark.parametrize("t", [37, 64, 150, 5])
def test_prefill_logits_match_the_reference(params, reference, t):
    toks = _tokens(t, t)
    got, kbar, vbar, (kring, vring) = eva_dense.prefill_forward(
        params, CFG, jnp.asarray(toks)[None], last_only=False)
    want = np.asarray(reference.logits(toks, 0, t))
    assert got.shape == (1, t, COLUMNS)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0, atol=TOL)
    # What it hands back: a pooled row a WHOLE chunk, the last window's
    # exact rows, nothing for the ragged chunk.
    assert kbar.shape == vbar.shape == (2, 1, t // 16, 128)
    assert kring.shape == vring.shape == (2, 1, t % 64, 128)


# -- prefill, then decoding through the paged cache -----------------------------

def _served_against_reference(params, reference, cache, step):
    """Slot 0: a prompt that ends mid-chunk (37 = 2 x 16 + 5) decodes 100
    bytes across two window edges (64, 128).  Slot 1: a prompt of a whole
    window and a chunk's edge (80) decodes 30 beside it.  Slot 2 joins at
    round 40 with a prompt shorter than a chunk.  Returns the widest gap
    to the reference over all eight heads' logits."""
    seqs = {0: _tokens(1, 37 + 100), 1: _tokens(2, 80 + 30),
            2: _tokens(3, 5 + 40)}
    cut = {0: 37, 1: 80, 2: 5}
    for s in (0, 1):
        _prefill_into(params, cache, s, seqs[s][:cut[s]])
    got = _decode(params, cache, step,
                  {s: seqs[s][cut[s]:cut[s] + 40] for s in (0, 1)}, 40)
    _prefill_into(params, cache, 2, seqs[2][:5])
    more = _decode(params, cache, step,
                   {0: seqs[0][77:], 2: seqs[2][5:]}, 60)
    got[0] = np.concatenate([got[0], more[0]])
    got[2] = more[2]
    worst = 0.0
    for s, rows in got.items():
        want = np.asarray(reference.logits(seqs[s], cut[s], len(rows)))
        worst = max(worst, float(np.abs(rows - want).max()))
    return worst


def test_cached_decode_matches_the_references_full_forward(params,
                                                           reference):
    cache = _cache()
    assert _served_against_reference(params, reference, cache,
                                     _step(cache)) < TOL
    # Slot 0 is at 137 bytes: nine pooled rows in one growing page, its
    # ring full; slot 1 finished at 110 and still holds its pages.
    assert int(cache.lengths[0]) == 137
    assert int(cache._allocated[0]) == 1 and int(cache._wallocated[0]) == 5
    # Release and re-admission: the slot taken again serves another
    # sequence out of the same pages, stale rows and all.
    for s in range(3):
        cache.free_slot(s)
    assert cache.live_pages == 0 and cache.refcounts_balanced()
    assert _served_against_reference(params, reference, cache,
                                     _step(cache)) < TOL


def _faulty_attention(fault):
    """``eva_decode_attention`` with one thing wrong: ``"early"`` shows
    the pooled rows of the window IN PROGRESS too (its whole chunks, one
    window early); ``"apart"`` normalises the exact rows and the pooled
    rows each by itself and averages the two."""

    def attend(q, pool, page_table, window_table, *, layer, lengths, window,
               row_tokens, kv_heads, scale, values, force_reference=False):
        b, h, d = q.shape
        page, ring = pool.shape[2], window_table.shape[1]
        w = jnp.maximum(lengths - 1, 0) // window
        pooled = w * (window // row_tokens)
        if fault == "early":
            pooled = lengths // row_tokens
        exact = jnp.where(lengths > 0, lengths - w * window, 0)
        turned = jnp.take_along_axis(
            window_table, ((w * (window // page))[:, None]
                           + jnp.arange(ring)) % ring, axis=1)

        def view(z, table):
            return z[layer, table].reshape(b, -1, kv_heads, d)

        def part(table, n):
            keys, vals = view(pool, table), view(values, table)
            live = (jnp.arange(keys.shape[1])[None] < n[:, None])[
                :, None, :]
            s = jnp.einsum("bhd,bshd->bhs", q, keys) * scale
            return jnp.where(live, s, -1e30), vals, live

        s1, v1, l1 = part(page_table, pooled)
        s2, v2, l2 = part(turned, exact)
        if fault == "apart":
            o1 = jnp.einsum("bhs,bshd->bhd", jnp.where(
                l1, jax.nn.softmax(s1, -1), 0.0), v1)
            o2 = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s2, -1), v2)
            return jnp.where((pooled > 0)[:, None, None], (o1 + o2) / 2, o2)
        p = jax.nn.softmax(jnp.concatenate([s1, s2], -1), -1)
        return jnp.einsum("bhs,bshd->bhd", p, jnp.concatenate([v1, v2], 1))

    return attend


def _mean_pooling(k, v, attn, cfg):
    def mean(z):
        return jnp.mean(z.reshape(*z.shape[:-1], cfg.num_kv_heads,
                                  cfg.head_dim), axis=-3).reshape(
            *z.shape[:-2], cfg.kv_width)
    return mean(k), mean(v)


@pytest.mark.parametrize("fault", ["sound", "mean_pooling", "early",
                                   "apart"])
def test_three_other_models_fail_the_comparison(params, reference,
                                                monkeypatch, fault):
    """The comparison above, with the program's attention taken apart
    into the same gathered views: sound, it passes; with a mean in the
    softmax pooling's place (in the rounds that fill a chunk), with the
    pooled rows shown one window early, or with the two sets normalised
    apart and averaged, it fails by far."""
    if fault == "mean_pooling":
        real = eva_dense.pool_chunks
        # (The prefill's whole chunks stay sound: its leading dims are
        # [batch, chunks]; a round's are [slots].)
        monkeypatch.setattr(eva_dense, "pool_chunks", lambda k, v, a, c: (
            real if k.ndim == 4 else _mean_pooling)(k, v, a, c))
    elif fault != "sound":
        monkeypatch.setattr(eva_dense, "eva_decode_attention",
                            _faulty_attention(fault))
    cache = _cache()
    gap = _served_against_reference(params, reference, cache, _step(cache))
    if fault == "sound":
        assert gap < TOL
    else:
        assert gap > 1000 * TOL, gap


def test_decode_with_interpreted_kernels_matches(monkeypatch):
    """Where every boundary falls on a page's edge (a window of 16 in
    chunks and pages of 4: four pooled rows a window, ONE growing page)
    the round walks one composed table: ``hvd_eva_decode``, interpreted
    here, against the same reference."""
    tiny = dict(TINY, window_size=16, chunk_size=4,
                serving={"slots": 2, "page_size": 4, "max_len": 64})
    cfg = family.program_config(tiny)
    p = eva_dense.init_params(cfg, jax.random.PRNGKey(1))
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn, "MLA_PAGES_PER_BLOCK", 4)
    monkeypatch.setattr(_attn, "MLA_KEYS_PER_SUB_BLOCK", 16)
    cache = _cache(2, cfg, 64, 4)
    step = _step(cache, cfg)
    assert "hvd_eva_decode" in str(step._fn.trace(
        p, cache.k, cache.v, jnp.zeros((2,), jnp.int32),
        cache.lengths_device(), cache.table_device(),
        jnp.zeros((2,), bool), cache.window_table_device(),
        no_round(2)).jaxpr)
    seqs = {0: _tokens(4, 50), 1: _tokens(5, 40)}
    cut = {0: 6, 1: 17}
    for s in seqs:
        _prefill_into(p, cache, s, seqs[s][:cut[s]], cfg)
    got = _decode(p, cache, step, {s: seqs[s][cut[s]:] for s in seqs}, 44)
    ref = family.Reference(tiny, p, pad_to=64, query_block=64)
    for s, rows in got.items():
        want = np.asarray(ref.logits(seqs[s], cut[s], len(rows)))
        np.testing.assert_allclose(rows, want, rtol=0, atol=TOL)


# -- the engine -------------------------------------------------------------

def _engine(params, cfg=CFG, **kw):
    return serving.ServingEngine(cfg, params, **dict(dict(
        slots=3, page_size=16, max_len=PAD, dtype=jnp.float32), **kw))


def _requests(sizes, seed=5):
    return [serving.Request(rid=i, prompt=_tokens(seed + i, n),
                            max_new_tokens=m, arrival_s=0.0)
            for i, (n, m) in enumerate(sizes)]


SIZES = [(37, 100), (70, 20), (5, 9), (130, 40), (64, 3)]


def _check_served(params, reqs, config=TINY, pad=PAD, **kw):
    ref = family.Reference(config, params, pad_to=pad, **kw)
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(ref.logits(ctx, len(r.prompt) - 1,
                                     len(r.tokens)))[:, :320]
        served = want[np.arange(len(r.tokens)), np.asarray(r.tokens)]
        assert np.all(want.max(axis=-1) - served < TOL)


def test_engine_serves_it_through_the_scheduler_and_both_groups(params):
    eng = _engine(params)
    assert eng.step.meta["arch"] == "eva_dense"
    assert eng.step.meta["attention"] == "walk"
    pooled = metrics.registry().counter("kv.pooled_rows_written")
    before = pooled.value
    rec = spans.recorder()
    rec.reset()
    reqs = _requests(SIZES)
    report = eng.serve(reqs)
    assert report.completed == 5 and report.new_tokens == 172
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    _check_served(params, reqs)
    # Every whole chunk of every finished sequence was pooled once: by
    # its prefill or by the round that filled it.  (A sequence's last
    # token is sampled and never written.)
    assert pooled.value - before == sum((n + m - 1) // 16
                                        for n, m in SIZES)
    prefills = {p.attrs["rid"]: p.attrs
                for p in rec.records(name="serve.prefill")}
    for i, (n, _) in enumerate(SIZES):
        a = prefills[i]
        assert (a["windows"], a["chunks_pooled"], a["pending_rows"],
                a["window_rows"]) == (-(-n // 64), n // 16, n % 16, n % 64)
    rounds = rec.records(name="decode.round")
    assert len(rounds) == report.decode_steps
    assert sum(r.attrs["chunks_pooled"] for r in rounds) == sum(
        (n + m - 1) // 16 - n // 16 for n, m in SIZES)
    # 37 + 100 crosses 64 and 128; 70 + 20, 5 + 9 and 130 + 40 (170 <
    # 192) cross none; after a prompt of 64 the first byte a round writes
    # IS position 64, a window's first.
    assert sum(r.attrs["window_crossings"] for r in rounds) == 3
    for r in rounds:
        a = r.attrs
        assert a["attended_rows"] == a["window_tokens"] + a["pooled_rows"]
        assert a["pooled_rows"] % 4 == 0 and a["window_planes"] == 2
        assert a["attended_rows"] <= a["live_tokens"]
    assert any(r.attrs["pooled_rows"] for r in rounds)


def test_the_other_blocks_rounds_say_nothing_of_pooled_rows():
    from serving_families import dense
    cfg, p = dense()
    eng = serving.ServingEngine(cfg, p, slots=2, page_size=4, max_len=32)
    rec = spans.recorder()
    rec.reset()
    eng.serve([serving.Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                               max_new_tokens=3, arrival_s=0.0)])
    for name in ("attended_rows", "pooled_rows", "chunks_pooled",
                 "window_crossings"):
        assert all(name not in r.attrs
                   for r in rec.records(name="decode.round"))
    assert all("windows" not in r.attrs and "pending_rows" not in r.attrs
               for r in rec.records(name="serve.prefill"))


def test_a_second_engine_of_another_window_in_one_process(params):
    """Two engines, windows of 64 and of 32, one after the other and then
    the first again: each serves its own model (nothing a window's length
    decides is cached under a key that leaves it out)."""
    narrow = dict(TINY, window_size=32)
    sizes = [(37, 60), (70, 20)]
    first = _engine(params)
    a = _requests(sizes)
    assert first.serve(a).completed == 2
    second = _engine(params, family.program_config(narrow))
    b = _requests(sizes)
    assert second.serve(b).completed == 2
    c = _requests(sizes)
    assert first.serve(c).completed == 2
    _check_served(params, a)
    _check_served(params, b, narrow)
    assert [r.tokens for r in c] == [r.tokens for r in a]
    assert [r.tokens for r in b] != [r.tokens for r in a]


def test_the_control_plane_serves_what_serve_serves(params):
    from horovod_tpu.serving import ServingControlPlane
    kw = dict(slots=3, page_size=16, max_len=PAD, dtype=jnp.float32)
    want = _requests(SIZES)
    assert _engine(params).serve(want).completed == 5
    plane = ServingControlPlane(CFG, params, devices=jax.devices()[:1],
                                initial_tp=1, **kw)
    got = _requests(SIZES)
    rep = plane.serve(got)
    assert rep.lost_requests == 0
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert plane.engine.cache.live_pages == 0


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


def test_the_fleet_refuses_its_handoff_by_name():
    with pytest.raises(NotImplementedError, match="^handoff:"):
        layer_spec(CFG).require(handoff=True)


def test_a_chunk_that_is_not_whole_pages_is_refused(params):
    with pytest.raises(NotImplementedError, match="WHOLE pages"):
        _engine(params, page_size=32)


def test_a_bfloat16_engine_stays_near_and_fails_the_float32_tolerance(
        params, reference):
    """The same comparison with the engine computing in bfloat16: near
    (the limit the benchmark's cell sets is of this order), and a
    hundred times outside the float32 tolerance."""
    toks = _tokens(8, 150)
    got = eva_dense.prefill_forward(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), params), CFG,
        jnp.asarray(toks)[None], dtype=jnp.bfloat16, last_only=False)[0]
    gap = np.abs(np.asarray(got[0]) - np.asarray(
        reference.logits(toks, 0, 150))).max()
    assert 100 * TOL < gap < 0.5
