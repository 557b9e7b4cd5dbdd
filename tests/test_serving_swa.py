"""Window and full attention layers in one cache manager, and a routed
layer that holds a share of its experts: ``serving/swa_moe.py`` through
``ServingEngine`` against the benchmark family's plain reference, the
share and the vocabulary's slices adding up to the uncut model, the
window in both attention paths against ``attention_reference`` under the
band's mask, the cache's window group (nine pages a slot at the served
sizes, reused pages' stale rows unreachable, both groups returned), the
lowering of the other served cells' walks, and ``moe_ffn``'s rows at a
share.  Tiny sizes, float32, no clock."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import exaone_swa_moe as family
from horovod_tpu import serving
from horovod_tpu.ops import attention as _attn
from horovod_tpu.ops import moe
from horovod_tpu.serving import kvcache, layerspec, stepparts, swa_moe
from horovod_tpu.timeline import metrics as _metrics
from serving_families import TINY_SWA as TINY
from serving_families import bf16_prefill_gaps as _bf16_prefill_gaps
from serving_families import lowered_for_tpu as _lowered_for_tpu
from serving_families import swa_moe as _tiny

KINDS = ("window", "window", "full", "window")


def _requests(lens, vocab=32, seed=0):
    rng = np.random.RandomState(seed)
    return [serving.Request(
        rid=i, prompt=rng.randint(0, vocab, size=n).astype(np.int32),
        max_new_tokens=m, arrival_s=0.0) for i, (n, m) in enumerate(lens)]


def _reused() -> float:
    return _metrics.registry().counter("kv.window_pages_reused").value


# -- (1) the engine against the plain reference ------------------------------------

@pytest.mark.parametrize("kernels", ["off", "interpreted"])
def test_engine_agrees_with_the_plain_reference(monkeypatch, kernels):
    """Prefill, then decode through both groups of planes, contexts that
    run past the window (8 tokens, pages of 4: a ring of 3) by several
    pages: every served token is the reference's best to rounding, over
    the same share (experts 4-7 of 16, 32 of 64 rows)."""
    if kernels == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    cfg, params = _tiny()
    eng = serving.ServingEngine(cfg, params, slots=3, page_size=4,
                                max_len=64, dtype=jnp.float32)
    assert eng.step.meta["attn_kinds"] == KINDS
    assert eng.step.meta["experts_held"] == 4
    before = _reused()
    reqs = _requests([(5, 20), (19, 30), (33, 12), (8, 40), (3, 3)])
    report = eng.serve(reqs)
    assert report.completed == 5 and report.new_tokens == 105
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    # Every window plane wrote pages again: 12-40 decoded tokens a slot
    # over a ring of 12 rows.
    assert _reused() - before >= 20
    gaps = family.served_gaps(
        TINY, params, [(r.prompt, r.tokens) for r in reqs], 64)
    assert gaps["tokens_compared"] == gaps["tokens_sampled"] == 105
    assert gaps["served_logit_gap_max"] < 1e-3


@pytest.mark.parametrize("t", [7, 16, 21, 29])
def test_a_prompt_goes_through_a_layer_in_chunks_whatever_its_length(
        monkeypatch, t):
    """Every prompt over ``PREFILL_TOKENS`` (8 here) takes a layer's
    per-token work in chunks, the rows left over after the whole chunks
    as a last, shorter one: under a chunk, whole chunks only, two chunks
    and 5 rows, three and 5.  Logits of every row and both groups' rows
    are the unchunked prefill's."""
    cfg, params = _tiny()
    toks = jnp.asarray(np.random.RandomState(t).randint(0, 32, (1, t)),
                       jnp.int32)
    def loops():
        return str(jax.make_jaxpr(lambda p, x: swa_moe.prefill_forward(
            p, cfg, x))(params, toks)).count("scan[")

    want = swa_moe.prefill_forward(params, cfg, toks, last_only=False)
    whole = loops()
    monkeypatch.setattr(swa_moe, "PREFILL_TOKENS", 8)
    assert (loops() > whole) == (t > 8)
    got = swa_moe.prefill_forward(params, cfg, toks, last_only=False)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_a_bfloat16_prefill_over_two_blocks_is_as_near_the_reference(
        monkeypatch):
    """1,024 tokens, two blocks of 512, through a window layer and a full
    one computing in bfloat16: ``hvd_flash_swa_fwd`` and ``hvd_flash_fwd``
    with bfloat16 products (interpreted) leave the logits of every row as
    near the family's float32 reference as XLA's attention does in the
    same type.  Every expert is chosen (top 4 of the 4 held), so no
    rounding flips a routing; float32 against float32 reads 6e-6 here,
    bfloat16 0.013 in the mean and 0.17-0.22 at the worst element either
    way, logits of deviation 1."""
    over = dict(num_hidden_layers=2, max_position_embeddings=1024,
                layer_types=["sliding_attention", "full_attention"],
                mlp_layer_types=["dense", "sparse"],
                published={"vocab_size": 64, "num_experts": 4},
                share={"first_expert": 0, "experts_held": 4})
    tiny = dict(TINY, **over)
    cfg, params = _tiny(**over)
    prompt = np.random.RandomState(5).randint(0, 32, 1024)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    want = np.asarray(family.Reference(tiny, params, 1024).logits(
        prompt, 0, 1024))

    run = functools.partial(_bf16_prefill_gaps, swa_moe.prefill_forward,
                            cfg, params, toks, want)
    text, xla = run()
    assert "hvd_flash" not in text
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    text, kernels = run()
    assert text.count("name=hvd_flash_swa_fwd") == 1
    assert text.count("name=hvd_flash_fwd") == 1
    assert kernels.mean() < 1.05 * xla.mean() < 0.02
    assert kernels.max() < 1.5 * xla.max() < 0.5


def test_a_forgotten_head_norm_or_rotation_fails_the_reference():
    """What the configuration file ``assumed``: the per-head norms and
    RoPE on window layers only.  A program without either is far from
    the reference."""
    cfg, params = _tiny()
    prompt = np.arange(20) % 32
    logits = swa_moe.prefill_forward(params, cfg, jnp.asarray(prompt)[None],
                                     last_only=False)[0][0]
    ref = family.Reference(TINY, params, 20)
    want = ref.logits(prompt, 0, 20)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    flat = dict(params["params"])
    for li in range(4):
        blk = dict(flat[f"layer_{li}"])
        blk["attn"] = dict(blk["attn"], q_norm={"scale": jnp.ones(16)})
        flat[f"layer_{li}"] = blk
    off = family.Reference(TINY, {"params": flat}, 20).logits(prompt, 0, 20)
    assert float(jnp.max(jnp.abs(off - want))) > 1e-2
    all_rotated = dict(TINY, layer_types=["sliding_attention"] * 4)
    off = family.Reference(all_rotated, params, 20).logits(prompt, 0, 20)
    assert float(jnp.max(jnp.abs(off - want))) > 1e-2


# -- (2) the share and the vocabulary's slices add up ------------------------------

def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_reference():
    """The routed layer of ``swa_moe`` run once for each of four shares
    (each routes over all 16 experts and computes its own four and the
    shared expert): the parts, the shared expert counted ONCE, add up to
    the family's reference over all 16."""
    whole_cfg, whole = _tiny(num_experts=16,
                             share={"first_expert": 0, "experts_held": 16})
    blk = whole["params"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 32))
    h = family._rms(x, blk["mlp_norm"]["scale"], 1e-5)
    want = family.ref_moe(h, blk["moe"], top_k=4, scale=2.5)
    shared = family._ref_swiglu(h, blk["moe"]["shared"], family._mm(None)[1])
    total, routed = jnp.zeros_like(x), 0
    for share in range(4):
        first = 4 * share
        cfg, _ = _tiny(share={"first_expert": first, "experts_held": 4})
        part = dict(blk, moe=dict(blk["moe"], experts={
            k: v[first:first + 4] for k, v in blk["moe"]["experts"].items()}))
        y, counts = swa_moe._ffn(x, part, cfg, jnp.float32)
        assert int(counts.sum()) == 40 * 4      # every share routes over all
        routed += int(counts[first:first + 4].sum())
        total = total + y - (shared if share else 0.0)
    assert routed == 40 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_the_slices_of_the_vocabulary_give_the_whole_readout():
    cfg, params = _tiny(vocab_size=64)
    p = params["params"]
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32))
    whole = stepparts.readout(x, p, 1e-5, jnp.float32, tied=False)
    parts = [stepparts.readout(
        x, dict(p, lm_head={"kernel": p["lm_head"]["kernel"][:, lo:lo + 16]}),
        1e-5, jnp.float32, tied=False) for lo in range(0, 64, 16)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, -1)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)
    want = family._mm(None)[1](
        family._rms(x, p["final_norm"]["scale"], 1e-5),
        p["lm_head"]["kernel"])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- (3) the window in both attention paths ---------------------------------------

def _band(t, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    return (j <= i) & (i - j < window)


def _by_hand(q, k, v, mask):
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("kernels", ["off", "interpreted"])
@pytest.mark.parametrize("t,window,block", [
    (16, 32, 512),      # under the window: plain causal, one block
    (32, 32, 512),      # at the window
    (96, 32, 32),       # over it, blocks of the window's length
    (96, 20, 32),       # a window that is no whole block
    (128, 24, 64),      # several blocks, two of keys a query block
    (72, 16, 8)])       # many small blocks
def test_flash_attention_with_a_window(monkeypatch, kernels, t, window,
                                       block):
    if kernels == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    rng = np.random.RandomState(t + window)
    q = rng.randn(1, 4, t, 16).astype(np.float32)
    k = rng.randn(1, 2, t, 16).astype(np.float32)
    v = rng.randn(1, 2, t, 16).astype(np.float32)
    got = _attn.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), causal=True, window=window,
                                block_q=block, block_kv=block)
    ref = _attn.attention_reference(
        jnp.asarray(q), jnp.repeat(jnp.asarray(k), 2, 1),
        jnp.repeat(jnp.asarray(v), 2, 1), causal=True, window=window)
    want = _by_hand(q, k, v, _band(t, window))
    np.testing.assert_allclose(np.asarray(ref), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_a_window_goes_with_causal_attention_only():
    z = jnp.zeros((1, 2, 16, 16))
    with pytest.raises(ValueError, match="window"):
        _attn.flash_attention(z, z, z, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        _attn.flash_attention(z, z, z, causal=True, window=8,
                              segment_ids=jnp.zeros((1, 16), jnp.int32))


def test_the_banded_kernel_runs_its_band_s_key_blocks_only(monkeypatch):
    """8,192 tokens, blocks of 512, a window of 128: two key blocks a
    query block in the grid, where the triangle has up to sixteen."""
    monkeypatch.setattr(_attn._pallas, "interpret_mode", lambda: False)
    S = jax.ShapeDtypeStruct
    text = _lowered_for_tpu(
        lambda q, k, v: _attn._flash_swa_fwd(
            q, k, v, scale=0.1, window=128, bq=512, bk=512),
        S((1, 64, 8192, 128), jnp.bfloat16),
        S((1, 8, 8192, 128), jnp.bfloat16),
        S((1, 8, 8192, 128), jnp.bfloat16))
    assert 'kernel_name = "hvd_flash_swa_fwd"' in text
    assert "iteration_bounds = array<i64: 1, 64, 16, 2>" in text


def _ring_pools(lengths, window, page, planes=3, kv=2, d=16, seed=0,
                entries=None):
    """Pools of a window group written token by token as the step writes
    them (entry ``n % ring`` of a slot's table holds tokens ``n * page
    ..``), and every token's rows."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    ring = entries or -(-window // page) + 1
    table = np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    kp = np.zeros((planes, b * ring + 1, page, kv * d), np.float32)
    vp = np.zeros_like(kp)
    rows = [(rng.randn(n, kv * d).astype(np.float32),
             rng.randn(n, kv * d).astype(np.float32)) for n in lengths]
    for s, n in enumerate(lengths):
        for t in range(n):
            pg = table[s, t // page % ring]
            kp[1, pg, t % page], vp[1, pg, t % page] = (rows[s][0][t],
                                                        rows[s][1][t])
    return kp, vp, table, rows


@pytest.mark.parametrize("kernels", ["off", "interpreted"])
@pytest.mark.parametrize("lengths,window,page,entries,two_pools", [
    ([0, 3, 16, 17, 40, 129], 16, 4, None, True),   # under, at, over
    ([5, 33, 64, 65, 100], 10, 4, None, False),     # no whole pages
    ([5, 33, 64, 65, 100], 32, 4, 12, True),        # a longer table
    ([128, 129, 144, 145, 300], 128, 16, None, True)])   # the served sizes
def test_the_window_walk_against_the_band_s_rows(monkeypatch, kernels,
                                                 lengths, window, page,
                                                 entries, two_pools):
    if kernels == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    kv, d, h = 2, 16, 4
    kp, vp, table, rows = _ring_pools(lengths, window, page, entries=entries,
                                      seed=sum(lengths))
    q = np.random.RandomState(1).randn(len(lengths), h, d).astype(np.float32)
    kw = dict(layer=1, lengths=jnp.asarray(lengths, jnp.int32), kv_heads=kv,
              scale=d ** -0.5, window=window)
    if two_pools:
        got = _attn.cca_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(table),
            values=jnp.asarray(vp), **kw)
    else:
        got = _attn.cca_decode_attention(
            jnp.asarray(q), jnp.asarray(np.concatenate([kp, vp], -1)),
            jnp.asarray(table), **kw)
    for s, n in enumerate(lengths):
        if not n:
            assert not np.asarray(got[s]).any()
            continue
        lo = max(0, n - window)
        k = rows[s][0][lo:n].reshape(1, -1, kv, d).transpose(0, 2, 1, 3)
        v = rows[s][1][lo:n].reshape(1, -1, kv, d).transpose(0, 2, 1, 3)
        want = _by_hand(q[s][None, :, None], k, v, True)[0, :, 0]
        np.testing.assert_allclose(np.asarray(got[s]), want, rtol=1e-5,
                                   atol=1e-5)


def test_the_window_walk_wants_the_ring_s_length():
    z = jnp.zeros((2, 5, 4, 32))
    with pytest.raises(ValueError, match="table entries"):
        _attn.cca_decode_attention(
            jnp.zeros((2, 4, 16)), z, jnp.zeros((2, 2), jnp.int32), layer=0,
            lengths=jnp.ones((2,), jnp.int32), kv_heads=2, scale=1.0,
            values=z, window=8)


# -- (4) the cache's two groups ----------------------------------------------------

def _cache(slots=3, **over):
    kw = dict(num_layers=2, slots=slots, page_size=16, max_len=9216,
              page=((32,), (32,)), window_layers=6, window=128)
    kw.update(over)
    return kvcache.PagedKVCache(kvcache.CacheConfig(**kw))


def test_a_slot_holds_nine_pages_a_window_plane_at_the_served_sizes():
    c = _cache()
    assert c.config.window_pages_per_slot == 9
    assert c.wk.shape == (6, 3 * 9 + 1, 16, 32) == c.wv.shape
    assert c.k.shape == (2, 3 * 576 + 1, 16, 32)
    before = _reused()
    rows = jnp.zeros((2, 8192, 32))
    tail = jnp.zeros((6, 127, 32))
    c.write_prefill(0, rows, rows, window_rows=(tail, tail))
    assert int(c._wallocated[0]) == 9 and int(c._allocated[0]) == 512
    assert _reused() == before              # a prefill takes nothing back
    for _ in range(1024):
        c.grow(0)
        assert int(c._wallocated[0]) == 9
    assert int(c._allocated[0]) == 576
    assert _reused() - before == 64         # one a page boundary crossed
    assert c.live_pages == 576 + 9 and c.window_live_pages == 9
    assert c.resident_bytes == (576 * 2 + 9 * 6) * 16 * 64 * 4
    c.free_slot(0)
    assert c.live_pages == 0 and c.refcounts_balanced()
    assert len(c._wfree) == 27


def test_both_groups_gate_admission_and_are_returned():
    c = _cache(slots=2)
    assert c.can_admit(9216)
    c.reserve(0, 40)
    c.reserve(1, 200)
    assert [int(n) for n in c._wallocated] == [3, 9]
    assert c.window_live_pages == 12 and c.live_pages == 3 + 13 + 12
    c._wfree.clear()                        # the window group alone is full
    assert not c.can_admit(16)
    with pytest.raises(RuntimeError, match="window page pool exhausted"):
        c.reserve(0, 200)
    c._wfree.extend(range(12, 18))
    assert c.release_all() == 3 + 13 + 12
    assert c.live_pages == 0 and c.refcounts_balanced()


def test_window_rows_are_the_prompt_s_last_ones():
    assert kvcache.window_rows_from(8192, 128) == 8065
    assert kvcache.window_rows_from(100, 128) == 0
    c = _cache()
    rows = jnp.zeros((2, 300, 32))
    with pytest.raises(ValueError, match="keeps rows 173-299"):
        c.write_prefill(0, rows, rows, window_rows=(jnp.zeros((6, 128, 32)),) * 2)
    with pytest.raises(ValueError, match="window rows missing"):
        c.write_prefill(0, rows, rows)
    plain = kvcache.PagedKVCache(kvcache.CacheConfig(
        num_layers=2, slots=2, page_size=16, max_len=64, page=((32,), (32,))))
    with pytest.raises(ValueError, match="0 window planes"):
        plain.write_prefill(0, rows[:, :8], rows[:, :8],
                            window_rows=(rows, rows))


def test_a_reused_window_page_s_stale_rows_are_unreachable():
    """Bitwise: a slot whose ring has been written round, and a slot that
    took over another sequence's pages, read exactly what a fresh cache
    holding the same last rows reads: what a page held before is behind
    the window's mask or past the length."""
    cfg, params = _tiny()
    prompt = np.arange(29) % 32
    toks = jnp.asarray(prompt)[None]

    def decode_logits(dirty):
        eng = serving.ServingEngine(cfg, params, slots=2, page_size=4,
                                    max_len=64, dtype=jnp.float32)
        if dirty:
            # Another sequence lives and dies in the slot first, and the
            # pools' every row is overwritten with a large value.
            eng.serve(_requests([(40, 20)], seed=9))
            c = eng.cache
            c.k, c.v = c.k + 1e3, c.v + 1e3
            c.wk, c.wv = c.wk + 1e3, c.wv + 1e3
        c = eng.cache
        lg, kl, vl, (wk, wv) = eng._prefill(eng.params, toks, None, None)
        c.write_prefill(1, kl[:, 0], vl[:, 0], window_rows=(wk[:, 0], wv[:, 0]))
        tok, out = int(jnp.argmax(lg[0, -1])), []
        for _ in range(14):                 # past the ring's end twice
            n = int(c.lengths[1])
            c.reserve(1, n + 1, writable_from=n)
            active = np.array([False, True])
            res = eng.step(
                eng._decode_params, c.k, c.v, jnp.asarray([0, tok]),
                c.lengths_device(), c.table_device(), jnp.asarray(active),
                c.window_table_device(), *c.carried, *eng._step_state,
                eng._told)
            c.k, c.v = res[1:3]
            c.take_carried(res[3:5])
            eng._step_state, eng._told = res[5:6], res[-1]
            c.lengths[1] += 1
            out.append(np.asarray(res[0][1]))
            tok = int(np.argmax(out[-1]))
        return np.stack(out)

    np.testing.assert_array_equal(decode_logits(True), decode_logits(False))


def test_the_served_configurations_build_the_pools_they_built():
    """``CacheConfig.layout()`` of the four served configurations that
    were there: no key added, no shape changed."""
    want = {
        "mistral": [4, 32 * 96 + 1, 16, 1024],
        "joyai": [5, 64 * 544 + 1, 16, 640],
        "zaya": [24, 96 * 96 + 1, 16, 512],
        "ouro": [192, 20 * 16 + 1, 16, 4096]}
    built = {
        "mistral": kvcache.CacheConfig(4, 8, 128, slots=32, page_size=16,
                                       max_len=1536, dtype="bfloat16"),
        "joyai": kvcache.CacheConfig(5, slots=64, page_size=16, max_len=8704,
                                     dtype="bfloat16", page=((640,), None)),
        "zaya": kvcache.CacheConfig(24, slots=96, page_size=16, max_len=1536,
                                    dtype="bfloat16", page=((512,), None),
                                    slot_state=2688),
        "ouro": kvcache.CacheConfig(192, slots=20, page_size=16, max_len=256,
                                    dtype="bfloat16", page=((4096,), None))}
    for name, config in built.items():
        layout = config.layout()
        assert layout["kv_shape"] == want[name]
        assert sorted(layout) == [
            "dtype", "kv_shape", "num_pages", "page_size", "page_table_shape",
            "pages_per_slot", "scratch_page"]
        assert config.window_pages_per_slot == 0
    exaone = kvcache.CacheConfig(2, slots=32, page_size=16, max_len=9216,
                                 dtype="bfloat16", page=((1024,), (1024,)),
                                 window_layers=6, window=128).layout()
    assert exaone["kv_shape"] == [2, 18433, 16, 1024]
    assert exaone["window_kv_shape"] == [6, 289, 16, 1024]
    assert exaone["window_table_shape"] == [32, 9]


def test_layer_spec_validates_the_attention_kinds():
    cfg, _ = _tiny()
    spec = cfg.layer_spec()
    assert spec.attn_kinds == KINDS and spec.window == 8
    assert spec.planes == 1 and spec.window_planes == 3
    import dataclasses
    for bad in (dict(attn_kinds=("window", "band", "full", "full")),
                dict(attn_kinds=("full",) * 3),
                dict(attn_kinds=("window",) * 4),
                dict(window=None), dict(window=0),
                dict(attn_kinds=("full",) * 4)):
        with pytest.raises(ValueError):
            dataclasses.replace(spec, **bad)
    with pytest.raises(NotImplementedError, match="one pass"):
        dataclasses.replace(spec, passes=2)
    plain = dataclasses.replace(spec, attn_kinds=None, window=None)
    assert plain.planes == 4 and plain.window_planes == 0


@pytest.mark.parametrize("feature", [
    "tp", "lora", "spec_decode", "kv_compress", "prefill_chunk",
    "prefix_cache", "handoff"])
def test_what_a_window_group_cannot_do_is_refused_by_name(feature):
    cfg, params = _tiny()
    with pytest.raises(NotImplementedError, match=feature):
        cfg.layer_spec().require(**{feature: True})
    kw = {"spec_decode": dict(spec_decode=True),
          "kv_compress": dict(kv_compress=True),
          "prefill_chunk": dict(prefill_chunk=8),
          "prefix_cache": dict(prefix_cache=True)}.get(feature)
    if kw is not None:
        with pytest.raises(NotImplementedError, match=feature):
            serving.ServingEngine(cfg, params, slots=2, page_size=4,
                                  max_len=64, **kw)
    if feature == "handoff":
        with pytest.raises(NotImplementedError, match="handoff"):
            serving.PrefillWorker("p0", cfg, params, kv=None, page_size=4)


# -- (5) the other served cells' walks lower to what they lowered to ---------------

# sha256 of the TPU lowering (Mosaic bodies printed without source
# locations: ``tests/serving_families.py:lowered_for_tpu``) recorded
# on PR 38's tree, the parent of the PR that gave the walk its window:
# Mistral's two-pool walk.  The three one-pool walks are held by
# ``test_one_pool_walk_lowers_to_what_it_was``.  The blocked flash
# kernel over Mistral's 1,024 tokens was recorded again on PR 41's tree,
# whose change it is (067d76a3.. before: both products in float32, the
# statistics a (bq, 1) column); its other shapes are held by
# ``test_blocked_and_head_group_kernels_lower_to_what_was_recorded``.
_TWO_POOL_LOWERED = \
    "3add0ab8a2db3f3bc66808447725b15a162dad59ff4de019503b6c1e8af51176"
_FLASH_1024_LOWERED = \
    "ec67ce3c2bea1f300cfec5800ef8de802a13fa03ff1ca33139363f9c8e1fc9ba"
# K-EXAONE's own programs, recorded on PR 41's tree, the parent of the PR
# that gave ``swa_moe.py`` its second instance (a router ahead of
# attention, ReLU gates, no head norm, no shared expert: fields of
# ``SwaMoeConfig`` whose defaults are K-EXAONE's): the decode step and a
# 2,560-token prefill (a whole chunk and a shorter one) at its widths, a
# window layer over a dense feed-forward and a full layer over a routed
# one.
_SWA_STEP_LOWERED = \
    "ea717161cb7432cf7ec21eb8fcef90f8f069868f835d304f7ebe6852300fc090"
# The prefill was recorded again on PR 45's tree, whose change it is
# (e5c77db0.. before): a layer's body is lowered to ONE function a kind
# of layer and called (``decode.one_trace``), where it was unrolled; the
# kernels in it are the recorded ones, and XLA inlines the calls (the
# optimised program of a deviceless v5e compile has the instructions,
# temporaries and estimated cycles it had).
_SWA_PREFILL_LOWERED = \
    "5071b5ec9058a4ca6a73ffa892cea0ed88fe1e727f0bca17c727741f389d4369"


def _k_exaone_two_layers():
    return swa_moe.SwaMoeConfig(
        vocab_size=153600, d_model=6144, num_heads=64, num_kv_heads=8,
        head_dim=128, ffn_hidden=18432, moe_hidden=2048, num_experts=128,
        experts_per_token=8, attn_kinds=("window", "full"),
        ffn_kinds=("dense", "moe"), window=128, routed_scale=2.5,
        max_seq_len=262144, experts_held=16, vocab_held=19200)


@pytest.mark.parametrize("what", ["two_pool_walk", "blocked_flash",
                                  "swa_step", "swa_prefill"])
def test_the_window_left_the_other_cells_kernels_as_they_were(monkeypatch,
                                                              what):
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn._pallas, "interpret_mode", lambda: False)
    S, bf, i32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.int32
    if what == "swa_step":
        from horovod_tpu.serving.decode import no_round
        cfg = _k_exaone_two_layers()
        slots, pps, ring = 32, 576, 9
        fn = swa_moe.build_decode_step(cfg, None, slots=slots, page_size=16,
                                       pages_per_slot=pps, dtype=bf)._fn
        pool = S((1, slots * pps + 1, 16, 1024), bf)
        wpool = S((1, slots * ring + 1, 16, 1024), bf)
        args = (swa_moe.param_shapes(cfg, bf), pool, pool, S((slots,), i32),
                S((slots,), i32), S((slots, pps), i32),
                S((slots,), jnp.bool_), S((slots, ring), i32), wpool, wpool,
                S((1, 128), i32), S(no_round(slots, 1).shape, i32))
        want = _SWA_STEP_LOWERED
    elif what == "swa_prefill":
        cfg = _k_exaone_two_layers()
        fn = lambda p, t: swa_moe.prefill_forward(  # noqa: E731
            p, cfg, t, dtype=bf)
        args = (swa_moe.param_shapes(cfg, bf), S((1, 2560), i32))
        want = _SWA_PREFILL_LOWERED
    elif what == "two_pool_walk":
        # Mistral's cells: 32 slots, 32 heads over 8, 96 pages a slot.
        fn = lambda q, k, v, t, n: _attn.cca_decode_attention(  # noqa: E731
            q, k, t, layer=1, lengths=n, kv_heads=8, scale=128 ** -0.5,
            values=v)
        args = (S((32, 32, 128), bf), S((4, 3073, 16, 1024), bf),
                S((4, 3073, 16, 1024), bf), S((32, 96), i32), S((32,), i32))
        want = _TWO_POOL_LOWERED
    else:
        fn = lambda q, k, v: _attn.flash_attention(  # noqa: E731
            q, k, v, causal=True)
        args = (S((1, 32, 1024, 128), bf), S((1, 8, 1024, 128), bf),
                S((1, 8, 1024, 128), bf))
        want = _FLASH_1024_LOWERED
    text = _lowered_for_tpu(fn, *args)
    assert hashlib.sha256(text.encode()).hexdigest() == want


def test_the_window_walk_is_the_walk_under_a_further_name(monkeypatch):
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn._pallas, "interpret_mode", lambda: False)
    S, bf, i32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.int32
    text = _lowered_for_tpu(
        lambda q, k, v, t, n: _attn.cca_decode_attention(
            q, k, t, layer=2, lengths=n, kv_heads=8, scale=128 ** -0.5,
            values=v, window=128),
        S((32, 64, 128), bf), S((6, 289, 16, 1024), bf),
        S((6, 289, 16, 1024), bf), S((32, 9), i32), S((32,), i32))
    assert 'kernel_name = "hvd_swa_decode"' in text
    assert "hvd_cca_decode" not in text


# -- (6) moe_ffn's rows at a share --------------------------------------------------

def test_a_share_s_rows_follow_the_held_pairs():
    # 8,192 prompt tokens, top 8 of 128: tiles of 128 rows.
    pairs, tm = 8192 * 8, moe.row_tile(8192 * 8, 128)
    assert tm == 128
    assert moe._padded_rows(pairs, 16, tm) == 67584      # all the pairs
    assert moe.pass_rows(pairs, 16, 128, tm) == 18432    # twice the share's
    # A decode round of 32 slots.
    assert moe.pass_rows(256, 16, 128, 16) == 304
    # Whoever holds every expert lays out what it always did: JoyAI's and
    # ZAYA's rounds and prompts.
    for pairs, experts in ((64 * 8, 256), (8192 * 8, 256), (96, 16),
                           (512, 16)):
        tm = moe.row_tile(pairs, experts)
        assert moe.pass_rows(pairs, experts, experts, tm) \
            == moe._padded_rows(pairs, experts, tm)


def _share_layer(tokens, skew, seed=0):
    """A layer of 16 experts of which 4 (4-7) are held; ``skew`` adds to
    the held experts' selection bias."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    d, f = 32, 16
    p = {"experts": {
        "w_gate": jax.random.normal(ks[0], (4, d, f)) / np.sqrt(d),
        "w_up": jax.random.normal(ks[1], (4, d, f)) / np.sqrt(d),
        "w_down": jax.random.normal(ks[2], (4, f, d)) / np.sqrt(f)}}
    h = jax.random.normal(ks[3], (tokens, d))
    bias = jnp.zeros(16).at[4:8].set(skew)
    r = moe.route(h, jax.random.normal(ks[4], (d, 16)) / np.sqrt(d), bias,
                  top_k=4, scale=2.5)
    return h, p, r


@pytest.mark.parametrize("kernels", ["off", "interpreted"])
@pytest.mark.parametrize("tokens,skew", [(40, 0.0), (40, 10.0), (7, 10.0)])
def test_a_share_drops_nothing_however_the_router_skews(monkeypatch, kernels,
                                                        tokens, skew):
    """Even routing fits one pass of the bounded rows; a router that sends
    EVERY pair to the held experts takes further passes and still adds
    every pair's part (against every expert applied to every row)."""
    if kernels == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    h, p, r = _share_layer(tokens, skew)
    y, counts = moe.moe_ffn(h, p, r, num_experts=16, first=4,
                            with_shared=False)
    held = np.asarray((r.experts >= 4) & (r.experts < 8))
    assert int(counts[4:8].sum()) == held.sum()
    if skew:
        assert held.all()
    want = np.zeros_like(np.asarray(h))
    for e in range(4):
        out = (jax.nn.silu(h @ p["experts"]["w_gate"][e])
               * (h @ p["experts"]["w_up"][e])) @ p["experts"]["w_down"][e]
        w = np.where(np.asarray(r.experts) == e + 4, np.asarray(r.weights),
                     0.0).sum(-1)
        want += np.asarray(out) * w[:, None]
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)


def test_a_share_gathers_a_pass_s_rows_and_no_more():
    """No array of the program grows with the pairs held elsewhere but
    the layout's int32 index arrays."""
    h, p, r = _share_layer(512, 0.0)
    pairs, tm = 512 * 4, moe.row_tile(512 * 4, 16)
    rows, worst = moe.pass_rows(pairs, 4, 16, tm), moe._padded_rows(pairs, 4,
                                                                    tm)
    assert rows < worst
    jaxpr = jax.make_jaxpr(lambda h, r: moe.moe_ffn(
        h, p, r, num_experts=16, first=4, with_shared=False))(h, r)

    def floats(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if jnp.issubdtype(v.aval.dtype, jnp.floating):
                    yield v.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from floats(sub)

    shapes = set(floats(jaxpr.jaxpr))
    assert (rows, 32) in shapes
    assert not [s for s in shapes if s and s[0] >= worst]
