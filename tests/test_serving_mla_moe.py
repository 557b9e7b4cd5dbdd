"""Latent attention and routed experts through the NORMAL serving path, at
a tiny size on the CPU (3 layers: one dense, two routed; 16 experts, top
4; widths 64/32/16; vocabulary 256), against the plain reference of
``benchmarks/families/joyai_mla_moe.py`` (float32, ``highest``, expanded
attention only, every expert on every row).  Logits are compared, never
tokens; no assertion reads a clock."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import joyai_mla_moe as family
from horovod_tpu import serving
from horovod_tpu.serving import mla_moe
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.serving.layerspec import layer_spec
from horovod_tpu.timeline import metrics, spans
from serving_families import TINY_MLA as TINY
from serving_families import bf16_prefill_gaps as _bf16_prefill_gaps

CFG = family.program_config(TINY)

# float32 against float32: what is left is the order of summation (the
# program's flash blocks, absorbed products and sorted expert runs against
# the reference's plain sums) at logits of deviation 1, largest 3.7.
# Measured here: 6.0e-6 (prefill), 4.3e-6 (decode).  Computing in
# bfloat16 reads 1.66: rounding flips an expert between the 4th and 5th
# score (``test_bfloat16_fails_the_float32_tolerance``).
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return mla_moe.init_params(CFG, jax.random.PRNGKey(0))


def _reference_logits(params, context, first, count, quant=None):
    ref = family.Reference(TINY, params, pad_to=64, quant=quant)
    return np.asarray(ref.logits(np.asarray(context), first, count))


def test_prefill_logits_match_the_reference(params):
    ctx = np.random.RandomState(1).randint(0, 256, size=40)
    got, rows, second = mla_moe.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None], last_only=False)
    assert second is None and rows.shape == (3, 1, 40, CFG.page_width)
    # The cached row: 32 latent + 8 rotated values, zeros to the tile.
    assert not np.any(np.asarray(rows[..., 40:]))
    want = _reference_logits(params, ctx, 0, 40)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0, atol=TOL)
    last, _, _ = mla_moe.prefill_forward(
        params, CFG, jnp.asarray(ctx, jnp.int32)[None])
    np.testing.assert_allclose(np.asarray(last[0, 0]), want[-1], rtol=0,
                               atol=TOL)


def test_prefill_with_interpreted_kernels_matches_the_reference(
        params, monkeypatch):
    """The tiny model's 40 tokens, keys 24 wide and values 16, with the
    kernels on: one block, and still the blocked forward (the head-group
    kernels know one width), within the file's tolerance of the
    reference like the prefill that runs no kernel."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    ctx = np.random.RandomState(1).randint(0, 256, size=40)
    toks = jnp.asarray(ctx, jnp.int32)[None]
    traced = jax.jit(lambda p, x: mla_moe.prefill_forward(
        p, CFG, x, last_only=False)[0]).trace(params, toks)
    text = str(traced.jaxpr)
    assert "name=hvd_flash_fwd" in text and "hvd_flash_hg" not in text
    got = traced.lower().compile()(params, toks)
    np.testing.assert_allclose(np.asarray(got[0]),
                               _reference_logits(params, ctx, 0, 40),
                               rtol=0, atol=TOL)


def test_a_bfloat16_prefill_at_the_served_head_width_over_two_blocks(
        monkeypatch):
    """1,024 tokens, two blocks of 512, at JoyAI's head widths (128 + 64
    query and key columns, 128 value columns that go in as they are)
    computing in bfloat16: ``hvd_flash_fwd`` with bfloat16 products
    (interpreted) leaves the logits of every row as near the float32
    reference as XLA's attention does in the same type.  Every expert is
    chosen (top 4 of 4), so no rounding flips a routing (16 experts read
    1.66, ``test_bfloat16_fails_the_float32_tolerance``); float32 against
    float32 reads 1e-5 here, bfloat16 0.011 in the mean and 0.10-0.11 at
    the worst element either way, logits of deviation 1."""
    tiny = dict(TINY, num_hidden_layers=2, num_attention_heads=2,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                n_routed_experts=4, max_position_embeddings=1024)
    cfg = family.program_config(tiny)
    params = mla_moe.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.RandomState(5).randint(0, 256, 1024)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    want = np.asarray(family.Reference(tiny, params, pad_to=1024).logits(
        prompt, 0, 1024))

    run = functools.partial(_bf16_prefill_gaps, mla_moe.prefill_forward,
                            cfg, params, toks, want)
    text, xla = run()
    assert "hvd_flash" not in text
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    text, kernels = run()
    assert text.count("name=hvd_flash_fwd") == 2          # a layer each
    # Queries and keys 192 wide, values and the kernel's result 128: no
    # pad of the values before the call, no cut of its result after it.
    assert text.count("bf16[1,2,1024,192] = transpose") == 4
    assert text.count("bf16[1,2,1024,128] = transpose") == 2
    assert text.count(
        "out_avals=(ShapedArray(bfloat16[1,2,1024,128]), ") == 2
    assert "bf16[1,1024,2,192] = jit[name=_pad" not in text
    assert "bf16[1,2,1024,128] = slice" not in text
    assert kernels.mean() < 1.05 * xla.mean() < 0.02
    assert kernels.max() < 1.5 * xla.max() < 0.5


def _prefill_then_decode(params, prompt, steps, dtype=jnp.float32,
                         pool_dtype=None):
    """Prefill slot 1 of a 3-slot paged latent cache, then ``steps`` decode
    rounds fed the reference-independent ``tokens``; returns the logits of
    every decode round ``[steps, vocab]`` and the tokens fed."""
    spec = layer_spec(CFG)
    ccfg = serving.CacheConfig(
        num_layers=3, slots=3, page_size=8, max_len=64, dtype=str(jnp.dtype(pool_dtype or dtype)),
        page=spec.page)
    cache = serving.PagedKVCache(ccfg)
    assert cache.v is None and cache.k.shape == (3, 25, 8, CFG.page_width)
    step = spec.build_step(None, slots=3, page_size=8, pages_per_slot=8,
                           dtype=dtype)
    _, rows, _ = spec.prefill(params, jnp.asarray(prompt, jnp.int32)[None],
                              dtype=dtype)
    cache.write_prefill(1, rows[:, 0], None)
    feed = np.random.RandomState(2).randint(0, 256, size=steps)
    state = spec.step_state()
    out = []
    for t in range(steps):
        n = int(cache.lengths[1])
        cache.reserve(1, n + 1, writable_from=n)
        tokens = jnp.zeros((3,), jnp.int32).at[1].set(int(feed[t]))
        active = jnp.zeros((3,), bool).at[1].set(True)
        logits, cache.k, cache.v, *rest = step(
            params, cache.k, cache.v, tokens, cache.lengths_device(),
            cache.table_device(), active, *state, no_round(3, 1))
        state, told = tuple(rest[:1]), rest[1]
        # One live slot, top 4, two routed layers: 8 experts touched.
        assert int(read_told(told, 3)[2][0]) == 8
        cache.lengths[1] += 1
        out.append(np.asarray(logits[1]))
    assert int(np.asarray(state[0]).sum()) == steps * 4 * 2
    return np.stack(out), feed


def test_cached_decode_matches_the_references_full_forward(params):
    """Prefill (expanded path), then 24 tokens decoded through the paged
    latent cache (absorbed path): each round's logits against the
    reference's ONE full forward over prompt + fed tokens, which only ever
    expands."""
    prompt = np.random.RandomState(3).randint(0, 256, size=19)
    got, feed = _prefill_then_decode(params, prompt, 24)
    ctx = np.concatenate([prompt, feed])
    want = _reference_logits(params, ctx, len(prompt), 24)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def test_bfloat16_fails_the_float32_tolerance(params):
    """The same comparison with the program computing in bfloat16 where
    the test states float32: outside the tolerance by two orders."""
    prompt = np.random.RandomState(3).randint(0, 256, size=19)
    got, feed = _prefill_then_decode(params, prompt, 24, dtype=jnp.bfloat16)
    ctx = np.concatenate([prompt, feed])
    want = _reference_logits(params, ctx, len(prompt), 24)
    assert np.max(np.abs(got - want)) > 100 * TOL


def test_decode_with_interpreted_kernels_matches(params, monkeypatch):
    """The same rounds with ``hvd_mla_decode`` and ``hvd_moe_gmm`` run by
    the Pallas interpreter."""
    prompt = np.random.RandomState(4).randint(0, 256, size=11)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    got, feed = _prefill_then_decode(params, prompt, 6)
    ctx = np.concatenate([prompt, feed])
    want = _reference_logits(params, ctx, len(prompt), 6)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


# -- the engine ---------------------------------------------------------------------

def _engine(params, **kw):
    return serving.ServingEngine(CFG, params, slots=4, page_size=8,
                                 max_len=64, dtype=jnp.float32, **kw)


def test_engine_serves_it_through_the_scheduler_and_the_paged_cache(params):
    eng = _engine(params)
    assert eng.spec.attention == "mla" and not eng.spec.tied_head
    assert eng.spec.ffn == ("dense", "moe", "moe")
    assert eng.cache.v is None
    assert eng.cache.k.shape == (3, 4 * 8 + 1, 8, CFG.page_width)
    rng = np.random.RandomState(5)
    reqs = [serving.Request(rid=i, prompt=rng.randint(0, 256, size=n)
                            .astype(np.int32), max_new_tokens=6,
                            arrival_s=0.0)
            for i, n in enumerate([16, 24, 16, 8, 24, 9])]
    routed = metrics.registry().counter(
        "moe.tokens_routed", labelnames=("layer", "expert"))
    before = sum(c.value for _, c in routed.samples())
    t0 = spans.recorder().records()[-1].end_ns if \
        spans.recorder().records() else 0
    report = eng.serve(reqs)
    assert report.completed == 6 and report.new_tokens == 36
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    # Greedy tokens are the argmax of the plain full forward.
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = _reference_logits(params, ctx, len(r.prompt) - 1, 6)
        served = want[np.arange(6), np.asarray(r.tokens)]
        assert np.all(want.max(axis=-1) - served < TOL)
    # The round's count of touched experts rides on the bookkeep span;
    # the device's histogram reaches the registry when serve returns.
    books = [r for r in spans.recorder().records(name="decode.bookkeep")
             if r.start_ns >= t0]
    assert len(books) == report.decode_steps
    assert all(1 <= r.attrs["experts_touched"] <= 2 * 16 for r in books)
    pairs = sum(c.value for _, c in routed.samples()) - before
    decoded = report.new_tokens - 6          # the first token is prefill's
    assert pairs == decoded * 4 * 2


@pytest.mark.parametrize("kwargs,name", [
    ({"spec_decode": True}, "spec_decode"),
    ({"kv_compress": True}, "kv_compress"),
    ({"prefill_chunk": 8}, "prefill_chunk"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"adapters": {"params": {}}}, "lora"),
    ({"mesh": 2}, "tp")])
def test_what_the_new_model_does_not_do_raises_by_name(params, kwargs, name):
    if "mesh" in kwargs:
        from jax.sharding import Mesh
        kwargs = {"mesh": Mesh(np.asarray(jax.devices()[:2]), ("tp",))}
    with pytest.raises(NotImplementedError, match="^" + name + ":"):
        _engine(params, **kwargs)


def test_a_llama_config_is_one_instance_of_the_same_description():
    from horovod_tpu.models.transformer import LLAMA_SERVE
    spec = layer_spec(LLAMA_SERVE)
    assert spec.attention == "gqa" and spec.tied_head
    # One row of 8 heads x 16 a token in each pool, no head dim: the
    # rows the decode step's page walk reads in place.
    assert spec.page == ((128,), (128,)) and spec.tp_page_dim == 0
    assert not spec.unsupported
    assert spec.ffn == ("dense",) * LLAMA_SERVE.num_layers
    with pytest.raises(TypeError, match="layer_spec"):
        layer_spec(object())


# -- every program that writes the pool donates it ---------------------------------

def test_the_new_programs_consume_the_pool_they_write(params):
    """PR 25's rule for the latent pool: the decode step (pool and routed
    histogram) and ``write_prefill`` delete the arrays they are given and
    hand back successors of the same shape."""
    spec = layer_spec(CFG)
    ccfg = serving.CacheConfig(
        num_layers=3, slots=2, page_size=8, max_len=32, page=spec.page)
    cache = serving.PagedKVCache(ccfg)
    _, rows, _ = spec.prefill(params, jnp.ones((1, 12), jnp.int32),
                              dtype=jnp.float32)
    given = cache.k
    cache.write_prefill(0, rows[:, 0], None)
    assert given.is_deleted() and cache.k.shape == given.shape
    step = spec.build_step(None, slots=2, page_size=8, pages_per_slot=4,
                           dtype=jnp.float32)
    cache.reserve(0, 13, writable_from=12)
    given, (hist,) = cache.k, spec.step_state()
    _, cache.k, _, hist2, _ = step(
        params, cache.k, None, jnp.ones((2,), jnp.int32),
        cache.lengths_device(), cache.table_device(),
        jnp.asarray([True, False]), hist, no_round(2, 1))
    assert given.is_deleted() and hist.is_deleted()
    assert cache.k.shape == given.shape and hist2.shape == (2, 16)
    text = step._fn.lower(
        params, cache.k, None, jnp.ones((2,), jnp.int32),
        cache.lengths_device(), cache.table_device(),
        jnp.asarray([True, False]), hist2, no_round(2, 1)).as_text()
    assert text.count("tf.aliasing_output") == 2
