"""The KV page pool is updated in place: whoever writes it consumes it.

The contract under test: the decode step, the verify step and every
``PagedKVCache`` writer (``write_prefill``, the copy-on-write clone,
``adopt_pages``) DONATE the pool they are given -- the array passed in
is deleted by the call and the successor (same shape, dtype and
sharding) takes its place -- on meshes of 1 AND 8 virtual devices (the
CPU backend honours donation, so this suite guards what the chip's
trace shows: no whole-pool copy a round).  Served token streams are
bitwise what a run fed ``jnp.copy`` of the pools emits.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.controller import fusion
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import (CacheConfig, LoadSpec, ModelDrafter,
                                 PagedKVCache, ServingEngine,
                                 build_decode_step, build_verify_step,
                                 cache_sharding, generate, prefix_spec)
from horovod_tpu.serving.decode import no_round

CFG = LLAMA_SERVE
WIDTH = 3


def mesh_1d(n):
    return Mesh(np.asarray(jax.devices()[:n], dtype=object).reshape(n),
                ("tp",))


@pytest.fixture(scope="module")
def params():
    model = LlamaLM(CFG, dtype=jnp.float32)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _make_cache(ndev, slots=4, page_size=8, max_len=64):
    mesh = mesh_1d(ndev)
    ccfg = CacheConfig(num_layers=CFG.num_layers,
                       num_kv_heads=CFG.num_kv_heads,
                       head_dim=CFG.head_dim, slots=slots,
                       page_size=page_size, max_len=max_len)
    return mesh, ccfg, PagedKVCache(ccfg, cache_sharding(mesh))


def _rows(cache, t, seed=0):
    """``[L, t, H * D]`` K and V rows a prefill would hand the cache."""
    c = cache.config
    rng = np.random.RandomState(seed)
    shape = (c.num_layers, t, *c.entries[0])
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _build_step(kind, mesh, ccfg):
    kw = dict(slots=ccfg.slots, page_size=ccfg.page_size,
              pages_per_slot=ccfg.pages_per_slot)
    if kind == "verify":
        return build_verify_step(CFG, mesh, width=WIDTH, **kw)
    return build_decode_step(CFG, mesh, **kw)


def _step_args(kind, params, cache):
    """One live slot with 12 resident tokens, about to decode."""
    k, v = _rows(cache, 12)
    cache.write_prefill(0, k, v)
    width = WIDTH if kind == "verify" else 1
    cache.reserve(0, 12 + width)
    slots = cache.config.slots
    tokens = jnp.ones((slots,) if width == 1 else (slots, width), jnp.int32)
    active = jnp.zeros((slots,), bool).at[0].set(True)
    prev = () if kind == "verify" else (no_round(slots),)
    return (params, cache.k, cache.v, tokens, cache.lengths_device(),
            cache.table_device(), active, *prev)


# Each writer sets its scene, then returns the pools it handed to the ONE
# write under test (the scene's own writes consumed earlier pools).


def _run_step(kind, params, mesh, ccfg, cache):
    args = _step_args(kind, params, cache)
    _, cache.k, cache.v, *_ = _build_step(kind, mesh, ccfg)(*args)
    return args[1], args[2]


def _write_prefill(params, mesh, ccfg, cache):
    k, v = _rows(cache, 12)
    given = cache.k, cache.v
    cache.write_prefill(1, k, v)
    assert int(cache.lengths[1]) == 12
    return given


def _cow_clone(params, mesh, ccfg, cache):
    k, v = _rows(cache, 16)
    cache.write_prefill(0, k, v)
    shared = [("f", int(p)) for p in cache.page_table[0, :2]]
    cache.attach_pages(1, shared, 16)
    before = np.asarray(cache.k[:, shared[1][1]])
    given = cache.k, cache.v
    # Slot 1 diverges inside the shared second page: it is cloned.
    cache.reserve(1, 17, writable_from=12)
    clone = int(cache.page_table[1, 1])
    assert clone != shared[1][1]
    np.testing.assert_array_equal(np.asarray(cache.k[:, clone]), before)
    np.testing.assert_array_equal(
        np.asarray(cache.k[:, shared[1][1]]), before)
    return given


def _adopt_pages(params, mesh, ccfg, cache):
    c = cache.config
    rng = np.random.RandomState(3)
    pages = rng.normal(size=(c.num_layers, 2, c.page_size,
                             *c.entries[0])).astype(np.float32)
    given = cache.k, cache.v
    entries = cache.adopt_pages(pages, pages * 2)
    got = np.asarray(cache.v[:, [pid for _, pid in entries]])
    np.testing.assert_array_equal(got, pages * 2)
    return given


WRITERS = {"decode": functools.partial(_run_step, "decode"),
           "verify": functools.partial(_run_step, "verify"),
           "write_prefill": _write_prefill, "cow_clone": _cow_clone,
           "adopt_pages": _adopt_pages}


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("writer", list(WRITERS))
def test_pool_writer_consumes_its_pool(params, writer, ndev):
    mesh, ccfg, cache = _make_cache(ndev)
    shape, sharding = cache.k.shape, cache.k.sharding
    k0, v0 = WRITERS[writer](params, mesh, ccfg, cache)
    assert k0.is_deleted() and v0.is_deleted(), writer
    assert cache.k is not k0 and cache.v is not v0
    for pool in (cache.k, cache.v):
        assert not pool.is_deleted()
        assert pool.shape == shape and pool.dtype == jnp.float32
        assert pool.sharding.is_equivalent_to(sharding, pool.ndim)
        assert np.isfinite(np.asarray(pool)).all()


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_step_donates_and_aliases_both_pools(params, monkeypatch, kind,
                                             ndev):
    """The jitted program itself: arguments 1 and 2 (and only those)
    are donated, the compiled module aliases them to outputs and holds
    no copy of a whole pool."""
    mesh, ccfg, cache = _make_cache(ndev)
    built = []
    real = fusion.plan_executable

    def capture(plan, build, extra=()):
        built.append(real(plan, build, extra))
        return built[-1]

    monkeypatch.setattr(fusion, "plan_executable", capture)
    step = _build_step(kind, mesh, ccfg)
    args = _step_args(kind, params, cache)
    _, cache.k, cache.v, *_ = step(*args)
    args = (params, cache.k, cache.v) + args[3:]

    lowered = built[-1].lower(*args)
    donated = [all(i.donated for i in jax.tree.leaves(a))
               for a in lowered.args_info[0]]
    donated[0] = any(i.donated for i in jax.tree.leaves(
        lowered.args_info[0][0]))
    # The pools alone are consumed: ``prev``, the decode step's last
    # operand, is still to be fetched when the next round has it.
    assert donated == [False, True, True] + [False] * (len(args) - 3)
    text = lowered.compile().as_text()
    assert "input_output_alias" in text
    local = list(cache.k.sharding.shard_shape(cache.k.shape))
    pool = r"f32\[" + ",".join(map(str, local)) + r"\]"
    copies = re.findall(pool + r"\S* copy\(", text)
    assert not copies, copies
    assert not cache.k.is_deleted()     # lowering consumes nothing


def test_gather_enqueued_before_a_donating_write_keeps_its_bytes(params):
    """A reader's RESULT outlives the pool it was read from: the runtime
    orders the gather before the in-place write."""
    mesh, ccfg, cache = _make_cache(1)
    k, v = _rows(cache, 16)
    cache.write_prefill(0, k, v)
    entries = [("f", int(p)) for p in cache.page_table[0, :2]]
    past_k, past_v = cache.gather_pages(entries)
    k2, v2 = _rows(cache, 16, seed=9)
    cache.write_prefill(0, k2, v2)          # overwrites the same pages
    np.testing.assert_array_equal(np.asarray(past_k[:, 0]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(past_v[:, 0]), np.asarray(v))
    now_k, _ = cache.gather_pages(entries)
    np.testing.assert_array_equal(np.asarray(now_k[:, 0]), np.asarray(k2))


# ---------------------------------------------------------------------------
# Served streams: in place == fed copies, bitwise
# ---------------------------------------------------------------------------


def _fed_copies(step):
    """The same step, handed copies: the pools it was called with stay
    whole, as they did before the pools were donated."""
    def fed(params, k_pool, v_pool, *rest):
        return step(params, jnp.copy(k_pool), jnp.copy(v_pool), *rest)
    return fed


VARIANTS = {
    "plain": dict(),
    "prefix_cache": dict(prefix_cache=True, session_ttl_steps=64),
    "spec_k": dict(spec_decode=True, spec_k=3),
}


def _serve(params, ndev, variant, copies):
    kw = dict(VARIANTS[variant])
    if variant == "spec_k":
        kw["drafter"] = ModelDrafter(CFG, params, slots=4, page_size=8,
                                     max_len=128, dtype=jnp.float32)
    eng = ServingEngine(CFG, params, mesh=mesh_1d(ndev), slots=4,
                        page_size=8, max_len=128, **kw)
    if copies:
        eng.step = _fed_copies(eng.step)
        if eng.verify_step is not None:
            eng.verify_step = _fed_copies(eng.verify_step)
        if "drafter" in kw:
            kw["drafter"].step = _fed_copies(kw["drafter"].step)
    if variant == "prefix_cache":
        spec = prefix_spec(num_requests=8, prompt_lens=(8, 13),
                           output_lens=(4, 7), prefix_lens=(32,),
                           num_prefixes=2, vocab_size=CFG.vocab_size)
    else:
        spec = LoadSpec(num_requests=8, rate_rps=200.0,
                        prompt_lens=(4, 9, 16), output_lens=(5, 9),
                        vocab_size=CFG.vocab_size, seed=3)
    reqs = generate(spec)
    report = eng.serve(reqs)
    assert report.completed == 8, report
    if variant == "prefix_cache":
        assert report.prefix_hits > 0
        eng._prefix.drop_all()
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    return {r.rid: tuple(r.tokens) for r in reqs}


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_served_stream_bitwise_equals_copy_fed_run(params, variant, ndev):
    in_place = _serve(params, ndev, variant, copies=False)
    fed_copies = _serve(params, ndev, variant, copies=True)
    assert in_place == fed_copies
    assert all(len(toks) > 0 for toks in in_place.values())
