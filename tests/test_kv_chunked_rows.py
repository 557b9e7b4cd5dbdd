"""A cache whose growing rows stand for CHUNKS of tokens beside a ring of
exact rows (``CacheConfig.row_tokens``, and with it ``window_aligned``
and ``window_in_pool``: EvaByte's layers): what a sequence of ``n`` bytes
holds in each group, for every ``n`` up to 5,000; that admission,
reservation and release price it so and leave nothing behind; where a
prefill's pooled rows and its last window's exact rows land; and that
every block served before lays out the pools, the tables and the free
lists it laid out before there were such rows (recorded on the parent of
PR 51)."""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.serving.kvcache import window_rows_from
from serving_families import FAMILIES_AND_EARLY_ROUTE

PAGE, CHUNK, WINDOW, RING = 16, 16, 2048, 129


def _cache(slots=2, max_len=14336, width=8, layers=2, **over):
    return serving.PagedKVCache(serving.CacheConfig(**dict(dict(
        num_layers=layers, slots=slots, page_size=PAGE, max_len=max_len,
        dtype="float32", page=((width,), (width,)), window_layers=layers,
        window=WINDOW, row_tokens=CHUNK), **over)))


def _held(cache, slot):
    return int(cache._allocated[slot]), int(cache._wallocated[slot])


# Every length to 600 (two pooled pages and a bit), then every edge of a
# chunk, of a growing page (256 bytes), of the ring (2,064 bytes) and of a
# window, and their neighbours, to 5,000.
LENGTHS = sorted(set(range(1, 601)) | {
    n + d for step in (16, 256, 2048, 2064) for n in range(step, 5001, step)
    for d in (-1, 0, 1) if n + d <= 5000} | {5000})


def test_the_lengths_hit_every_edge():
    assert len(LENGTHS) > 1400 and LENGTHS[-1] == 5000
    assert {255, 256, 257, 2047, 2048, 2049, 2063, 2064, 2065, 4096,
            4097} <= set(LENGTHS)


def test_a_sequence_holds_a_page_a_256_bytes_beside_its_ring():
    """Grown a reservation at a time through ``LENGTHS``, and reserved
    whole in a fresh slot: the same counts, the ones the model needs."""
    cache = _cache()
    c = cache.config
    assert (c.pages_per_slot, c.window_pages_per_slot) == (56, RING)
    assert (c.num_pages, c.window_num_pages, c.pool_pages) == (
        112, 258, 112 + 1 + 258)
    for n in LENGTHS:
        want = (-(-(-(-n // 16)) // 16), min(-(-n // 16), RING))
        cache.reserve(0, n, writable_from=max(n - 1, 0))
        assert _held(cache, 0) == want, n
        assert (c.pages_for(n), c.ring_pages_for(n)) == want
        cache.reserve(1, n)
        assert _held(cache, 1) == want, n
        cache.free_slot(1)
        assert cache.live_pages == sum(want) and cache.refcounts_balanced()
    # Full attention's count at the same length, for scale: 313 pages a
    # plane where this keeps 20 + 129.
    assert _held(cache, 0) == (20, RING) and -(-5000 // 16) == 313
    cache.free_slot(0)
    assert cache.live_pages == 0 and cache.refcounts_balanced()
    assert cache.free_pages == 112 and len(cache._wfree) == 258


def test_the_groups_pages_never_meet():
    """Growing ids lie below the scratch page, ring ids above it."""
    cache = _cache()
    cache.reserve(0, 5000)
    cache.reserve(1, 14336)
    c = cache.config
    assert c.scratch_page == 112 and c.window_first_page == 113
    for slot in (0, 1):
        grown, ring = _held(cache, slot)
        assert cache.page_table[slot, :grown].max() < c.scratch_page
        assert cache.window_table[slot, :ring].min() > c.scratch_page
        assert cache.window_table[slot, :ring].max() < c.pool_pages
    assert _held(cache, 1) == (56, RING)
    held = np.concatenate([cache.window_table[s, :RING] for s in (0, 1)])
    assert len(set(held.tolist())) == 2 * RING


def test_admission_prices_both_groups():
    cache = _cache(slots=2)
    assert cache.can_admit(14336) and cache.can_admit(1)
    cache.reserve(0, 14336)
    cache.reserve(1, 3000)
    # The growing pages of a third sequence are there (a slot's 56 are
    # sized for max_len); the ring's are not: 129 + 129 of 258 are held.
    assert cache.free_pages == 112 - 56 - 12
    assert not cache.can_admit(16) and len(cache._wfree) == 0
    with pytest.raises(ValueError, match="exceeds max_len"):
        cache.reserve(1, 14337)
    cache.free_slot(1)
    assert cache.can_admit(2064) and cache.can_admit(14336)
    cache.free_slot(0)
    assert cache.live_pages == 0 and cache.refcounts_balanced()
    assert cache.release_all() == 0


def test_the_ring_runs_short_before_any_page_is_taken():
    cache = _cache(slots=2, max_len=4096)
    cache.reserve(0, 4096)
    cache._wfree = cache._wfree[:10]          # another holder's, say
    with pytest.raises(RuntimeError, match="window page pool exhausted"):
        cache.reserve(1, 2064)
    assert _held(cache, 1) == (0, 0)


def test_pages_reused_are_counted_against_tokens_not_pooled_rows():
    from horovod_tpu.timeline import metrics
    cache = _cache()
    reused = metrics.registry().counter("kv.window_pages_reused")
    cache.reserve(0, 2064)                    # the ring is full: 129 pages
    before = reused.value
    for n in range(2065, 2065 + 64):          # four more pages of bytes
        cache.reserve(0, n, writable_from=n - 1)
    assert reused.value - before == 4
    assert _held(cache, 0) == (9, RING)


@pytest.mark.parametrize("t", [5, 16, 37, 64, 100, 150, 192])
def test_a_prefills_rows_land_where_the_rounds_will_look(t):
    """Pooled row ``c`` at row ``c % 16`` of growing page ``c // 16``; the
    last window's exact row ``j`` at row ``j % 16`` of ring entry ``j //
    16 % ring``; the length is what the two say together."""
    from horovod_tpu.timeline import metrics
    window, ring = 64, 5
    cache = _cache(max_len=512, width=4, window=window)
    pooled = metrics.registry().counter("kv.pooled_rows_written")
    before = pooled.value
    first = window_rows_from(t, window, True)
    assert first == t // window * window
    rng = np.random.RandomState(t)
    kbar, vbar = (rng.normal(size=(2, t // 16, 4)).astype(np.float32)
                  for _ in range(2))
    kring, vring = (rng.normal(size=(2, t - first, 4)).astype(np.float32)
                    for _ in range(2))
    cache.write_prefill(1, jnp.asarray(kbar), jnp.asarray(vbar),
                        window_rows=(jnp.asarray(kring), jnp.asarray(vring)))
    assert int(cache.lengths[1]) == t
    assert pooled.value - before == t // 16
    assert _held(cache, 1) == (-(-(-(-t // 16)) // 16), min(-(-t // 16), ring))
    k, v = np.asarray(cache.k), np.asarray(cache.v)
    for c in range(t // 16):
        page = cache.page_table[1, c // 16]
        np.testing.assert_array_equal(k[:, page, c % 16], kbar[:, c])
        np.testing.assert_array_equal(v[:, page, c % 16], vbar[:, c])
    for j in range(first, t):
        page = cache.window_table[1, j // 16 % ring]
        np.testing.assert_array_equal(k[:, page, j % 16], kring[:, j - first])
        np.testing.assert_array_equal(v[:, page, j % 16], vring[:, j - first])
    cache.free_slot(1)
    assert cache.live_pages == 0 and cache.refcounts_balanced()


def test_rows_that_are_no_prompts_are_refused():
    cache = _cache(max_len=512, width=4, window=64)
    rows = jnp.zeros((2, 3, 4))                    # three whole chunks ...
    ring = jnp.zeros((2, 20, 4))                   # ... and 20 ring rows
    with pytest.raises(ValueError, match="are no prompt's"):
        cache.write_prefill(0, rows, rows, window_rows=(ring, ring))
    with pytest.raises(ValueError, match="window rows missing"):
        cache.write_prefill(0, rows, rows)


@pytest.mark.parametrize("over,match", [
    (dict(window=2040), "aligned window of whole pages"),
    (dict(window=2056), "aligned window of whole pages"),
    (dict(window_layers=1), "a window plane a plane"),
    (dict(row_tokens=0), "tokens a row")])
def test_cache_config_refuses_what_does_not_fit(over, match):
    with pytest.raises(ValueError, match=match):
        _cache(**over)


# -- every block served before lays out what it laid out -----------------------

# ``CacheConfig.layout()``, the shapes of the pools, the slot state and the
# tables, the free lists' lengths and the count of arrays a step carries,
# of a three-slot engine with pages of 8 and contexts of 32 over each
# family's tiny configuration: recorded on the parent of PR 51.
LAID_OUT = {
 "cca_moe": {
  "arrays": {
   "k": [
    3,
    13,
    8,
    64
   ],
   "state": [
    3,
    3,
    208
   ],
   "v": None,
   "wk": None,
   "wv": None
  },
  "carried": 1,
  "free": [
   12,
   0
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    3,
    13,
    8,
    64
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": None
  }
 },
 "dense": {
  "arrays": {
   "k": [
    2,
    13,
    8,
    128
   ],
   "state": None,
   "v": [
    2,
    13,
    8,
    128
   ],
   "wk": None,
   "wv": None
  },
  "carried": 0,
  "free": [
   12,
   0
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    2,
    13,
    8,
    128
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": None
  }
 },
 "loop_dense": {
  "arrays": {
   "k": [
    9,
    13,
    8,
    64
   ],
   "state": None,
   "v": None,
   "wk": None,
   "wv": None
  },
  "carried": 0,
  "free": [
   12,
   0
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    9,
    13,
    8,
    64
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": None
  }
 },
 "mla_moe": {
  "arrays": {
   "k": [
    3,
    13,
    8,
    128
   ],
   "state": None,
   "v": None,
   "wk": None,
   "wv": None
  },
  "carried": 0,
  "free": [
   12,
   0
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    3,
    13,
    8,
    128
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": None
  }
 },
 "ssm_hybrid": {
  "arrays": {
   "k": [
    2,
    13,
    8,
    16
   ],
   "state": [
    2,
    3,
    800
   ],
   "v": [
    2,
    13,
    8,
    16
   ],
   "wk": None,
   "wv": None
  },
  "carried": 1,
  "free": [
   12,
   0
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    2,
    13,
    8,
    16
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": None
  }
 },
 "swa_moe": {
  "arrays": {
   "k": [
    1,
    13,
    8,
    32
   ],
   "state": None,
   "v": [
    1,
    13,
    8,
    32
   ],
   "wk": [
    3,
    7,
    8,
    32
   ],
   "wv": [
    3,
    7,
    8,
    32
   ]
  },
  "carried": 2,
  "free": [
   12,
   6
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    1,
    13,
    8,
    32
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12,
   "window": 8,
   "window_kv_shape": [
    3,
    7,
    8,
    32
   ],
   "window_num_pages": 6,
   "window_pages_per_slot": 2,
   "window_scratch_page": 6,
   "window_table_shape": [
    3,
    2
   ]
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": [
    3,
    2
   ]
  }
 },
 "swa_moe_early_route": {
  "arrays": {
   "k": [
    1,
    13,
    8,
    32
   ],
   "state": None,
   "v": [
    1,
    13,
    8,
    32
   ],
   "wk": [
    3,
    7,
    8,
    32
   ],
   "wv": [
    3,
    7,
    8,
    32
   ]
  },
  "carried": 2,
  "free": [
   12,
   6
  ],
  "layout": {
   "dtype": "float32",
   "kv_shape": [
    1,
    13,
    8,
    32
   ],
   "num_pages": 12,
   "page_size": 8,
   "page_table_shape": [
    3,
    4
   ],
   "pages_per_slot": 4,
   "scratch_page": 12,
   "window": 8,
   "window_kv_shape": [
    3,
    7,
    8,
    32
   ],
   "window_num_pages": 6,
   "window_pages_per_slot": 2,
   "window_scratch_page": 6,
   "window_table_shape": [
    3,
    2
   ]
  },
  "tables": {
   "page_table": [
    3,
    4
   ],
   "window_table": [
    3,
    2
   ]
  }
 }
}


@pytest.mark.parametrize("family", list(FAMILIES_AND_EARLY_ROUTE))
def test_every_block_lays_out_the_pools_it_laid_out_before(family):
    cfg, params = FAMILIES_AND_EARLY_ROUTE[family]()
    eng = serving.ServingEngine(cfg, params, slots=3, page_size=8,
                                max_len=32, dtype=jnp.float32)
    c = eng.cache

    def shapes(*names):
        return {n: None if getattr(c, n) is None
                else list(getattr(c, n).shape) for n in names}

    got = {"layout": c.layout(),
           "arrays": shapes("k", "v", "wk", "wv", "state"),
           "tables": shapes("page_table", "window_table"),
           "free": [len(c._free), len(c._wfree)],
           "carried": len(c.carried)}
    if family == "eva_dense":
        # The new block: a window of 16 in chunks of 8 here, both groups
        # in one pair of pools (1 growing page and a ring of 3 a slot).
        assert got == {
            "layout": {
                "dtype": "float32", "kv_shape": [2, 13, 8, 128],
                "num_pages": 3, "page_size": 8, "page_table_shape": [3, 1],
                "pages_per_slot": 1, "scratch_page": 3, "window": 16,
                "window_num_pages": 9, "window_pages_per_slot": 3,
                "window_table_shape": [3, 3], "row_tokens": 8,
                "window_first_page": 4},
            "arrays": {"k": [2, 13, 8, 128], "v": [2, 13, 8, 128],
                       "wk": None, "wv": None, "state": None},
            "tables": {"page_table": [3, 1], "window_table": [3, 3]},
            "free": [3, 9], "carried": 0}
        return
    assert got == LAID_OUT[family]
    assert (c.config.row_tokens, c.config.window_aligned,
            c.config.window_in_pool) == (1, False, False)
