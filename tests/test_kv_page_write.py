"""A prefilled prompt's rows go into the pools a page at a time.

``PagedKVCache.write_prefill`` (and the window group's writer under it)
writes the pages a prompt covers whole as PAGES and only the ragged ends
as rows.  Held here, case by case: the pools afterwards are bit for bit
what a scatter of single rows leaves (this file's own plain reference,
row by row in numpy), every page the write did not name keeps what it
held, the pools handed in are consumed (donated, never copied), and the
two counters say how many pages and rows went which way.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serving import kvcache
from horovod_tpu.timeline import metrics

LAYERS, WINDOW_LAYERS, PAGE = 2, 3, 16


def _bits(x):
    """The array's bytes as unsigned integers (a comparison no NaN or
    signed zero can blur)."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _noise(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.dtype(dtype))


def _row_scatter(pool, values, table, first, last):
    """The plain reference: ``pool`` (numpy) after rows ``first .. last -
    1`` of a slot land one at a time at ``(page, offset)`` through the
    slot's table, a ring where the rows reach past its end."""
    out = np.array(pool)
    for i, pos in enumerate(range(first, last)):
        out[:, table[pos // PAGE % len(table)], pos % PAGE] = values[:, i]
    return out


def _counters():
    reg = metrics.registry()
    return (reg.counter("kv.prefill_pages_written").value,
            reg.counter("kv.prefill_rows_written").value)


# (id, start, t, window, entries, dtype): ``window`` None where the cache
# has no window group; ``entries`` the two pools' trailing dims (the
# second None: one pool).
CASES = [
    ("aligned", 0, 64, None, ((32,), (32,)), "bfloat16"),
    ("aligned_f32", 0, 48, None, ((32,), (32,)), "float32"),
    ("one_pool", 0, 32, None, ((160,), None), "bfloat16"),
    ("head_dim", 0, 64, None, ((2, 16), (2, 16)), "bfloat16"),
    ("head_dim_one_pool_f32", 0, 40, None, ((2, 16), None), "float32"),
    ("ragged_tail", 0, 75, None, ((32,), (32,)), "bfloat16"),
    ("ragged_tail_head_dim", 0, 23, None, ((2, 16), (2, 16)), "float32"),
    ("shorter_than_a_page", 0, 9, None, ((32,), (32,)), "bfloat16"),
    ("seam_head", 21, 59, None, ((32,), (32,)), "bfloat16"),
    ("seam_head_and_tail", 37, 50, None, ((32,), None), "float32"),
    ("seam_inside_one_page", 35, 9, None, ((32,), (32,)), "bfloat16"),
    ("seam_two_pages_none_whole", 28, 12, None, ((32,), (32,)), "float32"),
    ("window_below", 0, 48, 64, ((32,), (32,)), "bfloat16"),
    ("window_head_15", 0, 64, 64, ((32,), (32,)), "bfloat16"),
    ("window_head_15_wrapped", 0, 128, 64, ((32,), (32,)), "bfloat16"),
    ("window_wrapped_ragged_tail_f32", 0, 203, 64, ((32,), (32,)),
     "float32"),
    ("window_wrapped_head_dim", 0, 160, 64, ((2, 16), (2, 16)), "bfloat16"),
]


@pytest.mark.parametrize("start,t,window,entries,dtype",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_pools_after_a_prefill_are_the_row_scatter_s(start, t, window,
                                                     entries, dtype):
    group = {} if window is None else dict(window_layers=WINDOW_LAYERS,
                                           window=window)
    cache = kvcache.PagedKVCache(kvcache.CacheConfig(
        num_layers=LAYERS, slots=3, page_size=PAGE, max_len=256,
        dtype=dtype, page=entries, **group))
    rng = np.random.RandomState(start + 7 * t)
    # Every page holds something beforehand, and another sequence owns
    # the pool's first pages: the slot's own are not 0, 1, 2 ...
    names = ("k", "v", "wk", "wv")
    for name in names:
        if getattr(cache, name) is not None:
            setattr(cache, name, _noise(rng, getattr(cache, name).shape,
                                        dtype))
    cache.reserve(0, 40)
    slot = 2
    given = {n: getattr(cache, n) for n in names
             if getattr(cache, n) is not None}
    # (Copies: a numpy view of a pool's buffer would keep the write from
    # taking the buffer over.)
    before = {n: np.array(a) for n, a in given.items()}
    rows = {n: _noise(rng, (LAYERS, t) + e, dtype)
            for n, e in zip("kv", entries) if e is not None}
    first = 0 if window is None else kvcache.window_rows_from(t, window)
    if window is not None:
        rows.update({"w" + n: _noise(rng, (WINDOW_LAYERS, t - first) + e,
                                     dtype)
                     for n, e in zip("kv", entries)})
    pages0, rows0 = _counters()

    cache.write_prefill(
        slot, rows["k"], rows.get("v"), start=start,
        window_rows=None if window is None else (rows["wk"], rows["wv"]))

    assert int(cache.lengths[slot]) == start + t
    for name, old in given.items():
        assert old.is_deleted(), f"{name} was copied, not consumed"
        ring = name.startswith("w")
        table = (cache.window_table if ring else cache.page_table)[slot]
        lo, hi = (first, t) if ring else (start, start + t)
        want = _row_scatter(before[name], np.asarray(rows[name]), table,
                            lo, hi)
        got = np.asarray(getattr(cache, name))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=name)
        # What the write did not name holds what it held: whole pages of
        # other owners, and the rows of the slot's own pages outside it.
        named = np.zeros(got.shape[1:3], bool)
        for pos in range(lo, hi):
            named[table[pos // PAGE % len(table)], pos % PAGE] = True
        np.testing.assert_array_equal(_bits(got)[:, ~named],
                                      _bits(before[name])[:, ~named])

    # The counters: one update a page or a row, a plane, a pool.
    def split(lo, hi):
        a, b = -(-lo // PAGE) * PAGE, hi // PAGE * PAGE
        whole = max(b - a, 0) // PAGE
        return whole, (hi - lo) - whole * PAGE

    pools = sum(e is not None for e in entries)
    whole, single = split(start, start + t)
    want_pages, want_rows = (LAYERS * pools * whole,
                             LAYERS * pools * single)
    if window is not None:
        whole, single = split(first, t)
        want_pages += WINDOW_LAYERS * pools * whole
        want_rows += WINDOW_LAYERS * pools * single
    pages1, rows1 = _counters()
    assert (pages1 - pages0, rows1 - rows0) == (want_pages, want_rows)
    assert PAGE * want_pages + want_rows == (
        LAYERS * pools * t
        + (0 if window is None else WINDOW_LAYERS * pools * (t - first)))


def test_a_prompt_length_is_one_program_a_pool():
    """A join dispatches what it dispatched: the pages and the ragged
    rows of one pool go through ONE jitted program a ``values`` shape,
    whichever pages the slot holds."""
    cache = kvcache.PagedKVCache(kvcache.CacheConfig(
        num_layers=LAYERS, slots=4, page_size=PAGE, max_len=256,
        page=((48,), (48,)), window_layers=WINDOW_LAYERS, window=64))
    rng = np.random.RandomState(0)
    was = kvcache._pool_set._cache_size()
    for slot, t in ((0, 128), (1, 128), (2, 128)):
        full = _noise(rng, (LAYERS, t, 48), "float32")
        tail = _noise(rng, (WINDOW_LAYERS, 63, 48), "float32")
        cache.write_prefill(slot, full, full, window_rows=(tail, tail))
    # One for the full planes' shape, one for the window planes'.
    assert kvcache._pool_set._cache_size() - was == 2
