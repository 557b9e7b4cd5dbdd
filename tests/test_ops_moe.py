"""The inference expert layer (``ops/moe.py``) and the page-table decode
kernels (``ops/attention.py``): the two routers by hand, nothing dropped,
the Mosaic kernels in interpret mode against ``jax.numpy`` (the grouped
matmul whole and in column slices, the page walk with one shared key and
with grouped key/value heads), and the shares of a layer split over four
holders adding up to the whole layer of the benchmark family's plain
reference.  Tiny sizes, float32, no clock."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import joyai_mla_moe as family
from horovod_tpu.ops import attention
from horovod_tpu.ops import moe

HI = jax.lax.Precision.HIGHEST


def _joyai_ffn(h, p, **kw):
    """``moe_ffn`` under JoyAI's router, as ``mla_moe._ffn`` calls it."""
    r = moe.route(h, p["router"]["kernel"],
                  p["router"]["e_score_correction_bias"], top_k=4, scale=2.5)
    return moe.moe_ffn(h, p, r, num_experts=16, **kw)


def _layer(seed=0, d=64, f=32, experts=16, bias=0.1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(k, *shape):
        return jax.random.normal(k, shape) / np.sqrt(shape[-2])

    return {
        "router": {"kernel": normal(ks[0], d, experts),
                   "e_score_correction_bias":
                       bias * jax.random.normal(ks[1], (experts,))},
        "experts": {"w_gate": normal(ks[2], experts, d, f),
                    "w_up": normal(ks[3], experts, d, f),
                    "w_down": normal(ks[4], experts, f, d)},
        "shared": {"w_gate": {"kernel": normal(ks[5], d, f)},
                   "w_up": {"kernel": normal(ks[6], d, f)},
                   "w_down": {"kernel": normal(ks[7], f, d)}}}


# -- the router, by hand ----------------------------------------------------------

def test_router_by_hand_with_a_tie_and_a_bias_that_only_chooses():
    """Two rows over four experts, top 2, scale 2.5.  Row 0's scores tie
    between experts 1 and 2 (``top_k`` takes the earlier).  The bias lifts
    expert 3 into row 1's choice; the WEIGHTS are made of the scores
    alone, so the bias changes who is chosen and not what a chosen expert
    weighs."""
    h = jnp.eye(2, dtype=jnp.float32)
    logits = jnp.asarray([[2.0, 0.0, 0.0, -2.0],
                          [1.0, 0.5, -3.0, 0.4]], jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    none = jnp.zeros((4,), jnp.float32)
    r = moe.route(h, logits, none, top_k=2, scale=2.5)
    np.testing.assert_array_equal(np.asarray(r.experts), [[0, 1], [0, 1]])
    np.testing.assert_allclose(
        np.asarray(r.weights[0]), 2.5 * s[0, [0, 1]] / s[0, [0, 1]].sum(),
        rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.05], jnp.float32)
    rb = moe.route(h, logits, bias, top_k=2, scale=2.5)
    np.testing.assert_array_equal(np.asarray(rb.experts[1]), [0, 3])
    np.testing.assert_allclose(
        np.asarray(rb.weights[1]), 2.5 * s[1, [0, 3]] / s[1, [0, 3]].sum(),
        rtol=1e-6)
    # Row 0 did not change its choice: neither did its weights.
    np.testing.assert_array_equal(np.asarray(rb.weights[0]),
                                  np.asarray(r.weights[0]))
    assert float(jnp.sum(rb.weights[1])) == pytest.approx(2.5, rel=1e-6)


def test_top1_router_by_hand_softmax_weight_and_a_bias_that_only_chooses():
    """Two rows over four experts.  The weight is the chosen expert's
    softmax score, not renormalised; the bias moves row 1 to expert 2
    and the weight it gets there is expert 2's own score."""
    logits = jnp.asarray([[2.0, 0.0, 0.0, -2.0],
                          [1.0, 0.5, 0.9, 0.4]], jnp.float32)
    z = np.exp(np.asarray(logits, np.float64))
    s = z / z.sum(-1, keepdims=True)
    r = moe.route_top1(logits, jnp.zeros((4,)))
    assert r.experts.shape == (2, 1) and r.weights.shape == (2, 1)
    np.testing.assert_array_equal(np.asarray(r.experts[:, 0]), [0, 0])
    np.testing.assert_allclose(np.asarray(r.weights[:, 0]), s[:, 0],
                               rtol=1e-6)
    rb = moe.route_top1(logits, jnp.asarray([0.0, 0.0, 0.05, 0.0]))
    np.testing.assert_array_equal(np.asarray(rb.experts[:, 0]), [0, 2])
    np.testing.assert_allclose(float(rb.weights[1, 0]), s[1, 2], rtol=1e-6)


@pytest.mark.parametrize("pairs,experts,want", [
    (96, 16, 16),        # a decode round of 96 slots, top 1 of 16
    (256, 16, 16), (512, 16, 32), (2048, 16, 128), (8192, 16, 128),
    (512, 256, 16),      # 64 slots, top 8 of 256: 2 rows an expert
    (8192, 256, 32)])
def test_row_tile_is_the_mean_run_as_a_power_of_two(pairs, experts, want):
    assert moe.row_tile(pairs, experts) == want


@pytest.mark.parametrize("kdim,n,weights,itemsize,want", [
    (2048, 768, 2, 2, 768),      # JoyAI gate and up: 12.6 MB, whole
    (768, 2048, 1, 2, 2048),     # JoyAI down: 6.3 MB, whole
    (2048, 2048, 2, 2, 1024),    # 16 wide experts, gate and up: 32 MB
    (2048, 2048, 1, 2, 2048),    # their down: 16 MB double-buffered, whole
    (2048, 2048, 2, 4, 512),     # the same in float32
    (128, 256, 2, 4, 256)])      # test sizes: whole
def test_column_block_is_chosen_from_shapes_alone(kdim, n, weights, itemsize,
                                                  want):
    assert moe._column_block(kdim, n, weights, itemsize) == want


def test_no_token_is_dropped_when_all_go_to_one_expert():
    """A bias that sends every token's first choice to expert 5: its run
    is ``tokens`` long (far above any capacity) and every row still gets
    the dense formula's result."""
    p = _layer(seed=1, bias=0.0)
    p["router"]["e_score_correction_bias"] = jnp.zeros((16,)).at[5].set(9.0)
    h = jax.random.normal(jax.random.PRNGKey(2), (48, 64))
    y, counts = _joyai_ffn(h, p)
    assert int(counts[5]) == 48 and int(counts.sum()) == 48 * 4
    want = family.ref_moe(h, p, top_k=4, scale=2.5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_layout_pads_runs_to_tiles_and_keeps_every_pair():
    experts_of = jnp.asarray([[0, 3], [3, 1], [3, 0], [2, 3]], jnp.int32)
    lay = moe.layout(experts_of, 4, 2)
    np.testing.assert_array_equal(np.asarray(lay.counts), [2, 1, 1, 4])
    # Runs of 2, 1, 1, 4 pairs in tiles of 2: 1 + 1 + 1 + 2 tiles.
    assert int(lay.active[0]) == 5
    np.testing.assert_array_equal(np.asarray(lay.tile_expert[:5]),
                                  [0, 1, 2, 3, 3])
    dest = np.asarray(lay.dest)
    assert len(set(dest.reshape(-1).tolist())) == 8      # nothing shared
    src = np.asarray(lay.src)
    for t in range(4):
        for j in range(2):
            assert src[dest[t, j]] == t
            assert np.asarray(lay.tile_expert)[dest[t, j] // 2] \
                == int(experts_of[t, j])
    assert bool(np.all(np.asarray(lay.held)))


# -- the kernels, interpreted, against jax.numpy ------------------------------------

@pytest.mark.parametrize("shape,budget,blocks", [
    # JoyAI's shape of tile (a narrow expert: the whole block a step).
    ((128, 256), None, 1),
    # A [k, k] expert whose gate and up do not fit the budget whole: the
    # column-sliced grid (the budget is scaled down with the test's k).
    ((256, 256), 2 * 2 * 256 * 128 * 4, 2),
    ((128, 512), 2 * 2 * 128 * 128 * 4, 4)])
def test_grouped_matmul_interpreted_matches_jnp(monkeypatch, shape, budget,
                                                blocks):
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    rng = np.random.RandomState(0)
    tm, tiles, experts = 16, 6, 5
    k, n = shape
    if budget is not None:
        monkeypatch.setattr(moe, "_WEIGHT_BLOCK_BUDGET", budget)
    assert n // moe._column_block(k, n, 2, 4) == blocks
    x = jnp.asarray(rng.normal(size=(tm * tiles, k)), jnp.float32)
    w0 = jnp.asarray(rng.normal(size=(experts, k, n)) / np.sqrt(k),
                     jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(experts, k, n)) / np.sqrt(k),
                     jnp.float32)
    te = jnp.asarray([0, 0, 2, 4, 4, 1], jnp.int32)
    active = jnp.asarray([4], jnp.int32)      # the last two tiles: skipped
    for ws in ((w0,), (w0, w1)):
        got = moe.grouped_matmul(x, ws, te, active, tm=tm)
        want = moe.grouped_matmul(x, ws, te, active, tm=tm,
                                  force_reference=True)
        by_hand = jnp.einsum("tmk,tkn->tmn", x.reshape(tiles, tm, k),
                             w0[te], precision=HI)
        if len(ws) == 2:
            by_hand = jax.nn.silu(by_hand) * jnp.einsum(
                "tmk,tkn->tmn", x.reshape(tiles, tm, k), w1[te],
                precision=HI)
        live = slice(0, 4 * tm)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got)[live],
            np.asarray(by_hand.reshape(tiles * tm, n))[live],
            rtol=1e-5, atol=1e-5)


def test_moe_ffn_interpreted_matches_the_reference(monkeypatch):
    p = _layer(seed=3)
    h = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    want = family.ref_moe(h, p, top_k=4, scale=2.5)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    live = jnp.arange(40) < 33
    y, counts = _joyai_ffn(h, p, live=live)
    np.testing.assert_allclose(np.asarray(y)[:33], np.asarray(want)[:33],
                               rtol=2e-5, atol=2e-5)
    assert int(counts.sum()) == 33 * 4          # dead rows route nowhere


def _paged(seed, b, pps, page, w, lengths, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    pages = b * pps + 1
    pool = jnp.asarray(rng.normal(size=(2, pages, page, w)), dtype)
    table = jnp.asarray(rng.permutation(pages - 1)[:b * pps].reshape(b, pps),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 4, w)), dtype)
    return q, pool, table, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("ppb,lengths", [
    (2, [0, 1, 16, 17, 40]),      # an idle row, a page's edges, a full row
    (3, [40, 5, 0, 24, 33]),      # blocks that do not divide the page list
    (8, [7, 40, 39, 1, 0])])      # one block a row
def test_mla_decode_interpreted_matches_jnp(monkeypatch, ppb, lengths):
    q, pool, table, lens = _paged(ppb, 5, 5, 8, 128, lengths)
    kw = dict(layer=1, lengths=lens, value_dim=96, scale=0.2)
    want = attention.mla_decode_attention(q, pool, table,
                                          force_reference=True, **kw)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(attention, "MLA_PAGES_PER_BLOCK", ppb)
    got = attention.mla_decode_attention(q, pool, table, **kw)
    assert got.shape == (5, 4, 96) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for i, n in enumerate(lengths):
        if n == 0:
            assert not np.any(np.asarray(got[i]))
    # By hand, one row: softmax over the live rows of its pages.
    i = int(np.argmax(lengths))
    kv = np.asarray(pool[1][table[i]]).reshape(-1, 128)[:lengths[i]]
    s = np.asarray(q[i]) @ kv.T * 0.2
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[i]), pr @ kv[:, :96],
                               rtol=2e-4, atol=2e-4)


def test_mla_decode_never_reads_past_the_length(monkeypatch):
    """Recycled-page garbage past ``lengths`` (huge, finite) changes
    nothing, kernel and ``jax.numpy`` alike."""
    q, pool, table, lens = _paged(9, 3, 4, 8, 128, [5, 20, 0])
    kw = dict(layer=0, lengths=lens, value_dim=64, scale=0.3)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(attention, "MLA_PAGES_PER_BLOCK", 2)
    clean = attention.mla_decode_attention(q, pool, table, **kw)
    view = np.array(pool[0][table])                # [b, pps, page, w]
    flat = view.reshape(3, -1, 128)
    for i, n in enumerate([5, 20, 0]):
        flat[i, n:] = 1e30
    dirty = pool.at[0, table].set(jnp.asarray(flat.reshape(view.shape)))
    got = attention.mla_decode_attention(q, dirty, table, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("ppb,lengths", [
    (2, [0, 1, 16, 17, 40]), (3, [40, 5, 0, 24, 33]), (8, [7, 40, 39, 1, 0])])
def test_cca_decode_interpreted_matches_jnp(monkeypatch, ppb, lengths):
    """Eight query heads over two key/value heads of 128 whose keys and
    values lie side by side in one 512-wide row: the walk of the page
    table, every head against each key/value head's columns and a row
    mask, against a gathered view and against one row by hand."""
    rng = np.random.RandomState(ppb)
    b, pps, page, d, kvh, h = 5, 5, 8, 128, 2, 8
    pool = jnp.asarray(rng.normal(size=(2, b * pps + 1, page, 2 * kvh * d)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kw = dict(layer=1, lengths=jnp.asarray(lengths, jnp.int32),
              kv_heads=kvh, scale=d ** -0.5)
    want = attention.cca_decode_attention(q, pool, table,
                                          force_reference=True, **kw)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(attention, "MLA_PAGES_PER_BLOCK", ppb)
    got = attention.cca_decode_attention(q, pool, table, **kw)
    assert got.shape == (b, h, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for i, n in enumerate(lengths):
        if n == 0:
            assert not np.any(np.asarray(got[i]))
    i = int(np.argmax(lengths))
    kv = np.asarray(pool[1][table[i]]).reshape(-1, 4 * d)[:lengths[i]]
    for head in (0, 3, 4, 7):
        g = head // 4
        s = np.asarray(q[i, head]) @ kv[:, g * d:(g + 1) * d].T * d ** -0.5
        pr = np.exp(s - s.max())
        pr /= pr.sum()
        np.testing.assert_allclose(
            np.asarray(got[i, head]),
            pr @ kv[:, (2 + g) * d:(3 + g) * d], rtol=2e-4, atol=2e-4)


# -- the chip's share adds up to the model ------------------------------------------

def test_four_shares_of_held_experts_add_up_to_the_whole_layer():
    """The expert layer run once for each of four ranges of held experts
    (each routes over all 16 and computes its own four), the shared
    expert counted once: the parts add up to the reference's whole
    layer."""
    p = _layer(seed=5)
    h = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
    whole = family.ref_moe(h, p, top_k=4, scale=2.5)
    total = jnp.zeros_like(h)
    routed = 0
    for share in range(4):
        first = 4 * share
        part = dict(p, experts={k: v[first:first + 4]
                                for k, v in p["experts"].items()})
        y, counts = _joyai_ffn(h, part, first=first,
                               with_shared=share == 0)
        ref_part = family.ref_moe(h, part, top_k=4, scale=2.5, first=first,
                                  with_shared=share == 0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   rtol=2e-5, atol=2e-5)
        total = total + y
        routed += int(counts[first:first + 4].sum())
        assert int(counts.sum()) == 24 * 4     # every share routes over all
    assert routed == 24 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=5e-5, atol=5e-5)
