"""The inference expert layer (``ops/moe.py``) and the page-table decode
kernels (``ops/attention.py``): the two routers by hand, nothing dropped,
the Mosaic kernels in interpret mode against ``jax.numpy`` (the grouped
matmul whole and in column slices, the page walk with one shared key and
with grouped key/value heads), and the shares of a layer split over four
holders adding up to the whole layer of the benchmark family's plain
reference.  Tiny sizes, float32, no clock."""

import functools

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


def _walk(monkeypatch, ppb, sub):
    """The kernels on, interpreted, ``ppb`` pages a block and ``sub`` keys
    a sub-block of the math."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(attention, "MLA_PAGES_PER_BLOCK", ppb)
    monkeypatch.setattr(attention, "MLA_KEYS_PER_SUB_BLOCK", sub)


def _at_plane(fn, plane, traced):
    """``fn(layer=...)`` with the plane a part of the program or, as a
    looped model hands it over, a traced scalar."""
    if not traced:
        return fn(layer=plane)
    return jax.jit(lambda t: fn(layer=t))(jnp.int32(plane))


# One row for every count of live pages a block of 8 can hold, each ending
# inside its last page, and one ending on every page's edge (with 16 keys
# a sub-block: on every sub-block's edge too).
_EVERY_COUNT = [8 * n - 3 for n in range(1, 9)] + [8 * n for n in range(1, 9)]

_WALKS = [
    # ppb, keys a sub-block, lengths, pages a slot, plane traced
    (2, 256, [0, 1, 16, 17, 40], 5, False),   # an idle row, a page's edges
    (3, 256, [40, 5, 0, 24, 33], 5, False),   # blocks that do not divide
    (8, 256, [7, 40, 39, 1, 0], 5, False),    # one block a row, one part
    (8, 16, _EVERY_COUNT, 8, False),          # every count, four parts
    (8, 16, _EVERY_COUNT, 8, True),
    (8, 32, [64, 128, 127, 129, 192, 0, 1, 65], 24, False),  # three blocks
    (6, 16, [48, 47, 49, 96, 1, 0, 33, 144], 18, False),     # ppb 6: 4 + 2
    (6, 24, [48, 47, 49, 96, 1, 0, 33, 144], 18, True),      # parts of 3
    (5, 16, [40, 39, 41, 80, 8, 0, 9, 120], 15, False),      # parts of 1
    (8, 16, [0, 0, 0], 8, False),             # no item at all
    (4, 16, [0, 5, 0], 8, False),             # fewer items than buffers
]


@pytest.mark.parametrize("ppb,sub,lengths,pps,traced", _WALKS)
def test_mla_decode_interpreted_matches_jnp(monkeypatch, ppb, sub, lengths,
                                            pps, traced):
    b = len(lengths)
    q, pool, table, lens = _paged(ppb, b, pps, 8, 128, lengths)
    kw = dict(lengths=lens, value_dim=96, scale=0.2)
    want = attention.mla_decode_attention(q, pool, table, layer=1,
                                          force_reference=True, **kw)
    _walk(monkeypatch, ppb, sub)
    got = _at_plane(functools.partial(attention.mla_decode_attention, q,
                                      pool, table, **kw), 1, traced)
    assert got.shape == (b, 4, 96) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for i, n in enumerate(lengths):
        if n == 0:
            assert not np.any(np.asarray(got[i]))
    # By hand, one row: softmax over the live rows of its pages.
    i = int(np.argmax(lengths))
    if not lengths[i]:
        return      # every row idle: the zeros above are the whole check
    kv = np.asarray(pool[1][table[i]]).reshape(-1, 128)[:lengths[i]]
    s = np.asarray(q[i]) @ kv.T * 0.2
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[i]), pr @ kv[:, :96],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kvh", [1, 2])
def test_page_walk_with_two_blocks_in_flight(monkeypatch, kvh):
    """Under wide resident rows the walk keeps two blocks in VMEM, not
    three (``_WALK_VMEM_BUDGET``): the same results, latent and grouped."""
    lengths = _EVERY_COUNT + [0, 129, 191]
    b, pps, page, d = len(lengths), 24, 8, 128
    rng = np.random.RandomState(7)
    pool = jnp.asarray(rng.normal(
        size=(2, b * pps + 1, page, d if kvh == 1 else 2 * kvh * d)),
        jnp.float32)
    table = jnp.asarray(rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 8, d)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    if kvh == 1:
        fn = functools.partial(attention.mla_decode_attention, q, pool,
                               table, layer=1, lengths=lens, value_dim=96,
                               scale=0.2)
    else:
        fn = functools.partial(attention.cca_decode_attention, q, pool,
                               table, layer=1, lengths=lens, kv_heads=kvh,
                               scale=d ** -0.5)
    want = fn(force_reference=True)
    _walk(monkeypatch, 8, 32)
    three = fn()
    monkeypatch.setattr(attention, "_WALK_VMEM_BUDGET", 0)
    two = fn()
    np.testing.assert_allclose(np.asarray(two), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(two), np.asarray(three))


def test_mla_decode_never_reads_past_the_length(monkeypatch):
    """Recycled-page garbage past ``lengths`` (huge, finite) changes
    nothing, kernel and ``jax.numpy`` alike."""
    q, pool, table, lens = _paged(9, 3, 4, 8, 128, [5, 20, 0])
    kw = dict(layer=0, lengths=lens, value_dim=64, scale=0.3)
    _walk(monkeypatch, 2, 256)
    clean = attention.mla_decode_attention(q, pool, table, **kw)
    view = np.array(pool[0][table])                # [b, pps, page, w]
    flat = view.reshape(3, -1, 128)
    for i, n in enumerate([5, 20, 0]):
        flat[i, n:] = 1e30
    dirty = pool.at[0, table].set(jnp.asarray(flat.reshape(view.shape)))
    got = attention.mla_decode_attention(q, dirty, table, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("sub", [256, 16])
def test_cca_decode_never_reads_past_the_length(monkeypatch, sub):
    """The twin under two key/value heads, and what a partial block
    leaves in its buffer: 1e30 in every cached row past a length, and
    row 0's LIVE tokens 1e30 as well, so that both halves of the buffer
    hold them when the short rows after it copy one page and two pages
    over a block of four.  No other row's result moves by a bit."""
    lengths = [64, 5, 12, 0, 33, 1]
    b, pps, page, d, kvh, h = len(lengths), 8, 8, 128, 2, 8
    rng = np.random.RandomState(3)
    pool = jnp.asarray(rng.normal(size=(1, b * pps + 1, page, 2 * kvh * d)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kw = dict(layer=0, lengths=jnp.asarray(lengths, jnp.int32),
              kv_heads=kvh, scale=d ** -0.5)
    _walk(monkeypatch, 4, sub)
    clean = attention.cca_decode_attention(q, pool, table, **kw)
    flat = np.array(pool[0][table]).reshape(b, pps * page, -1)
    for i, n in enumerate(lengths):
        flat[i, n:] = 1e30
    flat[0] = 1e30
    dirty = pool.at[0, table].set(
        jnp.asarray(flat.reshape(b, pps, page, -1)))
    got = attention.cca_decode_attention(q, dirty, table, **kw)
    np.testing.assert_array_equal(np.asarray(got[1:]), np.asarray(clean[1:]))
    want = attention.cca_decode_attention(q, dirty, table,
                                          force_reference=True, **kw)
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:]),
                               rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[3]))


@pytest.mark.parametrize("h,kvh", [(8, 2), (16, 16)])
@pytest.mark.parametrize("ppb,sub,lengths,pps,traced", _WALKS)
def test_cca_decode_interpreted_matches_jnp(monkeypatch, ppb, sub, lengths,
                                            pps, traced, h, kvh):
    """Eight query heads over two key/value heads of 128 whose keys and
    values lie side by side in one 512-wide row, and the looped model's
    class, one query head a key head in a 4,096-wide row: the walk of
    the page table, every head against each key/value head's columns
    and a row mask, against a gathered view and against one row by
    hand."""
    rng = np.random.RandomState(ppb)
    b, page, d = len(lengths), 8, 128
    pool = jnp.asarray(rng.normal(size=(2, b * pps + 1, page, 2 * kvh * d)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kw = dict(lengths=jnp.asarray(lengths, jnp.int32),
              kv_heads=kvh, scale=d ** -0.5)
    want = attention.cca_decode_attention(q, pool, table, layer=1,
                                          force_reference=True, **kw)
    _walk(monkeypatch, ppb, sub)
    got = _at_plane(functools.partial(attention.cca_decode_attention, q,
                                      pool, table, **kw), 1, traced)
    assert got.shape == (b, h, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for i, n in enumerate(lengths):
        if n == 0:
            assert not np.any(np.asarray(got[i]))
    i = int(np.argmax(lengths))
    if not lengths[i]:
        return      # every row idle: the zeros above are the whole check
    kv = np.asarray(pool[1][table[i]]).reshape(-1, 2 * kvh * d)[:lengths[i]]
    rep = h // kvh
    for head in (0, rep - 1, rep, h - 1):
        g = head // rep
        s = np.asarray(q[i, head]) @ kv[:, g * d:(g + 1) * d].T * d ** -0.5
        pr = np.exp(s - s.max())
        pr /= pr.sum()
        np.testing.assert_allclose(
            np.asarray(got[i, head]),
            pr @ kv[:, (kvh + g) * d:(kvh + g + 1) * d],
            rtol=2e-4, atol=2e-4)


# -- the chip's share adds up to the model ------------------------------------------

@pytest.mark.parametrize("block", ["sigmoid_silu_shared",
                                   "topk_softmax_relu_no_shared"])
def test_four_shares_of_held_experts_add_up_to_the_whole_layer(block):
    """The expert layer run once for each of four ranges of held experts
    (each routes over all 16 and computes its own four), the shared
    expert -- where the model has one -- counted once: the parts add up
    to the reference's whole layer.  Both served blocks: JoyAI's and
    K-EXAONE's (sigmoid scores, SiLU gates, a shared expert) against the
    JoyAI family's reference, and SmallThinker's (a softmax over the
    chosen logits, ReLU gates, no shared expert) against its family's."""
    p = _layer(seed=5)
    h = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
    if block == "sigmoid_silu_shared":
        def ffn(part, first, share):
            return _joyai_ffn(h, part, first=first, with_shared=share == 0)

        def ref(part, first=0, share=0):
            return family.ref_moe(h, part, top_k=4, scale=2.5, first=first,
                                  with_shared=share == 0)
    else:
        from benchmarks.families import smallthinker_swa_moe as small
        logits = small.ref_logits(h, p["router"])
        routing = moe.route_topk_softmax(h, p["router"]["kernel"], top_k=4)
        dense = small._weights_of(logits, routing.experts)

        def ffn(part, first, share):
            return moe.moe_ffn(h, part, routing, num_experts=16, first=first,
                               with_shared=False, gate_act="relu")

        def ref(part, first=0, share=0):
            # The family's reference holds every expert: a share's part is
            # the whole layer under the held experts' weights alone.
            held = part["experts"]["w_gate"].shape[0]
            y = jnp.zeros_like(h)
            for i in range(held):
                y += dense[:, first + i, None] * small._expert_block(
                    h, part["experts"], i, 1, lambda z: z)[0]
            return y

        np.testing.assert_allclose(
            np.asarray(ref(p)), np.asarray(small.ref_moe(
                h, logits, p, top_k=4)), rtol=2e-5, atol=2e-5)
    whole = ref(p)
    total = jnp.zeros_like(h)
    routed = 0
    for share in range(4):
        first = 4 * share
        part = dict(p, experts={k: v[first:first + 4]
                                for k, v in p["experts"].items()})
        y, counts = ffn(part, first, share)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ref(part, first, share)),
                                   rtol=2e-5, atol=2e-5)
        total = total + y
        routed += int(counts[first:first + 4].sum())
        assert int(counts.sum()) == 24 * 4     # every share routes over all
    assert routed == 24 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=5e-5, atol=5e-5)


# -- the other blocks' expert layers lower to what they lowered to ---------------------

# sha256 of the TPU lowering (Mosaic bodies printed without source
# locations) of ``moe_ffn`` under ZAYA's router (top 1 of 16 experts of
# 2,048 x 2,048, a decode round's 96 rows and a 512-token prompt) and
# under JoyAI's (top 8 of 256 experts of 768 beside a shared one, a round's
# 64 rows and an 8,192-token prompt), recorded on PR 41's tree, the parent
# of the PR that gave the grouped matmul its gate by name, ``moe_ffn`` a
# layout made ahead and the module a third router.
_EXPERT_LAYER_LOWERED = {
    ("zaya", 96):
        "804fd12a8ae71243975cc615751fe542064573f2a4e78e00d038f1dfba47bd6d",
    ("zaya", 512):
        "aa39213f44edb49376fd11c4f7b6fea349fb1b839ce82664c2e557159bee4f51",
    ("joyai", 64):
        "bf5130931339457d54c0025a6c20c69e5729cdd759fcfa7524de40124895f785",
    ("joyai", 8192):
        "571ea45c8501c267f1882adec695496d78f9b49a57b0d4279a641d0d2b9eb93e",
}


@pytest.mark.parametrize("block,rows", list(_EXPERT_LAYER_LOWERED))
def test_the_other_blocks_expert_layers_lower_to_what_they_lowered_to(
        monkeypatch, block, rows):
    import hashlib

    from serving_families import lowered_for_tpu as _lowered_for_tpu
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(attention._pallas, "interpret_mode", lambda: False)
    S, bf, f32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.float32

    def tree(e, d, f, shared):
        p = {"experts": {"w_gate": S((e, d, f), bf), "w_up": S((e, d, f), bf),
                         "w_down": S((e, f, d), bf)}}
        if shared:
            p["shared"] = {"w_gate": {"kernel": S((d, f), bf)},
                           "w_up": {"kernel": S((d, f), bf)},
                           "w_down": {"kernel": S((f, d), bf)}}
        return p

    if block == "zaya":
        def zaya(h, p, logits, bias, live):
            return moe.moe_ffn(h, p, moe.route_top1(logits, bias),
                               num_experts=16, with_shared=False, live=live)
        fn = zaya          # (the function's name is in the lowered text)
        args = (S((rows, 2048), bf), tree(16, 2048, 2048, False),
                S((rows, 16), f32), S((16,), f32), S((rows,), jnp.bool_))
    else:
        def joyai(h, p, wr, bias, live):
            return moe.moe_ffn(
                h.astype(bf), p, moe.route(h, wr, bias, top_k=8, scale=2.5),
                num_experts=256, live=live)
        fn = joyai
        args = (S((rows, 2048), f32), tree(256, 2048, 768, True),
                S((2048, 256), bf), S((256,), bf), S((rows,), jnp.bool_))
    text = _lowered_for_tpu(fn, *args)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _EXPERT_LAYER_LOWERED[block, rows]
