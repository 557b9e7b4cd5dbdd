"""The window-and-full routed block's SECOND instance
(``serving/swa_moe.py`` in SmallThinker's variant): a router that reads the
layer's input ahead of attention, the top k logits under a softmax over
the chosen, experts gated by ReLU, no shared expert, no per-head norm,
full layers that rotate nothing first in a period (``G L L L``), and seven
query heads a key/value head -- through ``ServingEngine`` (prefill, decode
across the window's edge, a ring that wraps, a prompt longer than the
window) against a plain forward written here, each equation with a wrong
variant that fails; the third router and the ReLU epilogue of the grouped
matmul by hand; and where in the decode program the routing is made.
Tiny sizes, float32, no clock."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.ops import moe
from horovod_tpu.serving import swa_moe
from horovod_tpu.timeline import metrics as _metrics
from serving_families import EARLY_EPS as EPS
from serving_families import EARLY_KINDS as KINDS
from serving_families import EARLY_THETA as THETA
from serving_families import EARLY_TOP_K as TOP_K
from serving_families import EARLY_WINDOW as WINDOW
from serving_families import early_route as _tiny

HI = jax.lax.Precision.HIGHEST
PAGE = 4


# -- the plain forward ------------------------------------------------------------

def _rms(x, scale=1.0):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HI)


def _rope(z, pos):
    d = z.shape[-1]
    freqs = THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    a, b = z[..., :d // 2], z[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def plain_logits(params, tokens, *, route_from="layer_input", gate="relu",
                 weigh="softmax_of_chosen", rotate_full=False,
                 head_norm=False):
    """The equations of ISSUE 42 over one context, every query against
    every key under a mask, every expert applied to every row; the
    keywords' other values are the WRONG variants."""
    p = params["params"]
    t = len(tokens)
    pos = jnp.arange(t)
    x = p["tok_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[gate]
    for li, kind in enumerate(KINDS):
        blk = p[f"layer_{li}"]
        a = blk["attn"]
        entered = x
        h = _rms(x, blk["attn_norm"]["scale"])
        q = _mm(h, a["wq"]["kernel"]).reshape(t, 14, 16)
        k = _mm(h, a["wk"]["kernel"]).reshape(t, 2, 16)
        v = _mm(h, a["wv"]["kernel"]).reshape(t, 2, 16)
        if head_norm:
            q, k = _rms(q), _rms(k)
        if kind == "window" or rotate_full:
            q, k = _rope(q, pos), _rope(k, pos)
        k, v = jnp.repeat(k, 7, axis=1), jnp.repeat(v, 7, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(16)
        seen = pos[:, None] >= pos[None, :]
        if kind == "window":
            seen &= pos[:, None] - pos[None, :] < WINDOW
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v,
                       precision=HI).reshape(t, 14 * 16)
        x = x + _mm(o, a["wo"]["kernel"])
        h = _rms(x, blk["mlp_norm"]["scale"])
        r = _mm(entered if route_from == "layer_input" else h,
                blk["moe"]["router"]["kernel"])
        chosen, idx = jax.lax.top_k(r, TOP_K)
        g = jax.nn.softmax(chosen, -1) if weigh == "softmax_of_chosen" \
            else jax.nn.sigmoid(chosen)
        ex = blk["moe"]["experts"]
        every = jnp.einsum(
            "etf,efd->etd",
            act(jnp.einsum("td,edf->etf", h, ex["w_gate"], precision=HI))
            * jnp.einsum("td,edf->etf", h, ex["w_up"], precision=HI),
            ex["w_down"], precision=HI)                     # [e, t, d]
        dense = jnp.zeros((t, 8)).at[jnp.arange(t)[:, None], idx].set(g)
        x = x + jnp.einsum("etd,te->td", every, dense, precision=HI)
    return _mm(_rms(x, p["final_norm"]["scale"]), p["lm_head"]["kernel"])


def _requests(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [serving.Request(
        rid=i, prompt=rng.randint(0, 64, size=n).astype(np.int32),
        max_new_tokens=m, arrival_s=0.0) for i, (n, m) in enumerate(lens)]


# -- (1) the engine against the plain forward ---------------------------------------

@pytest.mark.parametrize("kernels", ["off", "interpreted"])
def test_engine_agrees_with_the_plain_forward(monkeypatch, kernels):
    """Prefill, then decode through both groups of planes with seven
    query heads a key/value head: a request that stays under the window
    (3 + 3), ones that CROSS it while they decode (5 + 20: the ring of 3
    pages wraps, pages written again), prompts longer than the window
    (19, 33: the window planes get their last rows only).  Every served
    token is the plain forward's best to rounding."""
    if kernels == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    cfg, params = _tiny()
    eng = serving.ServingEngine(cfg, params, slots=3, page_size=PAGE,
                                max_len=64, dtype=jnp.float32)
    meta = eng.step.meta
    assert (meta["route_from"], meta["gate_act"], meta["heads"],
            meta["kv_heads"]) == ("layer_input", "relu", 14, 2)
    assert meta["attn_kinds"] == KINDS
    assert eng.cache.config.window_pages_per_slot == 3
    reused = _metrics.registry().counter("kv.window_pages_reused")
    before = reused.value
    reqs = _requests([(5, 20), (19, 30), (33, 12), (8, 40), (3, 3)])
    report = eng.serve(reqs)
    assert report.completed == 5 and report.new_tokens == 105
    assert eng.cache.live_pages == 0 and eng.cache.refcounts_balanced()
    assert reused.value - before >= 20
    for r in reqs:
        ctx = np.concatenate([r.prompt, np.asarray(r.tokens)])
        rows = plain_logits(params, ctx)[len(r.prompt) - 1:-1]
        gap = jnp.max(rows, -1) - rows[jnp.arange(len(r.tokens)),
                                       jnp.asarray(r.tokens)]
        assert float(jnp.max(gap)) < 1e-3, r.rid


def test_the_round_span_says_what_of_the_window_group_slots_hold():
    """``decode.round`` files ``window_pages_held``: a ring grows page by
    page, so a short request never holds a whole one."""
    from horovod_tpu.timeline import spans
    cfg, params = _tiny()
    eng = serving.ServingEngine(cfg, params, slots=3, page_size=PAGE,
                                max_len=64, dtype=jnp.float32)
    eng.serve(_requests([(3, 4), (19, 6)]))
    records = spans.recorder().records()
    call = [r for r in records if r.name == "serve"][-1]     # this serve's
    held = [r.attrs["window_pages_held"] for r in records
            if r.name == "decode.round" and r.start_ns >= call.start_ns]
    assert held and min(held) >= 1
    # Slot of 3 + 4 tokens: at most 2 pages of its ring of 3; the other
    # slot's ring is full; the group has 9 pages.
    assert max(held) <= 5 < eng.cache.config.window_num_pages == 9


# -- (2) each equation, and the variant that fails ---------------------------------

WRONG = {
    "routes_from_the_ffn_input": dict(route_from="ffn_input"),
    "silu_for_relu": dict(gate="silu"),
    "sigmoid_weights": dict(weigh="sigmoid"),
    "a_rotated_full_layer": dict(rotate_full=True),
    "a_head_norm_left_on": dict(head_norm=True),
}


@pytest.fixture(scope="module")
def prefilled():
    cfg, params = _tiny()
    prompt = (np.arange(20) * 7 + 3) % 64
    logits = swa_moe.prefill_forward(params, cfg, jnp.asarray(prompt)[None],
                                     last_only=False)[0][0]
    return params, prompt, np.asarray(logits)


def test_the_prefill_computes_the_plain_forward(prefilled):
    params, prompt, logits = prefilled
    np.testing.assert_allclose(logits, np.asarray(
        plain_logits(params, prompt)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_variant_of_an_equation_fails(prefilled, variant):
    params, prompt, logits = prefilled
    off = plain_logits(params, prompt, **WRONG[variant])
    assert float(np.max(np.abs(np.asarray(off) - logits))) > 1e-2


def test_the_program_s_own_variants_are_the_other_block_s(prefilled):
    """The same weights through the program under K-EXAONE's values of
    the two fields that need no further parameter: far from the
    SmallThinker forward, at the plain forward's wrong variants."""
    params, prompt, _ = prefilled
    for over, wrong in ((dict(route_from="ffn_input"),
                         "routes_from_the_ffn_input"),
                        (dict(gate_act="silu"), "silu_for_relu")):
        cfg, _ = _tiny(**over)
        got = swa_moe.prefill_forward(
            params, cfg, jnp.asarray(prompt)[None], last_only=False)[0][0]
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(plain_logits(params, prompt, **WRONG[wrong])),
            rtol=2e-4, atol=2e-4)


def test_the_variants_a_config_may_not_name_are_refused():
    for over in (dict(router="softmax"), dict(route_from="attention"),
                 dict(gate_act="gelu"), dict(num_shared_experts=-1),
                 dict(ffn_kinds=("dense", "moe", "moe", "moe"))):
        with pytest.raises(ValueError, match="router"):
            _tiny(**over)
    # The tree holds what the variant computes with, and nothing else.
    cfg, params = _tiny()
    blk = params["params"]["layer_1"]
    assert set(blk["attn"]) == {"wq", "wk", "wv", "wo"}
    assert set(blk["moe"]) == {"router", "experts"}
    assert set(blk["moe"]["router"]) == {"kernel"}


# -- (3) the third router -----------------------------------------------------------

def test_topk_softmax_router_by_hand():
    """Two rows over five experts, top 3: the three largest LOGITS, each
    weighed by the softmax over the three; the weights sum to one and are
    the softmax over all five renormalised over the chosen."""
    x = jnp.eye(2, dtype=jnp.float32)
    logits = jnp.asarray([[2.0, -1.0, 0.5, 0.0, 3.0],
                          [0.1, 0.2, 0.3, 0.4, -5.0]], jnp.float32)
    r = moe.route_topk_softmax(x, logits, top_k=3)
    np.testing.assert_array_equal(np.asarray(r.experts),
                                  [[4, 0, 2], [3, 2, 1]])
    np.testing.assert_allclose(np.asarray(r.weights.sum(-1)), [1.0, 1.0],
                               rtol=1e-6)
    over_all = np.asarray(jax.nn.softmax(logits, -1), np.float64)
    for row in range(2):
        picked = over_all[row, np.asarray(r.experts[row])]
        np.testing.assert_allclose(np.asarray(r.weights[row]),
                                   picked / picked.sum(), rtol=1e-6)
    e = np.exp([3.0, 2.0, 0.5])
    np.testing.assert_allclose(np.asarray(r.weights[0]), e / e.sum(),
                               rtol=1e-6)


# -- (4) the grouped matmul's ReLU epilogue -----------------------------------------

@pytest.mark.parametrize("path", ["jnp", "interpreted"])
def test_grouped_matmul_with_relu_gates(monkeypatch, path):
    if path == "interpreted":
        monkeypatch.setenv("HOROVOD_PALLAS", "1")
    rng = np.random.RandomState(0)
    tm, tiles, experts, k, n = 16, 5, 4, 128, 256
    x = jnp.asarray(rng.normal(size=(tm * tiles, k)), jnp.float32)
    w0, w1 = (jnp.asarray(rng.normal(size=(experts, k, n)) / np.sqrt(k),
                          jnp.float32) for _ in range(2))
    te = jnp.asarray([0, 3, 3, 1, 2], jnp.int32)
    active = jnp.asarray([4], jnp.int32)
    got = moe.grouped_matmul(x, (w0, w1), te, active, tm=tm, gate_act="relu")
    want = moe.grouped_matmul(x, (w0, w1), te, active, tm=tm,
                              gate_act="relu", force_reference=True)
    xt = x.reshape(tiles, tm, k)
    by_hand = jax.nn.relu(
        jnp.einsum("tmk,tkn->tmn", xt, w0[te], precision=HI)) * jnp.einsum(
            "tmk,tkn->tmn", xt, w1[te], precision=HI)
    live = slice(0, 4 * tm)
    for other in (want, by_hand.reshape(tiles * tm, n)):
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(other)[live],
                                   rtol=1e-5, atol=1e-5)
    # ReLU's zeros are there (SiLU has none), and the default is SiLU.
    assert float(jnp.mean(got[live] == 0.0)) > 0.3
    silu = moe.grouped_matmul(x, (w0, w1), te, active, tm=tm)
    assert float(jnp.max(jnp.abs(silu[live] - got[live]))) > 0.1
    with pytest.raises(ValueError, match="gate_act"):
        moe.grouped_matmul(x, (w0, w1), te, active, tm=tm, gate_act="gelu")


def test_a_layout_made_ahead_is_the_layout_moe_ffn_makes():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    d, f, e = 32, 16, 8
    p = {"experts": {"w_gate": jax.random.normal(ks[0], (e, d, f)),
                     "w_up": jax.random.normal(ks[1], (e, d, f)),
                     "w_down": jax.random.normal(ks[2], (e, f, d))}}
    h = jax.random.normal(ks[3], (24, d))
    live = jnp.arange(24) < 20
    routing = moe.route_topk_softmax(
        h, jax.random.normal(ks[4], (d, e)), top_k=3)
    kw = dict(num_experts=e, with_shared=False, gate_act="relu", live=live)
    y, counts = moe.moe_ffn(h, p, routing, **kw)
    lay = moe.routed_layout(routing, num_experts=e, held=e, live=live)
    y2, counts2 = moe.moe_ffn(h, p, routing, lay=lay, **kw)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts2))
    assert int(counts.sum()) == 20 * 3


# -- (5) where in the decode program the routing is made -----------------------------

def _marks(cfg):
    """What the decode step's top-level equations hold, in program order:
    ``top_k``, ``sort`` and the names of the Mosaic calls, found through
    whatever jitted function or loop holds them."""
    from horovod_tpu.serving.decode import no_round
    slots, pps, ring = 3, 16, 3
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    step = swa_moe.build_decode_step(cfg, None, slots=slots, page_size=PAGE,
                                     pages_per_slot=pps, dtype=f32)
    full, window = (cfg.attn_kinds.count(k) for k in ("full", "window"))
    pool = S((full, slots * pps + 1, PAGE, cfg.kv_width), f32)
    wpool = S((window, slots * ring + 1, PAGE, cfg.kv_width), f32)
    jaxpr = step._fn.trace(
        swa_moe.param_shapes(cfg), pool, pool, S((slots,), i32),
        S((slots,), i32), S((slots, pps), i32), S((slots,), jnp.bool_),
        S((slots, ring), i32), wpool, wpool,
        S((len(cfg.moe_layers), cfg.num_experts), i32),
        S(no_round(slots, 1).shape, i32)).jaxpr

    def held(eqn):
        name = eqn.primitive.name
        if name == "pallas_call":
            yield eqn.params["name"]
            return
        if name in ("top_k", "sort"):
            yield name
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                for e in getattr(inner, "eqns", ()):
                    yield from held(e)

    return [mark for eqn in jaxpr.eqns for mark in held(eqn)]


def test_an_early_router_s_routing_and_layout_precede_the_attention_call(
        monkeypatch):
    """Program order: with ``route_from="layer_input"`` a layer's router,
    its top-k and the layout's sort stand AHEAD of its page walk, and only
    the grouped matmuls follow it; with ``"ffn_input"`` (K-EXAONE's) they
    follow the walk."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    gmm = ["hvd_moe_gmm", "hvd_moe_gmm"]
    layer = {True: lambda walk: ["top_k", "sort", walk] + gmm,
             False: lambda walk: [walk, "top_k", "sort"] + gmm}
    for over, early in ((dict(), True), (dict(route_from="ffn_input"),
                                         False)):
        marks = _marks(_tiny(**over)[0])
        assert marks == layer[early]("hvd_cca_decode") \
            + 3 * layer[early]("hvd_swa_decode"), marks
