"""The ``smallthinker_swa_moe`` family: the block as
SmallThinker-21BA3B-Instruct publishes its sizes -- grouped-query
attention (28 query heads over 4 key/value heads of 128, seven a group)
whose layers are of two kinds, one that sees everything and rotates
nothing for every three that see a WINDOW of 4,096 tokens and rotate
(``G L L L``); no per-head norm; in EVERY layer 64 routed experts of 768,
gated by ReLU, of which a token takes the 6 whose router LOGITS are
largest, weighed by a softmax over those six; the router reads the
layer's INPUT, ahead of the first norm and of attention; no shared
expert, no dense layer; an untied head -- served WHOLE-LAYERED on one
chip by ``ServingEngine`` through ``horovod_tpu/serving/swa_moe.py`` (the
block's second instance there: every expert and the whole vocabulary live
here).

What the harness takes from here: how the engine is built from the
program's own entry points, the byte and operation counts of the two
kinds of cached layer, of the banded prefill and of one expert, the
names the programs and kernels carry in a device trace, and the plain
reference.  The reference (``ref_*``, ``Reference``) is straight
``jax.numpy`` in float32 at ``highest`` matmul precision over the
benchmark's own weights, upcast a layer (and, for the experts, a block of
experts) at a time: no kernels, no cache (a window is a mask over the
whole context's scores), no batching, nothing imported from
``horovod_tpu``.  It applies every expert to every row and weighs the
result by the router's weight or 0, so the program's sort-and-group, and
its routing made ahead of attention, are checked against no grouping and
no reordering at all.  Top 6 of 64 is discontinuous, so ``served_gaps``
judges a served token under every routing the reference's own LOGITS
allow within ``limits.routing_margin_min`` -- of the logits the row has AT
UNIT RMS, ``r / rms(x)``: top k does not change under a row's own scale,
and a seeded residual is 0.02 a value as it enters layer 0 and one
hundred times that a layer later, so no margin on ``r`` itself fits both
(``joyai_mla_moe.routings_within`` and ``Reference.row_gaps``, which this
family's ``Reference`` inherits: only what a layer computes is its own).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT
from . import joyai_mla_moe as _joyai
from .joyai_mla_moe import _mm, _rms, fan_in_experts
from .zaya_cca_moe import _rope_half

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``swa_moe_step`` (K-EXAONE's program name: one module,
# two instances); the prefill programs (one a prompt length) are the
# engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_swa_moe_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The Mosaic calls, as the ops line names them: the page walk of a full
# layer and of a window layer (one kernel function, two names), the
# grouped matmul, and the banded prefill kernel.
CCA_DECODE_KERNEL = r"^%hvd_cca_decode[.\d]* = "
SWA_DECODE_KERNEL = r"^%hvd_swa_decode[.\d]* = "
SWA_PREFILL_KERNEL = r"^%hvd_flash_swa_fwd[.\d]* = "
MOE_GMM_KERNEL = r"^%hvd_moe_gmm[.\d]* = "

QUERY_BLOCK = 256     # query rows a block of the reference's attention
EXPERT_BLOCK = 8      # experts upcast and applied at a time
NEAR_TIES_SHOWN = _joyai.NEAR_TIES_SHOWN


def _layouts(config: dict):
    """``(window, rotate)``: a layer's two flags, as the source's lists
    give them (``sliding_window_layout``, ``rope_layout``)."""
    window = tuple(bool(b) for b in config["sliding_window_layout"])
    rotate = tuple(bool(b) for b in config["rope_layout"])
    if not len(window) == len(rotate) == config["num_hidden_layers"]:
        raise ValueError(
            f"{config['num_hidden_layers']} layers, {len(window)} window "
            f"and {len(rotate)} rotation flags")
    return window, rotate


def moe_layers(config: dict) -> int:
    return config["num_hidden_layers"]


def window_layers(config: dict) -> int:
    return sum(_layouts(config)[0])


def full_layers(config: dict) -> int:
    return config["num_hidden_layers"] - window_layers(config)


def program_config(config: dict):
    from horovod_tpu.serving.swa_moe import SwaMoeConfig
    window, rotate = _layouts(config)
    if window != rotate:
        raise ValueError(
            "the program rotates its window layers and no other: "
            f"sliding_window_layout {window} and rope_layout {rotate} differ")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]) or config["tie_word_embeddings"]:
        raise ValueError("softmax weights over the chosen and an untied "
                         "head are what the program computes")
    return SwaMoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_hidden=0,
        moe_hidden=config["moe_ffn_hidden_size"],
        num_experts=config["moe_num_primary_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        attn_kinds=tuple("window" if w else "full" for w in window),
        ffn_kinds=("moe",) * len(window),
        window=config["sliding_window_size"], num_shared_experts=0,
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        qk_norm=False, router="topk_softmax", route_from="layer_input",
        gate_act="relu")


def kv_row_bytes(config: dict) -> int:
    """Bytes one token holds in ONE layer's planes, in the cache's type
    (2 bytes): its keys and its values, one row in each pool."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * 2


def kv_bytes_per_token(config: dict) -> int:
    """Bytes a LIVE token holds, whatever the context's length: its rows
    in the FULL layers' planes, what their walks (``hvd_cca_decode``) must
    read of it a round.  A window layer reads a slot's last rows only:
    ``swa_decode_roofline`` counts those."""
    return full_layers(config) * kv_row_bytes(config)


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's three matrices (2 bytes a weight)."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"] * 2


def weight_bytes(config: dict) -> int:
    """Bytes of the weights held (2 bytes a weight): every layer's
    attention, two norms, router and 64 experts; embedding, head and the
    final norm."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    e, f = config["moe_num_primary_experts"], config["moe_ffn_hidden_size"]
    layer = (2 * d * heads * dh + 2 * d * kv * dh + 2 * d + d * e
             + 3 * d * f * e)
    return 2 * (config["num_hidden_layers"] * layer
                + 2 * config["vocab_size"] * d + d)


def window_pages(config: dict) -> int:
    """Pages of the cache's window group (a plane): every slot's ring at
    its fullest, ``ceil(window / page) + 1`` pages."""
    s = config["serving"]
    return s["slots"] * (-(-config["sliding_window_size"] // s["page_size"])
                         + 1)


def cache_bytes(config: dict) -> int:
    """Bytes of the four pools (2 bytes a value): the full layers' planes
    over ``slots * max_len / page + 1`` pages, the window layers' over
    :func:`window_pages` + 1."""
    s = config["serving"]
    page_bytes = s["page_size"] * kv_row_bytes(config)
    full = s["slots"] * s["max_len"] // s["page_size"] + 1
    return page_bytes * (full_layers(config) * full
                         + window_layers(config) * (window_pages(config) + 1))


def swa_prefill_cost(config: dict, tokens: int) -> dict:
    """What ONE window layer's attention over a prompt of ``tokens`` must
    do at the least: the operations of its band (query ``i`` against
    ``min(i + 1, window)`` keys: a product for the score and one for the
    value, every head) and the bytes of the rows it reads and writes once
    (queries in, results out, keys and values in; 2 bytes a value)."""
    w = config["sliding_window_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    short = min(tokens, w)
    pairs = short * (short + 1) // 2 + (tokens - short) * w
    return {"flops": 4 * heads * dh * pairs,
            "bytes": 2 * tokens * dh * 2 * (heads + kv)}


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's variant first: a program without it fails here,
        # before any weight is made.
        cfg = program_config(config)

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving
        from horovod_tpu.serving import swa_moe

        self.config, self.chips = config, chips
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = swa_moe.param_shapes(cfg, dtype)
        t0 = time.perf_counter()
        self.params = fan_in_experts(
            weights.make_weights(seed, self.shapes, dtype))
        jax.block_until_ready(self.params)
        log(f"weights made in {time.perf_counter() - t0:.2f} s")
        s = config["serving"]
        mesh = Mesh(np.asarray(jax.devices()[:chips]), ("tp",))
        self.engine = serving.ServingEngine(
            cfg, self.params, mesh=mesh, slots=s["slots"],
            page_size=s["page_size"], max_len=s["max_len"], dtype=dtype)
        self.Request = serving.Request

    def requests(self, gen):
        return [self.Request(rid=g.rid, prompt=g.prompt,
                             max_new_tokens=g.max_new_tokens,
                             arrival_s=g.arrival_s,
                             session_id=g.session_id) for g in gen]

    def pool_drained(self) -> bool:
        """No page left live in either group of planes."""
        cache = self.engine.cache
        return cache.live_pages == 0 and bool(cache.refcounts_balanced())

    def free_engine(self):
        """Drop the engine and its cache; the weights stay for the
        reference."""
        self.engine = None


# -- the plain reference ----------------------------------------------------------

def _dims(config: dict):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["sliding_window_size"],
            float(config["rope_theta"]), float(config["rms_norm_eps"]))


def _queries_keys_values(x, blk, pos, *, dims, rotate, quant=None):
    """Per head, what attention takes from the rows ``x`` at positions
    ``pos``: queries ``[t, heads, d]``, keys and values ``[t, kv, d]``;
    queries and keys rotated where the layer rotates, and no norm a
    head."""
    heads, kv, dh, _, theta, eps = dims
    _, mm = _mm(quant)
    t = x.shape[0]
    a = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"], eps)
    q = mm(h, a["wq"]["kernel"]).reshape(t, heads, dh)
    k = mm(h, a["wk"]["kernel"]).reshape(t, kv, dh)
    if rotate:
        q, k = (_rope_half(z, theta, dh, pos) for z in (q, k))
    return q, k, mm(h, a["wv"]["kernel"]).reshape(t, kv, dh)


def ref_attention(x, blk, *, dims, banded, rotate, quant=None,
                  query_block=QUERY_BLOCK):
    """``x + attention(norm_1(x))`` over the whole context: every query
    against every key, the causal mask and, on a window layer, the
    window's over the scores; query head ``n`` reads key/value head ``n
    // 7``."""
    heads, kv, dh, window = dims[:4]
    q_, mm = _mm(quant)
    t = x.shape[0]
    q, k, v = _queries_keys_values(x, blk, jnp.arange(t), dims=dims,
                                   rotate=rotate, quant=quant)
    k, v = (jnp.repeat(z, heads // kv, axis=1) for z in (k, v))
    bq = math.gcd(t, query_block)
    cols = jnp.arange(t)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(k),
                       precision=HI) / math.sqrt(dh)
        seen = rows[:, None] >= cols[None, :]
        if banded:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(v), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, heads * dh)
    return x + mm(o, blk["attn"]["wo"]["kernel"])


def ref_attention_of_rows(xv, pos, x, blk, *, dims, banded, rotate):
    """The same attention for rows that stand in for rows of a context:
    row ``i`` of ``xv`` takes position ``pos[i]`` of the context whose
    rows are ``x``, attends to the context's rows BEFORE that position
    (on a window layer: the ``window - 1`` before it) and to itself."""
    heads, kv, dh, window = dims[:4]
    _, mm = _mm(None)
    _, k, v = _queries_keys_values(x, blk, jnp.arange(x.shape[0]),
                                   dims=dims, rotate=rotate)
    q_own, k_own, v_own = _queries_keys_values(xv, blk, pos, dims=dims,
                                               rotate=rotate)
    rep = heads // kv
    k, v, k_own, v_own = (jnp.repeat(z, rep, axis=1)
                          for z in (k, v, k_own, v_own))
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("qhd,khd->hqk", q_own, k, precision=HI) * scale
    cols = jnp.arange(x.shape[0])[None, :]
    seen = cols < pos[:, None]
    if banded:
        seen &= pos[:, None] - cols < window
    s = jnp.where(seen, s, -jnp.inf)
    own = jnp.einsum("qhd,qhd->hq", q_own, k_own, precision=HI) * scale
    top = jnp.maximum(jnp.max(s, axis=-1), own)
    e, e_own = jnp.exp(s - top[..., None]), jnp.exp(own - top)
    o = (jnp.einsum("hqk,khd->qhd", e, v, precision=HI)
         + e_own.T[..., None] * v_own) / (jnp.sum(e, axis=-1)
                                          + e_own).T[..., None]
    return xv + mm(o.reshape(xv.shape[0], heads * dh),
                   blk["attn"]["wo"]["kernel"])


def ref_logits(x, router, quant=None):
    """The router's logits ``[rows, experts]`` in float32, from the rows
    as they ENTER the layer."""
    return _mm(quant)[1](x, router["kernel"])


def _weights_of(r, idx):
    """Dense ``[rows, experts]`` weights of the choices ``idx``: a softmax
    over the chosen logits (the softmax over all 64, renormalised over the
    chosen, is the same numbers), 0 elsewhere."""
    g = jax.nn.softmax(jnp.take_along_axis(r, idx, axis=-1), axis=-1)
    return jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], idx].set(g)


def _expert_block(h, ex, i, eb, q_):
    """The experts ``i * eb .. (i + 1) * eb - 1`` applied to every row of
    ``h``: ``W_down(relu(h W_gate) * (h W_up))``, ``[eb, rows, d]``."""
    def up(name):
        return jax.lax.dynamic_slice_in_dim(
            ex[name], i * eb, eb).astype(jnp.float32)

    gate = jnp.einsum("td,edf->etf", q_(h), q_(up("w_gate")), precision=HI)
    lift = jnp.einsum("td,edf->etf", q_(h), q_(up("w_up")), precision=HI)
    return jnp.einsum("etf,efd->etd", q_(jax.nn.relu(gate) * lift),
                      q_(up("w_down")), precision=HI)


def ref_moe(h, r, moe, *, top_k, quant=None, expert_block=EXPERT_BLOCK):
    """Every expert applied to every row of ``h`` and weighed by the
    router's weight or 0 (the ``top_k`` largest of the logits ``r``), a
    block of experts at a time.  No shared expert."""
    q_ = QUANT[quant]
    g = _weights_of(r, jax.lax.top_k(r, top_k)[1])
    ex = moe["experts"]
    eb = math.gcd(ex["w_gate"].shape[0], expert_block)

    def block(y, i):
        w = jax.lax.dynamic_slice_in_dim(g, i * eb, eb, axis=1)
        return y + jnp.einsum("etd,te->td", _expert_block(h, ex, i, eb, q_),
                              w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h),
                        jnp.arange(ex["w_gate"].shape[0] // eb))
    return y


def ref_moe_of_choices(x, h, r, parent, idx, moe, *,
                       expert_block=EXPERT_BLOCK):
    """One routed layer's output for rows that share their inputs: row
    ``j`` of the result is ``x[parent[j]]`` plus the experts ``idx[j]``
    applied to ``h[parent[j]]``, weighed from the logits ``r[parent[j]]``
    over ``idx[j]``."""
    g = _weights_of(r[parent], idx)
    ex = moe["experts"]
    eb = math.gcd(ex["w_gate"].shape[0], expert_block)

    def block(y, i):
        out = _expert_block(h, ex, i, eb, QUANT[None])[:, parent]
        w = jax.lax.dynamic_slice_in_dim(g, i * eb, eb, axis=1)
        return y + jnp.einsum("ecd,ce->cd", out, w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros((parent.shape[0], h.shape[1]),
                                         jnp.float32),
                        jnp.arange(ex["w_gate"].shape[0] // eb))
    return x[parent] + y


def ref_layer(x, blk, *, dims, banded, rotate, top_k, quant=None):
    """One block: ``r = x W_r`` from the layer's input; ``x +=
    attention(norm_1(x))``; ``x += experts(norm_2(x))`` under ``r``."""
    r = ref_logits(x, blk["moe"]["router"], quant)
    x = ref_attention(x, blk, dims=dims, banded=banded, rotate=rotate,
                      quant=quant)
    h = _rms(x, blk["mlp_norm"]["scale"], dims[-1])
    return x + ref_moe(h, r, blk["moe"], top_k=top_k, quant=quant)


class Reference(_joyai.Reference):
    """The plain forward over one context at a time (``_forward``,
    ``logits`` and ``row_gaps`` are the judge's: every layer here is
    routed).  A layer's compiled functions are its own two flags': the
    dictionaries below are asked by the layer's own parameter block."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        self.top_k = top_k = config["moe_num_active_primary_experts"]
        dims = _dims(config)
        eps = dims[-1]
        _, mm = _mm(quant)
        flags_of = {id(self.p[f"layer_{li}"]): flags
                    for li, flags in enumerate(zip(*_layouts(config)))}
        kinds = sorted(set(flags_of.values()))
        layer = {f: jax.jit(functools.partial(
            ref_layer, dims=dims, banded=f[0], rotate=f[1], top_k=top_k,
            quant=quant)) for f in kinds}
        self._layer = lambda x, blk: layer[flags_of[id(blk)]](x, blk)
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._readout = jax.jit(lambda x, scale_, head: mm(
            _rms(x, scale_, eps), head))

        def route(xv, pos, x, blk, *, banded, rotate):
            r = ref_logits(xv, blk["moe"]["router"])
            x1 = ref_attention_of_rows(xv, pos, x, blk, dims=dims,
                                       banded=banded, rotate=rotate)
            h = _rms(x1, blk["mlp_norm"]["scale"], eps)
            # The judge's margin is on the logits of the row AT UNIT RMS
            # (the same choice: top k does not change under a row's own
            # positive scale): a seeded residual is 0.02 a value as it
            # enters layer 0 and one hundred times that a layer later.
            unit = jax.lax.rsqrt(jnp.mean(xv * xv, axis=-1, keepdims=True)
                                 + eps)
            vals, idx = jax.lax.top_k(
                r * unit, min(top_k + NEAR_TIES_SHOWN, r.shape[1]))
            return x1, h, r, vals, idx

        routes = {f: jax.jit(functools.partial(
            route, banded=f[0], rotate=f[1])) for f in kinds}
        self._route = lambda xv, pos, x, blk: routes[flags_of[id(blk)]](
            xv, pos, x, blk)
        self._choices = jax.jit(ref_moe_of_choices)

        def gaps(x, scale_, head, picks):
            logits = mm(_rms(x, scale_, eps), head)
            best = jnp.max(logits, axis=-1)
            return best[None] - jnp.take_along_axis(
                logits, picks.T, axis=-1).T

        self._gaps = jax.jit(gaps)


def served_gaps(config: dict, params, sample, pad_to: int,
                with_control: bool = False) -> dict:
    """For each sampled finished request, run the reference once over its
    prompt with its served tokens; the widest gap by which a served
    token's logit lies below the reference's best -- over the rows and
    under the routings :meth:`Reference.row_gaps` follows
    (``limits.routing_margin_min`` of the router's logits at unit RMS,
    ``limits.routing_branches_max``; ``tokens_compared`` counts the rows
    compared).  ``with_control`` also reads, at the same rows and under
    the same rule, the gap of the token the fp8 reference puts first.
    ``sample``: ``[(prompt, served_tokens), ...]``."""
    ref = Reference(config, params, pad_to)
    ctl = Reference(config, params, pad_to, quant="fp8") \
        if with_control else None
    tau = float(config["limits"]["routing_margin_min"])
    most = int(config["limits"]["routing_branches_max"])
    widest, tokens, sampled = np.zeros(2), 0, 0
    for prompt, served in sample:
        served = np.asarray(served, np.int64)
        ctx = np.concatenate([np.asarray(prompt, np.int64), served])
        first, n = len(prompt) - 1, len(served)
        picks = [served]
        if ctl is not None:
            picks.append(np.asarray(ctl.logits(ctx, first, n)).argmax(
                axis=-1))
        gaps, leaves, _ = ref.row_gaps(ctx, first, n, np.stack(picks), tau,
                                       most)
        sampled += n
        tokens += int(np.sum(leaves > 0))
        widest = np.maximum(widest, np.max(
            gaps[:, leaves > 0], axis=1, initial=0.0))
    # No compared row in the whole sample compares nothing: not correct.
    out = {"served_logit_gap_max": float(widest[0]) if tokens
           else float("inf"),
           "tokens_compared": tokens, "tokens_sampled": sampled}
    if with_control:
        out["control_logit_gap_max"] = float(widest[1])
    return out
