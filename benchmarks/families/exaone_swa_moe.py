"""The ``exaone_swa_moe`` family: the ``exaone_moe`` block as
K-EXAONE-236B-A23B publishes its sizes -- grouped-query attention (64
query and 8 key/value heads of 128) whose layers are of two kinds, three
that see a WINDOW of 128 tokens for every one that sees everything (``L L
L G``), RMSNorm on each head's query and key, RoPE on the window layers
only; a leading dense SwiGLU layer, then 128 routed experts (top 8,
sigmoid scores, a selection bias, one group) beside a shared expert; an
untied head -- served by ``ServingEngine`` through
``horovod_tpu/serving/swa_moe.py`` as ONE CHIP'S SHARE of an eight-chip
expert-parallel deployment: ``num_experts`` of the router's 128 experts
and ``vocab_size`` of the 153,600 rows live here.

What the harness takes from here: how the engine is built from the
program's own entry points, the byte and operation counts of the two
kinds of cached layer, of the banded prefill and of one expert, the
names the programs and kernels carry in a device trace, and the plain
reference.  The reference (``ref_*``, ``Reference``) is straight
``jax.numpy`` in float32 at ``highest`` matmul precision over the
benchmark's own weights, upcast a layer (and, for the experts, a block of
experts) at a time: no kernels, no cache (a window is a mask over the
whole context's scores), no batching, nothing imported from
``horovod_tpu``.  It is given the SAME SHARE as the program: it scores
all 128 experts, applies the held ones to every row and weighs each by
the router's weight or 0, adds the shared expert, and leaves out what
the experts held elsewhere would have added, layer after layer.  Top 8 of
128 is discontinuous, so ``served_gaps`` judges a served token under
every routing the reference's own scores allow within
``limits.routing_margin_min`` (``joyai_mla_moe.routings_within`` and
``Reference.row_gaps``, which this family's ``Reference`` inherits).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT
from . import joyai_mla_moe as _joyai
from .joyai_mla_moe import (_expert_block, _mm, _ref_swiglu, _rms,
                            _weights_of, even_routing, fan_in_experts,
                            ref_moe, ref_scores)
from .zaya_cca_moe import _off_identity, _rope_half

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``swa_moe_step``; the prefill programs (one a prompt
# length) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_swa_moe_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The Mosaic calls, as the ops line names them: the page walk of a full
# layer (ZAYA's and Mistral's walk, under the name the accepted
# ``cca_decode_roofline``'s reader asks for) and of a window layer (one
# kernel function, two names), the grouped matmul, and the banded prefill
# kernel.
CCA_DECODE_KERNEL = r"^%hvd_cca_decode[.\d]* = "
SWA_DECODE_KERNEL = r"^%hvd_swa_decode[.\d]* = "
SWA_PREFILL_KERNEL = r"^%hvd_flash_swa_fwd[.\d]* = "
MOE_GMM_KERNEL = r"^%hvd_moe_gmm[.\d]* = "

QUERY_BLOCK = 256     # query rows a block of the reference's attention
ROW_BLOCK = 1024      # rows a block of the reference's dense feed-forward
EXPERT_BLOCK = 2      # experts upcast and applied at a time
NEAR_TIES_SHOWN = _joyai.NEAR_TIES_SHOWN


def _kinds(config: dict):
    attn = tuple("window" if t == "sliding_attention" else "full"
                 for t in config["layer_types"])
    ffn = tuple("moe" if t == "sparse" else "dense"
                for t in config["mlp_layer_types"])
    if len(attn) != config["num_hidden_layers"] or len(ffn) != len(attn):
        raise ValueError(
            f"{config['num_hidden_layers']} layers, {len(attn)} attention "
            f"and {len(ffn)} feed-forward kinds")
    return attn, ffn


def moe_layers(config: dict) -> int:
    return _kinds(config)[1].count("moe")


def window_layers(config: dict) -> int:
    return _kinds(config)[0].count("window")


def full_layers(config: dict) -> int:
    return _kinds(config)[0].count("full")


def program_config(config: dict):
    from horovod_tpu.serving.swa_moe import SwaMoeConfig
    attn, ffn = _kinds(config)
    pub = config["published"]
    return SwaMoeConfig(
        vocab_size=pub["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ffn_hidden=config["intermediate_size"],
        moe_hidden=config["moe_intermediate_size"],
        num_experts=pub["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        attn_kinds=attn, ffn_kinds=ffn, window=config["sliding_window"],
        num_shared_experts=config["num_shared_experts"],
        routed_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        experts_held=config["num_experts"],
        first_expert=config["share"]["first_expert"],
        vocab_held=config["vocab_size"])


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
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * 2


def weight_bytes(config: dict) -> int:
    """Bytes of THIS SHARE's weights (2 bytes a weight): attention, the
    router at its full width, norms and the shared expert whole; the held
    experts; the held slice of embedding and head."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = 2 * d * heads * dh + 2 * d * kv * dh + 2 * dh + 2 * d
    f = config["moe_intermediate_size"]
    wide = config["published"]["num_experts"]
    routed = (attn + d * wide + wide + 3 * d * f * config["num_experts"]
              + 3 * d * f * config["num_shared_experts"])
    dense = attn + 3 * d * config["intermediate_size"]
    n = moe_layers(config)
    return 2 * ((config["num_hidden_layers"] - n) * dense + n * routed
                + 2 * config["vocab_size"] * d + d)


def swa_prefill_cost(config: dict, tokens: int) -> dict:
    """What ONE window layer's attention over a prompt of ``tokens`` must
    do at the least: the operations of its band (query ``i`` against
    ``min(i + 1, window)`` keys: a product for the score and one for the
    value, every head) and the bytes of the rows it reads and writes once
    (queries in, results out, keys and values in; 2 bytes a value)."""
    w = config["sliding_window"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    short = min(tokens, w)
    pairs = short * (short + 1) // 2 + (tokens - short) * w
    return {"flops": 4 * heads * dh * pairs,
            "bytes": 2 * tokens * dh * 2 * (heads + kv)}


def seeded_head_norms(params, seed: int):
    """The per-head query and key norms' scales ``SPREAD`` off one, from
    the seed (in place).  ``lib/weights.py`` draws every ``scale`` at
    one, which would leave these out of the mathematics: a program that
    forgets them then passes the comparison."""
    flat = jax.tree_util.tree_flatten_with_path(params["params"])[0]
    index = {weights.path_name(path): i for i, (path, _) in enumerate(flat)}
    for name, blk in params["params"].items():
        if not name.startswith("layer_"):
            continue
        for key in ("q_norm", "k_norm"):
            leaf = blk["attn"][key]["scale"]
            blk["attn"][key]["scale"] = _off_identity(
                jnp.uint32(weights.leaf_salt(
                    seed + 1, index[f"{name}/attn/{key}/scale"])),
                tuple(leaf.shape), leaf.dtype, 1.0)
    return params


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's new module first: a program without it fails
        # here, before any weight is made.
        from horovod_tpu.serving import swa_moe

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving

        self.config, self.chips = config, chips
        cfg = program_config(config)
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = swa_moe.param_shapes(cfg, dtype)
        t0 = time.perf_counter()
        self.params = seeded_head_norms(even_routing(fan_in_experts(
            weights.make_weights(seed, self.shapes, dtype))), seed)
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

def _queries_keys_values(x, blk, pos, *, dims, banded, quant=None):
    """Per head, what attention takes from the rows ``x`` at positions
    ``pos``: queries ``[t, heads, d]``, keys and values ``[t, kv, d]``;
    queries and keys normalised a head and, on a window layer, rotated."""
    heads, kv, dh, _, theta, eps = dims
    _, mm = _mm(quant)
    t = x.shape[0]
    a = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"], eps)
    q = _rms(mm(h, a["wq"]["kernel"]).reshape(t, heads, dh),
             a["q_norm"]["scale"], eps)
    k = _rms(mm(h, a["wk"]["kernel"]).reshape(t, kv, dh),
             a["k_norm"]["scale"], eps)
    if banded:
        q, k = (_rope_half(z, theta, dh, pos) for z in (q, k))
    return q, k, mm(h, a["wv"]["kernel"]).reshape(t, kv, dh)


def ref_attention(x, blk, *, dims, banded, quant=None,
                  query_block=QUERY_BLOCK):
    """``x + attention(norm(x))`` over the whole context: every query
    against every key, the causal mask and, on a window layer, the
    window's over the scores."""
    heads, kv, dh, window = dims[:4]
    q_, mm = _mm(quant)
    t = x.shape[0]
    q, k, v = _queries_keys_values(x, blk, jnp.arange(t), dims=dims,
                                   banded=banded, quant=quant)
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


def ref_attention_of_rows(xv, pos, x, blk, *, dims, banded):
    """The same attention for rows that stand in for rows of a context:
    row ``i`` of ``xv`` takes position ``pos[i]`` of the context whose
    rows are ``x``, attends to the context's rows BEFORE that position
    (on a window layer: the ``window - 1`` before it) and to itself."""
    heads, kv, dh, window = dims[:4]
    _, mm = _mm(None)
    _, k, v = _queries_keys_values(x, blk, jnp.arange(x.shape[0]),
                                   dims=dims, banded=banded)
    q_own, k_own, v_own = _queries_keys_values(xv, blk, pos, dims=dims,
                                               banded=banded)
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


def _swiglu_in_blocks(h, node, mm, row_block=ROW_BLOCK):
    """A wide SwiGLU over the rows ``h``, ``row_block`` rows at a time
    (9,216 rows of 18,432 float32 columns are 680 MB a product)."""
    rb = math.gcd(h.shape[0], row_block)
    return jax.lax.map(lambda rows: _ref_swiglu(rows, node, mm),
                       h.reshape(-1, rb, h.shape[1])).reshape(h.shape)


def ref_moe_of_choices(x, h, s, parent, idx, moe, *, scale, first,
                       expert_block=EXPERT_BLOCK):
    """One routed layer's output for rows that share their inputs: row
    ``j`` of the result is ``x[parent[j]]`` plus the HELD experts among
    ``idx[j]`` applied to ``h[parent[j]]`` (weighed from the scores
    ``s[parent[j]]`` over all of ``idx[j]``) plus the shared expert."""
    _, mm = _mm(None)
    g = _weights_of(s[parent], idx, scale)
    ex = moe["experts"]
    held = ex["w_gate"].shape[0]
    eb = math.gcd(held, expert_block)

    def block(y, i):
        out = _expert_block(h, ex, i, eb, QUANT[None])[:, parent]
        w = jax.lax.dynamic_slice_in_dim(g, first + i * eb, eb, axis=1)
        return y + jnp.einsum("ecd,ce->cd", out, w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros((parent.shape[0], h.shape[1]),
                                         jnp.float32),
                        jnp.arange(held // eb))
    return (x + _ref_swiglu(h, moe["shared"], mm))[parent] + y


def _dims(config: dict):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["sliding_window"],
            float(config["rope_parameters"]["rope_theta"]),
            float(config["rms_norm_eps"]))


def ref_layer(x, blk, *, dims, banded, top_k, scale, first, quant=None):
    """One block: ``x += attention(norm_1(x))``, ``x += ffn(norm_2(x))``;
    a routed layer's feed-forward is the SHARE's (the held experts
    ``first ..`` and the shared expert)."""
    x = ref_attention(x, blk, dims=dims, banded=banded, quant=quant)
    h = _rms(x, blk["mlp_norm"]["scale"], dims[-1])
    if "moe" in blk:
        return x + ref_moe(h, blk["moe"], top_k=top_k, scale=scale,
                           quant=quant, expert_block=EXPERT_BLOCK,
                           first=first)
    return x + _swiglu_in_blocks(h, blk["mlp"], _mm(quant)[1])


class Reference(_joyai.Reference):
    """The plain forward over one context at a time (``_forward``,
    ``logits`` and ``row_gaps`` are the sigmoid-top-8 family's: a leading
    dense layer, then routed ones).  A layer's compiled functions are
    its attention kind's: the dictionaries below are asked by the layer's
    own parameter block."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        self.top_k = top_k = config["num_experts_per_tok"]
        scale = float(config["routed_scaling_factor"])
        first = int(config["share"]["first_expert"])
        dims = _dims(config)
        eps = dims[-1]
        _, mm = _mm(quant)
        kinds = _kinds(config)[0]
        banded_of = {id(self.p[f"layer_{li}"]): kind == "window"
                     for li, kind in enumerate(kinds)}
        layer = {b: jax.jit(functools.partial(
            ref_layer, dims=dims, banded=b, top_k=top_k, scale=scale,
            first=first, quant=quant)) for b in (False, True)}
        self._layer = lambda x, blk: layer[banded_of[id(blk)]](x, blk)
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._readout = jax.jit(lambda x, scale_, head: mm(
            _rms(x, scale_, eps), head))

        def route(xv, pos, x, blk, *, banded):
            x1 = ref_attention_of_rows(xv, pos, x, blk, dims=dims,
                                       banded=banded)
            h = _rms(x1, blk["mlp_norm"]["scale"], eps)
            s = ref_scores(h, blk["moe"]["router"])
            vals, idx = jax.lax.top_k(
                s + blk["moe"]["router"]["e_score_correction_bias"].astype(
                    jnp.float32), min(top_k + NEAR_TIES_SHOWN, s.shape[1]))
            return x1, h, s, vals, idx

        routes = {b: jax.jit(functools.partial(route, banded=b))
                  for b in (False, True)}
        self._route = lambda xv, pos, x, blk: routes[banded_of[id(blk)]](
            xv, pos, x, blk)
        self._choices = jax.jit(functools.partial(
            ref_moe_of_choices, scale=scale, first=first))

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
    token's logit lies below the reference's best -- over the held slice
    of the vocabulary, over the rows and under the routings
    :meth:`Reference.row_gaps` follows (``limits.routing_margin_min``,
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
