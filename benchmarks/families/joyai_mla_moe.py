"""The ``joyai_mla_moe`` family: the DeepSeek-V3 block as JoyAI-LLM-Flash
publishes it -- latent attention (MLA) over a latent cache, a leading
dense SwiGLU layer, then 256 routed experts (top 8, sigmoid scores, a
selection bias, no groups) beside a shared expert, an untied head --
served by ``ServingEngine`` through ``horovod_tpu/serving/mla_moe.py``.

What the harness takes from here: how the engine is built from the
program's own entry points, the byte counts of the latent cache and of
one expert, the names the programs carry in a device trace, and the plain
reference.  The reference (``ref_*``, ``Reference``) is straight
``jax.numpy`` in float32 at ``highest`` matmul precision over the
benchmark's own weights, upcast a layer (and, for the experts, a block of
experts) at a time: no kernels, no cache, no batching, nothing imported
from ``horovod_tpu``.  It computes the EXPANDED attention only (per-head
keys and values from the latent), so the program's absorbed decode is
checked against different arithmetic, and it applies every expert to
every row and weighs the result by the router's weight or 0, so the
program's sort-and-group is checked against no grouping at all.
Attention runs in blocks of query rows (32 heads x 8,704 x 8,704 float32
scores are 9.7 GB) and the experts in blocks of ``EXPERT_BLOCK`` (one
routed layer in float32 is 4.96 GB beside 11.1 GB of bfloat16 weights).
Top 8 of 256 is discontinuous, so ``served_gaps`` judges a served token
under every routing the reference's own scores allow within
``limits.routing_margin_min`` (``routings_within``, ``Reference.row_gaps``:
a served row's alternatives are followed layer after layer as rows that
stand in for it in the context), because within that margin a flipped
expert is rounding.
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``mla_moe_step``; the prefill programs (one a prompt
# length) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_mla_moe_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The two Mosaic calls of the decode program, as the ops line names them.
MLA_DECODE_KERNEL = r"^%hvd_mla_decode[.\d]* = "
MOE_GMM_KERNEL = r"^%hvd_moe_gmm[.\d]* = "

QUERY_BLOCK = 544     # query rows a block of the reference's attention
EXPERT_BLOCK = 8      # experts upcast and applied at a time
ROWS = 256            # rows a call, where a served row's routings are followed
CHOICES = 1024        # routings a call that share those rows' experts
NEAR_TIES_SHOWN = 6   # experts past the last chosen whose scores are read:
                      # fewer near ties than that a side are enumerated


def moe_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def program_config(config: dict):
    from horovod_tpu.serving.mla_moe import MlaMoeConfig
    return MlaMoeConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        ffn_hidden=config["intermediate_size"],
        moe_hidden=config["moe_intermediate_size"],
        num_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
        first_dense_layers=config["first_k_dense_replace"],
        routed_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"])


def latent_bytes_per_token(config: dict) -> int:
    """Bytes one token of context holds over every layer, in the cache's
    type (2 bytes): the normalised latent and the one rotated key."""
    return (config["num_hidden_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * 2)


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's three matrices (2 bytes a weight)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * 2


def weight_bytes(config: dict) -> int:
    d, h = config["hidden_size"], config["num_attention_heads"]
    qr, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    attn = (d * qr + qr + qr * h * (dn + dr) + d * (r + dr) + r
            + r * h * (dn + dv) + h * dv * d)
    f, e = config["moe_intermediate_size"], config["n_routed_experts"]
    routed = (attn + 2 * d + d * e + e + 3 * d * f * e
              + 3 * d * f * config["n_shared_experts"])
    dense = attn + 2 * d + 3 * d * config["intermediate_size"]
    k = config["first_k_dense_replace"]
    return 2 * (k * dense + moe_layers(config) * routed
                + 2 * config["vocab_size"] * d + d)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(1,))
def _rescale(leaf, factor: float):
    return (leaf.astype(jnp.float32) * factor).astype(leaf.dtype)


def fan_in_experts(params):
    """``lib/weights.py`` draws a kernel at ``1 / sqrt(shape[0])``, which
    for a stacked ``[experts, fan_in, out]`` leaf is the number of
    experts: bring those leaves to ``1 / sqrt(fan_in)`` like every other
    kernel (in place: each leaf is donated)."""
    for name, blk in params["params"].items():
        if not (name.startswith("layer_") and "moe" in blk):
            continue
        ex = blk["moe"]["experts"]
        for key, leaf in ex.items():
            ex[key] = _rescale(leaf, math.sqrt(leaf.shape[0]
                                               / leaf.shape[1]))
    return params


def even_routing(params):
    """The selection bias at zero.  The published bias is what balancing
    left behind: it evens the experts' load out.  Seeded router weights
    load the experts evenly as they are, and a seeded bias would skew
    them (at ``weights.py``'s 1/sqrt(256) a round touched 63% of the
    experts, not the 87% of even routing), so the cell runs without one;
    the program still adds it, and the CPU tests choose by it."""
    for name, blk in params["params"].items():
        if name.startswith("layer_") and "moe" in blk:
            bias = blk["moe"]["router"]["e_score_correction_bias"]
            blk["moe"]["router"]["e_score_correction_bias"] = \
                jnp.zeros_like(bias)
    return params


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's new module first: a program without it fails
        # here, before any weight is made.
        from horovod_tpu.serving import mla_moe

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving

        self.config, self.chips = config, chips
        cfg = program_config(config)
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = mla_moe.param_shapes(cfg, dtype)
        t0 = time.perf_counter()
        self.params = even_routing(fan_in_experts(
            weights.make_weights(seed, self.shapes, dtype)))
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
        cache = self.engine.cache
        return cache.live_pages == 0 and bool(cache.refcounts_balanced())

    def free_engine(self):
        """Drop the engine and its cache; the weights stay for the
        reference."""
        self.engine = None


# -- the plain reference ----------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(
            jnp.float32)


def _rope_interleaved(x, theta, pos=None):
    """``x``: ``[t, ..., d]``, row ``i`` at position ``pos[i]`` (``i``
    where None), its last dim (even, odd) pairs: de-interleave, then
    rotate half against half."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = jnp.arange(t) if pos is None else pos
    ang = pos.astype(jnp.float32).reshape(
        (t,) + (1,) * (x.ndim - 1)) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mm(quant):
    q = QUANT[quant]
    return q, lambda a, b: jnp.matmul(q(a), q(b.astype(jnp.float32)),
                                      precision=HI)


def _queries_keys_values(x, blk, pos, *, dims, quant=None):
    """Per head, what attention takes from the rows ``x`` at positions
    ``pos``: ``(q_nope, q_pe, k_nope, k_pe, v)``, the keys and values
    expanded from the normalised latent."""
    heads, dn, dr, dv, r, theta, eps = dims
    _, mm = _mm(quant)
    t = x.shape[0]
    a = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"], eps)
    cq = _rms(mm(h, a["q_a"]["kernel"]), a["q_a_norm"]["scale"], eps)
    q = mm(cq, a["q_b"]["kernel"]).reshape(t, heads, dn + dr)
    kva = mm(h, a["kv_a"]["kernel"])
    c = _rms(kva[:, :r], a["kv_a_norm"]["scale"], eps)
    k_pe = _rope_interleaved(kva[:, r:], theta, pos)             # [t, dr]
    kv = mm(c, a["kv_b"]["kernel"]).reshape(t, heads, dn + dv)
    return (q[..., :dn], _rope_interleaved(q[..., dn:], theta, pos),
            kv[..., :dn], k_pe, kv[..., dn:])


def ref_attention(x, blk, *, dims, quant=None, query_block=QUERY_BLOCK):
    """``x + attention(norm(x))``, expanded: every head gets its own keys
    and values from the latent."""
    heads, dn, dr, dv = dims[:4]
    q_, mm = _mm(quant)
    t = x.shape[0]
    q_nope, q_pe, k_nope, k_pe, v = _queries_keys_values(
        x, blk, None, dims=dims, quant=quant)
    bq = math.gcd(t, query_block)
    cols = jnp.arange(t)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * bq, bq)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * bq, bq)
        s = (jnp.einsum("qhd,khd->hqk", q_(qn), q_(k_nope), precision=HI)
             + jnp.einsum("qhd,kd->hqk", q_(qp), q_(k_pe), precision=HI)
             ) / math.sqrt(dn + dr)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(v), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, heads * dv)
    return x + mm(o, blk["attn"]["wo"]["kernel"])


def ref_attention_of_rows(xv, pos, x, blk, *, dims):
    """The same attention for rows that stand in for rows of a context:
    row ``i`` of ``xv`` takes position ``pos[i]`` of the context whose
    rows are ``x``, attends to the context's rows BEFORE that position
    and to itself."""
    heads, dn, dr, dv = dims[:4]
    _, mm = _mm(None)
    _, _, k_nope, k_pe, v = _queries_keys_values(x, blk, None, dims=dims)
    qn, qp, kn_own, kp_own, v_own = _queries_keys_values(
        xv, blk, pos, dims=dims)
    scale = 1.0 / math.sqrt(dn + dr)
    s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HI)
         + jnp.einsum("qhd,kd->hqk", qp, k_pe, precision=HI)) * scale
    s = jnp.where(jnp.arange(x.shape[0])[None, :] < pos[:, None], s,
                  -jnp.inf)
    own = (jnp.einsum("qhd,qhd->hq", qn, kn_own, precision=HI)
           + jnp.einsum("qhd,qd->hq", qp, kp_own, precision=HI)) * scale
    top = jnp.maximum(jnp.max(s, axis=-1), own)
    e, e_own = jnp.exp(s - top[..., None]), jnp.exp(own - top)
    o = (jnp.einsum("hqk,khd->qhd", e, v, precision=HI)
         + e_own.T[..., None] * v_own) / (jnp.sum(e, axis=-1)
                                          + e_own).T[..., None]
    return xv + mm(o.reshape(xv.shape[0], heads * dv),
                   blk["attn"]["wo"]["kernel"])


def _ref_swiglu(h, node, mm):
    return mm(jax.nn.silu(mm(h, node["w_gate"]["kernel"]))
              * mm(h, node["w_up"]["kernel"]), node["w_down"]["kernel"])


def ref_scores(h, router, quant=None):
    """The router's scores ``[rows, experts]`` in float32 (sigmoid)."""
    return jax.nn.sigmoid(_mm(quant)[1](h, router["kernel"]))


def _weights_of(s, idx, scale):
    """Dense ``[rows, experts]`` weights of the choices ``idx``: the
    chosen scores renormalised and scaled, 0 elsewhere."""
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    g = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    rows = jnp.arange(s.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(g)


def ref_router(h, router, *, top_k, scale, quant=None):
    """The dense ``[rows, experts]`` weights: the router's weight where a
    row chose the expert, 0 elsewhere.  The ``top_k`` largest of ``score
    + bias`` are chosen; the bias chooses and never weighs."""
    s = ref_scores(h, router, quant)
    _, idx = jax.lax.top_k(
        s + router["e_score_correction_bias"].astype(jnp.float32), top_k)
    return _weights_of(s, idx, scale)


def _expert_block(h, ex, i, eb, q_):
    """The experts ``i * eb .. (i + 1) * eb - 1`` applied to every row of
    ``h``: ``[eb, rows, d]``."""
    def up(name):
        return jax.lax.dynamic_slice_in_dim(
            ex[name], i * eb, eb).astype(jnp.float32)

    gate = jnp.einsum("td,edf->etf", q_(h), q_(up("w_gate")), precision=HI)
    lift = jnp.einsum("td,edf->etf", q_(h), q_(up("w_up")), precision=HI)
    return jnp.einsum("etf,efd->etd", q_(jax.nn.silu(gate) * lift),
                      q_(up("w_down")), precision=HI)


def ref_moe(h, moe, *, top_k, scale, quant=None, expert_block=EXPERT_BLOCK,
            first=0, held=None, with_shared=True):
    """Every held expert applied to every row and weighed by the router's
    weight or 0, a block of experts at a time, and the shared expert."""
    q_, mm = _mm(quant)
    g = ref_router(h, moe["router"], top_k=top_k, scale=scale, quant=quant)
    ex = moe["experts"]
    held = ex["w_gate"].shape[0] if held is None else held
    eb = math.gcd(held, expert_block)

    def block(y, i):
        w = jax.lax.dynamic_slice_in_dim(g, first + i * eb, eb, axis=1)
        return y + jnp.einsum("etd,te->td", _expert_block(h, ex, i, eb, q_),
                              w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h), jnp.arange(held // eb))
    if with_shared:
        y = y + _ref_swiglu(h, moe["shared"], mm)
    return y


def ref_moe_of_choices(x, h, s, parent, idx, moe, *, scale,
                       expert_block=EXPERT_BLOCK):
    """One routed layer's output for rows that share their inputs: row
    ``j`` of the result is ``x[parent[j]]`` plus the experts ``idx[j]`` of
    ``h[parent[j]]`` (weighed from the scores ``s[parent[j]]``) plus the
    shared expert.  Every expert is applied to every row of ``h`` once,
    whatever the number of rows that choose among them."""
    _, mm = _mm(None)
    g = _weights_of(s[parent], idx, scale)
    ex = moe["experts"]
    eb = math.gcd(ex["w_gate"].shape[0], expert_block)

    def block(y, i):
        out = _expert_block(h, ex, i, eb, QUANT[None])[:, parent]
        w = jax.lax.dynamic_slice_in_dim(g, i * eb, eb, axis=1)
        return y + jnp.einsum("ecd,ce->cd", out, w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros((parent.shape[0], h.shape[1]),
                                         jnp.float32),
                        jnp.arange(ex["w_gate"].shape[0] // eb))
    return (x + _ref_swiglu(h, moe["shared"], mm))[parent] + y


def _dims(config: dict):
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]))


def _ref_layer(x, blk, *, config_dims, top_k, scale, eps, quant):
    x = ref_attention(x, blk, dims=config_dims, quant=quant)
    h = _rms(x, blk["mlp_norm"]["scale"], eps)
    if "moe" in blk:
        return x + ref_moe(h, blk["moe"], top_k=top_k, scale=scale,
                           quant=quant)
    return x + _ref_swiglu(h, blk["mlp"], _mm(quant)[1])


# -- routings within rounding ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _swaps(a: int, b: int):
    """Every way to trade ``m >= 1`` of the ``a`` last chosen experts for
    ``m`` of the ``b`` first left out.  Chosen are numbered 1.. from the
    LAST chosen upwards, left out 1.. from the FIRST left out downwards.
    A trade: ``(dropped, added, highest dropped, first kept, lowest
    added, first still out)``, the last four as such numbers."""
    out = []
    for m in range(1, min(a, b) + 1):
        for dropped in itertools.combinations(range(1, a + 1), m):
            kept = next(i for i in range(1, a + 2) if i not in dropped)
            for added in itertools.combinations(range(1, b + 1), m):
                still = next(i for i in range(1, b + 2) if i not in added)
                out.append((dropped, added, max(dropped), kept, max(added),
                            still))
    return out


def routings_within(vals, idx, top_k: int, tau: float, most: int):
    """The choices of ``top_k`` experts that a row's ``score + bias``
    allow once each may move by less than ``tau`` against another: every
    set whose lowest member stands less than ``tau`` BELOW the highest
    expert outside it (the reference's own set stands above; its cost is
    0).  ``vals``, ``idx``: each row's largest ``top_k + extra`` values of
    ``score + bias``, descending, and their experts.  Returns ``(row,
    experts, cost)`` of every such choice, the reference's own first
    among a row's, and ``over``: the rows that have more than ``most``
    (or more near ties than ``extra`` shows), of which nothing is
    returned."""
    vals, idx = np.asarray(vals, np.float64), np.asarray(idx)
    n, k = len(vals), top_k
    extra = vals.shape[1] - k
    a = np.sum(vals[:, :k] - vals[:, k:k + 1] < tau, axis=1)
    b = np.sum(vals[:, k - 1:k] - vals[:, k:] < tau, axis=1)
    over = (a >= extra) | (b >= extra)
    rows, experts, cost = [np.flatnonzero(~over)], [idx[~over, :k]], \
        [np.zeros(int(np.sum(~over)))]
    # u[:, j]: the j-th chosen from the last upwards (infinite past the
    # first); w[:, j]: the j-th left out (1-based, column 0 unused).
    u = np.concatenate([np.full((n, 1), np.nan), vals[:, k - 1::-1],
                        np.full((n, 1), np.inf)], axis=1)
    w = np.concatenate([np.full((n, 1), np.nan), vals[:, k:]], axis=1)
    count = np.ones(n, np.int64)
    for ai, bi in {(int(x), int(y)) for x, y in zip(a[~over], b[~over])}:
        if not ai:
            continue
        at = np.flatnonzero(~over & (a == ai) & (b == bi))
        for dropped, added, hi_d, kept, lo_a, still in _swaps(ai, bi):
            c = (np.maximum(u[at, hi_d], w[at, still])
                 - np.minimum(w[at, lo_a], u[at, kept]))
            ok = at[c < tau]
            if not len(ok):
                continue
            chosen = idx[ok, :k].copy()
            for d, e in zip(dropped, added):
                chosen[:, k - d] = idx[ok, k + e - 1]
            rows.append(ok)
            experts.append(chosen)
            cost.append(np.maximum(c[c < tau], 0.0))
            count[ok] += 1
    over = over | (count > most)
    rows, experts, cost = (np.concatenate(rows), np.concatenate(experts),
                           np.concatenate(cost))
    keep = ~over[rows]
    order = np.argsort(rows[keep], kind="stable")
    return (rows[keep][order], experts[keep][order], cost[keep][order],
            over)


def _chunks(n: int, size: int):
    return [(i, min(i + size, n)) for i in range(0, n, size)]


def _rows(values, size: int):
    """``values`` (a host array) padded with zero rows to ``size`` rows,
    on the device."""
    values = np.asarray(values)
    if values.dtype == np.int64:
        values = values.astype(np.int32)
    out = np.zeros((size,) + values.shape[1:], values.dtype)
    out[:len(values)] = values
    return jnp.asarray(out)


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length so that one compiled layer of each kind
    serves every sample (causal attention: the padding changes no earlier
    row; a row's routing is its own)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        self.top_k = config["num_experts_per_tok"]
        eps = float(config["rms_norm_eps"])
        scale = float(config["routed_scaling_factor"])
        dims = _dims(config)
        q, mm = _mm(quant)
        self._layer = jax.jit(functools.partial(
            _ref_layer, config_dims=dims, top_k=self.top_k, scale=scale,
            eps=eps, quant=quant))
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._readout = jax.jit(lambda x, scale, head: mm(
            _rms(x, scale, eps), head))

        top_k = self.top_k

        def route(xv, pos, x, blk):
            x1 = ref_attention_of_rows(xv, pos, x, blk, dims=dims)
            h = _rms(x1, blk["mlp_norm"]["scale"], eps)
            s = ref_scores(h, blk["moe"]["router"])
            vals, idx = jax.lax.top_k(
                s + blk["moe"]["router"]["e_score_correction_bias"].astype(
                    jnp.float32), min(top_k + NEAR_TIES_SHOWN, s.shape[1]))
            return x1, h, s, vals, idx

        self._route = jax.jit(route)
        self._choices = jax.jit(functools.partial(
            ref_moe_of_choices, scale=scale))

        def gaps(x, scale_, head, picks):
            logits = mm(_rms(x, scale_, eps), head)
            best = jnp.max(logits, axis=-1)
            return best[None] - jnp.take_along_axis(
                logits, picks.T, axis=-1).T

        self._gaps = jax.jit(gaps)

    def _forward(self, context: np.ndarray, keep: bool = False):
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(context)] = context
        x = self._embed(self.p["tok_embed"], jnp.asarray(toks))
        inputs = []
        for li in range(self.layers):
            if keep:
                inputs.append(x)
            x = self._layer(x, self.p[f"layer_{li}"])
        return x, inputs

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits [count, vocab] of the rows ``first .. first+count-1`` of
        ``context`` (row i predicts token i + 1)."""
        x, _ = self._forward(context)
        return self._readout(x[first:first + count],
                             self.p["final_norm"]["scale"],
                             self.p["lm_head"]["kernel"])

    def row_gaps(self, context: np.ndarray, first: int, count: int, picks,
                 tau: float, most: int):
        """For the rows ``first .. first+count-1`` of ``context``: by how
        much each token of ``picks`` (``[sets, count]``) lies below the
        row's best logit, under the routing that suits it best among
        those the reference's scores allow within ``tau``
        (:func:`routings_within`, layer after layer: a row that chooses
        other experts in one routed layer is routed anew in the next,
        over the context's rows as the reference has them).  Returns
        ``(gaps [sets, count], leaves [count], detail)``: ``leaves`` is
        the number of routings a row was followed through, 0 where they
        were more than ``most`` and the row is not compared; ``detail``
        holds ``(row, cost, gaps)`` of every routing, ``cost`` the
        furthest it departs from the reference's own."""
        _, inputs = self._forward(context, keep=True)
        routed = [li for li in range(self.layers)
                  if "moe" in self.p[f"layer_{li}"]]
        # One row a node, on the host: every call to the device below has
        # one shape, whatever the number of nodes.
        x = np.asarray(inputs[routed[0]])[first:first + count]
        owner = np.arange(count)
        cost = np.zeros(count)
        alive = np.ones(count, bool)
        for li in routed:
            if not len(owner):
                break
            blk = self.p[f"layer_{li}"]
            pos = first + owner
            parts = []
            for lo, hi in _chunks(len(owner), ROWS):
                x1, h, s, vals, idx = self._route(
                    _rows(x[lo:hi], ROWS), _rows(pos[lo:hi], ROWS),
                    inputs[li], blk)
                parts.append((x1, h, s, np.asarray(vals)[:hi - lo],
                              np.asarray(idx)[:hi - lo]))
            node, experts, c, over = routings_within(
                np.concatenate([p[3] for p in parts]),
                np.concatenate([p[4] for p in parts]), self.top_k, tau,
                most)
            # A row goes on while every one of its nodes does, and while
            # it has no more than ``most`` of them.
            alive[np.unique(owner[over])] = False
            per_row = np.bincount(owner[node], minlength=count)
            alive &= per_row <= most
            keep = alive[owner[node]]
            node, experts, c = node[keep], experts[keep], c[keep]
            out = [np.zeros((0, x.shape[1]), np.float32)]
            for (lo, hi), (x1, h, s, _, _) in zip(
                    _chunks(len(owner), ROWS), parts):
                mine = np.flatnonzero((node >= lo) & (node < hi))
                for a, b in _chunks(len(mine), CHOICES):
                    sel = mine[a:b]
                    out.append(np.asarray(self._choices(
                        x1, h, s, _rows(node[sel] - lo, CHOICES),
                        _rows(experts[sel], CHOICES), blk["moe"])
                    )[:len(sel)])
            x = np.concatenate(out)
            cost = np.maximum(cost[node], c)
            owner = owner[node]
        picks = np.asarray(picks, np.int32)
        gaps = [np.zeros((len(picks), 0))]
        for lo, hi in _chunks(len(owner), ROWS):
            gaps.append(np.asarray(self._gaps(
                _rows(x[lo:hi], ROWS), self.p["final_norm"]["scale"],
                self.p["lm_head"]["kernel"],
                _rows(picks[:, owner[lo:hi]].T, ROWS).T))[:, :hi - lo])
        gaps = np.concatenate(gaps, axis=1)
        best = np.full((len(picks), count), np.inf)
        np.minimum.at(best, (slice(None), owner), gaps)
        return (best, np.bincount(owner, minlength=count),
                (owner, cost, gaps))


def served_gaps(config: dict, params, sample, pad_to: int,
                with_control: bool = False) -> dict:
    """For each sampled finished request, run the reference once over its
    prompt with its served tokens; the widest gap by which a served
    token's logit lies below the reference's best -- over the rows and
    under the routings that follow.

    Top 8 of 256 is discontinuous: where a row's last chosen expert leads
    the first one left out by less than bfloat16 keeps of the activations,
    program and reference choose differently, the logits move by a third
    of their deviation, and neither routing is the wrong one.  So a row's
    gap is taken under the routing that suits the served token best AMONG
    THOSE THE REFERENCE'S OWN SCORES ALLOW within
    ``limits.routing_margin_min`` of ``score + bias``
    (:func:`routings_within`, :meth:`Reference.row_gaps`): a choice
    further than that from the reference's is judged as an error.  A row
    with more than ``limits.routing_branches_max`` such routings over its
    layers is not compared (``tokens_compared`` counts the rest: some
    three rows in ten; PERF.md section 2 has the readings).
    ``with_control`` also reads, at the same rows and under the same
    rule, the gap of the token the fp8 reference puts first.  ``sample``:
    ``[(prompt, served_tokens), ...]``."""
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
