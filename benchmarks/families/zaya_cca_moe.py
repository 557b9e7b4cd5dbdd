"""The ``zaya_cca_moe`` family: the ZAYA1 block as ZAYA1-8B publishes its
sizes -- compressed convolutional attention (CCA, arXiv:2510.04476: 8
query and 2 key/value heads of 128 in a latent the layer projects DOWN
into, two causal two-tap convolutions, a q-k mean, per-head
normalisation with a key temperature, RoPE on half of each head, values
whose second half is the previous token's) and an expert block whose
router is an MLP over a 256-wide stream that is averaged over the depth
of the model (ZAYA1 technical report, arXiv:2511.17127: softmax, top 1
of 16 experts of 2,048, no shared expert), learned per-channel residual
scales, a tied head -- served by ``ServingEngine`` through
``horovod_tpu/serving/cca_moe.py``.

What the harness takes from here: how the engine is built from the
program's own entry points, the byte counts of the cache, of the slot
state and of one expert, the names the programs carry in a device trace,
and the plain reference.  The reference (``ref_*``, ``Reference``) is
straight ``jax.numpy`` in float32 at ``highest`` matmul precision over
the benchmark's own weights, upcast a layer (and, for the experts, a
block of experts) at a time: no kernels, no cache, no slot state (every
shift is a shift of the whole context), no batching, nothing imported
from ``horovod_tpu``.  It applies every expert to every row and weighs
the result by the router's weight or 0, so the program's sort-and-group
is checked against no grouping at all; attention runs in blocks of
query rows.  Top 1 of 16 is discontinuous, so ``served_gaps`` judges a
served token under every routing the reference's own scores allow
within ``limits.routing_margin_min`` (``joyai_mla_moe.routings_within``,
``Reference.row_gaps``), and only where the ``CONTEXT_ROWS`` tokens
before it -- whose rows its convolutions and its shifted value half
read directly, not through attention's average -- are routed with no
such near tie: there the program's context may differ from the
reference's by a whole expert.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT
from .joyai_mla_moe import _chunks, _rows, routings_within

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``cca_moe_step``; the prefill programs (one a prompt
# length) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_cca_moe_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The two Mosaic calls of the decode program, as the ops line names them
# (the grouped matmul keeps one name, whole blocks or column slices).
CCA_DECODE_KERNEL = r"^%hvd_cca_decode[.\d]* = "
MOE_GMM_KERNEL = r"^%hvd_moe_gmm[.\d]* = "

QUERY_BLOCK = 512     # query rows a block of the reference's attention
EXPERT_BLOCK = 4      # experts upcast and applied at a time
ROWS = 256            # rows a call, where a served row's routings are followed
CHOICES = 1024        # routings a call that share those rows' experts
NEAR_TIES_SHOWN = 6   # experts past the chosen one whose scores are read
CONTEXT_ROWS = 2      # tokens before a row that its slot state is made of

# The vectors ``config.json`` does not fix (``assumed``), the value that
# leaves each out of the mathematics, and how far the seeded draw puts
# them off it: a program that forgets one fails the comparison.
ASSUMED_VECTORS = {"tau": 1.0, "gamma": 1.0, "attn_alpha": 1.0,
                   "moe_alpha": 1.0, "b0": 0.0, "b1": 0.0}
SPREAD = 0.1


def rotary_dim(config: dict) -> int:
    return int(config["head_dim"] * config["partial_rotary_factor"])


def program_config(config: dict):
    from horovod_tpu.serving.cca_moe import CcaMoeConfig
    return CcaMoeConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_hidden=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        router_hidden=config["router_hidden_size"],
        experts_per_token=config["num_experts_per_tok"],
        conv_taps=(config["cca_time0"], config["cca_time1"]),
        rotary_dim=rotary_dim(config),
        rope_theta=float(config["rope_parameters"]["hybrid"]["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"])


def kv_bytes_per_token(config: dict) -> int:
    """Bytes one token of context holds over every layer, in the cache's
    type (2 bytes): its keys after RoPE and its values."""
    return (config["num_hidden_layers"] * 2
            * config["num_key_value_heads"] * config["head_dim"] * 2)


def slot_state_bytes(config: dict) -> int:
    """Bytes a slot keeps beside its pages over every layer (2 bytes a
    value): ``u``, ``a`` and the next token's shifted value half."""
    d = config["head_dim"]
    conv = (config["num_attention_heads"]
            + config["num_key_value_heads"]) * d
    return config["num_hidden_layers"] * 2 * (
        2 * conv + config["num_key_value_heads"] * d // 2)


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's three matrices (2 bytes a weight)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * 2


def weight_bytes(config: dict) -> int:
    e, d = config["hidden_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    conv = (hq + hkv) * d
    attn = (e * hq * d + e * hkv * d + 2 * e * (hkv * d // 2) + hq * d * e
            + 2 * conv + conv + 2 * (hq + hkv) * d * d + conv + hkv)
    r, n = config["router_hidden_size"], config["num_experts"]
    router = e * r + r + r + 2 * r * r + r * n + n
    experts = n * 3 * e * config["moe_intermediate_size"]
    layer = attn + router + experts + 4 * e
    return 2 * (config["num_hidden_layers"] * layer
                + config["vocab_size"] * e + e)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(1,))
def _rescale(leaf, factor: float):
    return (leaf.astype(jnp.float32) * factor).astype(leaf.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _off_identity(salt, shape, dtype, identity: float):
    return (identity + SPREAD * weights.hash_normal(salt, shape)).astype(
        dtype)


def seeded_assumptions(params, seed: int):
    """What ``lib/weights.py`` cannot know of this tree, leaf by leaf (in
    place).  A stacked ``[experts, fan_in, out]`` leaf and the
    convolution's ``[taps, heads, d, d]`` matrices are drawn there at
    ``1 / sqrt(shape[0])``: brought to ``1 / sqrt(fan_in)`` like every
    other kernel.  The vectors of ``ASSUMED_VECTORS`` are drawn there as
    kernels: put ``SPREAD`` off their identity values.  The router's
    balancing ``bias`` stays at the zeros it is drawn at (ISSUE 30; the
    published bias is what balancing left behind.  The seeded router is
    NOT even without one: the configuration's ``assumed.balancing_bias``
    has the measurement)."""
    flat = jax.tree_util.tree_flatten_with_path(params["params"])[0]
    index = {weights.path_name(path): i for i, (path, _) in enumerate(flat)}
    for name, blk in params["params"].items():
        if not name.startswith("layer_"):
            continue
        ex = blk["moe"]["experts"]
        for key, leaf in ex.items():
            ex[key] = _rescale(leaf, math.sqrt(leaf.shape[0]
                                               / leaf.shape[1]))
        w1 = blk["attn"]["conv1"]["w"]
        blk["attn"]["conv1"]["w"] = _rescale(
            w1, math.sqrt(1.0 / w1.shape[2]))     # fan-in: taps x d
        for owner, key in ((blk["attn"], "tau"), (blk, "attn_alpha"),
                           (blk, "moe_alpha"),
                           (blk["attn"]["conv0"], "b0"),
                           (blk["attn"]["conv1"], "b1"),
                           (blk["moe"]["router"], "gamma")):
            path = next(p for p in index if p.startswith(name + "/")
                        and p.endswith("/" + key))
            leaf = owner[key]
            owner[key] = _off_identity(
                jnp.uint32(weights.leaf_salt(seed + 1, index[path])),
                tuple(leaf.shape), leaf.dtype, ASSUMED_VECTORS[key])
    return params


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's new module first: a program without it fails
        # here, before any weight is made.
        from horovod_tpu.serving import cca_moe

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving

        self.config, self.chips = config, chips
        cfg = program_config(config)
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = cca_moe.param_shapes(cfg, dtype)
        t0 = time.perf_counter()
        self.params = seeded_assumptions(
            weights.make_weights(seed, self.shapes, dtype), seed)
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


def _mm(quant):
    q = QUANT[quant]
    return q, lambda a, b: jnp.matmul(q(a), q(b.astype(jnp.float32)),
                                      precision=HI)


def _f32(leaf):
    return leaf.astype(jnp.float32)


def _rope_half(x, theta, rotary, pos):
    """``x``: ``[t, heads, d]``, row ``i`` at position ``pos[i]``: the
    first ``rotary`` columns of each head rotated half against half."""
    freqs = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rotary:]], -1)


def _before(z):
    """Row ``t`` gets row ``t - 1``; row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]])


def _down(x, blk, *, dims, quant=None):
    """Step 1 and the value projections of the rows ``x``: ``(u, w_v1 h,
    w_v2 h)``."""
    eps = dims[-1]
    _, mm = _mm(quant)
    a = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"], eps)
    u = jnp.concatenate([mm(h, a["wq"]["kernel"]), mm(h, a["wk"]["kernel"])],
                        axis=-1)
    return u, mm(h, a["wv1"]["kernel"]), mm(h, a["wv2"]["kernel"])


def _conv0(u, u_before, attn):
    w0 = _f32(attn["conv0"]["w"])
    return w0[0] * u_before + w0[1] * u + _f32(attn["conv0"]["b0"])


def _queries_keys(u, a, a_before, attn, pos, *, dims, quant=None):
    """Steps 3-6: per head, the queries ``[t, hq, d]`` and keys ``[t,
    hkv, d]`` of rows whose ``u``, ``a`` and previous ``a`` are given."""
    hq, hkv, d, rotary, theta, _ = dims
    q_ = QUANT[quant]
    t = u.shape[0]
    w1 = _f32(attn["conv1"]["w"])

    def tap(rows, w):
        return jnp.einsum("tjd,jde->tje", q_(rows.reshape(t, hq + hkv, d)),
                          q_(w), precision=HI)

    c = (tap(a_before, w1[0]) + tap(a, w1[1])
         + _f32(attn["conv1"]["b1"]).reshape(hq + hkv, d))
    uh = u.reshape(t, hq + hkv, d)
    q_dn, k_dn = uh[:, :hq].reshape(t, hkv, hq // hkv, d), uh[:, hq:]
    q = c[:, :hq] + 0.5 * (q_dn + k_dn[:, :, None]).reshape(t, hq, d)
    k = c[:, hq:] + 0.5 * (jnp.mean(q_dn, axis=2) + k_dn)

    def unit(z):
        return z * math.sqrt(d) / jnp.sqrt(
            jnp.sum(z * z, axis=-1, keepdims=True) + 1e-12)

    return (_rope_half(unit(q), theta, rotary, pos),
            _rope_half(unit(k) * _f32(attn["tau"])[:, None], theta, rotary,
                       pos))


def _context(x, blk, *, dims, quant=None):
    """What attention takes from every row of the context ``x``: ``(q,
    k, v, u, a, w_v2 h)``."""
    hkv, d = dims[1], dims[2]
    t = x.shape[0]
    u, v1, v2 = _down(x, blk, dims=dims, quant=quant)
    a = _conv0(u, _before(u), blk["attn"])
    q, k = _queries_keys(u, a, _before(a), blk["attn"], jnp.arange(t),
                         dims=dims, quant=quant)
    v = jnp.concatenate([v1, _before(v2)], axis=-1).reshape(t, hkv, d)
    return q, k, v, u, a, v2


def ref_attention(x, blk, *, dims, quant=None, query_block=QUERY_BLOCK):
    """``alpha * x + W_o attention(...)`` over the whole context."""
    hq, hkv, d = dims[:3]
    q_, mm = _mm(quant)
    t = x.shape[0]
    q, k, v, _, _, _ = _context(x, blk, dims=dims, quant=quant)
    k, v = (jnp.repeat(z, hq // hkv, axis=1) for z in (k, v))
    bq = math.gcd(t, query_block)
    cols = jnp.arange(t)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(k),
                       precision=HI) / math.sqrt(d)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(v), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, hq * d)
    return (x * _f32(blk["attn_alpha"])
            + mm(o, blk["attn"]["wo"]["kernel"]))


def ref_attention_of_rows(xv, pos, x, blk, *, dims):
    """The same block for rows that stand in for rows of a context: row
    ``i`` of ``xv`` takes position ``pos[i]`` of the context whose rows
    are ``x``; its convolutions and its shifted value half read the
    context's row BEFORE that position, it attends to the context's rows
    before that position and to itself."""
    hq, hkv, d = dims[:3]
    _, mm = _mm(None)
    n = xv.shape[0]
    _, k, v, u, a, v2 = _context(x, blk, dims=dims)
    prev = jnp.maximum(pos - 1, 0)
    first = (pos == 0)[:, None]
    u_b, a_b, v2_b = (jnp.where(first, 0.0, z[prev]) for z in (u, a, v2))
    u_own, v1_own, _ = _down(xv, blk, dims=dims)
    a_own = _conv0(u_own, u_b, blk["attn"])
    q_own, k_own = _queries_keys(u_own, a_own, a_b, blk["attn"], pos,
                                 dims=dims)
    v_own = jnp.concatenate([v1_own, v2_b], axis=-1).reshape(n, hkv, d)
    rep = hq // hkv
    k, v, k_own, v_own = (jnp.repeat(z, rep, axis=1)
                          for z in (k, v, k_own, v_own))
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("qhd,khd->hqk", q_own, k, precision=HI) * scale
    s = jnp.where(jnp.arange(x.shape[0])[None, :] < pos[:, None], s,
                  -jnp.inf)
    own = jnp.einsum("qhd,qhd->hq", q_own, k_own, precision=HI) * scale
    top = jnp.maximum(jnp.max(s, axis=-1), own)
    e, e_own = jnp.exp(s - top[..., None]), jnp.exp(own - top)
    o = (jnp.einsum("hqk,khd->qhd", e, v, precision=HI)
         + e_own.T[..., None] * v_own) / (jnp.sum(e, axis=-1)
                                          + e_own).T[..., None]
    return (xv * _f32(blk["attn_alpha"])
            + mm(o.reshape(n, hq * d), blk["attn"]["wo"]["kernel"]))


def ref_scores(g, r_before, router, eps, quant=None):
    """The router's stream ``r`` of this layer and its scores ``[rows,
    experts]`` (softmax, float32)."""
    _, mm = _mm(quant)
    r = mm(g, router["down"]["kernel"]) + _f32(router["gamma"]) * r_before
    z = _rms(r, router["norm"]["scale"], eps)
    z = jax.nn.gelu(mm(z, router["w1"]["kernel"]), approximate=False)
    z = jax.nn.gelu(mm(z, router["w2"]["kernel"]), approximate=False)
    return r, jax.nn.softmax(mm(z, router["w3"]["kernel"]), axis=-1)


def _expert_block(g, ex, i, eb, q_):
    """The experts ``i * eb .. (i + 1) * eb - 1`` applied to every row of
    ``g``: ``[eb, rows, d]``."""
    def up(name):
        return jax.lax.dynamic_slice_in_dim(
            ex[name], i * eb, eb).astype(jnp.float32)

    gate = jnp.einsum("td,edf->etf", q_(g), q_(up("w_gate")), precision=HI)
    lift = jnp.einsum("td,edf->etf", q_(g), q_(up("w_up")), precision=HI)
    return jnp.einsum("etf,efd->etd", q_(jax.nn.silu(gate) * lift),
                      q_(up("w_down")), precision=HI)


def _weigh(g, ex, weights_of, q_, *, first=0, expert_block=EXPERT_BLOCK):
    """Every held expert applied to every row of ``g`` and weighed by
    ``weights_of`` (``[rows, all experts]``, 0 where a row did not choose
    the expert), a block of experts at a time."""
    held = ex["w_gate"].shape[0]
    eb = math.gcd(held, expert_block)

    def block(y, i):
        w = jax.lax.dynamic_slice_in_dim(weights_of, first + i * eb, eb,
                                         axis=1)
        return y + jnp.einsum("etd,te->td", _expert_block(g, ex, i, eb, q_),
                              w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(g), jnp.arange(held // eb))
    return y


def ref_moe(x, r_before, blk, *, eps, quant=None, first=0):
    """The expert block: ``(alpha * x + s[e] * expert_e(norm(x)), r,
    lead)``, ``e`` the one expert ``score + bias`` puts first and ``lead``
    by how much it leads the second.  ``first``: the held experts are
    ``first .. first + held - 1`` (the leaves' leading dim); the others'
    part is left out."""
    q_ = QUANT[quant]
    g = _rms(x, blk["moe_norm"]["scale"], eps)
    router = blk["moe"]["router"]
    r, s = ref_scores(g, r_before, router, eps, quant)
    vals, idx = jax.lax.top_k(s + _f32(router["bias"]), 2)
    rows = jnp.arange(s.shape[0])
    chosen = jnp.zeros_like(s).at[rows, idx[:, 0]].set(s[rows, idx[:, 0]])
    y = _weigh(g, blk["moe"]["experts"], chosen, q_, first=first)
    return x * _f32(blk["moe_alpha"]) + y, r, vals[:, 0] - vals[:, 1]


def ref_moe_of_choices(x1, g, s, r, parent, idx, blk):
    """The expert block's output for rows that share their inputs: row
    ``j`` of the result is ``alpha * x1[parent[j]]`` plus the expert
    ``idx[j, 0]`` of ``g[parent[j]]`` weighed by its score; and the
    router stream those rows carry on."""
    ex = blk["moe"]["experts"]
    n = ex["w_gate"].shape[0]
    eb = math.gcd(n, EXPERT_BLOCK)
    g_of = jax.nn.one_hot(idx[:, 0], n) * jnp.take_along_axis(
        s[parent], idx, axis=-1)

    def block(y, i):
        out = _expert_block(g, ex, i, eb, QUANT[None])[:, parent]
        w = jax.lax.dynamic_slice_in_dim(g_of, i * eb, eb, axis=1)
        return y + jnp.einsum("ecd,ce->cd", out, w, precision=HI), None

    y, _ = jax.lax.scan(block, jnp.zeros((parent.shape[0], g.shape[1]),
                                         jnp.float32), jnp.arange(n // eb))
    return (x1 * _f32(blk["moe_alpha"]))[parent] + y, r[parent]


def _dims(config: dict):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], rotary_dim(config),
            float(config["rope_parameters"]["hybrid"]["rope_theta"]),
            float(config["rms_norm_eps"]))


def _ref_layer(x, r, blk, *, dims, quant):
    x = ref_attention(x, blk, dims=dims, quant=quant)
    return ref_moe(x, r, blk, eps=dims[-1], quant=quant)


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length so that one compiled layer serves every
    sample (everything is causal: the padding changes no earlier row; a
    row's routing is its own)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        self.width = config["router_hidden_size"]
        dims = _dims(config)
        eps = dims[-1]
        q = QUANT[quant]
        self._layer = jax.jit(functools.partial(
            _ref_layer, dims=dims, quant=quant))
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._readout = jax.jit(lambda x, scale, emb: jnp.matmul(
            q(_rms(x, scale, eps)), q(emb.astype(jnp.float32)).T,
            precision=HI))

        def route(xv, rv, pos, x, blk):
            x1 = ref_attention_of_rows(xv, pos, x, blk, dims=dims)
            g = _rms(x1, blk["moe_norm"]["scale"], eps)
            router = blk["moe"]["router"]
            r, s = ref_scores(g, rv, router, eps)
            vals, idx = jax.lax.top_k(
                s + _f32(router["bias"]),
                min(1 + NEAR_TIES_SHOWN, s.shape[1]))
            return x1, g, s, r, vals, idx

        self._route = jax.jit(route)
        self._choices = jax.jit(ref_moe_of_choices)

        def gaps(x, scale_, emb, picks):
            logits = jnp.matmul(_rms(x, scale_, eps),
                                emb.astype(jnp.float32).T, precision=HI)
            best = jnp.max(logits, axis=-1)
            return best[None] - jnp.take_along_axis(
                logits, picks.T, axis=-1).T

        self._gaps = jax.jit(gaps)

    def _forward(self, context: np.ndarray, keep: bool = False):
        """The last layer's output rows; with ``keep`` also every layer's
        input rows, the router stream before it, and each row's lead of
        its first expert over its second ``[layers, rows]``."""
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(context)] = context
        x = self._embed(self.p["tok_embed"], jnp.asarray(toks))
        r = jnp.zeros((self.pad_to, self.width), jnp.float32)
        inputs, streams, leads = [], [], []
        for li in range(self.layers):
            if keep:
                inputs.append(x)
                streams.append(r)
            x, r, lead = self._layer(x, r, self.p[f"layer_{li}"])
            if keep:
                leads.append(np.asarray(lead))
        return x, inputs, streams, leads

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits [count, vocab] of the rows ``first .. first+count-1`` of
        ``context`` (row i predicts token i + 1)."""
        x = self._forward(context)[0]
        return self._readout(x[first:first + count],
                             self.p["final_norm"]["scale"],
                             self.p["tok_embed"])

    def row_gaps(self, context: np.ndarray, first: int, count: int, picks,
                 tau: float, most: int):
        """For the rows ``first .. first+count-1`` of ``context``: by how
        much each token of ``picks`` (``[sets, count]``) lies below the
        row's best logit, under the routing that suits it best among
        those the reference's scores allow within ``tau``
        (``routings_within`` with one expert a row, layer after layer: a
        row that chooses another expert in one layer is routed anew in
        the next, over the context's rows as the reference has them, and
        carries its own router stream).  Returns ``(gaps [sets, count],
        leaves [count])``: ``leaves`` is the number of routings a row was
        followed through; 0 where they were more than ``most``, or where
        one of the ``CONTEXT_ROWS`` rows before it has a near tie of its
        own in some layer (its slot state may then be another expert's),
        and the row is not compared."""
        _, inputs, streams, leads = self._forward(context, keep=True)
        near = np.min(np.stack(leads), axis=0) < tau            # [rows]
        x = np.asarray(inputs[0])[first:first + count]
        r = np.asarray(streams[0])[first:first + count]
        owner = np.arange(count)
        alive = np.ones(count, bool)
        for back in range(1, CONTEXT_ROWS + 1):
            at = first + np.arange(count) - back
            alive &= ~(near[np.maximum(at, 0)] & (at >= 0))
        keep = alive[owner]
        x, r, owner = x[keep], r[keep], owner[keep]
        for li in range(self.layers):
            if not len(owner):
                break
            blk = self.p[f"layer_{li}"]
            pos = first + owner
            parts = []
            for lo, hi in _chunks(len(owner), ROWS):
                x1, g, s, rr, vals, idx = self._route(
                    _rows(x[lo:hi], ROWS), _rows(r[lo:hi], ROWS),
                    _rows(pos[lo:hi], ROWS), inputs[li], blk)
                parts.append((x1, g, s, rr, np.asarray(vals)[:hi - lo],
                              np.asarray(idx)[:hi - lo]))
            node, experts, _, over = routings_within(
                np.concatenate([p[4] for p in parts]),
                np.concatenate([p[5] for p in parts]), 1, tau, most)
            alive[np.unique(owner[over])] = False
            per_row = np.bincount(owner[node], minlength=count)
            alive &= per_row <= most
            keep = alive[owner[node]]
            node, experts = node[keep], experts[keep]
            out_x = [np.zeros((0, x.shape[1]), np.float32)]
            out_r = [np.zeros((0, r.shape[1]), np.float32)]
            for (lo, hi), (x1, g, s, rr, _, _) in zip(
                    _chunks(len(owner), ROWS), parts):
                mine = np.flatnonzero((node >= lo) & (node < hi))
                for a, b in _chunks(len(mine), CHOICES):
                    sel = mine[a:b]
                    xs, rs = self._choices(
                        x1, g, s, rr, _rows(node[sel] - lo, CHOICES),
                        _rows(experts[sel], CHOICES), blk)
                    out_x.append(np.asarray(xs)[:len(sel)])
                    out_r.append(np.asarray(rs)[:len(sel)])
            x, r = np.concatenate(out_x), np.concatenate(out_r)
            owner = owner[node]
        keep = alive[owner]
        x, owner = x[keep], owner[keep]
        picks = np.asarray(picks, np.int32)
        gaps = [np.zeros((len(picks), 0))]
        for lo, hi in _chunks(len(owner), ROWS):
            gaps.append(np.asarray(self._gaps(
                _rows(x[lo:hi], ROWS), self.p["final_norm"]["scale"],
                self.p["tok_embed"],
                _rows(picks[:, owner[lo:hi]].T, ROWS).T))[:, :hi - lo])
        gaps = np.concatenate(gaps, axis=1)
        best = np.full((len(picks), count), np.inf)
        np.minimum.at(best, (slice(None), owner), gaps)
        return best, np.bincount(owner, minlength=count)


def served_gaps(config: dict, params, sample, pad_to: int,
                with_control: bool = False) -> dict:
    """For each sampled finished request, run the reference once over its
    prompt with its served tokens; the widest gap by which a served
    token's logit lies below the reference's best -- over the rows and
    under the routings :meth:`Reference.row_gaps` follows
    (``limits.routing_margin_min``, ``limits.routing_branches_max``;
    ``tokens_compared`` counts the rows compared).  ``with_control`` also
    reads, at the same rows and under the same rule, the gap of the token
    the fp8 reference puts first.  ``sample``: ``[(prompt,
    served_tokens), ...]``."""
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
        gaps, leaves = ref.row_gaps(ctx, first, n, np.stack(picks), tau,
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
