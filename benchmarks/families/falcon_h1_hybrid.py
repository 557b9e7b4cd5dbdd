"""The ``falcon_h1_hybrid`` family: the block as Falcon-H1-34B-Instruct
publishes its sizes -- in EVERY layer a Mamba-2 mixer (32 heads of 128
columns, a state of 256 a column, 2 groups, a four-tap convolution)
beside grouped-query attention (20 query heads over 4 key/value heads of
128, five a group), both reading one normed input and both added to the
residual, then a SwiGLU feed-forward; fourteen multipliers on the
products; an untied head -- served whole-layered on one chip by
``ServingEngine`` through ``horovod_tpu/serving/ssm_hybrid.py``.

What the harness takes from here: how the engine is built from the
program's own entry points, the bytes a cached token and a slot's
recurrent state hold, the names the programs and kernels carry in a
device trace, the seeded vectors ``lib/weights.py`` cannot know, and the
plain reference.  The reference (``ref_*``, ``Reference``) is straight
``jax.numpy`` in float32 at ``highest`` matmul precision over the
benchmark's own weights, upcast a layer at a time: no kernels, no cache,
no batching, nothing imported from ``horovod_tpu``.  Its recurrence is a
plain ``lax.scan`` over TOKENS, ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t
(outer) B_t``, ``y_t = H_t C_t + D x_t``, with ``H`` kept ``[p, n]`` as
the equations write it: the program's chunked scan (prefill), its
in-place one-token update over a transposed state (decode) and the
convolution's rows it carries from the one into the other are checked
against no chunk, no transposition and no carried row at all.  The model
routes nothing: ``served_gaps`` is the plain comparison.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``ssm_hybrid_step``; the prefill programs (one a prompt
# length and group size) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_ssm_hybrid_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The Mosaic calls, as the ops line names them: the page walk of the
# attention half, and the in-place update of the recurrent state.
CCA_DECODE_KERNEL = r"^%hvd_cca_decode[.\d]* = "
SSM_DECODE_KERNEL = r"^%hvd_ssm_decode[.\d]* = "

# Mamba-2's own initialisation of the vectors the config does not fix.
A_RANGE = (1.0, 16.0)           # A uniform in it; A_log its logarithm
DT_RANGE = (1e-3, 1e-1)         # dt log-uniform in it; dt_bias its
#                                 softplus's inverse
D_VALUE = 1.0

QUERY_BLOCK = 256     # query rows a block of the reference's attention
HEAD_BLOCKS = 8       # column blocks the reference's head is upcast in


def program_config(config: dict):
    from horovod_tpu.serving.ssm_hybrid import SsmHybridConfig
    flags = {"attention_bias": False, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "mlp_bias": False,
             "projectors_bias": False, "mamba_rms_norm": True,
             "mamba_norm_before_gate": False, "mamba_use_mlp": True,
             "tie_word_embeddings": False, "hidden_act": "silu",
             "attn_layer_indices": None, "rope_scaling": None}
    wrong = {k: config[k] for k, v in flags.items() if config[k] != v}
    if wrong or config["mamba_d_ssm"] != (config["mamba_n_heads"]
                                          * config["mamba_d_head"]):
        raise ValueError(
            "the program computes a biased convolution and no other bias, "
            "a gated norm with the gate first, attention in every layer, "
            f"an untied head: {wrong}, mamba_d_ssm {config['mamba_d_ssm']}")
    return SsmHybridConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_hidden=config["intermediate_size"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        conv_taps=config["mamba_d_conv"],
        scan_chunk=config["mamba_chunk_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        embedding_multiplier=config["embedding_multiplier"],
        lm_head_multiplier=config["lm_head_multiplier"],
        attention_in_multiplier=config["attention_in_multiplier"],
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        ssm_multipliers=tuple(config["ssm_multipliers"]))


# -- counts -----------------------------------------------------------------------

def conv_width(config: dict) -> int:
    """Columns the convolution runs over: ``[x | B | C]``."""
    return config["mamba_d_ssm"] + 2 * (config["mamba_n_groups"]
                                        * config["mamba_d_state"])


def in_width(config: dict) -> int:
    """Columns of the mixer's projection: ``[z | x | B | C | dt]``."""
    return (config["mamba_d_ssm"] + conv_width(config)
            + config["mamba_n_heads"])


def layer_params(config: dict) -> int:
    """Parameters of one layer: attention, mixer, SwiGLU, two norms."""
    d, dh = config["hidden_size"], config["head_dim"]
    q, kv = (config["num_attention_heads"] * dh,
             config["num_key_value_heads"] * dh)
    attention = 2 * d * q + 2 * d * kv
    cw, taps = conv_width(config), config["mamba_d_conv"]
    mixer = (d * in_width(config) + config["mamba_d_ssm"] * d
             + cw * taps + cw + 3 * config["mamba_n_heads"]
             + config["mamba_d_ssm"])
    return attention + mixer + 3 * d * config["intermediate_size"] + 2 * d


def weight_bytes(config: dict) -> int:
    """Bytes of the weights held (2 bytes a weight): every layer, the
    embedding, the untied head and the final norm."""
    d = config["hidden_size"]
    return 2 * (config["num_hidden_layers"] * layer_params(config)
                + 2 * config["vocab_size"] * d + d)


def kv_bytes_per_token(config: dict) -> int:
    """Bytes a LIVE token holds over every layer, in the cache's type (2
    bytes): its keys and its values, one row in each pool, what the walks
    (``hvd_cca_decode``) must read of it a round."""
    return (config["num_hidden_layers"] * 2
            * config["num_key_value_heads"] * config["head_dim"] * 2)


def ssm_state_bytes_per_slot(config: dict) -> int:
    """Bytes of recurrent state ``H`` a live slot holds over every layer
    (float32, whatever the cache's type): what a decode round's update
    (``hvd_ssm_decode``) must read once and write once."""
    return (config["num_hidden_layers"] * config["mamba_n_heads"]
            * config["mamba_d_head"] * config["mamba_d_state"] * 4)


def slot_state_values(config: dict) -> int:
    """Float32 values a slot keeps a layer: ``H`` and, behind it, the
    convolution's last ``mamba_d_conv - 1`` inputs."""
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"]
            + (config["mamba_d_conv"] - 1) * conv_width(config))


def cache_bytes(config: dict) -> int:
    """Bytes of the two pools (2 bytes a value, ``slots * max_len / page +
    1`` pages a plane) and of the slot state (4 bytes a value)."""
    s = config["serving"]
    pages = s["slots"] * s["max_len"] // s["page_size"] + 1
    return (pages * s["page_size"] * kv_bytes_per_token(config)
            + config["num_hidden_layers"] * s["slots"]
            * slot_state_values(config) * 4)


# -- the vectors the seed cannot know ---------------------------------------------

@functools.lru_cache(maxsize=None)
def _uniform(shape):
    """Traceable: float32 uniforms in (0, 1) from a uint32 salt (the low
    half of ``weights.py``'s hash)."""
    def draw(salt):
        idx = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
        return ((weights._mix(idx ^ salt) & 0xFFFF).astype(jnp.float32)
                + 0.5) / 65536.0
    return jax.jit(draw)


def seeded_assumptions(params, seed: int):
    """What ``lib/weights.py`` cannot know of this tree, leaf by leaf (in
    place): the mixer's ``A_log``, ``dt_bias`` and ``D`` are drawn there
    as kernels; here they get Mamba-2's own initialisation (``A`` uniform
    in ``A_RANGE``, ``dt`` log-uniform in ``DT_RANGE`` under its
    softplus, ``D`` = 1).  The convolution's ``[taps, columns]`` weights
    are drawn there at ``1 / sqrt(taps)`` already, its bias at zero."""
    flat = jax.tree_util.tree_flatten_with_path(params["params"])[0]
    index = {weights.path_name(path): i for i, (path, _) in enumerate(flat)}
    for name, blk in params["params"].items():
        if not name.startswith("layer_"):
            continue
        ssm = blk["ssm"]

        def drawn(key, ssm=ssm, name=name):
            leaf = ssm[key]
            u = _uniform(tuple(leaf.shape))(jnp.uint32(weights.leaf_salt(
                seed + 1, index[f"{name}/ssm/{key}"])))
            return leaf, u

        leaf, u = drawn("A_log")
        ssm["A_log"] = jnp.log(
            A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * u).astype(leaf.dtype)
        leaf, u = drawn("dt_bias")
        dt = jnp.exp(math.log(DT_RANGE[0])
                     + u * math.log(DT_RANGE[1] / DT_RANGE[0]))
        ssm["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(leaf.dtype)
        ssm["D"] = jnp.full(ssm["D"].shape, D_VALUE, ssm["D"].dtype)
    return params


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's block first: a program without it fails here,
        # before any weight is made.
        cfg = program_config(config)

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving
        from horovod_tpu.serving import ssm_hybrid

        self.config, self.chips = config, chips
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = ssm_hybrid.param_shapes(cfg, dtype)
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

def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _mm(quant):
    q = QUANT[quant]
    return q, lambda a, b: jnp.matmul(q(a), q(_f32(b)), precision=HI)


def _rope(x, theta):
    """``x``: ``[t, heads, d]`` at positions 0..t-1; rotate-half over all
    ``d`` columns."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def ref_attention(u, attn, config: dict, quant=None,
                  query_block=QUERY_BLOCK):
    """Step 2 over the whole context: every query against every key
    under the causal mask, query head ``i`` reading key/value head ``i //
    5``."""
    q_, mm = _mm(quant)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, theta = config["head_dim"], float(config["rope_theta"])
    t = u.shape[0]
    h = u * config["attention_in_multiplier"]
    q = _rope(mm(h, attn["wq"]["kernel"]).reshape(t, heads, dh), theta)
    k = _rope((mm(h, attn["wk"]["kernel"])
               * config["key_multiplier"]).reshape(t, kv, dh), theta)
    v = mm(h, attn["wv"]["kernel"]).reshape(t, kv, dh)
    k, v = (jnp.repeat(z, heads // kv, axis=1) for z in (k, v))
    bq = math.gcd(t, query_block)
    cols = jnp.arange(t)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(k),
                       precision=HI) / math.sqrt(dh)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(v), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, heads * dh)
    return mm(o, attn["wo"]["kernel"]) * config["attention_out_multiplier"]


def ref_mixer(u, ssm, config: dict, quant=None):
    """Step 3 over the whole context, the recurrence a token at a time."""
    _, mm = _mm(quant)
    t = u.shape[0]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    d_ssm, taps = config["mamba_d_ssm"], config["mamba_d_conv"]
    bc = groups * n
    widths = [d_ssm, d_ssm, bc, bc, heads]
    m = jnp.concatenate([jnp.full((w,), mult, jnp.float32) for w, mult in
                         zip(widths, config["ssm_multipliers"])])
    zxbcdt = mm(u * config["ssm_in_multiplier"], ssm["w_in"]["kernel"]) * m
    z, xbc, dt_raw = (zxbcdt[:, :d_ssm], zxbcdt[:, d_ssm:2 * d_ssm + 2 * bc],
                      zxbcdt[:, 2 * d_ssm + 2 * bc:])
    w = _f32(ssm["conv"]["w"])
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    conv = sum(w[i] * padded[i:i + t] for i in range(taps)) \
        + _f32(ssm["conv"]["bias"])
    act = jax.nn.silu(conv)
    xs = act[:, :d_ssm].reshape(t, heads, p)
    bm = jnp.repeat(act[:, d_ssm:d_ssm + bc].reshape(t, groups, n),
                    heads // groups, axis=1)
    cm = jnp.repeat(act[:, d_ssm + bc:].reshape(t, groups, n),
                    heads // groups, axis=1)
    dt = jax.nn.softplus(dt_raw + _f32(ssm["dt_bias"]))       # [t, heads]
    a = -jnp.exp(_f32(ssm["A_log"]))

    def token(hs, row):
        x_t, b_t, c_t, dt_t = row
        hs = jnp.exp(dt_t * a)[:, None, None] * hs + (
            dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return hs, jnp.einsum("hpn,hn->hp", hs, c_t, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                        (xs, bm, cm, dt))
    y = (y + _f32(ssm["D"])[:, None] * xs).reshape(t, d_ssm)
    g = (y * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + float(config["rms_norm_eps"]))
    g = g.reshape(t, d_ssm) * _f32(ssm["norm"]["scale"])
    return mm(g, ssm["w_out"]["kernel"]) * config["ssm_out_multiplier"]


def ref_layer(x, blk, *, config, quant=None):
    """One block: both mixers on one normed input, then the SwiGLU."""
    _, mm = _mm(quant)
    eps = float(config["rms_norm_eps"])
    u = _rms(x, blk["in_norm"]["scale"], eps)
    x = x + ref_attention(u, blk["attn"], config, quant) \
        + ref_mixer(u, blk["ssm"], config, quant)
    v = _rms(x, blk["ffn_norm"]["scale"], eps)
    mlp = blk["mlp"]
    gate = mm(v, mlp["w_gate"]["kernel"]) * config["mlp_multipliers"][0]
    return x + mm(jax.nn.silu(gate) * mm(v, mlp["w_up"]["kernel"]),
                  mlp["w_down"]["kernel"]) * config["mlp_multipliers"][1]


def ref_head(x, head, quant=None, blocks=HEAD_BLOCKS):
    """``x W_head``, the head upcast a block of columns at a time (whole,
    in float32, the published head is 5.3 GB beside 10.5 GB of weights).
    The control's one scale a tensor is the WHOLE head's, as
    ``lib/lowprec.py`` takes it."""
    q_ = QUANT[quant]
    xq = q_(x)
    vocab = head.shape[1]
    vb = vocab // math.gcd(vocab, blocks)
    top = jnp.maximum(_f32(jnp.max(jnp.abs(head))), 1e-30)

    def block(i):
        w = _f32(jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1))
        if quant == "fp8":
            s = top / 448.0
            w = _f32((w / s).astype(jnp.float8_e4m3fn)) * s
        elif quant == "int8":
            s = top / 127.0
            w = jnp.round(w / s) * s
        return jnp.matmul(xq, w, precision=HI)

    out = jax.lax.map(block, jnp.arange(vocab // vb))   # [blocks, rows, vb]
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length so that one compiled layer serves every
    sample (causal attention, a causal convolution and a recurrence: the
    padding changes no earlier row)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        eps = float(config["rms_norm_eps"])
        # The config is a dict: closed over, not traced.
        self._layer = jax.jit(lambda x, blk: ref_layer(
            x, blk, config=config, quant=quant))
        self._embed = jax.jit(lambda emb, toks: _f32(emb[toks])
                              * config["embedding_multiplier"])
        self._readout = jax.jit(lambda x, scale, head: ref_head(
            _rms(x, scale, eps), head, quant)
            * config["lm_head_multiplier"])

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits [count, vocab] of the rows ``first .. first+count-1`` of
        ``context`` (row i predicts token i + 1)."""
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(context)] = context
        x = self._embed(self.p["tok_embed"], jnp.asarray(toks))
        for li in range(self.layers):
            x = self._layer(x, self.p[f"layer_{li}"])
        return self._readout(x[first:first + count],
                             self.p["final_norm"]["scale"],
                             self.p["lm_head"]["kernel"])


def served_gaps(config: dict, params, sample, pad_to: int,
                with_control: bool = False) -> dict:
    """For each sampled finished request, run the reference once over its
    prompt with its served tokens; the widest gap by which a served
    token's logit lies below the reference's best.  ``with_control`` also
    reads, at the same rows, the gap of the token the fp8 reference puts
    first.  ``sample``: ``[(prompt, served_tokens), ...]``."""
    ref = Reference(config, params, pad_to)
    ctl = Reference(config, params, pad_to, quant="fp8") \
        if with_control else None
    widest, widest_ctl, tokens = 0.0, 0.0, 0
    for prompt, served in sample:
        served = np.asarray(served, np.int64)
        ctx = np.concatenate([np.asarray(prompt, np.int64), served])
        first, n = len(prompt) - 1, len(served)
        logits = np.asarray(ref.logits(ctx, first, n), np.float64)
        best = logits.max(axis=-1)
        widest = max(widest, float(np.max(
            best - logits[np.arange(n), served])))
        tokens += n
        if ctl is not None:
            pick = np.asarray(ctl.logits(ctx, first, n)).argmax(axis=-1)
            widest_ctl = max(widest_ctl, float(np.max(
                best - logits[np.arange(n), pick])))
    out = {"served_logit_gap_max": widest, "tokens_compared": tokens}
    if with_control:
        out["control_logit_gap_max"] = widest_ctl
    return out
