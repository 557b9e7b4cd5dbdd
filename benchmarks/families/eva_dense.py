"""The ``eva_dense`` family: the block as EvaByte publishes its sizes --
32 query heads over 32 key/value heads of 128, EVA attention (an exact,
ALIGNED window of 2,048 bytes beside one pooled key and value for each
16-byte chunk of the windows behind it, ONE softmax over both), a SwiGLU
of 11,008, norms with a unit offset, a byte vocabulary of 320 and an
untied head of 8 x 320 columns -- served whole-layered on one chip by
``ServingEngine`` through ``horovod_tpu/serving/eva_dense.py``.

What the harness takes from here: how the engine is built from the
program's own entry points, the bytes a cached row holds, the names the
programs and the kernel carry in a device trace, the seeded vectors
``lib/weights.py`` cannot know, and the plain reference.  The reference
(``ref_*``, ``Reference``) is straight ``jax.numpy`` in float32 at
``highest`` matmul precision over the benchmark's own weights, upcast a
layer at a time: no kernels, no cache, no window at a time, nothing
imported from ``horovod_tpu``.  It computes the equations over the WHOLE
sequence under an explicit mask (exact key ``j`` is of window ``j //
2048``, pooled chunk ``c`` of window ``c // 128``; query ``i`` sees the
exact keys of its own window up to itself and the pooled chunks of
earlier windows), in blocks of queries so that 13,548 positions fit: the
program's ring, its pooled pages, its window-at-a-time prefill and its
in-round pooling are checked against no ring, no page and no round at
all.  The model routes nothing: ``served_gaps`` is the plain comparison,
over head 0's 320 columns, which the served byte was sampled from.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``eva_dense_step``; the prefill programs (one a prompt
# length) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_eva_dense_step\("
PREFILL_MODULE = r"^jit__prefill\("
# The one Mosaic call of the decode program, as the ops line names it:
# the page walk over the pooled pages and the ring.
EVA_DECODE_KERNEL = r"^%hvd_eva_decode[.\d]* = "

# The pooling vectors' published initialisation, as far as memory goes:
# a normal cut at one deviation, over the square root of the head's width.
POOL_CLIP = 1.0

QUERY_BLOCK = 256     # query rows a block of the reference's attention


def head_dim(config: dict) -> int:
    """The config gives no ``head_dim``: the hidden size over the heads."""
    return config["hidden_size"] // config["num_attention_heads"]


def program_config(config: dict):
    from horovod_tpu.serving.eva_dense import EvaDenseConfig
    flags = {"attention_bias": False, "attention_class": "eva",
             "hidden_act": "silu", "norm_add_unit_offset": True,
             "fp32_logits": True, "fp32_skip_add": True,
             "mixedp_attn": True, "rope_scaling": None,
             "tie_word_embeddings": False, "num_chunks": None}
    wrong = {k: config[k] for k, v in flags.items() if config[k] != v}
    if wrong or config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError(
            "the program computes EVA attention in chunks of chunk_size "
            "with no bias, SiLU gates, unit-offset norms, float32 logits "
            f"and residual, an untied head: {wrong}")
    return EvaDenseConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=head_dim(config), ffn_hidden=config["intermediate_size"],
        window=config["window_size"], chunk=config["chunk_size"],
        pred_heads=config["num_pred_heads"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"])


# -- counts -----------------------------------------------------------------------

def layer_params(config: dict) -> int:
    """Parameters of one layer: four attention projections, the SwiGLU's
    three, two norms and the two pooling vectors a key head."""
    d, dh = config["hidden_size"], head_dim(config)
    q = config["num_attention_heads"] * dh
    kv = config["num_key_value_heads"] * dh
    return (2 * d * q + 2 * d * kv + 3 * d * config["intermediate_size"]
            + 2 * d + 2 * kv)


def weight_bytes(config: dict) -> int:
    """Bytes of the weights held (2 bytes a weight): every layer, the
    embedding, the head of ``num_pred_heads`` x vocabulary columns and the
    final norm."""
    d, v = config["hidden_size"], config["vocab_size"]
    return 2 * (config["num_hidden_layers"] * layer_params(config)
                + v * d + d * config["num_pred_heads"] * v + d)


def kv_bytes_per_row(config: dict) -> int:
    """Bytes ONE cached row holds in ONE layer, in the cache's type (2
    bytes): its keys and its values, a row in each pool, whether the row
    is a byte's own or a chunk's pooled one.  What the walk
    (``hvd_eva_decode``) must read of an attended row."""
    return 2 * config["num_key_value_heads"] * head_dim(config) * 2


def ring_pages(config: dict) -> int:
    """Pages of a slot's ring: the window's, and one more."""
    return config["window_size"] // config["serving"]["page_size"] + 1


def pool_pages(config: dict) -> int:
    """Pages of a plane of each pool: a page a ``chunk_size * page_size``
    bytes of context for every slot (pooled rows), the scratch page, and
    every slot's ring."""
    s = config["serving"]
    grown = s["max_len"] // (config["chunk_size"] * s["page_size"])
    return s["slots"] * (grown + ring_pages(config)) + 1


def cache_bytes(config: dict) -> int:
    """Bytes of the two pools (2 bytes a value)."""
    return (config["num_hidden_layers"] * pool_pages(config)
            * config["serving"]["page_size"] * kv_bytes_per_row(config))


def slot_bytes_per_layer(config: dict) -> int:
    """Bytes a slot at ``max_len`` holds in one layer: its ring and its
    pooled pages."""
    s = config["serving"]
    grown = s["max_len"] // (config["chunk_size"] * s["page_size"])
    return ((ring_pages(config) + grown) * s["page_size"]
            * kv_bytes_per_row(config))


# -- the vectors the seed cannot know ---------------------------------------------

def seeded_assumptions(params, seed: int):
    """What ``lib/weights.py`` cannot know of this tree, leaf by leaf (in
    place).  The pooling vectors ``adaptive_mu_k`` and ``adaptive_phi``
    are drawn there as kernels (deviation ``1 / sqrt(heads)``); here they
    get the published initialisation: a normal cut at ``POOL_CLIP``, over
    ``sqrt(head_dim)``.  A norm's ``scale`` is its learned ``g`` under a
    UNIT OFFSET (the norm multiplies by ``1 + g``): drawn there as ones,
    it starts at zero here, as a unit-offset norm does."""
    flat = jax.tree_util.tree_flatten_with_path(params["params"])[0]
    index = {weights.path_name(path): i for i, (path, _) in enumerate(flat)}

    def zero(node):
        node["scale"] = jnp.zeros_like(node["scale"])

    for name, blk in params["params"].items():
        if name == "final_norm":
            zero(blk)
        if not name.startswith("layer_"):
            continue
        zero(blk["attn_norm"])
        zero(blk["mlp_norm"])
        attn = blk["attn"]
        for key in ("adaptive_mu_k", "adaptive_phi"):
            leaf = attn[key]
            n = weights.hash_normal(
                jnp.uint32(weights.leaf_salt(
                    seed + 1, index[f"{name}/attn/{key}"])),
                tuple(leaf.shape))
            attn[key] = (jnp.clip(n, -POOL_CLIP, POOL_CLIP)
                         / math.sqrt(leaf.shape[-1])).astype(leaf.dtype)
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
        from horovod_tpu.serving import eva_dense

        self.config, self.chips = config, chips
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = eva_dense.param_shapes(cfg, dtype)
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


def _norm(x, scale, eps):
    """``x / rms(x) * (1 + g)``."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f32(scale))


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


def ref_pooled(k, v, attn, config: dict):
    """The pooled key and value of every WHOLE chunk of ``k``, ``v``
    ``[t, kv_heads, d]``: ``[t // chunk, kv_heads, d]`` each.  ``a =
    softmax_j(mu . k_j)``, ``kbar = sum a_j k_j``; ``b = softmax_j(phi .
    k_j)``, ``vbar = sum b_j v_j``; no ``1 / sqrt(d)``."""
    c = config["chunk_size"]
    n = k.shape[0] // c
    kc = k[:n * c].reshape(n, c, *k.shape[1:])
    vc = v[:n * c].reshape(n, c, *v.shape[1:])

    def weights_of(vector):
        return jax.nn.softmax(jnp.einsum(
            "njhd,hd->njh", kc, _f32(attn[vector]), precision=HI), axis=1)

    return (jnp.einsum("njh,njhd->nhd", weights_of("adaptive_mu_k"), kc,
                       precision=HI),
            jnp.einsum("njh,njhd->nhd", weights_of("adaptive_phi"), vc,
                       precision=HI))


def ref_attention(u, attn, config: dict, quant=None,
                  query_block=QUERY_BLOCK):
    """EVA attention over the whole context: every query against every
    exact key AND every pooled chunk under one explicit mask, one
    softmax."""
    q_, mm = _mm(quant)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, theta = head_dim(config), float(config["rope_theta"])
    win, chunk = config["window_size"], config["chunk_size"]
    t = u.shape[0]
    q = _rope(mm(u, attn["wq"]["kernel"]).reshape(t, heads, dh), theta)
    k = _rope(mm(u, attn["wk"]["kernel"]).reshape(t, kv, dh), theta)
    v = mm(u, attn["wv"]["kernel"]).reshape(t, kv, dh)
    kbar, vbar = ref_pooled(k, v, attn, config)
    keys = jnp.repeat(jnp.concatenate([k, kbar]), heads // kv, axis=1)
    vals = jnp.repeat(jnp.concatenate([v, vbar]), heads // kv, axis=1)
    # The window each key is OF: an exact key's own, a pooled chunk's.
    of = jnp.concatenate([jnp.arange(t) // win,
                          jnp.arange(kbar.shape[0]) * chunk // win])
    at = jnp.concatenate([jnp.arange(t),
                          jnp.full((kbar.shape[0],), -1)])
    exact = jnp.arange(t + kbar.shape[0]) < t
    bq = math.gcd(t, query_block)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        mine = (rows // win)[:, None]
        seen = jnp.where(exact[None, :],
                         (of[None, :] == mine) & (at[None, :] <= rows[:, None]),
                         of[None, :] < mine)
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(keys),
                       precision=HI) / math.sqrt(dh)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(vals), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, heads * dh)
    return mm(o, attn["wo"]["kernel"])


def ref_layer(x, blk, *, config, quant=None):
    """One block: EVA attention, then the SwiGLU, each on its own norm."""
    _, mm = _mm(quant)
    eps = float(config["rms_norm_eps"])
    x = x + ref_attention(_norm(x, blk["attn_norm"]["scale"], eps),
                          blk["attn"], config, quant)
    h = _norm(x, blk["mlp_norm"]["scale"], eps)
    mlp = blk["mlp"]
    return x + mm(jax.nn.silu(mm(h, mlp["w_gate"]["kernel"]))
                  * mm(h, mlp["w_up"]["kernel"]), mlp["w_down"]["kernel"])


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length, a whole number of query blocks, so that
    one compiled layer serves every sample (the mask is causal, and a
    chunk is seen only from a LATER window: the padding changes no
    earlier row)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None,
                 query_block=QUERY_BLOCK):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = -(-pad_to // query_block) * query_block
        eps = float(config["rms_norm_eps"])
        _, mm = _mm(quant)
        # The config is a dict: closed over, not traced.
        self._layer = jax.jit(lambda x, blk: ref_layer(
            x, blk, config=config, quant=quant))
        self._embed = jax.jit(lambda emb, toks: _f32(emb[toks]))
        self._readout = jax.jit(lambda x, scale, head: mm(
            _norm(x, scale, eps), head))

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits ``[count, num_pred_heads * vocab]`` of the rows ``first
        .. first+count-1`` of ``context`` (row i's head 0 predicts token i
        + 1)."""
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
    token's logit lies below the reference's best, both over HEAD 0's
    columns (the next byte's, which the engine sampled from).
    ``with_control`` also reads, at the same rows, the gap of the token
    the fp8 reference puts first.  ``sample``: ``[(prompt,
    served_tokens), ...]``."""
    vocab = config["vocab_size"]
    ref = Reference(config, params, pad_to)
    ctl = Reference(config, params, pad_to, quant="fp8") \
        if with_control else None
    widest, widest_ctl, tokens = 0.0, 0.0, 0
    for prompt, served in sample:
        served = np.asarray(served, np.int64)
        ctx = np.concatenate([np.asarray(prompt, np.int64), served])
        first, n = len(prompt) - 1, len(served)
        logits = np.asarray(ref.logits(ctx, first, n)[:, :vocab], np.float64)
        best = logits.max(axis=-1)
        widest = max(widest, float(np.max(
            best - logits[np.arange(n), served])))
        tokens += n
        if ctl is not None:
            pick = np.asarray(
                ctl.logits(ctx, first, n)[:, :vocab]).argmax(axis=-1)
            widest_ctl = max(widest_ctl, float(np.max(
                best - logits[np.arange(n), pick])))
    out = {"served_logit_gap_max": widest, "tokens_compared": tokens}
    if with_control:
        out["control_logit_gap_max"] = widest_ctl
    return out
