"""The ``ouro_loop`` family: a dense pre-norm-and-post-norm decoder whose
layers run ``total_ut_steps`` times a token over ONE set of weights, the
model's final norm between two passes and an exit gate read after each
(the looped language model, arXiv:2510.25741, at the sizes
``ByteDance/Ouro-2.6B`` publishes) -- served by ``ServingEngine`` through
``horovod_tpu/serving/loop_dense.py``.

What the harness takes from here: how the engine is built from the
program's own entry points, the bytes a decode round must move (every
layer's weights once a pass, the head once, and a live token's keys and
values in every plane), the names the programs carry in a device trace,
and the plain reference.  The reference (``ref_*``, ``Reference``) is
straight ``jax.numpy`` in float32 at ``highest`` matmul precision over
the benchmark's own weights, upcast a layer at a time: no kernels, no
cache (each pass recomputes its keys and values over the whole context),
no planes, no batching, nothing imported from ``horovod_tpu``; attention
runs in blocks of query rows.  The loop is a Python loop over the passes
around a Python loop over the layers.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT

# Names on a device plane's modules line: the decode program is a plain
# ``jax.jit`` of ``loop_dense_step``; the prefill programs (one a prompt
# length) are the engine's ``_prefill`` as for every model.
DECODE_MODULE = r"^jit_loop_dense_step\("
PREFILL_MODULE = r"^jit__prefill\("

QUERY_BLOCK = 256     # query rows a block of the reference's attention

# What ``config.json`` gives no values for (``assumed``): every norm's
# scale and the gate's bias, the value that leaves each out of the
# mathematics, and how far the seeded draw puts them off it: a program
# that forgets one, or takes one norm for another, fails the comparison.
SPREAD = 0.1


def passes(config: dict) -> int:
    return int(config["total_ut_steps"])


def program_config(config: dict):
    from horovod_tpu.serving.loop_dense import LoopDenseConfig
    return LoopDenseConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ffn_hidden=config["intermediate_size"],
        passes=passes(config),
        exit_threshold=float(config["early_exit_threshold"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"])


def kv_bytes_per_token(config: dict) -> int:
    """Bytes one token of context holds over every PLANE (a pass a
    layer), in the cache's type (2 bytes): its keys after RoPE and its
    values.  A decode round reads them all, for every live token."""
    return (passes(config) * config["num_hidden_layers"] * 2
            * config["num_key_value_heads"] * config["head_dim"] * 2)


def layer_weight_bytes(config: dict) -> int:
    """Bytes of ONE set of the layers' weights (2 bytes a weight)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * f + 4 * d
    return 2 * config["num_hidden_layers"] * layer


def weight_bytes_per_round(config: dict) -> int:
    """The least bytes of weights a decode round must read: every
    layer's, once a pass (pass t + 1 of the first layer needs pass t of
    the last, so no order of the loops saves a stream), and the head
    once.  The embedding is read a row a slot."""
    return (passes(config) * layer_weight_bytes(config)
            + 2 * config["hidden_size"] * config["vocab_size"])


def weight_bytes(config: dict) -> int:
    d = config["hidden_size"]
    return (layer_weight_bytes(config)
            + 2 * (2 * config["vocab_size"] * d + d + d + 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _off_identity(salt, shape, dtype, identity: float):
    return (identity + SPREAD * weights.hash_normal(salt, shape)).astype(
        dtype)


def seeded_assumptions(params, seed: int):
    """What ``lib/weights.py`` cannot know of this tree (in place): it
    draws every ``scale`` at one and every ``bias`` at zero, where a norm
    taken for another, or left out, changes nothing.  Every norm's scale
    is put ``SPREAD`` off one and the gate's bias ``SPREAD`` off zero."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        kind = weights.leaf_kind(weights.path_name(path))
        if kind in ("scale", "bias"):
            leaf = _off_identity(
                jnp.uint32(weights.leaf_salt(seed + 1, i)),
                tuple(leaf.shape), leaf.dtype,
                1.0 if kind == "scale" else 0.0)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        # The program's new module first: a program without it fails
        # here, before any weight is made.
        from horovod_tpu.serving import loop_dense

        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving

        self.config, self.chips = config, chips
        cfg = program_config(config)
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = loop_dense.param_shapes(cfg, dtype)
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

def _f32(leaf):
    return leaf.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, theta):
    """x: [t, heads, d]; all ``d`` columns rotated half against half at
    positions 0..t-1."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def ref_layer(x, blk, *, heads, kv_heads, head_dim, theta, eps, quant=None,
              query_block=QUERY_BLOCK):
    """One block over the whole context ``x`` ``[t, d]``: ``a = x +
    N2(Attn(N1(x)))``, then ``a + N4(MLP(N3(a)))``."""
    q_ = QUANT[quant]

    def mm(a, b):
        return jnp.matmul(q_(a), q_(_f32(b)), precision=HI)

    t = x.shape[0]
    at = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"], eps)
    qh = _rope(mm(h, at["wq"]["kernel"]).reshape(t, heads, head_dim), theta)
    kh = _rope(mm(h, at["wk"]["kernel"]).reshape(t, kv_heads, head_dim),
               theta)
    vh = mm(h, at["wv"]["kernel"]).reshape(t, kv_heads, head_dim)
    kh, vh = (jnp.repeat(z, heads // kv_heads, axis=1) for z in (kh, vh))
    bq = math.gcd(t, query_block)
    cols = jnp.arange(t)

    def block(i):
        rows = i * bq + jnp.arange(bq)
        qb = jax.lax.dynamic_slice_in_dim(qh, i * bq, bq)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(kh),
                       precision=HI) / math.sqrt(head_dim)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(vh), precision=HI)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(
        t, heads * head_dim)
    a = x + _rms(mm(o, at["wo"]["kernel"]), blk["post_attn_norm"]["scale"],
                 eps)
    m = blk["mlp"]
    h = _rms(a, blk["mlp_norm"]["scale"], eps)
    y = mm(jax.nn.silu(mm(h, m["w_gate"]["kernel"]))
           * mm(h, m["w_up"]["kernel"]), m["w_down"]["kernel"])
    return a + _rms(y, blk["post_mlp_norm"]["scale"], eps)


def ref_between(x, final_scale, gate, eps):
    """What follows a pass: ``h_t = N_f(x)`` and the gate's ``lambda_t``
    a row."""
    h = _rms(x, final_scale, eps)
    z = jnp.matmul(h, _f32(gate["kernel"]), precision=HI)[:, 0]
    return h, jax.nn.sigmoid(z + _f32(gate["bias"])[0])


def exit_distribution(leave: np.ndarray) -> np.ndarray:
    """``lambda`` ``[T, rows]`` -> ``p`` ``[T, rows]``, written as the
    paper writes it: pass by pass, what is left at the last."""
    leave = np.asarray(leave, np.float64)
    out, stay = np.zeros_like(leave), np.ones_like(leave[0])
    for t in range(len(leave) - 1):
        out[t] = leave[t] * stay
        stay = stay * (1.0 - leave[t])
    out[-1] = stay
    return out


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length so that one compiled layer serves every
    sample (causal attention: the padding changes no earlier row)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.passes = passes(config)
        self.threshold = float(config["early_exit_threshold"])
        self.pad_to = pad_to
        eps = float(config["rms_norm_eps"])
        q = QUANT[quant]
        self._layer = jax.jit(functools.partial(
            ref_layer, heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            theta=float(config["rope_theta"]), eps=eps, quant=quant))
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._between = jax.jit(functools.partial(ref_between, eps=eps))
        self._readout = jax.jit(lambda h, head: jnp.matmul(
            q(h), q(_f32(head)), precision=HI))

    def forward(self, context: np.ndarray):
        """``(h [T, rows, d], p [T, rows])``: every pass's normed output
        and the exit distribution, over the padded context."""
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(context)] = context
        x = self._embed(self.p["tok_embed"], jnp.asarray(toks))
        hs, leave = [], []
        for _ in range(self.passes):
            for li in range(self.layers):
                x = self._layer(x, self.p[f"layer_{li}"])
            x, lam = self._between(x, self.p["final_norm"]["scale"],
                                   self.p["exit_gate"])
            hs.append(x)
            leave.append(np.asarray(lam))
        return hs, exit_distribution(np.stack(leave))

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits [count, vocab] of the rows ``first .. first+count-1`` of
        ``context`` (row i predicts token i + 1): each row read out at the
        first pass whose running sum of ``p`` reaches the threshold (at 1:
        the last pass, for every row)."""
        hs, p = self.forward(context)
        rows = np.arange(first, first + count)
        # The last pass where none reaches it (rounding: the sum ends a
        # few ulp off 1).
        reach = np.cumsum(p[:, rows], axis=0) >= self.threshold - 1e-9
        at = np.where(reach.any(axis=0), reach.argmax(axis=0),
                      self.passes - 1)
        h = jnp.stack(hs)[jnp.asarray(at), jnp.asarray(rows)]
        return self._readout(h, self.p["lm_head"]["kernel"])


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
