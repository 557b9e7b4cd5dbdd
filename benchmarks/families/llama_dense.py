"""The ``llama_dense`` family: a dense pre-norm decoder with grouped-query
attention, RoPE and a SwiGLU feed-forward -- the block
``horovod_tpu/serving/decode.py`` computes -- served by ``ServingEngine``.

What the harness takes from here: how the engine is built from the
program's own entry points, the byte counts of its decode attention, the
names its programs and kernels carry in a device trace, and the plain
reference.  The reference (``ref_*``) is straight ``jax.numpy`` in
float32 at ``highest`` matmul precision over the benchmark's own weights
upcast layer by layer: no kernels, no cache, no batching, nothing
imported from ``horovod_tpu``.  It follows the program, which departs
from Mistral-7B in one place noted in the configuration file: the readout
is tied to the embedding.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import weights
from ..lib.lowprec import HI, QUANT

# Names on a device plane's modules line, as the v5e trace of PR 23
# shows them: the decode program (a jitted shard_map of ``spmd``) and the
# prefill programs (one a prompt length).  The split-KV kernel is the
# decode program's only Mosaic call.
DECODE_MODULE = r"^jit_spmd\("
PREFILL_MODULE = r"^jit__prefill\("


def program_config(config: dict):
    from horovod_tpu.models.transformer import LlamaConfig
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_model=config["hidden_size"],
        ffn_hidden=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_position_embeddings"])


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of K and V one token of context holds over every layer, in
    the cache's type (2 bytes)."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * 2)


def weight_bytes(config: dict) -> int:
    d, f = config["hidden_size"], config["intermediate_size"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    q = config["num_attention_heads"] * config["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d
    return 2 * (config["num_hidden_layers"] * layer
                + config["vocab_size"] * d + d)


class Program:
    """The engine with its weights and cache, built once and handed to
    the window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        import time

        from jax.sharding import Mesh

        from horovod_tpu import serving
        from horovod_tpu.models import LlamaLM

        self.config, self.chips = config, chips
        cfg = program_config(config)
        dtype = jnp.dtype(config["compute_dtype"])
        self.shapes = jax.eval_shape(
            LlamaLM(cfg, dtype=dtype).init, jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32))
        t0 = time.perf_counter()
        self.params = weights.make_weights(seed, self.shapes, dtype)
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

def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [t, heads, d]; rotate-half convention at positions 0..t-1."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _ref_layer(x, blk, *, heads, kv_heads, head_dim, theta, quant):
    q = QUANT[quant]

    def mm(a, b):
        return jnp.matmul(q(a), q(b.astype(jnp.float32)), precision=HI)

    t = x.shape[0]
    a = blk["attn"]
    h = _rms(x, blk["attn_norm"]["scale"].astype(jnp.float32))
    qh = _rope(mm(h, a["wq"]["kernel"]).reshape(t, heads, head_dim), theta)
    kh = _rope(mm(h, a["wk"]["kernel"]).reshape(t, kv_heads, head_dim),
               theta)
    vh = mm(h, a["wv"]["kernel"]).reshape(t, kv_heads, head_dim)
    rep = heads // kv_heads
    kh, vh = (jnp.repeat(z, rep, axis=1) for z in (kh, vh))
    s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh),
                   precision=HI) / math.sqrt(head_dim)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(s, axis=-1)), q(vh),
                   precision=HI).reshape(t, heads * head_dim)
    x = x + mm(o, a["wo"]["kernel"])
    m = blk["mlp"]
    h = _rms(x, blk["mlp_norm"]["scale"].astype(jnp.float32))
    return x + mm(jax.nn.silu(mm(h, m["w_gate"]["kernel"]))
                  * mm(h, m["w_up"]["kernel"]), m["w_down"]["kernel"])


class Reference:
    """The plain forward over one context at a time.  Contexts are padded
    on the right to one length so that one compiled layer serves every
    sample (causal attention: the padding changes no earlier row)."""

    def __init__(self, config: dict, params, pad_to: int, quant=None):
        self.p = params["params"]
        self.layers = config["num_hidden_layers"]
        self.pad_to = pad_to
        q = QUANT[quant]
        self._layer = jax.jit(functools.partial(
            _ref_layer, heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            theta=float(config["rope_theta"]), quant=quant))
        self._embed = jax.jit(
            lambda emb, toks: emb[toks].astype(jnp.float32))
        self._readout = jax.jit(lambda x, scale, emb: jnp.matmul(
            q(_rms(x, scale.astype(jnp.float32))),
            q(emb.astype(jnp.float32)).T, precision=HI))

    def logits(self, context: np.ndarray, first: int, count: int):
        """Logits [count, vocab] of the rows ``first .. first+count-1`` of
        ``context`` (row i predicts token i + 1)."""
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(context)] = context
        x = self._embed(self.p["tok_embed"], jnp.asarray(toks))
        for li in range(self.layers):
            x = self._layer(x, self.p[f"layer_{li}"])
        return self._readout(x[first:first + count], self.p["final_norm"]["scale"],
                             self.p["tok_embed"])


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
