"""Transformer model family: Llama-style decoder LM and BERT encoder.

Workload parity: BASELINE.json names "BERT-Large pretrain (Adasum + fp16
grad compression)" and "Llama-3 8B LoRA fine-tune (large bf16 allreduce,
tensor-fusion stress)" as target configs.  The reference framework itself is
model-agnostic (it ships examples, not model code), so these are built
TPU-first rather than ported: bfloat16 activations with float32 parameters,
head/FFN dims that tile the 128-lane MXU, fused attention via the Pallas
FlashAttention kernels in ``horovod_tpu.ops.attention``, and static shapes
throughout so XLA can schedule everything onto the MXU.

LoRA (Hu et al., arXiv:2106.09685) is built into the projection layers
(``DenseGeneral`` here) rather than monkey-patched: pass ``lora_rank > 0``
and every attention/MLP projection gains a rank-``r`` adapter pair.
``lora_mask`` produces the optax mask that freezes base weights.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import flash_attention

Dtype = Any


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def quantize_int8(w, axis: int = 0):
    """Per-channel symmetric int8 quantization of a 2D kernel.

    ``axis`` is the reduction axis (scales live on the OTHER axis, one per
    output channel for ``axis=0``).  Returns ``{"q": int8, "scale": f32}``
    with ``w ~= q * scale``.
    """
    w32 = jnp.asarray(w, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=axis) / 127.0, 1e-12)
    q = jnp.round(w32 / jnp.expand_dims(scale, axis)).astype(jnp.int8)
    return {"q": q, "scale": scale}


def _q8_init(inner):
    """Param init producing an int8-quantized kernel pytree (flax params
    can be arbitrary pytrees): sample the f32 init, quantize per output
    channel.  Random-init benchmarking only -- trained checkpoints convert
    via :func:`quantize_frozen_base`."""
    def init(key, shape):
        return quantize_int8(inner(key, shape, jnp.float32))
    return init


class Dense(nn.Module):
    """Linear layer with optional fused LoRA adapter.

    Base kernel is float32 (master weights), compute in ``dtype``.  With
    ``lora_rank > 0`` adds ``x @ A @ B * (alpha/r)``; A is Gaussian, B is
    zero-init so the adapter starts as identity (standard LoRA init).

    ``base_dtype="int8"`` stores the FROZEN base kernel as int8 with one
    f32 scale per output channel (a single pytree param ``kernel_q8``):
    ``y = (x @ q) * scale`` -- XLA fuses the int8->bf16 convert into the
    matmul operand load, so the bf16 kernel is never materialized in HBM.
    This quarters base-weight HBM vs f32 master weights, which is what
    lets Llama-3 8B LoRA fit a single 16 GB chip: LoRA training needs no
    base grads or master weights, so the base can live at int8 while the
    adapters keep full precision.
    """

    features: int
    use_bias: bool = False
    dtype: Dtype = jnp.bfloat16
    lora_rank: int = 0
    lora_alpha: float = 16.0
    kernel_init: Any = nn.initializers.lecun_normal()
    base_dtype: Optional[str] = None  # None (f32 master) or "int8"

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        if self.base_dtype == "int8":
            p = self.param("kernel_q8", _q8_init(self.kernel_init),
                           (in_features, self.features))
            y = ((x.astype(self.dtype) @ p["q"].astype(self.dtype))
                 * p["scale"].astype(self.dtype))
        elif self.base_dtype is None:
            kernel = self.param("kernel", self.kernel_init,
                                (in_features, self.features), jnp.float32)
            y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        else:
            raise ValueError(f"unsupported base_dtype {self.base_dtype!r}")
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        if self.lora_rank > 0:
            a = self.param("lora_a",
                           nn.initializers.normal(stddev=0.02),
                           (in_features, self.lora_rank), jnp.float32)
            b = self.param("lora_b", nn.initializers.zeros,
                           (self.lora_rank, self.features), jnp.float32)
            scale = jnp.asarray(self.lora_alpha / self.lora_rank, self.dtype)
            y = y + (x.astype(self.dtype) @ a.astype(self.dtype)
                     @ b.astype(self.dtype)) * scale
        return y


class RMSNorm(nn.Module):
    epsilon: float = 1e-5
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (norm * scale).astype(self.dtype)


def rotary_embedding(x, positions, theta: float = 500000.0):
    """Apply RoPE. x: (b, h, t, d) with even d; positions: (b, t)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class CausalSelfAttention(nn.Module):
    """GQA causal attention with RoPE, fused via Pallas flash attention."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Dtype = jnp.bfloat16
    rope_theta: float = 500000.0
    lora_rank: int = 0
    base_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        dense = partial(Dense, dtype=self.dtype, lora_rank=self.lora_rank,
                        base_dtype=self.base_dtype)
        b, t, _ = x.shape
        q = dense(self.num_heads * self.head_dim, name="wq")(x)
        k = dense(self.num_kv_heads * self.head_dim, name="wk")(x)
        v = dense(self.num_kv_heads * self.head_dim, name="wv")(x)
        q = q.reshape(b, t, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, self.num_kv_heads,
                      self.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, self.num_kv_heads,
                      self.head_dim).transpose(0, 2, 1, 3)
        q = rotary_embedding(q, positions, self.rope_theta)
        k = rotary_embedding(k, positions, self.rope_theta)
        o = flash_attention(q, k, v, causal=True, segment_ids=segment_ids)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
        return dense(x.shape[-1], name="wo")(o)


class SwiGLU(nn.Module):
    hidden: int
    dtype: Dtype = jnp.bfloat16
    lora_rank: int = 0
    base_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        dense = partial(Dense, dtype=self.dtype, lora_rank=self.lora_rank,
                        base_dtype=self.base_dtype)
        gate = dense(self.hidden, name="w_gate")(x)
        up = dense(self.hidden, name="w_up")(x)
        return dense(x.shape[-1], name="w_down")(nn.silu(gate) * up)


class DecoderBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_hidden: int
    dtype: Dtype = jnp.bfloat16
    rope_theta: float = 500000.0
    lora_rank: int = 0
    base_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        h = RMSNorm(dtype=self.dtype, name="attn_norm")(x)
        x = x + CausalSelfAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            dtype=self.dtype, rope_theta=self.rope_theta,
            lora_rank=self.lora_rank, base_dtype=self.base_dtype,
            name="attn")(h, positions, segment_ids)
        h = RMSNorm(dtype=self.dtype, name="mlp_norm")(x)
        x = x + SwiGLU(self.ffn_hidden, dtype=self.dtype,
                       lora_rank=self.lora_rank, base_dtype=self.base_dtype,
                       name="mlp")(h)
        return x


# ---------------------------------------------------------------------------
# Llama-style decoder LM
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    d_model: int = 4096
    ffn_hidden: int = 14336
    rope_theta: float = 500000.0
    max_seq_len: int = 8192


# Llama-3 8B architecture (public config: 32 layers, 32 heads / 8 KV heads,
# d_model 4096, FFN 14336, vocab 128256, rope theta 5e5).
LLAMA3_8B = LlamaConfig()
# ~0.9B single-chip variant; same shape family, used for the
# comfortable single-chip LoRA benchmark.  (The full 8B also runs on a
# 16 GB chip via base_dtype="int8": examples/llama_lora.py --8b.)
LLAMA_1B = LlamaConfig(vocab_size=32000, num_layers=16, num_heads=16,
                       num_kv_heads=8, head_dim=128, d_model=2048,
                       ffn_hidden=5632, max_seq_len=4096)
LLAMA_TINY = LlamaConfig(vocab_size=256, num_layers=2, num_heads=4,
                         num_kv_heads=2, head_dim=16, d_model=64,
                         ffn_hidden=128, max_seq_len=128)
# Serving-test variant: full-MHA head counts (8 query AND 8 kv heads) so a
# tensor-parallel decode step divides evenly across the 8-device virtual
# mesh (kv heads shard over tp; LLAMA_TINY's 2 kv heads cap tp at 2).
LLAMA_SERVE = LlamaConfig(vocab_size=256, num_layers=2, num_heads=8,
                          num_kv_heads=8, head_dim=16, d_model=64,
                          ffn_hidden=128, max_seq_len=128)


class LlamaLM(nn.Module):
    """Decoder-only LM (Llama-3 family architecture).

    ``remat=True`` rematerializes each decoder block in the backward pass
    (``jax.checkpoint`` via ``nn.remat``): activation HBM drops from
    O(layers x tokens x d) to O(tokens x d) at ~1.3x FLOPs -- the
    standard TPU trade for long sequences / big batches.
    """

    config: LlamaConfig
    dtype: Dtype = jnp.bfloat16
    lora_rank: int = 0
    remat: bool = False
    base_dtype: Optional[str] = None  # "int8": frozen base at int8+scales

    @nn.compact
    def __call__(self, tokens, positions=None, *, segment_ids=None):
        cfg = self.config
        if positions is None:
            if segment_ids is not None:
                # Packed sequences: RoPE positions restart at each
                # segment boundary (position = offset WITHIN the packed
                # sequence, not within the buffer).
                idx = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                       tokens.shape)
                first = jnp.concatenate(
                    [jnp.ones_like(segment_ids[:, :1], bool),
                     segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
                seg_start = jax.lax.cummax(
                    jnp.where(first, idx, 0), axis=1)
                positions = idx - seg_start
            else:
                positions = jnp.broadcast_to(
                    jnp.arange(tokens.shape[1]), tokens.shape)
        if self.base_dtype == "int8":
            # Tied embedding at int8 (one f32 scale per d_model channel):
            # the gather dequantizes per row; the readout folds the scale
            # into x so the [V, D] int8 table is the only big operand.
            p = self.param("tok_embed_q8",
                           _q8_init(nn.initializers.normal(stddev=0.02)),
                           (cfg.vocab_size, cfg.d_model))
            x = (p["q"][tokens].astype(self.dtype)
                 * p["scale"].astype(self.dtype))
            # Fold the channel scales into h; the big [V, D] operand stays
            # int8 in HBM (converted per-tile inside the matmul).  The
            # matmul runs in compute dtype (f32 accumulation on the MXU),
            # cast up for the softmax.
            readout = lambda h: (  # noqa: E731
                (h * p["scale"]).astype(self.dtype)
                @ p["q"].astype(self.dtype).T).astype(jnp.float32)
        else:
            emb = self.param("tok_embed",
                             nn.initializers.normal(stddev=0.02),
                             (cfg.vocab_size, cfg.d_model), jnp.float32)
            x = emb[tokens].astype(self.dtype)
            # NB the f32 spelling does NOT cost MXU rate: JAX's default
            # TPU matmul precision executes f32 dots with bf16 operands +
            # f32 accumulation, so this already runs at full MXU speed
            # (measured round 5: an explicit bf16-operand rewrite changed
            # neither step time nor the printed losses).
            readout = lambda h: h @ emb.T  # noqa: E731
        block_cls = nn.remat(DecoderBlock) if self.remat else DecoderBlock
        for i in range(cfg.num_layers):
            x = block_cls(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                          cfg.ffn_hidden, dtype=self.dtype,
                          rope_theta=cfg.rope_theta,
                          lora_rank=self.lora_rank,
                          base_dtype=self.base_dtype,
                          name=f"layer_{i}")(x, positions, segment_ids)
        x = RMSNorm(dtype=self.dtype, name="final_norm")(x)
        # Tied-embedding readout in f32 for stable softmax.
        return readout(x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# BERT encoder
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    num_layers: int = 24
    num_heads: int = 16
    d_model: int = 1024
    ffn_hidden: int = 4096
    max_seq_len: int = 512
    type_vocab_size: int = 2


BERT_LARGE = BertConfig()
BERT_BASE = BertConfig(num_layers=12, num_heads=12, d_model=768,
                       ffn_hidden=3072)
BERT_TINY = BertConfig(vocab_size=256, num_layers=2, num_heads=4,
                       d_model=64, ffn_hidden=128, max_seq_len=128)


class EncoderBlock(nn.Module):
    num_heads: int
    ffn_hidden: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids=None):
        b, t, d = x.shape
        head_dim = d // self.num_heads
        dense = partial(Dense, dtype=self.dtype, use_bias=True)
        ln = partial(nn.LayerNorm, dtype=self.dtype, epsilon=1e-12,
                     param_dtype=jnp.float32)
        # Pre-LN (stability at scale; BERT's published post-LN converges
        # identically with warmup but pre-LN is the TPU-era default).
        h = ln(name="attn_norm")(x)
        q = dense(d, name="wq")(h).reshape(b, t, self.num_heads, head_dim)
        k = dense(d, name="wk")(h).reshape(b, t, self.num_heads, head_dim)
        v = dense(d, name="wv")(h).reshape(b, t, self.num_heads, head_dim)
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=False,
                            segment_ids=segment_ids)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + dense(d, name="wo")(o)
        h = ln(name="mlp_norm")(x)
        h = dense(self.ffn_hidden, name="w_in")(h)
        h = nn.gelu(h, approximate=True)
        return x + dense(d, name="w_out")(h)


class Bert(nn.Module):
    """BERT encoder with MLM + NSP heads (pretraining objective).

    ``remat=True``: see :class:`LlamaLM` -- per-block rematerialization
    for long-sequence / large-batch training.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, token_types=None, *, pack_segment_ids=None):
        # NB ``token_types`` IS what the BERT paper calls "segment ids"
        # (the sentence-A/B embedding); ``pack_segment_ids`` is the
        # attention-isolation input (packing / padding), keyword-only so
        # the two can never be confused positionally.
        cfg = self.config
        b, t = tokens.shape
        if token_types is None:
            token_types = jnp.zeros_like(tokens)
        emb = self.param("tok_embed", nn.initializers.normal(stddev=0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (cfg.max_seq_len, cfg.d_model), jnp.float32)
        typ = self.param("type_embed", nn.initializers.normal(stddev=0.02),
                         (cfg.type_vocab_size, cfg.d_model), jnp.float32)
        x = (emb[tokens] + pos[None, :t] + typ[token_types]).astype(self.dtype)
        x = nn.LayerNorm(dtype=self.dtype, epsilon=1e-12,
                         param_dtype=jnp.float32, name="embed_norm")(x)
        block_cls = nn.remat(EncoderBlock) if self.remat else EncoderBlock
        for i in range(cfg.num_layers):
            x = block_cls(cfg.num_heads, cfg.ffn_hidden,
                          dtype=self.dtype, name=f"layer_{i}")(
                              x, pack_segment_ids)
        x = nn.LayerNorm(dtype=self.dtype, epsilon=1e-12,
                         param_dtype=jnp.float32, name="final_norm")(x)
        # MLM head: transform + tied-embedding readout (f32 softmax input).
        h = Dense(cfg.d_model, use_bias=True, dtype=self.dtype,
                  name="mlm_transform")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.LayerNorm(dtype=self.dtype, epsilon=1e-12,
                         param_dtype=jnp.float32, name="mlm_norm")(h)
        # f32 spelling, full MXU speed: JAX's default TPU matmul
        # precision runs this with bf16 operands + f32 accumulation (see
        # the LlamaLM readout note; verified on-chip round 5).
        mlm_logits = h.astype(jnp.float32) @ emb.T
        # NSP head on [CLS] (position 0).
        cls = jnp.tanh(Dense(cfg.d_model, use_bias=True, dtype=self.dtype,
                             name="pooler")(x[:, 0]))
        nsp_logits = Dense(2, use_bias=True, dtype=self.dtype,
                           name="nsp")(cls).astype(jnp.float32)
        return mlm_logits, nsp_logits


def bert_tp_apply(params, config: BertConfig, tokens, token_types=None, *,
                  axis: str = "model", dtype: Dtype = jnp.float32):
    """Tensor-parallel :class:`Bert` forward over LOCAL param shards.

    The Megatron split of the encoder, as an SPMD function for use inside
    ``jax.shard_map`` over a ``build_3d_mesh`` ``model`` axis: per block,
    ``wq``/``wk``/``wv``/``w_in`` are column shards (heads and the FFN
    hidden split over tp, biases split with them -- the
    ``parallel.tp_param_specs`` layout), ``wo``/``w_out`` row shards
    closing in one psum each, and everything else (embeddings,
    layernorms, the MLM/NSP heads) replicated.  Exactly two allreduces
    per block forward, both of the full ``(b, t, d_model)`` activation;
    numerics match ``Bert.apply`` on the unsharded tree to float
    tolerance.

    ``params`` is the ``Bert.init`` variables dict (``{"params": ...}``)
    as sliced by the spec tree; requires ``num_heads`` and ``ffn_hidden``
    divisible by the tp extent.
    """
    from ..parallel.tp import copy_to_tp, row_parallel

    cfg = config
    p = params["params"]
    b, t = tokens.shape
    if token_types is None:
        token_types = jnp.zeros_like(tokens)

    def ln(x, node):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + 1e-12)
        return (y * node["scale"] + node["bias"]).astype(dtype)

    def dense(x, node):
        return x @ node["kernel"].astype(dtype) + node["bias"].astype(dtype)

    emb = p["tok_embed"]
    x = (emb[tokens] + p["pos_embed"][None, :t]
         + p["type_embed"][token_types]).astype(dtype)
    x = ln(x, p["embed_norm"])
    for i in range(cfg.num_layers):
        blk = p[f"layer_{i}"]
        # copy_to_tp is Megatron's "f": identity forward, one backward
        # psum merging the per-rank partial input cotangents of the
        # column layers it feeds (q/k/v here, w_in below).
        h = copy_to_tp(ln(x, blk["attn_norm"]), axis=axis)
        # Local head count comes off the sliced kernel, not the mesh.
        d_local = blk["wq"]["kernel"].shape[-1]
        head_dim = cfg.d_model // cfg.num_heads
        heads_local = d_local // head_dim
        q = dense(h, blk["wq"]).reshape(b, t, heads_local, head_dim)
        k = dense(h, blk["wk"]).reshape(b, t, heads_local, head_dim)
        v = dense(h, blk["wv"]).reshape(b, t, heads_local, head_dim)
        o = flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=False)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d_local)
        x = x + row_parallel(o, blk["wo"]["kernel"].astype(dtype),
                             blk["wo"]["bias"].astype(dtype), axis=axis)
        h = copy_to_tp(ln(x, blk["mlp_norm"]), axis=axis)
        h = nn.gelu(dense(h, blk["w_in"]), approximate=True)
        x = x + row_parallel(h, blk["w_out"]["kernel"].astype(dtype),
                             blk["w_out"]["bias"].astype(dtype), axis=axis)
    x = ln(x, p["final_norm"])
    h = nn.gelu(dense(x, p["mlm_transform"]), approximate=True)
    h = ln(h, p["mlm_norm"])
    mlm_logits = h.astype(jnp.float32) @ emb.T
    cls = jnp.tanh(dense(x[:, 0], p["pooler"]))
    nsp_logits = dense(cls, p["nsp"]).astype(jnp.float32)
    return mlm_logits, nsp_logits


# ---------------------------------------------------------------------------
# LoRA utilities
# ---------------------------------------------------------------------------


def lora_mask(params) -> Any:
    """Pytree of bools: True only on ``lora_a``/``lora_b`` leaves.

    Use with ``optax.multi_transform`` (adapters -> real optimizer, base
    weights -> ``optax.set_to_zero``) to train only the adapters -- the
    Llama-LoRA workload in BASELINE.json.  Matching by param name mirrors
    how torch LoRA wrappers select ``lora_`` attributes.
    """
    def is_lora(path) -> bool:
        return any(getattr(k, "key", None) in ("lora_a", "lora_b")
                   for k in path)

    return jax.tree_util.tree_map_with_path(
        lambda p, _: is_lora(p), params)


def split_frozen(params, mask=None):
    """Split a params pytree into ``(trainable, frozen)`` by LoRA mask.

    The trainable tree carries ONLY the adapter leaves, so gradients, the
    fused allreduce, and optimizer state never touch the (possibly
    multi-GB) frozen base -- pass both trees to a step built with
    ``make_train_step(..., with_frozen=True)`` and recombine inside the
    loss with :func:`merge_frozen`.
    """
    from flax import traverse_util

    mask = lora_mask(params) if mask is None else mask
    flat_p = traverse_util.flatten_dict(params)
    flat_m = traverse_util.flatten_dict(mask)
    train = {k: v for k, v in flat_p.items() if flat_m[k]}
    frozen = {k: v for k, v in flat_p.items() if not flat_m[k]}
    return (traverse_util.unflatten_dict(train),
            traverse_util.unflatten_dict(frozen))


def merge_frozen(trainable, frozen):
    """Inverse of :func:`split_frozen` (valid inside jit: dict surgery
    only)."""
    from flax import traverse_util

    flat = dict(traverse_util.flatten_dict(frozen))
    flat.update(traverse_util.flatten_dict(trainable))
    return traverse_util.unflatten_dict(flat)


def quantize_frozen_base(params):
    """Convert a trained f32-base LoRA params tree to the ``base_dtype=
    "int8"`` layout: every non-LoRA Dense ``kernel`` becomes ``kernel_q8 =
    {"q": int8, "scale": f32/channel}``, ``tok_embed`` becomes
    ``tok_embed_q8``.  Biases, norm scales, and the LoRA adapters stay
    full precision.  The result loads into a model built with
    ``base_dtype="int8"``."""

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            if k == "kernel":
                out["kernel_q8"] = quantize_int8(v)
            elif k == "tok_embed":
                out["tok_embed_q8"] = quantize_int8(v)
            else:
                out[k] = walk(v)
        return out

    return walk(params)


def merge_lora(params, alpha: float = 16.0):
    """Fold trained adapters into base kernels (inference export).

    Returns a new params pytree where every Dense holding ``lora_a/b`` has
    ``kernel += A @ B * alpha/r`` and the adapter leaves removed.  ``alpha``
    must match the ``lora_alpha`` the model was built with (flax params
    don't carry module attributes, so it can't be recovered from the tree).
    """

    def merge(tree):
        if not isinstance(tree, dict):
            return tree
        if "lora_a" in tree and "kernel" in tree:
            r = tree["lora_a"].shape[1]
            delta = (tree["lora_a"] @ tree["lora_b"]) * (alpha / r)
            out = {k: v for k, v in tree.items()
                   if k not in ("lora_a", "lora_b")}
            out["kernel"] = tree["kernel"] + delta
            return out
        return {k: merge(v) for k, v in tree.items()}

    return merge(params)
