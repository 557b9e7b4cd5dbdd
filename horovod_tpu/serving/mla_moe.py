"""Latent attention over a latent page pool and routed experts beside a
shared one: the DeepSeek-V3 block, served.

The second instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`
(a ``LlamaConfig`` is the first).  One layer, as published (``x`` the
residual stream, RMSNorm everywhere, no biases):

* attention: ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` in heads of
  ``nope + rope`` columns; ``h W_kva`` gives the latent ``c_kv`` (normed)
  and ONE rotated key ``k_pe`` shared by every head; ``c_kv W_kvb`` gives
  each head's ``k_nope`` and ``v``.  RoPE is the interleaved kind: the
  rope columns are (even, odd) pairs, de-interleaved and then rotated
  half against half.
* the cache holds, a token a layer, ``c_kv`` and the rotated ``k_pe``
  side by side in one row and nothing else: ONE pool, no second.  The
  row is padded with zeros to whole 128-lane tiles (576 values in 640
  columns: what the TPU's tiled layout stores for a 576-wide row in any
  case), so that a page is one aligned slab a kernel can copy.  No
  per-head K or V is ever written.
* prefill takes the EXPANDED path (per-head keys and values through
  ``flash_attention``, the values at their own narrower width);
  decode takes the ABSORBED path (``q_nope`` carried into the latent
  space, ``hvd_mla_decode`` over the latents, the result carried out
  through ``W_kvb``'s value half) -- the same mathematics.
* feed-forward: SwiGLU in the leading dense layers, then
  :func:`horovod_tpu.ops.moe.moe_ffn` under
  :func:`~horovod_tpu.ops.moe.route` (sigmoid router, ``top_k`` of all
  the experts by score + bias, nothing dropped, a shared expert).
* an untied head; the prefill reads out its last row only.
* the residual stream is float32: every matmul takes operands in the
  engine's ``dtype`` (bfloat16 on the chip) and accumulates in float32,
  a branch's result is added unrounded, and the router reads the
  normalised row before it is rounded.  Routing is discontinuous (the
  8th and 9th score of 256 lie 0.006 apart), so what the residual
  stream loses to rounding comes back as flipped experts.

What these programs do not do is refused by name when the engine is
built (``LayerSpec.unsupported``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import flash_attention, mla_decode_attention
from ..ops import moe as _moe
from . import stepparts
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .layerspec import LayerSpec
from .stepparts import dense_out as _dense_out, lane_pad as _lane_pad

@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_hidden: int              # the leading dense layers' SwiGLU
    moe_hidden: int              # one expert's SwiGLU
    num_experts: int
    experts_per_token: int
    num_shared_experts: int = 1
    first_dense_layers: int = 1
    routed_scale: float = 1.0
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 8192

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_dense_layers

    @property
    def page_width(self) -> int:
        """Columns of a cached row: latent and rotated key, padded to
        whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_dense_layers

    def layer_spec(self) -> LayerSpec:
        cfg = self

        def prefill(params, tokens, **kw):
            return prefill_forward(params, cfg, tokens, **kw)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why_page = ("the latent page (one shared key a token) has no "
                    "program for it yet")
        return LayerSpec(
            attention="mla",
            page=((cfg.page_width,), None),
            page_holds=("the normalised latent c_kv, beside it the rotated "
                        "shared key k_pe, zeros to the lane tile", None),
            ffn=tuple("moe" if cfg.is_moe(i) else "dense"
                      for i in range(cfg.num_layers)),
            tied_head=False, max_seq_len=cfg.max_seq_len,
            tp_page_dim=None, prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported={
                "tp": "the latent is one vector for all heads and the "
                      "experts are not spread over chips: tp = 1 only",
                "lora": "no adapter banks over the low-rank projections",
                "spec_decode": "no verify step over latent pages: "
                               + why_page,
                "kv_compress": "no fp8 cold pool for latent pages: "
                               + why_page,
                "prefill_chunk": "no prefill continuation from cached "
                                 "latents: " + why_page,
                "prefix_cache": "a prefix hit prefills its tail as a "
                                "continuation from cached latents: "
                                + why_page},
            step_state=lambda: (jnp.zeros(
                (cfg.moe_layers, cfg.num_experts), jnp.int32),),
            publish_state=lambda state: stepparts.publish_routed(state[0]),
            step_tells=("experts_touched",))


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------


def param_shapes(config: MlaMoeConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``),
    experts stacked ``[num_experts, ...]``: what a seeded generator fills
    leaf by leaf."""
    c = config
    d, h = c.d_model, c.num_heads

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def kernel(*shape):
        return {"kernel": leaf(*shape)}

    def swiglu(f):
        return {"w_gate": kernel(d, f), "w_up": kernel(d, f),
                "w_down": kernel(f, d)}

    def layer(i):
        out = {
            "attn_norm": {"scale": leaf(d)},
            "attn": {
                "q_a": kernel(d, c.q_lora_rank),
                "q_a_norm": {"scale": leaf(c.q_lora_rank)},
                "q_b": kernel(c.q_lora_rank, h * (c.qk_nope_head_dim
                                                  + c.qk_rope_head_dim)),
                "kv_a": kernel(d, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_a_norm": {"scale": leaf(c.kv_lora_rank)},
                "kv_b": kernel(c.kv_lora_rank, h * (c.qk_nope_head_dim
                                                    + c.v_head_dim)),
                "wo": kernel(h * c.v_head_dim, d)},
            "mlp_norm": {"scale": leaf(d)}}
        if c.is_moe(i):
            e, f = c.num_experts, c.moe_hidden
            out["moe"] = {
                "router": {"kernel": leaf(d, e),
                           "e_score_correction_bias": leaf(e)},
                "experts": {"w_gate": leaf(e, d, f), "w_up": leaf(e, d, f),
                            "w_down": leaf(e, f, d)},
                "shared": swiglu(f * c.num_shared_experts)}
        else:
            out["mlp"] = swiglu(c.ffn_hidden)
        return out

    tree = {f"layer_{i}": layer(i) for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_size, d),
                final_norm={"scale": leaf(d)},
                lm_head=kernel(d, c.vocab_size))
    return {"params": tree}


def init_params(config: MlaMoeConfig, key, dtype=jnp.float32,
                bias_scale: float = 0.1):
    """Random parameters for tests: normal over the fan-in (the stacked
    experts' too), norms at one, the embedding at 0.02, the selection
    bias at ``bias_scale``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "scale":
            v = jnp.ones(s.shape, jnp.float32)
        elif name == "e_score_correction_bias":
            v = bias_scale * jax.random.normal(k, s.shape)
        elif name == "tok_embed":
            v = 0.02 * jax.random.normal(k, s.shape)
        else:
            v = jax.random.normal(k, s.shape) / math.sqrt(s.shape[-2])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics.
# ---------------------------------------------------------------------------


def _rope_interleaved(x, positions, theta: float):
    """RoPE over the last dim of ``x``, whose columns are (even, odd)
    pairs: de-interleave, then rotate half against half.  ``positions``
    broadcasts against ``x.shape[:-1]``.  The result keeps the
    de-interleaved order (queries and keys alike)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(h, node, dtype):
    gate = _dense(h, node["w_gate"], dtype)
    up = _dense(h, node["w_up"], dtype)
    return _dense_out(jax.nn.silu(gate) * up, node["w_down"], dtype)


def _queries(h, attn, cfg, dtype):
    """``h`` ``[..., d]`` -> per-head queries ``[..., heads, nope + rope]``
    (not yet rotated)."""
    cq = _rmsnorm(_dense(h, attn["q_a"], dtype),
                  attn["q_a_norm"]["scale"], dtype, cfg.rms_eps)
    q = _dense(cq, attn["q_b"], dtype)
    return q.reshape(*h.shape[:-1], cfg.num_heads,
                     cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _latents(h, attn, cfg, positions, dtype):
    """``h`` ``[..., d]`` -> what the cache holds of these tokens, one
    row ``[..., page_width]`` each: the normalised latent, beside it the
    rotated shared key, zeros after."""
    kva = _dense(h, attn["kv_a"], dtype)
    r = cfg.kv_lora_rank
    c = _rmsnorm(kva[..., :r], attn["kv_a_norm"]["scale"], dtype,
                 cfg.rms_eps)
    k_pe = _rope_interleaved(kva[..., r:], positions, cfg.rope_theta)
    return _lane_pad(jnp.concatenate([c, k_pe], axis=-1), cfg.page_width)


def _ffn(x, blk, cfg, li, dtype, *, live=None, first_expert=0,
         with_shared=True):
    """The layer's feed-forward over ``x`` ``[tokens, d]`` (float32);
    returns the residual's float32 addend and, for a routed layer, the
    per-expert counts."""
    h32 = _rmsnorm(x, blk["mlp_norm"]["scale"], jnp.float32, cfg.rms_eps)
    h = h32.astype(dtype)
    if not cfg.is_moe(li):
        return _swiglu(h, blk["mlp"], dtype), None
    router = blk["moe"]["router"]
    # The router is looked up where it lives at call time: the benchmark's
    # tests put a faulty one in its place there.
    routing = _moe.route(h32, router["kernel"],
                         router["e_score_correction_bias"],
                         top_k=cfg.experts_per_token,
                         scale=cfg.routed_scale)
    return _moe.moe_ffn(h, blk["moe"], routing,
                        num_experts=cfg.num_experts, first=first_expert,
                        with_shared=with_shared, live=live)


# ---------------------------------------------------------------------------
# Prefill: the expanded path.
# ---------------------------------------------------------------------------


def prefill_forward(params, config: MlaMoeConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True):
    """Forward a prompt batch ``tokens`` ``[b, t]``; returns ``(logits,
    rows, None)``: float32 logits of the LAST row (``[b, 1, vocab]``;
    every row with ``last_only=False``: all rows of a long prompt over a
    wide vocabulary are gigabytes), what the cache holds of the prompt,
    ``[num_layers, b, t, page_width]``, and None for the second pool
    this model does not keep."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "latent-attention prefill takes neither adapter banks nor a "
            "continuation from cached latents")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dr, heads = cfg.qk_rope_head_dim, cfg.num_heads
    x = p["tok_embed"][tokens].astype(jnp.float32)
    # A layer of each kind of feed-forward, to ask ``cfg`` about the kind.
    of_kind = {cfg.is_moe(li): li for li in reversed(range(cfg.num_layers))}

    @one_trace
    def layer(x, blk, positions):
        attn = blk["attn"]
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        q = _queries(h, attn, cfg, dtype)
        row = _latents(h, attn, cfg, positions, dtype)
        c, k_pe = row[..., :r], row[..., r:r + dr]
        q = jnp.concatenate(
            [q[..., :dn], _rope_interleaved(q[..., dn:],
                                            positions[..., None],
                                            cfg.rope_theta)], axis=-1)
        kv = _dense(c, attn["kv_b"], dtype).reshape(b, t, heads, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                k_pe[:, :, None, :], (b, t, heads, k_pe.shape[-1]))],
            axis=-1)
        # The values go in at their own width, narrower than the keys'.
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            kv[..., dn:].transpose(0, 2, 1, 3), causal=True,
            scale=cfg.softmax_scale)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, heads * dv)
        x = x + _dense_out(o, attn["wo"], dtype)
        y, _ = _ffn(x.reshape(b * t, -1), blk, cfg, of_kind["moe" in blk],
                    dtype)
        return x + y.reshape(b, t, -1), row

    rows = []
    for li in range(cfg.num_layers):
        x, row = layer(x, p[f"layer_{li}"], positions)
        rows.append(row)
    if last_only:
        x = x[:, -1:]
    return (stepparts.readout(x, p, cfg.rms_eps, dtype, tied=False),
            jnp.stack(rows), None)


# ---------------------------------------------------------------------------
# Decode: the absorbed path.
# ---------------------------------------------------------------------------


def build_decode_step(config: MlaMoeConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step.

    Signature of the returned step::

        logits, pool, None, routed, told = step(
            params, pool, None, tokens, positions, page_table, active,
            routed, prev)

    as ``decode.build_decode_step``'s (the second pool's place is None:
    this model keeps one), with one more operand before ``prev``:
    ``routed`` (``[moe layers, experts]`` int32, the running histogram
    of (token, choice) pairs).  ``told`` (``[tokens | finite | tells]``,
    ``stepparts.build_one_chip_step``) ends in ``experts_touched``:
    experts, summed over the routed layers, that at least one live slot
    chose this round.  The step CONSUMES ``pool`` and ``routed``: both
    are donated and their successors returned.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "latent-attention", mesh, width=width, with_lora=with_lora,
        compress=compress)
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    heads = cfg.num_heads

    def layer(li, blk, x, pool, carried, local, rnd):
        s = x.shape[0]
        attn = blk["attn"]
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        q = _queries(h, attn, cfg, dtype)                        # [S, H, .]
        row = _latents(h, attn, cfg, rnd.positions, dtype)
        pool = pool.at[li, rnd.page, rnd.off].set(row.astype(pool.dtype))
        q_pe = _rope_interleaved(q[..., dn:], rnd.positions[:, None],
                                 cfg.rope_theta)
        w_kvb = attn["kv_b"]["kernel"].astype(dtype).reshape(
            r, heads, dn + dv)
        q_lat = jnp.einsum("shn,rhn->shr", q[..., :dn],
                           w_kvb[..., :dn]).astype(dtype)
        o_lat = mla_decode_attention(
            _lane_pad(jnp.concatenate([q_lat, q_pe], axis=-1),
                      cfg.page_width), pool, rnd.page_table,
            layer=li, lengths=rnd.lengths, value_dim=r,
            scale=cfg.softmax_scale)
        o = jnp.einsum("shr,rhv->shv", o_lat.astype(dtype),
                       w_kvb[..., dn:]).astype(dtype)
        x = x + _dense_out(o.reshape(s, heads * dv), attn["wo"], dtype)
        y, counts = _ffn(x, blk, cfg, li, dtype, live=rnd.active)
        return (x + y, pool, carried, local, li - cfg.first_dense_layers,
                counts)

    return stepparts.build_one_chip_step(
        "mla_moe_step", layer, num_layers=cfg.num_layers, eps=cfg.rms_eps,
        tied=False, page_size=page_size, scratch=slots * pages_per_slot,
        dtype=dtype, tells=("experts_touched",), carried=0,
        meta={"arch": "mla_moe", "d_model": cfg.d_model,
              "slots": int(slots)})
