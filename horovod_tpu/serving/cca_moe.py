"""Compressed convolutional attention with a slot state beside the page
pool, and a top-1 router that is an MLP over wide experts: the ZAYA1
block, served.

The third instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`.
One layer is an attention block and then an expert block; there is no
dense feed-forward.  ``x`` is the residual stream (float32), RMSNorm
everywhere, ``E`` the model width, ``H_q`` query and ``H_kv`` key/value
heads of ``d`` columns, ``G = H_q / H_kv``.

Attention block, token ``t``, ``h_t = norm(x_t)``:

1. down: ``q~_t = W_q h_t`` (``H_q d`` wide), ``k~_t = W_k h_t`` (``H_kv
   d``); ``u_t = [q~_t ; k~_t]``, ``H_q + H_kv`` heads of ``d``;
2. convolution 0, depthwise, causal, two taps: ``a_t = w0[0] * u_{t-1} +
   w0[1] * u_t + b0``;
3. convolution 1, a ``[d, d]`` matrix a head and tap: ``c_t^j = W1[0]^j
   a_{t-1}^j + W1[1]^j a_t^j + b1^j``; ``c_t`` splits into ``q^_t`` and
   ``k^_t``;
4. the q-k mean: ``q_t[i] = q^_t[i] + (q~_t[i] + k~_t[i // G]) / 2``;
   ``k_t[j] = k^_t[j] + (mean of group j's q~_t + k~_t[j]) / 2``;
5. a head: ``q <- sqrt(d) q / |q|``, ``k <- tau_j sqrt(d) k / |k|``;
6. RoPE on the first ``rotary_dim`` columns of each head, half against
   half;
7. values with the shift: ``v_t = [W_v1 h_t ; W_v2 h_{t-1}]``, two
   halves of ``H_kv d / 2``;
8. causal softmax attention in that latent, GQA, scale ``1/sqrt(d)``;
   ``W_o`` carries the ``H_q d`` result up to ``E``; ``x <- alpha_a * x
   + that``.

Expert block, layer ``l``, ``g_t = norm(x_t)``: ``r_t^l = W_down g_t +
gamma^l * r_t^{l-1}`` (a second stream, ``router_hidden`` wide, that
crosses the layers beside the residual stream; zero before the first
layer); ``s_t = softmax(W_3 gelu(W_2 gelu(W_1 norm(r_t^l))))`` in
float32; the one expert ``argmax(s_t + bias)``; ``y_t = s_t[e] *
SwiGLU_e(g_t)``; ``x <- alpha_m * x + y_t``.  No shared expert, nothing
dropped (:func:`horovod_tpu.ops.moe.moe_ffn` under
:func:`~horovod_tpu.ops.moe.route_top1`).

What is kept, and where:

* a token a layer caches ``k_t`` after step 6 and ``v_t``: ONE pool row
  ``[k (H_kv d) | v (H_kv d)]`` with no head dim (512 columns at the
  published widths: four 128-lane tiles, 1,024 bytes in bfloat16);
* token ``t + 1`` needs ``u_t``, ``a_t`` and ``W_v2 h_t``, which no page
  holds: the SLOT STATE, ``2 (H_q + H_kv) d + H_kv d / 2`` values a
  layer a slot (``LayerSpec.slot_state``), rounded to the engine's
  ``dtype`` exactly where the prefill rounds them, so that decoding
  after a prefill computes what a longer prefill would.

Prefill runs steps 1-8 over the whole prompt (the shifts are a pad and
a slice; ``flash_attention`` causal, GQA) and hands back the page rows
and the last token's slot state; decode runs them for one token a slot
out of the slot state and ``hvd_cca_decode`` over the page table.  The
tied head reads the embedding's own rows.  The residual stream and the
router's whole path are float32 (top 1 of 16 is discontinuous; the
router's matrices are a thousandth of a layer).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import cca_decode_attention, flash_attention
from ..ops.moe import moe_ffn, route_top1
from . import stepparts
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .layerspec import LayerSpec
from .stepparts import dense_out as _dense_out

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class CcaMoeConfig:
    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int               # query heads of the latent
    num_kv_heads: int
    head_dim: int
    moe_hidden: int              # one expert's SwiGLU
    num_experts: int
    router_hidden: int
    experts_per_token: int = 1
    conv_taps: tuple = (2, 2)    # cca_time0, cca_time1
    rotary_dim: int = 64         # partial_rotary_factor * head_dim
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 8192

    def __post_init__(self):
        if self.experts_per_token != 1 or tuple(self.conv_taps) != (2, 2):
            raise NotImplementedError(
                "this block routes to one expert a token and convolves "
                f"over two tokens; got top {self.experts_per_token}, taps "
                f"{self.conv_taps}")
        if self.num_heads % self.num_kv_heads or self.kv_width % 2 \
                or self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads of {self.head_dim}, {self.rotary_dim} "
                "rotated")

    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Columns of ``u`` and of ``a``: the query and the key latent."""
        return self.q_width + self.kv_width

    @property
    def page_width(self) -> int:
        """Columns of a cached row: the keys and, beside them, the
        values."""
        return 2 * self.kv_width

    @property
    def slot_state_width(self) -> int:
        """``u_t``, ``a_t`` and ``W_v2 h_t``: what token ``t + 1`` reads
        of token ``t`` that is in no page."""
        return 2 * self.conv_width + self.kv_width // 2

    def layer_spec(self) -> LayerSpec:
        cfg = self

        def prefill(params, tokens, **kw):
            return prefill_forward(params, cfg, tokens, **kw)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why_state = ("the slot state (the convolutions' last inputs and "
                     "the shifted value half) is kept of a sequence's "
                     "LAST token only: ")
        return LayerSpec(
            attention="cca",
            page=((cfg.page_width,), None),
            page_holds=("the normalised, rotated keys of every key/value "
                        "head side by side, beside them the values "
                        "(current token's half, previous token's half)",
                        None),
            ffn=("moe",) * cfg.num_layers, tied_head=True,
            max_seq_len=cfg.max_seq_len, tp_page_dim=None,
            prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported={
                "tp": "the key/value latent is two heads and the experts "
                      "are not spread over chips: tp = 1 only",
                "lora": "no adapter banks over the latent's projections",
                "spec_decode": "no verify step: a rejected draft would "
                               "have to roll the slot state back",
                "kv_compress": "no fp8 cold pool for rows with no head "
                               "dim",
                "prefill_chunk": why_state + "a chunk boundary would need "
                                 "it carried into the next chunk's "
                                 "prefill, which takes no past",
                "prefix_cache": why_state + "a matched prefix would have "
                                "to bring the state of ITS last token, and "
                                "no snapshot is kept with a page"},
            step_state=lambda: (jnp.zeros(
                (cfg.num_layers, cfg.num_experts), jnp.int32),),
            publish_state=lambda state: stepparts.publish_routed(state[0]),
            step_tells=("experts_touched", "peak_expert_rows"),
            slot_state=cfg.slot_state_width,
            slot_state_holds="a layer: the convolutions' inputs of the last "
                             "token (u, then a) and its W_v2 h, the next "
                             "token's shifted value half")


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------

# The vectors ``config.json`` does not fix, with the value that leaves
# each out of the mathematics: a program that forgets one computes as if
# it stood at this value.
IDENTITY = {"tau": 1.0, "gamma": 1.0, "attn_alpha": 1.0, "moe_alpha": 1.0,
            "b0": 0.0, "b1": 0.0}


def param_shapes(config: CcaMoeConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``),
    experts stacked ``[num_experts, ...]``."""
    c = config
    d, dh, r = c.d_model, c.head_dim, c.router_hidden
    conv_heads = c.num_heads + c.num_kv_heads

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def kernel(*shape):
        return {"kernel": leaf(*shape)}

    def layer():
        e, f = c.num_experts, c.moe_hidden
        return {
            "attn_norm": {"scale": leaf(d)},
            "attn": {
                "wq": kernel(d, c.q_width), "wk": kernel(d, c.kv_width),
                "wv1": kernel(d, c.kv_width // 2),
                "wv2": kernel(d, c.kv_width // 2),
                "wo": kernel(c.q_width, d),
                "conv0": {"w": leaf(2, c.conv_width),
                          "b0": leaf(c.conv_width)},
                "conv1": {"w": leaf(2, conv_heads, dh, dh),
                          "b1": leaf(c.conv_width)},
                "tau": leaf(c.num_kv_heads)},
            "attn_alpha": leaf(d),
            "moe_norm": {"scale": leaf(d)},
            "moe": {
                "router": {"down": kernel(d, r), "gamma": leaf(r),
                           "norm": {"scale": leaf(r)},
                           "w1": kernel(r, r), "w2": kernel(r, r),
                           "w3": kernel(r, e), "bias": leaf(e)},
                "experts": {"w_gate": leaf(e, d, f), "w_up": leaf(e, d, f),
                            "w_down": leaf(e, f, d)}},
            "moe_alpha": leaf(d)}

    tree = {f"layer_{i}": layer() for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_size, d),
                final_norm={"scale": leaf(d)})
    return {"params": tree}


def init_params(config: CcaMoeConfig, key, dtype=jnp.float32,
                bias_scale: float = 0.02, spread: float = 0.1):
    """Random parameters for tests: kernels normal over the fan-in (the
    stacked experts' and the convolution's matrices too), norms at one,
    the embedding at 0.02, the selection bias at ``bias_scale``, and
    every vector of ``IDENTITY`` ``spread`` off its identity value."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        owner = str(getattr(path[-2], "key", "")) if len(path) > 1 else ""
        k = jax.random.fold_in(key, i)
        n = jax.random.normal(k, s.shape)
        if name == "scale":
            v = jnp.ones(s.shape, jnp.float32)
        elif name in IDENTITY:
            v = IDENTITY[name] + spread * n
        elif name == "bias":
            v = bias_scale * n
        elif name == "tok_embed":
            v = 0.02 * n
        elif owner == "conv0":
            v = n / math.sqrt(2.0)
        elif owner == "conv1":
            v = n / math.sqrt(2.0 * s.shape[-2])
        else:
            v = n / math.sqrt(s.shape[-2])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics.  Rows are ``[..., width]``; "previous" rows are the
# same rows one token earlier (the prefill's shift, the decode's slot
# state).
# ---------------------------------------------------------------------------


def _rope_partial(x, positions, theta: float, rotary: int):
    """RoPE on the first ``rotary`` columns of the last dim, half against
    half; ``positions`` broadcasts against ``x.shape[:-1]``."""
    freqs = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def _conv0(u, u_prev, attn, dtype):
    """Step 2: ``a`` in ``dtype``, as convolution 1 and the next token
    read it."""
    f32 = jnp.float32
    w0 = attn["conv0"]["w"].astype(f32)
    return (w0[0] * u_prev.astype(f32) + w0[1] * u.astype(f32)
            + attn["conv0"]["b0"].astype(f32)).astype(dtype)


def _latent_qk(u, a, a_prev, attn, cfg, positions, dtype):
    """Steps 3-6: from ``u``, ``a`` and the previous token's ``a`` (all
    in ``dtype``), the queries ``[..., H_q, d]`` and keys ``[..., H_kv,
    d]`` attention takes (float32)."""
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = u.shape[:-1]
    f32 = jnp.float32
    w1 = attn["conv1"]["w"].astype(dtype)

    def tap(rows, w):
        return jnp.einsum("...jd,jde->...je",
                          rows.reshape(*lead, hq + hkv, d), w,
                          preferred_element_type=f32)

    c = (tap(a_prev, w1[0]) + tap(a, w1[1])
         + attn["conv1"]["b1"].astype(f32).reshape(hq + hkv, d))
    u32 = u.astype(f32).reshape(*lead, hq + hkv, d)
    q_dn = u32[..., :hq, :].reshape(*lead, hkv, hq // hkv, d)
    k_dn = u32[..., hq:, :]                                   # [., hkv, d]
    q = c[..., :hq, :] + 0.5 * (q_dn + k_dn[..., None, :]).reshape(
        *lead, hq, d)
    k = c[..., hq:, :] + 0.5 * (jnp.mean(q_dn, axis=-2) + k_dn)

    def unit(z):
        return z * (math.sqrt(d) * jax.lax.rsqrt(
            jnp.sum(z * z, axis=-1, keepdims=True) + 1e-12))

    q = unit(q)
    k = unit(k) * attn["tau"].astype(f32)[:, None]
    pos = positions[..., None]
    return (_rope_partial(q, pos, cfg.rope_theta, cfg.rotary_dim),
            _rope_partial(k, pos, cfg.rope_theta, cfg.rotary_dim))


def _router_logits(g32, r_prev, router, cfg):
    """The depth-averaged stream ``r`` and the MLP's scores over the
    experts, all float32 at full precision."""
    f32 = jnp.float32

    def mm(x, node):
        return jnp.matmul(x, node["kernel"].astype(f32), precision=_HI)

    r = mm(g32, router["down"]) + router["gamma"].astype(f32) * r_prev
    z = _rmsnorm(r, router["norm"]["scale"].astype(f32), f32, cfg.rms_eps)
    z = jax.nn.gelu(mm(z, router["w1"]), approximate=False)
    z = jax.nn.gelu(mm(z, router["w2"]), approximate=False)
    return r, mm(z, router["w3"])


def _experts(x, r_prev, blk, cfg, dtype, *, live=None, first_expert=0):
    """The expert block over ``x`` ``[tokens, d]`` (float32): the
    residual's float32 addend (this share's: the held experts ``first ..
    first + held - 1``), the router stream and the per-expert counts."""
    g32 = _rmsnorm(x, blk["moe_norm"]["scale"], jnp.float32, cfg.rms_eps)
    router = blk["moe"]["router"]
    r, logits = _router_logits(g32, r_prev, router, cfg)
    y, counts = moe_ffn(g32.astype(dtype), blk["moe"],
                        route_top1(logits, router["bias"]),
                        num_experts=cfg.num_experts, first=first_expert,
                        with_shared=False, live=live)
    return y, r, counts


def _scaled(x, alpha):
    return x * alpha.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Prefill.
# ---------------------------------------------------------------------------


def _shift(x):
    """Row ``t`` gets row ``t - 1`` (axis 1), row 0 zeros."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))


def prefill_forward(params, config: CcaMoeConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True):
    """Forward a prompt batch ``tokens`` ``[b, t]``; returns ``(logits,
    rows, None, state)``: float32 logits of the LAST row (``[b, 1,
    vocab]``; every row with ``last_only=False``), what the cache holds
    of the prompt ``[num_layers, b, t, page_width]``, None for the
    second pool this model does not keep, and the slot state once the
    last token is in, ``[num_layers, b, slot_state_width]``."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "this prefill takes neither adapter banks nor a continuation "
            "from cached rows (the slot state is of the last token only)")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = stepparts.embed(p, tokens)
    r = jnp.zeros((b * t, cfg.router_hidden), jnp.float32)
    @one_trace
    def layer(x, r, blk, positions):
        attn = blk["attn"]
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        u = jnp.concatenate([_dense(h, attn["wq"], dtype),
                             _dense(h, attn["wk"], dtype)], axis=-1)
        a = _conv0(u, _shift(u), attn, dtype)
        q, k = _latent_qk(u, a, _shift(a), attn, cfg, positions, dtype)
        v2 = _dense(h, attn["wv2"], dtype)
        v = jnp.concatenate([_dense(h, attn["wv1"], dtype), _shift(v2)],
                            axis=-1)
        k = k.astype(dtype)
        row = jnp.concatenate([k.reshape(b, t, cfg.kv_width), v], axis=-1)
        last = jnp.concatenate([u[:, -1], a[:, -1], v2[:, -1]], axis=-1)
        o = flash_attention(
            q.astype(dtype).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim).transpose(
                0, 2, 1, 3),
            causal=True, scale=cfg.head_dim ** -0.5)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_width)
        x = _scaled(x, blk["attn_alpha"]) + _dense_out(o, attn["wo"], dtype)
        y, r, _ = _experts(x.reshape(b * t, -1), r, blk, cfg, dtype)
        return _scaled(x, blk["moe_alpha"]) + y.reshape(b, t, -1), r, row, \
            last

    rows, state = [], []
    for li in range(cfg.num_layers):
        x, r, row, last = layer(x, r, p[f"layer_{li}"], positions)
        rows.append(row)
        state.append(last)
    if last_only:
        x = x[:, -1:]
    return (stepparts.readout(x, p, cfg.rms_eps, dtype, tied=True),
            jnp.stack(rows), None, jnp.stack(state))


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


def build_decode_step(config: CcaMoeConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step.

    Signature of the returned step::

        logits, pool, None, slot_state, routed, told = step(
            params, pool, None, tokens, positions, page_table, active,
            slot_state, routed, prev)

    as ``mla_moe.build_decode_step``'s with one more operand before
    ``routed``: ``slot_state`` ``[layers, slots, slot_state_width]``, of
    which the step reads every live slot's row (the token before) and
    writes this token's in its place; an idle slot's row is left as it
    is.  ``told`` ends in ``[experts_touched, peak_expert_rows]``.  The
    step CONSUMES ``pool``, ``slot_state`` and ``routed``.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "compressed-convolutional-attention", mesh, width=width,
        with_lora=with_lora, compress=compress)
    cw, hq, d = cfg.conv_width, cfg.num_heads, cfg.head_dim

    def layer(li, blk, x, pool, carried, r, rnd):
        state, = carried
        s = x.shape[0]
        attn = blk["attn"]
        was = state[li].astype(dtype)                            # [S, W]
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        u = jnp.concatenate([_dense(h, attn["wq"], dtype),
                             _dense(h, attn["wk"], dtype)], axis=-1)
        a = _conv0(u, was[:, :cw], attn, dtype)
        q, k = _latent_qk(u, a, was[:, cw:2 * cw], attn, cfg,
                          rnd.positions, dtype)
        v2 = _dense(h, attn["wv2"], dtype)
        row = jnp.concatenate(
            [k.astype(dtype).reshape(s, cfg.kv_width),
             _dense(h, attn["wv1"], dtype), was[:, 2 * cw:]], axis=-1)
        pool = pool.at[li, rnd.page, rnd.off].set(row.astype(pool.dtype))
        # An idle slot's row stays as it is: cleared, or mid-prefill.
        state = state.at[li].set(jnp.where(
            rnd.active[:, None], jnp.concatenate([u, a, v2], axis=-1), was
        ).astype(state.dtype))
        o = cca_decode_attention(
            q.astype(dtype), pool, rnd.page_table, layer=li,
            lengths=rnd.lengths, kv_heads=cfg.num_kv_heads,
            scale=d ** -0.5)
        x = _scaled(x, blk["attn_alpha"]) + _dense_out(
            o.reshape(s, hq * d), attn["wo"], dtype)
        y, r, counts = _experts(x, r, blk, cfg, dtype, live=rnd.active)
        return (_scaled(x, blk["moe_alpha"]) + y, pool, (state,), r, li,
                counts)

    return stepparts.build_one_chip_step(
        "cca_moe_step", layer, num_layers=cfg.num_layers,
        eps=cfg.rms_eps, tied=True, page_size=page_size,
        scratch=slots * pages_per_slot, dtype=dtype,
        tells=("experts_touched", "peak_expert_rows"), carried=1,
        meta={"arch": "cca_moe", "d_model": cfg.d_model,
              "slots": int(slots)},
        # The router's stream: born and spent within a round.
        local=lambda x: jnp.zeros((x.shape[0], cfg.router_hidden),
                                  jnp.float32))
