"""A state-space mixer beside attention in every layer, both reading one
normed input and both added to the residual: the Falcon-H1
(``falcon_h1``) block, served.

The sixth instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`,
and the first whose slot state is a RECURRENCE's: float32, megabytes a
slot a layer (more than the slot's pages), advanced whole by every decode
round (:func:`horovod_tpu.ops.ssm.ssm_decode_update`, in place) and made
by the prefill as a chunked scan (:func:`horovod_tpu.ops.ssm.ssm_scan`).
``x`` is the residual stream (float32), RMSNorm (``rms_eps``)
everywhere, no bias but the convolution's.  EVERY product carries one of
the config's fourteen multipliers (``*_multiplier``, two
``mlp_multipliers``, five ``ssm_multipliers``).  ``x0 = E[token] *
embedding_multiplier``.  A layer:

1. ``u = norm_in(x)``.
2. Attention: ``q = (u * attention_in_multiplier) W_q`` in ``num_heads``
   heads of ``head_dim``, ``k = ((u * attention_in_multiplier) W_k) *
   key_multiplier`` and ``v = (u * attention_in_multiplier) W_v`` in
   ``num_kv_heads``; ``q`` and ``k`` rotated by RoPE (``rope_theta``,
   half against half over all ``head_dim`` columns); causal softmax,
   scale ``1 / sqrt(head_dim)``, query head ``i`` reading key/value head
   ``i // (num_heads / num_kv_heads)``; ``a = (attn W_o) *
   attention_out_multiplier``.
3. Mixer (Mamba-2): ``zxbcdt = ((u * ssm_in_multiplier) W_in) * m``, laid
   out ``[z d_ssm | x d_ssm | B groups * n | C groups * n | dt
   ssm_heads]``, ``m`` the vector that holds ``ssm_multipliers[0..4]``
   over those five spans.  A depthwise causal convolution of
   ``conv_taps`` taps, with bias, over the columns ``[x | B | C]``
   (zeros before the sequence), then SiLU.  ``x`` is ``ssm_heads`` heads
   of ``ssm_head_dim`` (``p``), ``B`` and ``C`` are ``ssm_groups`` groups
   of ``ssm_state`` (``n``), head ``h`` reading group ``h // (ssm_heads /
   ssm_groups)``.  ``dt = softplus(dt_raw + dt_bias)``, ``A =
   -exp(A_log)``, both a head.  A head's state ``H`` (``n x p``): ``H_t =
   exp(dt_t A) H_(t-1) + dt_t * B_t (outer) x_t``, ``y_t = C_t H_t + D
   x_t``.  Then the gate BEFORE the norm: ``y = norm_grouped(y *
   silu(z); w_norm)``, normalised within each of the ``ssm_groups``
   groups of ``d_ssm / ssm_groups`` columns; ``s = (y W_out) *
   ssm_out_multiplier``.
4. ``x = x + a + s``.
5. ``v = norm_ff(x)``; ``x = x + ((silu((v W_gate) * mlp_multipliers[0])
   * (v W_up)) W_down) * mlp_multipliers[1]``.

``logits = (norm_final(x) W_head) * lm_head_multiplier``: an untied
head.

What is kept, and where.  A token a layer caches ``k`` (multiplied and
rotated) and ``v``, one row of ``num_kv_heads * head_dim`` columns in
each of two pools, read by ``hvd_cca_decode`` over the page table.  A
SLOT a layer keeps ``[H of every head, n x p each, the head's columns
last | the convolution's last conv_taps - 1 inputs, a tap a row]``:
``ssm_heads * n * p + (conv_taps - 1) * conv_width`` float32 values
(``LayerSpec.slot_state``; 1,063,936 at the published widths, 4.26 MB:
four hundred of a slot's tokens' pages).  The prefill runs the recurrence
as a scan in chunks of ``scan_chunk`` tokens and hands back the state
once the prompt's last token is in; a decode round reads every live
slot's ``H`` once and writes it once, in place (``hvd_ssm_decode``), and
shifts the convolution's rows.  The state is float32 whatever the
engine's ``dtype``: a recurrence rounds what it keeps again every token.

Departures: the residual stream is float32 (operands in the engine's
``dtype``, float32 accumulation, branch results added unrounded); the
mixer's projection, convolution, ``dt`` and recurrence are float32 from
the projection's float32 result on; the prefill reads out its last row
only.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import cca_decode_attention, flash_attention
from ..ops.ssm import ssm_decode_update, ssm_scan
from . import stepparts
from .cca_moe import _rope_partial
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .layerspec import FEATURES, LayerSpec
from .stepparts import dense_out as _dense_out


@dataclasses.dataclass(frozen=True)
class SsmHybridConfig:
    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_hidden: int
    ssm_heads: int
    ssm_head_dim: int            # p: a head's columns
    ssm_state: int               # n: the state's size a head's column
    ssm_groups: int              # B and C come a group, heads share them
    conv_taps: int = 4
    scan_chunk: int = 128
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    # The fourteen multipliers (1: the product as it stands).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)        # gate, down
    ssm_multipliers: tuple = (1.0,) * 5        # z, x, B, C, dt

    def __post_init__(self):
        object.__setattr__(self, "mlp_multipliers",
                           tuple(float(m) for m in self.mlp_multipliers))
        object.__setattr__(self, "ssm_multipliers",
                           tuple(float(m) for m in self.ssm_multipliers))
        if self.num_heads % self.num_kv_heads or self.head_dim % 2 \
                or self.ssm_heads % self.ssm_groups \
                or self.d_ssm % self.ssm_groups or self.conv_taps < 2 \
                or len(self.mlp_multipliers) != 2 \
                or len(self.ssm_multipliers) != 5:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} of "
                f"{self.head_dim}; {self.ssm_heads} mixer heads in "
                f"{self.ssm_groups} groups, {self.conv_taps} taps, "
                f"multipliers {self.mlp_multipliers} and "
                f"{self.ssm_multipliers}")

    @property
    def kv_width(self) -> int:
        """Columns of a cached row, in each pool."""
        return self.num_kv_heads * self.head_dim

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def bc_width(self) -> int:
        """Columns of ``B``, and of ``C``."""
        return self.ssm_groups * self.ssm_state

    @property
    def conv_width(self) -> int:
        """Columns the convolution runs over: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.bc_width

    @property
    def in_width(self) -> int:
        """Columns of the mixer's projection: ``[z | x | B | C | dt]``."""
        return 2 * self.d_ssm + 2 * self.bc_width + self.ssm_heads

    @property
    def state_width(self) -> int:
        """Values of every head's ``H``, a slot a layer."""
        return self.ssm_heads * self.ssm_state * self.ssm_head_dim

    @property
    def slot_state_width(self) -> int:
        """``H`` and, behind it, the convolution's last ``conv_taps - 1``
        inputs."""
        return self.state_width + (self.conv_taps - 1) * self.conv_width

    def layer_spec(self) -> LayerSpec:
        cfg = self

        def prefill(params, tokens, **kw):
            return prefill_forward(params, cfg, tokens, **kw)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why = ("the recurrent state (megabytes a slot a layer, float32) is "
               "kept of a sequence's LAST token only: ")
        reasons = {
            "tp": "the state and the mixer's heads are not spread over "
                  "chips: tp = 1 only",
            "lora": "no adapter banks over these projections",
            "spec_decode": why + "a rejected draft would have to roll it "
                           "back, and no verify step keeps a copy",
            "kv_compress": "no fp8 cold pool for rows with no head dim",
            "prefill_chunk": why + "a chunk boundary would need it carried "
                             "into the next chunk's prefill, which takes "
                             "no past",
            "prefix_cache": why + "a matched prefix would have to bring the "
                            "state of ITS last token, and no snapshot is "
                            "kept with a page",
            "handoff": why + "the KV plane ships whole planes of whole "
                       "pages and no slot's row"}
        assert set(reasons) == set(FEATURES)
        return LayerSpec(
            attention="gqa",
            page=((cfg.kv_width,), (cfg.kv_width,)),
            page_holds=("the keys of every key/value head side by side, "
                        "multiplied and rotated", "the values"),
            ffn=("dense",) * cfg.num_layers, tied_head=False,
            max_seq_len=cfg.max_seq_len, tp_page_dim=None,
            prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported=reasons,
            slot_state=cfg.slot_state_width,
            slot_state_holds="a layer: every mixer head's recurrent state "
                             f"H ({cfg.ssm_state} x {cfg.ssm_head_dim}, the "
                             "head's columns last) and, behind them, the "
                             f"convolution's last {cfg.conv_taps - 1} "
                             "inputs over [x | B | C], a tap a row",
            slot_state_dtype="float32", slot_state_step=cfg.state_width,
            scan_chunk=cfg.scan_chunk)


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------


def param_shapes(config: SsmHybridConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``)."""
    c = config
    d = c.d_model

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def kernel(*shape):
        return {"kernel": leaf(*shape)}

    def layer():
        return {
            "in_norm": {"scale": leaf(d)},
            "attn": {"wq": kernel(d, c.num_heads * c.head_dim),
                     "wk": kernel(d, c.kv_width),
                     "wv": kernel(d, c.kv_width),
                     "wo": kernel(c.num_heads * c.head_dim, d)},
            "ssm": {"w_in": kernel(d, c.in_width),
                    "conv": {"w": leaf(c.conv_taps, c.conv_width),
                             "bias": leaf(c.conv_width)},
                    "A_log": leaf(c.ssm_heads), "D": leaf(c.ssm_heads),
                    "dt_bias": leaf(c.ssm_heads),
                    "norm": {"scale": leaf(c.d_ssm)},
                    "w_out": kernel(c.d_ssm, d)},
            "ffn_norm": {"scale": leaf(d)},
            "mlp": {"w_gate": kernel(d, c.ffn_hidden),
                    "w_up": kernel(d, c.ffn_hidden),
                    "w_down": kernel(c.ffn_hidden, d)}}

    tree = {f"layer_{i}": layer() for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_size, d),
                final_norm={"scale": leaf(d)},
                lm_head=kernel(d, c.vocab_size))
    return {"params": tree}


def init_params(config: SsmHybridConfig, key, dtype=jnp.float32):
    """Random parameters for tests: kernels normal over the fan-in, the
    embedding at 0.02, every norm's scale and ``D`` 0.1 off one, the
    convolution's bias 0.1 off zero, ``A`` uniform in 1..16 and ``dt``
    log-uniform in 0.001..0.1 (Mamba-2's own initialisation)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        n = jax.random.normal(k, s.shape)
        u = jax.random.uniform(k, s.shape)
        if name in ("scale", "D"):
            v = 1.0 + 0.1 * n
        elif name == "bias":
            v = 0.1 * n
        elif name == "A_log":
            v = jnp.log(1.0 + 15.0 * u)
        elif name == "dt_bias":
            dt = jnp.exp(np.log(1e-3) + u * np.log(1e2))
            v = dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
        elif name == "tok_embed":
            v = 0.02 * n
        else:
            v = n / np.sqrt(s.shape[0])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics: rows are ``[..., width]``.
# ---------------------------------------------------------------------------


def _spans(cfg):
    """Where ``[z | x | B | C | dt]`` lie in the projection's columns."""
    edges = np.cumsum([0, cfg.d_ssm, cfg.d_ssm, cfg.bc_width, cfg.bc_width,
                       cfg.ssm_heads])
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]


def _normed(x, blk, cfg):
    """``norm_in(x)`` in float32: both mixers' input, each multiplier
    still to come."""
    return _rmsnorm(x, blk["in_norm"]["scale"], jnp.float32, cfg.rms_eps)


def _qkv(u32, attn, cfg, positions, dtype):
    """Queries ``[..., heads, head_dim]`` and keys ``[..., kv_heads,
    head_dim]``, rotated (the keys multiplied first), in ``dtype``, and
    the values' row ``[..., kv_width]``."""
    lead, dh = u32.shape[:-1], cfg.head_dim
    h = (u32 * cfg.attention_in_multiplier).astype(dtype)

    def heads(node, n, mult):
        # Float32 out of the product: rounded once, after the rotation.
        z = _dense_out(h, node, dtype).reshape(*lead, n, dh) * mult
        return _rope_partial(z, positions[..., None], cfg.rope_theta,
                             dh).astype(dtype)

    return (heads(attn["wq"], cfg.num_heads, 1.0),
            heads(attn["wk"], cfg.num_kv_heads, cfg.key_multiplier),
            _dense(h, attn["wv"], dtype))


def _projected(u32, ssm, cfg, dtype):
    """The mixer's projection with its five multipliers, float32: ``(z,
    xbc, dt_raw)``."""
    m = np.ones((cfg.in_width,), np.float32)
    for (a, b), mult in zip(_spans(cfg), cfg.ssm_multipliers):
        m[a:b] = mult
    zxbcdt = _dense_out((u32 * cfg.ssm_in_multiplier).astype(dtype),
                        ssm["w_in"], dtype) * m
    (_, z1), _, _, (_, c1), _ = _spans(cfg)
    return zxbcdt[..., :z1], zxbcdt[..., z1:c1], zxbcdt[..., c1:]


def _convolved(inputs, ssm):
    """``silu(conv)`` over the convolution's ``taps`` inputs ``[...,
    conv_width]`` each, the oldest first, the newest the row's own."""
    f32 = jnp.float32
    w = ssm["conv"]["w"].astype(f32)
    return jax.nn.silu(sum(w[i] * z for i, z in enumerate(inputs))
                       + ssm["conv"]["bias"].astype(f32))


def _split(act, cfg):
    """``silu(conv)`` -> ``x`` ``[..., heads, p]``, ``B`` and ``C``
    ``[..., groups, n]``."""
    lead = act.shape[:-1]
    d, bc = cfg.d_ssm, cfg.bc_width
    return (act[..., :d].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            act[..., d:d + bc].reshape(*lead, cfg.ssm_groups, cfg.ssm_state),
            act[..., d + bc:].reshape(*lead, cfg.ssm_groups, cfg.ssm_state))


def _dt(dt_raw, ssm):
    return jax.nn.softplus(dt_raw + ssm["dt_bias"].astype(jnp.float32))


def _gated_out(y, z, ssm, cfg, dtype):
    """``(norm_grouped(y * silu(z)) W_out) * ssm_out_multiplier``: the
    gate before the norm, the norm within each group's columns."""
    lead = y.shape[:-1]
    g = (y * jax.nn.silu(z)).reshape(*lead, cfg.ssm_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.rms_eps)
    g = g.reshape(*lead, cfg.d_ssm) * ssm["norm"]["scale"].astype(
        jnp.float32)
    return _dense_out(g, ssm["w_out"], dtype) * cfg.ssm_out_multiplier


def _feed_forward(x, blk, cfg, dtype):
    v = _rmsnorm(x, blk["ffn_norm"]["scale"], dtype, cfg.rms_eps)
    mlp = blk["mlp"]
    gate = _dense_out(v, mlp["w_gate"], dtype) * cfg.mlp_multipliers[0]
    up = _dense_out(v, mlp["w_up"], dtype)
    return _dense_out(jax.nn.silu(gate) * up, mlp["w_down"],
                      dtype) * cfg.mlp_multipliers[1]


def _embed(p, tokens, cfg):
    return stepparts.embed(p, tokens) * cfg.embedding_multiplier


def _readout(x, p, cfg, dtype):
    return stepparts.readout(x, p, cfg.rms_eps, dtype,
                             tied=False) * cfg.lm_head_multiplier


# ---------------------------------------------------------------------------
# Prefill.
# ---------------------------------------------------------------------------


def prefill_forward(params, config: SsmHybridConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True):
    """Forward a prompt batch ``tokens`` ``[b, t]``; returns ``(logits,
    keys, values, state)``: float32 logits of the LAST row (``[b, 1,
    vocab]``; every row with ``last_only=False``), the rows of each pool
    ``[num_layers, b, t, kv_width]`` and the slot state once the last
    token is in, ``[num_layers, b, slot_state_width]`` float32."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "this prefill takes neither adapter banks nor a continuation "
            "from cached rows (the recurrent state is of the last token "
            "only)")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = _embed(p, tokens, cfg)
    taps = cfg.conv_taps

    def heads(z):
        return z.reshape(b, t, -1, cfg.head_dim).transpose(0, 2, 1, 3)

    @one_trace
    def layer(x, blk, positions):
        u32 = _normed(x, blk, cfg)
        q, k, v = _qkv(u32, blk["attn"], cfg, positions, dtype)
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            heads(v), causal=True,
                            scale=cfg.head_dim ** -0.5)
        a = _dense_out(o.transpose(0, 2, 1, 3).reshape(b, t, -1),
                       blk["attn"]["wo"], dtype) * cfg.attention_out_multiplier
        ssm = blk["ssm"]
        z, xbc, dt_raw = _projected(u32, ssm, cfg, dtype)
        # Zeros before the sequence; row i's inputs are rows i .. i + taps
        # - 1 of the padded ones.
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xs, bm, cm = _split(_convolved(
            [padded[:, i:i + t] for i in range(taps)], ssm), cfg)
        y, h_last = ssm_scan(
            xs, _dt(dt_raw, ssm), -jnp.exp(ssm["A_log"].astype(jnp.float32)),
            bm, cm, ssm["D"], chunk=cfg.scan_chunk)
        s = _gated_out(y.reshape(b, t, cfg.d_ssm), z, ssm, cfg, dtype)
        x = x + a + s
        x = x + _feed_forward(x, blk, cfg, dtype)
        kept = jnp.concatenate([h_last.reshape(b, -1),
                                padded[:, t:].reshape(b, -1)], axis=-1)
        return x, k.reshape(b, t, cfg.kv_width), v, kept

    keys, values, state = [], [], []
    for li in range(cfg.num_layers):
        x, k, v, kept = layer(x, p[f"layer_{li}"], positions)
        keys.append(k)
        values.append(v)
        state.append(kept)
    if last_only:
        x = x[:, -1:]
    return (_readout(x, p, cfg, dtype), jnp.stack(keys), jnp.stack(values),
            jnp.stack(state))


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


def build_decode_step(config: SsmHybridConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step (program
    ``jit_ssm_hybrid_step``).

    Signature of the returned step::

        logits, keys, values, slot_state, told = step(
            params, keys, values, tokens, positions, page_table, active,
            slot_state, prev)

    over TWO pools and the slot state ``[layers, slots,
    slot_state_width]`` float32, of which the step advances every live
    slot's ``H`` in place (``hvd_ssm_decode``) and shifts its
    convolution rows; an idle slot's row is left as it is.  The step
    CONSUMES both pools and ``slot_state``.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "state-space-hybrid", mesh, width=width, with_lora=with_lora,
        compress=compress)
    hw, cw, taps = cfg.state_width, cfg.conv_width, cfg.conv_taps

    def layer(li, blk, x, pools, carried, local, rnd):
        state, = carried
        s = x.shape[0]
        plane = rnd.first_plane + li
        u32 = _normed(x, blk, cfg)
        q, k, v = _qkv(u32, blk["attn"], cfg, rnd.positions, dtype)
        kp, vp = pools
        kp = kp.at[plane, rnd.page, rnd.off].set(
            k.reshape(s, cfg.kv_width).astype(kp.dtype))
        vp = vp.at[plane, rnd.page, rnd.off].set(v.astype(vp.dtype))
        o = cca_decode_attention(
            q, kp, rnd.page_table, layer=plane, lengths=rnd.lengths,
            kv_heads=cfg.num_kv_heads, scale=cfg.head_dim ** -0.5, values=vp)
        a = _dense_out(o.reshape(s, -1), blk["attn"]["wo"],
                       dtype) * cfg.attention_out_multiplier
        ssm = blk["ssm"]
        z, xbc, dt_raw = _projected(u32, ssm, cfg, dtype)
        # Cut out of the whole array (``state[plane]`` first would be a
        # plane of it, a third of a gigabyte, copied a layer).
        tail = jax.lax.dynamic_slice(
            state, (plane, 0, hw), (1, s, (taps - 1) * cw)
        ).reshape(s, taps - 1, cw)
        window = jnp.concatenate([tail, xbc[:, None]], axis=1)
        # An idle slot's rows stay as they are: cleared, or mid-prefill.
        state = state.at[plane, :, hw:].set(jnp.where(
            rnd.active[:, None, None], window[:, 1:], tail
        ).reshape(s, -1).astype(state.dtype))
        xs, bm, cm = _split(_convolved(
            [window[:, i] for i in range(taps)], ssm), cfg)
        state, y = ssm_decode_update(
            state, xs, _dt(dt_raw, ssm),
            -jnp.exp(ssm["A_log"].astype(jnp.float32)), bm, cm, ssm["D"],
            rnd.active, plane=plane)
        sm = _gated_out(y.reshape(s, cfg.d_ssm), z, ssm, cfg, dtype)
        x = x + a + sm
        x = x + _feed_forward(x, blk, cfg, dtype)
        return x, (kp, vp), (state,), local, None, None

    return stepparts.build_one_chip_step(
        "ssm_hybrid_step", layer, num_layers=cfg.num_layers,
        eps=cfg.rms_eps, tied=False, page_size=page_size,
        scratch=slots * pages_per_slot, dtype=dtype, tells=(), carried=1,
        routed=False, embed_scale=cfg.embedding_multiplier,
        logit_scale=cfg.lm_head_multiplier,
        meta={"arch": "ssm_hybrid", "d_model": cfg.d_model,
              "slots": int(slots), "heads": cfg.num_heads,
              "kv_heads": cfg.num_kv_heads, "ssm_heads": cfg.ssm_heads,
              "ssm_state": cfg.ssm_state})
