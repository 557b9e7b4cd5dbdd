"""Window and full attention layers side by side over a routed
feed-forward of which this chip may hold a SHARE: the K-EXAONE
(``exaone_moe``) block and the SmallThinker block, served.

The fifth instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`,
and the first whose layers are of two attention kinds
(``LayerSpec.attn_kinds``) and whose routed layers may hold fewer experts
than the router scores.  ``x`` is the residual stream (float32), RMSNorm
(``rms_eps``) everywhere, no biases.  Layer ``l`` has an attention kind
``attn_kinds[l]`` (``"window"`` | ``"full"``) and a feed-forward kind
``ffn_kinds[l]`` (``"dense"`` | ``"moe"``).

Attention, token ``i``, ``h = norm_1(x)``: ``q = h W_q`` in ``num_heads``
heads of ``head_dim``, ``k = h W_k`` and ``v = h W_v`` in ``num_kv_heads``;
where ``qk_norm``, ``q`` and ``k`` are RMS-normalised A HEAD over their
``head_dim`` columns (learned scales ``q_norm``, ``k_norm``); then both
are rotated by RoPE (``rope_theta``, half against half) ON WINDOW LAYERS
ONLY: a full layer rotates nothing.  Scores ``q k^T / sqrt(head_dim)``;
query ``i`` sees keys ``j <= i`` on a full layer and ``i - window < j <=
i`` on a window layer (``window`` keys, itself among them); query head
``n`` reads key/value head ``n // (num_heads / num_kv_heads)``.  ``a =
softmax(scores) v`` goes out through ``W_o``.

Feed-forward, ``h = norm_2(x)``.  Dense: a SwiGLU of ``ffn_hidden``.
Routed: the router reads ``z`` (``route_from``: ``h`` itself, or the
layer's INPUT ``x`` as it stood before attention) and chooses
``experts_per_token`` experts ``i`` with weights ``g_i`` (``router``);
``y = [shared(h) +] sum over the chosen i of g_i E_i(h)``, each ``E_i``
and the shared expert (where ``num_shared_experts``) ``W_down(gate_act(h
W_gate) * (h W_up))`` of ``moe_hidden``: the routers of
:mod:`horovod_tpu.ops.moe` and its ``moe_ffn`` as they stand.

The block: ``x += attention(norm_1(x))``, ``x += feed_forward(norm_2(x))``;
a final norm; an untied head.

THE BLOCK'S TWO INSTANCES differ in equations, each a field of
:class:`SwaMoeConfig` that a model's config decides (none is a knob):

====================  ==========================  ==========================
field                 K-EXAONE-236B-A23B          SmallThinker-21BA3B
====================  ==========================  ==========================
``qk_norm``           True: a norm a head on q,k  False: none
``router``            ``"sigmoid_bias"``: sigmoid ``"topk_softmax"``: the
                      over all, top k of ``s +    top k LOGITS, a softmax
                      bias``, ``routed_scale *    over the chosen
                      s_i / sum``
``route_from``        ``"ffn_input"``:            ``"layer_input"``: ``x``
                      ``norm_2(x')``              ahead of ``norm_1`` and of
                                                  attention
``gate_act``          ``"silu"``                  ``"relu"``
``num_shared_experts``  1                         0
period                ``L L L G``, a leading      ``G L L L``, every layer
                      dense layer                 routed
====================  ==========================  ==========================

With ``route_from="layer_input"`` a layer's routing is KNOWN BEFORE ITS
ATTENTION: the decode step makes it, and the pairs' layout (the sort and
the scans of ``ops.moe.layout``), at the layer's top, so that only the
gather, the two grouped matmuls and the weighted sum stand between the
attention call and the residual (nothing is fetched under attention yet:
ROADMAP, Speed); the prefill makes the routing with the other per-token
work ahead of attention.  Router, top-k and layout lie under the named
scope ``hvd_moe_route``.

THE SHARE.  ``experts_held`` of the ``num_experts`` routed experts live
here, ``first_expert ..``; the router keeps its full width.  A routed
layer computes its own experts' part for the (token, choice) pairs
routed to them, and the shared expert; what the experts held elsewhere
would have added is LEFT OUT, and that partial sum is what goes on to
the next layer (expert parallelism without its exchange: nothing stands
in for the absent chips).  ``vocab_held`` rows of the embedding and
columns of the head live here: token ids, logits and sampling are over
that slice.

What is kept, and where.  A token a layer caches ``k`` (after its norm
and, on a window layer, its rotation) and ``v``, one row of
``num_kv_heads * head_dim`` columns in each of two pools.  The full
layers' planes grow with the sequence; the window layers' planes are the
cache's WINDOW GROUP (``ceil(window / page_size) + 1`` pages a slot,
written round and round): the prefill hands back a window plane's LAST
rows only, and the decode step reads a window layer through
``hvd_swa_decode`` (the page walk over the slot's window pages, rows
older than the window masked) and a full layer through
``hvd_cca_decode``.

Departures: the residual stream is float32 (operands in the engine's
``dtype``, float32 accumulation, the router reads the normalised row
before it is rounded); the prefill reads out its last row only; the
multi-token-prediction block is not loaded and not served.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import moe as _moe
from ..ops.attention import cca_decode_attention, flash_attention
from . import stepparts
from .cca_moe import _rope_partial
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .kvcache import window_rows_from
from .layerspec import LayerSpec
from .mla_moe import _swiglu
from .stepparts import dense_out as _dense_out


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    vocab_size: int              # the whole vocabulary
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_hidden: int              # a dense layer's SwiGLU
    moe_hidden: int              # one expert's SwiGLU
    num_experts: int             # the router's width
    experts_per_token: int
    attn_kinds: tuple            # a layer: "window" | "full"
    ffn_kinds: tuple             # a layer: "dense" | "moe"
    window: int
    num_shared_experts: int = 1  # 0: the routed layers have none
    routed_scale: float = 1.0    # (the "sigmoid_bias" router's)
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    # This chip's share (None: everything).
    experts_held: Optional[int] = None
    first_expert: int = 0
    vocab_held: Optional[int] = None
    # The block's variants (module docstring): equations, not knobs.
    qk_norm: bool = True
    router: str = "sigmoid_bias"       # | "topk_softmax"
    route_from: str = "ffn_input"      # | "layer_input"
    gate_act: str = "silu"             # | "relu"

    def __post_init__(self):
        for name, whole in (("experts_held", self.num_experts),
                            ("vocab_held", self.vocab_size)):
            if getattr(self, name) is None:
                object.__setattr__(self, name, whole)
        if len(self.attn_kinds) != len(self.ffn_kinds) \
                or self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{len(self.attn_kinds)} attention kinds over "
                f"{len(self.ffn_kinds)} layers, {self.num_heads} query "
                f"heads over {self.num_kv_heads} of {self.head_dim}")
        if not 0 < self.experts_held <= self.num_experts - self.first_expert \
                or self.first_expert < 0 \
                or not 0 < self.vocab_held <= self.vocab_size:
            raise ValueError(
                f"a share of {self.experts_held} experts from "
                f"{self.first_expert} of {self.num_experts}, "
                f"{self.vocab_held} of {self.vocab_size} rows")
        if self.router not in ("sigmoid_bias", "topk_softmax") \
                or self.route_from not in ("ffn_input", "layer_input") \
                or self.gate_act not in _moe.GATE_ACTS \
                or self.num_shared_experts < 0 \
                or (self.gate_act != "silu" and "dense" in self.ffn_kinds):
            raise ValueError(
                f"router {self.router!r} from {self.route_from!r}, "
                f"{self.gate_act!r} gates, {self.num_shared_experts} shared "
                "experts (a dense layer's gate is silu)")

    @property
    def num_layers(self) -> int:
        return len(self.ffn_kinds)

    @property
    def kv_width(self) -> int:
        """Columns of a cached row, in each pool."""
        return self.num_kv_heads * self.head_dim

    @property
    def moe_layers(self) -> tuple:
        return tuple(i for i, k in enumerate(self.ffn_kinds) if k == "moe")

    def plane(self, layer: int) -> int:
        """Which plane of its group's pools ``layer`` reads and writes:
        its number among the layers of its attention kind."""
        return self.attn_kinds[:layer].count(self.attn_kinds[layer])

    def layer_spec(self) -> LayerSpec:
        cfg = self

        def prefill(params, tokens, **kw):
            return prefill_forward(params, cfg, tokens, **kw)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why = ("the window group (a ring of pages a slot, older rows "
               "overwritten) has no program for it: ")
        return LayerSpec(
            attention="gqa",
            page=((cfg.kv_width,), (cfg.kv_width,)),
            page_holds=("the keys of every key/value head side by side, "
                        + ("normalised a head and, " if cfg.qk_norm else "")
                        + "on a window layer, rotated",
                        "the values"),
            ffn=cfg.ffn_kinds, tied_head=False,
            max_seq_len=cfg.max_seq_len, tp_page_dim=None,
            prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported={
                "tp": "a share of the experts is held here and nothing "
                      "exchanges (token, choice) pairs between chips: "
                      "tp = 1 only",
                "lora": "no adapter banks over these projections",
                "spec_decode": why + "a verify step would write several "
                               "rows a slot a round into it and roll "
                               "rejected ones back",
                "kv_compress": why + "no fp8 cold pool beside it",
                "prefill_chunk": why + "a chunk would need the rows "
                                 "before it, which a window plane has "
                                 "let go",
                "prefix_cache": why + "a matched prefix's window planes "
                                "hold its LAST rows only and belong to "
                                "the slot that wrote them",
                "handoff": why + "the KV plane ships whole planes of "
                           "whole pages"},
            step_state=lambda: (jnp.zeros(
                (len(cfg.moe_layers), cfg.num_experts), jnp.int32),),
            publish_state=lambda state: stepparts.publish_routed(state[0]),
            step_tells=("experts_touched",),
            attn_kinds=cfg.attn_kinds,
            window=cfg.window if "window" in cfg.attn_kinds else None)


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------


def param_shapes(config: SwaMoeConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``):
    THIS SHARE's -- the held experts stacked ``[experts_held, ...]``, the
    held rows of the embedding and columns of the head; the router at
    its full width."""
    c = config
    d, dh = c.d_model, c.head_dim

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
            "attn": {"wq": kernel(d, c.num_heads * dh),
                     "wk": kernel(d, c.kv_width),
                     "wv": kernel(d, c.kv_width),
                     "wo": kernel(c.num_heads * dh, d)},
            "mlp_norm": {"scale": leaf(d)}}
        if c.qk_norm:
            out["attn"].update(q_norm={"scale": leaf(dh)},
                               k_norm={"scale": leaf(dh)})
        if c.ffn_kinds[i] == "moe":
            e, f = c.experts_held, c.moe_hidden
            out["moe"] = {
                "router": {"kernel": leaf(d, c.num_experts)},
                "experts": {"w_gate": leaf(e, d, f), "w_up": leaf(e, d, f),
                            "w_down": leaf(e, f, d)}}
            if c.router == "sigmoid_bias":
                out["moe"]["router"]["e_score_correction_bias"] = leaf(
                    c.num_experts)
            if c.num_shared_experts:
                out["moe"]["shared"] = swiglu(f * c.num_shared_experts)
        else:
            out["mlp"] = swiglu(c.ffn_hidden)
        return out

    tree = {f"layer_{i}": layer(i) for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_held, d),
                final_norm={"scale": leaf(d)},
                lm_head=kernel(d, c.vocab_held))
    return {"params": tree}


def init_params(config: SwaMoeConfig, key, dtype=jnp.float32,
                bias_scale: float = 0.1, spread: float = 0.1):
    """Random parameters for tests: kernels normal over the fan-in (the
    stacked experts' too), the layer norms at one, the per-head query
    and key norms ``spread`` off one (a program that forgets them fails
    a comparison), the embedding at 0.02, the selection bias at
    ``bias_scale``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        owner = str(getattr(path[-2], "key", "")) if len(path) > 1 else ""
        n = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        if owner in ("q_norm", "k_norm"):
            v = 1.0 + spread * n
        elif name == "scale":
            v = jnp.ones(s.shape, jnp.float32)
        elif name == "e_score_correction_bias":
            v = bias_scale * n
        elif name == "tok_embed":
            v = 0.02 * n
        else:
            v = n / math.sqrt(s.shape[-2])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics.
# ---------------------------------------------------------------------------


def _qkv(h, attn, cfg, positions, dtype, *, rotate: bool):
    """``h`` ``[..., d]`` -> the queries ``[..., heads, head_dim]``, the
    keys ``[..., kv_heads, head_dim]`` (both normalised a head where the
    model does so and, where ``rotate``, rotated; in ``dtype``) and the
    values' row ``[..., kv_heads * head_dim]``."""
    lead, dh = h.shape[:-1], cfg.head_dim
    f32 = jnp.float32

    def heads(node, n, norm):
        if cfg.qk_norm:
            z = _rmsnorm(_dense(h, node, dtype).reshape(*lead, n, dh),
                         attn[norm]["scale"], f32, cfg.rms_eps)
        else:
            # Float32 out of the product: rounded once, after the rotation.
            z = _dense_out(h, node, dtype).reshape(*lead, n, dh)
        if rotate:
            z = _rope_partial(z, positions[..., None], cfg.rope_theta, dh)
        return z.astype(dtype)

    return (heads(attn["wq"], cfg.num_heads, "q_norm"),
            heads(attn["wk"], cfg.num_kv_heads, "k_norm"),
            _dense(h, attn["wv"], dtype))


def _route(z, blk, cfg) -> _moe.Routing:
    """A routed layer's routing from the rows ``z`` ``[tokens, d]``
    (float32) that its router reads: ``norm_2(x')`` or the layer's input
    (``cfg.route_from``)."""
    router = blk["moe"]["router"]
    with jax.named_scope("hvd_moe_route"):
        if cfg.router == "topk_softmax":
            return _moe.route_topk_softmax(z, router["kernel"],
                                           top_k=cfg.experts_per_token)
        return _moe.route(z, router["kernel"],
                          router["e_score_correction_bias"],
                          top_k=cfg.experts_per_token,
                          scale=cfg.routed_scale)


def _lay_out(routing, cfg, live=None) -> _moe.Layout:
    """Where ``moe_ffn`` will find the pairs of ``routing``."""
    with jax.named_scope("hvd_moe_route"):
        return _moe.routed_layout(
            routing, num_experts=cfg.num_experts, held=cfg.experts_held,
            first=cfg.first_expert, live=live)


def _ffn(x, blk, cfg, dtype, *, live=None, routing=None, lay=None):
    """The layer's feed-forward over ``x`` ``[tokens, d]`` (float32): the
    residual's float32 addend -- a routed layer's is THIS SHARE's part
    (the held experts' and, where the model has one, the shared expert's)
    -- and, for a routed layer, the ``[num_experts]`` counts of the pairs
    its live rows routed.  ``routing`` (and ``lay``, its layout): what a
    router that reads the layer's input made ahead of attention; None:
    made here, from ``norm_2(x)``."""
    h32 = _rmsnorm(x, blk["mlp_norm"]["scale"], jnp.float32, cfg.rms_eps)
    h = h32.astype(dtype)
    if "moe" not in blk:
        return _swiglu(h, blk["mlp"], dtype), None
    if routing is None:
        routing = _route(h32, blk, cfg)
    if lay is None:
        lay = _lay_out(routing, cfg, live)
    return _moe.moe_ffn(h, blk["moe"], routing,
                        num_experts=cfg.num_experts,
                        first=cfg.first_expert, live=live,
                        with_shared=cfg.num_shared_experts > 0,
                        gate_act=cfg.gate_act, lay=lay)


# ---------------------------------------------------------------------------
# Prefill.
# ---------------------------------------------------------------------------

# Tokens of a prompt that go through a layer's per-token work at a time.
# Everything but attention itself is a row's own: the projections with
# their norms and rotation before it, the closing projection, the
# residual and the feed-forward after it.  Over a whole 8,192-token prompt
# their float32 intermediates (``[tokens, heads, head_dim]`` a norm and a
# rotation, ``[tokens, d]`` a branch, ``[tokens, ffn_hidden]`` a dense
# layer) are gigabytes live at once beside weights and a cache that
# leave the chip some 2 GB; in chunks, one after another, they are a
# quarter of that.  A chunk reads the layer's weights again: 1.2 GB of
# held experts, 1.5 ms at the chip's bandwidth, against the 2,048
# tokens' own 8 ms of products.
PREFILL_TOKENS = 2048


def _by_chunks(fn, *arrays):
    """``fn(*arrays)`` over ``[b, t, ...]`` arrays, ``PREFILL_TOKENS`` of
    the ``t`` rows at a time where there are more, and what is left over
    after the whole chunks as a last, shorter one (``fn`` works a row at
    a time: the chunks change nothing), the results joined again."""
    b, t = arrays[0].shape[:2]
    if t <= PREFILL_TOKENS:
        return fn(*arrays)
    n = t // PREFILL_TOKENS
    whole = n * PREFILL_TOKENS
    out = jax.lax.map(lambda chunk: fn(*chunk), tuple(
        a[:, :whole].reshape(b, n, PREFILL_TOKENS, *a.shape[2:]).swapaxes(0, 1)
        for a in arrays))
    out = jax.tree.map(
        lambda a: a.swapaxes(0, 1).reshape(b, whole, *a.shape[3:]), out)
    if whole == t:
        return out
    rest = fn(*(a[:, whole:] for a in arrays))
    return jax.tree.map(lambda a, z: jnp.concatenate([a, z], axis=1),
                        out, rest)


def prefill_forward(params, config: SwaMoeConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True):
    """Forward a prompt batch ``tokens`` ``[b, t]``; returns ``(logits,
    keys, values, (window keys, window values))``: float32 logits of the
    LAST row over the held vocabulary (``[b, 1, vocab_held]``; every row
    with ``last_only=False``), the FULL layers' rows ``[full layers, b, t,
    kv_width]`` of each pool, and the WINDOW layers' last rows ``[window
    layers, b, t - window_rows_from(t, window), kv_width]``: what the
    next token's window still sees, and nothing older."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "this prefill takes neither adapter banks nor a continuation "
            "from cached rows (a window plane has let the older ones go)")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    kept = window_rows_from(t, cfg.window)
    x = stepparts.embed(p, tokens)
    def heads(z):
        return z.reshape(b, t, cfg.num_kv_heads,
                         cfg.head_dim).transpose(0, 2, 1, 3)

    def layer(x, blk, positions, *, banded):
        early = cfg.route_from == "layer_input" and "moe" in blk

        def before(x, positions):
            # A router that reads the layer's input: with the rest of the
            # rows' own work ahead of attention, a chunk at a time.
            routed = tuple(z.reshape(*x.shape[:2], -1) for z in _route(
                x.reshape(-1, x.shape[-1]), blk, cfg)) if early else ()
            h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
            q, k, v = _qkv(h, blk["attn"], cfg, positions, dtype,
                           rotate=banded)
            return (q, k.reshape(*k.shape[:2], cfg.kv_width), v) + routed

        def after(x, o, *routed):
            x = x + _dense_out(o, blk["attn"]["wo"], dtype)
            routing = _moe.Routing(*(z.reshape(-1, z.shape[-1])
                                     for z in routed)) if routed else None
            y, _ = _ffn(x.reshape(-1, x.shape[-1]), blk, cfg, dtype,
                        routing=routing)
            return x + y.reshape(x.shape)

        q, k, v, *routed = _by_chunks(before, x, positions)
        o = flash_attention(
            q.transpose(0, 2, 1, 3), heads(k), heads(v), causal=True,
            scale=cfg.head_dim ** -0.5,
            window=cfg.window if banded else None)
        x = _by_chunks(after, x, o.transpose(0, 2, 1, 3).reshape(b, t, -1),
                       *routed)
        first = kept if banded else 0
        return x, k[:, first:], v[:, first:]

    layers = {kind: one_trace(functools.partial(layer,
                                                banded=kind == "window"))
              for kind in ("full", "window")}
    rows = {"full": ([], []), "window": ([], [])}
    for li, kind in enumerate(cfg.attn_kinds):
        x, k, v = layers[kind](x, p[f"layer_{li}"], positions)
        rows[kind][0].append(k)
        rows[kind][1].append(v)
    if last_only:
        x = x[:, -1:]
    out = (stepparts.readout(x, p, cfg.rms_eps, dtype, tied=False),
           jnp.stack(rows["full"][0]), jnp.stack(rows["full"][1]))
    if rows["window"][0]:
        out += ((jnp.stack(rows["window"][0]),
                 jnp.stack(rows["window"][1])),)
    return out


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


def build_decode_step(config: SwaMoeConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step (program
    ``jit_swa_moe_step``).

    Signature of the returned step::

        logits, keys, values, window_keys, window_values, routed, told = \\
            step(params, keys, values, tokens, positions, page_table,
                 active, window_table, window_keys, window_values,
                 routed, prev)

    as ``mla_moe.build_decode_step``'s, over TWO pools (``keys``,
    ``values``: the full layers' planes) and with the window group
    behind ``active``: its page table ``[slots, ring]`` (read only) and
    its two pools (without window layers the three are absent).  A full
    layer reads plane ``config.plane(layer)`` of ``keys``/``values``
    through ``page_table``; a window layer writes its row into the
    slot's ring (``window_table[slot, position // page_size % ring]``)
    and reads plane ``config.plane(layer)`` of the window pools through
    ``hvd_swa_decode``.  ``routed`` is ``[routed layers, num_experts]``
    wide, the router's whole width; ``told`` ends in ``experts_touched``:
    HELD experts, summed over the routed layers, that a live slot chose.
    The step CONSUMES the four pools and ``routed``.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "window-and-full-attention routed", mesh, width=width,
        with_lora=with_lora, compress=compress)
    heads, dh = cfg.num_heads, cfg.head_dim
    windowed = "window" in cfg.attn_kinds
    routed_index = {li: i for i, li in enumerate(cfg.moe_layers)}

    def layer(li, blk, x, pools, carried, local, rnd):
        s = x.shape[0]
        attn = blk["attn"]
        banded = cfg.attn_kinds[li] == "window"
        plane = cfg.plane(li)
        routing = lay = None
        if cfg.route_from == "layer_input" and "moe" in blk:
            # The router reads the layer's input: routing and layout are
            # made here, ahead of the attention call.
            routing = _route(x, blk, cfg)
            lay = _lay_out(routing, cfg, rnd.active)
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        q, k, v = _qkv(h, attn, cfg, rnd.positions, dtype, rotate=banded)
        k = k.reshape(s, cfg.kv_width)
        if banded:
            table = rnd.window_table
            ring = table.shape[1]
            wk, wv = carried
            # Idle slots write the window pools' trailing scratch page.
            page = jnp.where(
                rnd.active,
                table[jnp.arange(s), rnd.positions // page_size % ring],
                wk.shape[1] - 1)
            wk = wk.at[plane, page, rnd.off].set(k.astype(wk.dtype))
            wv = wv.at[plane, page, rnd.off].set(v.astype(wv.dtype))
            carried = (wk, wv)
            o = cca_decode_attention(
                q, wk, table, layer=plane, lengths=rnd.lengths,
                kv_heads=cfg.num_kv_heads, scale=dh ** -0.5, values=wv,
                window=cfg.window)
        else:
            kp, vp = pools
            kp = kp.at[plane, rnd.page, rnd.off].set(k.astype(kp.dtype))
            vp = vp.at[plane, rnd.page, rnd.off].set(v.astype(vp.dtype))
            pools = (kp, vp)
            o = cca_decode_attention(
                q, kp, rnd.page_table, layer=plane, lengths=rnd.lengths,
                kv_heads=cfg.num_kv_heads, scale=dh ** -0.5, values=vp)
        x = x + _dense_out(o.reshape(s, heads * dh), attn["wo"], dtype)
        y, counts = _ffn(x, blk, cfg, dtype, live=rnd.active,
                         routing=routing, lay=lay)
        return x + y, pools, carried, local, routed_index.get(li), counts

    return stepparts.build_one_chip_step(
        "swa_moe_step", layer, num_layers=cfg.num_layers, eps=cfg.rms_eps,
        tied=False, page_size=page_size, scratch=slots * pages_per_slot,
        dtype=dtype, tells=("experts_touched",),
        carried=0, window_group=windowed,
        held=slice(cfg.first_expert, cfg.first_expert + cfg.experts_held),
        meta={"arch": "swa_moe", "d_model": cfg.d_model,
              "slots": int(slots), "attn_kinds": tuple(cfg.attn_kinds),
              "window": cfg.window, "experts_held": cfg.experts_held,
              "route_from": cfg.route_from, "gate_act": cfg.gate_act,
              "heads": heads, "kv_heads": cfg.num_kv_heads})
