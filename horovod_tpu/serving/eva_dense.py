"""EVA attention over a dense SwiGLU stack: every layer keeps an exact,
ALIGNED window of the newest tokens beside ONE pooled key and value for
each chunk of the windows behind it: the EvaByte (``evabyte``) block,
served.

The seventh instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`,
and the first whose growing planes hold a row a CHUNK and not a row a
token (``LayerSpec.row_tokens``), whose every layer has a plane in both
groups of the cache (``attn_kinds``: ``"chunked"``) and whose window is
therefore an aligned one (``window_aligned``).  ``x`` is the residual stream (float32),
``norm(x) = x / rms(x) * (1 + g)`` (``rms_eps``; the learned ``g`` is the
tree's ``scale``), no bias anywhere.  A layer, token ``i``:

1. ``u = norm_1(x)``; ``q = u W_q`` in ``num_heads`` heads of
   ``head_dim``, ``k = u W_k`` and ``v = u W_v`` in ``num_kv_heads``;
   ``q`` and ``k`` rotated by RoPE at position ``i`` (``rope_theta``,
   half against half over all ``head_dim`` columns).
2. Chunk ``c`` is tokens ``chunk * c .. chunk * c + chunk - 1``.  Key
   head ``h`` has two learned vectors ``mu_h`` and ``phi_h``
   (``adaptive_mu_k``, ``adaptive_phi``: ``[num_kv_heads, head_dim]``):
   ``a = softmax over the chunk's j of (mu_h . k_j)``, ``kbar_c = sum_j
   a_j k_j``; ``b = softmax over j of (phi_h . k_j)``, ``vbar_c = sum_j
   b_j v_j``.  The keys are pooled AFTER their rotation; the pooling
   logits carry no ``1 / sqrt(head_dim)``.
3. Token ``i`` lies in window ``w = i // window``.  It sees the exact
   rows ``window * w <= j <= i`` and the pooled rows of the chunks ``c <
   w * window / chunk`` (every chunk of the windows before): ONE softmax
   over both sets of scores ``q . k / sqrt(head_dim)``, float32
   statistics, applied to ``[v_j | vbar_c]``; query head ``n`` reads
   key/value head ``n // (num_heads / num_kv_heads)``.  A chunk of the
   current window is seen exactly and not pooled.
4. ``x = x + o W_o``; ``x = x + W_down(silu(W_gate norm_2(x)) * W_up
   norm_2(x))``.

``logits = norm_f(x) W_head`` in float32: ``pred_heads * vocab_size``
columns, prediction head ``p`` in columns ``p * vocab_size ..``; head 0
is the next token's, and the one the engine samples from.  The further
heads' columns are computed and returned and nothing drafts from them.

What is kept, and where.  Both groups of a layer's planes lie in ONE pair
of pools (keys, values; ``CacheConfig.window_in_pool``).  A slot's RING
(``window / page_size + 1`` pages of exact rows, the page of tokens ``n
* page_size ..`` at ring entry ``n % ring``) and, growing with the
sequence, one POOLED row a chunk (row ``c % page_size`` of growing page
``c // page_size``).  The chunk is whole pages (ONE, where ``chunk ==
page_size``, as served), so the chunk that fills is whole pages of the
ring.  The prefill pools
the prompt's whole chunks and hands back those rows, the exact rows of
its last window and nothing else: the ragged last chunk's rows wait in
the ring.  A decode round writes token ``i``'s row into the ring, where
``i % chunk == chunk - 1`` pools that page into row ``i // chunk`` of the
growing planes, and attends through ``hvd_eva_decode``
(:func:`horovod_tpu.ops.attention.eva_decode_attention`): one walk over
the visible pooled pages and then the ring, turned to the window's start.

Departures: the residual stream is float32 (operands in the engine's
``dtype``, float32 accumulation, branch results added unrounded); the
pooling's statistics and sums are float32 over the rows AS CACHED (in the
engine's ``dtype``), in the prefill as in the round that fills a chunk;
the prefill reads out its last row only.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import eva_decode_attention, flash_attention
from . import stepparts
from .cca_moe import _rope_partial
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .layerspec import FEATURES, LayerSpec
from .stepparts import dense_out as _dense_out
from .swa_moe import _by_chunks

# Key blocks of the prefill's attention calls: the largest of these that
# divides a window's keys (its pooled rows and its own).
_KEY_BLOCKS = (512, 384, 256, 128)


@dataclasses.dataclass(frozen=True)
class EvaDenseConfig:
    vocab_size: int              # one prediction head's columns
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_hidden: int
    window: int = 2048           # tokens of the exact, aligned window
    chunk: int = 16              # tokens a pooled row stands for
    pred_heads: int = 8          # prediction heads, side by side
    rope_theta: float = 1e5
    rms_eps: float = 1e-5
    max_seq_len: int = 32768

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2 \
                or self.chunk < 1 or self.window % self.chunk \
                or self.pred_heads < 1:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} of "
                f"{self.head_dim}, a window of {self.window} in chunks of "
                f"{self.chunk}, {self.pred_heads} prediction heads")

    @property
    def kv_width(self) -> int:
        """Columns of a cached row, in each pool."""
        return self.num_kv_heads * self.head_dim

    def layer_spec(self) -> LayerSpec:
        cfg = self

        def prefill(params, tokens, **kw):
            # The engine samples a join's first token from what this
            # hands back: the next token's head alone.
            logits, *rest = prefill_forward(params, cfg, tokens, **kw)
            return (logits[..., :cfg.vocab_size], *rest)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why = ("a growing row stands for a CHUNK of tokens beside a ring "
               "of exact rows: ")
        reasons = {
            "tp": "both groups' pages lie in one pool that one walk reads "
                  "whole: tp = 1 only",
            "lora": "no adapter banks over these projections",
            "spec_decode": why + "a verify step would write several rows "
                           "a slot a round, pool a chunk that a rejected "
                           "draft then un-fills, and roll both back; "
                           "drafting from the model's own further "
                           "prediction heads is not built",
            "kv_compress": why + "no fp8 cold pool beside them",
            "prefill_chunk": why + "a chunk of the prompt that ends inside "
                             "a window would need that window's exact rows "
                             "again, and a pooled past takes no "
                             "continuation",
            "prefix_cache": why + "a matched prefix's pages hold pooled "
                            "rows, its exact rows lie in the ring of the "
                            "slot that wrote them, and a page is keyed by "
                            "page_size token ids, not by a chunk's",
            "handoff": why + "the KV plane ships whole planes of rows a "
                       "token and knows no ring"}
        assert set(reasons) == set(FEATURES)
        return LayerSpec(
            attention="gqa",
            page=((cfg.kv_width,), (cfg.kv_width,)),
            page_holds=("the rotated keys of every key/value head side by "
                        "side: a token's own (a ring page) or a chunk's "
                        "pooled ones (a growing page)",
                        "the values, a token's or a chunk's pooled ones"),
            ffn=("dense",) * cfg.num_layers, tied_head=False,
            max_seq_len=cfg.max_seq_len, tp_page_dim=None,
            prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported=reasons,
            attn_kinds=("chunked",) * cfg.num_layers, window=cfg.window,
            row_tokens=cfg.chunk)


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------


def param_shapes(config: EvaDenseConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``).
    A norm's ``scale`` is its learned ``g``: the norm multiplies by ``1 +
    g``."""
    c = config
    d, dh = c.d_model, c.head_dim

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def kernel(*shape):
        return {"kernel": leaf(*shape)}

    def layer():
        return {
            "attn_norm": {"scale": leaf(d)},
            "attn": {"wq": kernel(d, c.num_heads * dh),
                     "wk": kernel(d, c.kv_width),
                     "wv": kernel(d, c.kv_width),
                     "wo": kernel(c.num_heads * dh, d),
                     "adaptive_mu_k": leaf(c.num_kv_heads, dh),
                     "adaptive_phi": leaf(c.num_kv_heads, dh)},
            "mlp_norm": {"scale": leaf(d)},
            "mlp": {"w_gate": kernel(d, c.ffn_hidden),
                    "w_up": kernel(d, c.ffn_hidden),
                    "w_down": kernel(c.ffn_hidden, d)}}

    tree = {f"layer_{i}": layer() for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_size, d),
                final_norm={"scale": leaf(d)},
                lm_head=kernel(d, c.pred_heads * c.vocab_size))
    return {"params": tree}


def init_params(config: EvaDenseConfig, key, dtype=jnp.float32):
    """Random parameters for tests: kernels normal over the fan-in, the
    embedding at 0.5 (rows that differ, so that keys do), every norm's
    ``g`` 0.1 off ZERO (a program that forgets the unit offset fails a
    comparison) and the pooling vectors normal at 1: pooling weights far
    from even, so that a mean in the softmax's place fails one too."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        n = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        if name == "scale":
            v = 0.1 * n
        elif name in ("adaptive_mu_k", "adaptive_phi"):
            v = n
        elif name == "tok_embed":
            v = 0.5 * n
        else:
            v = n / np.sqrt(s.shape[0])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics.
# ---------------------------------------------------------------------------


def _norm(x, node, dtype, eps):
    """``x / rms(x) * (1 + g)``."""
    return _rmsnorm(x, 1.0 + node["scale"].astype(jnp.float32), dtype, eps)


def _qkv(h, attn, cfg, positions, dtype):
    """``h`` ``[..., d]`` -> the queries ``[..., heads, head_dim]`` and
    the keys' row ``[..., kv_width]``, both rotated (float32 out of the
    product: rounded once, after the rotation), and the values' row."""
    lead, dh = h.shape[:-1], cfg.head_dim

    def heads(node, n):
        z = _dense_out(h, node, dtype).reshape(*lead, n, dh)
        return _rope_partial(z, positions[..., None], cfg.rope_theta,
                             dh).astype(dtype)

    return (heads(attn["wq"], cfg.num_heads),
            heads(attn["wk"], cfg.num_kv_heads).reshape(*lead, cfg.kv_width),
            _dense(h, attn["wv"], dtype))


def pool_chunks(k, v, attn, cfg):
    """Rows ``[..., chunk, kv_width]`` of each pool, as cached -> the
    chunks' pooled rows ``[..., kv_width]`` in the rows' type.  Statistics
    and sums are float32 and elementwise (no matrix unit rounds a weight
    or a row again)."""
    f32 = jnp.float32
    lead = k.shape[:-2]
    shape = (*lead, cfg.chunk, cfg.num_kv_heads, cfg.head_dim)
    kh, vh = k.astype(f32).reshape(shape), v.astype(f32).reshape(shape)

    def weights(vector):
        return jax.nn.softmax(
            jnp.sum(kh * attn[vector].astype(f32), axis=-1), axis=-2)

    def pooled(rows, w):
        return jnp.sum(rows * w[..., None], axis=-3).reshape(
            *lead, cfg.kv_width).astype(k.dtype)

    return (pooled(kh, weights("adaptive_mu_k")),
            pooled(vh, weights("adaptive_phi")))


def _feed_forward(x, blk, cfg, dtype):
    h = _norm(x, blk["mlp_norm"], dtype, cfg.rms_eps)
    mlp = blk["mlp"]
    return _dense_out(
        jax.nn.silu(_dense_out(h, mlp["w_gate"], dtype))
        * _dense_out(h, mlp["w_up"], dtype), mlp["w_down"], dtype)


def _readout(x, p, cfg, dtype):
    return stepparts.readout(x, p, cfg.rms_eps, dtype, tied=False,
                             unit_offset=True)


# ---------------------------------------------------------------------------
# Prefill.
# ---------------------------------------------------------------------------


def prefill_forward(params, config: EvaDenseConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True):
    """Forward a prompt batch ``tokens`` ``[b, t]``; returns ``(logits,
    pooled keys, pooled values, (ring keys, ring values))``: float32
    logits of the LAST row over every prediction head (``[b, 1,
    pred_heads * vocab_size]``; every row with ``last_only=False``), the
    pooled rows of the prompt's ``t // chunk`` whole chunks ``[layers, b,
    t // chunk, kv_width]`` of each pool, and the exact rows of its last
    window, ``window * (t // window) ..``, ``[layers, b, t % window,
    kv_width]``.  The rows of a ragged last chunk are among the ring's
    and are pooled nowhere yet.

    Attention is a window at a time: window ``w``'s queries against ``[the
    pooled rows of the windows before | the window's own rows]`` under
    the bottom-right causal mask, so that every query sees every pooled
    row and its own window causally; a ragged last window is padded to
    ``window`` rows on both sides (padded keys lie after every real
    query; padded queries are dropped)."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "this prefill takes neither adapter banks nor a continuation "
            "from cached rows (a pooled past takes none)")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    win, per = cfg.window, cfg.window // cfg.chunk
    chunks, kept = t // cfg.chunk, t // win * win
    x = stepparts.embed(p, tokens)

    def heads(z):
        return z.reshape(*z.shape[:2], -1, cfg.head_dim).transpose(0, 2, 1, 3)

    @one_trace
    def layer(x, blk, positions):
        attn = blk["attn"]

        def before(x, positions):
            return _qkv(_norm(x, blk["attn_norm"], dtype, cfg.rms_eps),
                        attn, cfg, positions, dtype)

        def after(x, o):
            x = x + _dense_out(o, attn["wo"], dtype)
            return x + _feed_forward(x, blk, cfg, dtype)

        q, k, v = _by_chunks(before, x, positions)
        kbar, vbar = pool_chunks(
            *(z[:, :chunks * cfg.chunk].reshape(b, chunks, cfg.chunk,
                                                cfg.kv_width)
              for z in (k, v)), attn, cfg)
        outs = []
        for w in range(-(-t // win)):
            lo, hi = w * win, min((w + 1) * win, t)
            pad = ((0, 0), (0, win - (hi - lo)), (0, 0))
            qw, kw, vw = (jnp.pad(z[:, lo:hi].reshape(b, hi - lo, -1), pad)
                          for z in (q, k, v))
            keys = win + w * per
            o = flash_attention(
                heads(qw), heads(jnp.concatenate([kbar[:, :w * per], kw], 1)),
                heads(jnp.concatenate([vbar[:, :w * per], vw], 1)),
                causal=True, scale=cfg.head_dim ** -0.5,
                block_kv=next((n for n in _KEY_BLOCKS if keys % n == 0),
                              _KEY_BLOCKS[0]))
            outs.append(o[:, :, :hi - lo])
        o = jnp.concatenate(outs, axis=2).transpose(0, 2, 1, 3)
        x = _by_chunks(after, x, o.reshape(b, t, -1))
        return x, kbar, vbar, k[:, kept:], v[:, kept:]

    rows = [], [], [], []
    for li in range(cfg.num_layers):
        x, *kept_rows = layer(x, p[f"layer_{li}"], positions)
        for into, z in zip(rows, kept_rows):
            into.append(z)
    if last_only:
        x = x[:, -1:]
    kbar, vbar, kring, vring = (jnp.stack(z) for z in rows)
    return _readout(x, p, cfg, dtype), kbar, vbar, (kring, vring)


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


def build_decode_step(config: EvaDenseConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step (program
    ``jit_eva_dense_step``).

    Signature of the returned step::

        logits, keys, values, told = step(
            params, keys, values, tokens, positions, page_table, active,
            window_table, prev)

    over ONE pair of pools that holds both groups' pages: ``page_table``
    ``[slots, pages_per_slot]`` names a slot's growing pages (pooled rows)
    and ``window_table`` ``[slots, ring]`` its ring's (exact rows), both
    read only.  Layer ``l``, a live slot whose token is at position
    ``i``: (i) its rotated key and its value go to row ``i % page_size``
    of ring entry ``i // page_size % ring``; (ii) where that row is its
    chunk's last the chunk's pages are pooled (:func:`pool_chunks`) into
    row ``i // chunk % page_size`` of growing page ``i // chunk //
    page_size``
    (every other slot's pooled row goes to the scratch page); (iii)
    ``hvd_eva_decode`` reads the pooled rows of the windows before and
    the ring's rows of this one under one softmax.  ``logits`` are all
    ``pred_heads * vocab_size`` columns; ``told``'s token is the greedy
    one of head 0's.  The step CONSUMES both pools.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "chunk-pooled-attention", mesh, width=width, with_lora=with_lora,
        compress=compress)
    if cfg.chunk % page_size:
        raise NotImplementedError(
            f"a chunk of {cfg.chunk} tokens over pages of {page_size}: the "
            "chunk that fills is pooled as WHOLE pages of the ring (one, "
            "where the chunk is the page)")
    scratch = slots * pages_per_slot
    per_chunk = cfg.chunk // page_size

    def layer(li, blk, x, pools, carried, local, rnd):
        s = x.shape[0]
        attn = blk["attn"]
        plane = rnd.first_plane + li
        h = _norm(x, blk["attn_norm"], dtype, cfg.rms_eps)
        q, k, v = _qkv(h, attn, cfg, rnd.positions, dtype)
        kp, vp = pools
        table = rnd.window_table
        at = rnd.positions // page_size          # the token's page
        slot = jnp.arange(s)
        # The ring entries of the chunk's pages, the token's own last.
        entries = (at[:, None] - (per_chunk - 1) + jnp.arange(per_chunk)
                   ) % table.shape[1]
        # (i) Idle slots read and write the pools' scratch page.
        ring = jnp.where(rnd.active[:, None], table[slot[:, None], entries],
                         scratch)
        kp = kp.at[plane, ring[:, -1], rnd.off].set(k.astype(kp.dtype))
        vp = vp.at[plane, ring[:, -1], rnd.off].set(v.astype(vp.dtype))
        # (ii) The chunk the round's row has just filled, pooled.
        chunk = rnd.positions // cfg.chunk
        fills = rnd.active & (rnd.positions % cfg.chunk == cfg.chunk - 1)
        grown = jnp.where(
            fills, rnd.page_table[slot, jnp.minimum(
                chunk // page_size, rnd.page_table.shape[1] - 1)], scratch)
        kbar, vbar = pool_chunks(
            *(z[plane, ring].reshape(s, cfg.chunk, cfg.kv_width)
              for z in (kp, vp)), attn, cfg)
        kp = kp.at[plane, grown, chunk % page_size].set(kbar)
        vp = vp.at[plane, grown, chunk % page_size].set(vbar)
        # (iii)
        o = eva_decode_attention(
            q, kp, rnd.page_table, table, layer=plane, lengths=rnd.lengths,
            window=cfg.window, row_tokens=cfg.chunk,
            kv_heads=cfg.num_kv_heads, scale=cfg.head_dim ** -0.5, values=vp)
        x = x + _dense_out(o.reshape(s, -1), attn["wo"], dtype)
        x = x + _feed_forward(x, blk, cfg, dtype)
        return x, (kp, vp), carried, local, None, None

    return stepparts.build_one_chip_step(
        "eva_dense_step", layer, num_layers=cfg.num_layers, eps=cfg.rms_eps,
        tied=False, page_size=page_size, scratch=scratch, dtype=dtype,
        tells=(), carried=0, routed=False, window_group=True,
        window_pools=False, unit_offset=True,
        sample_columns=cfg.vocab_size,
        meta={"arch": "eva_dense", "d_model": cfg.d_model,
              "slots": int(slots), "heads": cfg.num_heads,
              "kv_heads": cfg.num_kv_heads, "window": cfg.window,
              "chunk": cfg.chunk, "pred_heads": cfg.pred_heads})
