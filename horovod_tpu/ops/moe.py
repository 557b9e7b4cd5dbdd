"""Routed experts for a served step: a layer that drops nothing and a
grouped matmul over the (token, choice) pairs that tokens chose.

:func:`moe_ffn` is the inference expert layer (``parallel/moe.py`` is the
Switch-style TRAINING layer, with a capacity that drops tokens, and is
left alone):

* the ROUTER IS THE CALLER'S: ``moe_ffn`` takes a :class:`Routing` (each
  token's chosen experts and what each adds) and never scores anything.
  Three routers call it: :func:`route` (one matrix, sigmoid scores in
  float32, ``top_k`` of ALL the experts by ``score + bias``, weights
  renormalised and scaled), :func:`route_top1` (scores the caller's
  own network made, softmax, the one expert ``score + bias`` puts first,
  weighed by its score) -- in both the bias chooses and never weighs --
  and :func:`route_topk_softmax` (one matrix, the ``top_k`` largest
  LOGITS, a softmax over the chosen; no bias).  A caller that knows its
  routing ahead of the rows it is applied to (a router that reads the
  layer's input, before attention) also lays the pairs out ahead
  (:func:`routed_layout`) and hands ``moe_ffn`` the :class:`Layout`;
* the ``tokens * top_k`` pairs are sorted by expert and laid out in
  row tiles of ``tm`` that never straddle two experts (each expert's run
  is padded up to a tile), so the matmul kernel is a plain tiled product
  whose weight block is picked by a scalar-prefetched expert id a tile.
  Work grows with ``tokens * top_k`` (plus under one tile an expert
  touched), never with ``tokens * experts``; no capacity, nothing
  dropped.  Where an expert's whole ``[k, n]`` block double-buffered
  would not leave VMEM room to pipeline (``_column_block``, from shapes
  alone), the kernel takes the block in column slices: a second grid
  axis, outermost, so that an expert's tiles still share one fetch of
  each slice;
* the layer is told which experts it HOLDS (``first``, and the leading
  dim of the stacked weights): it routes over all of them, computes its
  own experts' part and adds the shared expert only where
  ``with_shared`` says so (a model without one says no) -- the cut
  expert parallelism asks for, with
  no exchange on one chip.  A share's rows follow the pairs routed to
  ITS experts, not all the pairs: the grouped matmul runs over
  :func:`pass_rows` padded rows a pass (twice what even routing brings
  the share, plus the padding), and a second pass takes what a skewed
  router sends beyond that, so nothing is dropped and nothing is sized
  for the pairs held elsewhere (8,192 prompt tokens, top 8 of 128, 16
  held: 18,432 rows a pass, where all the pairs are 67,584);
* the gate's activation is the model's, by name (``gate_act``: ``"silu"``
  | ``"relu"``): one kernel body, the epilogue chosen statically.

The Mosaic call is named ``hvd_moe_gmm`` (the trace's name for it); off
the TPU the same tiles go through a ``jax.numpy`` loop-free reference
unless ``HOROVOD_PALLAS=1`` asks for the interpreter.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas as _pallas

_HI = jax.lax.Precision.HIGHEST
_MIN_TILE = 16        # bf16 sublane tile: the smallest row tile.
_MAX_TILE = 128       # the v5e MXU's rows.
_VMEM_LIMIT = 48 * 1024 * 1024
# What a grid step's weight blocks may take, both buffers of every
# weight: a third of the limit, so that the fetch of the next block has
# room beside the one in use, the row tiles and Mosaic's own scratch.
_WEIGHT_BLOCK_BUDGET = 16 * 1024 * 1024
# The gate's activation of an expert, by the name a model's config gives.
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


class Routing(NamedTuple):
    experts: jax.Array     # [tokens, top_k] int32, chosen expert ids
    weights: jax.Array     # [tokens, top_k] float32, what each adds


def route(h, w_router, bias, *, top_k: int, scale: float) -> Routing:
    """Sigmoid scores in float32, ``top_k`` by ``score + bias``, weights
    ``scale * score / (sum of the chosen scores + 1e-20)``."""
    s = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32),
                                  w_router.astype(jnp.float32),
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    g = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return Routing(idx.astype(jnp.int32), g)


def route_top1(logits, bias) -> Routing:
    """Softmax in float32 over scores the caller's own network made
    (``[tokens, experts]``), the ONE expert ``score + bias`` puts first,
    weighed by its score."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(s + bias.astype(jnp.float32), axis=-1)[:, None]
    return Routing(idx.astype(jnp.int32),
                   jnp.take_along_axis(s, idx, axis=-1))


def route_topk_softmax(x, w_router, *, top_k: int) -> Routing:
    """The ``top_k`` largest of the float32 logits ``x @ w_router``, each
    weighed by the softmax over the chosen logits (equal to a softmax
    over all the experts renormalised over the chosen)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=_HI)
    chosen, idx = jax.lax.top_k(logits, top_k)
    return Routing(idx.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1))


def row_tile(pairs: int, experts: int) -> int:
    """Rows a tile: about the mean run of an expert, as a power of two
    between the bf16 sublane tile and the MXU's rows.  At 16 experts: a
    decode round of 96 slots choosing one expert each (6 rows an expert)
    gets 16, the smallest; so does a 256-token prompt (16 rows an
    expert); 512 tokens get 32 and 2,048 or more get 128."""
    want = max(pairs // max(experts, 1), 1)
    tm = _MIN_TILE
    while tm < min(want, _MAX_TILE):
        tm *= 2
    return tm


class Layout(NamedTuple):
    """Where each (token, choice) pair lies among the padded rows."""
    src: jax.Array          # [rows] token each padded row reads
    dest: jax.Array         # [tokens, top_k] row of each pair
    held: jax.Array         # [tokens, top_k] bool: pair's expert is here
    tile_expert: jax.Array  # [rows // tm] local expert id a tile
    active: jax.Array       # [1] tiles that hold at least one pair
    counts: jax.Array       # [experts] pairs routed to each expert (all)


def layout(experts_of, num_experts: int, tm: int, *, first: int = 0,
           held: Optional[int] = None, live=None) -> Layout:
    """Sort the pairs by expert and pad each held expert's run to a
    multiple of ``tm``.  ``live`` (``[tokens]`` bool) leaves dead rows'
    pairs out of every count."""
    t, k = experts_of.shape
    held = num_experts if held is None else held
    flat = experts_of.reshape(-1)
    if live is not None:
        flat = jnp.where(jnp.repeat(live, k), flat, num_experts)
    counts = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(1)
    counts = counts[:num_experts]
    local = flat - first
    here = (local >= 0) & (local < held) & (flat < num_experts)
    key = jnp.where(here, local, held)                 # the rest last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    key_sorted = key[order]
    mine = jax.lax.dynamic_slice(counts, (first,), (held,))
    tiles = (mine + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    starts = jnp.cumsum(mine) - mine                   # unpadded run start
    pad_starts = (tile_end - tiles) * tm
    safe = jnp.minimum(key_sorted, held - 1)
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[safe]
    row_sorted = pad_starts[safe] + rank
    rows = _padded_rows(t * k, held, tm)
    row_sorted = jnp.where(key_sorted < held, row_sorted, rows)  # dropped
    src = jnp.zeros((rows,), jnp.int32).at[row_sorted].set(
        order // k, mode="drop")
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.minimum(row_sorted, rows - 1))
    tile_ids = jnp.arange(rows // tm, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile_ids, side="right"),
        held - 1).astype(jnp.int32)
    return Layout(src, dest.reshape(t, k), here.reshape(t, k), tile_expert,
                  tile_end[-1:].astype(jnp.int32), counts)


def _padded_rows(pairs: int, held: int, tm: int) -> int:
    """The most rows the padded layout can take, as whole tiles."""
    worst = pairs + min(held, pairs) * (tm - 1)
    return -(-worst // tm) * tm


def pass_rows(pairs: int, held: int, num_experts: int, tm: int) -> int:
    """Padded rows ONE pass of the grouped matmul takes.  A layer that
    holds every expert lays all its pairs out at once (``_padded_rows``,
    as ever).  A share lays out at most twice the pairs even routing
    brings its ``held`` of ``num_experts`` experts; further passes take
    the rest (:func:`moe_ffn`)."""
    if held < num_experts:
        pairs = min(pairs, 2 * -(-pairs * held // num_experts))
    return _padded_rows(pairs, held, tm)


def _gmm_kernel(te_ref, na_ref, x_ref, *refs, gated: bool, tile_axis: int,
                gate_act: str):
    """One row tile against its expert's weight block (or a column slice
    of it).  ``gated``: ``gate_act(x @ w_gate) * (x @ w_up)`` in one pass
    over ``x``."""
    del te_ref
    o_ref = refs[-1]

    @pl.when(pl.program_id(tile_axis) < na_ref[0])
    def _tile():
        x = x_ref[...]
        y = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        if gated:
            y = GATE_ACTS[gate_act](y) * jnp.dot(
                x, refs[1][...], preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def _column_block(kdim: int, n: int, weights: int, itemsize: int) -> int:
    """Columns of an expert's ``[kdim, n]`` block a grid step takes: all
    ``n`` where every weight's block, double-buffered, fits
    ``_WEIGHT_BLOCK_BUDGET`` (256 experts of ``[2048, 768]``: 12.6 MB for
    gate and up); else the widest whole-lane-tile divisor of ``n`` that
    does (16 experts of ``[2048, 2048]``: 32 MB whole, so 1,024)."""
    def fits(bn):
        return 2 * weights * kdim * bn * itemsize <= _WEIGHT_BLOCK_BUDGET

    if fits(n) or n % 128:
        return n
    bn = n
    while bn > 128 and not (n % bn == 0 and bn % 128 == 0 and fits(bn)):
        bn -= 128
    return bn


def _gmm_pallas(x, ws, tile_expert, active, tm: int, gate_act: str):
    rows, kdim = x.shape
    n = ws[0].shape[2]
    bn = _column_block(kdim, n, len(ws), x.dtype.itemsize)

    def last(i, na):
        return jnp.minimum(i, jnp.maximum(na[0] - 1, 0))

    # Tiles past the last active one name its blocks again: nothing is
    # fetched for them and the kernel body is predicated off.
    if bn == n:
        grid = (rows // tm,)
        w_spec = pl.BlockSpec(
            (None, kdim, n), lambda i, te, na: (te[last(i, na)], 0, 0))
        x_spec = pl.BlockSpec(
            (tm, kdim), lambda i, te, na: (last(i, na), 0))
        o_spec = pl.BlockSpec((tm, n), lambda i, te, na: (last(i, na), 0))
    else:
        # Column slices outermost: the tiles of one expert follow one
        # another under one slice, which is fetched once for them all;
        # the row tiles (small) are read once a slice.
        grid = (n // bn, rows // tm)
        w_spec = pl.BlockSpec(
            (None, kdim, bn),
            lambda j, i, te, na: (te[last(i, na)], 0, j))
        x_spec = pl.BlockSpec(
            (tm, kdim), lambda j, i, te, na: (last(i, na), 0))
        o_spec = pl.BlockSpec(
            (tm, bn), lambda j, i, te, na: (last(i, na), j))
    kernel = functools.partial(_gmm_kernel, gated=len(ws) == 2,
                               tile_axis=len(grid) - 1, gate_act=gate_act)
    with jax.named_scope("hvd_moe_gmm"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=[x_spec] + [w_spec] * len(ws),
                out_specs=o_spec,
            ),
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="hvd_moe_gmm",
            interpret=_pallas.interpret_mode(),
        )(tile_expert, active, x, *ws)


def _gmm_reference(x, ws, tile_expert, active, tm: int, gate_act: str):
    """The same tiles in ``jax.numpy``: each tile against the weights of
    its expert, gathered a tile (fine at test sizes, and the CPU path of
    a tiny engine)."""
    rows, kdim = x.shape
    xt = x.reshape(rows // tm, tm, kdim)

    def mm(w):
        return jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert],
                          preferred_element_type=jnp.float32)

    y = mm(ws[0])
    if len(ws) == 2:
        y = GATE_ACTS[gate_act](y) * mm(ws[1])
    live = jnp.arange(rows // tm) < active[0]
    return jnp.where(live[:, None, None], y, 0.0).astype(x.dtype).reshape(
        rows, -1)


def grouped_matmul(x, ws, tile_expert, active, *, tm: int,
                   gate_act: str = "silu", force_reference: bool = False):
    """``x`` ``[rows, k]`` in tiles of ``tm`` rows, tile ``i`` against
    ``w[tile_expert[i]]`` for each ``w`` ``[experts, k, n]`` of ``ws``:
    one weight gives ``x @ w``; two give ``gate_act(x @ w0) * (x @ w1)``
    (``gate_act``: a key of ``GATE_ACTS``).  Only the first ``active[0]``
    tiles are computed; the rows of the others are undefined."""
    if x.shape[0] % tm:
        raise ValueError(f"{x.shape[0]} rows are not whole tiles of {tm}")
    if len(ws) not in (1, 2):
        raise ValueError(f"one or two weights, got {len(ws)}")
    if gate_act not in GATE_ACTS:
        raise ValueError(f"gate_act {gate_act!r}: one of {sorted(GATE_ACTS)}")
    if not force_reference and _pallas.pallas_enabled("moe_gmm"):
        return _gmm_pallas(x, tuple(ws), tile_expert, active, tm, gate_act)
    return _gmm_reference(x, tuple(ws), tile_expert, active, tm, gate_act)


def _share_passes(experts, lay: Layout, weights, rows: int, tm: int, shape):
    """A share's part of the routed sum, ``rows`` padded rows a pass: pass
    ``w`` takes the tiles ``w * rows / tm ..`` of the layout (whose index
    arrays cover every pair; only the gathered rows are sized by the
    pass) and adds each token's pairs that lie there, a choice at a time
    (``[tokens, top_k, d]`` in float32 is gigabytes at a long prompt's
    width).  The passes run while there are active tiles: one under even
    routing."""
    pad = -lay.src.shape[0] % rows
    src = jnp.pad(lay.src, (0, pad))
    tile_expert = jnp.pad(lay.tile_expert, (0, pad // tm))
    tiles = rows // tm

    def one(w, y):
        ys = experts(
            jax.lax.dynamic_slice(src, (w * rows,), (rows,)),
            jax.lax.dynamic_slice(tile_expert, (w * tiles,), (tiles,)),
            jnp.clip(lay.active - w * tiles, 0, tiles))
        at = lay.dest - w * rows
        here = lay.held & (at >= 0) & (at < rows)
        at = jnp.clip(at, 0, rows - 1)

        def choice(c, y):
            # A loop, not eight gathers side by side: one ``[tokens, d]``
            # gather is live at a time.
            col = jax.lax.dynamic_index_in_dim(at, c, 1, keepdims=False)
            keep = jax.lax.dynamic_index_in_dim(here, c, 1)
            g = jax.lax.dynamic_index_in_dim(weights, c, 1)
            return y + jnp.where(keep, ys[col].astype(jnp.float32) * g, 0.0)

        return jax.lax.fori_loop(0, weights.shape[1], choice, y)

    return jax.lax.fori_loop(0, (lay.active[0] + tiles - 1) // tiles, one,
                             jnp.zeros(shape, jnp.float32))


def routed_layout(routing: Routing, *, num_experts: int, held: int,
                  first: int = 0, live=None) -> Layout:
    """The :class:`Layout` :func:`moe_ffn` computes under ``routing``, for
    a caller that has the routing before it has the rows: the sort and
    the scans need the chosen experts alone."""
    t, top_k = routing.experts.shape
    return layout(routing.experts, num_experts,
                  row_tile(t * top_k, num_experts), first=first, held=held,
                  live=live)


def moe_ffn(h, params, routing: Routing, *, num_experts: int,
            first: int = 0, with_shared: bool = True, live=None,
            gate_act: str = "silu", lay: Optional[Layout] = None,
            force_reference: bool = False):
    """The routed layer over ``h`` ``[tokens, d]`` under the caller's
    ``routing`` (``[tokens, top_k]`` experts of ``num_experts`` and
    weights: :func:`route`, :func:`route_top1`,
    :func:`route_topk_softmax`, or any other router).  ``gate_act``: the
    gate's activation in the experts and in the shared expert.  ``lay``:
    the pairs' layout where the caller made it ahead
    (:func:`routed_layout` under the same ``first``, ``live`` and held
    experts); None: made here.

    ``params``: ``experts`` (``w_gate``, ``w_up`` ``[held, d, f]``,
    ``w_down`` ``[held, f, d]``: the experts ``first .. first + held -
    1``) and, where ``with_shared``, ``shared`` (a SwiGLU's three
    kernels).  Returns ``(y, counts)``: this share's part of the layer's
    output (every held expert's, and the shared expert's where
    ``with_shared``) in float32, unrounded, and the ``[num_experts]``
    count of pairs routed to each expert by the ``live`` rows.
    """
    dtype = h.dtype
    t, top_k = routing.experts.shape
    ex = params["experts"]
    held = ex["w_gate"].shape[0]
    tm = row_tile(t * top_k, num_experts)
    if lay is None:
        lay = routed_layout(routing, num_experts=num_experts, held=held,
                            first=first, live=live)

    def experts(src, tile_expert, active):
        act = grouped_matmul(h[src], (ex["w_gate"].astype(dtype),
                                      ex["w_up"].astype(dtype)),
                             tile_expert, active, tm=tm, gate_act=gate_act,
                             force_reference=force_reference)
        return grouped_matmul(act, (ex["w_down"].astype(dtype),),
                              tile_expert, active, tm=tm,
                              force_reference=force_reference)

    rows = pass_rows(t * top_k, held, num_experts, tm)
    if rows >= lay.src.shape[0]:
        ys = experts(lay.src, lay.tile_expert, lay.active)
        picked = jnp.where(lay.held[..., None],
                           ys[lay.dest].astype(jnp.float32), 0.0)
        y = jnp.einsum("tkd,tk->td", picked, routing.weights)
    else:
        y = _share_passes(experts, lay, routing.weights, rows, tm,
                          (t, h.shape[1]))
    if with_shared:
        sh = params["shared"]
        gate = h @ sh["w_gate"]["kernel"].astype(dtype)
        up = h @ sh["w_up"]["kernel"].astype(dtype)
        y = y + jnp.dot((GATE_ACTS[gate_act](gate) * up).astype(dtype),
                        sh["w_down"]["kernel"].astype(dtype),
                        preferred_element_type=jnp.float32)
    return y, lay.counts
