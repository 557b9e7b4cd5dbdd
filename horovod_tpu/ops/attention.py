"""Fused multi-head attention: Pallas TPU kernels + XLA reference.

The reference framework (Horovod) ships no attention kernels -- its BERT /
Llama workloads (BASELINE.json configs) lean on the host framework's fused
attention (torch SDPA / cuDNN flash attention).  The TPU-native equivalent
of that dependency is a Pallas flash-attention kernel pair (forward +
backward, FlashAttention-2 schedule) tiled for the MXU, with an XLA
reference implementation for CPU tests and as numerical ground truth.

Design notes (see /opt/skills/guides/pallas_guide.md).  ``flash_attention``
has two kernel sets and picks one from the shapes it is given
(``_flash_path``): the BLOCKED kernels (``hvd_flash_fwd``,
``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``) whenever the queries or the
keys take more than one block or segment ids ride along, the HEAD-GROUP
kernels (``hvd_flash_hg_fwd``, ``hvd_flash_hg_bwd``) when one block holds
the sequence.  The names are what the ops line of a device trace shows.

Blocked kernels:

* Grid ``(batch, heads, q_blocks, kv_blocks)`` -- the last grid dimension
  is sequential on TPU, so VMEM scratch (running max ``m``, normaliser
  ``l``, accumulator ``acc``) carries the online-softmax state across kv
  blocks; output and logsumexp are written on the final kv step.
* Softmax statistics cross the kernel boundary as ``(block, 128)``
  lane-broadcast tiles (the layout jax's own TPU flash attention uses for
  its l/m residuals); the persistent VJP residual is sliced to ``(b,h,t)``
  so only transient kernel I/O pays the lane broadcast.
* Backward is the standard two-kernel FA2 split: ``dq`` accumulates over
  kv blocks, ``dk/dv`` accumulate over q blocks; ``delta = rowsum(dO*O)``
  is precomputed by XLA (a trivially fused elementwise reduce).  dk/dv
  leave at query-head granularity in float32 and are group-summed and
  cast outside.

Head-group kernels (T = 128 pays 512 grid steps a call and 320 KB of
broadcast statistics a head in the blocked set; PERF.md, PR 29):

* Grid ``(batch, heads / G)``: a grid step takes G query heads of one
  batch row with their kv heads -- whole GQA groups, the largest G whose
  tiles fit ``_HEAD_GROUP_VMEM_BUDGET`` (``_head_group``, a function of
  the shapes alone) -- and walks them in an unrolled loop.  One block
  holds all keys, so there is no online-softmax state: a plain softmax.
* The score tile is computed TRANSPOSED, keys on the sublanes and queries
  on the lanes, so a head's statistics are one ``(1, T)`` row: ``lse``
  crosses the boundary as ``(b, h, 1, T)`` float32, 512 bytes a head at
  T = 128, and nothing is broadcast.  The VJP residual is the same
  ``(b, h, t)`` logsumexp.
* ONE backward kernel gives dq, dk and dv from one pass over the score
  tiles.  ``delta`` is ``sum_k P * dP``, softmax's own backward over the
  whole row (equal to ``rowsum(dO*O)``), so O is not read; a kv head's
  query heads are summed in the kernel, so dk/dv leave in the operands'
  type.

Both sets:

* Matmul operands: q.k^T and dO.v^T hand the MXU the operands in the type
  they arrive in (head-group) or upcast to float32 (blocked); p and ds
  are float32 values handed to ``dot`` at Mosaic's default precision,
  which on the v5e is ONE bfloat16 pass whatever the operand type
  (measured, PERF.md PR 29) -- so both sets compute the same products.
* Causal masking is bottom-right aligned (query ``i`` sits at absolute
  position ``tk - tq + i``, the KV-cache/decode convention, matching
  ``attention_reference``); in the blocked set whole blocks above the
  diagonal are predicated off with ``@pl.when``.
* Grouped-query attention never materializes repeated K/V in HBM: the
  blocked set broadcasts kv heads through the BlockSpec ``index_map``
  (query head ``h`` reads kv head ``h // rep``), the head-group set loads
  a group's kv heads once a grid step.
* Segment ids (and their DEAD rows: zero output, zero gradients) are the
  blocked set's; without them every query sees a key and no row is dead.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas as _pallas

_LANES = 128          # TPU lane count: last-dim tile granularity.
_MIN_BLOCK = 8        # f32 sublane tile; smallest sane seq block.
_NEG_INF = -1e30      # Softmax mask value (finite: avoids NaN on empty rows).

logger = logging.getLogger("horovod_tpu.ops")

# Swept on a v5e on an earlier runtime (July-August 2026, not reproduced;
# B1 H8 S8192 D128 causal bf16 fwd+bwd, within-run comparisons of
# differential scan-chains): kv=512 beats kv=256 by ~19% at S=2048 and
# ~39% at S=8192 -- the wider kv block halves the grid-iteration VMEM
# swaps per q block and feeds the MXU longer runs; q=512 beats q=256 by
# ~16% at S=8192 (5.18 -> 4.33 ms kernel time) and directionally at
# S=2048 -- the bigger q tile amortizes the backward's dq/dk/dv re-reads.
# Shorter sequences clamp the block to the sequence automatically; a
# sequence that ONE block holds (T <= 512 at these defaults) is no longer
# governed by this sweep: it runs the head-group kernels (PR 29).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512


def _use_pallas() -> bool:
    # Unified switch (PR 13): HOROVOD_PALLAS / HOROVOD_PALLAS_FLASH,
    # with the legacy HVD_TPU_FLASH honored behind a deprecation note.
    return _pallas.pallas_enabled("flash")


def _block(seq: int, preferred: int) -> int:
    """Largest 8-multiple block <= preferred dividing seq, else 0.

    Kernels assume blocks tile the sequence evenly and respect the f32
    8-sublane tile; sequences with no such divisor fall back to the
    reference path (dispatcher checks for 0).
    """
    b = min(preferred, seq) // _MIN_BLOCK * _MIN_BLOCK
    while b >= _MIN_BLOCK and seq % b:
        b -= _MIN_BLOCK
    return max(b, 0)


def _block_lane(seq: int, preferred: int) -> int:
    """Largest block <= preferred dividing seq that also satisfies the
    LANE-dim rule (multiple of 128, or the whole sequence), else 0.

    The whole-sequence case still requires the 8-sublane rule (the same
    block tiles q/k/v), so non-8-multiple sequences fall back like the
    non-segment path does.
    """
    if seq <= preferred:
        return seq if seq % _MIN_BLOCK == 0 else 0
    b = min(preferred, seq) // _LANES * _LANES
    while b >= _LANES and seq % b:
        b -= _LANES
    return max(b, 0)


# ---------------------------------------------------------------------------
# Reference (XLA) implementation -- ground truth + CPU fallback.
# ---------------------------------------------------------------------------

def attention_reference(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None,
                        segment_ids=None, kv_segment_ids=None):
    """Plain XLA attention. q,k,v: (batch, heads, seq, head_dim).

    Causal masking is bottom-right aligned: with ``tq < tk`` (decode with a
    KV cache), query ``i`` attends keys ``0 .. tk - tq + i``.

    ``segment_ids``/``kv_segment_ids`` (``(batch, tq)`` / ``(batch, tk)``
    int): a query attends only keys with an EQUAL segment id -- the
    packed-sequence convention (and padding isolation: give pad tokens a
    segment of their own).  A DEAD row (segment matches no key, i.e.
    pure padding) produces ZERO output and zero gradients, identical
    between this reference and the Pallas kernels.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, _NEG_INF)
    if segment_ids is not None:
        if kv_segment_ids is None:
            if q.shape[2] != k.shape[2]:
                raise ValueError("kv_segment_ids is required when "
                                 "tq != tk")
            kv_segment_ids = segment_ids
        seg = (segment_ids[:, None, :, None]
               == kv_segment_ids[:, None, None, :])
        logits = jnp.where(seg, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if segment_ids is not None:
        # DEAD rows (segment matches no key, e.g. padding): zero output
        # and zero gradients, matching the Pallas kernels -- not the
        # uniform softmax a plain -inf mask degenerates to.
        alive = jnp.max(logits, axis=-1, keepdims=True) > _NEG_INF / 2
        probs = jnp.where(alive, probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def decode_attention(q, k, v, *, lengths, scale: Optional[float] = None,
                     block_kv: int = DEFAULT_BLOCK_KV,
                     force_reference: bool = False):
    """Single-token decode attention over a length-masked KV cache.

    ``q``: ``(b, h, 1, d)`` -- the current token's query per slot.
    ``k``/``v``: ``(b, h_kv, s, d)`` -- the cache view, where only the
    first ``lengths[i]`` positions of row ``i`` hold live keys (anything
    beyond is recycled-page garbage and must not contribute).
    ``lengths``: ``(b,)`` int, live key count per row; a row with
    ``lengths == 0`` (an idle batch slot) produces EXACTLY zero output
    via the reference's dead-row convention.

    No causal mask is needed: the current token sits at position
    ``lengths - 1`` and every cached key is at a position ``< lengths``,
    so the length mask IS the bottom-right-aligned causal mask for a
    one-token query.

    Dispatch: the split-KV flash-decoding kernel when the ``flash_decode``
    family is enabled (``HOROVOD_PALLAS`` / ``HOROVOD_PALLAS_DECODE``) and
    the cache length has a block divisor; the XLA reference otherwise.
    The kernel grids over KV page-blocks with the grouped query heads of
    one kv head as the MXU tile, carrying online-softmax partials
    (running max / normalizer / accumulator) across the sequential block
    axis -- the log-sum-exp merge of the split-KV partials.  Pages past
    ``lengths`` are either whole-block predicated off or masked per
    column, so recycled-page garbage never contributes.
    """
    if q.shape[2] != 1:
        raise ValueError(f"decode_attention expects a single-token query, "
                         f"got tq={q.shape[2]}")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"query heads {q.shape[1]} not a multiple of "
                         f"kv heads {k.shape[1]}")
    if lengths.shape != (q.shape[0],):
        raise ValueError(f"lengths must be ({q.shape[0]},), got "
                         f"{lengths.shape}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = k.shape[2]
    bk = _block(s, block_kv)
    if (not force_reference and bk >= _MIN_BLOCK
            and _pallas.pallas_enabled("flash_decode")):
        return _flash_decode(q, k, v, lengths, float(scale), bk)
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    kv_seg = (jnp.arange(s)[None, :]
              < lengths[:, None]).astype(jnp.int32)
    q_seg = jnp.ones((q.shape[0], 1), jnp.int32)
    return attention_reference(q, k, v, causal=False, scale=scale,
                               segment_ids=q_seg, kv_segment_ids=kv_seg)


def verify_attention(q, k, v, *, lengths, scale: Optional[float] = None,
                     block_kv: int = DEFAULT_BLOCK_KV,
                     force_reference: bool = False):
    """Width-k verify attention: the speculative-decoding generalisation
    of :func:`decode_attention` to ``w`` draft positions per slot.

    ``q``: ``(b, h, w, d)`` -- query row ``i`` is the token being
    verified at absolute position ``lengths - 1 + i`` (row 0 is exactly
    the plain decode query).  ``k``/``v``: ``(b, h_kv, s, d)`` cache
    views that ALREADY hold the w in-step-written keys.  ``lengths``:
    ``(b,)`` live key count as seen by row 0 (pre-step length + 1);
    row ``i`` sees ``lengths + i`` keys -- the length mask doubles as
    the bottom-right-aligned causal mask across the draft window, the
    same argument that makes single-token decode mask-free.

    Implementation: one :func:`decode_attention` call per row, so every
    row's softmax runs the EXACT op shapes of the plain decode step --
    the greedy-exactness contract (speculative streams bitwise equal to
    plain decode) rides on row-for-row numerical identity, not on a
    reimplementation agreeing to tolerance.  ``w`` is the speculation
    width (small), so the unrolled loop costs w kernel calls inside one
    jitted step, not w dispatches.
    """
    w = q.shape[2]
    outs = []
    for i in range(w):
        li = jnp.where(lengths > 0,
                       jnp.minimum(lengths + i, k.shape[2]), 0)
        outs.append(decode_attention(
            q[:, :, i:i + 1, :], k, v, lengths=li, scale=scale,
            block_kv=block_kv, force_reference=force_reference))
    return jnp.concatenate(outs, axis=2)


# ---------------------------------------------------------------------------
# Flash-decoding: split-KV kernel for the single-token cache read.
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, bk, nk):
    """Grid ``(batch, kv_heads, kv_blocks)``; the last axis is sequential
    on TPU, so VMEM scratch carries the online-softmax state across KV
    blocks and the final block folds the partials -- the split-KV
    log-sum-exp merge without a second kernel launch.

    The q tile is the ``rep`` grouped query heads of this kv head
    (``(rep, d)``): decode has one token per slot, so the head group is
    the only MXU row dimension available.  Blocks wholly past
    ``lengths[b]`` are predicated off; the straddling block masks per
    column.  A dead slot (``lengths == 0``) runs no live block and
    finishes with ``l == 0`` -> exactly zero output.

    ``len_ref`` is the whole ``(batch,)`` lengths vector, scalar-
    prefetched into SMEM: a per-row scalar has no legal VMEM tile
    (Mosaic refuses a ``(1, 1)`` block of a ``(b, 1)`` array for b > 1).
    """
    ki = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * bk < length)
    def _step():
        qg = q_ref[0, 0].astype(jnp.float32)          # (rep, d)
        kb = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(qg, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, _NEG_INF)

        m_prev = m_scr[:, :1]                         # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (rep, bk)
        alpha = jnp.exp(m_prev - m_new)               # (rep, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        vb = v_ref[0, 0].astype(jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        o = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _flash_decode(q, k, v, lengths, scale: float, bk: int):
    """Split-KV decode dispatch: ``q (b, h, 1, d)``, ``k/v (b, h_kv, s,
    d)`` -> ``(b, h, 1, d)``.  GQA folds the query-head group onto the
    sublane axis (``q4[b, kv, rep, d]``) instead of repeating K/V in HBM,
    matching the training kernels' ``h // rep`` index-map broadcast."""
    b, h, _, d = q.shape
    h_kv, s = k.shape[1], k.shape[2]
    rep = h // h_kv
    nk = s // bk
    q4 = q.reshape(b, h_kv, rep, d)
    from ..controller import fusion as _fusion
    from ..timeline import spans as _spans
    _spans.note_leg(_fusion.plan_exchange(
        "kernel", kernel="flash_decode",
        nbytes=k.size * k.dtype.itemsize * 2).legs[0])
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk)
    with jax.named_scope("hvd_flash_decode"):
        o = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, h_kv, nk),
                in_specs=[
                    pl.BlockSpec((1, 1, rep, d),
                                 lambda bi, hi, j, lens: (bi, hi, 0, 0)),
                    pl.BlockSpec((1, 1, bk, d),
                                 lambda bi, hi, j, lens: (bi, hi, j, 0)),
                    pl.BlockSpec((1, 1, bk, d),
                                 lambda bi, hi, j, lens: (bi, hi, j, 0)),
                ],
                out_specs=pl.BlockSpec((1, 1, rep, d),
                                       lambda bi, hi, j, lens: (bi, hi, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((rep, _LANES), jnp.float32),
                    pltpu.VMEM((rep, _LANES), jnp.float32),
                    pltpu.VMEM((rep, d), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, h_kv, rep, d), q.dtype),
            name="hvd_flash_decode",
            interpret=_pallas.interpret_mode(),
        )(lengths.astype(jnp.int32), q4, k, v)
    return o.reshape(b, h, 1, d)


# ---------------------------------------------------------------------------
# Latent (MLA) decode: the absorbed form over a shared latent key.
# ---------------------------------------------------------------------------

# Pages a grid step of ``hvd_mla_decode`` copies and computes: 64 pages of
# 16 tokens are 1,024 keys a step, 1.3 MB a buffer at 640 columns.
MLA_PAGES_PER_BLOCK = 64


def mla_decode_attention(q, pool, page_table, *, layer, lengths,
                         value_dim: int, scale: float,
                         force_reference: bool = False):
    """Single-token decode attention in latent attention's ABSORBED form,
    read straight out of the page pool.

    ``q``: ``(b, h, w)`` -- each head's query against a cached row: its
    first ``value_dim`` columns already carried into the latent space
    (``q_nope @ W_kvb^K``), the rest its rotated part.  ``pool``:
    ``(layers, pages, page_size, w)``, the cache's one pool: a token's
    row is its normalised latent (``value_dim`` columns) and, beside it,
    the one rotated key ALL heads share.  ``layer``: the plane of the
    pool to read, a Python int (part of the kernel) or a traced int32
    scalar (a looped model reads plane ``pass * layers + layer`` from
    inside a rolled loop over its passes; the kernel then takes it as a
    scalar-prefetched operand).  ``page_table``: ``(b,
    pages_per_slot)`` int32, row ``i``'s pages in order; ``lengths``:
    ``(b,)`` live tokens of each row.  Scores are ``(q . row) * scale``
    over the whole row; the values are the row's first ``value_dim``
    columns, so the result is ``(b, h, value_dim)`` float32 in the latent
    space (the caller carries it out through ``W_kvb^V``).  A row with
    ``lengths == 0`` gives exactly zero.

    Dispatch as :func:`decode_attention`: the ``hvd_mla_decode`` kernel
    where the ``mla_decode`` family is on, ``jax.numpy`` over a gathered
    view otherwise.  The kernel WALKS THE PAGE TABLE: no view of a slot's
    pages is ever materialised.  Its grid is the list of live (row, block
    of ``MLA_PAGES_PER_BLOCK`` pages) items; each step copies its block's
    live pages from the pool into VMEM by their scalar-prefetched ids
    while the step before computes (two buffers), takes the ``h`` heads
    as the MXU tile's rows (all of them share the key, as one GQA group
    does) and uses the block once as key and, its first columns, as
    value.  Pages past ``lengths`` are neither copied nor computed."""
    b, h, w = q.shape
    if pool.ndim != 4 or pool.shape[3] != w or not 0 < value_dim <= w \
            or page_table.shape[0] != b:
        raise ValueError(
            f"mla_decode_attention: q {q.shape}, pool {pool.shape}, "
            f"page_table {page_table.shape} and value_dim {value_dim} do "
            "not fit together")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be ({b},), got {lengths.shape}")
    if not force_reference and _pallas.pallas_enabled("mla_decode"):
        return _mla_decode(q, pool, page_table, lengths, layer,
                           value_dim, float(scale), MLA_PAGES_PER_BLOCK)
    s = page_table.shape[1] * pool.shape[2]
    kv = pool[layer, page_table].reshape(b, s, w).astype(q.dtype)
    logits = jnp.einsum("bhw,bsw->bhs", q, kv,
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(s)[None, None, :] < lengths[:, None, None]
    logits = jnp.where(live, logits, _NEG_INF)
    probs = jnp.where(live, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("bhs,bsr->bhr", probs.astype(kv.dtype),
                      kv[..., :value_dim],
                      preferred_element_type=jnp.float32)


def _mla_decode_kernel(len_ref, table_ref, slot_ref, blk_ref, n_ref,
                       *refs, plane, scale, ppb, page, value_dim,
                       kv_heads=1, value_off=0):
    """Grid ``(items,)``, sequential: item ``i`` is block ``blk_ref[i]``
    (``ppb`` pages) of row ``slot_ref[i]``; the first ``n_ref[0]`` items
    are live, a row's items follow one another, and the online-softmax
    state lives in VMEM scratch across them, as in ``_decode_kernel``.
    While item ``i`` computes out of one half of ``buf``, the pages of
    item ``i + 1`` are on their way into the other.  Every page is read
    from plane ``plane`` of the pool: a Python int, part of the program
    (a DMA's address then costs the scalar core nothing: measured, 7% of
    this kernel's time over 1 KB rows), or None, and then a sixth
    prefetched scalar before ``q_ref`` names it.  The operands go to the
    MXU in their own type (bfloat16 on the chip) with float32
    accumulation.

    ``kv_heads == 1``: every query head reads the whole row as its key
    and the row's first ``value_dim`` columns as its value (the latent).
    ``kv_heads > 1``: the row is ``kv_heads`` keys of the queries' width
    side by side and, from column ``value_off``, as many values of
    ``value_dim``; query head ``i`` belongs to key/value head ``i //
    (heads / kv_heads)``.  EVERY query head is multiplied against each
    key/value head's tile-aligned columns and a row mask keeps its own:
    the MXU's cost is the key tiles it is loaded with, not the 8 rows
    that stream past them, and no array is ever cut below a tile."""
    *plane_ref, q_ref, pool_ref, o_ref, buf, sem, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(0)
    n_items = n_ref[0]
    layer = plane_ref[0][0] if plane is None else plane
    bk = ppb * page
    heads, dk = q_ref.shape[1], q_ref.shape[2]
    rep = heads // kv_heads

    def own(parts):
        """Row ``i`` of part ``i // rep``."""
        if kv_heads == 1:
            return parts[0]
        head = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
        out = parts[-1]
        for j in range(kv_heads - 2, -1, -1):
            out = jnp.where(head < (j + 1) * rep, parts[j], out)
        return out

    def live_pages(item):
        left = len_ref[slot_ref[item]] - blk_ref[item] * bk
        return jnp.clip((left + page - 1) // page, 0, ppb)

    def start(item, half):
        first = blk_ref[item] * ppb
        row = slot_ref[item]

        def one(j, carry):
            pltpu.make_async_copy(
                pool_ref.at[layer, table_ref[row, first + j]],
                buf.at[half, j], sem.at[half]).start()
            return carry

        jax.lax.fori_loop(0, live_pages(item), one, 0)

    def wait(item, half):
        def one(j, carry):
            pltpu.make_async_copy(pool_ref.at[layer, 0], buf.at[half, 0],
                                  sem.at[half]).wait()
            return carry

        jax.lax.fori_loop(0, live_pages(item), one, 0)

    @pl.when(i == 0)
    def _first():
        # Pages a block does not copy keep what the buffer held: masked
        # below, but never a NaN's bit pattern.
        buf[...] = jnp.zeros_like(buf)
        start(0, 0)

    @pl.when(i < n_items)
    def _item():
        half = i % 2

        @pl.when(i + 1 < n_items)
        def _next():
            start(i + 1, 1 - half)

        wait(i, half)
        blk = blk_ref[i]
        length = len_ref[slot_ref[i]]

        @pl.when(blk == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        kv = buf[half].reshape(bk, buf.shape[-1])     # (bk, w)
        s = own([jax.lax.dot_general(
            q_ref[0], kv if kv_heads == 1 else kv[:, j * dk:(j + 1) * dk],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for j in range(kv_heads)]
        ) * scale
        cols = blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = cols < length
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_scr[:, :1]                         # (h, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)  # (h, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + own([jax.lax.dot_general(
            p.astype(kv.dtype),
            kv[:, value_off + j * value_dim:value_off + (j + 1) * value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for j in range(kv_heads)])
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when((blk + 1) * bk >= length)
        def _finish():
            l = l_scr[:, :1]
            o_ref[0] = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)


def _mla_decode(q, pool, page_table, lengths, layer, value_dim: int,
                scale: float, ppb: int, *, kv_heads: int = 1,
                value_off: int = 0, name: str = "hvd_mla_decode"):
    b, h, dk = q.shape
    w = pool.shape[3]
    page = pool.shape[2]
    pps = page_table.shape[1]
    ppb = min(ppb, pps)
    bk = ppb * page
    per_row = -(-pps // ppb)
    lengths = lengths.astype(jnp.int32)
    # The live items: every row has one at least (an idle row's only
    # item copies nothing and writes its zeros), a live row one a block
    # that holds a live token.  ``items`` is the static bound.
    blocks = jnp.maximum((lengths + bk - 1) // bk, 1)
    ends = jnp.cumsum(blocks)
    items = b * per_row
    ids = jnp.arange(items, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, ids, side="right"),
                       b - 1).astype(jnp.int32)
    blk = jnp.clip(ids - (ends - blocks)[slot], 0, per_row - 1).astype(
        jnp.int32)
    n_items = ends[-1:].astype(jnp.int32)
    if pps % ppb:
        # The last block of a full row reads table entries past its end.
        page_table = jnp.pad(page_table, ((0, 0), (0, per_row * ppb - pps)))

    def row(i, lens, table, slots, blks, n, *plane):
        # Items past the last live one name its row again: nothing moves.
        return slots[jnp.minimum(i, n[0] - 1)], 0, 0

    # A plane known when the program is built is part of it; a traced one
    # (a looped model's ``pass * layers + layer``) rides as a scalar.
    traced = (layer.astype(jnp.int32).reshape(1),) \
        if isinstance(layer, jax.Array) else ()
    kernel = functools.partial(_mla_decode_kernel, scale=scale, ppb=ppb,
                               page=page, value_dim=value_dim,
                               plane=None if traced else int(layer))
    if kv_heads > 1:
        kernel = functools.partial(kernel, kv_heads=kv_heads,
                                   value_off=value_off)
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5 + len(traced),
                grid=(items,),
                in_specs=[pl.BlockSpec((1, h, dk), row),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, h, value_dim), row),
                scratch_shapes=[
                    pltpu.VMEM((2, ppb, page, w), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.VMEM((h, _LANES), jnp.float32),
                    pltpu.VMEM((h, _LANES), jnp.float32),
                    pltpu.VMEM((h, value_dim), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, h, value_dim), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name=name,
            interpret=_pallas.interpret_mode(),
        )(lengths, page_table.astype(jnp.int32), slot, blk, n_items,
          *traced, q.astype(pool.dtype), pool)


def cca_decode_attention(q, pool, page_table, *, layer, lengths,
                         kv_heads: int, scale: float,
                         force_reference: bool = False):
    """Single-token grouped-query decode attention over rows that hold
    every key/value head side by side, read straight out of the page
    pool.

    ``q``: ``(b, h, d)``; ``pool``: ``(layers, pages, page_size, 2 *
    kv_heads * d)``, a token's row ``[k_0 .. k_{kv-1} | v_0 .. v_{kv-1}]``
    with no head dim (a ``(kv_heads, d)`` entry of two heads would be
    padded fourfold by the (8, 128) tiling); ``layer``, ``page_table``,
    ``lengths`` as :func:`mla_decode_attention`.  Query head ``i`` attends to
    key/value head ``i // (h / kv_heads)``; the result is ``(b, h, d)``
    float32, exactly zero for a row with ``lengths == 0``.

    The kernel is ``hvd_mla_decode``'s walk of the page table under the
    name ``hvd_cca_decode`` (same family switch): one copy of a block's
    live pages serves every head, keys and values alike."""
    b, h, d = q.shape
    if pool.ndim != 4 or pool.shape[3] != 2 * kv_heads * d \
            or h % kv_heads or page_table.shape[0] != b:
        raise ValueError(
            f"cca_decode_attention: q {q.shape}, pool {pool.shape}, "
            f"page_table {page_table.shape} and kv_heads {kv_heads} do not "
            "fit together")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be ({b},), got {lengths.shape}")
    if not force_reference and _pallas.pallas_enabled("mla_decode"):
        return _mla_decode(q, pool, page_table, lengths, layer, d,
                           float(scale), MLA_PAGES_PER_BLOCK,
                           kv_heads=kv_heads, value_off=kv_heads * d,
                           name="hvd_cca_decode")
    s = page_table.shape[1] * pool.shape[2]
    kv = pool[layer, page_table].reshape(b, s, 2, kv_heads, d).astype(
        q.dtype)
    qg = q.reshape(b, kv_heads, h // kv_heads, d)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg, kv[:, :, 0],
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(live, logits, _NEG_INF)
    probs = jnp.where(live, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("bgrs,bsgd->bgrd", probs.astype(kv.dtype),
                      kv[:, :, 1],
                      preferred_element_type=jnp.float32).reshape(b, h, d)


def _causal_mask(s, qi, ki, bq, bk, off):
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + off
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


def _seg_mask(s, qseg_ref, kseg_ref):
    """Mask logits where query/key segment ids differ (refs hold the
    ``(1, bq)`` / ``(1, bk)`` id blocks for this grid cell)."""
    qs = qseg_ref[0, 0][:, None]                  # (bq, 1)
    ks = kseg_ref[0, 0][None, :]                  # (1, bk)
    return jnp.where(qs == ks, s, _NEG_INF)


def _seg_live(live, qseg_ref, kseg_ref):
    """Combine the causal block-liveness predicate with a dynamic
    segment-range test: a q block and a kv block with disjoint
    [min, max] id ranges share NO equal pair for ANY id layout, so the
    whole block is skippable (the splash-attention pruning).  Sortedness
    is NOT a correctness precondition -- sorted packed ids merely make
    per-block ranges tight, maximising how often pruning fires.
    Skipping is numerically exact: a processed all-masked block only
    ever contributes alpha-erased garbage (before any live block) or
    p = 0 terms (after one), and the all-skipped dead-row case is
    handled by the _finish zeroing.
    """
    qs = qseg_ref[0, 0]
    ks = kseg_ref[0, 0]
    overlap = ((jnp.min(qs) <= jnp.max(ks))
               & (jnp.max(qs) >= jnp.min(ks)))
    return overlap if live is True else live & overlap


# ---------------------------------------------------------------------------
# Forward kernel.
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, has_seg,
                bq, bk, nk, off):
    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: block is live unless it lies entirely above the diagonal;
    # with segment ids, also unless the blocks' id ranges are disjoint
    # (dynamic predicate -- packed ids are sorted, so this prunes every
    # cross-sequence block).
    live = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)
    if has_seg:
        live = _seg_live(live, qseg_ref, kseg_ref)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk, off)
        if has_seg:
            s = _seg_mask(s, qseg_ref, kseg_ref)

        m_prev = m_scr[:, :1]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = acc_scr[:] / l_safe
        lse = m_scr[:, :1] + jnp.log(l_safe)
        if has_seg:
            # DEAD rows (m never rose above the mask floor): zero the
            # output, and push lse to +BIG so both backward kernels'
            # p = exp(s - lse) underflows to exactly 0 -- without this,
            # f32 absorbs log(l) into -1e30 and the backward sees
            # p = 1 PER KEY (a ~tk-fold gradient explosion on pad rows;
            # caught by review, regression-tested).
            dead = m_scr[:, :1] <= _NEG_INF / 2
            o = jnp.where(dead, 0.0, o)
            lse = jnp.where(dead, -_NEG_INF, lse)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[-2:])


def _flash_fwd(q, k, v, qseg, kseg, *, scale, causal, bq, bk):
    batch, heads, tq, d = q.shape
    tk = k.shape[2]
    rep = heads // k.shape[1]
    bq = _block(tq, bq)
    bk = _block(tk, bk)
    nq, nk = tq // bq, tk // bk
    off = tk - tq
    grid = (batch, heads, nq, nk)
    has_seg = qseg is not None
    path, group = _flash_path(q, k, has_seg=has_seg, bq=bq, bk=bk)
    if path == "head_group":
        return _hg_fwd(q, k, v, scale=scale, causal=causal, group=group)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_seg=has_seg, bq=bq, bk=bk, nk=nk,
                               off=off)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j: (b, h // rep, j, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j: (b, h // rep, j, 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        # (batch, 1, t) with a (1, 1, block) spec: the sublane block dim
        # equals the array dim (Mosaic's last-two-dims rule); the lane
        # dim must divide by 128 or equal t (dispatcher guarantees it).
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
        ]
        operands += [qseg[:, None, :], kseg[:, None, :]]
    with jax.named_scope("hvd_flash_fwd"):
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, _LANES),
                             lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((batch, heads, tq, _LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            name="hvd_flash_fwd",
            interpret=_pallas.interpret_mode(),
        )(*operands)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward kernels.
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, has_seg, bq, bk, nk, off):
    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)
    if has_seg:
        live = _seg_live(live, qseg_ref, kseg_ref)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk, off)
        if has_seg:
            s = _seg_mask(s, qseg_ref, kseg_ref)
        p = jnp.exp(s - lse)                               # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, has_seg, bq, bk, nq, off):
    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        qseg_ref = kseg_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = True if not causal else (qi * bq + bq - 1 + off >= ki * bk)
    if has_seg:
        live = _seg_live(live, qseg_ref, kseg_ref)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk, off)
        if has_seg:
            s = _seg_mask(s, qseg_ref, kseg_ref)
        p = jnp.exp(s - lse)                               # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # (bq, bk)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:]
        dv_ref[0, 0] = dv_scr[:]


def _flash_bwd(res, g, *, scale, causal, bq, bk):
    q, k, v, o, lse, qseg, kseg = res
    batch, heads, tq, d = q.shape
    h_kv, tk = k.shape[1], k.shape[2]
    rep = heads // h_kv
    bq = _block(tq, bq)
    bk = _block(tk, bk)
    nq, nk = tq // bq, tk // bk
    off = tk - tq
    has_seg = qseg is not None
    path, group = _flash_path(q, k, has_seg=has_seg, bq=bq, bk=bk)
    if path == "head_group":
        return _hg_bwd(q, k, v, lse, g, scale=scale, causal=causal,
                       group=group)

    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_t = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta_t = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    stat_spec_q = pl.BlockSpec((1, 1, bq, _LANES),
                               lambda b, h, i, j: (b, h, i, 0))

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j: (b, h // rep, j, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j: (b, h // rep, j, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        stat_spec_q,
        stat_spec_q,
    ]
    dq_operands = [q, k, v, g, lse_t, delta_t]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
        ]
        dq_operands += [qseg[:, None, :], kseg[:, None, :]]
    with jax.named_scope("hvd_flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              has_seg=has_seg, bq=bq, bk=bk, nk=nk, off=off),
            grid=(batch, heads, nq, nk),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b, h, i, j: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            name="hvd_flash_bwd_dq",
            interpret=_pallas.interpret_mode(),
        )(*dq_operands)

    # dk/dv at *query*-head granularity in f32 (per-group partials), group-
    # summed outside the kernel; transient only -- forward K/V are never
    # materialized per query head.
    stat_spec_kq = pl.BlockSpec((1, 1, bq, _LANES),
                                lambda b, h, j, i: (b, h, i, 0))
    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, j, i: (b, h // rep, j, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, j, i: (b, h // rep, j, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0)),
        stat_spec_kq,
        stat_spec_kq,
    ]
    dkv_operands = [q, k, v, g, lse_t, delta_t]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, h, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j)),
        ]
        dkv_operands += [qseg[:, None, :], kseg[:, None, :]]
    with jax.named_scope("hvd_flash_bwd_dkv"):
        dk_h, dv_h = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              has_seg=has_seg, bq=bq, bk=bk, nq=nq, off=off),
            grid=(batch, heads, nk, nq),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((batch, heads, tk, d), jnp.float32),
                jax.ShapeDtypeStruct((batch, heads, tk, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            name="hvd_flash_bwd_dkv",
            interpret=_pallas.interpret_mode(),
        )(*dkv_operands)
    if rep > 1:
        dk_h = dk_h.reshape(batch, h_kv, rep, tk, d).sum(axis=2)
        dv_h = dv_h.reshape(batch, h_kv, rep, tk, d).sum(axis=2)
    return dq, dk_h.astype(k.dtype), dv_h.astype(v.dtype)


# ---------------------------------------------------------------------------
# Head-group kernels: a sequence that one block holds.
# ---------------------------------------------------------------------------

# What a grid step of the head-group kernels may hold in VMEM, counted by
# ``_head_group_bytes``: 10 of the 16 MiB a Mosaic kernel gets on a v5e,
# the rest is the compiler's for what it spills.
_HEAD_GROUP_VMEM_BUDGET = 10 * 1024 * 1024


def _head_group_bytes(g_kv: int, rep: int, tq: int, tk: int, d: int,
                      itemsize: int) -> int:
    """VMEM bytes a grid step over ``g_kv`` kv heads (``g_kv * rep`` query
    heads) holds in the backward kernel, the larger of the two: q, do, dq
    and k, v, dk, dv tiles, each twice (the pipeline's double buffer) and
    with the head dim padded to the 128 lanes; a statistics row a query
    head; and the score-shaped float32 tiles of the one head being
    computed (s/p, dp, ds and the transposed copy the dq matmul takes)."""
    lanes = -(-d // _LANES) * _LANES
    tq_lanes = -(-tq // _LANES) * _LANES
    tiles = g_kv * (3 * rep * tq + 4 * tk) * lanes * itemsize
    stats = g_kv * rep * _MIN_BLOCK * tq_lanes * 4
    scores = 4 * tk * tq_lanes * 4
    return 2 * (tiles + stats) + scores


def _head_group(heads: int, kv_heads: int, tq: int, tk: int, d: int,
                itemsize: int) -> int:
    """Query heads a grid step of the head-group kernels takes: whole
    kv-head groups, the largest divisor of ``kv_heads`` whose working set
    is within ``_HEAD_GROUP_VMEM_BUDGET``; 0 when one kv head's group
    alone is over it (the blocked kernels run).  A function of the
    shapes alone."""
    rep = heads // kv_heads
    for g_kv in range(kv_heads, 0, -1):
        if kv_heads % g_kv == 0 and _head_group_bytes(
                g_kv, rep, tq, tk, d, itemsize) <= _HEAD_GROUP_VMEM_BUDGET:
            return g_kv * rep
    return 0


def _flash_path(q, k, *, has_seg: bool, bq: int, bk: int):
    """``("head_group", G)`` when one block holds the query sequence and
    one the keys, no segment ids ride along and a group fits VMEM;
    ``("blocked", 0)`` (the online-softmax kernels) otherwise.  Decided
    at trace time from shapes; logged once a lowering."""
    tq, tk = q.shape[2], k.shape[2]
    group = 0
    if not has_seg and _block(tq, bq) == tq and _block(tk, bk) == tk:
        group = _head_group(q.shape[1], k.shape[1], tq, tk, q.shape[3],
                            q.dtype.itemsize)
    path = ("head_group", group) if group else ("blocked", 0)
    logger.debug("flash attention q%s k%s blocks (%d, %d): %s kernels, "
                 "%d heads a grid step", q.shape, k.shape, bq, bk, *path)
    return path


def _scores_t(q, k, *, scale, causal, off):
    """The score tile TRANSPOSED, ``(tk, tq)``: keys on the sublanes and
    queries on the lanes, so a query's statistics are one lane of a
    ``(1, tq)`` row.  q and k go to the MXU in the type they arrive in
    (a bfloat16 product is exact in float32)."""
    s_t = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * scale
    if causal:
        keys = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
        queries = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1) + off
        s_t = jnp.where(queries >= keys, s_t, _NEG_INF)
    return s_t


def _hg_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                   rep, off):
    """Grid ``(batch, head groups)``: a plain softmax a head over the one
    block, no state carried.  Every query sees a key (causal is
    bottom-right aligned and ``tq <= tk``), so no row is dead."""
    for j in range(k_ref.shape[1]):
        k, v = k_ref[0, j], v_ref[0, j].astype(jnp.float32)
        for g in range(j * rep, (j + 1) * rep):
            s_t = _scores_t(q_ref[0, g], k, scale=scale, causal=causal,
                            off=off)
            m = jnp.max(s_t, axis=0, keepdims=True)           # (1, tq)
            p_t = jnp.exp(s_t - m)                            # (tk, tq)
            l = jnp.sum(p_t, axis=0, keepdims=True)
            o = jax.lax.dot_general(
                p_t * (1.0 / l), v, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, g] = o.astype(o_ref.dtype)
            lse_ref[0, g] = m + jnp.log(l)


def _hg_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                   dq_ref, dk_ref, dv_ref, *, scale, causal, rep, off):
    """dq, dk and dv of a head group in one pass over its score tiles.
    With the whole row of keys in one tile, softmax's backward is the
    textbook one: ``delta = sum_k P * dP``, a sublane reduction of two
    float32 tiles the kernel holds anyway (equal to the blocked kernels'
    ``rowsum(dO * O)``, which needs O and a pass over all kv blocks).  A
    kv head's ``rep`` query heads sum into its dk/dv here, so all three
    leave in the operands' type."""
    f32 = jnp.float32
    for j in range(k_ref.shape[1]):
        k, v = k_ref[0, j], v_ref[0, j]
        dk = dv = None
        for g in range(j * rep, (j + 1) * rep):
            q, do = q_ref[0, g], do_ref[0, g]
            s_t = _scores_t(q, k, scale=scale, causal=causal, off=off)
            p_t = jnp.exp(s_t - lse_ref[0, g])                # (tk, tq)
            dp_t = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                       preferred_element_type=f32)
            delta = jnp.sum(p_t * dp_t, axis=0, keepdims=True)  # (1, tq)
            ds_t = p_t * (dp_t - delta) * scale
            dv_g = jnp.dot(p_t, do.astype(f32), preferred_element_type=f32)
            dk_g = jnp.dot(ds_t, q.astype(f32), preferred_element_type=f32)
            dv = dv_g if dv is None else dv + dv_g
            dk = dk_g if dk is None else dk + dk_g
            dq = jax.lax.dot_general(ds_t, k.astype(f32),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=f32)
            dq_ref[0, g] = dq.astype(dq_ref.dtype)
        dk_ref[0, j] = dk.astype(dk_ref.dtype)
        dv_ref[0, j] = dv.astype(dv_ref.dtype)


def _hg_specs(group, rep, tq, tk, d):
    q_spec = pl.BlockSpec((1, group, tq, d), lambda b, g: (b, g, 0, 0))
    kv_spec = pl.BlockSpec((1, group // rep, tk, d),
                           lambda b, g: (b, g, 0, 0))
    # One (1, tq) row a head, the queries on the lanes.
    lse_spec = pl.BlockSpec((1, group, 1, tq), lambda b, g: (b, g, 0, 0))
    return q_spec, kv_spec, lse_spec


# The head loop is unrolled (a loop inside the kernel costs 2-3x on the
# chip: nothing overlaps across its iterations), so a kernel body is G
# heads long.  Under ``jax.jit`` a model's layers share ONE trace and one
# lowered function of it; XLA inlines the calls.
@functools.partial(jax.jit, static_argnames=("scale", "causal", "group"))
def _hg_fwd(q, k, v, *, scale, causal, group):
    batch, heads, tq, d = q.shape
    tk = k.shape[2]
    rep = heads // k.shape[1]
    q_spec, kv_spec, lse_spec = _hg_specs(group, rep, tq, tk, d)
    with jax.named_scope("hvd_flash_hg_fwd"):
        o, lse = pl.pallas_call(
            functools.partial(_hg_fwd_kernel, scale=scale, causal=causal,
                              rep=rep, off=tk - tq),
            grid=(batch, heads // group),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((batch, heads, 1, tq), jnp.float32),
            ],
            name="hvd_flash_hg_fwd",
            interpret=_pallas.interpret_mode(),
        )(q, k, v)
    return o, lse[:, :, 0]


@functools.partial(jax.jit, static_argnames=("scale", "causal", "group"))
def _hg_bwd(q, k, v, lse, g, *, scale, causal, group):
    batch, heads, tq, d = q.shape
    tk = k.shape[2]
    rep = heads // k.shape[1]
    q_spec, kv_spec, lse_spec = _hg_specs(group, rep, tq, tk, d)
    with jax.named_scope("hvd_flash_hg_bwd"):
        return pl.pallas_call(
            functools.partial(_hg_bwd_kernel, scale=scale, causal=causal,
                              rep=rep, off=tk - tq),
            grid=(batch, heads // group),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec],
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            name="hvd_flash_hg_bwd",
            interpret=_pallas.interpret_mode(),
        )(q, k, v, g, lse[:, :, None, :])



# ---------------------------------------------------------------------------
# custom_vjp wrapper + public API.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, bq, bk):
    o, _ = _flash_fwd(q, k, v, None, None, scale=scale, causal=causal,
                      bq=bq, bk=bk)
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, None, None, scale=scale, causal=causal,
                        bq=bq, bk=bk)
    return o, (q, k, v, o, lse, None, None)


def _flash_vjp_bwd(scale, causal, bq, bk, res, g):
    return _flash_bwd(res, g, scale=scale, causal=causal, bq=bq, bk=bk)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# Segment-id variant: ids are integer primal operands (traced arrays), so
# they ride the custom_vjp as primals with float0 cotangents.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_seg(q, k, v, qseg, kseg, scale, causal, bq, bk):
    o, _ = _flash_fwd(q, k, v, qseg, kseg, scale=scale, causal=causal,
                      bq=bq, bk=bk)
    return o


def _flash_seg_vjp_fwd(q, k, v, qseg, kseg, scale, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, qseg, kseg, scale=scale, causal=causal,
                        bq=bq, bk=bk)
    return o, (q, k, v, o, lse, qseg, kseg)


def _flash_seg_vjp_bwd(scale, causal, bq, bk, res, g):
    dq, dk, dv = _flash_bwd(res, g, scale=scale, causal=causal,
                            bq=bq, bk=bk)
    qseg, kseg = res[5], res[6]
    # Integer primals take float0 cotangents (jax custom_vjp contract).
    zq = jnp.zeros(qseg.shape, jax.dtypes.float0)
    zk = jnp.zeros(kseg.shape, jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_flash_seg.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids=None, kv_segment_ids=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV,
                    force_reference: bool = False):
    """Fused attention. q: (b, h, t, d); k, v: (b, h_kv, s, d).

    ``h_kv`` may divide ``h`` (grouped-query attention); kv heads are
    broadcast to query heads via the kernel block index map (no HBM copy).
    ``causal=True`` requires ``t <= s`` and masks bottom-right aligned.

    ``segment_ids`` (``(b, t)`` int) restricts each query to keys with an
    EQUAL id -- packed-sequence training and padding isolation (give pad
    tokens their own id; their DEAD rows produce zero output and zero
    gradients).  ``kv_segment_ids`` (``(b, s)``) defaults to
    ``segment_ids`` when the key sequence has the same length; it is
    required for cross-length attention.  Composes with ``causal``.

    Dispatch: Pallas kernels when running on TPU (or ``HOROVOD_PALLAS=1``
    / ``HOROVOD_PALLAS_FLASH=1``, which use the interpreter off-TPU --
    slow, for tests; the legacy ``HVD_TPU_FLASH`` is still honored with a
    deprecation note), XLA reference otherwise.  Sequence lengths with no
    block-divisor >= 8 (e.g. primes) fall back to the reference
    implementation.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"query heads {q.shape[1]} not a multiple of "
                         f"kv heads {k.shape[1]}")
    if causal and q.shape[2] > k.shape[2]:
        raise ValueError(
            f"causal attention requires tq <= tk, got {q.shape[2]} > "
            f"{k.shape[2]}")
    if block_q < _MIN_BLOCK or block_kv < _MIN_BLOCK:
        raise ValueError(f"block_q/block_kv must be >= {_MIN_BLOCK}, got "
                         f"{block_q}/{block_kv}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    tq, tk = q.shape[2], k.shape[2]
    if segment_ids is not None:
        if kv_segment_ids is None:
            if tq != tk:
                raise ValueError(
                    "kv_segment_ids is required when tq != tk "
                    f"({tq} != {tk})")
            kv_segment_ids = segment_ids
        if segment_ids.shape != (q.shape[0], tq):
            raise ValueError(f"segment_ids must be (batch, {tq}), got "
                             f"{segment_ids.shape}")
        if kv_segment_ids.shape != (q.shape[0], tk):
            raise ValueError(f"kv_segment_ids must be (batch, {tk}), got "
                             f"{kv_segment_ids.shape}")
        segment_ids = segment_ids.astype(jnp.int32)
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)
    elif kv_segment_ids is not None:
        raise ValueError("kv_segment_ids given without segment_ids")
    if segment_ids is None:
        rbq, rbk = _block(tq, block_q), _block(tk, block_kv)
        usable_blocks = rbq >= _MIN_BLOCK and rbk >= _MIN_BLOCK
    else:
        # Segment-id blocks put the sequence on the LANE dim, so Mosaic
        # needs each block to divide by 128 or span the whole sequence;
        # search for a conforming divisor (e.g. tq=1920 -> 384) rather
        # than falling back to the O(t^2) reference.
        rbq = _block_lane(tq, block_q)
        rbk = _block_lane(tk, block_kv)
        usable_blocks = rbq >= _MIN_BLOCK and rbk >= _MIN_BLOCK
        block_q, block_kv = rbq, rbk
    if force_reference or not usable_blocks or not _use_pallas():
        if q.shape[1] != k.shape[1]:
            rep = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return attention_reference(q, k, v, causal=causal, scale=scale,
                                   segment_ids=segment_ids,
                                   kv_segment_ids=kv_segment_ids)
    if segment_ids is not None:
        return _flash_seg(q, k, v, segment_ids, kv_segment_ids,
                          float(scale), bool(causal),
                          int(block_q), int(block_kv))
    return _flash(q, k, v, float(scale), bool(causal),
                  int(block_q), int(block_kv))
