"""Functional Llama prefill / tensor-parallel incremental decode.

:class:`~horovod_tpu.models.transformer.LlamaLM` is a flax module built
for training; serving needs the SAME math refactored into two functional
entry points that thread a paged KV cache instead of re-reading the whole
context every token:

* :func:`prefill_forward` -- full-context forward over a prompt that also
  returns the per-layer post-RoPE K/V ready to scatter into the cache
  (replicated; prompt work is compute-bound and tiny next to decode).
* :func:`build_decode_step` -- a single-token batched decode step
  compiled as ``jit(shard_map(...))`` over a named ``tp`` mesh.  Head
  projections are column-parallel, the ``wo``/``w_down`` closures
  row-parallel via :func:`horovod_tpu.parallel.tp.row_parallel`, so every
  activation collective is a ``collectives.ops.allreduce`` -- visible to
  the fusion planner, registered with the span recorder at trace time
  (:func:`~horovod_tpu.timeline.spans.note_leg`), and priced by the
  static auditor through the ``_meta`` dict the returned wrapper carries
  (the ``_InstrumentedStep`` convention).

Every cast mirrors ``models/transformer.py`` operation-for-operation
(``Dense`` computes ``x.astype(dtype) @ kernel.astype(dtype)``, RMSNorm
normalizes in f32, RoPE rotates in f32, the tied-embedding readout runs
in f32), so incremental decode matches the flax full-context forward to
float tolerance -- the tentpole parity contract.

Multi-LoRA: ``stack_adapters`` packs N trained adapter trees into banked
``[n_adapters, ...]`` leaves; the decode step then gathers each slot's
adapter pair by a per-slot ``adapter_ids`` operand INSIDE the step, so
one base model serves heterogeneous adapters in one decode batch
(tensor-parallel meshes decline the banks -- adapters stay tp=1).

How the step reads the cache.  A token's K and V are one row each of
``num_kv_heads * head_dim`` columns in ``k_pool`` and ``v_pool``
(``[layers, pages + 1, page_size, row]``, no head dim).  The one-token
step over uncompressed pools WALKS THE PAGE TABLE
(:func:`~horovod_tpu.ops.attention.cca_decode_attention` over both
pools): the live pages of every slot are copied out of the whole pools
by their ids, no ``pool[layer]``, no gathered slot view, no transpose,
no padded keys.  The verify step and the fp8 path gather the slot view
(``[slots, max_len, row]``, a reshape away from heads) for
:func:`~horovod_tpu.ops.attention.verify_attention` /
:func:`~horovod_tpu.ops.attention.decode_attention`.  Which of the two
a step does follows from what it is built for (``width``,
``compress``), and its ``_meta["attention"]`` says it: ``"walk"`` or
``"view"``.

Shared read-only pages (PR 16): the decode step never sees page
ownership -- it reads K/V through the slot's ``page_table`` row and
masks positions at or beyond ``lengths[slot]``, so two slots whose
table rows point at the SAME physical page (a radix prefix-cache hit)
compute bitwise-identical attention to two slots holding private
copies: identical bytes in, identical gather/mask/matmul, identical
logits out.  Isolation is therefore the cache's contract, not the
step's: decode writes always scatter at ``lengths[slot]`` (past any
shared prefix, which is page-aligned and shorter than the prompt), and
any write that WOULD land inside a shared page is preceded by a
copy-on-write clone in ``PagedKVCache.reserve(..., writable_from=)``.
The shared-page bitwise proof lives next to the eviction/reuse proof in
``test_slot_eviction_reuse_no_stale_attention_mass``.
"""

from __future__ import annotations

import time as _time
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models.transformer import LlamaConfig, rotary_embedding
from ..ops import pallas as _pallas
from ..ops.attention import (cca_decode_attention, decode_attention,
                             flash_attention, verify_attention)
from ..parallel.tp import row_parallel
from ..timeline import spans as _spans

TP_AXIS = "tp"

_COLUMN_KEYS = ("wq", "wk", "wv", "w_gate", "w_up")
_ROW_KEYS = ("wo", "w_down")


# ---------------------------------------------------------------------------
# Shared math (the Dense/RMSNorm mirror).
# ---------------------------------------------------------------------------


def _dense(x, node, dtype, *, lora_select=None, lora_alpha=16.0):
    """``Dense.__call__`` replayed over a raw param node.

    ``lora_select``: optional ``(a, b)`` adapter pair already gathered
    for this call -- either a plain ``[d_in, r]/[r, d_out]`` pair (one
    adapter) or per-slot ``[s, d_in, r]/[s, r, d_out]`` banks.
    """
    y = x.astype(dtype) @ node["kernel"].astype(dtype)
    if lora_select is not None:
        a, b = lora_select
        r = a.shape[-1]
        scale = jnp.asarray(lora_alpha / r, dtype)
        if a.ndim == 2:
            y = y + (x.astype(dtype) @ a.astype(dtype)
                     @ b.astype(dtype)) * scale
        else:
            # Per-slot banks: slot s uses its own (a[s], b[s]).
            t = jnp.einsum("sqd,sdr->sqr", x.astype(dtype),
                           a.astype(dtype))
            y = y + jnp.einsum("sqr,sro->sqo", t, b.astype(dtype)) * scale
    return y


def one_trace(layer):
    """``layer``, the body of one layer of a prefill, as the Python loop
    over the layers calls it: jitted, so that the layers of one shape
    are traced ONCE and lowered to one function of the program they are
    part of.  Unrolled, a body is traced and lowered again for every
    layer, at every start-up, compile cache or not (three of the four
    seconds a 48-layer prefill program of one more shape cost a warm
    start); XLA inlines the calls, so the compiled program is the
    unrolled one's.  What varies from layer to layer (its weights, a
    past, its adapters) goes in as arguments."""
    return jax.jit(layer)


def _rmsnorm(x, scale, dtype, epsilon: float = 1e-5):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + epsilon)
    return (norm * scale).astype(dtype)


def _node_lora(node, adapters_node, select):
    """Resolve the adapter pair for one Dense node, preferring banked
    adapters (``adapters_node``) gathered by ``select`` over in-tree
    ``lora_a``/``lora_b`` leaves."""
    if adapters_node is not None:
        return select(adapters_node["lora_a"], adapters_node["lora_b"])
    if "lora_a" in node:
        return node["lora_a"], node["lora_b"]
    return None


# ---------------------------------------------------------------------------
# Prefill: full-context forward exposing per-layer K/V.
# ---------------------------------------------------------------------------


def prefill_forward(params, config: LlamaConfig, tokens, positions=None,
                    *, segment_ids=None, dtype=jnp.float32,
                    adapters=None, adapter_id=None, lora_alpha=16.0,
                    past=None) -> Tuple[Any, Any, Any]:
    """Forward a prompt batch, returning ``(logits, k_layers, v_layers)``.

    ``tokens``: ``[b, t]`` int32.  ``k_layers``/``v_layers``:
    ``[num_layers, b, t, num_kv_heads * head_dim]`` post-RoPE, head ``j``
    in columns ``j * head_dim .. (j + 1) * head_dim`` -- the cache's rows,
    as :meth:`PagedKVCache.write_prefill` scatters them (squeeze the
    batch dim for the per-slot write).  Padding isolation via ``segment_ids``
    follows the model convention (pad tokens get segment 0).

    ``adapters``/``adapter_id``: banked LoRA tree + the ONE adapter this
    prompt uses (prefill admits one request at a time).

    ``past``: chunked prefill continuation -- a ``(k_layers, v_layers)``
    pair from the previous chunks (``[num_layers, b, t_past, kv_heads *
    head_dim]`` each).  ``tokens`` is then the CURRENT chunk only; its
    queries attend over ``past ++ chunk`` keys with the bottom-right
    aligned causal mask (exactly the KV-cache convention
    :func:`~horovod_tpu.ops.attention.flash_attention` implements for
    ``tq < tk``), and the returned K/V cover the FULL context so the
    caller chains chunks by simple replacement.  ``positions`` must be
    the chunk's absolute offsets (``t_past .. t_past + t``); the chunk
    logits equal the same rows of a whole-prompt forward to float
    tolerance (the chunked-prefill parity contract).
    """
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    t_past = 0 if past is None else int(past[0].shape[2])
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t_past, t_past + t),
                                     (b, t))
    if past is not None and segment_ids is not None:
        raise NotImplementedError(
            "chunked prefill with segment_ids: pad isolation across "
            "the past/chunk seam is not modeled; chunk unpadded prompts")
    emb = p["tok_embed"]
    x = emb[tokens].astype(dtype)

    ad = (adapters["params"] if adapters is not None and
          "params" in adapters else adapters)
    ks, vs = [], []

    def heads_of(rows):
        """Cache rows ``[b, t, kv_heads * head_dim]`` as attention takes
        them, ``[b, kv_heads, t, head_dim]``."""
        return rows.reshape(*rows.shape[:2], cfg.num_kv_heads,
                            cfg.head_dim).transpose(0, 2, 1, 3)

    def rows_of(heads):
        return heads.transpose(0, 2, 1, 3).reshape(
            heads.shape[0], heads.shape[2], -1)

    @one_trace
    def layer(x, blk, abk, past, positions, segment_ids, adapter_id):
        def select(a, bnk):
            return a[adapter_id], bnk[adapter_id]

        def lora(group, name):
            node = blk[group][name]
            anode = None if abk is None else abk.get(group, {}).get(name)
            return _node_lora(node, anode, select)

        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype)
        attn = blk["attn"]
        q = _dense(h, attn["wq"], dtype, lora_select=lora("attn", "wq"),
                   lora_alpha=lora_alpha)
        k = _dense(h, attn["wk"], dtype, lora_select=lora("attn", "wk"),
                   lora_alpha=lora_alpha)
        v = _dense(h, attn["wv"], dtype, lora_select=lora("attn", "wv"),
                   lora_alpha=lora_alpha)
        q = q.reshape(b, t, cfg.num_heads, cfg.head_dim).transpose(
            0, 2, 1, 3)
        v_rows = v
        k = rotary_embedding(heads_of(k), positions, cfg.rope_theta)
        v = heads_of(v)
        q = rotary_embedding(q, positions, cfg.rope_theta)
        # The cache's rows: what V's projection gave, and K's after RoPE.
        k_rows = rows_of(k)
        if past is not None:
            # Chunk continuation: this chunk's queries see every past
            # key; the bottom-right aligned causal mask handles the
            # within-chunk triangle.  past k/v arrive as cache rows
            # [b, t_past, H * D]: the FULL context (past ++ chunk) goes
            # back the same way, so chunk callers chain by replacement.
            k_rows = jnp.concatenate([past[0].astype(k.dtype), k_rows],
                                     axis=1)
            v_rows = jnp.concatenate([past[1].astype(v.dtype), v_rows],
                                     axis=1)
            k, v = heads_of(k_rows), heads_of(v_rows)
        o = flash_attention(q, k, v, causal=True,
                            segment_ids=segment_ids)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
        x = x + _dense(o, attn["wo"], dtype, lora_select=lora("attn", "wo"),
                       lora_alpha=lora_alpha)

        h = _rmsnorm(x, blk["mlp_norm"]["scale"], dtype)
        mlp = blk["mlp"]
        gate = _dense(h, mlp["w_gate"], dtype,
                      lora_select=lora("mlp", "w_gate"),
                      lora_alpha=lora_alpha)
        up = _dense(h, mlp["w_up"], dtype,
                    lora_select=lora("mlp", "w_up"),
                    lora_alpha=lora_alpha)
        x = x + _dense(jax.nn.silu(gate) * up, mlp["w_down"], dtype,
                       lora_select=lora("mlp", "w_down"),
                       lora_alpha=lora_alpha)
        return x, k_rows, v_rows

    for li in range(cfg.num_layers):
        x, k_rows, v_rows = layer(
            x, p[f"layer_{li}"], None if ad is None else ad.get(f"layer_{li}"),
            None if past is None else (past[0][li], past[1][li]),
            positions, segment_ids, adapter_id)
        ks.append(k_rows)
        vs.append(v_rows)

    x = _rmsnorm(x, p["final_norm"]["scale"], dtype)
    logits = x.astype(jnp.float32) @ emb.astype(jnp.float32).T
    return logits, jnp.stack(ks), jnp.stack(vs)


# ---------------------------------------------------------------------------
# Tensor-parallel decode step.
# ---------------------------------------------------------------------------


def decode_param_specs(params, tp_axis: str = TP_AXIS):
    """PartitionSpec tree for ``shard_map`` over the decode params:
    column kernels split on the output dim, row kernels on the input dim,
    everything else replicated (the ``shard_tp_params`` key convention)."""

    def spec(path, leaf):
        names = [getattr(kk, "key", "") for kk in path]
        if "kernel" in names and leaf.ndim == 2:
            owner = names[-2] if names[-1] == "kernel" else ""
            if owner in _COLUMN_KEYS:
                return P(None, tp_axis)
            if owner in _ROW_KEYS:
                return P(tp_axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


class ServingDecodeStep:
    """Callable wrapper around the jitted decode step.

    Carries the builder ``_meta`` the static auditor dispatches on (the
    ``_InstrumentedStep`` convention: ``analysis.meta_from_step`` reads
    ``_meta``, ``audit_step`` unwraps ``_fn``) and times each dispatch
    into the span recorder under its leg (``serving_decode`` for the
    one-token step, ``serving_verify`` for the speculative verify step).
    """

    def __init__(self, fn, meta: dict, leg: str = "serving_decode"):
        self._fn = fn
        self._meta = meta
        self._leg = leg

    @property
    def meta(self) -> dict:
        """What the step was built as; ``"attention"`` is ``"walk"``
        where it reads attention out of the page pools by walking the
        page table, ``"view"`` where it gathers slot views."""
        return self._meta

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *args):
        rec = _spans.recorder()
        with rec.span("dispatch", name="decode.dispatch", leg=self._leg):
            return self._fn(*args)


def build_decode_step(config: LlamaConfig, mesh, *,
                      slots: int, page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, with_lora: bool = False,
                      lora_alpha: float = 16.0,
                      tp_axis: str = TP_AXIS, width: int = 1,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched decode (or width-k verify) step over ``mesh``.

    Signature of the returned step (``width == 1``)::

        logits, k_pool, v_pool, told = step(params, k_pool, v_pool,
                                            tokens, positions,
                                            page_table, active
                                            [, kq, vq, kscale, vscale,
                                               ctable, cmask]
                                            [, adapters, adapter_ids],
                                            prev)

    ``tokens``/``positions``/``active``: ``[slots]`` (current token, its
    absolute position == live length before this step, slot liveness).
    ``page_table``: ``[slots, pages_per_slot]``.  The step writes the new
    token's post-RoPE K/V rows into their page in-step, attends over the
    slot's live rows and returns replicated next-token logits.  Over
    uncompressed pools it reads them by walking the page table
    (``hvd_cca_decode`` over both pools, local to a ``tp`` shard's
    heads; ``_meta["attention"] == "walk"``); the fp8 path gathers the
    length-masked slot view (``"view"``).  Idle slots produce zero
    attention output (dead-row convention) and their logits are
    discarded by the engine.

    The step tells its round itself.  ``told``, its last output, is one
    int32 vector ``[tokens | finite | tells]`` (:func:`tell_round`):
    every slot's greedy token over the float32 logits, every slot's
    finite flag (the engine's screen: 1 where the sum of the slot's
    logits is finite), then the model's own ``LayerSpec.step_tells``
    (none here).  ``prev``, its last operand, is the ``told`` of the
    round before (:func:`no_round` where there is none): where the host
    gives a slot the token ``-1`` the step reads the slot's token from
    there (:func:`round_inputs`), so a round can be dispatched before
    the host has read the round before it.  ``prev`` is read only; the
    logits are still returned, and cost nothing where nobody fetches
    them.

    The step CONSUMES ``k_pool`` and ``v_pool``: both are donated, the
    scatters update them in place and the returned pools are their
    successors.  The arrays passed in are deleted by the call -- rebind
    from the result (``logits, cache.k, cache.v = step(...)``) and pass
    ``jnp.copy(pool)`` to keep a snapshot.  Every other operand (params,
    the fp8 pools, tables) is read only.

    ``width > 1`` is the speculative-decoding VERIFY step (built through
    :func:`build_verify_step`; no ``prev`` and no ``told``: the host
    walks the drafts between rounds): ``tokens`` widens to
    ``[slots, width]``
    (the last sampled token followed by ``width - 1`` drafts), every
    column's K/V is scattered to its own (page, offset) in-step, and
    attention runs :func:`~horovod_tpu.ops.attention.verify_attention`
    over the gathered slot view, with the length mask extended one key
    per draft column.  Logits come back ``[slots, width, vocab]``, target
    argmaxes for ALL width positions from ONE dispatch.  Columns past a
    slot's accepted prefix leave garbage K/V above the rolled-back
    length -- unreachable by the masking contract, exactly like a
    recycled page.

    ``compress=True`` (the fp8 KV-cache path) appends the six e4m3 pool
    operands from :meth:`PagedKVCache.compress_operands`; gathers blend
    dequantised cold pages in wherever ``cmask`` is set.  Purely local
    indexing/dequant -- the collective contract is unchanged.
    """
    cfg = config
    tp = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                      if a == tp_axis])) if mesh is not None else 1
    if mesh is not None and tp_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {tp_axis!r} axis: {mesh.axis_names}")
    for what, n in (("num_heads", cfg.num_heads),
                    ("num_kv_heads", cfg.num_kv_heads),
                    ("ffn_hidden", cfg.ffn_hidden)):
        if n % tp:
            raise ValueError(f"{what}={n} not divisible by tp={tp}")
    if with_lora and tp > 1:
        raise NotImplementedError(
            "per-slot LoRA banks are tp=1 only (a row-parallel adapter "
            "would need its own psum fold); shard requests, not adapters")
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    if with_lora and width > 1:
        raise NotImplementedError(
            "speculative verify with per-slot LoRA banks is not wired; "
            "serve adapters with plain decode")
    heads_l = cfg.num_heads // tp
    kvh_l = cfg.num_kv_heads // tp
    hd = cfg.head_dim
    kind = "serving_decode" if width == 1 else "serving_verify"
    # One token a slot over pools that hold every row as written: read
    # by walking the page table.  A verify step's queries are ``width``
    # wide and the fp8 path blends two pools a page: they gather a view.
    walk = width == 1 and not compress
    # Per-layer TP psum rows come from the shared exchange-plan IR
    # (planned once, rendered verbatim by spans/auditor): legs[2*li] is
    # layer li's attn_wo psum, legs[2*li + 1] its mlp_down psum.
    from ..controller import fusion as _fusion
    splan = _fusion.plan_exchange(
        "serving", kind=kind, layers=cfg.num_layers, slots=slots,
        width=width, d_model=cfg.d_model, dtype=str(jnp.dtype(dtype)),
        axis=tp_axis)
    # Register the plan rows at BUILD time, not trace time: plan-
    # fingerprint executable sharing means an identical step may never
    # re-trace, but each built step still owns its legs in the span
    # registry (one registration per build, like one per trace before).
    for _leg in splan.legs:
        _spans.note_leg(_leg, bucket_id=_leg.bucket)
    max_len = pages_per_slot * page_size

    def spmd(params, k_pool, v_pool, tokens, positions, page_table,
             active, *extra):
        if width == 1:
            *extra, prev = extra
            tokens, active = round_inputs(tokens, active, prev)
        if compress:
            kq_pool, vq_pool, kscale, vscale, ctable, cmask = extra[:6]
            extra = extra[6:]
        adapters, adapter_ids = extra if extra else (None, None)
        p = params["params"] if "params" in params else params
        ad = (adapters["params"] if adapters is not None and
              "params" in adapters else adapters)
        s = tokens.shape[0]
        emb = p["tok_embed"]
        scratch = slots * pages_per_slot
        if width == 1:
            x = emb[tokens].astype(dtype)[:, None, :]      # [S, 1, d]
            pos2 = positions[:, None]                      # [S, 1]
            # The step writes EVERY slot's K/V (fixed batch shape); idle
            # slots are redirected to the pool's trailing scratch page
            # so they never clobber a live page.
            page = jnp.where(
                active,
                page_table[jnp.arange(s), positions // page_size],
                scratch)
            off = positions % page_size
        else:
            x = emb[tokens].astype(dtype)                  # [S, W, d]
            pos2 = positions[:, None] + jnp.arange(width)[None, :]
            # Columns may run past max_len on a nearly-full slot (the
            # host caps emission); redirect those writes to scratch too.
            writable = active[:, None] & (pos2 < max_len)
            idx = jnp.clip(pos2 // page_size, 0, pages_per_slot - 1)
            page = jnp.where(
                writable,
                jnp.take_along_axis(page_table, idx, axis=1), scratch)
            off = pos2 % page_size

        def gather_view(li, pool, qpool=None, scale=None):
            view = pool[li][page_table]     # [S, pps, page, kvh_l * hd]
            if compress:
                deq = (qpool[li][ctable].astype(jnp.float32)
                       * scale[li][ctable][..., None]).astype(view.dtype)
                view = jnp.where(cmask[..., None, None], deq, view)
            return view.reshape(
                s, pages_per_slot * page_size, kvh_l, hd
            ).transpose(0, 2, 1, 3)

        def rows(z):
            """``[S, kvh_l, W, hd]`` as the pools hold it: ``[S(, W),
            kvh_l * hd]``."""
            z = z.transpose(0, 2, 1, 3).reshape(s, width, kvh_l * hd)
            return (z[:, 0] if width == 1 else z).astype(k_pool.dtype)

        def select(a, b):
            return a[adapter_ids], b[adapter_ids]

        for li in range(cfg.num_layers):
            blk = p[f"layer_{li}"]
            abk = None if ad is None else ad.get(f"layer_{li}")

            def lora(group, name, _blk=blk, _abk=abk):
                node = _blk[group][name]
                anode = (None if _abk is None
                         else _abk.get(group, {}).get(name))
                return _node_lora(node, anode, select)

            h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype)
            attn = blk["attn"]
            q = _dense(h, attn["wq"], dtype,
                       lora_select=lora("attn", "wq"),
                       lora_alpha=lora_alpha)
            k = _dense(h, attn["wk"], dtype,
                       lora_select=lora("attn", "wk"),
                       lora_alpha=lora_alpha)
            v = _dense(h, attn["wv"], dtype,
                       lora_select=lora("attn", "wv"),
                       lora_alpha=lora_alpha)
            q = q.reshape(s, width, heads_l, hd).transpose(0, 2, 1, 3)
            k = k.reshape(s, width, kvh_l, hd).transpose(0, 2, 1, 3)
            v = v.reshape(s, width, kvh_l, hd).transpose(0, 2, 1, 3)
            q = rotary_embedding(q, pos2, cfg.rope_theta)
            k = rotary_embedding(k, pos2, cfg.rope_theta)

            # In-step cache write: each column's K/V row lands at its
            # (page, offset) -- one scatter per pool per layer, in place
            # (the layer is indexed like the page: no slice of a pool).
            k_pool = k_pool.at[li, page, off].set(rows(k))
            v_pool = v_pool.at[li, page, off].set(rows(v))

            lengths = jnp.where(active, positions + 1, 0)
            if walk:
                # Straight out of both pools, by the page table.
                o = cca_decode_attention(
                    q[:, :, 0].astype(dtype), k_pool, page_table, layer=li,
                    lengths=lengths, kv_heads=kvh_l, scale=hd ** -0.5,
                    values=v_pool)[:, :, None]
            else:
                # Slot view: gather this slot's pages -> [S, kvh, max_len,
                # d] (cold pages dequantised from the e4m3 pool when
                # present).
                if compress:
                    ks = gather_view(li, k_pool, kq_pool, kscale)
                    vs = gather_view(li, v_pool, vq_pool, vscale)
                else:
                    ks = gather_view(li, k_pool)
                    vs = gather_view(li, v_pool)
                attend = decode_attention if width == 1 \
                    else verify_attention
                o = attend(q.astype(dtype), ks.astype(dtype),
                           vs.astype(dtype), lengths=lengths)
            o = o.transpose(0, 2, 1, 3).reshape(s, width, heads_l * hd)

            # Row-parallel closures: the activation allreduce routes
            # through collectives.ops (planner/auditor/span visible).
            y = row_parallel(o.astype(dtype),
                             attn["wo"]["kernel"].astype(dtype),
                             axis=tp_axis)
            wo_lora = lora("attn", "wo")
            if wo_lora is not None:
                y = y + _dense_lora_only(o, wo_lora, dtype, lora_alpha)
            x = x + y

            h = _rmsnorm(x, blk["mlp_norm"]["scale"], dtype)
            mlp = blk["mlp"]
            gate = _dense(h, mlp["w_gate"], dtype,
                          lora_select=lora("mlp", "w_gate"),
                          lora_alpha=lora_alpha)
            up = _dense(h, mlp["w_up"], dtype,
                        lora_select=lora("mlp", "w_up"),
                        lora_alpha=lora_alpha)
            act = (jax.nn.silu(gate) * up).astype(dtype)
            y = row_parallel(act, mlp["w_down"]["kernel"].astype(dtype),
                             axis=tp_axis)
            wd_lora = lora("mlp", "w_down")
            if wd_lora is not None:
                y = y + _dense_lora_only(act, wd_lora, dtype, lora_alpha)
            x = x + y

        x = _rmsnorm(x, p["final_norm"]["scale"], dtype)
        logits = x.astype(jnp.float32) @ emb.astype(jnp.float32).T
        if width > 1:
            return logits, k_pool, v_pool
        logits = logits[:, 0, :]                           # [S, vocab]
        return logits, k_pool, v_pool, tell_round(logits)

    n_base = 7 + (6 if compress else 0)
    # What follows the adapter banks, where there are any: ``prev``.
    n_last = 1 if width == 1 else 0

    def _build(params_example, adapters_example=None):
        pool_spec = P(None, None, None, tp_axis)
        in_specs = [decode_param_specs(params_example, tp_axis),
                    pool_spec, pool_spec, P(), P(), P(), P()]
        if compress:
            # e4m3 pools shard like the f32 pools; scales/table/mask
            # are replicated host metadata.
            in_specs += [pool_spec, pool_spec, P(), P(), P(), P()]
        if adapters_example is not None:
            in_specs += [jax.tree.map(lambda _: P(), adapters_example),
                         P()]
        in_specs += [P()] * n_last
        fn = jax.shard_map(spmd, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(P(), pool_spec, pool_spec)
                           + (P(),) * n_last,
                           check_vma=False)
        # The pools are updated in place: outputs 1 and 2 alias inputs
        # 1 and 2 (same shape, dtype and ``pool_spec``), so the round
        # scatters into the caller's buffers instead of into a copy.
        return jax.jit(fn, donate_argnums=(1, 2))

    # The jitted callable is built lazily on first call so the shard_map
    # in_specs can mirror the actual params tree (LoRA leaves included).
    # Memoized in the session ExecutableCache by the plan fingerprint:
    # serving steps sharing exchange structure (same config/slots/width)
    # on the same mesh share one compiled executable.
    def step(*args):
        # The fingerprint keys the exchange structure (layers, slots,
        # width, d_model, dtype, axis); the extras pin EVERY other
        # constant the traced program closes over: arg arity, page
        # geometry, mesh, how it reads the cache, and the model's own
        # (RoPE's base, the head counts and size, LoRA's alpha), and
        # whether the attention kernel's switch is on (read when the
        # step is traced).  Two engines of one process that differ in
        # any of them must not share a step.  (``ffn_hidden`` and the
        # vocabulary are shapes of the params, which ``jax.jit`` keys on
        # itself.)
        fn = _fusion.plan_executable(
            splan,
            lambda: _build(
                args[0],
                args[n_base] if len(args) > n_base + n_last else None),
            extra=(len(args), bool(compress), int(page_size),
                   int(pages_per_slot), mesh, walk, float(cfg.rope_theta),
                   int(cfg.num_heads), int(cfg.num_kv_heads), int(hd),
                   float(lora_alpha), _pallas.pallas_enabled(
                       "mla_decode" if walk else "flash_decode")))
        return fn(*args)

    meta = {"kind": kind, "world": tp, "tp": tp,
            "num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "slots": int(slots), "dtype": str(jnp.dtype(dtype)),
            "lora": bool(with_lora), "compress": bool(compress),
            "attention": "walk" if walk else "view"}
    if width > 1:
        meta["width"] = int(width)
    return ServingDecodeStep(step, meta, leg=kind)


def build_verify_step(config: LlamaConfig, mesh, *,
                      slots: int, width: int, page_size: int,
                      pages_per_slot: int, dtype=jnp.float32,
                      tp_axis: str = TP_AXIS,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the speculative-decoding verify step: one fixed-shape
    dispatch scoring ``width`` tokens per slot (the last sampled token
    plus ``width - 1`` drafter proposals).

    A width-k generalisation of :func:`build_decode_step` -- same paged
    scatter, same length-masked attention (one extra visible key per
    draft column), same two row-parallel psums per layer, just ``width``
    times as wide (``slots * width * d_model`` elements; the widened
    contract the static auditor prices under ``kind=serving_verify``).
    The engine accepts each slot's longest draft prefix agreeing with
    the returned argmaxes, plus the target's own token at the first
    disagreement -- greedy-exact by construction.
    """
    if width < 2:
        raise ValueError(
            f"verify step needs width >= 2 (got {width}); width 1 is "
            "plain decode -- use build_decode_step")
    return build_decode_step(
        config, mesh, slots=slots, page_size=page_size,
        pages_per_slot=pages_per_slot, dtype=dtype, tp_axis=tp_axis,
        width=width, compress=compress)


def _dense_lora_only(x, lora_select, dtype, lora_alpha):
    """The adapter half of ``_dense`` (added after a row-parallel psum;
    tp=1 only, enforced by the builder)."""
    a, b = lora_select
    r = a.shape[-1]
    scale = jnp.asarray(lora_alpha / r, dtype)
    if a.ndim == 2:
        return (x.astype(dtype) @ a.astype(dtype)
                @ b.astype(dtype)) * scale
    t = jnp.einsum("sqd,sdr->sqr", x.astype(dtype), a.astype(dtype))
    return jnp.einsum("sqr,sro->sqo", t, b.astype(dtype)) * scale


def greedy_sample(logits) -> jnp.ndarray:
    """Deterministic next token per slot: argmax over the vocab."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def tell_round(logits, tells=()) -> jnp.ndarray:
    """What a decode step tells of its round, in one int32 vector (its
    last output): ``[tokens | finite | tells]``.  ``tokens``: every
    slot's greedy token over the ``[slots, vocab]`` float32 logits.
    ``finite``: 1 where the sum of the slot's logits is finite (any NaN
    or Inf in the row reaches the sum): the engine's screen.  ``tells``:
    the model's own whole numbers about the round
    (``LayerSpec.step_tells``)."""
    return jnp.concatenate([
        greedy_sample(logits),
        jnp.isfinite(jnp.sum(logits, axis=-1)).astype(jnp.int32),
        jnp.array(list(tells), jnp.int32)])


def round_inputs(tokens, active, prev):
    """``(tokens, active)`` of this round.  A slot's token is the host's
    or, where the host gives ``-1``, the one the round before sampled
    (``prev``: its ``told``).  Such a slot sits the round out where the
    round before screened it as not finite: the host will drop what
    this round computes for it and re-prefill it, and until then
    nothing is written to its pages or beside them."""
    slots = tokens.shape[0]
    ahead = tokens < 0
    return (jnp.where(ahead, prev[:slots], tokens),
            active & ~(ahead & (prev[slots:2 * slots] == 0)))


def no_round(slots: int, tells: int = 0) -> jnp.ndarray:
    """The ``prev`` operand of a step before which no round ran (the
    host then gives every live slot its token)."""
    return jnp.zeros((2 * slots + tells,), jnp.int32)


def read_told(told, slots: int):
    """``(tokens, finite, tells)`` of a fetched ``told`` vector."""
    told = np.asarray(told)
    return (told[:slots], told[slots:2 * slots].astype(bool),
            told[2 * slots:])


# ---------------------------------------------------------------------------
# Multi-LoRA banks.
# ---------------------------------------------------------------------------


def stack_adapters(param_trees) -> Any:
    """Pack N per-adapter param trees into one banked adapter tree.

    Input trees are full model params (each holding ``lora_a``/``lora_b``
    leaves, e.g. from ``LlamaLM(lora_rank=r).init``); the result keeps
    ONLY the adapter leaves, stacked on a new leading ``n_adapters`` dim,
    nested exactly like the source tree -- the layout the decode step's
    per-slot ``adapter_ids`` gather consumes.
    """
    if not param_trees:
        raise ValueError("need at least one adapter tree")

    def keep(tree):
        if not isinstance(tree, dict):
            return None
        out = {}
        for kk, vv in tree.items():
            if kk in ("lora_a", "lora_b"):
                out[kk] = vv
            else:
                sub = keep(vv)
                if sub:
                    out[kk] = sub
        return out

    kept = [keep(t) for t in param_trees]
    if not kept[0]:
        raise ValueError("adapter trees hold no lora_a/lora_b leaves")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *kept)
