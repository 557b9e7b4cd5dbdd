"""A selective state-space recurrence (Mamba-2's, "SSD") for a served
step: the chunked scan a prefill runs over a whole prompt, and the
one-token update a decode round runs over every live slot's state in
place.

A head ``h`` keeps a state ``H`` of ``n x p`` float32 values (``p``: the
head's columns, ``n``: the state's size; kept ``[n, p]``, the head's
columns in the lanes, so that one vector register holds one state row of
one sequence a sublane); heads come ``heads / groups`` to a group, and a
group shares its ``B`` and ``C``.  With ``dt_t > 0`` and ``A < 0`` a
head::

    H_t = exp(dt_t A) H_(t-1) + dt_t * B_t (outer) x_t
    y_t = C_t H_t + D x_t

* :func:`ssm_scan` is the same recurrence over a prompt in chunks of
  ``chunk`` tokens, matmuls a chunk: with ``L_t`` the running sum of
  ``dt_s A`` inside a chunk, ``y_t = sum_(s<=t) exp(L_t - L_s) dt_s (C_t .
  B_s) x_s + exp(L_t) C_t H_in + D x_t`` and ``H_out = exp(L_Q) H_in +
  sum_s exp(L_Q - L_s) dt_s B_s (outer) x_s``, the chunks one after
  another (``lax.scan``), in ``jax.numpy`` at float32 ``highest``: it is
  under a hundredth of a prefill's operations.
* :func:`ssm_decode_update` is one step of it for every slot of a decode
  round, over the cache's slot-state array ``[planes, slots, width]``
  whose rows BEGIN with the heads' states (``heads * n * p`` values;
  whatever else a slot keeps rides behind them and is not touched): ONE
  pass that reads a live slot's state once and writes it once, in place.
  On the TPU a Mosaic kernel, ``hvd_ssm_decode``: the array aliased input
  to output, blocks of eight slots (one a sublane) by ``heads_a_block``
  heads, visited for the groups of eight that hold a live slot only (the
  group ids are prefetched scalars, live groups first; the steps beyond
  them revisit the last live block, which moves nothing); ``B`` and ``C``
  of a group are spread over the lanes once a group of heads, in VMEM.
  Idle slots' rows are left as they are.  Off the TPU the same function
  in ``jax.numpy`` unless ``HOROVOD_PALLAS=1`` asks for the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas as _pallas

_HI = jax.lax.Precision.HIGHEST
# Slots a block of the decode kernel: a float32 tile's sublanes.
_SUBLANES = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def _to_heads(z, heads: int):
    """``[..., groups, n]`` -> ``[..., heads, n]``: head ``h`` reads
    group ``h // (heads / groups)``."""
    return jnp.repeat(z, heads // z.shape[-2], axis=-2)


def ssm_scan(x, dt, A, B, C, D, h0=None, chunk: int = 128):
    """The recurrence over ``t`` tokens, in chunks of ``chunk``.

    ``x`` ``[b, t, heads, p]``, ``dt`` ``[b, t, heads]`` (positive: after
    its softplus), ``A`` ``[heads]`` (negative), ``B`` and ``C`` ``[b, t,
    groups, n]``, ``D`` ``[heads]``, ``h0`` ``[b, heads, n, p]`` (None:
    zeros).  Returns ``(y, h_last)``: ``[b, t, heads, p]`` and ``[b,
    heads, n, p]``, float32.  ``t`` need be no multiple of ``chunk``: the
    last chunk is filled with steps of ``dt = 0``, which leave the state
    as it is."""
    f32 = jnp.float32
    b, t, heads, p = x.shape
    n = B.shape[-1]
    q = int(chunk)
    pad = -t % q
    x, dt, B, C = (jnp.pad(z.astype(f32), ((0, 0), (0, pad))
                           + ((0, 0),) * (z.ndim - 2))
                   for z in (x, dt, B, C))
    c = (t + pad) // q

    def chunks(z):
        return z.reshape(b, c, q, *z.shape[2:]).swapaxes(0, 1)

    A = A.astype(f32)
    seen = jnp.tril(jnp.ones((q, q), bool))
    ein = functools.partial(jnp.einsum, precision=_HI)

    def one(h, blk):
        xc, dtc, bc, cc = blk                    # [b, q, ...]
        run = jnp.cumsum(dtc * A, axis=1)        # L_t  [b, q, heads]
        bh, ch = _to_heads(bc, heads), _to_heads(cc, heads)
        # exp(L_t - L_s) for s <= t, a head: [b, heads, q(t), q(s)].
        lt = run.transpose(0, 2, 1)
        decay = jnp.exp(jnp.where(seen, lt[..., :, None] - lt[..., None, :],
                                  -jnp.inf))
        w = ein("bthn,bshn->bhts", ch, bh) * decay \
            * dtc.transpose(0, 2, 1)[:, :, None, :]
        y = ein("bhts,bshp->bthp", w, xc)
        y += ein("bthn,bhnp->bthp", ch * jnp.exp(run)[..., None], h)
        # What each step still weighs at the chunk's end.
        left = jnp.exp(run[:, -1:] - run) * dtc  # [b, q, heads]
        h = jnp.exp(run[:, -1])[..., None, None] * h + ein(
            "bshn,bshp->bhnp", bh * left[..., None], xc)
        return h, y

    if h0 is None:
        h0 = jnp.zeros((b, heads, n, p), f32)
    h_last, y = jax.lax.scan(one, h0.astype(f32),
                             tuple(chunks(z) for z in (x, dt, B, C)))
    y = y.swapaxes(0, 1).reshape(b, t + pad, heads, p)[:, :t]
    return y + D.astype(f32)[:, None] * x[:, :t], h_last


# ---------------------------------------------------------------------------
# One token a slot, in place.
# ---------------------------------------------------------------------------


def _decode_kernel(plane_ref, group_ref, count_ref, da_ref, dtx_ref, b_ref,
                   c_ref, h_ref, o_ref, y_ref, bx_ref, cx_ref, *, n: int,
                   p: int, heads_a_block: int, heads_a_group: int):
    """One block: ``heads_a_block`` heads of eight slots.  ``h_ref`` /
    ``o_ref`` ``[8, heads_a_block * n * p]`` (a slot a sublane, a head's
    state ``[n, p]`` flat); ``da_ref`` (``exp(dt A)``) and ``dtx_ref``
    (``dt * x``) ``[8, heads_a_block * p]``; ``b_ref`` / ``c_ref`` ``[8,
    n]`` of the heads' group; ``bx_ref`` / ``cx_ref`` ``[8, n * p]``: the
    group's ``B`` and ``C``, each value over the ``p`` lanes of its state
    row."""
    del plane_ref, group_ref
    i, j = pl.program_id(0), pl.program_id(1)
    blocks_a_group = heads_a_group // heads_a_block
    run = 8 if n % 8 == 0 else 1

    @pl.when(i < count_ref[0])
    def _():
        @pl.when(j % blocks_a_group == 0)
        def _():
            b, c = b_ref[...], c_ref[...]
            for k in range(n):
                bx_ref[:, k * p:(k + 1) * p] = jnp.broadcast_to(
                    b[:, k:k + 1], (b.shape[0], p))
                cx_ref[:, k * p:(k + 1) * p] = jnp.broadcast_to(
                    c[:, k:k + 1], (c.shape[0], p))

        for hh in range(heads_a_block):
            da = da_ref[:, hh * p:(hh + 1) * p]
            dtx = dtx_ref[:, hh * p:(hh + 1) * p]

            def rows(k, acc, hh=hh, da=da, dtx=dtx):
                # ``run`` state rows a turn (Mosaic's loop takes no
                # partial unroll: written out).
                for r in range(run):
                    at = pl.multiple_of((k * run + r) * p, p)
                    here = pl.ds(pl.multiple_of(
                        hh * n * p + (k * run + r) * p, p), p)
                    new = da * h_ref[:, here] \
                        + dtx * bx_ref[:, pl.ds(at, p)]
                    o_ref[:, here] = new
                    acc = acc + new * cx_ref[:, pl.ds(at, p)]
                return acc

            y_ref[:, hh * p:(hh + 1) * p] = jax.lax.fori_loop(
                0, n // run, rows, jnp.zeros(da.shape, jnp.float32))


def _heads_a_block(heads_a_group: int, n: int, p: int) -> int:
    """Heads a block of the kernel: two where a head's eight slots are a
    mebibyte (a 2 MiB block in and out, twice buffered, beside 2 MiB of
    spread ``B`` and ``C``), more where they are less."""
    most = max((2 << 20) // (_SUBLANES * n * p * 4), 1)
    hb = 1
    while hb * 2 <= most and heads_a_group % (hb * 2) == 0:
        hb *= 2
    return hb


def _decode_pallas(state, plane, da, dtx, bm, cm, live, *, heads: int,
                   groups: int, n: int, p: int):
    slots = state.shape[1]
    sb = _SUBLANES if slots % _SUBLANES == 0 else slots
    blocks = slots // sb
    per = heads // groups
    hb = _heads_a_block(per, n, p)
    # The groups of ``sb`` slots that hold a live one, first; the steps
    # beyond them stay on the last of these.
    # (A round with no live slot still visits its first group, whose
    # rows ``exp(dt A) = 1`` and ``dt x = 0`` leave as they are: a block
    # that is visited is written back.)
    held = jnp.any(live.reshape(blocks, sb), axis=1)
    count = jnp.maximum(jnp.sum(held, dtype=jnp.int32), 1)
    order = jnp.argsort(~held, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(blocks) < count, order, order[count - 1])
    last = heads // hb - 1

    def cols(i, j, cnt):
        # A step beyond the live groups stays on the LAST block a live
        # step visited, whole index: nothing is fetched for it and
        # nothing of it written back but what that step left.
        return jnp.where(i < cnt[0], j, last)

    def head_cols(i, j, pln, grp, cnt):
        return grp[i], cols(i, j, cnt)

    def group_cols(i, j, pln, grp, cnt):
        return grp[i], cols(i, j, cnt) * hb // per

    def state_block(i, j, pln, grp, cnt):
        return pln[0], grp[i], cols(i, j, cnt)

    kernel = functools.partial(_decode_kernel, n=n, p=p, heads_a_block=hb,
                               heads_a_group=per)
    with jax.named_scope("hvd_ssm_decode"):
        new, y = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(blocks, heads // hb),
                in_specs=[pl.BlockSpec((sb, hb * p), head_cols),
                          pl.BlockSpec((sb, hb * p), head_cols),
                          pl.BlockSpec((sb, n), group_cols),
                          pl.BlockSpec((sb, n), group_cols),
                          pl.BlockSpec((None, sb, hb * n * p), state_block)],
                out_specs=[
                    pl.BlockSpec((None, sb, hb * n * p), state_block),
                    pl.BlockSpec((sb, hb * p), head_cols)],
                scratch_shapes=[pltpu.VMEM((sb, n * p), jnp.float32),
                                pltpu.VMEM((sb, n * p), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((slots, heads * p),
                                            jnp.float32)],
            # The state is the call's eighth operand, behind the three
            # prefetched scalars.
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="hvd_ssm_decode",
            interpret=_pallas.interpret_mode(),
        )(jnp.asarray(plane, jnp.int32).reshape(1), ids, count.reshape(1),
          da, dtx, bm, cm, state)
    return new, y


def _decode_reference(state, plane, da, dtx, bm, cm, live, *, heads: int,
                      groups: int, n: int, p: int):
    slots = state.shape[1]
    hw = heads * n * p
    was = jax.lax.dynamic_index_in_dim(state, plane, 0, keepdims=False)
    h = was[:, :hw].reshape(slots, heads, n, p)
    bh = _to_heads(bm.reshape(slots, groups, n), heads)
    ch = _to_heads(cm.reshape(slots, groups, n), heads)
    new = da.reshape(slots, heads, 1, p) * h \
        + bh[..., None] * dtx.reshape(slots, heads, 1, p)
    y = jnp.sum(new * ch[..., None], axis=2).reshape(slots, heads * p)
    new = jnp.where(live[:, None], new.reshape(slots, hw), was[:, :hw])
    return jax.lax.dynamic_update_slice(
        state, new[None].astype(state.dtype), (plane, 0, 0)), y


def ssm_decode_update(state, x, dt, A, B, C, D, live, *, plane=0):
    """One token a slot: ``(state, y)``.

    ``state`` ``[planes, slots, width]`` float32, of which row ``[plane,
    slot]`` begins with the slot's ``heads * n * p`` state values (``[n,
    p]`` a head); ``x`` ``[slots, heads, p]``, ``dt`` ``[slots, heads]``
    (positive), ``A`` and ``D`` ``[heads]``, ``B`` and ``C`` ``[slots,
    groups, n]``, ``live`` ``[slots]`` bool, ``plane`` a whole number
    (traced or not).  A live slot's state is advanced by the token and
    ``y`` ``[slots, heads, p]`` (float32) is what it reads out of the
    NEW state, ``C H + D x``; an idle slot's row is left as it is and its
    ``y`` is zero.  The array is updated in place where the caller
    donates it."""
    f32 = jnp.float32
    slots, heads, p = x.shape
    groups, n = B.shape[-2:]
    if state.dtype != f32 or state.shape[2] < heads * n * p:
        raise ValueError(
            f"a state of {state.dtype} rows of {state.shape[2]} values for "
            f"{heads} float32 states of {n} x {p}")
    x = x.astype(f32)
    on = live[:, None]
    dt = dt.astype(f32)
    # An idle slot's step: exp(dt A) = 1, dt x = 0.
    da = jnp.where(on, jnp.exp(dt * A.astype(f32)), 1.0)
    dtx = jnp.where(on[..., None], dt[..., None] * x, 0.0)
    update = _decode_pallas if _pallas.pallas_enabled("ssm_decode") \
        else _decode_reference
    state, y = update(
        state, plane,
        jnp.broadcast_to(da[..., None], x.shape).reshape(slots, heads * p),
        dtx.reshape(slots, heads * p),
        B.astype(f32).reshape(slots, groups * n),
        C.astype(f32).reshape(slots, groups * n), live,
        heads=heads, groups=groups, n=n, p=p)
    y = y.reshape(slots, heads, p) + D.astype(f32)[:, None] * x
    return state, jnp.where(on[..., None], y, 0.0)
