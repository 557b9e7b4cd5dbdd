"""What the one-chip decode steps share, written once.

``mla_moe.py``, ``cca_moe.py``, ``loop_dense.py``, ``swa_moe.py``,
``ssm_hybrid.py`` and ``eva_dense.py`` build their decode programs from
the same parts: the embedding read into a float32 residual stream (times
a constant, where the model has one), where a round's token lands in the
page pool, a loop
over the layers that hands each one the pool (and whatever else the
model carries), around it -- for a model whose tokens make several
passes over the same layers -- ONE rolled loop over the passes with the
model's own step between two of them, the readout over a tied or an
untied head, the running ``[routed layers, experts]`` histogram with
what the step tells of its round, and the jit that donates every operand
the step rewrites.  ``decode.py``'s step is a
``shard_map`` over the ``tp`` axis with adapter banks and an fp8 pool and
keeps its own loop; it shares ``_dense`` and ``_rmsnorm``, which live
there.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .decode import (ServingDecodeStep, _rmsnorm, round_inputs,
                     tell_round)


class Round(NamedTuple):
    """Where one decode round reads and writes, a slot."""
    positions: jax.Array    # [slots] the token's position
    page: jax.Array         # [slots] the page its row goes to
    off: jax.Array          # [slots] the row within that page
    lengths: jax.Array      # [slots] live tokens once it is written
    page_table: jax.Array   # [slots, pages_per_slot]
    active: jax.Array       # [slots] bool
    # The pool's first plane of the pass being run: 0, a Python int, for
    # a model of one pass; ``pass * layers``, traced, inside the loop
    # over the passes.  Layer ``li`` reads and writes plane ``first_plane
    # + li``.
    first_plane: object = 0
    # The window group's page table ``[slots, ring]``, where the model
    # has window layers (``build_one_chip_step``'s ``window_group``).
    window_table: Optional[jax.Array] = None


def lane_pad(x, width: int):
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, width - x.shape[-1]),))


def dense_out(x, node, dtype):
    """A branch's closing projection: operands in ``dtype``, the result
    float32, unrounded, for the residual stream."""
    return jnp.dot(x.astype(dtype), node["kernel"].astype(dtype),
                   preferred_element_type=jnp.float32)


def embed(p, tokens):
    """The residual stream's first value: float32."""
    return p["tok_embed"][tokens].astype(jnp.float32)


def readout(x, p, eps: float, dtype, *, tied: bool, normed: bool = False,
            unit_offset: bool = False):
    """Float32 logits over the final norm (``normed``: ``x`` has been
    through it already; ``unit_offset``: the norm multiplies by ``1 +
    scale``): against ``lm_head`` or, tied, against the embedding's own
    rows."""
    if not normed:
        scale = p["final_norm"]["scale"]
        x = _rmsnorm(x, 1.0 + scale.astype(jnp.float32) if unit_offset
                     else scale, dtype, eps)
    if not tied:
        return dense_out(x, p["lm_head"], dtype)
    return jax.lax.dot_general(
        x, p["tok_embed"].astype(dtype), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def round_of(positions, page_table, active, *, page_size: int,
             scratch: int) -> Round:
    """Every slot writes (fixed batch shape); idle slots write the pool's
    trailing scratch page."""
    s = positions.shape[0]
    page = jnp.where(
        active, page_table[jnp.arange(s), positions // page_size], scratch)
    return Round(positions, page, positions % page_size,
                 jnp.where(active, positions + 1, 0), page_table, active)


TELLS = {
    # Experts, summed over the routed layers, that a live slot chose.
    "experts_touched": lambda counts: jnp.sum(counts > 0, dtype=jnp.int32),
    # The most rows one expert took, the largest over the routed layers.
    "peak_expert_rows": lambda counts: jnp.max(counts).astype(jnp.int32),
}
_JOIN = {"experts_touched": jnp.add, "peak_expert_rows": jnp.maximum}


def build_one_chip_step(name: str, layer: Callable, *, num_layers: int,
                        eps: float, tied: bool, page_size: int,
                        scratch: int, dtype, tells: Sequence[str],
                        carried: int, meta: dict,
                        local: Callable = lambda x: None,
                        routed: bool = True, passes: int = 1,
                        after_pass: Optional[Callable] = None,
                        window_group: bool = False,
                        held: Optional[slice] = None,
                        embed_scale: float = 1.0, logit_scale: float = 1.0,
                        window_pools: bool = True,
                        unit_offset: bool = False,
                        sample_columns: Optional[int] = None
                        ) -> ServingDecodeStep:
    """The jitted step ``name``::

        logits, pool, None, *carried, routed, told = step(
            params, pool, None, tokens, positions, page_table, active,
            *carried, routed, prev)

    ``layer(li, blk, x, pool, carried, local, rnd) -> (x, pool, carried,
    local, routed index or None, counts or None)`` computes layer ``li``
    for every slot: it writes the round's row into ``pool``, may rewrite
    the ``carried`` arrays (a model's own state beside the pool: per-slot
    rows, say) and ``local`` (what crosses the layers within the round
    and no further; ``local(x)`` before the first), and for a routed
    layer returns its row of the histogram and the ``[experts]`` counts
    of its live slots.  ``routed`` is the
    running ``[routed layers, experts]`` int32 histogram (``routed=False``:
    the step has no such operand and no such output).  ``told``, the
    step's last output, is what it tells of its round, one int32 vector
    ``[tokens | finite | tells]`` (``decode.tell_round``): every slot's
    greedy token over the float32 logits, every slot's finite flag, and
    the ``tells`` of the round, one int32 each.  ``prev``, its last
    operand, is the ``told`` of the round before: a slot to which the
    host gives the token ``-1`` takes its token from there
    (``decode.round_inputs``).  The pool,
    the carried arrays and ``routed`` are donated and their successors
    returned; ``prev`` is read only.

    ``after_pass`` given (a looped model): a token runs the
    ``num_layers`` layers ``passes`` times, over the same weights, as ONE
    rolled loop (``lax.fori_loop``) around the layer bodies: pass ``t``
    hands the layers ``rnd.first_plane = t * num_layers`` (traced), and
    ``after_pass(x, p) -> (x, leave)`` runs after each, the last too: the
    model's own step between two passes and, ``[slots]`` float32, the
    share of a token that would leave the loop here if it were still in
    it.  The step then takes one more
    operand before ``prev`` and hands its successor back in that place,
    donated: ``exit_mass`` ``[passes]`` float32, to which it adds, a
    pass, the exit distribution's mass summed over the round's live
    slots (``leave_t * prod_{j<t} (1 - leave_j)``; what is left, at the
    last pass).  Every pass is always run and the readout takes what the
    last one left, which ``after_pass`` has normed.  Without
    ``after_pass`` there is no loop and no such operand, and the step
    lowers to what it lowered to before there was one.

    A model of TWO pools hands the second in ``None``'s place: ``layer``
    then gets and returns the pair, and both are donated.
    ``window_group`` (a model with window layers): behind ``active`` the
    step takes the window group's page table, read only, which ``layer``
    finds as ``rnd.window_table``, and the group's two pools, donated,
    which lead the ``carried`` arrays.  ``held`` (a chip that holds a
    share of the experts): the slice of a routed layer's counts that the
    ``tells`` are made of; the histogram keeps the router's whole width.
    ``embed_scale`` and ``logit_scale`` (a model that multiplies its
    embedding and its logits by constants): applied where they are not
    1, so that a model without them lowers to what it lowered to.
    ``window_pools=False`` (a model whose window group's pages lie in the
    pools themselves, ``CacheConfig.window_in_pool``): the step takes the
    window group's table and no further pool.  ``unit_offset``: the
    final norm multiplies by ``1 + scale``.  ``sample_columns`` (a head
    of several predictions side by side): the logits' leading columns
    that the round's token is the greedy one of; the step still returns
    every column.
    """
    looped = after_pass is not None
    if passes > 1 and not looped:
        raise ValueError(f"{passes} passes and no after_pass")

    def step(params, pool, no_pool, tokens, positions, page_table, active,
             *state):
        *state, prev = state
        window_table = state.pop(0) if window_group else None
        two_pools = no_pool is not None
        if two_pools:
            pool = (pool, no_pool)
        mass = state.pop() if looped else None
        hist = state.pop() if routed else None
        carry = state
        p = params["params"] if "params" in params else params
        tokens, active = round_inputs(tokens, active, prev)
        x = embed(p, tokens)                                     # [S, d]
        if embed_scale != 1.0:
            x = x * embed_scale
        rnd = round_of(positions, page_table, active, page_size=page_size,
                       scratch=scratch)._replace(window_table=window_table)
        told = [jnp.zeros((), jnp.int32) for _ in tells]

        def one_pass(x, pool, carry, hist, told, rnd):
            within = local(x)
            for li in range(num_layers):
                x, pool, carry, within, mi, counts = layer(
                    li, p[f"layer_{li}"], x, pool, carry, within, rnd)
                if counts is not None:
                    hist = hist.at[mi].add(counts)
                    if held is not None:
                        counts = counts[held]
                    told = [_JOIN[t](was, TELLS[t](counts))
                            for t, was in zip(tells, told)]
            return x, pool, list(carry), hist, told

        if not looped:
            x, pool, carry, hist, told = one_pass(x, pool, carry, hist,
                                                  told, rnd)
        else:
            live = active.astype(jnp.float32)

            def body(t, loop):
                x, pool, carry, hist, told, mass, stay = loop
                x, pool, carry, hist, told = one_pass(
                    x, pool, carry, hist, told,
                    rnd._replace(first_plane=t * num_layers))
                x, leave = after_pass(x, p)
                here = jnp.where(t < passes - 1, leave * stay, stay)
                return (x, pool, carry, hist, told,
                        mass.at[t].add(jnp.sum(here * live)),
                        stay * (1.0 - leave))

            x, pool, carry, hist, told, mass, _ = jax.lax.fori_loop(
                0, passes, body, (x, pool, list(carry), hist, told, mass,
                                  jnp.ones(x.shape[:1], jnp.float32)))
        logits = readout(x, p, eps, dtype, tied=tied, normed=looped,
                         **({"unit_offset": True} if unit_offset else {}))
        if logit_scale != 1.0:
            logits = logits * logit_scale
        own = ([hist] if routed else []) + ([mass] if looped else [])
        if two_pools:
            pool, no_pool = pool
        return (logits, pool, no_pool, *carry, *own,
                tell_round(logits if sample_columns is None
                           else logits[:, :sample_columns], told))

    step.__name__ = step.__qualname__ = name
    first = 7 + window_group
    fn = jax.jit(step, donate_argnums=(1, 2) + tuple(range(
        first, first + 2 * (window_group and window_pools) + carried
        + routed + looped)))
    return ServingDecodeStep(fn, dict(
        meta, kind="serving_decode", world=1, tp=1, num_layers=num_layers,
        passes=passes, dtype=str(jnp.dtype(dtype)), lora=False,
        compress=False, attention="walk"))


def refuse_beyond_one_chip(what: str, mesh, *, width: int, with_lora: bool,
                           compress: bool) -> None:
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            f"{what} decode is tp = 1 only, got a mesh of "
            f"{mesh.devices.size}")
    if width != 1 or with_lora or compress:
        raise NotImplementedError(
            f"{what} decode has no verify step, no adapter banks and no "
            "fp8 pool")


def publish_routed(hist) -> None:
    """The device's ``[routed layers, experts]`` histogram of routed
    (token, choice) pairs into the registry, once a ``serve``."""
    import numpy as np

    from ..timeline import metrics as _metrics
    counter = _metrics.registry().counter(
        "moe.tokens_routed",
        "(token, choice) pairs a decode round routed to each expert",
        labelnames=("layer", "expert"))
    hist = np.asarray(hist)
    for layer, expert in zip(*np.nonzero(hist)):
        counter.labels(layer=int(layer), expert=int(expert)).inc(
            int(hist[layer, expert]))


def publish_exit_mass(mass) -> None:
    """The device's ``[passes]`` exit mass (the exit distribution summed
    over every live token of every round) into the registry, once a
    ``serve``."""
    import numpy as np

    from ..timeline import metrics as _metrics
    counter = _metrics.registry().counter(
        "loop.exit_mass",
        "mass of the exit distribution a pass, summed over decoded tokens",
        labelnames=("pass",))
    for t, m in enumerate(np.asarray(mass, np.float64)):
        counter.labels(**{"pass": t}).inc(float(m))
