"""Speculative-decoding drafters.

Speculative decoding splits each decode round into a cheap PROPOSE pass
(k draft tokens per slot) and one fixed-shape VERIFY dispatch of the
target model (:func:`~horovod_tpu.serving.decode.build_verify_step`,
width ``k + 1``).  The engine accepts each slot's longest draft prefix
that agrees with the target's own argmaxes plus the target's token at
the first disagreement -- so the emitted stream is bitwise identical to
plain greedy decode no matter how bad the drafter is; the drafter only
moves THROUGHPUT (one verify dispatch can emit up to ``k + 1`` tokens
where plain decode needs ``k + 1`` dispatches).

Two drafters:

* :class:`NgramDrafter` -- prompt-lookup drafting on the host: propose
  the continuation that followed the most recent earlier occurrence of
  the current suffix n-gram in ``prompt + emitted``.  Zero device cost,
  no state beyond the request itself, and surprisingly effective on
  repetitive streams (code, templated text, greedy toy models).
* :class:`ModelDrafter` -- a small Llama run through its OWN paged
  cache and one-token decode step on a single-device mesh (drafting is
  tiny; sharding it would waste ICI).  Keeps its cache exactly one
  token behind the target's context and rolls back rejected drafts by
  the same masking contract the target cache uses (garbage above
  ``lengths`` is unreachable).

Both expose the same four hooks the engine drives:
``on_admit(slot, req)`` after target prefill, ``propose(reqs, k,
last_tokens)`` before each verify, ``observe(slot, req, accepted)``
after it, and ``on_release(slot)`` when the slot recycles.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .decode import (build_decode_step, no_round, prefill_forward,
                     read_told)
from .kvcache import CacheConfig, PagedKVCache
from .scheduler import Request


class NgramDrafter:
    """Prompt-lookup drafting: no draft model, no device work.

    For each slot, search ``prompt + emitted`` (excluding the final
    token) backwards for the most recent earlier occurrence of the
    current ``ngram``-token suffix; propose the tokens that followed
    it.  Falls back to shorter suffixes, then to repeating the last
    token (a draft is never "missing" -- the verify step needs a full
    ``[slots, k]`` block and wrong drafts only cost acceptance).
    """

    def __init__(self, ngram: int = 2):
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        self.ngram = ngram

    # -- engine hooks (stateless: everything lives on the request) -----
    def on_admit(self, slot: int, req: Request) -> None:
        pass

    def observe(self, slot: int, req: Request, accepted: int) -> None:
        pass

    def on_release(self, slot: int) -> None:
        pass

    def re_prefill(self, slot: int, req: Request) -> None:
        pass

    def propose(self, reqs: Dict[int, Request], k: int,
                last_tokens: np.ndarray) -> np.ndarray:
        slots = last_tokens.shape[0]
        out = np.zeros((slots, k), np.int32)
        for slot, req in reqs.items():
            ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.tokens, np.int32)])
            out[slot] = self._lookup(ctx, k)
        return out

    def _lookup(self, ctx: np.ndarray, k: int) -> np.ndarray:
        n = len(ctx)
        for g in range(min(self.ngram, n - 1), 0, -1):
            suffix = ctx[n - g:]
            # Most recent earlier match of the suffix (exclude the
            # suffix's own position so the continuation is non-empty).
            for i in range(n - g - 1, -1, -1):
                if np.array_equal(ctx[i:i + g], suffix):
                    cont = ctx[i + g:i + g + k]
                    if len(cont):
                        out = np.empty((k,), np.int32)
                        out[:len(cont)] = cont
                        out[len(cont):] = cont[-1]
                        return out
        return np.full((k,), ctx[-1], np.int32)


class ModelDrafter:
    """Draft with a small Llama through its own single-device cache.

    The drafter's cache tracks the target's context minus its final
    token (that token is the round's first verify input, fed to the
    drafter as ``x0``).  During a propose round the drafter feeds
    ``x0, d1 .. d_{k-1}`` -- writing their K/V at its write head -- and
    :meth:`observe` then rolls the head back to the accepted prefix;
    rejected entries stay as masked garbage above ``lengths``, exactly
    the recycled-page contract.  If plain (non-speculative) decode ran
    in between (e.g. a control-plane drain), :meth:`propose` first
    catches the cache up token-by-token from the request's emitted
    stream, so the drafter tolerates arbitrary interleaving.
    """

    def __init__(self, config, params, *, slots: int, page_size: int,
                 max_len: int, dtype=jnp.float32):
        from jax.sharding import Mesh
        self.config = config
        self.params = params
        self.dtype = dtype
        self.mesh = Mesh(
            np.asarray(jax.devices()[:1], dtype=object).reshape(1),
            ("tp",))
        self.cache_config = CacheConfig(
            num_layers=config.num_layers,
            num_kv_heads=config.num_kv_heads, head_dim=config.head_dim,
            slots=slots, page_size=page_size, max_len=max_len,
            dtype=str(jnp.dtype(dtype)))
        self.cache = PagedKVCache(self.cache_config)
        self.step = build_decode_step(
            config, self.mesh, slots=slots, page_size=page_size,
            pages_per_slot=self.cache_config.pages_per_slot, dtype=dtype)
        self.slots = slots
        self.max_len = max_len
        # The host walks the drafts: every token is its own.
        self._no_round = no_round(slots)

        def _prefill(p, toks):
            return prefill_forward(p, config, toks, dtype=dtype)

        self._prefill = jax.jit(_prefill)
        self._round_base: Dict[int, tuple] = {}

    # -- engine hooks --------------------------------------------------
    def on_admit(self, slot: int, req: Request) -> None:
        self._prefill_ctx(slot, np.asarray(req.prompt, np.int32))

    def re_prefill(self, slot: int, req: Request) -> None:
        self.cache.free_slot(slot)
        ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                              np.asarray(req.tokens[:-1], np.int32)])
        self._prefill_ctx(slot, ctx)

    def on_release(self, slot: int) -> None:
        self.cache.free_slot(slot)

    def observe(self, slot: int, req: Request, accepted: int) -> None:
        # Roll the write head back to the accepted prefix: the round
        # wrote inputs (x0, d1..d_{k-1}); x0 plus the first ``accepted``
        # drafts are now real context, the rest is masked garbage.
        head = self._round_base.pop(slot, None)
        if head is None:
            return
        base, written = head
        self.cache.lengths[slot] = base + min(accepted + 1, written)

    def propose(self, reqs: Dict[int, Request], k: int,
                last_tokens: np.ndarray) -> np.ndarray:
        cache = self.cache
        # Catch up any slot whose cache trails context-minus-one (plain
        # decode rounds in between, or a full-acceptance round's +1 gap).
        self._catch_up(reqs)

        drafts = np.zeros((self.slots, k), np.int32)
        cur = np.zeros((self.slots,), np.int32)
        active = np.zeros((self.slots,), bool)
        base = np.zeros((self.slots,), np.int32)
        for slot, req in reqs.items():
            base[slot] = cache.lengths[slot]
            # A slot too close to its cap cannot host k writes; skip it
            # (its drafts stay 0 -- wrong drafts only cost acceptance).
            if base[slot] + k > self.max_len:
                continue
            cache.reserve(slot, int(base[slot]) + k)
            cur[slot] = req.tokens[-1]
            active[slot] = True
        if not active.any():
            return drafts
        for slot in reqs:
            if active[slot]:
                self._round_base[slot] = (int(base[slot]), k)
        table = cache.table_device()
        act_dev = jnp.asarray(active)
        for i in range(k):
            _, cache.k, cache.v, told = self.step(
                self.params, cache.k, cache.v,
                jnp.asarray(cur), jnp.asarray(base + i), table, act_dev,
                self._no_round)
            drafts[:, i] = np.where(active, read_told(told, self.slots)[0],
                                    0)
            cur = drafts[:, i].copy()
        return drafts

    # -- internals -----------------------------------------------------
    def _prefill_ctx(self, slot: int, ctx: np.ndarray) -> None:
        self.cache.reserve(slot, len(ctx))
        _, kl, vl = self._prefill(self.params, jnp.asarray(ctx)[None])
        self.cache.write_prefill(slot, kl[:, 0], vl[:, 0])

    def _catch_up(self, reqs: Dict[int, Request]) -> None:
        cache = self.cache
        while True:
            feed: Dict[int, int] = {}
            for slot, req in reqs.items():
                need = req.prompt_len + len(req.tokens) - 1
                have = int(cache.lengths[slot])
                if have < min(need, self.max_len):
                    # Token at context position ``have``.
                    pos = have
                    tok = (req.prompt[pos] if pos < req.prompt_len
                           else req.tokens[pos - req.prompt_len])
                    feed[slot] = int(tok)
            if not feed:
                return
            toks = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            for slot, tok in feed.items():
                cache.reserve(slot, int(cache.lengths[slot]) + 1)
                toks[slot] = tok
                active[slot] = True
            _, cache.k, cache.v, _ = self.step(
                self.params, cache.k, cache.v, jnp.asarray(toks),
                cache.lengths_device(), cache.table_device(),
                jnp.asarray(active), self._no_round)
            for slot in feed:
                cache.lengths[slot] += 1
