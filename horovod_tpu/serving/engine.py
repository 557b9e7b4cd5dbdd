"""Serving engine front-end: prefetch -> prefill -> continuous decode.

One object owns the whole data plane: the paged KV cache, the
continuous-batching scheduler, the jitted prefill, and the
tensor-parallel decode step.  The request feed generalizes the
``data.DevicePrefetcher`` double-buffering idiom from training batches
to requests: a producer thread stages each upcoming prompt onto device
while the engine is still decoding, so admission never stalls on a
host-to-device copy.

Whichever loop drives the engine (``serve``, the control plane's, the
fleet's decode worker): ONE run state (``run_state``), ONE join
(``join``: a first token stays on the chip) and ONE round
(``decode_once``, one ahead; ``catch_up`` behind it where a loop must).

Knobs (all overridable per-constructor-arg, documented in docs/api.md):

* ``HOROVOD_SERVING_SLOTS`` -- decode batch slots (default 8)
* ``HOROVOD_SERVING_PAGE_SIZE`` -- KV page length in tokens (default 16)
* ``HOROVOD_SERVING_MAX_LEN`` -- per-sequence cap (default: model max)
* ``HOROVOD_SERVING_PREFETCH`` -- request prefetch depth (default 2)
* ``HOROVOD_SPEC_DECODE`` -- speculative decoding on/off (default off)
* ``HOROVOD_SPEC_K`` -- draft tokens per speculative round (default 4)
* ``HOROVOD_PREFILL_CHUNK`` -- chunked-prefill chunk length in tokens
  (default 0 = whole-prompt prefill)
* ``HOROVOD_KV_COMPRESS`` -- fp8 cold-page KV compression (default off)
* ``HOROVOD_PREFIX_CACHE`` -- radix prefix cache over the page pool
  (default off): a request whose prompt hits a cached prefix attaches
  the matched pages refcounted copy-on-write and prefills only the
  tail through the chunked path
* ``HOROVOD_SESSION_TTL_STEPS`` -- engine steps a session's warm KV
  context stays pinned without reuse (default 512)
* ``HOROVOD_TENANT_CLASSES`` -- per-tenant SLO classes,
  ``name:weight[:ttft_slo_s[:max_share]],...`` (default: single
  tenant)

The engine keeps two clocks: a VIRTUAL clock that fast-forwards through
idle gaps in the open-loop arrival schedule (TTFT and queueing are
measured against it, so latency percentiles are arrival-faithful), and
the real wall clock for throughput (tokens/s is never diluted by
fast-forwarded idle time).
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.config import _env, _env_bool, _env_int
from ..timeline import spans as _spans
from .decode import greedy_sample, no_round, read_told
from .kvcache import (CacheConfig, PagedKVCache, PrefixCache,
                      window_rows_from)
from .layerspec import layer_spec
from .scheduler import (ContinuousBatchScheduler, Request,
                        parse_tenant_classes)
from .spec import NgramDrafter
from .swa_moe import PREFILL_TOKENS


class _Stop:
    def __init__(self, error: Optional[BaseException] = None):
        self.error = error


class RequestPrefetcher:
    """Stage upcoming requests' prompts onto device ahead of admission.

    Same shape as ``data.DevicePrefetcher``: bounded queue, daemon
    producer, sentinel-carried errors, context-manager close.  Yields
    ``(request, device_prompt)`` in arrival order.
    """

    def __init__(self, requests: Sequence[Request], depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._closed = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(list(requests),),
            name="serving-prefetch", daemon=True)
        self._thread.start()

    def _produce(self, requests):
        try:
            for req in requests:
                if self._closed.is_set():
                    return
                dev = jax.device_put(jnp.asarray(req.prompt, jnp.int32))
                while not self._closed.is_set():
                    try:
                        self._q.put((req, dev), timeout=0.1)
                        break
                    except queue.Full:
                        continue
            self._q.put(_Stop())
        except BaseException as e:  # surfaced in the consumer
            self._q.put(_Stop(e))

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if isinstance(item, _Stop):
            if item.error is not None:
                raise item.error
            raise StopIteration
        return item

    def close(self) -> None:
        self._closed.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


@dataclasses.dataclass
class ServingReport:
    """Aggregate result of one ``serve()`` run."""

    num_requests: int
    completed: int
    rejected: int
    prompt_tokens: int
    new_tokens: int
    wall_s: float
    decode_steps: int
    tokens_per_s: float
    ttft_p50_s: float
    ttft_p99_s: float
    token_latency_p50_s: float
    token_latency_p99_s: float
    mean_occupancy: float
    # Of ``decode_steps``, the rounds dispatched while the round before
    # was still in flight (``serve``'s look-ahead; ``decode.round``'s
    # ``ahead``).
    rounds_ahead: int = 0
    # Speculative decoding (zero when HOROVOD_SPEC_DECODE is off).
    spec_rounds: int = 0
    proposed_tokens: int = 0
    accepted_tokens: int = 0
    acceptance_rate: float = 0.0
    # Prefix cache (zero when HOROVOD_PREFIX_CACHE is off).
    prefix_queries: int = 0
    prefix_hits: int = 0
    prefix_hit_rate: float = 0.0
    prefill_tokens_cached: int = 0
    # Fraction of prompt tokens whose per-token prefill forward was
    # skipped outright (matched pages attached instead of computed).
    prefill_flops_avoided: float = 0.0
    session_resumes: int = 0
    # The serve loop's own account of the call: self seconds by span
    # name (``serve.account``'s ``self_ns``; the root ``serve`` holds
    # what no phase covers).  They add up to the ``serve`` span's time.
    loop_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _Flight:
    """A decode round the chip has been handed and the host has not read
    yet: its live slots, the step's ``told`` vector (on the device) and
    when it was dispatched, and the number of its ``decode.round``."""

    slots: List[int]
    told: Any
    t0: float
    round: int


@dataclasses.dataclass
class _Join:
    """A prefill the chip has been handed whose first token the host has
    not read yet: its slot and request, and the ``[token, finite]``
    pairs of its prefill's group on the device (:func:`_hand_over`),
    their copy to the host started; ``row``: which pair is its own."""

    slot: int
    req: Request
    first: Any
    row: int = 0


# The joins of one length that go through ONE prefill program.
GROUP = 4


def group_size(prompt_len: int, max_rows: int) -> int:
    """How many joins of ``prompt_len`` tokens go through one prefill
    program: ``GROUP`` where its rows, ``GROUP * prompt_len``, are
    within ``max_rows``, else 1.  One size, and not a pair's beside it:
    every further size is a further program to trace, lower and load at
    every start-up (two to four seconds each on the chip's host)."""
    return GROUP if GROUP * prompt_len <= max_rows else 1


def group_joins(keys: Sequence[Any], max_rows: int) -> List[List[int]]:
    """The joins of ONE admission, grouped for their prefills: lists of
    indices into ``keys``, each list one prefill program.  ``keys[i]``:
    ``(prompt_len, ...)`` of a plain join, which may share a program
    with the joins of the same key, or None for one that goes alone (a
    prefix hit, a chunked prompt).  The joins of a key are taken
    :func:`group_size` at a time; what is left over, fewer than that,
    goes alone (a join alone is bound by nothing here).  The groups come
    in the order in which their first members were admitted."""
    buckets: Dict[Any, List[int]] = {}
    groups = []
    for i, key in enumerate(keys):
        if key is None:
            groups.append([i])
        else:
            buckets.setdefault(key, []).append(i)
    for key, members in buckets.items():
        size = group_size(key[0], max_rows)
        whole = len(members) - len(members) % size
        groups += [members[i:i + size] for i in range(0, whole, size)]
        groups += [[i] for i in members[whole:]]
    return sorted(groups)


def _members(out):
    """What a prefill of ``b`` prompts hands back beside its logits
    (arrays ``[planes, b, ...]``), a member: ``b`` trees of ``[planes,
    ...]``."""
    b = jax.tree.leaves(out)[0].shape[1]
    return [jax.tree.map(lambda x: x[:, i], out) for i in range(b)]


def _hand_over(told, rows, slot, *, slots: int):
    """A group's first tokens, left on the chip.  ``rows``: the float32
    logits ``[b, vocab]`` of the prompts' last positions; ``slot``:
    their ``b`` slots.  A row's greedy token and its finite flag (as
    :func:`decode.tell_round` gives them of a round) go into its slot's
    two places of ``told``, the vector the next round reads as its
    ``prev``: the host gives that round ``-1`` for the slot, as for one
    that continues.  Returns the patched vector and the pairs alone
    (``[b, 2]``), the first tokens' own way back to the host (the next
    round's ``told`` holds a slot's SECOND token)."""
    first = jnp.stack([
        greedy_sample(rows),
        jnp.isfinite(jnp.sum(rows, axis=-1)).astype(jnp.int32)], axis=-1)
    return (told.at[slot].set(first[:, 0])
            .at[slots + slot].set(first[:, 1])), first


@jax.jit
def _verify_told(logits):
    """A verify round in one program and one fetch: the ``[slots,
    width]`` greedy tokens with the slot's finite flag behind them (a
    poisoned column anywhere in the window disqualifies the slot's
    whole round: the agreeing-prefix walk would condition on it)."""
    finite = jnp.isfinite(jnp.sum(logits, axis=(-2, -1)))
    return jnp.concatenate(
        [greedy_sample(logits), finite[:, None].astype(jnp.int32)], axis=1)


def _file_account(rec, root, before, **counts) -> Dict[str, float]:
    """File the ``serve.account`` record of the ``serve`` call whose
    span ``root`` is still open, and return its self seconds by name.

    ``before``: the recorder's ``(totals(), filed, dropped)`` when the
    call began.  ``spans`` gives ``{count, total_ns, self_ns}`` for every
    span name that closed during the call, and for ``serve`` itself as
    of now: the ``self_ns`` add up to ``wall_ns`` as long as every span
    of the process lay under this root.  ``filed`` and ``dropped`` are
    the ring's over the call: a reader that finds fewer than ``filed``
    records begun since the root's start knows the ring has lost some
    of them."""
    totals0, filed0, dropped0 = before
    wall_ns = root.elapsed_ns()
    spans = {"serve": {"count": 1, "total_ns": wall_ns,
                       "self_ns": wall_ns - root.child_ns}}
    for name, total in rec.totals().items():
        was = totals0.get(name, (0, 0, 0))
        if total[0] > was[0]:
            spans[name] = dict(zip(("count", "total_ns", "self_ns"),
                                   (a - b for a, b in zip(total, was))))
    rec.file("serve.account", under="serve", spans=spans, wall_ns=wall_ns,
             filed=rec.filed - filed0, dropped=rec.dropped - dropped0,
             **counts)
    return {name: t["self_ns"] / 1e9 for name, t in spans.items()}


def _pct(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, np.float64), q))


class ServingEngine:
    """Continuous-batching inference over one model.

    ``config``: a ``LlamaConfig`` or any config with a ``layer_spec()``
    (``serving.mla_moe.MlaMoeConfig``).  The prefill, the decode step
    and the cache's layout are built from that one description
    (``serving.layerspec.LayerSpec``); a feature the model's programs
    lack raises ``NotImplementedError`` here, by name.

    ``mesh``: the ``("tp",)`` mesh the decode step and the KV pool shard
    over.  The default, ``mesh=None``, is ``jax.devices()[:1]`` -- ONE
    chip, however many the host has; pass a mesh over the chips you mean
    to use.

    ``params``: the tree as ``model.init`` / a checkpoint restore returns
    it (one device, uncommitted).  Prefill is replicated math and runs
    there; the engine places a second, tp-sharded copy on the mesh for
    the decode step once, at construction.  A tree already sharded over
    several chips cannot feed prefill on a TPU: GSPMD does not partition
    the Mosaic flash kernel.
    """

    def __init__(self, config, params, *, mesh=None, slots: int = 0,
                 page_size: int = 0, max_len: int = 0, dtype=jnp.float32,
                 adapters=None, adapter_ids=None, lora_alpha: float = 16.0,
                 prefetch_depth: int = 0,
                 spec_decode: Optional[bool] = None, spec_k: int = 0,
                 drafter=None, prefill_chunk: int = -1,
                 kv_compress: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 session_ttl_steps: int = 0, tenants=None):
        self.config = config
        self.spec = spec = layer_spec(config)
        self.params = params
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
        self.mesh = mesh
        spec.require(tp=mesh.devices.size > 1, lora=adapters is not None)
        self._decode_params = self._place_decode_params()
        self.slots = slots or _env_int("SERVING_SLOTS", 8)
        self.page_size = page_size or _env_int("SERVING_PAGE_SIZE", 16)
        self.max_len = max_len or _env_int("SERVING_MAX_LEN",
                                           spec.max_seq_len)
        self.prefetch_depth = prefetch_depth or _env_int(
            "SERVING_PREFETCH", 2)
        self.spec_decode = (_env_bool("SPEC_DECODE")
                            if spec_decode is None else bool(spec_decode))
        self.spec_k = spec_k or _env_int("SPEC_K", 4)
        self.prefill_chunk = (_env_int("PREFILL_CHUNK", 0)
                              if prefill_chunk < 0 else prefill_chunk)
        self.kv_compress = (_env_bool("KV_COMPRESS")
                            if kv_compress is None else bool(kv_compress))
        self.prefix_cache = (_env_bool("PREFIX_CACHE")
                             if prefix_cache is None
                             else bool(prefix_cache))
        self.session_ttl_steps = session_ttl_steps or _env_int(
            "SESSION_TTL_STEPS", 512)
        if tenants is None:
            classes = _env("TENANT_CLASSES")
            tenants = parse_tenant_classes(classes) if classes else None
        spec.require(spec_decode=self.spec_decode,
                     kv_compress=self.kv_compress,
                     prefill_chunk=self.prefill_chunk > 0,
                     prefix_cache=self.prefix_cache)
        if self.spec_decode and self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if adapters is not None and self.spec_decode:
            raise NotImplementedError(
                "speculative decoding with LoRA banks is not wired; "
                "run adapters through plain decode")
        if adapters is not None and self.kv_compress:
            raise NotImplementedError(
                "fp8 KV compression with LoRA banks is not wired")
        if adapters is not None and self.prefix_cache:
            raise NotImplementedError(
                "prefix cache with LoRA banks is not wired: cached K/V "
                "is keyed by tokens only, but LoRA'd wk/wv make K/V "
                "adapter-dependent")
        self.dtype = dtype
        self.adapters = adapters
        self.lora_alpha = lora_alpha
        self.cache_config = CacheConfig(
            num_layers=spec.planes, slots=self.slots,
            page_size=self.page_size, max_len=self.max_len,
            dtype=str(jnp.dtype(dtype)), compress=self.kv_compress,
            page=spec.page, slot_state=spec.slot_state,
            slot_state_dtype=spec.slot_state_dtype,
            window_layers=spec.window_planes, window=spec.window,
            row_tokens=spec.row_tokens)
        self.cache = PagedKVCache(self.cache_config,
                                  spec.pool_sharding(mesh))
        # Admission must price the widest step a slot can take: k drafts
        # + the target's bonus token under speculation, else 1.
        budget = self.spec_k + 1 if self.spec_decode else 1
        self.scheduler = ContinuousBatchScheduler(
            self.slots, self.cache, token_budget=budget,
            tenants=tenants)
        self._tenants = tenants
        # Radix prefix cache over the page pool: installed as the
        # cache's reclaim callback so page pressure demotes/evicts
        # cached prefixes instead of failing admission.
        self._prefix: Optional[PrefixCache] = None
        if self.prefix_cache:
            self._prefix = PrefixCache(
                self.cache, session_ttl_steps=self.session_ttl_steps)
        self._build_steps()
        self.drafter = None
        if self.spec_decode:
            self.drafter = drafter if drafter is not None \
                else NgramDrafter()

        def _prefill(p, toks, ad, aid):
            return spec.prefill(p, toks, dtype=dtype, adapters=ad,
                                adapter_id=aid, lora_alpha=lora_alpha)

        def _prefill_chunk(p, toks, past):
            return spec.prefill(p, toks, dtype=dtype, past=past)

        def _prefill_group(p, toks, ad, aid):
            # What the prompts hand back leaves the program a member:
            # nothing holds the rows of all of them a second time.
            logits, *out = _prefill(p, toks, ad, aid)
            return logits, _members(out)

        # A trace names a program by its function: a group's is a
        # prefill program like one prompt's (``jit__prefill``).
        _prefill_group.__name__ = _prefill.__name__
        self._prefill = jax.jit(_prefill)
        self._prefill_group = jax.jit(_prefill_group)
        self._prefill_chunked = jax.jit(_prefill_chunk)
        # The prompt lengths whose group program is compiled
        # (:meth:`_prepare_group`).
        self._group_ready: set = set()
        # In-progress chunked prefills: slot -> dict(req, prompt, pos,
        # past).  Slots in here are state "prefill" and excluded from
        # the decode batch until their last chunk lands.
        self._chunking: Dict[int, Dict[str, Any]] = {}

    def _place_decode_params(self):
        """The decode step's copy of the params, sharded over ``tp`` --
        placed once, so a dispatch does not re-shard the tree."""
        return jax.device_put(self.params, jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.spec.param_specs(self.params)))

    def _build_steps(self) -> None:
        """The decode step (and the verify step under speculation) over
        ``self.mesh``, with the device state the step carries beside
        the pools."""
        common = dict(slots=self.slots, page_size=self.page_size,
                      pages_per_slot=self.cache_config.pages_per_slot,
                      dtype=self.dtype, compress=self.kv_compress)
        self.step = self.spec.build_step(
            self.mesh, with_lora=self.adapters is not None,
            lora_alpha=self.lora_alpha, **common)
        self._step_state = self._fresh_step_state()
        # The step's last output and its last operand: what the round
        # before told.
        self._told = self._whole(
            no_round(self.slots, len(self.spec.step_tells)))
        # A joining slot's first token goes into that vector on the
        # chip; what comes out is committed as the step's own is.
        whole = NamedSharding(self.mesh, PartitionSpec())
        self._hand_over = jax.jit(
            functools.partial(_hand_over, slots=self.slots),
            out_shardings=(whole, whole))
        self.verify_step = None
        if self.spec_decode:
            self.verify_step = self.spec.build_step(
                self.mesh, width=self.spec_k + 1, **common)

    def _whole(self, x):
        """``x`` whole on every chip of the mesh, committed as the step
        hands its outputs back: the first dispatch compiles the program
        that every later one runs."""
        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _fresh_step_state(self) -> tuple:
        return tuple(self._whole(x) for x in self.spec.step_state())

    def run_state(self) -> Dict[str, Any]:
        """One run's mutable state, every key there from the start."""
        return {
            "completed": [], "occ_samples": [], "decode_steps": 0,
            "spec_rounds": 0, "proposed": 0, "accepted": 0,
            "prefix_queries": 0, "prefix_hits": 0,
            "prefill_cached": 0, "prefill_computed": 0,
            "session_resumes": 0, "prefills": 0,
            # ``serve.prefill`` programs dispatched for them, and the
            # prompts among them that shared one.
            "prefill_groups": 0, "prefills_grouped": 0,
            "last_tokens": np.zeros((self.slots,), np.int32),
            "adapter_ids": np.zeros((self.slots,), np.int32),
            # The round the chip has and the host has not read, and the
            # prefills whose first token is still on the chip.
            "in_flight": None, "rounds_ahead": 0,
            "joins": [], "first_tokens_deferred": 0}

    # -- one turn's joins --------------------------------------------------
    @property
    def group_rows(self) -> int:
        """The rows ``b * t`` a group of joins may have: no more than
        one prompt the engine must be able to take (``max_len``), nor
        than the rows a layer's per-token work was sized for
        (``swa_moe.PREFILL_TOKENS``)."""
        return min(self.max_len, PREFILL_TOKENS)

    def _join_key(self, req: Request):
        """What the joins that may share a prefill program with ``req``
        have in common (:func:`group_joins`), None where it goes alone:
        under a prefix cache (a join's pages enter the tree as it is
        prefilled and the next join of the same admission may hit them)
        and where its prompt is chunked."""
        if self._prefix is not None \
                or 0 < self.prefill_chunk < req.prompt_len:
            return None
        return (req.prompt_len,
                req.adapter_id if self.adapters is not None else 0)

    def _prepare_group(self, prompt_len: int) -> None:
        """Compile what a group of prompts of ``prompt_len`` tokens runs
        beyond what one prompt alone does, in one throwaway call: the
        prefill over ``[b, prompt_len]`` and the hand-over of ``b``
        first tokens (the pool writes are one prompt's, member by
        member).  Done the first time the serve loop prefills the
        length, so that a group never compiles when it forms: a warm-up
        of one request a length prepares every length's group."""
        self._group_ready.add(prompt_len)
        b = group_size(prompt_len, self.group_rows)
        if 1 < b <= self.slots:
            logits, _ = self._prefill_group(
                self.params, jnp.zeros((b, prompt_len), jnp.int32),
                self.adapters,
                None if self.adapters is None else jnp.int32(0))
            self._hand_over(self._told, logits[:, -1, :],
                            np.zeros((b,), np.int32))

    def join(self, st: Dict[str, Any], joins: Sequence[tuple], now) -> None:
        """One turn of a loop, and the one way a prompt joins: the
        ``(slot, request, prompt on the device)`` it admitted, plain
        joins of one length through one prefill program
        (:func:`group_joins`) behind whatever round is in flight, and a
        chunk more of every chunked prefill.  (Dispatched from this body:
        a frame more above a prefill's first trace costs seconds of
        set-up, PERF.md section 6, PR 47.)"""
        for group in group_joins([self._join_key(req) for _, req, _ in joins],
                                 self.group_rows):
            members = [joins[i] for i in group]
            hit = self._begin_prefill(st, members, now)
            if hit is None:
                continue
            (slot, req, dev), *others = members
            if self._join_key(req) is not None \
                    and req.prompt_len not in self._group_ready:
                self._prepare_group(req.prompt_len)
            flight = st["in_flight"]
            first = self._do_prefill(
                slot, req, dev, *hit, others=others,
                behind=-1 if flight is None else flight.round)
            st["prefill_groups"] += 1
            if others:
                st["prefills_grouped"] += len(members)
            for row, (slot, req, _) in enumerate(members):
                self._await_first(st, slot, req, first, row)
                self._note_resident(st, slot, req)
        if self._chunking:
            with _spans.recorder().phase("serve.chunks"):
                self._advance_chunks(st, now)

    def _begin_prefill(self, st: Dict[str, Any], members: Sequence[tuple],
                       now) -> Optional[tuple]:
        """Take up one prefill program's joins, ``(slot, request,
        prompt on the device)`` each: one join, or a group of plain
        joins of one length (:func:`group_joins`).  One join alone:
        radix-match the prompt against the prefix cache (attach matched
        pages, no compute).  Returns ``(matched, entries)``, what the
        prefill program is spared, or None where the tail is long and
        goes in chunk by chunk (:meth:`_advance_chunks`)."""
        slot, req, dev = members[0]
        matched, entries = 0, ()
        for _, r, _ in members:
            r.prefill_start_s = now()
        st["prefills"] += len(members)
        if self._prefix is not None:
            matched, entries = self._prefix.match(req.prompt)
            st["prefix_queries"] += 1
            if matched:
                st["prefix_hits"] += 1
                st["prefill_cached"] += matched
                self.cache.attach_pages(slot, entries, matched)
            st["prefill_computed"] += req.prompt_len - matched
            if req.session_id is not None and \
                    self._prefix.touch_session(req.session_id) and matched:
                st["session_resumes"] += 1
        if 0 < self.prefill_chunk < req.prompt_len - matched:
            # Long tail: fill in chunk-by-chunk, one chunk per loop
            # iteration, decode interleaved.  A matched prefix seeds
            # the running past from the cached pages.
            past = self.cache.gather_pages(entries) if matched else None
            self._chunking[slot] = {
                "req": req, "dev": dev, "pos": matched,
                "start": matched, "past": past}
            return None
        return matched, entries

    def _await_first(self, st: Dict[str, Any], slot: int, req: Request,
                     first, row: int = 0) -> None:
        """The request's first token is on the chip (row ``row`` of
        ``first``): the host reads it where it next waits for the chip."""
        req.state = "decode"
        req.in_flight = 1
        st["joins"].append(_Join(slot, req, first, row))
        st["first_tokens_deferred"] += 1

    def _do_prefill(self, slot: int, req: Request, prompt_dev,
                    matched: int = 0, entries: Sequence = (),
                    behind: int = -1, others: Sequence[tuple] = ()):
        """Dispatch one prefill program, its pool writes and its slot
        states', and leave its first tokens on the chip, in the slots'
        places of the vector the next round reads (:func:`_hand_over`).
        ``others``: the ``(slot, request, prompt)`` of the further
        members of a GROUP, plain joins of ``req``'s length that go
        through the program with it as ``tokens[b, t]`` (the weights are
        read once a group); each member's rows then go to its own slot
        as one prompt's do.  ``behind``: the number of the decode round
        in flight while this prefill is dispatched (it queues behind it
        on the chip), -1 where there is none.  Returns the ``[token,
        finite]`` pairs on the device, a row a member, without waiting
        for anything."""
        rec = _spans.recorder()
        members = [(slot, req, prompt_dev), *others]
        slots = [m[0] for m in members]
        if others and matched:
            raise ValueError("a group takes plain joins")
        # With a window group: the rows a window plane is written, the
        # prompt's last ones.
        windowed = {} if self.spec.window is None else {
            "window_rows": req.prompt_len - window_rows_from(
                req.prompt_len, self.spec.window, self.spec.window_aligned),
            "window_planes": self.spec.window_planes}
        if self.spec.row_tokens > 1:
            # Pooled rows: the windows a member's prefill attends in, the
            # whole chunks it pools and the rows of its ragged last chunk,
            # which wait in the ring for the round that fills it.
            windowed.update(
                windows=-(-req.prompt_len // self.spec.window),
                chunks_pooled=req.prompt_len // self.spec.row_tokens,
                pending_rows=req.prompt_len % self.spec.row_tokens)
        if self.spec.scan_chunk:
            # A prefill that is a scan: the chunks its members run.
            windowed["scan_chunks"] = len(members) * -(
                -req.prompt_len // self.spec.scan_chunk)
        with rec.span("dispatch", name="serve.prefill",
                      leg="serving_prefill", rid=req.rid, slot=slot,
                      prompt_len=req.prompt_len, passes=self.spec.passes,
                      planes=self.spec.planes, behind=behind,
                      deferred=True, group=len(members),
                      rids=tuple(m[1].rid for m in members),
                      slots=tuple(slots), **windowed):
            with rec.phase("prefill.dispatch", rid=req.rid):
                if matched:
                    # Prefix hit: only the tail goes through the forward
                    # pass, conditioned on the cached pages as past K/V
                    # -- the matched tokens' prefill FLOPs are avoided.
                    past = self.cache.gather_pages(entries)
                    logits, kl, vl = self._prefill_chunked(
                        self.params, prompt_dev[matched:][None], past)
                    rows = [(kl[:, 0, matched:], vl[:, 0, matched:])]
                else:
                    aid = jnp.int32(req.adapter_id) \
                        if self.adapters is not None else None
                    # The prompts of a group go up from the host as one
                    # array (stacking them on the device would be a
                    # program a size).
                    if others:
                        logits, rows = self._prefill_group(
                            self.params, jnp.asarray(np.stack([
                                np.asarray(m[1].prompt, np.int32)
                                for m in members])), self.adapters, aid)
                    else:
                        # One prompt alone: the program and the slices
                        # it always took.
                        logits, *out = self._prefill(
                            self.params, prompt_dev[None], self.adapters,
                            aid)
                        rows = _members(out)
            self._write_rows(members, rows, matched)
            return self._leave_first(logits, slots, req.rid)

    def _write_rows(self, members: Sequence[tuple], rows: Sequence,
                    matched: int = 0) -> None:
        """Write what ONE prefill program handed back (``rows``, a
        member each) into the members' slots: pool rows from ``matched``
        on, window rows, slot state.  A join's and a re-prefill's."""
        phase = _spans.recorder().phase
        for (slot, req, _), (kl, vl, *state) in zip(members, rows):
            with phase("prefill.write_kv", rid=req.rid):
                self.cache.write_prefill(
                    slot, kl, vl, start=matched,
                    window_rows=self._window_rows(state))
            if self.spec.slot_state is not None:
                # What the slot keeps beside its pages: the prompt's
                # trailing rows (the spec refuses a hit or a chunk).
                with phase("prefill.write_state", rid=req.rid,
                           state_bytes=state[0].size
                           * self.cache.state.dtype.itemsize):
                    self.cache.write_state(slot, state[0])

    def _leave_first(self, logits, slots: Sequence[int], rid: int):
        """Hand ``logits``' last rows over to ``slots`` (``_hand_over``)."""
        with _spans.recorder().phase("prefill.hand_over", rid=rid):
            self._told, first = self._hand_over(
                self._told, logits[:, -1, :], np.asarray(slots, np.int32))
            first.copy_to_host_async()
        return first

    def _window_rows(self, beyond: list):
        """The window planes' rows of ONE prompt out of what its prefill
        handed back beyond its two planes (``LayerSpec.attn_kinds``: the
        last of them; popped), None for a model without window layers."""
        if self.spec.window is None:
            return None
        return tuple(beyond.pop())

    def _advance_chunks(self, st: Dict[str, Any], now) -> None:
        """Push each in-progress chunked prefill forward by ONE chunk.

        One chunk per slot per loop iteration: a kilotoken
        admission is sliced into ``prefill_chunk``-token forwards
        interleaved with decode steps, so the live decode batch keeps
        emitting while the long prompt fills in (the TTFT-p99 gate).
        The final chunk's full-context K/V is scattered once -- chunked
        and whole-prompt prefill land the identical cache state.
        """
        rec = _spans.recorder()
        for slot in list(self._chunking):
            c = self._chunking[slot]
            req: Request = c["req"]
            chunk = c["dev"][c["pos"]:c["pos"] + self.prefill_chunk]
            with rec.span("dispatch", name="prefill_chunk",
                          leg="serving_prefill_chunk"):
                logits, kl, vl = self._prefill_chunked(
                    self.params, chunk[None], c["past"])
                c["past"] = (kl, vl)
                c["pos"] += int(chunk.shape[0])
                if c["pos"] < req.prompt_len:
                    continue
                # The last chunk's span holds the pool write and the
                # first token's hand-over, as ``serve.prefill`` does.
                del self._chunking[slot]
                start = int(c["start"])
                with rec.phase("prefill.write_kv", rid=req.rid):
                    self.cache.write_prefill(slot, kl[:, 0, start:],
                                             vl[:, 0, start:], start=start)
                first = self._leave_first(logits, [slot], req.rid)
            self._await_first(st, slot, req, first)
            self._note_resident(st, slot, req)

    def _join_decode(self, st: Dict[str, Any], slot: int, req: Request,
                     first: int, now) -> None:
        """A request whose pages are resident and whose first token the
        HOST has (the fleet's imported ticket) enters the decode batch."""
        self._note_resident(st, slot, req)
        self._book_first(st, slot, req, first, now)

    def _note_resident(self, st: Dict[str, Any], slot: int,
                       req: Request) -> None:
        """The prompt's rows are in the slot's pages (the write is
        dispatched): what the next round and the next admission need of
        that, before any token of the request is known."""
        st["adapter_ids"][slot] = req.adapter_id
        if self._prefix is not None:
            # Register the prompt's full pages in the radix tree (tree
            # holds its own refs, so they outlive the slot) and pin the
            # session's path so multi-turn context stays warm.
            self._prefix.insert(req.prompt, slot)
            if req.session_id is not None:
                self._prefix.pin_session(req.session_id, req.prompt)

    def _book_first(self, st: Dict[str, Any], slot: int, req: Request,
                    first: int, now) -> None:
        """The host has the request's first token: stamped now."""
        req.tokens.append(first)
        self.scheduler.note_prefill(req, now())
        st["last_tokens"][slot] = first
        if self.drafter is not None:
            self.drafter.on_admit(slot, req)
        if req.finished:
            self._release(st, slot, now)

    def _release(self, st: Dict[str, Any], slot: int, now) -> None:
        if self.drafter is not None:
            self.drafter.on_release(slot)
        st["completed"].append(self.scheduler.release(slot, now()))

    def _decode_slots(self) -> List[int]:
        """Slots the next decode round takes: live requests minus
        still-chunking prefills and pages-in-flight handoffs (neither
        has resident context yet), and minus a slot whose last token is
        in flight.  That is known by COUNT, before any token of the
        round in flight is read: tokens emitted plus tokens dispatched
        against ``max_new_tokens``, and the slot's length (which
        advances at dispatch) against ``max_len``."""
        return [s for s, r in self.scheduler.active.items()
                if r.state not in ("prefill", "handoff")
                and len(r.tokens) + r.in_flight < r.max_new_tokens
                and int(self.cache.lengths[s]) < self.max_len]

    def _quarantine_logits(self, st: Dict[str, Any], slot: int) -> None:
        """A slot produced nonfinite logits: never stream a token
        sampled from a poisoned distribution.

        The slot's resident KV (or the dispatch that read it) is
        suspect, so rebuild the context from the request's own token
        history via :meth:`re_prefill` -- ``write_prefill`` re-derives
        the slot's length and page mapping from scratch, so the
        quarantine cannot leak pages -- and retry the same position on
        the next round: only the round is lost.  Where it was the
        prefill's own logits (the request has no token yet) the prompt
        is handed in again, whole, as a join is.
        """
        from ..timeline import metrics as _metrics
        _metrics.registry().counter(
            "horovod_guard_serving_reprefills_total",
            "Decode rounds where a slot's nonfinite logits were "
            "quarantined by re-prefilling its context").inc()
        req = self.scheduler.active[slot]
        if req.tokens:
            st["last_tokens"][slot] = self.re_prefill(slot, req)
        else:
            st["prefills"] += 1
            st["prefill_groups"] += 1
            self._await_first(st, slot, req, self._do_prefill(
                slot, req, jnp.asarray(req.prompt, jnp.int32)))

    # -- one decode round --------------------------------------------------
    def _round_span(self, st: Dict[str, Any], slots: List[int],
                    ahead: bool = False, step=None):
        """The ``decode.round`` span of one round over ``slots``,
        dispatched through ``step`` (the decode step; a speculative
        round hands in its verify step).
        ``live_tokens`` is what the round's attention reads: each slot's
        resident context and the token this round writes.  ``round``:
        the number of the round whose ``decode.dispatch`` lies under
        this span; ``decode.sample_fetch`` and ``decode.bookkeep`` carry
        the number of the round they RETIRE themselves, because under
        the look-ahead they lie under the span of the round after.
        ``ahead``: 1
        where the round is dispatched while the one before is still in
        flight.  ``passes`` and ``planes``: how often the round runs the
        layers, and from how many planes of the pool it reads each live
        token (``LayerSpec.passes``, ``.planes``).  ``pages``: the pages
        a page walk copies in ONE plane this round, each slot's live
        tokens rounded up to whole pages (with ``planes`` and a kernel's
        time, a trace gives the nanoseconds a page).  ``walk``: 1 where
        the step reads attention by that walk (its ``meta["attention"]``),
        0 where it gathers slot views (verify, the fp8 path).  With a
        window group: ``window_tokens`` and ``window_pages``, what a
        window layer's walk reads in ONE of its planes, and
        ``window_pages_held``, the group's pages that slots hold as the
        round is dispatched (of ``window_num_pages``: a ring grows page
        by page, so short requests never hold a whole one).  With a slot
        state: ``state_planes`` and ``state_bytes``, the live slots' rows
        (``LayerSpec.slot_state_step`` values of each, a plane) that the
        round's update reads and writes again.  With pooled rows
        (``LayerSpec.row_tokens`` > 1): ``attended_rows``, the exact rows
        of its aligned window and the pooled rows of the windows before
        that each live slot attends, in ONE layer, ``pooled_rows`` the
        pooled part, ``chunks_pooled`` the chunks this round's tokens
        fill (pooled inside the round) and ``window_crossings`` the slots
        whose token is a window's first; ``pages`` are then the pages of
        those rows, and ``window_tokens`` and ``window_pages`` the exact
        part's."""
        live = [int(self.cache.lengths[s]) + 1 for s in slots]
        page = self.page_size
        # (A test's stand-in for the step may be a bare function.)
        meta = getattr(step or self.step, "meta", {})
        windowed = {}
        pages = sum(-(-n // page) for n in live)
        if self.spec.row_tokens > 1:
            # Pooled rows: a slot attends the exact rows of the aligned
            # window its token lies in and one pooled row a chunk of the
            # windows before, not its live tokens.
            w, rows = self.spec.window, self.spec.row_tokens
            exact = [(n - 1) % w + 1 for n in live]
            pooled = [(n - 1) // w * (w // rows) for n in live]
            pages = sum(-(-n // page) for n in exact + pooled)
            windowed = dict(
                attended_rows=sum(exact) + sum(pooled),
                pooled_rows=sum(pooled),
                chunks_pooled=self._chunks_filled(slots),
                window_crossings=sum(n % w == 1 for n in live),
                window_tokens=sum(exact),
                window_pages=sum(-(-n // page) for n in exact),
                window_planes=self.spec.window_planes,
                window_pages_held=self.cache.window_live_pages)
        elif self.spec.window is not None:
            # What a window layer's walk reads in ONE of its planes: a
            # slot's last ``window`` tokens, and the pages they lie in.
            w = self.spec.window
            windowed = dict(
                window_tokens=sum(min(n, w) for n in live),
                window_pages=sum((n - 1) // page - max(n - w, 0) // page + 1
                                 for n in live),
                window_planes=self.spec.window_planes,
                window_pages_held=self.cache.window_live_pages)
        if self.spec.slot_state is not None:
            # What the round's state update must read, and write again:
            # each live slot's row (the part a round rewrites) a plane.
            spec = self.spec
            windowed.update(
                state_planes=spec.planes,
                state_bytes=len(slots) * spec.planes
                * (spec.slot_state_step or spec.slot_state)
                * self.cache.state.dtype.itemsize)
        return _spans.recorder().phase(
            "decode.round", round=int(st["decode_steps"]), slots=len(slots),
            live_tokens=sum(live), ahead=int(ahead),
            passes=self.spec.passes, planes=self.spec.planes,
            pages=pages,
            walk=int(meta.get("attention") == "walk"), **windowed)

    def _chunks_filled(self, slots: List[int]) -> int:
        """Slots whose token of the round about to be dispatched is its
        chunk's last (pooled rows): the round pools that chunk."""
        return sum((int(self.cache.lengths[s]) + 1) % self.spec.row_tokens
                   == 0 for s in slots)

    def decode_once(self, st: Dict[str, Any], now) -> float:
        """Dispatch one plain continuous-batching decode round over the
        live slots, and read and book the round before it.

        ``st``: the run's state (:meth:`run_state`).  The loop runs ONE
        ROUND AHEAD: this call reserves pages for, builds the operands
        of and dispatches the round for the slots that are live by count
        (:meth:`_decode_slots`), leaves it in ``st["in_flight"]``, and
        only then fetches and books the round that was in flight
        (:meth:`_retire`), so the chip has the next round queued while
        the host reads the last one.  A continuing slot's token never
        leaves the chip: the host gives the step ``-1`` for it and the
        step reads it from the ``told`` vector of the round before.  So
        with a JOINING slot's first token: its prefill left it in that
        vector (:func:`_hand_over`), and the host reads it with the
        round it retires next (or alone, where none was in flight:
        :meth:`_settle_joins`).  A loop that must have a round's tokens
        before it goes on (it rewrites slots or the mesh between rounds)
        calls :meth:`catch_up` behind this.  On entry
        ``_decode_slots()`` and ``cache.lengths`` describe the round
        this call dispatches: lengths advance at dispatch.  Returns the
        seconds from the retired round's dispatch to its fetch (0.0
        where nothing was retired).
        """
        cache = self.cache
        phase = _spans.recorder().phase
        slots = self._decode_slots()
        flying: Optional[_Flight] = st["in_flight"]
        n = int(st["decode_steps"])
        with self._round_span(st, slots, ahead=flying is not None):
            with phase("decode.reserve"):
                for slot in slots:
                    length = int(cache.lengths[slot])
                    cache.reserve(slot, length + 1, writable_from=length)
            with phase("decode.args"):
                active = np.zeros((self.slots,), bool)
                active[slots] = True
                tokens = np.array(st["last_tokens"])
                if flying is not None:
                    # Their tokens are on the chip, in the round before's
                    # ``told``, and so is the first token of a slot
                    # whose prefill was dispatched since; the host has
                    # every other live slot's (a re-prefill's, an
                    # imported ticket's).
                    tokens[flying.slots] = -1
                tokens[[j.slot for j in st["joins"]]] = -1
                args = [self._decode_params, cache.k, cache.v,
                        jnp.asarray(tokens),
                        cache.lengths_device(), cache.table_device(),
                        jnp.asarray(active)]
                if cache.window_table is not None:
                    args.append(cache.window_table_device())
                if self.kv_compress:
                    args += list(cache.compress_operands())
                if self.adapters is not None:
                    args += [self.adapters,
                             jnp.asarray(np.array(st["adapter_ids"]))]
            t0 = time.monotonic()
            # Beside the pools a step may carry device state of its own
            # (donated in, handed back).  The cache's slot state (where
            # the model has one) leads it: the step advances the rows of
            # its live slots; then the window group's pools.  Last comes
            # what the round tells of itself, the one thing the host
            # fetches.
            own = cache.carried
            n_state = len(own) + len(self._step_state)
            out = self.step(*args, *own, *self._step_state, self._told)
            _, cache.k, cache.v = out[:3]
            cache.take_carried(out[3:3 + len(own)])
            self._step_state = out[3 + len(own):3 + n_state]
            self._told = out[-1]
            # Sent to the host as soon as the round ends, whenever the
            # host comes to read it.
            self._told.copy_to_host_async()
            if self.spec.row_tokens > 1:
                cache.count_pooled(self._chunks_filled(slots))
            for slot in slots:
                cache.lengths[slot] += 1
                self.scheduler.active[slot].in_flight += 1
            st["decode_steps"] += 1
            st["occ_samples"].append(self.scheduler.occupancy)
            st["in_flight"] = _Flight(slots, self._told, t0, n)
            st["rounds_ahead"] += flying is not None
            if flying is not None:
                return self._retire(st, flying, now)
            # No round to read: the first tokens of the prefills this
            # round was dispatched behind, as soon as they end.
            self._settle_joins(st, now)
            return 0.0

    def _retire(self, st: Dict[str, Any], flight: _Flight, now,
                dropped: Sequence[int] = ()) -> float:
        """Fetch a dispatched round's ``told`` vector (the round's one
        sync point) and book it: tokens appended and time-stamped NOW,
        when the host has them; finished requests released; a slot with
        nonfinite logits quarantined.  ``dropped``: slots whose result
        is thrown away (the round ran on a token that was quarantined
        after its dispatch)."""
        sched = self.scheduler
        phase = _spans.recorder().phase
        with phase("decode.sample_fetch", round=flight.round):
            sampled, finite, tells = read_told(flight.told, self.slots)
            step_s = time.monotonic() - flight.t0
            # The prefills dispatched behind this round end after it:
            # the host waits here for their first tokens too, while the
            # chip has the round dispatched since.
            joined = self._fetch_joins(st)
        # What the step told of its round (``LayerSpec.step_tells``:
        # a routed model's touched experts) goes on the bookkeep span.
        told = {name: int(x) for name, x in zip(self.spec.step_tells,
                                                tells)}
        with phase("decode.bookkeep", round=flight.round, **told):
            poisoned = self._book_joins(st, joined, now)
            t_tok = now()
            for slot in flight.slots:
                req = sched.active[slot]
                req.in_flight -= 1
                if slot in dropped:
                    continue
                if not finite[slot]:
                    poisoned.append(slot)
                    continue
                tok = int(sampled[slot])
                req.tokens.append(tok)
                st["last_tokens"][slot] = tok
                sched.note_decode_token(req, t_tok)
                if req.finished or (not req.in_flight and int(
                        self.cache.lengths[slot]) >= self.max_len):
                    self._release(st, slot, now)
        self._quarantine(st, poisoned, now)
        return step_s

    def _quarantine(self, st: Dict[str, Any], poisoned: List[int],
                    now) -> None:
        """Slots whose token the host has just read was sampled from
        logits that were not finite."""
        if poisoned:
            # The round behind took these slots' tokens from the
            # poisoned one (and sat them out): read it first, without
            # them.
            self.catch_up(st, now, dropped=poisoned)
            for slot in poisoned:
                self._quarantine_logits(st, slot)

    def _fetch_joins(self, st: Dict[str, Any]) -> list:
        """``[(join, [token, finite])]`` of the prefills whose first
        token was left on the chip, read now: the host waits for the
        last of them to end."""
        joins, st["joins"] = st["joins"], []
        return [(join, np.asarray(join.first)[join.row]) for join in joins]

    def _book_joins(self, st: Dict[str, Any], joined: list,
                    now) -> List[int]:
        """Book the first tokens just fetched (what :meth:`_join_decode`
        does of a token the host had at once), ahead of anything the
        round after them brought.  Returns the slots whose first token
        came from logits that were not finite: nothing of them is
        booked, and the round dispatched since sat them out."""
        poisoned = []
        for join, (first, finite) in joined:
            join.req.in_flight -= 1
            if finite:
                self._book_first(st, join.slot, join.req, int(first), now)
            else:
                poisoned.append(join.slot)
        return poisoned

    def _settle_joins(self, st: Dict[str, Any], now) -> None:
        """Read and book the first tokens left on the chip where there
        is no round to read with them: the ONE ``prefill.sample_fetch``."""
        while st["joins"]:
            with _spans.recorder().phase("prefill.sample_fetch",
                                         joins=len(st["joins"])):
                joined = self._fetch_joins(st)
            self._quarantine(st, self._book_joins(st, joined, now), now)

    def catch_up(self, st: Dict[str, Any], now,
                 dropped: Sequence[int] = ()) -> float:
        """Retire the round in flight, if there is one, and read what
        first tokens are still on the chip: when the loop has nothing to
        dispatch, and before anything that rewrites slot or mesh state.
        Returns the retired round's seconds from dispatch to fetch."""
        flight, st["in_flight"] = st["in_flight"], None
        step_s = 0.0 if flight is None \
            else self._retire(st, flight, now, dropped)
        self._settle_joins(st, now)
        return step_s

    def spec_round(self, st: Dict[str, Any], now) -> float:
        """One speculative round: draft k, verify k+1 wide, accept the
        longest agreeing prefix per slot.

        Greedy-exact by construction -- every emitted token is the
        TARGET model's argmax (column j's logits condition on the
        accepted prefix only), so the stream is bitwise identical to
        plain decode; the drafter only changes how many tokens one
        dispatch amortises.  Rejected draft K/V stays above the rolled-
        back length: masked garbage, the recycled-page contract.
        """
        sched = self.scheduler
        cache = self.cache
        phase = _spans.recorder().phase
        k = self.spec_k
        width = k + 1
        self.catch_up(st, now)
        slots = self._decode_slots()
        n = int(st["decode_steps"])
        reqs = {s: sched.active[s] for s in slots}
        base = {s: int(cache.lengths[s]) for s in slots}
        with self._round_span(st, slots, step=self.verify_step):
            with phase("decode.reserve"):
                for s in slots:
                    # Room for this round's widest write, capped at the
                    # slot's page allotment (columns past max_len scatter
                    # to scratch).
                    cache.reserve(s, min(base[s] + width, self.max_len),
                                  writable_from=base[s])
            with phase("decode.args"):
                drafts = self.drafter.propose(reqs, k,
                                              np.array(st["last_tokens"]))
                tokens_in = np.zeros((self.slots, width), np.int32)
                tokens_in[:, 0] = st["last_tokens"]
                tokens_in[:, 1:] = drafts
                active = np.zeros((self.slots,), bool)
                active[slots] = True
                args = [self._decode_params, cache.k, cache.v,
                        jnp.asarray(tokens_in),
                        cache.lengths_device(), cache.table_device(),
                        jnp.asarray(active)]
                if self.kv_compress:
                    args += list(cache.compress_operands())
            t0 = time.monotonic()
            logits, cache.k, cache.v = self.verify_step(*args)
            with phase("decode.sample_fetch", round=n):
                told = np.asarray(_verify_told(logits))      # sync point
                sampled, finite = told[:, :width], told[:, width] != 0
            step_s = time.monotonic() - t0
            with phase("decode.bookkeep", round=n):
                st["decode_steps"] += 1
                st["spec_rounds"] += 1
                st["occ_samples"].append(sched.occupancy)
                t_tok = now()
                for s in slots:
                    req = reqs[s]
                    if not finite[s]:
                        self._quarantine_logits(st, s)
                        continue
                    # Longest agreeing prefix: draft j survives iff every
                    # earlier draft did AND it equals the target's argmax
                    # for the position it sits at.
                    m = 0
                    while m < k and drafts[s, m] == sampled[s, m]:
                        m += 1
                    emit = min(m + 1,
                               req.max_new_tokens - len(req.tokens),
                               self.max_len - base[s])
                    accepted = max(emit - 1, 0)
                    st["proposed"] += k
                    st["accepted"] += accepted
                    sched.note_spec(k, accepted)
                    for j in range(emit):
                        req.tokens.append(int(sampled[s, j]))
                        sched.note_decode_token(req, t_tok)
                    cache.lengths[s] = base[s] + emit
                    st["last_tokens"][s] = req.tokens[-1]
                    self.drafter.observe(s, req, accepted)
                    if req.finished or \
                            int(cache.lengths[s]) >= self.max_len:
                        self._release(st, s, now)
        return step_s

    # -- elastic resize hooks (driven by serving.controlplane) -------------
    def rebuild_mesh(self, mesh) -> None:
        """Swap the decode data plane onto a new tp mesh.

        The cache LAYOUT is mesh-size invariant by contract
        (``CacheConfig.layout``), so a resize is: fresh page pool with
        the new kv-head sharding, same scheduler (queue and in-flight
        request identity survive), and a rebuilt decode step.  The
        jitted ``_prefill`` is replicated math and carries over as-is --
        suspended requests are re-prefilled through it onto the new
        pool via :meth:`re_prefill`.
        """
        old_tp = int(self.mesh.devices.size)
        self.mesh = mesh
        self._decode_params = self._place_decode_params()
        self.spec.require(tp=mesh.devices.size > 1)
        self.cache = PagedKVCache(self.cache_config,
                                  self.spec.pool_sharding(mesh))
        self.scheduler.cache = self.cache
        if self._prefix is not None:
            # Cached pages lived in the old pool: start a fresh tree
            # over the new one (suspended requests re-prefill anyway).
            self._prefix = PrefixCache(
                self.cache, session_ttl_steps=self.session_ttl_steps)
        self._build_steps()
        # The auditor's serving branch notes resize provenance so the
        # post-shrink gate can assert the exchange contract held.
        self.step._meta["resized_from"] = old_tp
        if self.verify_step is not None:
            self.verify_step._meta["resized_from"] = old_tp

    def re_prefill(self, slot: int, req: Request) -> int:
        """Rebuild a suspended request's KV on the CURRENT mesh from its
        prompt + emitted tokens; returns the next decode input token.

        All emitted tokens except the last are part of the restored
        context (their K/V must be resident); the last token is the one
        the next decode step consumes, exactly as if it had just been
        sampled on this mesh.
        """
        if not req.tokens:
            raise ValueError(f"request {req.rid} has no emitted tokens")
        full = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.tokens[:-1], np.int32)])
        with _spans.recorder().span("dispatch", name="reprefill",
                                    leg="serving_reprefill"):
            aid = jnp.int32(req.adapter_id) if self.adapters is not None \
                else None
            _, *out = self._prefill(
                self.params, jnp.asarray(full)[None], self.adapters, aid)
            self._write_rows([(slot, req, None)], _members(out))
        if self.drafter is not None:
            self.drafter.re_prefill(slot, req)
        return int(req.tokens[-1])

    # -- the serve loop ----------------------------------------------------
    def serve(self, requests: Sequence[Request]) -> ServingReport:
        """Run the open-loop request stream to completion."""
        sched = self.scheduler
        pending = sorted(requests, key=lambda r: r.arrival_s)
        rejected = 0
        admissible = []
        for req in pending:
            if req.prompt_len + req.max_new_tokens > self.max_len:
                rejected += 1
                sched._m_requests.labels(event="rejected").inc()
            else:
                admissible.append(req)

        start = time.monotonic()
        skip = 0.0

        def now() -> float:
            return time.monotonic() - start + skip

        st = self.run_state()
        completed: List[Request] = st["completed"]
        prompts_dev: Dict[int, Any] = {}
        self._chunking.clear()

        rec = _spans.recorder()
        phase = rec.phase
        before = (rec.totals(), rec.filed, rec.dropped)
        with phase("serve", requests=len(admissible)) as root, \
                RequestPrefetcher(admissible, self.prefetch_depth) as feed:
            fetched = next(feed, None)

            while True:
                if self._prefix is not None:
                    # Advance the session-TTL clock every iteration
                    # (idle spins included) so pinned sessions always
                    # expire and page pressure can resolve.
                    self._prefix.tick()
                # Pull every request whose arrival time has passed.
                with phase("serve.arrivals"):
                    while fetched is not None and \
                            fetched[0].arrival_s <= now():
                        req, dev = fetched
                        prompts_dev[req.rid] = dev
                        sched.submit(req)
                        fetched = next(feed, None)
                if not sched.has_work():
                    if fetched is None:
                        break
                    # Idle: fast-forward the virtual clock to the next
                    # arrival instead of sleeping.
                    gap = fetched[0].arrival_s - now()
                    if gap > 0:
                        skip += gap
                    continue

                with phase("serve.admit"):
                    admitted = sched.admit(now())
                self.join(st, [(slot, req, prompts_dev.pop(req.rid))
                               for slot, req in admitted], now)
                if not self._decode_slots():
                    # Nothing to dispatch: what is live has its last
                    # token in flight, or is still being prefilled.
                    self.catch_up(st, now)
                    continue

                # One continuous-batching round over the decode batch:
                # a k-draft verify dispatch when speculating, else one
                # plain single-token step.
                if self.spec_decode:
                    self.spec_round(st, now)
                else:
                    self.decode_once(st, now)

            loop_s = _file_account(rec, root, before,
                                   rounds=int(st["decode_steps"]),
                                   prefills=int(st["prefills"]),
                                   prefill_groups=int(st["prefill_groups"]),
                                   prefills_grouped=int(
                                       st["prefills_grouped"]),
                                   first_tokens_deferred=int(
                                       st["first_tokens_deferred"]))

        wall_s = max(time.monotonic() - start, 1e-9)
        if self._step_state:
            # What the step accumulated on the device over this call,
            # fetched once now; the next call starts from zero.
            self.spec.publish_state(self._step_state)
            self._step_state = self._fresh_step_state()
        new_tokens = sum(len(r.tokens) for r in completed)
        prompt_tokens = sum(r.prompt_len for r in completed)
        ttfts = [r.ttft_s for r in completed if r.ttft_s is not None]
        lats = [g for r in completed for g in r.token_gaps]
        proposed = int(st["proposed"])
        accepted = int(st["accepted"])
        pq, ph = int(st["prefix_queries"]), int(st["prefix_hits"])
        cached = int(st["prefill_cached"])
        computed = int(st["prefill_computed"])
        return ServingReport(
            num_requests=len(requests), completed=len(completed),
            rejected=rejected, prompt_tokens=prompt_tokens,
            new_tokens=new_tokens, wall_s=wall_s,
            decode_steps=int(st["decode_steps"]),
            rounds_ahead=int(st["rounds_ahead"]),
            tokens_per_s=new_tokens / wall_s,
            ttft_p50_s=_pct(ttfts, 50), ttft_p99_s=_pct(ttfts, 99),
            token_latency_p50_s=_pct(lats, 50),
            token_latency_p99_s=_pct(lats, 99),
            mean_occupancy=(float(np.mean(st["occ_samples"]))
                            if st["occ_samples"] else 0.0),
            spec_rounds=int(st["spec_rounds"]),
            proposed_tokens=proposed, accepted_tokens=accepted,
            acceptance_rate=(accepted / proposed if proposed else 0.0),
            prefix_queries=pq, prefix_hits=ph,
            prefix_hit_rate=(ph / pq if pq else 0.0),
            prefill_tokens_cached=cached,
            prefill_flops_avoided=(cached / (cached + computed)
                                   if cached + computed else 0.0),
            session_resumes=int(st["session_resumes"]), loop_s=loop_s)
