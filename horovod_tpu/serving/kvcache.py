"""Sharded paged KV cache for the serving data plane.

Physical layout is a fixed page pool per layer::

    k, v: [num_layers, num_pages, page_size, num_kv_heads * head_dim]

A token's entry is ONE row with no head dim, head ``j`` in columns ``j *
head_dim .. (j + 1) * head_dim``: the decode step's page walk copies
whole pages and cuts a head's keys out of a page as tile-aligned
columns, which a ``[kv_heads, head_dim]`` entry does not allow.  (Where
``CacheConfig.page`` says what one token holds in each pool the pools
are ``[num_layers, num_pages, page_size, *page[0]]`` and ``*page[1]``; a
``page[1]`` of None means there is no second pool and ``v`` is None: a
latent-attention model keeps its normalised latent and its one rotated
key side by side in ONE row -- pages, refcounts, the table and every
write path are the same.)  The pools are sharded over the ``tp`` mesh
axis on the row: contiguous heads a shard, the same split the
tensor-parallel decode step gives the attention projections, so a rank's
cache shard pairs exactly with its ``wk``/``wv`` kernel shards and no
cross-rank traffic ever touches the cache.  The LOGICAL view -- which
pages belong to which batch slot, and how many tokens are live -- is
host-side metadata: an int32 ``page_table[slots, pages_per_slot]`` plus a
``lengths[slots]`` vector, shipped into the compiled step as plain
replicated operands.  Correctness never depends on page contents being
zeroed: every read masks positions ``>= lengths`` through
:func:`horovod_tpu.ops.attention.decode_attention`, so a recycled page's
stale keys are unreachable by construction (the eviction/reuse test
asserts this bit-for-bit).

A WINDOW GROUP (``CacheConfig.window_layers`` planes of layers that
read the last ``window`` tokens only) lives beside those pools with
pools, a free list and a page table of its own::

    wk, wv: [window_layers, slots * window_pages_per_slot + 1,
             page_size, row]

A slot holds at most ``window_pages_per_slot = ceil(window / page_size)
+ 1`` of its pages, as a ring: the page of tokens ``n * page_size ..``
is ``window_table[slot, n % window_pages_per_slot]``, and once the
window has moved past a page's tokens the page is written again (the
counter ``kv.window_pages_reused``).  A read masks what is older than
the window and what is past the length, so a reused page's stale rows
are as unreachable as a recycled full page's.

POOLED ROWS (``CacheConfig.row_tokens`` > 1; a model whose layers read
an ALIGNED window exactly and every chunk of ``row_tokens`` tokens behind
it through one pooled row): a row of the growing planes then stands for
a chunk, so a sequence of ``n`` tokens holds ``ceil(ceil(n / row_tokens)
/ page_size)`` growing pages beside its ring, and admission, reservation
and release price it so.  Every such layer has a plane in both groups,
and the window group's pages lie IN the growing planes' pools, behind
their scratch page (``window_in_pool``), so that one page walk reaches a
slot's pooled rows and its ring::

    k, v: [num_layers, num_pages + 1 + slots * window_pages_per_slot,
           page_size, row]

Pages are allocated lazily from a free list as a slot's sequence grows
and returned wholesale on eviction -- continuous batching recycles slots
mid-flight, so the pool, not the slot count, bounds resident KV bytes.

fp8 cold-page compression (``CacheConfig(compress=True)``): pages that
sit ``hot_pages`` full pages behind a slot's write head are *cold* --
decode only reads them, never writes them again while the slot lives.
A cold page can be migrated into a parallel e4m3 pool through the PR 5
fp8 codec (:func:`~horovod_tpu.collectives.compression.fp8_quantize`,
one max-abs scale per token-layer row so an all-zero row roundtrips to
exact zeros), after which its f32 page returns to the free list.  The
decode/verify steps blend the two pools on gather (``comp_mask`` picks
the dequantised e4m3 page), so compression is invisible to the masking
contract: a recycled compressed page's stale bytes are unreachable for
exactly the reason a recycled f32 page's are.  Admission is therefore
page-gated on COMPRESSED size: ``can_admit``/``reserve`` count cold
pages at their e4m3 cost (compressing on demand to reclaim f32 pages),
so the same physical pool admits roughly 4x the cold-token residency.

Prefix sharing (PR 16): pages are REFCOUNTED, so one physical page can
back the same token prefix in many slots at once.  A page popped off
the free list starts at refcount 1 (its slot); :meth:`attach_pages`
maps an existing page into another slot's table with refcount +1, and
:meth:`free_slot` is a refcount DECREMENT -- the page returns to the
free list only when its last holder lets go.  Shared pages are
immutable by construction: decode/verify only write at positions ``>=
lengths``, which always land past a matched prefix, and every write
path additionally runs a copy-on-write guard (:meth:`reserve` with
``writable_from``, :meth:`write_prefill`) that clones a still-shared
page into a private one before the first byte changes -- a divergent
continuation can NEVER mutate the shared original (asserted bitwise in
tests/test_serving.py).  On top sits :class:`PrefixCache`: a radix
tree over page-sized token-id chunks mapping shared prompt prefixes
(system prompts, RAG templates, multi-turn session context) to resident
pages, with session pinning, TTL expiry, the fp8 pool as its demotion
tier, and LRU eviction under page pressure.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..collectives.compression import fp8_quantize
from ..timeline.metrics import registry as _registry


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Static shape of the pool (identical on every rank and mesh size)."""

    num_layers: int
    # K and V of ``num_kv_heads * head_dim`` columns a token, one row in
    # each of two pools -- or ``page``, not both: the pools' shapes have
    # ONE source.
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    _: dataclasses.KW_ONLY
    slots: int
    page_size: int
    max_len: int
    dtype: str = "float32"
    compress: bool = False         # fp8 cold-page compression on/off
    hot_pages: int = 1             # full pages behind the head kept f32
    # Trailing dims of ONE token's entry in the first and second pool
    # (the second None: one pool only), in place of ``num_kv_heads`` and
    # ``head_dim`` (which then stay None).
    page: Optional[Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]] = None
    # Values a slot keeps a layer beside its pages (``LayerSpec.
    # slot_state``); None: no such state.  ``slot_state_dtype``: their
    # type (None: ``dtype``, the pools').
    slot_state: Optional[int] = None
    slot_state_dtype: Optional[str] = None
    # Planes of the WINDOW GROUP (``LayerSpec.window_planes``; 0: there
    # is none) and the window's length in tokens.
    window_layers: int = 0
    window: Optional[int] = None
    # Tokens a row of the growing planes stands for
    # (``LayerSpec.row_tokens``: a pooled row a chunk).  More than one:
    # every layer has a plane in both groups (``LayerSpec.attn_kinds``:
    # "chunked"), the window is an ALIGNED one (``window_aligned``) and
    # the window group's pages lie in the growing planes' own pools
    # (``window_in_pool``).
    row_tokens: int = 1

    def __post_init__(self):
        heads = (self.num_kv_heads, self.head_dim)
        if (self.page is None) == (None in heads):
            raise ValueError(
                "give num_kv_heads and head_dim, or page, and not both: "
                f"{heads}, {self.page}")
        if self.page is None:
            row = (int(self.num_kv_heads) * int(self.head_dim),)
            object.__setattr__(self, "page", (row, row))
        else:
            object.__setattr__(self, "page", tuple(
                None if e is None else tuple(int(n) for n in e)
                for e in self.page))
        if self.compress and self.page[1] is None:
            raise NotImplementedError(
                "the fp8 cold pool mirrors TWO pools (six step operands), "
                f"not {self.page}")
        if self.max_len % self.page_size or self.row_tokens < 1:
            raise ValueError(
                f"max_len {self.max_len} not a multiple of page_size "
                f"{self.page_size}" + (
                    f"; {self.row_tokens} tokens a row"
                    if self.row_tokens != 1 else ""))
        if self.hot_pages < 0:
            raise ValueError(f"hot_pages must be >= 0: {self.hot_pages}")
        if (self.window_layers > 0) != (self.window is not None) or (
                self.window is not None and self.window < 1):
            raise ValueError(
                f"{self.window_layers} window planes and a window of "
                f"{self.window}")
        if self.window_layers and (self.compress or self.page[1] is None):
            raise NotImplementedError(
                "a window group goes with two pools and no fp8 cold "
                f"pool: compress {self.compress}, pools {self.page}")
        if self.row_tokens > 1 and (
                self.window_layers != self.num_layers
                or self.window % self.page_size
                or self.window % self.row_tokens):
            raise ValueError(
                f"rows of {self.row_tokens} tokens go with a window plane "
                f"a plane ({self.window_layers} of {self.num_layers}) and "
                f"an aligned window of whole pages and rows: {self.window} "
                f"over pages of {self.page_size}")

    @property
    def window_in_pool(self) -> bool:
        """The window group's pages lie in the growing planes' own pools,
        behind the scratch page, under the ids ``window_first_page ..``:
        a layer reads both groups through one walk."""
        return self.row_tokens > 1

    @property
    def window_aligned(self) -> bool:
        """Token ``i`` sees the window it lies in, from ``window * (i //
        window)``, not its last ``window`` tokens."""
        return self.row_tokens > 1

    @property
    def window_pages_per_slot(self) -> int:
        """Pages a slot holds in a window plane at most: the window's,
        and one more for a window that straddles page boundaries."""
        if not self.window_layers:
            return 0
        return -(-self.window // self.page_size) + 1

    @property
    def window_num_pages(self) -> int:
        return self.slots * self.window_pages_per_slot

    @property
    def entries(self) -> tuple:
        """What one token holds in the first and in the second pool
        (None: there is no second pool)."""
        return self.page

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_len // (self.page_size * self.row_tokens))

    @property
    def num_pages(self) -> int:
        return self.slots * self.pages_per_slot

    @property
    def pool_pages(self) -> int:
        """Pages of a plane of the pools: the growing group's, the
        scratch page and, where they lie there, the window group's."""
        return self.num_pages + 1 + (
            self.window_num_pages if self.window_in_pool else 0)

    @property
    def window_first_page(self) -> int:
        """The id of the window group's first page: 0 in pools of its
        own, behind the scratch page in the growing planes'."""
        return self.num_pages + 1 if self.window_in_pool else 0

    def pages_for(self, length: int) -> int:
        """Growing pages a sequence of ``length`` tokens holds."""
        return -(-(-(-int(length) // self.row_tokens)) // self.page_size)

    def ring_pages_for(self, length: int) -> int:
        """Window pages a sequence of ``length`` tokens holds: its
        tokens' pages, as far as the ring goes."""
        return min(-(-int(length) // self.page_size),
                   self.window_pages_per_slot)

    @property
    def scratch_page(self) -> int:
        """Index of the write sink: the decode step writes EVERY slot's
        K/V unconditionally (fixed-shape batch), so idle slots are
        redirected to this extra page past the allocatable pool instead
        of clobbering page 0."""
        return self.num_pages

    def layout(self) -> dict:
        """GLOBAL layout descriptor.  Mesh-size invariant by contract:
        the pool shape, page table geometry and dtype never depend on
        how many ranks the kv-head dim is split over (asserted by
        tests/test_serving.py across 1- and 8-device meshes)."""
        out = {
            "kv_shape": [self.num_layers, self.pool_pages,
                         self.page_size, *self.entries[0]],
            "page_table_shape": [self.slots, self.pages_per_slot],
            "page_size": self.page_size,
            "pages_per_slot": self.pages_per_slot,
            "num_pages": self.num_pages,
            "scratch_page": self.scratch_page,
            "dtype": str(jnp.dtype(self.dtype)),
        }
        if self.window_layers:
            # (A model without window layers describes what it always
            # did: no key is added to its layout.)
            out.update(
                window=self.window,
                window_table_shape=[self.slots,
                                    self.window_pages_per_slot],
                window_pages_per_slot=self.window_pages_per_slot,
                window_num_pages=self.window_num_pages)
            if self.window_in_pool:
                # (Nor to a window group's in pools of its own.)
                out.update(row_tokens=self.row_tokens,
                           window_first_page=self.window_first_page)
            else:
                out.update(
                    window_kv_shape=[self.window_layers,
                                     self.window_num_pages + 1,
                                     self.page_size, *self.entries[0]],
                    window_scratch_page=self.window_num_pages)
        return out


def window_rows_from(length: int, window: int,
                     aligned: bool = False) -> int:
    """The first row a window plane keeps of a prompt of ``length``
    tokens: the oldest token that the NEXT token's window still sees
    (``aligned``: the first token of the window the next token lies
    in)."""
    if aligned:
        return length // window * window
    return max(length + 1 - window, 0)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("head",))
def _pool_set(pool, values, pages=None, rows=None, head=None):
    """Write ``values`` into the pool in place: the pool is donated, so
    the scatters write what they name and copy nothing else.  Compiles
    once per ``values`` shape (``head`` follows from it in every prompt a
    cell sends).

    ``head`` None: ``values`` ARE pages, ``[layers, (n,) page_size,
    *entry]`` for ``pool[:, pages]`` (a streamed page, a clone).

    Else ``values`` are a prompt's rows ``[layers, t, *entry]``: rows
    ``head .. head + n * page_size`` are the ``n`` WHOLE pages ``pages``
    names (None: the rows cover no page whole), one scatter update a
    page a layer; what lies before and after them (a first page entered
    past its start, a last page left before its end) goes to the
    ``(page, offset)`` pairs ``rows`` ``[2, r]`` names (None: there is
    nothing ragged), one update a row a layer.  Why pages: a pool is
    laid out in tiles of 16 bfloat16 rows by 128 columns (8 float32
    rows), so a 16-row page is one row of tiles (two), and an update of
    ONE row rewrites a tile under each 128 of its columns and costs an
    update, some 100-130 ns on a v5e whatever the row holds -- sixteen
    rows cost sixteen updates, their page one of 125-210 ns (``PERF.md``
    section 6, PR 50).  The layers are indexed like the pages: under a
    leading slice XLA re-lays a pool of rows with no head dim out to
    scatter (two pool-sized copies) and copies the pages' values
    transposed first, both seen in a deviceless compile; indexed, every
    form scatters in place."""
    with jax.named_scope("hvd_kv_write_prefill"):
        if head is None:
            return pool.at[:, pages].set(values)
        layers = jnp.arange(pool.shape[0])[:, None]
        span = 0 if pages is None else pages.shape[0] * pool.shape[2]
        if span:
            whole = values[:, head:head + span].reshape(
                (pool.shape[0], -1) + pool.shape[2:])
            pool = pool.at[layers, pages[None, :]].set(whole)
        if rows is not None:
            ragged = jnp.concatenate(
                [values[:, :head], values[:, head + span:]], axis=1)
            pool = pool.at[layers, rows[0][None, :], rows[1][None, :]].set(
                ragged)
        return pool


@functools.partial(jax.jit, donate_argnums=(0,))
def _state_set(state, rows, slot):
    """``state.at[:, slot].set(rows)`` in place (``slot`` traced: one
    program for every slot)."""
    with jax.named_scope("hvd_slot_state_write"):
        return jax.lax.dynamic_update_slice(
            state, rows[:, None].astype(state.dtype), (0, slot, 0))


class PagedKVCache:
    """Device page pool + host page table / free list for one model.

    Two kinds of per-sequence state live here.  PAGES: what each token
    holds, ``[layers, pages, page_size, *entry]`` a pool, mapped through
    the page table.  SLOT STATE (``CacheConfig.slot_state``; None for a
    model whose pages are all it has): what the sequence itself holds
    beside them, ``state`` ``[layers, slots, width]``, one row a slot,
    in a type of its own where the model says so
    (``CacheConfig.slot_state_dtype``: a recurrence's float32 state
    beside bfloat16 pools) --
    written by :meth:`write_state` from the prefill's trailing rows,
    advanced by the decode step, cleared by :meth:`free_slot` (from one
    row of zeros kept on the device).  It is owned like a pool: every
    program that writes it is handed the array, donated, and returns
    its successor; it may outweigh the pools, and nothing ever holds a
    second copy of it.

    ``k`` and ``v`` have ONE owner at a time: every program that writes
    a pool (the decode/verify step, :meth:`write_prefill`, the
    copy-on-write clone, :meth:`adopt_pages`) consumes the array it is
    given and hands back its successor, which is rebound here.  Read
    ``cache.k``/``cache.v`` at call time and keep no reference across a
    write -- the old array is deleted; ``jnp.copy`` it first to keep a
    snapshot."""

    def __init__(self, config: CacheConfig, sharding=None):
        self.config = config
        c = config
        # +1: trailing scratch page, the write sink for idle slots.
        lead = (c.num_layers, c.pool_pages, c.page_size)
        k = jnp.zeros(lead + c.entries[0], jnp.dtype(c.dtype))
        v = None if c.entries[1] is None else jnp.zeros(
            lead + c.entries[1], jnp.dtype(c.dtype))
        if sharding is not None:
            k = jax.device_put(k, sharding)
            v = None if v is None else jax.device_put(v, sharding)
        self.sharding = sharding
        self.k = k
        self.v = v
        self.state = None
        if c.slot_state is not None:
            kept = jnp.dtype(c.slot_state_dtype or c.dtype)
            self.state = jnp.zeros(
                (c.num_layers, c.slots, c.slot_state), kept)
            # A released slot's row is cleared from this ONE row of
            # zeros, made once: a release builds nothing.
            self._cleared = jnp.zeros((c.num_layers, c.slot_state), kept)
            self._m_state_written = _registry().counter(
                "kv.state_bytes_written",
                "bytes of slot state that joins wrote (a prefill's "
                "trailing rows, a plane a row)")
            self._m_state_cleared = _registry().counter(
                "kv.state_rows_cleared",
                "slot-state rows (a slot's, over every plane) that "
                "releases cleared")
            if sharding is not None:
                # Whole on every chip, and committed like the pools: a
                # step compiles once, whoever wrote the array last.
                self.state = jax.device_put(
                    self.state, jax.sharding.NamedSharding(
                        sharding.mesh, jax.sharding.PartitionSpec()))
        # The window group: pools, table, free list and per-slot page
        # counts of its own (None and empty where the model has no
        # window layer).
        self.wk = self.wv = None
        self.window_table = None
        self._wallocated = np.zeros((c.slots,), np.int32)
        # Pages of tokens a slot's sequence has reached (its growing
        # pages, where a row is a token).
        self._wreached = np.zeros((c.slots,), np.int32)
        self._wfree: List[int] = []
        if c.window_layers:
            if not c.window_in_pool:
                wlead = (c.window_layers, c.window_num_pages + 1,
                         c.page_size)
                self.wk = jnp.zeros(wlead + c.entries[0],
                                    jnp.dtype(c.dtype))
                self.wv = jnp.zeros(wlead + c.entries[1],
                                    jnp.dtype(c.dtype))
                if sharding is not None:
                    self.wk = jax.device_put(self.wk, sharding)
                    self.wv = jax.device_put(self.wv, sharding)
            self.window_table = np.full(
                (c.slots, c.window_pages_per_slot), c.window_first_page,
                np.int32)
            self._wfree = list(range(
                c.window_first_page + c.window_num_pages - 1,
                c.window_first_page - 1, -1))
            self._m_reused = _registry().counter(
                "kv.window_pages_reused",
                "pages a window plane's slot wrote again once their "
                "tokens had left the window")
        # What prefills wrote, by scatter updates (one a page or a row,
        # a plane, a pool): the rows' share that took the page path is
        # ``page_size * pages / (page_size * pages + rows)``.
        self._m_pages_written = _registry().counter(
            "kv.prefill_pages_written",
            "whole pages that prefills wrote, a plane a pool: one "
            "scatter update each")
        self._m_rows_written = _registry().counter(
            "kv.prefill_rows_written",
            "rows of a page entered past its start or left before its "
            "end that prefills wrote singly, a plane a pool")
        if c.row_tokens > 1:
            self._m_pooled = _registry().counter(
                "kv.pooled_rows_written",
                "pooled rows (a chunk of a slot's sequence each, one row "
                "in every growing plane of each pool) that prefills and "
                "decode rounds wrote")
        # Host-side logical view.  Unallocated table entries point at
        # page 0 -- harmless, reads beyond ``lengths`` are masked.
        self.page_table = np.zeros((c.slots, c.pages_per_slot), np.int32)
        self.lengths = np.zeros((c.slots,), np.int32)
        self._allocated = np.zeros((c.slots,), np.int32)  # pages per slot
        self._free = list(range(c.num_pages - 1, -1, -1))  # pop() -> 0, 1...
        # Holders per physical page: 0 = on the free list, 1 = private,
        # >1 = shared across slots and/or pinned by the prefix tree.
        self._refcount = np.zeros((c.num_pages,), np.int32)
        # Optional page-pressure hook (PrefixCache installs itself
        # here): called with the page shortfall before admission or
        # reservation gives up, so cached-but-unreferenced prefixes are
        # demoted/evicted instead of blocking live traffic.
        self.reclaim_cb = None
        # fp8 cold-page pool: a parallel e4m3 page space plus one max-abs
        # scale per (layer, page, offset) row, blended in on gather by the
        # decode/verify steps wherever ``comp_mask`` is set.
        self.compress = bool(c.compress)
        if self.compress:
            self.kq = jnp.zeros(lead + c.entries[0], jnp.float8_e4m3fn)
            self.vq = jnp.zeros(lead + c.entries[1], jnp.float8_e4m3fn)
            if sharding is not None:
                self.kq = jax.device_put(self.kq, sharding)
                self.vq = jax.device_put(self.vq, sharding)
            self.kscale = jnp.ones(lead, jnp.float32)
            self.vscale = jnp.ones(lead, jnp.float32)
            self.cpage_table = np.zeros((c.slots, c.pages_per_slot),
                                        np.int32)
            self.comp_mask = np.zeros((c.slots, c.pages_per_slot), bool)
            self._cfree = list(range(c.num_pages - 1, -1, -1))
            self._cheld = np.zeros((c.slots,), np.int32)
            self._crefcount = np.zeros((c.num_pages,), np.int32)

    # -- page accounting ---------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        """f32 pages currently held by slots (free_pages +
        allocated_pages == num_pages is the pool invariant the drain
        tests assert; compressed pages live in the e4m3 pool and are
        accounted by :attr:`compressed_pages`)."""
        total = int(self._allocated.sum())
        if self.compress:
            total -= int(self._cheld.sum())
        return total

    @property
    def compressed_pages(self) -> int:
        return int(self._cheld.sum()) if self.compress else 0

    @property
    def live_pages(self) -> int:
        """Physical f32 pages with at least one holder, the window
        group's among them.  The pool
        invariant under sharing is ``free_pages + live_pages ==
        num_pages`` of the full group (``allocated_pages`` counts TABLE
        ENTRIES and double-counts a page shared by two slots)."""
        return int((self._refcount > 0).sum()) + self.window_live_pages

    @property
    def window_live_pages(self) -> int:
        """Pages of the window group that a slot holds (never shared)."""
        return int(self._wallocated.sum())

    def refcounts_balanced(self) -> bool:
        """True when every page is either on a free list (refcount 0)
        or held (refcount > 0) with the free lists consistent -- the
        drain-time leak check."""
        ok = len(self._free) + int((self._refcount > 0).sum()) \
            == self.config.num_pages
        ok = ok and not any(self._refcount[p] for p in self._free)
        ok = ok and len(self._wfree) + self.window_live_pages \
            == self.config.window_num_pages
        if self.compress:
            live_c = int((self._crefcount > 0).sum())
            ok = ok and len(self._cfree) + live_c == self.config.num_pages
            ok = ok and not any(self._crefcount[p] for p in self._cfree)
        return bool(ok)

    # -- refcount primitives ----------------------------------------------
    def add_page_ref(self, pid: int, kind: str = "f") -> None:
        if kind == "c":
            self._crefcount[pid] += 1
        else:
            self._refcount[pid] += 1

    def drop_page_ref(self, pid: int, kind: str = "f") -> bool:
        """Drop one holder; returns True when that freed the physical
        page (last reference gone -- the page rejoins its free list
        unzeroed, the masking contract keeps its stale bytes dark)."""
        if kind == "c":
            self._crefcount[pid] -= 1
            if self._crefcount[pid] == 0:
                self._cfree.append(int(pid))
                return True
            return False
        self._refcount[pid] -= 1
        if self._refcount[pid] == 0:
            self._free.append(int(pid))
            return True
        return False

    @property
    def resident_bytes(self) -> int:
        """Logical KV residency at COMPRESSED accounting: f32 pages at
        full price, cold e4m3 pages at one byte per element plus the
        per-row f32 scale (the number ``can_admit`` effectively budgets
        against)."""
        c = self.config
        row = sum(int(np.prod(e)) for e in c.entries     # both pools
                  if e is not None)
        page_f32 = c.num_layers * c.page_size * row \
            * jnp.dtype(c.dtype).itemsize
        page_fp8 = c.num_layers * c.page_size * (row + 8)
        page_window = c.window_layers * c.page_size * row \
            * jnp.dtype(c.dtype).itemsize
        return (self.allocated_pages * page_f32
                + self.compressed_pages * page_fp8
                + self.window_live_pages * page_window)

    def _cold_candidates(self, exclude: Optional[int] = None
                         ) -> List[int]:
        """Slots ordered by how many not-yet-compressed cold pages they
        hold (descending) -- the reclaim sweep order."""
        c = self.config
        out = []
        for slot in range(c.slots):
            if slot == exclude:
                continue
            n = self._cold_count(slot)
            if n > 0:
                out.append((n, slot))
        return [slot for _, slot in sorted(out, reverse=True)]

    def _cold_indices(self, slot: int) -> List[int]:
        """Table indices of ``slot``'s cold pages still resident in
        f32: full pages at least ``hot_pages`` behind the write head
        that are not yet compressed and not SHARED (migrating a page
        another holder still reads through the f32 table would dangle
        their gather).  Pages at or past ``lengths`` are NEVER cold --
        the decode/verify steps may still write them (speculative
        rejects roll ``lengths`` back below already-written
        positions)."""
        c = self.config
        full = int(self.lengths[slot]) // c.page_size
        out = []
        for i in range(max(0, full - c.hot_pages)):
            if self.comp_mask[slot, i]:
                continue
            if self._refcount[int(self.page_table[slot, i])] != 1:
                continue
            out.append(i)
        return out

    def _cold_count(self, slot: int) -> int:
        return len(self._cold_indices(slot))

    def can_admit(self, length: int) -> bool:
        """Whether a sequence of ``length`` tokens fits the pool now.

        With compression the gate prices cold pages at their compressed
        size: f32 pages reclaimable by a cold sweep (bounded by e4m3
        pool headroom) count as free.  Under page pressure the prefix
        tree's ``reclaim_cb`` is asked to demote/evict unreferenced
        cached prefixes first -- live traffic always outranks cache
        residency."""

        def avail() -> int:
            a = len(self._free)
            if self.compress:
                cold = sum(self._cold_count(s)
                           for s in range(self.config.slots))
                a += min(cold, len(self._cfree))
            return a

        c = self.config
        need = c.pages_for(max(int(length), 1))
        if need > avail() and self.reclaim_cb is not None:
            self.reclaim_cb(need - avail())
        # The window group: a slot's ring at its fullest.
        return need <= avail() and c.ring_pages_for(
            max(int(length), 1)) <= len(self._wfree)

    def reserve(self, slot: int, length: int,
                writable_from: Optional[int] = None) -> None:
        """Ensure slot ``slot`` has pages for ``length`` tokens,
        compressing other slots' cold pages on demand when the f32 free
        list runs short.

        ``writable_from``: token position of the first upcoming WRITE
        (the decode step's append point).  Every page covering
        ``writable_from ..`` is made private first -- the copy-on-write
        guard for shared prefix pages."""
        c = self.config
        if length > c.max_len:
            raise ValueError(f"length {length} exceeds max_len {c.max_len}")
        need = c.pages_for(length)
        have = int(self._allocated[slot])
        ring_short = c.ring_pages_for(length) \
            - int(self._wallocated[slot]) - len(self._wfree)
        if ring_short > 0:
            # Before a page of either group is taken.
            raise RuntimeError(
                f"window page pool exhausted: slot {slot} is "
                f"{ring_short} page(s) short")
        if need > have:
            short = need - have - len(self._free)
            if short > 0 and self.reclaim_cb is not None:
                self.reclaim_cb(short)
                short = need - have - len(self._free)
            if short > 0 and self.compress:
                self._reclaim(short, exclude=slot)
            if need - have > len(self._free):
                raise RuntimeError(
                    f"KV page pool exhausted: slot {slot} needs "
                    f"{need - have} page(s), {len(self._free)} free")
            for i in range(have, need):
                pid = self._free.pop()
                self._refcount[pid] = 1
                self.page_table[slot, i] = pid
            self._allocated[slot] = need
        if c.window_layers:
            self._reserve_window(slot, -(-int(length) // c.page_size))
        if writable_from is not None:
            self._make_writable(slot, writable_from)

    def _reserve_window(self, slot: int, need: int) -> None:
        """The window group's side of :meth:`reserve`, ``need`` the pages
        of TOKENS the sequence now reaches: the slot's ring grows to
        ``min(need, window_pages_per_slot)`` pages; a page beyond that
        is one of the ring's own, taken back from tokens the window has
        left (counted against the pages the slot's sequence had reached
        before this call; a prompt's prefill, which starts from none,
        writes its last pages only and takes nothing back)."""
        ring = self.config.window_pages_per_slot
        held = int(self._wallocated[slot])
        have = int(self._wreached[slot])
        want = min(need, ring)
        for i in range(held, want):
            self.window_table[slot, i] = self._wfree.pop()
        self._wallocated[slot] = max(held, want)
        self._wreached[slot] = max(have, need)
        if have and need > max(have, ring):
            self._m_reused.inc(need - max(have, ring))

    def _make_writable(self, slot: int, from_pos: int) -> None:
        """Copy-on-write guard: clone every still-shared page covering
        positions ``>= from_pos`` into a private page before the slot
        writes there.  The shared original is never mutated -- holders
        reading it through the tree or another slot keep seeing the
        exact bytes they attached (bitwise, by construction: the write
        lands in the clone)."""
        c = self.config
        for i in range(int(from_pos) // (c.page_size * c.row_tokens),
                       int(self._allocated[slot])):
            if self.compress and self.comp_mask[slot, i]:
                raise RuntimeError(
                    f"slot {slot} page {i} is fp8-demoted inside the "
                    "write range; demotion must stay strictly below "
                    "the write head")
            pid = int(self.page_table[slot, i])
            if self._refcount[pid] <= 1:
                continue
            if not self._free and self.reclaim_cb is not None:
                self.reclaim_cb(1)
            if not self._free and self.compress:
                self._reclaim(1, exclude=slot)
            if not self._free:
                raise RuntimeError(
                    "KV page pool exhausted during copy-on-write "
                    f"divergence of slot {slot}")
            new = self._free.pop()
            self._refcount[new] = 1
            self.k = _pool_set(self.k, self.k[:, pid], new)
            if self.v is not None:
                self.v = _pool_set(self.v, self.v[:, pid], new)
            self.page_table[slot, i] = new
            self.drop_page_ref(pid)

    def _reclaim(self, pages: int, exclude: Optional[int] = None) -> int:
        """Compress cold pages across slots until ``pages`` f32 pages
        came back (or candidates ran out).  Returns pages reclaimed."""
        got = 0
        for slot in self._cold_candidates(exclude=exclude):
            if got >= pages:
                break
            got += self.compress_cold(
                slot, max_pages=pages - got)
        return got

    def compress_cold(self, slot: int, max_pages: Optional[int] = None
                      ) -> int:
        """Migrate up to ``max_pages`` of ``slot``'s cold pages into the
        e4m3 pool (lowest table index first -- compression grows from
        the prefix end; shared pages are skipped, other holders still
        read them through f32), returning their f32 pages to the free
        list.  The freed f32 table entries are pointed at the scratch
        page; gathers never read them (``comp_mask`` blends the e4m3
        page in) but a sound table beats a dangling one."""
        if not self.compress:
            raise RuntimeError("cache built without compress=True")
        c = self.config
        idxs = self._cold_indices(slot)
        if max_pages is not None:
            idxs = idxs[:max_pages]
        idxs = idxs[:len(self._cfree)]
        if not idxs:
            return 0
        pids = np.asarray([self.page_table[slot, i] for i in idxs],
                          np.int32)
        cpids = np.asarray([self._cfree.pop() for _ in idxs], np.int32)
        dev_pids = jnp.asarray(pids)
        kq, ksc = _quantize_pages(self.k, dev_pids)
        vq, vsc = _quantize_pages(self.v, dev_pids)
        cp = jnp.asarray(cpids)
        self.kq = self.kq.at[:, cp].set(kq)
        self.vq = self.vq.at[:, cp].set(vq)
        self.kscale = self.kscale.at[:, cp].set(ksc)
        self.vscale = self.vscale.at[:, cp].set(vsc)
        for i, cpid, pid in zip(idxs, cpids, pids):
            self.cpage_table[slot, i] = cpid
            self.comp_mask[slot, i] = True
            self._crefcount[cpid] = 1
            self.page_table[slot, i] = c.scratch_page
            self.drop_page_ref(int(pid))
        self._cheld[slot] += len(idxs)
        return len(idxs)

    def free_slot(self, slot: int) -> None:
        """Refcount-decrement the slot's pages and mark it idle.  A
        private page rejoins the free list immediately; a SHARED page
        (prefix tree or another slot still holds it) stays resident
        until its last reference drops.  Page CONTENTS are deliberately
        left in place either way: the masking contract, not zeroing, is
        what guarantees no stale attention mass."""
        n = int(self._allocated[slot])
        for i in range(n - 1, -1, -1):
            if self.compress and self.comp_mask[slot, i]:
                self.drop_page_ref(int(self.cpage_table[slot, i]), "c")
                self.comp_mask[slot, i] = False
            else:
                self.drop_page_ref(int(self.page_table[slot, i]))
        self._allocated[slot] = 0
        for i in range(int(self._wallocated[slot]) - 1, -1, -1):
            self._wfree.append(int(self.window_table[slot, i]))
        self._wallocated[slot] = self._wreached[slot] = 0
        if self.compress:
            self._cheld[slot] = 0
        if self.state is not None and self.lengths[slot]:
            # Unlike a page's contents the slot's row is not behind a
            # length mask: the next sequence here starts from zeros.
            self.state = _state_set(self.state, self._cleared,
                                    jnp.int32(slot))
            self._m_state_cleared.inc()
        self.lengths[slot] = 0

    def release_all(self) -> int:
        """Free every slot and return how many pages that recovered.

        The drain path frees each suspended slot individually, so a
        healthy shrink sees ``release_all() == 0`` afterwards -- the
        control-plane tests use that as the exact-release check (a
        non-zero return means a slot leaked its pages past the drain).
        """
        freed = 0
        for slot in range(self.config.slots):
            n = int(self._allocated[slot]) + int(self._wallocated[slot])
            if n:
                freed += n
                self.free_slot(slot)
        return freed

    # -- prefix sharing ----------------------------------------------------
    def attach_pages(self, slot: int,
                     entries: Sequence[Tuple[str, int]],
                     length: int) -> None:
        """Map already-resident pages into an EMPTY slot's table with
        refcount +1 each -- the prefix-cache hit path: the matched
        prefix's K/V is live without a single prefill FLOP.  Entries
        are ``("f", page)`` f32 or ``("c", cpage)`` fp8-demoted; the
        slot's first ``length`` tokens (``len(entries)`` full pages)
        are then readable and the tail prefill continues at ``start=
        length`` via :meth:`write_prefill`."""
        c = self.config
        if int(self._allocated[slot]):
            raise RuntimeError(
                f"attach_pages: slot {slot} is not empty")
        if len(entries) * c.page_size != int(length):
            raise ValueError(
                f"attach_pages: {len(entries)} page(s) cannot back "
                f"{length} tokens at page_size {c.page_size}")
        for i, (kind, pid) in enumerate(entries):
            if kind == "c":
                if not self.compress:
                    raise RuntimeError(
                        "compressed prefix entry on a compress=False "
                        "cache")
                self.cpage_table[slot, i] = pid
                self.comp_mask[slot, i] = True
                self.page_table[slot, i] = c.scratch_page
                self._cheld[slot] += 1
            else:
                self.page_table[slot, i] = pid
            self.add_page_ref(pid, kind)
        self._allocated[slot] = len(entries)
        self.lengths[slot] = int(length)

    def adopt_pages(self, k_pages, v_pages=None) -> List[Tuple[str, int]]:
        """Materialize STREAMED full pages (``[planes, n, page_size,
        *entry]`` of each pool, the ``serving.kvwire`` f32 tier;
        ``v_pages`` None where there is one pool) as resident pool pages at
        refcount 1, owned by the caller.  The disaggregated import path
        then maps them into a slot with :meth:`attach_pages` and drops
        the importer's reference -- exactly the prefix-hit flow, except
        the bytes arrived over the rendezvous KV plane instead of being
        computed here.  Contents are written verbatim (no requantize,
        no cast beyond the pool dtype), so an f32-tier import is
        bitwise identical to a local ``write_prefill``."""
        k_pages = np.asarray(k_pages)
        n = int(k_pages.shape[1])
        if n == 0:
            return []
        short = n - len(self._free)
        if short > 0 and self.reclaim_cb is not None:
            self.reclaim_cb(short)
            short = n - len(self._free)
        if short > 0 and self.compress:
            self._reclaim(short)
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: adopting {n} streamed "
                f"page(s), {len(self._free)} free")
        pids = np.asarray([self._free.pop() for _ in range(n)], np.int32)
        for pid in pids:
            self._refcount[pid] = 1
        dt = jnp.dtype(self.config.dtype)
        dev = jnp.asarray(pids)
        self.k = _pool_set(self.k, jnp.asarray(k_pages, dt), dev)
        if self.v is not None:
            self.v = _pool_set(self.v, jnp.asarray(np.asarray(v_pages), dt),
                               dev)
        return [("f", int(p)) for p in pids]

    def adopt_compressed_pages(self, kq, vq, kscale, vscale
                               ) -> List[Tuple[str, int]]:
        """fp8 twin of :meth:`adopt_pages`: land streamed e4m3 pages +
        per-row scales (the ``serving.kvwire`` fp8 tier, the PR 14
        cold-page codec) straight into the compressed pool at refcount
        1.  Because the wire quantization reuses ``_quantize_pages``'s
        exact reshape/axis, an imported page is bit-identical to
        :meth:`demote_page` of the same resident bytes -- the decode
        gather blend cannot tell the two apart."""
        if not self.compress:
            raise RuntimeError("cache built without compress=True")
        kq = np.asarray(kq)
        n = int(kq.shape[1])
        if n == 0:
            return []
        if n > len(self._cfree):
            raise RuntimeError(
                f"e4m3 pool exhausted: adopting {n} streamed cold "
                f"page(s), {len(self._cfree)} free")
        cpids = np.asarray([self._cfree.pop() for _ in range(n)],
                           np.int32)
        for cpid in cpids:
            self._crefcount[cpid] = 1
        cp = jnp.asarray(cpids)
        self.kq = self.kq.at[:, cp].set(
            jnp.asarray(kq, jnp.float8_e4m3fn))
        self.vq = self.vq.at[:, cp].set(
            jnp.asarray(np.asarray(vq), jnp.float8_e4m3fn))
        self.kscale = self.kscale.at[:, cp].set(
            jnp.asarray(np.asarray(kscale), jnp.float32))
        self.vscale = self.vscale.at[:, cp].set(
            jnp.asarray(np.asarray(vscale), jnp.float32))
        return [("c", int(p)) for p in cpids]

    def gather_pages(self, entries: Sequence[Tuple[str, int]]) -> tuple:
        """Materialize page contents as chunked-prefill ``past``
        operands: ``(k, v)`` each ``[num_layers, 1, n * page_size,
        *entry]``, fp8-demoted pages dequantized
        through their per-row scales (same blend the decode gather
        does)."""
        c = self.config
        fp = np.asarray([pid if kind == "f" else c.scratch_page
                         for kind, pid in entries], np.int32)
        any_c = any(kind == "c" for kind, _ in entries)
        cp = np.asarray([pid if kind == "c" else 0
                         for kind, pid in entries], np.int32)
        cmask = np.asarray([kind == "c" for kind, _ in entries], bool)
        out = []
        for pool, qpool, scale in (
                (self.k, getattr(self, "kq", None),
                 getattr(self, "kscale", None)),
                (self.v, getattr(self, "vq", None),
                 getattr(self, "vscale", None))):
            view = pool[:, jnp.asarray(fp)]        # [L, n, ps, *entry]
            if any_c:
                cpd = jnp.asarray(cp)
                entry = (1,) * (view.ndim - 3)
                rows = scale[:, cpd]               # one scale a row
                deq = (qpool[:, cpd].astype(jnp.float32)
                       * rows.reshape(rows.shape + entry)
                       ).astype(view.dtype)
                view = jnp.where(
                    jnp.asarray(cmask).reshape((1, -1, 1) + entry),
                    deq, view)
            l, n, ps = view.shape[:3]
            out.append(view.reshape(l, n * ps, *view.shape[3:])[:, None])
        return tuple(out)

    def demote_page(self, pid: int) -> int:
        """Quantize ONE tree-held f32 page into the e4m3 pool (the PR
        14 codec) and return the compressed page id at refcount 1.  The
        caller drops its f32 reference afterwards -- the prefix tree's
        demotion tier under page pressure."""
        if not self.compress:
            raise RuntimeError("cache built without compress=True")
        if not self._cfree:
            raise RuntimeError("e4m3 pool exhausted")
        cpid = int(self._cfree.pop())
        dev = jnp.asarray(np.asarray([pid], np.int32))
        kq, ksc = _quantize_pages(self.k, dev)
        vq, vsc = _quantize_pages(self.v, dev)
        cp = jnp.asarray(np.asarray([cpid], np.int32))
        self.kq = self.kq.at[:, cp].set(kq)
        self.vq = self.vq.at[:, cp].set(vq)
        self.kscale = self.kscale.at[:, cp].set(ksc)
        self.vscale = self.vscale.at[:, cp].set(vsc)
        self._crefcount[cpid] = 1
        return cpid

    # -- device writes -----------------------------------------------------
    def write_state(self, slot: int, rows) -> None:
        """The slot's row of the slot state, ``[num_layers, width]``:
        what the sequence holds beside its pages once its last
        prefilled token is in."""
        self.state = _state_set(self.state, rows, jnp.int32(slot))
        self._m_state_written.inc(
            int(rows.size) * self.state.dtype.itemsize)

    def write_prefill(self, slot: int, k_layers, v_layers,
                      start: int = 0, state=None, window_rows=None) -> None:
        """Scatter a prefilled prompt's K/V into the slot's pages: the
        pages it covers whole (all of them, where ``start`` and ``start
        + t`` are multiples of the page size) a PAGE an update, one tile
        row written once; the rows of a first page entered past its
        start and of a last page left before its end a ROW an update,
        each reading and writing the tiles it lies in (:func:`_pool_set`
        has the arithmetic).  The counters ``kv.prefill_pages_written``
        and ``kv.prefill_rows_written`` say how much went which way.

        ``k_layers``/``v_layers``: ``[num_layers, t, *entry]`` of each
        pool (``num_kv_heads * head_dim`` columns, post-RoPE, as the
        decode step expects; ``v_layers`` None where there is one pool).
        Reserves
        pages for ``start + t`` tokens and sets ``lengths[slot] =
        start + t``.  ``start`` is the prefix-cache seam: a matched
        prefix's pages are already attached and immutable, only the
        tail ``[start:]`` is scattered (through the copy-on-write
        guard, so a partial shared page is cloned first).  ``state``:
        the prefill's trailing rows for :meth:`write_state`, where the
        caller does not write them itself.  ``window_rows``: with a
        window group, the pair ``(first, second)`` of its planes' rows
        ``[window_layers, rows, *entry]``: the prompt's LAST rows, from
        :func:`window_rows_from` on -- a window plane is written those
        only, into the slot's ring.

        With pooled rows (``row_tokens`` > 1) ``k_layers``/``v_layers``
        are the POOLED rows of the prompt's whole chunks, ``[num_layers,
        t // row_tokens, *entry]``, and ``window_rows`` the exact rows of
        its last (aligned) window: the prompt's length is what the two
        say together.  The rows of the ragged last chunk are in the ring
        and nowhere else: the decode round that fills the chunk pools
        it."""
        c = self.config
        t = int(k_layers.shape[1])
        if (window_rows is not None) != bool(c.window_layers) or (
                c.window_layers and start):
            raise ValueError(
                f"{c.window_layers} window planes, window rows "
                f"{'given' if window_rows is not None else 'missing'}, "
                f"start {start}")
        rows = t
        if c.row_tokens > 1:
            # ``rows`` whole chunks; the windows they fill, and the last
            # window's own rows behind them.
            t = rows * c.row_tokens // c.window * c.window \
                + int(window_rows[0].shape[1])
            if t // c.row_tokens != rows:
                raise ValueError(
                    f"{rows} pooled rows of {c.row_tokens} tokens and "
                    f"{int(window_rows[0].shape[1])} rows of the last "
                    f"window of {c.window} are no prompt's")
            self._m_pooled.inc(rows)
        self.reserve(slot, start + t, writable_from=start)
        if window_rows is not None:
            self._write_window(slot, t, *window_rows)
        self.k, self.v = self._scatter_rows(
            (self.k, self.v), (k_layers, v_layers), self.page_table[slot],
            start, start + rows)
        if state is not None:
            self.write_state(slot, state)
        self.lengths[slot] = start + t

    def _write_window(self, slot: int, length: int, wk_rows, wv_rows
                      ) -> None:
        c = self.config
        first = window_rows_from(length, c.window, c.window_aligned)
        if int(wk_rows.shape[1]) != length - first:
            raise ValueError(
                f"a window plane keeps rows {first}-{length - 1} of a "
                f"prompt of {length}, got {int(wk_rows.shape[1])} rows")
        if c.window_in_pool:
            self.k, self.v = self._scatter_rows(
                (self.k, self.v), (wk_rows, wv_rows),
                self.window_table[slot], first, length)
            return
        self.wk, self.wv = self._scatter_rows(
            (self.wk, self.wv), (wk_rows, wv_rows), self.window_table[slot],
            first, length)

    def _scatter_rows(self, pools, values, table, first: int, last: int
                    ) -> list:
        """Rows ``first .. last - 1`` of a slot into each of ``pools``
        (None: there is no such pool) through the slot's ``table`` (a
        ring where it is shorter than the rows reach: the window
        group's); the pools' successors.  The pages that the rows cover
        whole are written as pages, the ragged ends as rows
        (:func:`_pool_set`); one program a pool either way."""
        c = self.config
        ps = c.page_size
        lo, hi = -(-first // ps) * ps, last // ps * ps
        if hi <= lo:                    # no page is covered whole
            lo = hi = first
        # (An index array is built, and sent, only where it names
        # something: the aligned prompts of every cell send the pages'
        # alone, a sixteenth of what the rows' two were.)
        pages = jnp.asarray(table[
            np.arange(lo, hi, ps) // ps % len(table)]) if hi > lo else None
        pos = np.concatenate([np.arange(first, lo), np.arange(hi, last)])
        rows = jnp.asarray(np.stack(
            [table[pos // ps % len(table)], pos % ps]).astype(np.int32)
            ) if len(pos) else None
        dt = jnp.dtype(c.dtype)
        out = [None if pool is None else _pool_set(
            pool, rows_of.astype(dt), pages, rows, head=lo - first)
            for pool, rows_of in zip(pools, values)]
        planes = sum(int(pool.shape[0]) for pool in out if pool is not None)
        self._m_pages_written.inc(planes * ((hi - lo) // ps))
        self._m_rows_written.inc(planes * len(pos))
        return out

    def count_pooled(self, rows: int) -> None:
        """Pooled rows the decode round just dispatched writes: a live
        slot's each, where the round's token is its chunk's last."""
        self._m_pooled.inc(int(rows))

    def grow(self, slot: int) -> None:
        """Account one decoded token (the decode step already wrote its
        K/V in-step); reserves the next page at a boundary crossing."""
        new_len = int(self.lengths[slot]) + 1
        self.reserve(slot, new_len, writable_from=new_len - 1)
        self.lengths[slot] = new_len

    # -- step operands -----------------------------------------------------
    def table_device(self) -> jnp.ndarray:
        # np.array copy matters: jnp.asarray of host numpy is zero-copy
        # on CPU, so the device operand would ALIAS the mutable host
        # table and later host updates would race the dispatched step.
        return jnp.asarray(np.array(self.page_table))

    def lengths_device(self) -> jnp.ndarray:
        return jnp.asarray(np.array(self.lengths))

    def window_table_device(self) -> jnp.ndarray:
        return jnp.asarray(np.array(self.window_table))

    @property
    def carried(self) -> tuple:
        """What the cache owns beside ``k`` and ``v`` and a decode step
        rewrites: the slot state, the window group's pools.  The step
        takes them after its read-only operands, donated, and returns
        their successors after the pools (:meth:`take_carried`)."""
        return tuple(x for x in (self.state, self.wk, self.wv)
                     if x is not None)

    def take_carried(self, arrays) -> None:
        arrays = list(arrays)
        if self.state is not None:
            self.state = arrays.pop(0)
        if self.wk is not None:
            self.wk, self.wv = arrays

    def ctable_device(self) -> jnp.ndarray:
        return jnp.asarray(np.array(self.cpage_table))

    def cmask_device(self) -> jnp.ndarray:
        return jnp.asarray(np.array(self.comp_mask))

    def compress_operands(self) -> tuple:
        """The six extra step operands a ``compress=True`` decode/verify
        step takes after ``active`` (pools, scales, table, mask)."""
        return (self.kq, self.vq, self.kscale, self.vscale,
                self.ctable_device(), self.cmask_device())

    def layout(self) -> dict:
        return self.config.layout()


class _PrefixNode:
    """One full page of prompt tokens in the radix tree.  ``key`` is
    the page's token-id tuple, ``page`` the backing page id (``kind``
    ``"f"`` f32 or ``"c"`` fp8-demoted), ``touch`` the LRU clock,
    ``pins`` the live-session pin count."""

    __slots__ = ("key", "parent", "children", "kind", "page", "touch",
                 "pins", "dead")

    def __init__(self, key, parent, kind, page, touch):
        self.key = key
        self.parent = parent
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.kind = kind
        self.page = page
        self.touch = touch
        self.pins = 0
        self.dead = False


class PrefixCache:
    """Radix tree over token-id prefixes -> refcounted KV pages.

    The tree's unit is one FULL page (``page_size`` token ids); a
    request's prompt is matched page-chunk by page-chunk, and every
    matched chunk's K/V is already resident -- :meth:`match` +
    :meth:`PagedKVCache.attach_pages` make the whole matched prefix
    live with zero prefill FLOPs, only the tail runs through the PR 14
    chunked flash prefill.  After a prefill the slot's full prompt
    pages are :meth:`insert`-ed, so the NEXT request sharing the prefix
    hits (the tree holds its own +1 reference per page; tree-held pages
    survive ``free_slot``).

    Multi-turn sessions: :meth:`pin_session` pins the node path of a
    session's context so it stays warm across requests; pins expire
    after ``session_ttl_steps`` engine steps without reuse
    (:meth:`tick`).  Under page pressure (:meth:`release_pages`,
    installed as the cache's ``reclaim_cb``) tree-only f32 pages are
    first DEMOTED into the fp8 cold-page pool (still matchable, ~4x
    cheaper), then evicted leaf-first in LRU order -- unpinned entries
    before pinned ones, so live sessions are the last thing page
    pressure takes.
    """

    def __init__(self, cache: PagedKVCache,
                 session_ttl_steps: int = 0):
        self.cache = cache
        self.session_ttl_steps = int(session_ttl_steps)
        self._children: Dict[tuple, _PrefixNode] = {}
        self._clock = 0
        self._sessions: "collections.OrderedDict[object, dict]" = \
            collections.OrderedDict()
        self.queries = 0
        self.hits = 0
        self.nodes = 0
        reg = _registry()
        self._g_hit = reg.gauge(
            "horovod_serving_prefix_hit_rate",
            "Fraction of prefill queries that matched a cached prefix")
        self._g_pages = reg.gauge(
            "horovod_serving_prefix_pages",
            "KV pages pinned by the prefix tree")
        self._g_sessions = reg.gauge(
            "horovod_serving_sessions_live",
            "Sessions with pinned warm KV context")
        self._m_tok = reg.counter(
            "horovod_serving_prefix_tokens_total",
            "Prefill tokens by provenance (cached = prefill FLOPs "
            "avoided)", labelnames=("source",))
        cache.reclaim_cb = self.release_pages

    # -- stats -------------------------------------------------------------
    @property
    def hit_rate(self) -> float:
        return self.hits / self.queries if self.queries else 0.0

    @property
    def sessions_live(self) -> int:
        return len(self._sessions)

    def stats(self) -> dict:
        return {"queries": self.queries, "hits": self.hits,
                "hit_rate": self.hit_rate, "nodes": self.nodes,
                "sessions": len(self._sessions)}

    # -- the radix walk ----------------------------------------------------
    def _chunk(self, prompt, i: int) -> tuple:
        ps = self.cache.config.page_size
        return tuple(int(x) for x in prompt[i * ps:(i + 1) * ps])

    def match(self, prompt) -> Tuple[int, List[Tuple[str, int]]]:
        """Deepest cached prefix of ``prompt`` in full pages, capped at
        ``len(prompt) - 1`` tokens so the tail prefill always has at
        least one token to produce first-token logits from.  Returns
        ``(matched_tokens, [(kind, page), ...])`` ready for
        :meth:`PagedKVCache.attach_pages`."""
        ps = self.cache.config.page_size
        limit = (len(prompt) - 1) // ps
        entries: List[Tuple[str, int]] = []
        children = self._children
        for i in range(limit):
            node = children.get(self._chunk(prompt, i))
            if node is None:
                break
            node.touch = self._clock
            entries.append((node.kind, node.page))
            children = node.children
        self.queries += 1
        if entries:
            self.hits += 1
        matched = len(entries) * ps
        self._m_tok.labels(source="cached").inc(matched)
        self._m_tok.labels(source="computed").inc(len(prompt) - matched)
        self._g_hit.set(self.hit_rate)
        return matched, entries

    def insert(self, prompt, slot: int) -> int:
        """Register ``slot``'s resident full prompt pages under their
        token chunks (tree refcount +1 each); chunks already present
        are touched, not duplicated.  Returns newly registered pages."""
        cache = self.cache
        n = min(len(prompt), int(cache.lengths[slot])) \
            // cache.config.page_size
        children = self._children
        parent = None
        new = 0
        for i in range(n):
            key = self._chunk(prompt, i)
            node = children.get(key)
            if node is None:
                if cache.compress and cache.comp_mask[slot, i]:
                    kind, pid = "c", int(cache.cpage_table[slot, i])
                else:
                    kind, pid = "f", int(cache.page_table[slot, i])
                node = _PrefixNode(key, parent, kind, pid, self._clock)
                cache.add_page_ref(pid, kind)
                children[key] = node
                self.nodes += 1
                new += 1
            node.touch = self._clock
            parent = node
            children = node.children
        self._g_pages.set(self.nodes)
        return new

    # -- sessions ----------------------------------------------------------
    def pin_session(self, sid, prompt) -> None:
        """Pin the node path backing ``prompt``'s full pages under
        session id ``sid`` -- the multi-turn warm set.  Re-pinning the
        same session releases its previous pins first (the context
        grew) and refreshes its TTL."""
        nodes: List[_PrefixNode] = []
        children = self._children
        n = len(prompt) // self.cache.config.page_size
        for i in range(n):
            node = children.get(self._chunk(prompt, i))
            if node is None:
                break
            nodes.append(node)
            children = node.children
        old = self._sessions.pop(sid, None)
        if old is not None:
            for nd in old["nodes"]:
                if not nd.dead:
                    nd.pins -= 1
        for nd in nodes:
            nd.pins += 1
        self._sessions[sid] = {"nodes": nodes, "step": self._clock}
        self._g_sessions.set(len(self._sessions))

    def touch_session(self, sid) -> bool:
        """Refresh a session's TTL on reuse; True when it was warm."""
        entry = self._sessions.get(sid)
        if entry is None:
            return False
        entry["step"] = self._clock
        self._sessions.move_to_end(sid)
        return True

    def _expire_session(self, sid) -> None:
        entry = self._sessions.pop(sid)
        for nd in entry["nodes"]:
            if not nd.dead:
                nd.pins -= 1
        self._g_sessions.set(len(self._sessions))

    def tick(self, steps: int = 1) -> None:
        """Advance the LRU/TTL clock (one call per engine step).
        Sessions idle past ``session_ttl_steps`` lose their pins --
        their pages stay cached but become ordinary LRU fodder."""
        self._clock += int(steps)
        if not self.session_ttl_steps:
            return
        while self._sessions:
            sid, entry = next(iter(self._sessions.items()))
            if self._clock - entry["step"] <= self.session_ttl_steps:
                break
            self._expire_session(sid)

    # -- pressure: demote, then evict --------------------------------------
    def _iter_nodes(self):
        stack = list(self._children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node

    def _drop(self, node: _PrefixNode) -> bool:
        """Remove one leaf; True when its f32 page actually freed."""
        owner = self._children if node.parent is None \
            else node.parent.children
        owner.pop(node.key, None)
        node.dead = True
        self.nodes -= 1
        freed = self.cache.drop_page_ref(node.page, node.kind)
        self._g_pages.set(self.nodes)
        return freed and node.kind == "f"

    def _demote(self, need: int) -> int:
        """fp8 demotion tier: quantize LRU tree-only f32 pages into the
        cold pool, freeing their f32 pages while keeping the prefix
        matchable."""
        cache = self.cache
        if not cache.compress:
            return 0
        cand = [nd for nd in self._iter_nodes()
                if nd.kind == "f" and cache._refcount[nd.page] == 1]
        cand.sort(key=lambda nd: nd.touch)
        freed = 0
        for nd in cand:
            if freed >= need or not cache._cfree:
                break
            cpid = cache.demote_page(nd.page)
            if cache.drop_page_ref(nd.page):
                freed += 1
            nd.kind, nd.page = "c", cpid
        return freed

    def _evict(self, need: int) -> int:
        """LRU leaf eviction, unpinned entries strictly before pinned
        ones (a live session's warm set is the last thing to go)."""
        freed = 0
        for take_pinned in (False, True):
            while freed < need:
                leaves = [nd for nd in self._iter_nodes()
                          if not nd.children
                          and (nd.pins > 0) == take_pinned]
                if not leaves:
                    break
                if self._drop(min(leaves, key=lambda nd: nd.touch)):
                    freed += 1
            if freed >= need:
                break
        return freed

    def release_pages(self, need: int) -> int:
        """Give back ``need`` f32 pages to live traffic: demote first
        (residency survives at e4m3 cost), evict LRU after.  Installed
        as the cache's ``reclaim_cb``."""
        freed = self._demote(need)
        if freed < need:
            freed += self._evict(need - freed)
        return freed

    def drop_all(self) -> None:
        """Release every tree reference and session pin (drain/leak
        check: afterwards the pool must be fully free again)."""
        for sid in list(self._sessions):
            self._expire_session(sid)
        while True:
            leaves = [nd for nd in self._iter_nodes()
                      if not nd.children]
            if not leaves:
                break
            for nd in leaves:
                self._drop(nd)


def _quantize_pages(pool, pids):
    """fp8-quantize pages ``pids`` of one pool through the PR 5 codec:
    one max-abs e4m3 scale per (layer, page, offset) row over the whole
    entry, so a never-written row (absmax 0) roundtrips to exact zeros
    with scale 1.  Returns ``(q [L, n, page, *entry] e4m3, scales [L, n,
    page] f32)``."""
    x = pool[:, pids]
    l, n, pg = x.shape[:3]
    q, s = fp8_quantize(x.reshape(l * n * pg, -1), axis=0)
    return q.reshape(x.shape), s.reshape(l, n, pg)


def cache_sharding(mesh, tp_axis: str = "tp", *, entry_rank: int = 1,
                   split: Optional[int] = 0):
    """NamedSharding of a pool ``[layers, pages, page_size, *entry]``:
    the ``tp`` axis splits dim ``split`` of a token's entry (by default
    the one row of ``kv_heads * head_dim`` columns, contiguous heads a
    shard; None: the pool is whole on every chip)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if mesh is None:
        return None
    dims = [None] * (3 + entry_rank)
    if split is not None:
        dims[3 + split] = tp_axis
    return NamedSharding(mesh, P(*dims))
