"""Versioned wire codec for KV-page streaming (prefill -> decode).

Disaggregated serving splits one engine into a prefill worker and a
decode worker on separate (virtual) meshes; the only thing that moves
between them is a prompt's finished K/V pages, published as opaque
bytes over the rendezvous KV plane (``run/http_kv.py``).  This module
is the wire format: a framed, versioned, content-hashed payload that a
decode worker can land in its OWN :class:`~.kvcache.PagedKVCache` via
``adopt_pages`` + ``attach_pages``.

Two tiers, selected by ``HOROVOD_KV_PAGE_WIRE``:

* ``f32`` (default) -- full pages travel as the pool dtype's raw bytes.
  Import is BITWISE: the decode worker's pool holds exactly the bytes
  the prefill worker computed, so a disaggregated decode stream is
  bit-for-bit equal to a colocated engine's (the round-20 parity gate).
* ``fp8`` -- full pages travel through the PR 14 cold-page codec
  (:func:`~..collectives.compression.fp8_quantize`, one max-abs e4m3
  scale per (layer, page, offset) row), ~4x cheaper on the wire.  The
  quantization is performed with the SAME reshape/axis the in-pool
  ``demote_page`` path uses, so an imported fp8 page is bit-identical
  to demoting the equivalent resident page -- the decode step's gather
  blend cannot tell streamed cold pages from locally demoted ones.

The partial tail page (``length % page_size`` tokens) always travels
f32: a partial page is by definition at the write head, and the pool
never holds a hot page in e4m3 either.

Framing: ``b"HVKW" | u16 version | u32 header_len | header JSON |
payload``.  The header carries the geometry, the payload byte count
and a SHA-256 content hash; :func:`decode_kv` rejects a version
mismatch, a truncated payload, and a hash mismatch with distinct
``ValueError`` messages -- a half-written or stale KV entry must never
reach ``attach_pages``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import struct
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..collectives.compression import fp8_quantize
from ..core.config import _env

MAGIC = b"HVKW"
WIRE_VERSION = 1
TIER_F32 = "f32"
TIER_FP8 = "fp8"
_FRAME = struct.Struct("<4sHI")
_FP8_DTYPE = np.dtype(jnp.float8_e4m3fn)


def wire_tier() -> str:
    """Tier selected by ``HOROVOD_KV_PAGE_WIRE`` (``f32`` default)."""
    tier = (_env("KV_PAGE_WIRE") or TIER_F32).lower()
    if tier not in (TIER_F32, TIER_FP8):
        raise ValueError(
            f"HOROVOD_KV_PAGE_WIRE must be '{TIER_F32}' or '{TIER_FP8}', "
            f"got {tier!r}")
    return tier


@dataclasses.dataclass
class WirePages:
    """Decoded page payload, ready for :func:`import_pages`."""

    tier: str
    length: int                    # tokens covered (full pages + tail)
    page_size: int
    dtype: str                     # pool dtype of the f32 tier / tail
    # f32 tier: [L, full, page_size, H, D] in the pool dtype (``L``:
    # the pool's planes, passes x layers; ``[L, full, page_size,
    # *entry]`` whatever a token's entry is, and every ``v`` None where
    # the model keeps one pool).
    k_pages: Optional[np.ndarray] = None
    v_pages: Optional[np.ndarray] = None
    # fp8 tier: e4m3 pages + one f32 scale per (layer, page, offset) row.
    kq: Optional[np.ndarray] = None
    vq: Optional[np.ndarray] = None
    kscale: Optional[np.ndarray] = None
    vscale: Optional[np.ndarray] = None
    # Partial tail page, always the pool dtype: [L, tail, H, D].
    k_tail: Optional[np.ndarray] = None
    v_tail: Optional[np.ndarray] = None

    @property
    def full_pages(self) -> int:
        return self.length // self.page_size

    @property
    def tail_tokens(self) -> int:
        return self.length - self.full_pages * self.page_size


def _quantize_full_pages(pages: np.ndarray):
    """PR 14 cold-page codec over ``[L, n, ps, *entry]`` -- the SAME
    reshape and reduction axis as ``kvcache._quantize_pages``, so wire
    quantization of a page is bitwise what ``demote_page`` would have
    produced for the identical resident bytes."""
    l, n, pg = pages.shape[:3]
    q, s = fp8_quantize(jnp.asarray(pages).reshape(l * n * pg, -1), axis=0)
    return (np.asarray(q).reshape(pages.shape),
            np.asarray(s).reshape(l, n, pg))


def encode_kv(k_layers, v_layers=None, *, page_size: int,
              tier: Optional[str] = None) -> bytes:
    """Serialize a prompt's post-RoPE K/V (``[L, T, H, D]``, the
    ``prefill_forward`` per-sequence output) into one framed payload of
    ``T // page_size`` full pages plus an f32 tail.  In general ``[L, T,
    *entry]`` a pool, as ``LayerSpec.prefill`` hands them back: ``L`` is
    the pool's PLANES (every pass of a looped model's every layer: a
    handoff ships them all), ``v_layers`` None for a model of one
    pool."""
    tier = tier or wire_tier()
    if tier not in (TIER_F32, TIER_FP8):
        raise ValueError(f"unknown KV wire tier {tier!r}")
    pools = [np.asarray(k_layers)] + (
        [] if v_layers is None else [np.asarray(v_layers)])
    k = pools[0]
    if k.ndim < 3 or any(v.shape != k.shape for v in pools[1:]):
        raise ValueError(
            "expected [L, T, *entry] K/V (matching, where there are "
            f"two), got {' vs '.join(str(z.shape) for z in pools)}")
    layers, length = k.shape[:2]
    entry = k.shape[2:]
    if length < 1:
        raise ValueError("cannot encode an empty context")
    full = length // page_size
    tail = length - full * page_size
    pages = [z[:, :full * page_size].reshape(layers, full, page_size,
                                             *entry) for z in pools]
    chunks = []
    if full:
        if tier == TIER_FP8:
            quant = [_quantize_full_pages(z) for z in pages]
            chunks += [q.tobytes() for q, _ in quant]
            chunks += [s.astype(np.float32).tobytes() for _, s in quant]
        else:
            chunks += [z.tobytes() for z in pages]
    if tail:
        chunks += [z[:, full * page_size:].tobytes() for z in pools]
    payload = b"".join(chunks)
    header = json.dumps({
        "tier": tier, "layers": layers, "entry": list(entry),
        "pools": len(pools), "page_size": page_size, "length": length,
        "dtype": str(k.dtype), "payload_bytes": len(payload),
        "sha256": hashlib.sha256(payload).hexdigest(),
    }, sort_keys=True).encode()
    return _FRAME.pack(MAGIC, WIRE_VERSION, len(header)) + header + payload


def decode_kv(buf: bytes) -> WirePages:
    """Parse and validate one framed payload; every malformation is a
    ``ValueError`` (version mismatch, truncation, hash mismatch) so the
    import path can never attach garbage pages."""
    if len(buf) < _FRAME.size:
        raise ValueError(
            f"truncated KV-page payload: {len(buf)} byte(s) is shorter "
            f"than the {_FRAME.size}-byte frame")
    magic, version, hlen = _FRAME.unpack_from(buf)
    if magic != MAGIC:
        raise ValueError(
            f"not a KV-page wire payload (magic {magic!r})")
    if version != WIRE_VERSION:
        raise ValueError(
            f"KV wire version mismatch: payload v{version}, this codec "
            f"speaks v{WIRE_VERSION} -- refusing a cross-version import")
    if len(buf) < _FRAME.size + hlen:
        raise ValueError(
            "truncated KV-page payload: header cut short")
    try:
        hdr = json.loads(buf[_FRAME.size:_FRAME.size + hlen])
    except ValueError as e:
        raise ValueError(f"corrupt KV wire header: {e}") from e
    payload = buf[_FRAME.size + hlen:]
    want = int(hdr["payload_bytes"])
    if len(payload) != want:
        raise ValueError(
            f"truncated KV-page payload: have {len(payload)} payload "
            f"byte(s), header promises {want}")
    digest = hashlib.sha256(payload).hexdigest()
    if digest != hdr["sha256"]:
        raise ValueError(
            "KV-page content hash mismatch: payload bytes do not match "
            "the header's sha256 (partial write or in-flight corruption)")
    tier = hdr["tier"]
    layers, ps = int(hdr["layers"]), int(hdr["page_size"])
    entry = tuple(int(n) for n in hdr["entry"])
    two = int(hdr["pools"]) == 2
    length = int(hdr["length"])
    dt = np.dtype(hdr["dtype"])
    full = length // ps
    tail = length - full * ps
    wp = WirePages(tier=tier, length=length, page_size=ps,
                   dtype=str(dt))
    off = 0

    def take(count: int, dtype, shape):
        nonlocal off
        nbytes = count * dtype.itemsize
        arr = np.frombuffer(payload, dtype, count=count,
                            offset=off).reshape(shape)
        off += nbytes
        return arr

    def take_pools(shape, dtype):
        count = int(np.prod(shape))
        first = take(count, dtype, shape)
        return first, (take(count, dtype, shape) if two else None)

    if full:
        pshape = (layers, full, ps) + entry
        if tier == TIER_FP8:
            wp.kq, wp.vq = take_pools(pshape, _FP8_DTYPE)
            wp.kscale, wp.vscale = take_pools((layers, full, ps),
                                              np.dtype(np.float32))
        else:
            wp.k_pages, wp.v_pages = take_pools(pshape, dt)
    if tail:
        wp.k_tail, wp.v_tail = take_pools((layers, tail) + entry, dt)
    return wp


def import_pages(cache, slot: int, wp: WirePages) -> int:
    """Land a decoded payload in an empty slot of ``cache``: full pages
    are adopted into the pool (f32 or the e4m3 cold pool) and mapped in
    through :meth:`~.kvcache.PagedKVCache.attach_pages` -- the same
    entry point the prefix-cache hit path uses -- then the partial tail
    is scattered via ``write_prefill``.  Returns the number of full
    pages streamed in.  The slot ends with ``lengths[slot] ==
    wp.length`` and every page held at refcount 1 by the slot."""
    c = cache.config
    if wp.page_size != c.page_size:
        raise ValueError(
            f"wire page_size {wp.page_size} != pool page_size "
            f"{c.page_size}")
    if wp.tier == TIER_FP8 and not cache.compress:
        raise ValueError(
            "fp8 wire tier needs a compress=True decode-side cache "
            "(HOROVOD_KV_COMPRESS)")
    entries: List[Tuple[str, int]] = []
    if wp.full_pages:
        if wp.tier == TIER_FP8:
            entries = cache.adopt_compressed_pages(
                wp.kq, wp.vq, wp.kscale, wp.vscale)
        else:
            entries = cache.adopt_pages(wp.k_pages, wp.v_pages)
        cache.attach_pages(slot, entries, wp.full_pages * c.page_size)
        # attach_pages took the slot's own reference; drop the
        # importer's so the slot is the sole holder (free_slot later
        # returns the page to the pool, the leak-gate invariant).
        for kind, pid in entries:
            cache.drop_page_ref(pid, kind)
    if wp.tail_tokens:
        cache.write_prefill(slot, wp.k_tail, wp.v_tail,
                            start=wp.full_pages * c.page_size)
    return len(entries)
