"""KV-page wire codec (PR 20): framed roundtrips, bitwise import, and
malformed-payload rejection.

The invariants the disaggregated parity gate rests on:

* f32 tier roundtrips BITWISE -- importing a payload leaves the decode
  pool holding exactly the bytes a local ``write_prefill`` of the same
  K/V would have (verified through the slot's page table);
* fp8 tier quantizes with the in-pool cold-page codec's exact
  reshape/axis, so a streamed cold page is bit-identical to
  ``demote_page`` of the equivalent resident page, and the decode-side
  ``gather_pages`` blend cannot tell them apart;
* every malformation (bad magic, version skew, truncation, hash
  mismatch) is a distinct ``ValueError`` before any page is touched.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.models.transformer import LLAMA_SERVE
from horovod_tpu.serving import (CacheConfig, PagedKVCache,
                                 cache_sharding, decode_kv, encode_kv,
                                 import_pages)
from horovod_tpu.serving.kvwire import (MAGIC, WIRE_VERSION, _FRAME,
                                        WirePages, wire_tier)

CFG = LLAMA_SERVE
L, H, D = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
PS = 8


def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1],
                           dtype=object).reshape(1), ("tp",))


def _cache(compress=False, slots=4, max_len=64):
    ccfg = CacheConfig(num_layers=L, num_kv_heads=H, head_dim=D,
                       slots=slots, page_size=PS, max_len=max_len,
                       compress=compress)
    return PagedKVCache(ccfg, cache_sharding(_mesh1()))


def _kv(T, seed=0):
    rng = np.random.RandomState(seed)
    k = rng.randn(L, T, H * D).astype(np.float32)     # the cache's rows
    v = rng.randn(L, T, H * D).astype(np.float32)
    return k, v


def test_wire_tier_env(monkeypatch):
    monkeypatch.delenv("HOROVOD_KV_PAGE_WIRE", raising=False)
    assert wire_tier() == "f32"
    monkeypatch.setenv("HOROVOD_KV_PAGE_WIRE", "fp8")
    assert wire_tier() == "fp8"
    monkeypatch.setenv("HOROVOD_KV_PAGE_WIRE", "int4")
    with pytest.raises(ValueError, match="KV_PAGE_WIRE"):
        wire_tier()


def test_f32_roundtrip_bitwise():
    """Full pages AND the partial tail survive the frame bit-for-bit."""
    k, v = _kv(T=21)  # 2 full pages + 5-token tail
    wp = decode_kv(encode_kv(k, v, page_size=PS, tier="f32"))
    assert (wp.length, wp.page_size) == (21, PS)
    assert wp.full_pages == 2 and wp.tail_tokens == 5
    want_k = k[:, :16].reshape(L, 2, PS, H * D)
    assert wp.k_pages.tobytes() == want_k.tobytes()
    assert wp.v_pages.tobytes() == \
        v[:, :16].reshape(L, 2, PS, H * D).tobytes()
    assert wp.k_tail.tobytes() == k[:, 16:].tobytes()
    assert wp.v_tail.tobytes() == v[:, 16:].tobytes()


def test_f32_import_matches_local_write_prefill_bitwise():
    """Import vs local prefill: walking both slots' page tables must
    read identical pool bytes -- physical page ids differ, content
    cannot."""
    k, v = _kv(T=21)
    local = _cache()
    local.write_prefill(0, k, v)
    remote = _cache()
    wp = decode_kv(encode_kv(k, v, page_size=PS, tier="f32"))
    n = import_pages(remote, 2, wp)
    assert n == 2 and int(remote.lengths[2]) == 21
    pages = -(-21 // PS)
    for i in range(pages):
        lp = int(local.page_table[0, i])
        rp = int(remote.page_table[2, i])
        assert np.asarray(local.k[:, lp]).tobytes() == \
            np.asarray(remote.k[:, rp]).tobytes()
        assert np.asarray(local.v[:, lp]).tobytes() == \
            np.asarray(remote.v[:, rp]).tobytes()
    # The importer dropped its refs: the slot is the sole holder, so
    # freeing it leaks nothing.
    remote.free_slot(2)
    assert remote.release_all() == 0 and remote.refcounts_balanced()


def test_fp8_wire_matches_demote_page_bitwise():
    """Wire fp8 quantization == in-pool ``demote_page`` of the same
    resident bytes (same reshape, same per-row e4m3 scale), and the
    ``gather_pages`` blend of an imported cold page equals the locally
    demoted one exactly."""
    k, v = _kv(T=16)  # exactly 2 full pages
    local = _cache(compress=True)
    local.write_prefill(0, k, v)
    cpids = [local.demote_page(int(local.page_table[0, i]))
             for i in range(2)]
    wp = decode_kv(encode_kv(k, v, page_size=PS, tier="fp8"))
    for i, cpid in enumerate(cpids):
        assert wp.kq[:, i].tobytes() == \
            np.asarray(local.kq[:, cpid]).tobytes()
        assert wp.vq[:, i].tobytes() == \
            np.asarray(local.vq[:, cpid]).tobytes()
        assert wp.kscale[:, i].tobytes() == \
            np.asarray(local.kscale[:, cpid]).tobytes()
        assert wp.vscale[:, i].tobytes() == \
            np.asarray(local.vscale[:, cpid]).tobytes()
    # Imported cold pages blend identically through gather_pages.
    remote = _cache(compress=True)
    import_pages(remote, 0, wp)
    rk, rv = remote.gather_pages(
        [("c", int(remote.cpage_table[0, i])) for i in range(2)])
    lk, lv = local.gather_pages([("c", c) for c in cpids])
    assert np.asarray(rk).tobytes() == np.asarray(lk).tobytes()
    assert np.asarray(rv).tobytes() == np.asarray(lv).tobytes()
    remote.free_slot(0)
    assert remote.release_all() == 0 and remote.refcounts_balanced()


def test_fp8_tier_requires_compress_cache():
    k, v = _kv(T=16)
    wp = decode_kv(encode_kv(k, v, page_size=PS, tier="fp8"))
    with pytest.raises(ValueError, match="compress=True"):
        import_pages(_cache(compress=False), 0, wp)


def test_page_size_mismatch_rejected():
    k, v = _kv(T=16)
    wp = decode_kv(encode_kv(k, v, page_size=4, tier="f32"))
    with pytest.raises(ValueError, match="page_size"):
        import_pages(_cache(), 0, wp)


def test_malformed_payloads_rejected():
    """Version skew, truncation, and corruption each fail with their
    own ValueError -- a torn KV object can never reach attach_pages."""
    k, v = _kv(T=12)
    buf = encode_kv(k, v, page_size=PS, tier="f32")

    with pytest.raises(ValueError, match="not a KV-page wire"):
        decode_kv(b"XXXX" + buf[4:])
    with pytest.raises(ValueError, match="shorter than"):
        decode_kv(buf[:_FRAME.size - 2])

    # Version bump: repack the frame with v+1.
    magic, version, hlen = _FRAME.unpack_from(buf)
    assert magic == MAGIC and version == WIRE_VERSION
    bumped = _FRAME.pack(MAGIC, WIRE_VERSION + 1, hlen) \
        + buf[_FRAME.size:]
    with pytest.raises(ValueError, match="version mismatch"):
        decode_kv(bumped)

    # Truncated payload: header promises more bytes than arrive.
    with pytest.raises(ValueError, match="header promises"):
        decode_kv(buf[:-10])

    # Bit-flip in the payload: sha256 mismatch.
    corrupt = bytearray(buf)
    corrupt[-1] ^= 0xFF
    with pytest.raises(ValueError, match="hash mismatch"):
        decode_kv(bytes(corrupt))


def test_encode_rejects_bad_shapes():
    k, v = _kv(T=8)
    with pytest.raises(ValueError, match="matching"):
        encode_kv(k, v[:, :4], page_size=PS)
    with pytest.raises(ValueError, match="empty"):
        encode_kv(k[:, :0], v[:, :0], page_size=PS)
    with pytest.raises(ValueError, match="unknown KV wire tier"):
        encode_kv(k, v, page_size=PS, tier="int4")


def test_tail_only_prompt_streams_without_full_pages():
    """A prompt shorter than one page travels as tail-only f32 and
    imports through write_prefill alone."""
    k, v = _kv(T=5)
    wp = decode_kv(encode_kv(k, v, page_size=PS, tier="fp8"))
    assert wp.full_pages == 0 and wp.tail_tokens == 5
    assert wp.kq is None and wp.k_tail is not None
    cache = _cache(compress=True)
    assert import_pages(cache, 1, wp) == 0
    assert int(cache.lengths[1]) == 5
    cache.free_slot(1)
    assert cache.release_all() == 0
