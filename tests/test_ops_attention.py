"""Flash-attention kernels vs XLA reference (Pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention as _attn
from horovod_tpu.ops import attention_reference, flash_attention
from horovod_tpu.ops.attention import _flash


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk", [(64, 64), (64, 128)])
def test_flash_forward_matches_reference(causal, tq, tk):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand((2, 2, tq, 32), keys[0])
    k = _rand((2, 2, tk, 32), keys[1])
    v = _rand((2, 2, tk, 32), keys[2])
    ref = attention_reference(q, k, v, causal=causal)
    got = _flash(q, k, v, q.shape[-1] ** -0.5, causal, 32, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand((1, 2, 64, 16), keys[0])
    k = _rand((1, 2, 64, 16), keys[1])
    v = _rand((1, 2, 64, 16), keys[2])

    def loss_flash(q, k, v):
        o = _flash(q, k, v, q.shape[-1] ** -0.5, causal, 32, 32)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_gqa_kernel_broadcasts_kv_heads():
    """GQA path through the kernels (index-map broadcast, incl. backward)."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand((1, 4, 32, 16), keys[0])
    k = _rand((1, 2, 32, 16), keys[1])
    v = _rand((1, 2, 32, 16), keys[2])
    kr, vr = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)

    out = _flash(q, k, v, q.shape[-1] ** -0.5, True, 32, 32)
    ref = attention_reference(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(_flash(q, k, v, q.shape[-1] ** -0.5,
                                      True, 32, 32)))

    def loss_ref(q, k, v):
        o = attention_reference(q, jnp.repeat(k, 2, axis=1),
                                jnp.repeat(v, 2, axis=1), causal=True)
        return jnp.sum(jnp.sin(o))

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_gqa_dispatch_path():
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand((1, 4, 32, 16), keys[0])
    k = _rand((1, 2, 32, 16), keys[1])
    v = _rand((1, 2, 32, 16), keys[2])
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, jnp.repeat(k, 2, axis=1),
                              jnp.repeat(v, 2, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_causal_decode_alignment():
    """tq < tk causal = bottom-right aligned (KV-cache decode semantics)."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand((1, 1, 8, 16), keys[0])
    k = _rand((1, 1, 64, 16), keys[1])
    v = _rand((1, 1, 64, 16), keys[2])
    got = _flash(q, k, v, q.shape[-1] ** -0.5, True, 8, 32)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_causal_tq_gt_tk_rejected():
    q = jnp.zeros((1, 1, 64, 16))
    k = jnp.zeros((1, 1, 32, 16))
    with pytest.raises(ValueError, match="tq <= tk"):
        flash_attention(q, k, k, causal=True)


def test_uneven_block_sizes_fall_back_to_divisors():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand((1, 1, 96, 16), keys[0])  # 96 not divisible by 64
    k = _rand((1, 1, 96, 16), keys[1])
    v = _rand((1, 1, 96, 16), keys[2])
    got = _flash(q, k, v, q.shape[-1] ** -0.5, True, 64, 64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_prime_seq_falls_back_to_reference():
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand((1, 1, 127, 16), keys[0])  # prime: no divisor >= 8
    k = _rand((1, 1, 127, 16), keys[1])
    v = _rand((1, 1, 127, 16), keys[2])
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def _packed_segments(key, batch, t, max_segs=4):
    """Random packed-sequence ids: sorted segments like a packing loader."""
    lens = jax.random.randint(key, (batch, max_segs), 1, t)
    ids = []
    for b in range(batch):
        row = np.zeros(t, np.int32)
        pos, seg = 0, 0
        for L in np.asarray(lens[b]):
            if pos >= t:
                break
            row[pos:pos + int(L)] = seg
            pos += int(L)
            seg += 1
        row[pos:] = seg  # tail = final segment
        ids.append(row)
    return jnp.asarray(np.stack(ids))


def _dense_mask_reference(q, k, v, qseg, kseg, causal):
    """Ground truth built from an explicit dense mask (independent of
    attention_reference's own segment path)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = qseg[:, None, :, None] == kseg[:, None, None, :]
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = mask & jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
    logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(logits, axis=-1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_match_dense_mask(causal):
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = _rand((2, 2, 64, 32), keys[0])
    k = _rand((2, 2, 64, 32), keys[1])
    v = _rand((2, 2, 64, 32), keys[2])
    seg = _packed_segments(keys[3], 2, 64)
    ref = _dense_mask_reference(q, k, v, seg, seg, causal)
    got = _flash_seg(q, k, v, seg, seg, q.shape[-1] ** -0.5, causal,
                     32, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # attention_reference's own segment path agrees too.
    ref2 = attention_reference(q, k, v, causal=causal, segment_ids=seg,
                               kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(ref2), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_grads_match_reference(causal):
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    q = _rand((1, 2, 64, 16), keys[0])
    k = _rand((1, 2, 64, 16), keys[1])
    v = _rand((1, 2, 64, 16), keys[2])
    seg = _packed_segments(keys[3], 1, 64)

    def loss_flash(q, k, v):
        o = _flash_seg(q, k, v, seg, seg, q.shape[-1] ** -0.5, causal,
                       32, 32)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = _dense_mask_reference(q, k, v, seg, seg, causal)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_segment_ids_public_api_and_validation():
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q = _rand((2, 4, 64, 16), keys[0])
    k = _rand((2, 2, 64, 16), keys[1])      # GQA: 2 kv heads
    v = _rand((2, 2, 64, 16), keys[2])
    seg = _packed_segments(keys[3], 2, 64)
    # Reference fallback (CPU dispatch) handles GQA + segments.
    out = flash_attention(q, k, v, causal=True, segment_ids=seg)
    krep = jnp.repeat(k, 2, axis=1)
    vrep = jnp.repeat(v, 2, axis=1)
    ref = _dense_mask_reference(q, krep, vrep, seg, seg, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    with pytest.raises(ValueError, match="kv_segment_ids given without"):
        flash_attention(q, k, v, kv_segment_ids=seg)
    with pytest.raises(ValueError, match="segment_ids must be"):
        flash_attention(q, k, v, segment_ids=seg[:, :32])
    with pytest.raises(ValueError, match="kv_segment_ids is required"):
        flash_attention(q, k[:, :, :32], v[:, :, :32],
                        segment_ids=seg)


def test_segment_ids_isolate_sequences():
    """Two packed sequences attend independently: packing [A|B] must equal
    attending A and B separately (the point of the feature)."""
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(10), 3)
    qa = _rand((1, 2, 32, 16), keys[0])
    qb = _rand((1, 2, 32, 16), keys[1])
    v_all = _rand((1, 2, 64, 16), keys[2])
    q_pack = jnp.concatenate([qa, qb], axis=2)
    seg = jnp.concatenate([jnp.zeros((1, 32), jnp.int32),
                           jnp.ones((1, 32), jnp.int32)], axis=1)
    packed = _flash_seg(q_pack, q_pack, v_all, seg, seg,
                        qa.shape[-1] ** -0.5, True, 32, 32)
    sep_a = attention_reference(qa, qa, v_all[:, :, :32], causal=True)
    sep_b = attention_reference(qb, qb, v_all[:, :, 32:], causal=True)
    np.testing.assert_allclose(np.asarray(packed[:, :, :32]),
                               np.asarray(sep_a), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(packed[:, :, 32:]),
                               np.asarray(sep_b), atol=2e-5, rtol=2e-5)


def test_segment_dead_rows_zero_output_and_grads():
    """A query row whose segment matches NO key (pure padding) must give
    zero output and inject ZERO gradients -- the f32 lse for such a row
    would otherwise absorb log(l) into -1e30 and the backward would see
    p = 1 per key (a ~tk-fold gradient explosion; review regression)."""
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand((1, 1, 16, 8), keys[0])
    k = _rand((1, 1, 16, 8), keys[1])
    v = _rand((1, 1, 16, 8), keys[2])
    # Last 4 query rows carry segment 5, present in NO key row.
    qseg = jnp.asarray([[0] * 12 + [5] * 4], jnp.int32)
    kseg = jnp.zeros((1, 16), jnp.int32)

    out = _flash_seg(q, k, v, qseg, kseg, q.shape[-1] ** -0.5, False,
                     8, 8)
    np.testing.assert_allclose(np.asarray(out[0, 0, 12:]), 0.0)
    ref = attention_reference(q, k, v, segment_ids=qseg,
                              kv_segment_ids=kseg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o = _flash_seg(q, k, v, qseg, kseg, q.shape[-1] ** -0.5, False,
                       8, 8)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, segment_ids=qseg,
                                kv_segment_ids=kseg)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # Dead rows contribute nothing to dq...
    np.testing.assert_allclose(np.asarray(gf[0][0, 0, 12:]), 0.0)
    # ...and the live rows' gradients match the reference everywhere.
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_reference_defaults_kv_segment_ids():
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    q = _rand((1, 1, 32, 8), keys[0])
    k = _rand((1, 1, 32, 8), keys[1])
    v = _rand((1, 1, 32, 8), keys[2])
    seg = jnp.asarray([[0] * 16 + [1] * 16], jnp.int32)
    a = attention_reference(q, k, v, segment_ids=seg)
    b = attention_reference(q, k, v, segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="kv_segment_ids is required"):
        attention_reference(q, k[:, :, :16], v[:, :, :16],
                            segment_ids=seg)


def test_segment_lane_block_search():
    """Sequences like 1920 (no 512-aligned divisor that is a multiple of
    128 <= 512... actually 384) must keep the Pallas path by searching
    for a lane-aligned block, not fall back to the O(t^2) reference."""
    from horovod_tpu.ops.attention import _block_lane
    assert _block_lane(1920, 512) == 384
    assert _block_lane(1664, 512) == 128
    assert _block_lane(4864, 512) == 256
    assert _block_lane(1024, 512) == 512
    assert _block_lane(64, 512) == 64       # whole-seq block
    assert _block_lane(20, 512) == 0        # not an 8-multiple: fallback
    assert _block_lane(1031, 512) == 0      # prime: fallback


@pytest.mark.parametrize("causal", [False, True])
def test_segment_pruning_grads_hit_pruned_blocks(causal):
    """Block-aligned disjoint segments (32 zeros + 32 ones at bq=bk=32)
    force the backward kernels' _seg_live pruning to actually SKIP the
    cross-segment block pairs -- the random-segment grad tests never
    prune (all their block id-ranges overlap), so this is the test that
    defends gradient exactness of the pruning fast path."""
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q = _rand((1, 2, 64, 16), keys[0])
    k = _rand((1, 2, 64, 16), keys[1])
    v = _rand((1, 2, 64, 16), keys[2])
    seg = jnp.concatenate([jnp.zeros((1, 32), jnp.int32),
                           jnp.ones((1, 32), jnp.int32)], axis=1)

    def loss_flash(q, k, v):
        o = _flash_seg(q, k, v, seg, seg, q.shape[-1] ** -0.5, causal,
                       32, 32)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = _dense_mask_reference(q, k, v, seg, seg, causal)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_cross_length_decode(causal):
    """tq < tk (decode with a KV cache) + distinct q/kv segment ids: the
    kernels' bottom-right-aligned causal offset must compose with the
    segment mask."""
    from horovod_tpu.ops.attention import _flash_seg
    keys = jax.random.split(jax.random.PRNGKey(14), 3)
    q = _rand((2, 2, 32, 16), keys[0])
    k = _rand((2, 2, 64, 16), keys[1])
    v = _rand((2, 2, 64, 16), keys[2])
    kseg = jnp.asarray(np.concatenate(
        [np.zeros((2, 40)), np.ones((2, 24))], axis=1).astype(np.int32))
    qseg = jnp.ones((2, 32), jnp.int32)     # queries are the live tail
    # attention_reference, not _dense_mask_reference: causal + segments
    # makes early queries DEAD (no id-1 key inside their causal range),
    # and only the real reference zeroes dead rows like the kernel.
    ref = attention_reference(q, k, v, causal=causal, segment_ids=qseg,
                              kv_segment_ids=kseg)
    got = _flash_seg(q, k, v, qseg, kseg, q.shape[-1] ** -0.5, causal,
                     32, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Flash-decoding: split-KV decode kernel (HOROVOD_PALLAS / _PALLAS_DECODE).
# ---------------------------------------------------------------------------

from horovod_tpu.ops.attention import _flash_decode, decode_attention


def _decode_case(key, b=4, h=8, h_kv=8, s=128, d=32):
    keys = jax.random.split(key, 4)
    q = _rand((b, h, 1, d), keys[0])
    k = _rand((b, h_kv, s, d), keys[1])
    v = _rand((b, h_kv, s, d), keys[2])
    lengths = jax.random.randint(keys[3], (b,), 1, s + 1)
    return q, k, v, lengths


@pytest.mark.parametrize("h_kv", [8, 2])  # MHA and GQA (rep=4)
def test_flash_decode_matches_reference(h_kv):
    q, k, v, lengths = _decode_case(jax.random.PRNGKey(20), h_kv=h_kv)
    ref = decode_attention(q, k, v, lengths=lengths, force_reference=True)
    got = _flash_decode(q, k, v, lengths, q.shape[-1] ** -0.5, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_block_straddles_length():
    """Lengths that cut a KV block mid-way exercise the per-column mask
    (not just the whole-block predication)."""
    q, k, v, _ = _decode_case(jax.random.PRNGKey(21))
    lengths = jnp.asarray([1, 31, 33, 128], jnp.int32)
    ref = decode_attention(q, k, v, lengths=lengths, force_reference=True)
    got = _flash_decode(q, k, v, lengths, q.shape[-1] ** -0.5, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_dead_slot_exactly_zero():
    """An idle batch slot (lengths == 0) runs no live KV block, so the
    finish step's l == 0 guard must yield EXACTLY zero -- not a uniform
    average over garbage keys."""
    q, k, v, _ = _decode_case(jax.random.PRNGKey(22))
    lengths = jnp.asarray([0, 64, 0, 128], jnp.int32)
    got = _flash_decode(q, k, v, lengths, q.shape[-1] ** -0.5, 32)
    np.testing.assert_array_equal(np.asarray(got[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[2]), 0.0)
    ref = decode_attention(q, k, v, lengths=lengths, force_reference=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_masked_page_reuse():
    """Cache positions past lengths hold recycled-page garbage; poisoning
    them with huge values must not change the output (the mask, not the
    data, decides)."""
    q, k, v, _ = _decode_case(jax.random.PRNGKey(23))
    lengths = jnp.asarray([17, 40, 96, 5], jnp.int32)
    live = jnp.arange(k.shape[2])[None, None, :, None] < \
        lengths[:, None, None, None]
    k_poison = jnp.where(live, k, 1e4)
    v_poison = jnp.where(live, v, -1e4)
    clean = _flash_decode(q, k, v, lengths, q.shape[-1] ** -0.5, 32)
    poisoned = _flash_decode(q, k_poison, v_poison, lengths,
                             q.shape[-1] ** -0.5, 32)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(clean),
                               atol=1e-6, rtol=1e-6)


def test_decode_attention_env_dispatch(monkeypatch):
    """HOROVOD_PALLAS_DECODE=1 routes decode_attention through the kernel
    (interpreter off-TPU); =0 pins the XLA reference; both agree."""
    q, k, v, lengths = _decode_case(jax.random.PRNGKey(24), h_kv=2)
    monkeypatch.setenv("HOROVOD_PALLAS_DECODE", "0")
    ref = decode_attention(q, k, v, lengths=lengths)
    monkeypatch.setenv("HOROVOD_PALLAS_DECODE", "1")
    got = decode_attention(q, k, v, lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_validation():
    q = jnp.zeros((2, 4, 1, 16))
    k = jnp.zeros((2, 2, 32, 16))
    lengths = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="single-token"):
        decode_attention(jnp.zeros((2, 4, 2, 16)), k, k, lengths=lengths)
    with pytest.raises(ValueError, match="not a multiple"):
        decode_attention(jnp.zeros((2, 3, 1, 16)), k, k, lengths=lengths)
    with pytest.raises(ValueError, match="lengths must be"):
        decode_attention(q, k, k, lengths=jnp.zeros((3,), jnp.int32))


# ---------------------------------------------------------------------------
# The page walk over TWO pools: the dense decode step's attention.
# ---------------------------------------------------------------------------

from horovod_tpu.ops.attention import cca_decode_attention
from serving_families import lowered_for_tpu as _lowered_for_tpu

_PAGE, _PPS, _PPB = 8, 12, 4        # a block of 32 keys, three a slot


def _two_pools(seed, lengths, h, kvh, d=128):
    """Keys in one pool, values in another (rows of ``kvh`` heads side by
    side, two planes), a shuffled page table, and one query a row."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    shape = (2, b * _PPS + 1, _PAGE, kvh * d)
    keys = jnp.asarray(rng.normal(size=shape), jnp.float32)
    values = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray(rng.permutation(b * _PPS).reshape(b, _PPS),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    return q, keys, values, table, jnp.asarray(lengths, jnp.int32)


def _walk_on(monkeypatch, sub=16):
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn, "MLA_PAGES_PER_BLOCK", _PPB)
    monkeypatch.setattr(_attn, "MLA_KEYS_PER_SUB_BLOCK", sub)


def _slot_view(pool, table, kvh, d=128):
    """``[b, kvh, max_len, d]`` of plane 1: what the step used to gather."""
    b = table.shape[0]
    return pool[1][table].reshape(b, _PPS * _PAGE, kvh, d).transpose(
        0, 2, 1, 3)


# Lengths that end inside a page (5, 37), on a page's edge (8, 40),
# inside a block and on its edge (33; 32, 64), at ``max_len`` (96); a
# dead row.
_LENGTHS = [5, 8, 33, 32, 37, 40, 64, 95, 96, 0, 1]


@pytest.mark.parametrize("h,kvh", [(32, 8), (8, 2)])
@pytest.mark.parametrize("sub", [16, 256])
def test_two_pool_walk_matches_attention_reference(monkeypatch, h, kvh,
                                                   sub):
    q, keys, values, table, lens = _two_pools(h + sub, _LENGTHS, h, kvh)
    rep = h // kvh
    kv_seg = (jnp.arange(_PPS * _PAGE)[None, :]
              < lens[:, None]).astype(jnp.int32)
    want = attention_reference(
        q[:, :, None], jnp.repeat(_slot_view(keys, table, kvh), rep, 1),
        jnp.repeat(_slot_view(values, table, kvh), rep, 1),
        segment_ids=jnp.ones((len(_LENGTHS), 1), jnp.int32),
        kv_segment_ids=kv_seg)[:, :, 0]
    _walk_on(monkeypatch, sub)
    got = cca_decode_attention(q, keys, table, layer=1, lengths=lens,
                               kv_heads=kvh, scale=128 ** -0.5,
                               values=values)
    assert got.shape == (len(_LENGTHS), h, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[_LENGTHS.index(0)]))    # exactly zero
    # Kernels off, the same call IS ``decode_attention``'s reference over
    # the gathered view, bit for bit: a verify step's rows stay a decode
    # step's.
    off = cca_decode_attention(q, keys, table, layer=1, lengths=lens,
                               kv_heads=kvh, scale=128 ** -0.5,
                               values=values, force_reference=True)
    ref = decode_attention(q[:, :, None], _slot_view(keys, table, kvh),
                           _slot_view(values, table, kvh), lengths=lens,
                           force_reference=True)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(off), np.asarray(ref))


def test_two_pool_walk_plane_is_traced_or_static(monkeypatch):
    """One jitted function for every layer: the plane as a Python int
    and as a traced scalar read the same rows."""
    q, keys, values, table, lens = _two_pools(3, [40, 0, 9], 8, 2)
    _walk_on(monkeypatch)
    kw = dict(lengths=lens, kv_heads=2, scale=0.1, values=values)
    static = cca_decode_attention(q, keys, table, layer=1, **kw)
    traced = jax.jit(lambda t: cca_decode_attention(
        q, keys, table, layer=t, **kw))(jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))
    other = cca_decode_attention(q, keys, table, layer=0, **kw)
    assert np.abs(np.asarray(other) - np.asarray(static)).max() > 1e-3


@pytest.mark.parametrize("which", ["keys", "values", "both"])
def test_two_pool_walk_never_reads_past_the_length(monkeypatch, which):
    """Recycled-page garbage past ``lengths`` (huge, finite), in either
    pool: the rest of a live page, every page after it, a dead row's
    whole list.  Nothing moves by a bit."""
    lengths = [5, 37, 0, 64, 1]
    q, keys, values, table, lens = _two_pools(11, lengths, 8, 2)
    _walk_on(monkeypatch)
    kw = dict(layer=1, lengths=lens, kv_heads=2, scale=128 ** -0.5)
    clean = cca_decode_attention(q, keys, table, values=values, **kw)

    def poisoned(pool):
        flat = np.array(pool[1][table]).reshape(len(lengths), -1,
                                                pool.shape[-1])
        for i, n in enumerate(lengths):
            flat[i, n:] = 1e30
        return pool.at[1, table].set(jnp.asarray(flat.reshape(
            len(lengths), _PPS, _PAGE, -1)))

    dirty_k = poisoned(keys) if which != "values" else keys
    dirty_v = poisoned(values) if which != "keys" else values
    got = cca_decode_attention(q, dirty_k, table, values=dirty_v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert not np.any(np.asarray(got[2]))


def test_two_pool_walk_refuses_pools_that_do_not_match():
    q, keys, values, table, lens = _two_pools(0, [4, 4], 8, 2)
    kw = dict(layer=0, lengths=lens, kv_heads=2, scale=1.0)
    with pytest.raises(ValueError, match="do not fit together"):
        cca_decode_attention(q, keys, table, values=values[:, :-1], **kw)
    with pytest.raises(ValueError, match="do not fit together"):
        # One pool of this width is ONE key/value head of 128, not two.
        cca_decode_attention(q, keys, table, **kw)


# What the walk lowers to for the TPU with ONE pool, at the three served
# shapes: sha256 of the lowered text with each Mosaic body printed as
# MLIR without source locations (the recipe of
# ``.claude/skills/verify/SKILL.md``).  Recorded on PR 37's tree: the
# second pool is a STATIC branch, and ``hvd_mla_decode`` /
# ``hvd_cca_decode`` in JoyAI's, ZAYA's and Ouro's cells must stay the
# program they were.  A change of the one-pool kernel itself re-records
# these, and owes those three cells a measurement.
_ONE_POOL_LOWERED = {
    "zaya": "7c134b5debaf4f726b478fcf46cf0b8712bce9637cf1a9d5947a17b901ea87db",
    "ouro": "1612732b6a3731f777c426eb6f26b813cc2180d6f1c47ce1949e9444be4721ca",
    "joyai": "435906f506d3563cc70ad4590b1df2d3ab8371ba85e4d833dc22f4c91d73bf09",
}


@pytest.mark.parametrize("cell", list(_ONE_POOL_LOWERED))
def test_one_pool_walk_lowers_to_what_it_was(monkeypatch, cell):
    import hashlib

    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn._pallas, "interpret_mode", lambda: False)
    S, bf, i32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.int32
    fn, args = {
        # 96 slots, 8 query heads over 2 key heads, rows [k k | v v].
        "zaya": (lambda q, pool, t, n: cca_decode_attention(
            q, pool, t, layer=3, lengths=n, kv_heads=2, scale=128 ** -0.5),
            (S((96, 8, 128), bf), S((24, 9217, 16, 512), bf),
             S((96, 96), i32), S((96,), i32))),
        # 20 slots, 16 heads over 16, the plane a traced scalar.
        "ouro": (lambda q, pool, t, n, l: cca_decode_attention(
            q, pool, t, layer=l, lengths=n, kv_heads=16, scale=128 ** -0.5),
            (S((20, 16, 128), bf), S((192, 321, 16, 4096), bf),
             S((20, 16), i32), S((20,), i32), S((), i32))),
        # 64 slots, 32 heads over one latent row of 512 + 64 (+ 64).
        "joyai": (lambda q, pool, t, n: _attn.mla_decode_attention(
            q, pool, t, layer=2, lengths=n, value_dim=512, scale=0.07),
            (S((64, 32, 640), bf), S((5, 34817, 16, 640), bf),
             S((64, 544), i32), S((64,), i32))),
    }[cell]
    text = _lowered_for_tpu(fn, *args)
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _ONE_POOL_LOWERED[cell]


# ---------------------------------------------------------------------------
# The unified HOROVOD_PALLAS switch (ops.pallas).
# ---------------------------------------------------------------------------

def test_pallas_switch_resolution(monkeypatch):
    from horovod_tpu.ops import pallas as _pallas
    for var in ("HOROVOD_PALLAS", "HVD_TPU_PALLAS", "HVD_TPU_FLASH",
                "HOROVOD_PALLAS_FLASH", "HOROVOD_PALLAS_DECODE"):
        monkeypatch.delenv(var, raising=False)
    # auto: follows the backend (CPU here -> off).
    assert not _pallas.pallas_enabled("flash")
    # auto on TPU: only the families Mosaic compiles at full width; the
    # refuted BN kernel and the refused PowerSGD kernels stay on XLA.
    with monkeypatch.context() as m:
        m.setattr(_pallas.jax, "default_backend", lambda: "tpu")
        assert _pallas.active_kernels() == ("flash", "flash_decode",
                                            "mla_decode", "moe_gmm",
                                            "ssm_decode")
    # global switch gates every family...
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    assert _pallas.active_kernels() == _pallas.registered_kernels()
    # ...and the per-family override wins over it.
    monkeypatch.setenv("HOROVOD_PALLAS_DECODE", "0")
    assert not _pallas.pallas_enabled("flash_decode")
    assert not _pallas.pallas_enabled("mla_decode")   # the same switch
    assert not _pallas.pallas_enabled("ssm_decode")   # a decode kernel too
    assert _pallas.pallas_enabled("flash")
    assert _pallas.pallas_enabled("moe_gmm")          # the global one only
    with pytest.raises(ValueError, match="unknown pallas kernel family"):
        _pallas.pallas_enabled("nope")


def test_pallas_switch_legacy_flash_flag(monkeypatch):
    from horovod_tpu.ops import pallas as _pallas
    for var in ("HOROVOD_PALLAS", "HVD_TPU_PALLAS",
                "HOROVOD_PALLAS_FLASH"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    monkeypatch.setattr(_pallas, "_warned_legacy", False)
    with pytest.warns(DeprecationWarning, match="HVD_TPU_FLASH"):
        assert _pallas.pallas_enabled("flash")
    # The legacy flag only speaks for the flash family...
    assert not _pallas.pallas_enabled("flash_decode")
    # ...and loses to the unified per-family override.
    monkeypatch.setenv("HOROVOD_PALLAS_FLASH", "0")
    assert not _pallas.pallas_enabled("flash")


def test_pallas_kernel_contracts_are_collective_free():
    """The registry every kernel family ships: no in-kernel collectives,
    no wire-byte deltas -- what stepmodel/trace_audit build on."""
    from horovod_tpu.ops import pallas as _pallas
    fams = _pallas.registered_kernels()
    assert set(fams) >= {"flash", "flash_decode", "fused_update",
                         "bn_bwd"}
    for fam in fams:
        contract = _pallas.kernel_contract(fam)
        assert contract["collectives"] == ()
        assert contract["wire_delta_bytes"] == 0
        assert contract["site"]


# ---------------------------------------------------------------------------
# Head-group kernels: a sequence that one block holds (PR 29).
# ---------------------------------------------------------------------------

# (T, d, heads, kv_heads, causal): the BERT-Large cell's shape, a short
# one, Mistral's class (GQA 4:1, d = 128, causal) and two tiles of keys.
_HG_SHAPES = [(128, 64, 16, 16, False), (64, 64, 4, 4, False),
              (128, 128, 8, 2, True), (256, 64, 4, 4, False)]
_HG_IDS = ["bert_large", "t64", "mistral_class", "t256"]
_HG_TOL = {jnp.float32: dict(atol=5e-5, rtol=5e-5),
           jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def _hg_case(shape, dtype, batch=1):
    t, d, h, h_kv, causal = shape
    keys = jax.random.split(jax.random.PRNGKey(t + d + h), 4)
    q = _rand((batch, h, t, d), keys[0], dtype)
    k = _rand((batch, h_kv, t, d), keys[1], dtype)
    v = _rand((batch, h_kv, t, d), keys[2], dtype)
    w = _rand((batch, h, t, d), keys[3])
    assert _attn._flash_path(q, k, has_seg=False, bq=512, bk=512)[0] \
        == "head_group"

    def flash(q, k, v):
        return _flash(q, k, v, d ** -0.5, causal, 512, 512)

    def ref(q, k, v):
        rep = h // h_kv
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        return attention_reference(
            f32[0], jnp.repeat(f32[1], rep, axis=1),
            jnp.repeat(f32[2], rep, axis=1), causal=causal)

    return (q, k, v), w, flash, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", _HG_SHAPES, ids=_HG_IDS)
def test_head_group_forward_matches_reference(shape, dtype):
    args, _, flash, ref = _hg_case(shape, dtype, batch=2)
    got = flash(*args)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref(*args)), **_HG_TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", _HG_SHAPES, ids=_HG_IDS)
def test_head_group_grads_match_reference(shape, dtype):
    """dq, dk and dv, each in its operand's type (a shared kv head's
    query heads are summed inside the kernel)."""
    args, w, flash, ref = _hg_case(shape, dtype)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(*args)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(*args)
    for g, r, x in zip(got, want, args):
        assert g.shape == x.shape and g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   **_HG_TOL[dtype])


def test_head_group_cross_length_causal():
    """tq < tk in one block each: bottom-right aligned, as the blocked
    kernels and the reference."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand((2, 4, 16, 32), keys[0])
    k = _rand((2, 2, 64, 32), keys[1])
    v = _rand((2, 2, 64, 32), keys[2])
    assert _attn._flash_path(q, k, has_seg=False, bq=512, bk=512) \
        == ("head_group", 4)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    flash = lambda q, k, v: _flash(q, k, v, 0.2, True, 512, 512)  # noqa
    ref = lambda q, k, v: attention_reference(  # noqa: E731
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
        causal=True, scale=0.2)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def _shapes(q, k, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct(q, dtype), jax.ShapeDtypeStruct(k, dtype))


@pytest.mark.parametrize("q,k,blocks,has_seg,want", [
    # One block holds the sequence: the head-group kernels.
    ((32, 16, 128, 64), (32, 16, 128, 64), (512, 512), False, "head_group"),
    ((1, 32, 128, 128), (1, 8, 128, 128), (512, 512), False, "head_group"),
    ((1, 32, 512, 128), (1, 8, 512, 128), (512, 512), False, "head_group"),
    ((2, 4, 16, 32), (2, 2, 64, 32), (512, 512), False, "head_group"),
    # More than one block, at the served widths (Mistral's 1,024 prompt,
    # JoyAI's 1,024 and 8,192 with keys 192 wide): the blocked kernels.
    ((1, 32, 1024, 128), (1, 8, 1024, 128), (512, 512), False, "blocked"),
    ((1, 32, 1024, 192), (1, 32, 1024, 192), (512, 512), False, "blocked"),
    ((1, 32, 8192, 192), (1, 32, 8192, 192), (512, 512), False, "blocked"),
    # Blocks asked smaller than the sequence; one side in two blocks.
    ((2, 4, 64, 32), (2, 4, 64, 32), (32, 32), False, "blocked"),
    ((2, 4, 64, 32), (2, 4, 1024, 32), (512, 512), False, "blocked"),
    # Segment ids are declined.
    ((32, 16, 128, 64), (32, 16, 128, 64), (512, 512), True, "blocked"),
    # One kv head's group alone is over the budget (float32, T = 512).
    ((1, 32, 512, 128), (1, 8, 512, 128), (512, 512), None, "blocked"),
])
def test_flash_path_is_chosen_by_shape(q, k, blocks, has_seg, want):
    dtype = jnp.float32 if has_seg is None else jnp.bfloat16
    path, group = _attn._flash_path(*_shapes(q, k, dtype),
                                    has_seg=bool(has_seg),
                                    bq=blocks[0], bk=blocks[1])
    assert path == want
    assert (group > 0) == (want == "head_group")


def test_flash_paths_carry_their_kernel_names():
    """The names the ops line of a device trace shows say which path a
    shape took: ``hvd_flash_hg_*`` for one block, ``hvd_flash_*`` above."""
    import re

    def names(t, block):
        x = jax.ShapeDtypeStruct((1, 2, t, 16), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: _flash(q, k, v, 0.25, True, block,
                                   block).sum(), argnums=(0, 1, 2)))(x, x, x)
        return sorted(set(re.findall(r"name=(hvd_\w+)", str(jaxpr))))

    assert names(64, 512) == ["hvd_flash_hg_bwd", "hvd_flash_hg_fwd"]
    assert names(64, 32) == ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq",
                             "hvd_flash_fwd"]


@pytest.mark.parametrize("heads,kv_heads,tq,tk,d,itemsize", [
    (16, 16, 128, 128, 64, 2),      # BERT-Large's cell: all 16 heads
    (16, 16, 128, 128, 64, 4),
    (32, 8, 128, 128, 128, 2),      # Mistral's prompts of 128 ... 512
    (32, 8, 256, 256, 128, 2),
    (32, 8, 512, 512, 128, 2),
    (16, 8, 384, 384, 128, 2),
    (16, 16, 512, 512, 64, 2),
    (32, 4, 256, 256, 128, 2),
    (8, 8, 8, 512, 256, 4),
])
def test_head_group_is_a_function_of_shapes_within_the_budget(
        heads, kv_heads, tq, tk, d, itemsize):
    rep = heads // kv_heads
    group = _attn._head_group(heads, kv_heads, tq, tk, d, itemsize)
    assert group == _attn._head_group(heads, kv_heads, tq, tk, d, itemsize)
    assert group > 0 and group % rep == 0 and heads % group == 0
    g_kv = group // rep
    assert _attn._head_group_bytes(g_kv, rep, tq, tk, d, itemsize) \
        <= _attn._HEAD_GROUP_VMEM_BUDGET < 16 * 2 ** 20
    # The largest: the next divisor of the kv heads is over the budget.
    bigger = [g for g in range(g_kv + 1, kv_heads + 1) if kv_heads % g == 0]
    if bigger:
        assert _attn._head_group_bytes(bigger[0], rep, tq, tk, d,
                                       itemsize) \
            > _attn._HEAD_GROUP_VMEM_BUDGET
    # The batch is not an argument, and the group does not grow with the
    # sequence or the head dim.
    assert _attn._head_group(heads, kv_heads, 2 * tq, 2 * tk, d,
                             itemsize) <= group
    assert _attn._head_group(heads, kv_heads, tq, tk, 2 * d,
                             itemsize) <= group


def test_head_group_cell_shape_takes_all_sixteen_heads():
    assert _attn._head_group(16, 16, 128, 128, 64, 2) == 16
    assert _attn._head_group(32, 8, 512, 512, 128, 2) == 4
    assert _attn._head_group(32, 8, 512, 512, 128, 4) == 0


@pytest.mark.parametrize("causal", [False, True])
def test_one_block_with_segment_ids_runs_blocked_kernels(causal):
    """Segment ids are declined by the head-group path: a packed batch
    that one block holds still goes through the online-softmax kernels
    and matches the reference, gradients and dead rows included."""
    t = 128
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    q = _rand((2, 2, t, 16), keys[0])
    k = _rand((2, 2, t, 16), keys[1])
    v = _rand((2, 2, t, 16), keys[2])
    seg = _packed_segments(keys[3], 2, t)
    assert _attn._flash_path(q, k, has_seg=True, bq=t, bk=t) \
        == ("blocked", 0)

    def flash(q, k, v):
        return _attn._flash_seg(q, k, v, seg, seg, 0.25, causal, t, t)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal, scale=0.25,
                                   segment_ids=seg)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    for a, b in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_public_api_reaches_head_group_kernels(monkeypatch):
    """``flash_attention`` itself, kernels forced on: BERT's call (no
    mask, 16 heads of 64) takes the head-group path and logs its choice
    once a kernel at debug level."""
    import logging
    monkeypatch.setenv("HOROVOD_PALLAS_FLASH", "1")
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (_rand((1, 16, 128, 64), kk, jnp.bfloat16) for kk in keys)
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("horovod_tpu.ops")
    logger.addHandler(handler)
    old = logger.level
    logger.setLevel(logging.DEBUG)
    try:
        out = flash_attention(q, k, v)
    finally:
        logger.setLevel(old)
        logger.removeHandler(handler)
    ref = attention_reference(*(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)
    said = [r.getMessage() for r in records]
    assert len(said) == 1 and "head_group kernels, 16 heads" in said[0]


# ---------------------------------------------------------------------------
# The blocked forward multiplies in its operands' type (PR 41).
# ---------------------------------------------------------------------------

def _blocked_by_hand(q, k, v, *, scale, block, window, round_p):
    """``_fwd_kernel``'s arithmetic in ``jax.numpy``, a head and a query
    block at a time: the products take q, k and v as they are and sum in
    float32, the statistics are float32, ``l`` is summed from the float32
    ``p``; with ``round_p`` the weights are rounded to the values' type
    before ``p v``, without it they and the values multiply in float32
    (what the kernel did before)."""
    f32 = jnp.float32
    b, h, t, d = q.shape
    rep = h // k.shape[1]
    out = np.zeros(q.shape, np.float32)
    at = jnp.arange(block)
    for bi, hi, i in np.ndindex(b, h, t // block):
        rows = slice(i * block, (i + 1) * block)
        m = jnp.full((block, 1), _attn._NEG_INF, f32)
        l = jnp.zeros((block, 1), f32)
        acc = jnp.zeros((block, d), f32)
        first = 0 if window is None else _attn._band_first(
            i, block, block, 0, window)
        for j in range(first, i + 1):
            cols = slice(j * block, (j + 1) * block)
            kb, vb = k[bi, hi // rep, cols], v[bi, hi // rep, cols]
            s = jax.lax.dot_general(q[bi, hi, rows], kb,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * scale
            gap = (i - j) * block + at[:, None] - at[None, :]
            keep = gap >= 0 if window is None else (gap >= 0) & (gap < window)
            s = jnp.where(keep, s, _attn._NEG_INF)
            m_new = jnp.maximum(m, s.max(1, keepdims=True))
            p, alpha = jnp.exp(s - m_new), jnp.exp(m - m_new)
            l = alpha * l + p.sum(1, keepdims=True)
            pv = (p.astype(vb.dtype), vb) if round_p else (p, vb.astype(f32))
            acc = acc * alpha + jax.lax.dot_general(
                *pv, (((1,), (0,)), ((), ())), preferred_element_type=f32)
            m = m_new
        out[bi, hi, rows] = np.asarray((acc / l).astype(q.dtype), np.float32)
    return out


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [128, 192])
@pytest.mark.parametrize("kernel,window", [("hvd_flash_fwd", None),
                                           ("hvd_flash_swa_fwd", 96)])
def test_blocked_forward_multiplies_in_the_operands_type(monkeypatch, kernel,
                                                         window, d, dtype):
    """q, k and v over two blocks of 128 (two query heads a key head).
    bfloat16: the kernel IS the online softmax with bfloat16 products and
    float32 sums, ``p`` rounded to bfloat16 before ``p v`` (the result
    leaves as bfloat16, so a float32 ulp of the accumulation either
    vanishes or shows as ONE bfloat16 ulp of a rare element), which the
    same arithmetic with a float32 ``p v`` is not (it agrees on 63% of
    the elements); it stays within the head-group kernels' bfloat16
    tolerance of the float32 reference; no q, k or v tile is converted to
    float32 inside it.  float32: the same online softmax in float32,
    element for element (the statistics' width is a layout, not an
    arithmetic)."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(d), 3)
    q = _rand((1, 2, 256, d), keys[0], dtype)
    k = _rand((1, 1, 256, d), keys[1], dtype)
    v = _rand((1, 1, 256, d), keys[2], dtype)

    def run(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=128, block_kv=128)

    got = run(q, k, v)
    assert got.dtype == dtype
    got = np.asarray(got, np.float32)
    hand = {r: _blocked_by_hand(q, k, v, scale=d ** -0.5, block=128,
                                window=window, round_p=r)
            for r in (True, False)}
    if dtype == bf:
        assert np.mean(got == hand[True]) > 0.999
        np.testing.assert_allclose(got, hand[True], rtol=2.0 ** -7,
                                   atol=1e-30)
        assert np.mean(got == hand[False]) < 0.9
    else:           # a few float32 ulps: the products' order of summation
        np.testing.assert_allclose(got, hand[True], rtol=2e-6, atol=2e-6)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = attention_reference(f32[0], jnp.repeat(f32[1], 2, axis=1),
                              jnp.repeat(f32[2], 2, axis=1), causal=True,
                              window=window)
    np.testing.assert_allclose(got, np.asarray(ref), **_HG_TOL[dtype])

    calls = [e for e in _equations(jax.make_jaxpr(run)(q, k, v).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [str(e.params["name"]) for e in calls] == [kernel]
    body = list(_equations(calls[0].params["jaxpr"]))
    assert sum(e.primitive.name == "dot_general" for e in body) == 2
    for e in body:
        if e.primitive.name == "dot_general":
            assert [x.aval.dtype for x in e.invars] == [dtype, dtype]
            assert e.outvars[0].aval.dtype == jnp.float32
        if e.primitive.name == "convert_element_type":
            assert e.invars[0].aval.dtype != bf, e
    # The statistics are read and written a whole (bq, 128) tile, every
    # lane of a row alike: the one (bq, 1) column a step makes is a row
    # reduction's (the max and the sum of the step, and nothing in
    # ``_init`` or ``_finish``), never a slice of a scratch tile, which
    # the cross-lane unit would have to broadcast again.
    columns = [e.primitive.name for e in body
               if any(getattr(v.aval, "shape", None) == (128, 1)
                      for v in e.outvars)]
    assert columns == ["broadcast_in_dim"] * 2       # the two keepdims


# sha256 of the TPU lowering (``serving_families.lowered_for_tpu``) of the
# blocked kernels.
# The backward pair and the head-group pair were recorded on PR 40's tree,
# the parent of the PR that rewrote ``_fwd_kernel``'s step: that PR did
# not touch them, in either type.  The forward's were recorded on PR 41's
# own tree (its products in the operands' type, its statistics a whole
# (bq, 128) tile: float32 operands no longer lower to the parent's text,
# their arithmetic is the parent's element for element): whoever changes
# code these kernels share sees it here, re-records, and owes JoyAI's and
# K-EXAONE's cells a measurement.  At Mistral's 1,024 tokens the forward
# is also held by ``tests/test_serving_swa.py``.
_BLOCKED_LOWERED = {
    "flash_fwd_f32":
        "80fa44c4e3b5a6aafea039ea0efdde62c3091e4882f335943c03378f5e5cbda1",
    "flash_fwd_bf16":
        "e2a01657fee3dc2eb3d46093c1d2effb74f05ce2b538c6fbc9420ab5cde33ddf",
    "flash_fwd_d192_f32":
        "8ac605e954a57c00bdee4c42ac2c89fa3f60ed42687518a0fc1e13a6c2bc94b5",
    "flash_fwd_d192_bf16":
        "5b21536348bce849e1fe85f09915bce1f805fb5d569cb1f8205c267a78545cb4",
    # Values 128 wide beside keys of 192 (JoyAI's prefill since PR 49):
    # recorded on PR 49's tree; every other entry is as PR 49 found it.
    "flash_fwd_d192_v128_f32":
        "54a0c15715864392e17888a8d18a63ecd4c0c19751b872871cfc46d9e817be00",
    "flash_fwd_d192_v128_bf16":
        "c89e076e33e463a778827e186ecb243066b8c5fc42b28a912450950902669c84",
    "flash_swa_fwd_f32":
        "897a4d89d88548c86032be3ca2205a9f2eea16b69178581193c32e5f213e0b95",
    "flash_swa_fwd_bf16":
        "e8ab31a5187eb1f72c1284aa77c73c805ccdc913535ba124dd203c947d4dd54b",
    "flash_vjp_f32":
        "f77ea63ee201d998614a2d2961013e3476826803b1b9e1f6210d7b476a186275",
    "flash_seg_vjp_f32":
        "79efd766cd6f92432fd36f69c4c6635e44219493acbc53c82cb539c9309bd031",
    # Untouched by PR 41: the parent's text.
    "flash_bwd_f32":
        "252d05b653e8649fdfab9088e282f3d11d53c1271da4c04e2ac09ad4a5ed20c6",
    "flash_bwd_bf16":
        "43bda09f87090aa46f226790832657dea9e1fb22495f719b3d1ee717ef21fb26",
    "hg_fwd_f32":
        "0b1879797d7ca6a35c8e189faeed049d3999b244f6681554a011cdcc31f7b9b8",
    "hg_fwd_bf16":
        "007261c2981d62e1dd4ade28e71efd3efd62b2c0f5f9aacfd9de30fb54b5c320",
    "hg_bwd_f32":
        "3e6369a5642320360ec788dffcd9fb48177260d2cd8dfed4f1521abf9c0d27b4",
    "hg_bwd_bf16":
        "df712031cd7bd7d8c797e70e87b516a02bdde2adb6df0d3faafca81749c909f2",
}


def _blocked_lowered_case(case):
    """``(fn, shapes, Mosaic calls)`` of a case: 1,024 tokens in blocks
    of 512, eight query heads over two key heads of 128 (``d192``: four
    over four of 192, JoyAI's width; ``v128``: its values at their own
    128); the head-group pair at BERT-Large's
    sixteen heads of 64 over 128 tokens, under a scale no other test
    gives them (they are ``jax.jit`` functions: a trace another test made
    with the interpreter on would be found again here)."""
    S = jax.ShapeDtypeStruct
    what, dt = case.rsplit("_", 1)
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dt]
    q, kv = S((1, 8, 1024, 128), dt), S((1, 2, 1024, 128), dt)
    seg, stat = S((1, 1024), jnp.int32), S((1, 8, 1024), jnp.float32)
    hq, hstat = S((2, 16, 128, 64), dt), S((2, 16, 128), jnp.float32)
    opts = dict(scale=0.1, causal=True, bq=512, bk=512)
    total = lambda o: o.astype(jnp.float32).sum()  # noqa: E731
    return {
        "flash_fwd": (lambda q, k, v: _attn._flash_fwd(
            q, k, v, None, None, **opts), (q, kv, kv), 1),
        "flash_fwd_d192": (lambda q, k, v: _attn._flash_fwd(
            q, k, v, None, None, **opts),
            (S((1, 4, 1024, 192), dt),) * 3, 1),
        "flash_fwd_d192_v128": (lambda q, k, v: _attn._flash_fwd(
            q, k, v, None, None, **opts),
            (S((1, 4, 1024, 192), dt),) * 2 + (S((1, 4, 1024, 128), dt),),
            1),
        "flash_swa_fwd": (lambda q, k, v: _attn._flash_swa_fwd(
            q, k, v, scale=0.1, window=128, bq=512, bk=512), (q, kv, kv), 1),
        # The forward inside the custom_vjp pair, beside its backward.
        "flash_vjp": (jax.grad(lambda q, k, v: total(_flash(
            q, k, v, 0.1, True, 512, 512)), (0, 1, 2)), (q, kv, kv), 3),
        "flash_seg_vjp": (jax.grad(lambda q, k, v, a, b: total(
            _attn._flash_seg(q, k, v, a, b, 0.1, True, 512, 512)),
            (0, 1, 2)), (q, kv, kv, seg, seg), 3),
        "flash_bwd": (lambda q, k, v, o, lse, g: _attn._flash_bwd(
            (q, k, v, o, lse, None, None), g, **opts),
            (q, kv, kv, q, stat, q), 2),
        "hg_fwd": (lambda q, k, v: _attn._hg_fwd(
            q, k, v, scale=0.12, causal=False, group=16), (hq, hq, hq), 1),
        "hg_bwd": (lambda q, k, v, lse, g: _attn._hg_bwd(
            q, k, v, lse, g, scale=0.12, causal=False, group=16),
            (hq, hq, hq, hstat, hq), 1),
    }[what]


@pytest.mark.parametrize("case", list(_BLOCKED_LOWERED))
def test_blocked_and_head_group_kernels_lower_to_what_was_recorded(
        monkeypatch, case):
    import hashlib

    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(_attn._pallas, "interpret_mode", lambda: False)
    fn, shapes, mosaic_calls = _blocked_lowered_case(case)
    text = _lowered_for_tpu(fn, *shapes)
    assert "loc(" not in text
    assert text.count("stablehlo.custom_call @tpu_custom_call") \
        == mosaic_calls
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _BLOCKED_LOWERED[case]


# ---------------------------------------------------------------------------
# Values of a width of their own (PR 49).
# ---------------------------------------------------------------------------

def _own_width_case(shape, dv, dtype, kv_heads=None, seed=49):
    """q and k of ``shape``, v ``dv`` wide, and v padded with zeros to the
    keys' width: what latent attention's prefill handed the kernel
    before."""
    b, h, t, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed + t + dv), 3)
    q = _rand(shape, keys[0], dtype)
    k = _rand((b, kv_heads or h, t, d), keys[1], dtype)
    v = _rand((b, kv_heads or h, t, dv), keys[2], dtype)
    padded = jnp.pad(v, ((0, 0),) * 3 + ((0, max(d - dv, 0)),))
    return q, k, v, padded


def _own_width_reference(q, k, v, **kw):
    rep = q.shape[1] // k.shape[1]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return attention_reference(f32[0], jnp.repeat(f32[1], rep, axis=1),
                               jnp.repeat(f32[2], rep, axis=1), **kw)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_blocked_forward_takes_values_narrower_than_the_keys(monkeypatch,
                                                             dtype):
    """JoyAI's prefill call at four heads: 1,024 tokens in blocks of 512,
    queries and keys 192 wide, values 128.  The result is 128 wide and
    BIT FOR BIT the kept columns of the call with the values padded to
    192 (a column of ``p v`` is its own dot product over the same ``p``);
    the one kernel reads ``v`` and writes ``o`` 128 wide and its body is
    the padded call's but for the widths."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    q, k, v, padded = _own_width_case((1, 4, 1024, 192), 128, dtype)

    def run(q, k, v):
        return flash_attention(q, k, v, causal=True)

    got = run(q, k, v)
    assert got.shape == (1, 4, 1024, 128) and got.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(run(q, k, padded)[..., :128], np.float32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_own_width_reference(q, k, v, causal=True)),
        **_HG_TOL[dtype])

    def call(v):
        calls = [e for e in _equations(jax.make_jaxpr(run)(q, k, v).jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert [str(e.params["name"]) for e in calls] == ["hvd_flash_fwd"]
        return calls[0]

    own, wide = call(v), call(padded)
    assert [x.aval.shape[-1] for x in own.invars] == [192, 192, 128]
    assert [x.aval.shape[-1] for x in own.outvars] == [128, 128]
    assert [x.aval.shape[-1] for x in wide.outvars] == [192, 128]
    # No branch on the width: the same equations, less what ``_lanes``
    # needs to lay a statistic 192 columns wide
    # (``alpha`` beside the accumulator, ``l`` at the close).
    import collections
    names = lambda call: collections.Counter(  # noqa: E731
        e.primitive.name for e in _equations(call.params["jaxpr"]))
    assert names(wide) - names(own) == {"concatenate": 2, "slice": 2}
    assert not names(own) - names(wide)


@pytest.mark.parametrize("d,dv", [(24, 16), (16, 24)],
                         ids=["narrower", "wider"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 512], ids=["blocks", "one_block"])
def test_blocked_grads_with_values_of_their_own_width(d, dv, causal, block):
    """The backward kernels know one width: ``_flash_bwd`` pads the
    narrower of (q, k) and (v, o, dO) with zeros, runs and cuts.  dq, dk
    and dv equal the reference's, each in its operand's shape; four query
    heads over two key heads.  ``one_block``: the forward still runs
    blocked, and the padded backward, which one block holds, takes the
    head-group kernel over the blocked forward's logsumexp."""
    q, k, v, _ = _own_width_case((1, 4, 64, d), dv, jnp.float32, kv_heads=2)

    def flash(q, k, v):
        return _flash(q, k, v, d ** -0.5, causal, block, block)

    names = str(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v))
    assert "hvd_flash_fwd" in names and "hvd_flash_hg_fwd" not in names
    assert ("hvd_flash_hg_bwd" in names) == (block == 512)

    def ref(q, k, v):
        return _own_width_reference(q, k, v, causal=causal)

    assert flash(q, k, v).shape == (1, 4, 64, dv)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    for x, a, b in zip((q, k, v),
                       jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                       jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        assert a.shape == x.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_window_takes_values_of_their_own_width(monkeypatch):
    """``hvd_flash_swa_fwd`` as the docstring says: the band's kernel
    carries the values at their width too, bit for bit the padded
    call's kept columns."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    q, k, v, padded = _own_width_case((1, 2, 256, 192), 128, jnp.float32,
                                      kv_heads=1)

    def run(q, k, v):
        return flash_attention(q, k, v, causal=True, window=96,
                               block_q=128, block_kv=128)

    got = run(q, k, v)
    assert got.shape == (1, 2, 256, 128)
    assert "hvd_flash_swa_fwd" in str(jax.make_jaxpr(run)(q, k, v))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(run(q, k, padded)[..., :128]))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_own_width_reference(
            q, k, v, causal=True, window=96)), **_HG_TOL[jnp.float32])


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_take_values_of_their_own_width(monkeypatch, causal):
    """A packed batch with values narrower than its keys: the blocked
    kernels with segment ids, forward, gradients and dead rows as the
    reference has them."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    q, k, v, _ = _own_width_case((2, 2, 128, 24), 16, jnp.float32)
    seg = _packed_segments(jax.random.PRNGKey(3), 2, 128)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal, segment_ids=seg)

    assert "hvd_flash_fwd" in str(jax.make_jaxpr(flash)(q, k, v))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    for a, b in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("t,want", [(128, "blocked"), (1024, "blocked")])
def test_values_of_their_own_width_take_the_blocked_path(caplog, t, want):
    """One block or many: the head-group kernels know one width, so a
    call whose values differ from its keys runs the blocked set, and the
    debug line says how wide the values are."""
    import logging
    q, k = _shapes((1, 32, t, 192), (1, 32, t, 192))
    v = jax.ShapeDtypeStruct((1, 32, t, 128), jnp.bfloat16)
    with caplog.at_level(logging.DEBUG, logger="horovod_tpu.ops"):
        assert _attn._flash_path(q, k, v, has_seg=False, bq=512, bk=512) \
            == (want, 0)
        as_wide = _attn._flash_path(q, k, k, has_seg=False, bq=512, bk=512)
    assert as_wide == _attn._flash_path(q, k, has_seg=False, bq=512, bk=512)
    assert (as_wide[0] == "head_group") == (t == 128)
    assert "values 128 wide" in caplog.messages[0]
    assert "values 192 wide" in caplog.messages[1]


@pytest.mark.parametrize("k_shape,v_shape", [
    ((1, 2, 64, 16), (1, 2, 64, 24)),       # keys narrower than queries
    ((1, 2, 64, 24), (1, 2, 32, 24)),       # a value a key
    ((1, 2, 64, 24), (1, 1, 64, 24))])      # ... and a head
def test_operands_that_do_not_fit_together_are_refused(k_shape, v_shape):
    x = jnp.zeros((1, 2, 64, 24))
    with pytest.raises(ValueError, match="do not fit together"):
        flash_attention(x, jnp.zeros(k_shape), jnp.zeros(v_shape))


# ---------------------------------------------------------------------------
# ``hvd_eva_decode``: one softmax over a ring of exact rows and the pooled
# rows of the windows before, both in ONE pair of pools.
# ---------------------------------------------------------------------------

from horovod_tpu.ops.attention import eva_decode_attention

# Pages and chunks of 8 rows; a window of 64 tokens is 8 pages of the ring
# (of 9) and 8 pooled rows, ONE growing page: every boundary on an edge.
_EVA_PAGE, _EVA_WINDOW, _EVA_RING, _EVA_PPS = 8, 64, 9, 3
# Live tokens, the current one among them: inside the first window (no
# pooled row), a window's last token, a window's FIRST token (one exact row
# beside 8 and 16 pooled ones), mid-window and mid-page, an idle row.
_EVA_LENGTHS = [1, 5, 64, 65, 129, 100, 191, 0, 150]


def _eva_case(seed, lengths, h, kvh, d=128, page=_EVA_PAGE,
              window=_EVA_WINDOW, ring=_EVA_RING, pps=_EVA_PPS):
    """One pair of pools holding both groups' pages (two planes), shuffled
    tables of each group, and what a slot's rows ARE, in order: its pooled
    rows ``[b, pps * page, kvh * d]`` and the exact rows of its sequence
    ``[b, tokens, kvh * d]`` (token ``n`` in ring entry ``n // page %
    ring``; a page that a later token has overwritten holds the later
    one)."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    pages = b * (pps + ring) + 1
    shape = (2, pages, page, kvh * d)
    keys = rng.normal(size=shape).astype(np.float32)
    values = rng.normal(size=shape).astype(np.float32)
    order = rng.permutation(pages - 1)
    table = order[:b * pps].reshape(b, pps).astype(np.int32)
    wtable = order[b * pps:].reshape(b, ring).astype(np.int32)
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(keys), jnp.asarray(values),
            jnp.asarray(table), jnp.asarray(wtable),
            jnp.asarray(lengths, jnp.int32))


def _eva_by_hand(q, keys, values, table, wtable, lengths, kvh, *, plane,
                 page=_EVA_PAGE, window=_EVA_WINDOW, chunk=_EVA_PAGE):
    """The equations a row at a time in numpy: the exact rows ``window *
    w .. i`` out of the ring and the pooled rows ``c < w * window /
    chunk`` out of the growing pages, ONE softmax."""
    q, keys, values = (np.asarray(z, np.float64) for z in (q, keys, values))
    b, h, d = q.shape
    ring = wtable.shape[1]
    out = np.zeros((b, h, d))
    for s, n in enumerate(np.asarray(lengths)):
        if n == 0:
            continue
        i = n - 1
        w = i // window
        ks, vs = [], []
        for c in range(w * window // chunk):
            pid, off = int(table[s, c // page]), c % page
            ks.append(keys[plane, pid, off])
            vs.append(values[plane, pid, off])
        for j in range(w * window, i + 1):
            pid, off = int(wtable[s, j // page % ring]), j % page
            ks.append(keys[plane, pid, off])
            vs.append(values[plane, pid, off])
        ks, vs = (np.stack(z).reshape(len(ks), kvh, d) for z in (ks, vs))
        for head in range(h):
            g = head // (h // kvh)
            z = ks[:, g] @ q[s, head] * d ** -0.5
            p = np.exp(z - z.max())
            out[s, head] = (p / p.sum()) @ vs[:, g]
    return out


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2)])
@pytest.mark.parametrize("kernel", [True, False])
def test_eva_decode_is_one_softmax_over_ring_and_pooled_rows(
        monkeypatch, h, kvh, kernel):
    q, keys, values, table, wtable, lens = _eva_case(
        h + kernel, _EVA_LENGTHS, h, kvh)
    want = _eva_by_hand(q, keys, values, table, wtable, lens, kvh, plane=1)
    if kernel:
        _walk_on(monkeypatch)
    got = eva_decode_attention(
        q, keys, table, wtable, layer=1, lengths=lens, window=_EVA_WINDOW,
        row_tokens=_EVA_PAGE, kv_heads=kvh, scale=128 ** -0.5,
        values=values, force_reference=not kernel)
    assert got.shape == (len(_EVA_LENGTHS), h, 128)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[_EVA_LENGTHS.index(0)]))


def test_eva_decode_kernel_is_the_walk_under_its_own_name(monkeypatch):
    q, keys, values, table, wtable, lens = _eva_case(2, [70, 0, 130], 4, 4)
    _walk_on(monkeypatch)
    text = str(jax.make_jaxpr(lambda *a: eva_decode_attention(
        *a, layer=0, lengths=lens, window=_EVA_WINDOW, row_tokens=_EVA_PAGE,
        kv_heads=4, scale=0.1, values=values))(q, keys, table, wtable))
    assert "hvd_eva_decode" in text and "hvd_cca_decode" not in text


@pytest.mark.parametrize("kernel", [True, False])
def test_eva_decode_reads_nothing_it_does_not_see(monkeypatch, kernel):
    """Huge, finite garbage in every row a slot does NOT attend: the ring's
    rows past the current token and before the window (pages the window
    has left behind and not yet overwritten), the pooled rows of the
    window in progress and after, an idle slot's everything.  Nothing
    moves by a bit."""
    lengths = [5, 65, 0, 150, 129]
    q, keys, values, table, wtable, lens = _eva_case(9, lengths, 4, 4)
    if kernel:
        _walk_on(monkeypatch)
    kw = dict(layer=1, lengths=lens, window=_EVA_WINDOW,
              row_tokens=_EVA_PAGE, kv_heads=4, scale=128 ** -0.5,
              force_reference=not kernel)
    clean = eva_decode_attention(q, keys, table, wtable, values=values, **kw)

    def poisoned(pool):
        pool = np.array(pool)
        for s, n in enumerate(lengths):
            i, w = n - 1, max(n - 1, 0) // _EVA_WINDOW
            seen = w * _EVA_WINDOW // _EVA_PAGE if n else 0
            for c in range(seen, _EVA_PPS * _EVA_PAGE):
                pool[1, table[s, c // _EVA_PAGE], c % _EVA_PAGE] = 1e30
            held = {j // _EVA_PAGE % _EVA_RING: j // _EVA_PAGE
                    for j in range(w * _EVA_WINDOW, i + 1)} if n else {}
            for entry in range(_EVA_RING):
                for off in range(_EVA_PAGE):
                    j = held.get(entry, -1) * _EVA_PAGE + off
                    if entry not in held or j > i:
                        pool[1, wtable[s, entry], off] = 1e30
        return jnp.asarray(pool)

    got = eva_decode_attention(q, poisoned(keys), table, wtable,
                               values=poisoned(values), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert not np.any(np.asarray(got[2]))


def test_eva_decode_off_a_page_s_edge_takes_the_gathered_form(monkeypatch):
    """A window whose pooled rows fill no whole page (64 tokens in chunks
    of 16 over pages of 16: 4 pooled rows a window) cannot be walked as
    one composed table: the same softmax over gathered views, kernels on
    or off."""
    lengths = [70, 130, 0, 64]
    q, keys, values, table, wtable, lens = _eva_case(
        4, lengths, 4, 4, page=16, ring=5, pps=2)
    want = _eva_by_hand(q, keys, values, table, wtable, lens, 4, plane=0,
                        page=16, chunk=16)
    _walk_on(monkeypatch)
    fn = lambda *a: eva_decode_attention(                        # noqa: E731
        *a, layer=0, lengths=lens, window=64, row_tokens=16, kv_heads=4,
        scale=128 ** -0.5, values=values)
    assert "hvd_eva_decode" not in str(jax.make_jaxpr(fn)(
        q, keys, table, wtable))
    np.testing.assert_allclose(np.asarray(fn(q, keys, table, wtable)), want,
                               rtol=2e-5, atol=2e-5)


def test_eva_decode_refuses_what_does_not_fit():
    q, keys, values, table, wtable, lens = _eva_case(0, [4, 4], 4, 4)
    kw = dict(layer=0, lengths=lens, row_tokens=_EVA_PAGE, kv_heads=4,
              scale=1.0)
    with pytest.raises(ValueError, match="do not fit together"):
        eva_decode_attention(q, keys, table, wtable, window=_EVA_WINDOW,
                             values=values[:, :-1], **kw)
    with pytest.raises(ValueError, match="ring entries"):
        # A window of 128 tokens wants 17 ring entries of 8 rows.
        eva_decode_attention(q, keys, table, wtable, window=128,
                             values=values, **kw)
    with pytest.raises(ValueError, match="whole pages"):
        eva_decode_attention(q, keys, table, wtable, window=60,
                             values=values, **kw)
