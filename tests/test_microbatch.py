"""Backward-overlap microbatched train step tests (``microbatches=k``).

The microbatched variant splits the per-step batch into k sub-batches
inside ONE compiled executable and reduce-scatters the gradient buckets
of microbatch i while microbatch i+1's backward runs.  Contracts under
test:

* k=1 is bitwise the single-shot builder (same code path).
* k>1 matches single-shot at the same global batch within the documented
  cross-microbatch f32-accumulation tolerance (loss must be a
  per-example MEAN for the split to be equivalent).
* The emitted StableHLO interleaves ``reduce_scatter`` ops between the
  microbatch backward segments (a reduce_scatter appears BEFORE the last
  backward matmul) -- the structural property the latency-hiding
  scheduler needs.
* Incompatible configurations are rejected eagerly at build time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hv
from horovod_tpu.utils.scaling import emitted_collective_stats

RTOL, ATOL = 2e-5, 2e-6  # documented accumulation tolerance (f32 accum)


def _params0():
    rng = np.random.RandomState(0)
    return {"w": rng.randn(6, 4).astype(np.float32),
            "b": rng.randn(4).astype(np.float32)}


def _batch(n_rows=32):
    return (np.random.RandomState(1).randn(n_rows, 6).astype(np.float32),
            np.random.RandomState(2).randn(n_rows, 4).astype(np.float32))


def _loss(p, b):
    # Per-example MEAN: required for microbatch equivalence.
    return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)


def _run(k, steps=4, compression=None, microbatches_kw=True):
    kw = {} if compression is None else {"compression": compression}
    opt = hv.DistributedOptimizer(optax.sgd(0.1, momentum=0.9), **kw)
    params = hv.replicate(_params0())
    opt_state = hv.replicate(opt.init(params))
    step = hv.make_train_step(_loss, opt, microbatches=k)
    batch = hv.shard_batch(_batch())
    lowered = step.lower(params, opt_state, batch)
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    return jax.tree.map(np.asarray, params), float(loss), lowered


@pytest.mark.parametrize("k", [2, 4])
def test_microbatch_parity_with_single_shot(hvd, k):
    p1, l1, _ = _run(1)
    pk, lk, _ = _run(k)
    assert np.isclose(l1, lk, rtol=RTOL)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pk)):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


def test_microbatch_k1_is_bitwise_single_shot(hvd):
    """k=1 takes the single-shot builder branch: bitwise identical."""
    p1, l1, _ = _run(1)
    pk, lk, _ = _run(1, microbatches_kw=True)
    assert l1 == lk
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pk)):
        np.testing.assert_array_equal(a, b)


def test_microbatch_hlo_interleaves_exchange_with_backward(hvd):
    """Structural overlap: a per-microbatch reduce_scatter is emitted
    BEFORE the last backward dot_general, i.e. exchange(i) sits between
    backward segments, not after all of them."""
    _, _, lowered = _run(4, steps=1)
    txt = lowered.as_text()
    first_rs = txt.find("reduce_scatter")
    last_dot = txt.rfind("dot_general")
    assert 0 <= first_rs < last_dot
    stats = emitted_collective_stats(txt)
    # k reduce-scatters (one per microbatch, single bucket for this tiny
    # model), ONE finalize all-gather, one loss all-reduce.
    assert stats.counts.get("reduce-scatter", 0) == 4
    assert stats.counts.get("all-gather", 0) == 1
    assert stats.counts.get("all-reduce", 0) == 1


def test_microbatch_compressed_exchange_runs(hvd):
    """bf16 wire compression composes with the microbatch exchange."""
    pk, lk, lowered = _run(2, compression=hv.Compression.bf16)
    assert np.isfinite(lk)
    # Wire dtype is bf16: the reduce-scatter operand must be bf16.
    assert "reduce_scatter" in lowered.as_text()
    for leaf in jax.tree.leaves(pk):
        assert np.isfinite(leaf).all()


def test_microbatch_flax_parity(hvd):
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(4)(nn.relu(nn.Dense(8)(x)))

    model = MLP()
    x = np.random.RandomState(3).randn(32, 6).astype(np.float32)
    y = np.random.RandomState(4).randint(0, 4, (32,)).astype(np.int32)
    fp = jax.tree.map(np.asarray,
                      model.init(jax.random.PRNGKey(0), x[:2])["params"])

    def frun(k):
        opt = hv.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        params = hv.replicate(fp)
        opt_state = hv.replicate(opt.init(params))
        step = hv.make_flax_train_step(model.apply, opt, microbatches=k)
        batch = hv.shard_batch((x, y))
        stats = {}
        for _ in range(3):
            params, stats, opt_state, loss = step(
                params, stats, opt_state, batch)
        return jax.tree.map(np.asarray, params), float(loss)

    f1, l1 = frun(1)
    f4, l4 = frun(4)
    assert np.isclose(l1, l4, rtol=RTOL)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f4)):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


# -- rejections -------------------------------------------------------------

def test_microbatch_rejects_zero_stage(hvd):
    with pytest.raises(ValueError, match="zero_stage"):
        hv.make_train_step(_loss, optax.sgd(0.1), zero_stage=1,
                           microbatches=2)


def test_microbatch_rejects_backward_passes_per_step(hvd):
    opt = hv.DistributedOptimizer(optax.sgd(0.1),
                                  backward_passes_per_step=2)
    with pytest.raises(ValueError, match="backward_passes_per_step"):
        hv.make_train_step(_loss, opt, microbatches=2)


def test_microbatch_rejects_adasum(hvd):
    opt = hv.DistributedOptimizer(optax.sgd(0.1), op=hv.Adasum)
    with pytest.raises(ValueError, match="Sum/Average"):
        hv.make_train_step(_loss, opt, microbatches=2)


def test_microbatch_rejects_fp8_compression(hvd):
    fp8 = getattr(hv.Compression, "fp8", None)
    if fp8 is None:
        pytest.skip("no fp8 compressor in this build")
    opt = hv.DistributedOptimizer(optax.sgd(0.1), compression=fp8)
    with pytest.raises(NotImplementedError):
        hv.make_train_step(_loss, opt, microbatches=2)


def test_microbatch_rejects_invalid_k(hvd):
    with pytest.raises(ValueError, match="microbatches"):
        hv.make_train_step(_loss, optax.sgd(0.1), microbatches=0)


def test_microbatch_rejects_indivisible_batch(hvd):
    opt = hv.DistributedOptimizer(optax.sgd(0.1))
    params = hv.replicate(_params0())
    opt_state = hv.replicate(opt.init(params))
    step = hv.make_train_step(_loss, opt, microbatches=3)
    # 32 global rows / n devices is not divisible by 3 -> trace error.
    batch = hv.shard_batch(_batch(48))  # 48/8 = 6 per device, 6 % 3 == 0
    step(params, opt_state, batch)  # divisible case traces fine
    bad = hv.shard_batch(_batch(32))  # 32/8 = 4 per device, 4 % 3 != 0
    with pytest.raises(ValueError, match="must divide"):
        step(params, opt_state, bad)


# -- env + config plumbing --------------------------------------------------

def test_microbatch_env_reaches_builders(monkeypatch):
    monkeypatch.setenv("HOROVOD_MICROBATCHES", "2")
    hv.shutdown()
    hv.init()
    try:
        assert hv.microbatches() == 2
        opt = hv.DistributedOptimizer(optax.sgd(0.1))
        params = hv.replicate(_params0())
        opt_state = hv.replicate(opt.init(params))
        step = hv.make_train_step(_loss, opt)  # k picked up from env
        batch = hv.shard_batch(_batch())
        txt = step.lower(params, opt_state, batch).as_text()
        assert emitted_collective_stats(txt).counts.get(
            "reduce-scatter", 0) == 2
    finally:
        hv.shutdown()


def test_reverse_bucket_plan_orders_last_leaves_first(hvd):
    """reverse=True walks leaves last-to-first: under autodiff the LAST
    layers' gradients are ready FIRST, so reverse bucketing lets bucket 0
    ship while earlier layers are still differentiating."""
    from horovod_tpu.controller.fusion import plan_buckets

    leaves = [np.zeros((4,), np.float32), np.zeros((8,), np.float32),
              np.zeros((1024,), np.float32)]
    fwd = plan_buckets(leaves, threshold_bytes=64)
    rev = plan_buckets(leaves, threshold_bytes=64, reverse=True)
    first_fwd = [s.index for s in fwd.buffers[0][1]]
    first_rev = [s.index for s in rev.buffers[0][1]]
    assert first_fwd[0] == 0
    assert first_rev[0] == 2  # biggest/last leaf leads the reverse plan
    # Same leaves covered overall, just different bucket order.
    cover = sorted(s.index for _, ls in rev.buffers for s in ls)
    assert cover == [0, 1, 2]


@pytest.mark.parametrize("n", (1, 2, 8, 64, 256))
def test_pad_quantum_is_mesh_size_invariant(n):
    """Every mesh size that divides 256 pads a bucket to the same length,
    so the planner's bytes are the payload's at each of them; a size that
    does not divide it pads to its own multiple."""
    from horovod_tpu.collectives.ops import microbatch_pad_quantum
    assert microbatch_pad_quantum(n) == 256
    assert microbatch_pad_quantum(3 * n) == 768
