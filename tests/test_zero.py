"""ZeRO-1 sharded optimizer path (``optim/zero.py`` + ``zero_stage=1``).

Parity contract: a zero1 step must produce the same parameters as the
replicated DistributedOptimizer step -- the reduce-scattered gradient
shards ARE the allreduced gradient, sliced, and the compressed allgather
reconstructs every replica from the same wire bytes.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hv
from horovod_tpu.optim import zero as zero_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Arena plan: pure shape arithmetic, no mesh needed.
# ---------------------------------------------------------------------------

def test_arena_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    leaves = [jnp.asarray(rng.randn(4, 5), jnp.float32),      # 20
              jnp.asarray(rng.randn(7), jnp.bfloat16),        # 7
              jnp.asarray(rng.randint(0, 9, (3,)), jnp.int32),  # 3
              jnp.asarray(rng.randn(13), jnp.float32)]        # 13
    spec = zero_mod.plan_arena(leaves, world=8)
    arenas = zero_mod.arena_pack(leaves, spec)
    assert len(arenas) == 3  # f32, bf16, i32
    for arena, buf in zip(arenas, spec.buffers):
        assert arena.shape == (buf.padded,)
        assert buf.padded % 8 == 0 and buf.shard * 8 == buf.padded
        assert buf.padded >= buf.size
    out = zero_mod.arena_unpack(arenas, spec)
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_arena_padding_is_minimal():
    leaves = [jnp.zeros((33,), jnp.float32)]
    spec = zero_mod.plan_arena(leaves, world=8)
    (buf,) = spec.buffers
    assert (buf.size, buf.padded, buf.shard) == (33, 40, 5)


# ---------------------------------------------------------------------------
# In-process parity on the 8-device CPU mesh.
# ---------------------------------------------------------------------------

_BASE = {
    "w": np.random.RandomState(0).randn(4, 5).astype(np.float32),
    "b": np.random.RandomState(1).randn(7).astype(np.float32),
    "half": np.random.RandomState(2).randn(13).astype(np.float32),
}


def _fresh_params():
    """Uneven leaf sizes (20+7 f32 -> padded, 13 bf16 -> padded)."""
    return {"w": jnp.asarray(_BASE["w"]), "b": jnp.asarray(_BASE["b"]),
            "half": jnp.asarray(_BASE["half"], jnp.bfloat16)}


def _loss(p, batch):
    x, y = batch
    pred = ((x @ p["w"]).sum(-1) + p["b"].sum()
            + p["half"].astype(jnp.float32).sum())
    return jnp.mean((pred - y) ** 2)


def _run_steps(step, params, state, steps=6, frozen=None):
    rng = np.random.RandomState(42)
    losses = []
    for _ in range(steps):
        x = jnp.asarray(rng.randn(16, 4), jnp.float32)
        y = jnp.asarray(rng.randn(16), jnp.float32)
        batch = (hv.shard_batch(x), hv.shard_batch(y))
        args = (params, state, batch) + (() if frozen is None else (frozen,))
        params, state, loss = step(*args)
        losses.append(float(loss))
    return params, state, losses


def _assert_params_close(a_tree, b_tree, f32_atol=5e-5, bf16_atol=5e-2):
    for k in a_tree:
        a = np.asarray(a_tree[k], np.float32)
        b = np.asarray(b_tree[k], np.float32)
        atol = bf16_atol if a_tree[k].dtype == jnp.bfloat16 else f32_atol
        np.testing.assert_allclose(a, b, atol=atol, err_msg=k)


def test_zero1_matches_replicated_adam_uneven(hvd):
    opt = optax.adam(1e-2)
    rep_step = hv.make_train_step(_loss, hv.DistributedOptimizer(opt))
    rep_params, rep_state, rep_losses = _run_steps(
        rep_step, _fresh_params(), opt.init(_fresh_params()))

    z_step = hv.make_train_step(_loss, opt, zero_stage=1)
    z0 = _fresh_params()
    z_params, z_state, z_losses = _run_steps(
        z_step, z0, hv.zero_init(opt, z0))

    np.testing.assert_allclose(rep_losses, z_losses, rtol=1e-5)
    _assert_params_close(rep_params, z_params)
    # Sharded-state layout contract: leading [n, ...] axis over the mesh.
    n = hv.size()
    for leaf in jax.tree.leaves(z_state):
        assert leaf.shape[0] == n


def test_zero1_with_frozen_matches_replicated(hvd):
    """LoRA layout: frozen tree replicated + undifferentiated; the zero
    arena spans only the trainable params."""
    frozen = {"base": jnp.asarray(
        np.random.RandomState(7).randn(4).astype(np.float32))}

    def loss(p, fz, batch):
        x, y = batch
        pred = ((x @ p["w"]).sum(-1) + p["b"].sum()
                + p["half"].astype(jnp.float32).sum()
                + (x @ fz["base"]))
        return jnp.mean((pred - y) ** 2)

    opt = optax.adam(1e-2)
    rep_step = hv.make_train_step(loss, hv.DistributedOptimizer(opt),
                                  with_frozen=True)
    rep_params, _, rep_losses = _run_steps(
        rep_step, _fresh_params(), opt.init(_fresh_params()), frozen=frozen)

    z_step = hv.make_train_step(loss, opt, with_frozen=True, zero_stage=1)
    z0 = _fresh_params()
    z_params, _, z_losses = _run_steps(
        z_step, z0, hv.zero_init(opt, z0), frozen=frozen)

    np.testing.assert_allclose(rep_losses, z_losses, rtol=1e-5)
    _assert_params_close(rep_params, z_params)


def test_zero1_fp16_compressed_gather_close(hvd):
    """fp16-wire allgather: params carry fp16 rounding, bounded drift."""
    opt = optax.sgd(1e-2)
    rep_step = hv.make_train_step(_loss, hv.DistributedOptimizer(opt))
    rep_params, _, _ = _run_steps(rep_step, _fresh_params(),
                                  opt.init(_fresh_params()))

    z_step = hv.make_train_step(_loss, opt, zero_stage=1,
                                zero_compression=hv.Compression.fp16)
    z0 = _fresh_params()
    z_params, _, z_losses = _run_steps(z_step, z0, hv.zero_init(opt, z0))

    assert all(np.isfinite(z_losses))
    _assert_params_close(rep_params, z_params, f32_atol=2e-2, bf16_atol=5e-2)


def test_zero1_fp8_compressed_gather_runs(hvd):
    """fp8 gather: e4m3 wire + per-shard scale; replicas must agree and
    training must stay finite (values are coarsely quantized)."""
    opt = optax.sgd(1e-2)
    z_step = hv.make_train_step(_loss, opt, zero_stage=1,
                                zero_compression=hv.Compression.fp8)
    z0 = _fresh_params()
    z_params, _, z_losses = _run_steps(z_step, z0, hv.zero_init(opt, z0),
                                       steps=3)
    assert all(np.isfinite(z_losses))
    for leaf in jax.tree.leaves(z_params):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_zero1_rejects_distributed_optimizer(hvd):
    opt = hv.DistributedOptimizer(optax.adam(1e-2))
    with pytest.raises(ValueError, match="bare optax optimizer"):
        hv.make_train_step(_loss, opt, zero_stage=1)
    with pytest.raises(ValueError, match="bare optax optimizer"):
        hv.zero_init(opt, _fresh_params())
    with pytest.raises(ValueError, match="zero_stage must be 0 or 1"):
        hv.make_train_step(_loss, optax.adam(1e-2), zero_stage=2)


def test_zero_stage_env_default(hvd, monkeypatch):
    """HOROVOD_ZERO=1 makes zero the default for steps built without an
    explicit zero_stage argument."""
    hv.shutdown()
    monkeypatch.setenv("HOROVOD_ZERO", "1")
    hv.init()
    from horovod_tpu.core.state import global_state
    assert global_state().config.zero_stage == 1
    from horovod_tpu.training import _resolve_zero_stage
    assert _resolve_zero_stage(None) == 1
    assert _resolve_zero_stage(0) == 0


def test_zero_report_accounting():
    params = {"w": jnp.zeros((4, 5), jnp.float32),
              "b": jnp.zeros((7,), jnp.float32),
              "half": jnp.zeros((13,), jnp.bfloat16)}
    opt = optax.adam(1e-2)
    rep = hv.zero_report(opt, params, world=8)
    # Uncompressed RS+AG moves exactly one ring allreduce of bytes.
    assert rep["zero1_exchanged_bytes_per_chip"] == \
        rep["replicated_allreduce_bytes_per_chip"]
    # Opt-state HBM shrinks by ~world (padding + the scalar count leaf
    # keep it from being exactly /8).
    assert rep["opt_state_bytes_per_chip_zero1"] * 4 < \
        rep["opt_state_bytes_per_chip_replicated"]

    fp16 = hv.zero_report(opt, params, world=8,
                          compression=hv.Compression.fp16)
    assert fp16["allgather_bytes_per_chip"] < \
        fp16["reducescatter_bytes_per_chip"]
    assert fp16["zero1_exchanged_bytes_per_chip"] < \
        fp16["replicated_allreduce_bytes_per_chip"]

    # fp8: e4m3 wire beats the fp16 wire once the arena outweighs the
    # per-shard f32 scales (tiny toy arenas are dominated by the scales).
    big = {"w": jnp.zeros((256, 256), jnp.float32)}
    fp16_big = hv.zero_report(opt, big, world=8,
                              compression=hv.Compression.fp16)
    fp8_big = hv.zero_report(opt, big, world=8,
                             compression=hv.Compression.fp8)
    assert fp8_big["allgather_bytes_per_chip"] < \
        fp16_big["allgather_bytes_per_chip"]


# ---------------------------------------------------------------------------
# Multi-process CPU-mesh parity (the acceptance gate: 2 and 4 ranks).
# ---------------------------------------------------------------------------

@pytest.mark.integration
@pytest.mark.parametrize("nproc", [2, 4])
def test_zero1_parity_multiprocess(nproc):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", str(nproc),
         "--cpu", sys.executable,
         os.path.join(REPO, "tests", "zero_parity_worker.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ZERO PARITY OK" in out.stdout
