"""The elementwise gradient exchange builds no fusion buffer (PR 27).

Where the exchange is elementwise (``Sum`` or ``Average`` under
``none``/``fp16``/``bf16`` on the flat exchange) every leaf is cast,
reduced by a psum of its own and cast back: nothing is raveled,
concatenated, sliced or reshaped, and XLA's all-reduce combiner groups the
psums.  These tests hold the leaf-wise path to the packed path's bits,
show that the lowered program carries no pack and does not change with
the fusion threshold, and that every exchange that needs one contiguous
vector still packs.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hv
from horovod_tpu.collectives import ops as _ops
from horovod_tpu.controller import fusion
from horovod_tpu.optim import distributed as _dist
from horovod_tpu.timeline import metrics as _metrics

THRESHOLD = 256     # bytes: splits the tree below into several buckets

CODECS = {"none": hv.Compression.none, "fp16": hv.Compression.fp16,
          "bf16": hv.Compression.bf16}


@pytest.fixture(params=[8, 4], ids=["mesh8", "mesh4"])
def world(request):
    hv.shutdown()
    hv.init(devices=jax.devices()[:request.param])
    yield request.param
    hv.shutdown()


def _tree(n: int):
    """Per-rank gradients (leading axis = rank): mixed dtypes, a matrix
    larger than a bucket, a scalar, an integer leaf, a leaf that is
    already narrower than the wire, an empty leaf at a bucket's edge."""
    rng = np.random.RandomState(7)
    return {
        "a": rng.randn(n, 33, 7).astype(np.float32),
        "b": rng.randn(n, 5).astype(np.float32),
        "e": np.zeros((n, 0), np.float32),
        "h": rng.randn(n, 9).astype(np.float16),
        "i": rng.randint(-5, 5, (n, 4)).astype(np.int32),
        "s": rng.randn(n).astype(np.float32),
        "w": rng.randn(n, 16, 8).astype(np.float32),
    }


def _on_mesh(fn, tree):
    mesh = hv.mesh()
    return jax.jit(jax.shard_map(
        lambda t: fn(jax.tree.map(lambda x: x[0], t)), mesh=mesh,
        in_specs=P(mesh.axis_names), out_specs=P(), check_vma=False))(tree)


def _packed_reference(comp, op, pre, post):
    """The packed path written out: ravel + concatenate a bucket, cast the
    buffer, one psum, cast back, slice and reshape."""
    def exchange(tree):
        leaves, treedef = jax.tree.flatten(tree)
        spec = fusion.plan_buckets(leaves, THRESHOLD)
        reduced = []
        for buf in fusion.pack(leaves, spec):
            c, ctx = comp.compress(buf)
            r = _ops.allreduce(c, op, prescale_factor=pre,
                               postscale_factor=post)
            reduced.append(comp.decompress(r, ctx))
        return jax.tree.unflatten(treedef, fusion.unpack(reduced, spec))
    return exchange


@pytest.mark.parametrize("scales", [(1.0, 1.0), (0.5, 1.0), (1.0, 3.0)],
                         ids=["unscaled", "prescale", "postscale"])
@pytest.mark.parametrize("op", [hv.Sum, hv.Average], ids=["sum", "average"])
@pytest.mark.parametrize("codec", sorted(CODECS))
def test_reduced_tree_is_bitwise_the_packed_paths(world, codec, op, scales):
    comp = CODECS[codec]
    pre, post = scales
    tree = _tree(world)
    assert not _dist.exchange_packs(comp, op)
    got = _on_mesh(lambda t: hv.allreduce_gradients(
        t, op, compression=comp, fusion_threshold=THRESHOLD,
        prescale_factor=pre, postscale_factor=post), tree)
    want = _on_mesh(_packed_reference(comp, op, pre, post), tree)
    for key in tree:
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype == tree[key].dtype, key
        assert g.shape == w.shape == tree[key].shape[1:], key
        assert g.tobytes() == w.tobytes(), key


def test_leafwise_exchange_notes_a_flat_leg_a_leaf(world):
    """The trace-time leg registry: one ``flat_ar`` row a leaf, and the
    bytes add up to what the plan's buckets hold on the wire (the step
    report's wire bytes did not move with the buffers)."""
    from horovod_tpu.timeline import spans
    tree = _tree(world)
    rec = spans.recorder()
    before = dict(rec.legs.get("flat_ar", {"nbytes": 0, "buckets": 0}))
    _on_mesh(lambda t: hv.allreduce_gradients(
        t, hv.Average, compression=hv.Compression.fp16,
        fusion_threshold=THRESHOLD), tree)
    after = rec.legs["flat_ar"]
    spec = fusion.plan_buckets([v[0] for v in tree.values()], THRESHOLD)
    planned = sum(
        fusion.plan_exchange("flat", size=sum(s.size for s in lspecs),
                             dtype=str(jnp.dtype(dt)),
                             compression="fp16").legs[0].nbytes
        for dt, lspecs in spec.buffers)
    assert after["nbytes"] - before["nbytes"] == planned
    assert after["buckets"] - before["buckets"] == len(tree)


_NEEDS_VECTOR = {
    # (compression, op, two_level, chunked) -> packs
    "none-sum": ("none", hv.Sum, False, False, False),
    "none-average": (None, hv.Average, False, False, False),
    "fp16-average": ("fp16", hv.Average, False, False, False),
    "bf16-sum": ("bf16", hv.Sum, False, False, False),
    "fp16-two-level": ("fp16", hv.Average, True, False, True),
    "fp16-chunked": ("fp16", hv.Average, False, True, True),
    "fp16-adasum": ("fp16", hv.Adasum, False, False, True),
    "none-max": ("none", hv.Max, False, False, True),
    "fp8": ("fp8", hv.Average, False, False, True),
    "powersgd": ("powersgd:2", hv.Average, False, False, True),
    "topk": ("topk:0.25", hv.Sum, False, False, True),
    "per-leg": ("ici:fp16,dcn:none", hv.Average, False, False, True),
}


@pytest.mark.parametrize("case", sorted(_NEEDS_VECTOR))
def test_exchange_needs_vector(case):
    """The one rule that routes the exchange, the auditor's contract,
    ``explain_plan``'s column and the step report."""
    from horovod_tpu.collectives.compression import parse_compression
    comp, op, two_level, chunked, want = _NEEDS_VECTOR[case]
    comp = parse_compression(comp) if comp is not None else None
    assert fusion.exchange_needs_vector(
        comp, op, two_level=two_level, chunked=chunked) is want


def _train_step_text(compression, **opt_kwargs):
    rng = np.random.RandomState(0)
    p0 = {"w": rng.randn(16, 4).astype(np.float32),
          "v": rng.randn(4, 4).astype(np.float32),
          "b": np.zeros((4,), np.float32)}
    opt = hv.DistributedOptimizer(optax.sgd(0.05), compression=compression,
                                  fusion_threshold=THRESHOLD, **opt_kwargs)
    step = hv.make_train_step(
        lambda p, x: jnp.mean(((x @ p["w"]) @ p["v"] + p["b"]) ** 2), opt)
    x = hv.shard_batch(np.asarray(rng.randn(hv.size() * 2, 16), np.float32))
    return step.lower(hv.replicate(p0), hv.replicate(opt.init(p0)),
                      x).as_text(debug_info=True), p0, opt


def _exchange_ops(text: str):
    """Names of the ops the lowered text holds under an ``hvd_exchange/``
    scope (``debug_info`` names every op ``<scope>/<primitive>``)."""
    return set(re.findall(r'loc\("(hvd_exchange/[^"]*)"', text))


_COPIES = ("concatenate", "slice", "reshape", "dynamic_update_slice")


def test_lowered_step_has_a_psum_a_leaf_and_no_concatenate(world):
    """One ``all_reduce`` a leaf in the lowered text, and one for the loss
    (XLA's combiner makes the many-operand all-reduces,
    ``tests/test_scaling.py`` holds that on the v5e), and nothing under
    ``hvd_exchange/`` concatenates, slices or reshapes."""
    text, p0, _ = _train_step_text("fp16")
    assert text.count('"stablehlo.all_reduce"') == len(p0) + 1
    ops = _exchange_ops(text)
    assert {"hvd_exchange/compress/convert_element_type",
            "hvd_exchange/collective/psum",
            "hvd_exchange/decompress/convert_element_type"} <= ops
    assert not [o for o in ops if o.rsplit("/", 1)[-1] in _COPIES], ops
    assert not [o for o in ops if "pack" in o], ops


def test_fusion_threshold_does_not_reach_the_leafwise_step(world):
    """The leaf-wise step is the same program at every threshold (XLA's
    combiner, not ``HOROVOD_FUSION_THRESHOLD``, draws its all-reduces);
    a packed step is not."""
    def text(compression, threshold):
        rng = np.random.RandomState(0)
        p0 = {"w": rng.randn(16, 4).astype(np.float32),
              "v": rng.randn(4, 4).astype(np.float32)}
        opt = hv.DistributedOptimizer(optax.sgd(0.05),
                                      compression=compression,
                                      fusion_threshold=threshold)
        step = hv.make_train_step(
            lambda p, x: jnp.mean(((x @ p["w"]) @ p["v"]) ** 2), opt)
        x = hv.shard_batch(
            np.asarray(rng.randn(hv.size() * 2, 16), np.float32))
        return step.lower(hv.replicate(p0), hv.replicate(opt.init(p0)),
                          x).as_text()
    assert text("fp16", 64) == text("fp16", 1 << 20)
    assert text("fp8", 64) != text("fp8", 1 << 20)


_PACKED_CASES = {
    "powersgd": dict(compression="powersgd:2"),
    "topk": dict(compression="topk:0.25"),
    "fp8": dict(compression="fp8"),
    "adasum": dict(compression="fp16", op=hv.Adasum),
    "chunked": dict(compression="fp16",
                    env={"HOROVOD_EXCHANGE_CHUNK_MB": "1"}),
    "hierarchical": dict(compression="fp16",
                         env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"},
                         hierarchical=True),
}


@pytest.mark.parametrize("case", sorted(_PACKED_CASES))
def test_exchanges_that_need_a_vector_still_pack(monkeypatch, case):
    spec = dict(_PACKED_CASES[case])
    for key, value in spec.pop("env", {}).items():
        monkeypatch.setenv(key, value)
    hier = spec.pop("hierarchical", False)
    hv.shutdown()
    if hier:
        from horovod_tpu.parallel.mesh import build_mesh
        hv.init(mesh=build_mesh(jax.devices()[:8], hierarchical=True,
                                dcn_size=2))
    else:
        hv.init(devices=jax.devices()[:4])
    try:
        comp = spec["compression"]
        op = spec.get("op", hv.Average)
        assert _dist.exchange_packs(comp, op)
        text, p0, opt = _train_step_text(comp, **(
            {"op": op} if "op" in spec else {}))
        # Each bucket is concatenated into one flat buffer ...
        # (the error-feedback exchange packs outside the named scopes).
        assert "stablehlo.concatenate" in text
        if case not in ("powersgd", "topk"):
            assert "hvd_exchange/pack/concatenate" in _exchange_ops(text)
        # ... and the step report counts every gradient byte as packed.
        from horovod_tpu.training import _step_exchange_accounting
        raw = sum(v.size * v.dtype.itemsize for v in p0.values())
        meta = {"optimizer": opt, "world": hv.size(), "microbatches": 1}
        assert _step_exchange_accounting(p0, meta)[3] == raw
        rows = fusion.explain_plan(p0, threshold_bytes=THRESHOLD,
                                   compression=comp, register=False)
        if op is not hv.Adasum:   # explain_plan prices Sum/Average
            assert all(r["packed"] for r in rows)
    finally:
        hv.shutdown()


@pytest.mark.parametrize("compression, world_packs", [
    ("fp16", False), ("none", False), ("powersgd:2", True),
], ids=["fp16", "none", "powersgd"])
def test_step_report_counts_packed_bytes(hvd, compression, world_packs):
    """``StepReport.packed_bytes`` and its gauge: 0 where buckets ride as
    groups of leaves, every gradient byte where the exchange packs; the
    wire bytes do not depend on it."""
    rng = np.random.RandomState(0)
    p0 = {"w": rng.randn(16, 4).astype(np.float32),
          "b": np.zeros((4,), np.float32)}
    opt = hvd.DistributedOptimizer(optax.sgd(0.05), compression=compression)
    step = hvd.make_train_step(
        lambda p, x: jnp.mean((x @ p["w"] + p["b"]) ** 2), opt)
    x = hvd.shard_batch(np.asarray(rng.randn(16, 16), np.float32))
    step(hvd.replicate(p0), hvd.replicate(opt.init(p0)), x)
    rep = _metrics.last_step_report()
    raw = sum(v.size * v.dtype.itemsize for v in p0.values())
    assert rep.uncompressed_bytes == raw
    assert rep.packed_bytes == (raw if world_packs else 0)
    snap = hvd.metrics_snapshot()
    assert snap["horovod_packed_bytes_per_step"]["value"] == \
        rep.packed_bytes
    rows = fusion.explain_plan(p0, compression=compression, register=False)
    assert [r["packed"] for r in rows] == [world_packs] * len(rows)
    table = fusion.render_plan(rows)
    assert "packed" in table.splitlines()[0]
    assert str(world_packs) in table.splitlines()[2]


def test_packed_bytes_is_zero_on_one_device():
    """World 1 bypasses the planner (leaf-wise identity psums)."""
    hv.shutdown()
    hv.init(devices=jax.devices()[:1])
    try:
        from horovod_tpu.training import _step_exchange_accounting
        p0 = {"w": np.ones((16, 4), np.float32)}
        opt = hv.DistributedOptimizer(optax.sgd(0.05),
                                      compression="ici:fp16,dcn:none")
        meta = {"optimizer": opt, "world": 1, "microbatches": 1}
        codec, wire, raw, packed = _step_exchange_accounting(p0, meta)
        assert (raw, packed) == (256, 0)
    finally:
        hv.shutdown()
