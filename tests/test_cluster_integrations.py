"""Spark/Ray integration analogues and the MXNet shim."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Spark
# ---------------------------------------------------------------------------


def test_spark_run_requires_pyspark():
    import horovod_tpu.spark as s
    with pytest.raises(ImportError, match="pyspark"):
        s.run(lambda: None)


def test_spark_task_env_layout():
    from horovod_tpu.spark import task_env
    env = task_env(rank=3, size=8, coordinator="10.0.0.5", port=1234)
    assert env["HOROVOD_RANK"] == "3"
    assert env["HOROVOD_SIZE"] == "8"
    assert env["HVD_TPU_COORDINATOR_ADDR"] == "10.0.0.5"
    assert env["HVD_TPU_COORDINATOR_PORT"] == "1234"


def test_local_store_layout_and_io(tmp_path):
    from horovod_tpu.spark import LocalStore, Store
    store = Store.create(str(tmp_path))
    assert isinstance(store, LocalStore)
    ckpt = store.get_checkpoint_path("run1")
    assert ckpt.startswith(str(tmp_path))
    assert "run1" in ckpt
    store.write(os.path.join(ckpt, "model.bin"), b"abc")
    assert store.exists(os.path.join(ckpt, "model.bin"))
    assert store.read(os.path.join(ckpt, "model.bin")) == b"abc"
    store.delete(store.get_run_path("run1"))
    assert not store.exists(ckpt)
    assert store.get_train_data_path(2).endswith(".2")


def test_hdfs_store_raises_with_guidance(tmp_path):
    from horovod_tpu.spark import Store
    with pytest.raises(ImportError, match="hdfs"):
        Store.create("hdfs://namenode/path")
    with pytest.raises(ValueError, match="mount"):
        Store.create("s3://bucket/path")


# ---------------------------------------------------------------------------
# Ray (local backend)
# ---------------------------------------------------------------------------


def _worker_identity():
    return (os.environ["HOROVOD_RANK"], os.environ["HOROVOD_SIZE"])


def test_ray_executor_requires_start():
    from horovod_tpu.ray import RayExecutor
    ex = RayExecutor(num_workers=2, use_ray=False)
    with pytest.raises(RuntimeError, match="start"):
        ex.run(_worker_identity)


@pytest.mark.integration
def test_ray_executor_local_backend_runs_workers():
    from horovod_tpu.ray import RayExecutor
    ex = RayExecutor(num_workers=2, cpu=True, use_ray=False)
    ex.start()
    try:
        results = ex.run(_worker_identity)
    finally:
        ex.shutdown()
    assert results == [("0", "2"), ("1", "2")]


@pytest.mark.integration
def test_ray_executor_local_backend_propagates_failure():
    from horovod_tpu.ray import RayExecutor

    ex = RayExecutor(num_workers=2, cpu=True, use_ray=False)
    ex.start()
    try:
        with pytest.raises(RuntimeError, match="worker.* failed"):
            ex.run(_crashing_worker)
    finally:
        ex.shutdown()


def _crashing_worker():
    raise ValueError("boom")


# ---------------------------------------------------------------------------
# MXNet shim
# ---------------------------------------------------------------------------


def test_mxnet_identity_works_without_mxnet():
    import horovod_tpu.mxnet as m
    assert not m.nccl_built()
    assert m.tpu_built() in (True, False)


def test_mxnet_tensor_apis_raise_with_guidance():
    # Tensor APIs are real functions that bridge NDArrays when mxnet is
    # importable; without it they raise ImportError with guidance.
    import horovod_tpu.mxnet as m
    assert callable(m.allreduce)

    class FakeND:  # minimal NDArray stand-in to reach the import gate
        def asnumpy(self):
            import numpy as np
            return np.zeros(2, np.float32)

    with pytest.raises(ImportError, match="mxnet"):
        m.allreduce(FakeND())
    with pytest.raises(ImportError, match="mxnet"):
        m.DistributedOptimizer(object())
    with pytest.raises(AttributeError):
        m.not_a_real_api


# ---------------------------------------------------------------------------
# Estimators (horovod/spark estimator parity, local backend)
# ---------------------------------------------------------------------------

import numpy as np


def _blobs(n=64, d=4, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, classes, n)
    centers = rng.randn(classes, d) * 3
    x = centers[y] + rng.randn(n, d) * 0.3
    return x.astype(np.float32), y.astype(np.int64)


import flax.linen as _nn


class _FlaxMLP(_nn.Module):
    """Top-level so estimator workers can unpickle it in spawned procs."""

    @_nn.compact
    def __call__(self, x, train: bool = True):
        x = _nn.relu(_nn.Dense(16)(x))
        return _nn.Dense(3)(x)


def test_estimator_data_normalization():
    from horovod_tpu.spark.estimator import _as_arrays
    import pandas as pd
    x, y = _blobs(n=10)
    df = pd.DataFrame({"f0": x[:, 0], "f1": x[:, 1], "f2": x[:, 2],
                       "f3": x[:, 3], "label": y})
    arrays = _as_arrays(df, ["f0", "f1", "f2", "f3"], ["label"])
    assert arrays["features"].shape == (10, 4)
    assert arrays["labels"].shape == (10,)
    np.testing.assert_allclose(arrays["features"], x, rtol=1e-6)
    arrays2 = _as_arrays((x, y), None, None)
    np.testing.assert_allclose(arrays2["features"], x)


def test_write_shards_equal_sizes(tmp_path):
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import (_iter_chunks, _load_shard,
                                             _write_shards)
    x, y = _blobs(n=11)
    store = LocalStore(str(tmp_path))
    _write_shards(store, _iter_chunks({"features": x, "labels": y},
                                      None, None), 2, 0.0)
    s0 = _load_shard(store, store.get_train_data_path(0))
    s1 = _load_shard(store, store.get_train_data_path(1))
    # Equal shard sizes even when rows don't divide evenly (collective
    # step-count alignment).
    assert len(s0["features"]) == len(s1["features"]) == 5


def test_write_shards_streams_without_materializing(tmp_path):
    """SURVEY.md 3.6 (Petastorm-scale feeds): a multi-chunk source streams
    to Store shards with bounded driver memory -- no chunk ever holds the
    dataset, shards stay equal-length, and every row lands exactly once."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import (_ShardWriter, _iter_chunks,
                                             _load_shard)

    n_chunks, rows_per_chunk, num_proc = 13, 7, 3
    total = n_chunks * rows_per_chunk  # 91

    def source():
        for c in range(n_chunks):
            base = c * rows_per_chunk
            feats = np.arange(base, base + rows_per_chunk,
                              dtype=np.float32)[:, None] * [1.0, 10.0]
            labels = np.arange(base, base + rows_per_chunk, dtype=np.int32)
            yield {"features": feats, "labels": labels}

    store = LocalStore(str(tmp_path))
    w = _ShardWriter(store, num_proc, val_fraction=0.0, flush_rows=10)
    peak = 0
    for chunk in _iter_chunks(source(), None, None):
        w.add(chunk)
        peak = max(peak, sum(w.buf_rows) + w.val_rows)
    assert w.finish() == 0
    # Bounded buffering: never anywhere near the full dataset.
    assert peak < num_proc * 10 + rows_per_chunk, peak
    # Multiple chunk files per rank actually got written.
    assert all(len(store.list_prefix(
        f"{store.get_train_data_path(r)}.chunk")) > 1
        for r in range(num_proc))
    shards = [_load_shard(store, store.get_train_data_path(r))
              for r in range(num_proc)]
    target = total // num_proc  # 30 (1 ragged row trimmed)
    assert all(len(s["features"]) == target for s in shards)
    got = np.sort(np.concatenate([s["labels"] for s in shards]))
    # Every kept row appears exactly once, in round-robin assignment.
    assert len(got) == target * num_proc
    assert len(np.unique(got)) == len(got)


class _FakeRow:
    def __init__(self, d):
        self._d = d

    def asDict(self):
        return dict(self._d)


class _FakeCollected:
    def __init__(self, items):
        self._items = items

    def collect(self):
        return self._items


class _FakeRDD:
    """Executes the partition task per 'executor' (sequentially here) --
    the shape of pyspark's RDD.mapPartitionsWithIndex().collect()."""

    def __init__(self, parts):
        self.parts = parts

    def mapPartitionsWithIndex(self, fn):
        out = []
        for i, part in enumerate(self.parts):
            out.extend(fn(i, iter(part)))
        return _FakeCollected(out)


class _FakeSparkDF:
    """Spark-DataFrame stand-in: partitioned rows behind an .rdd; the
    driver-streaming path is booby-trapped so tests prove it is unused."""

    def __init__(self, parts):
        self.rdd = _FakeRDD(parts)
        self.sparkSession = object()

    def toLocalIterator(self):
        raise AssertionError("driver streaming must not be used when the "
                             "executor path is available")


def _fake_spark_blobs(n=64, n_parts=5, seed=0):
    rng = np.random.RandomState(seed)
    x, y = _blobs(n=n, d=2)
    x = x.astype(np.float64)  # Spark rows carry Python floats
    order = rng.permutation(n)
    rows = [_FakeRow({"x0": float(x[i, 0]), "x1": float(x[i, 1]),
                      "label": int(y[i])}) for i in order]
    # Deliberately unequal partitions.
    cuts = sorted(rng.choice(range(1, n), n_parts - 1, replace=False))
    parts = np.split(np.arange(n), cuts)
    return _FakeSparkDF([[rows[i] for i in p] for p in parts]), x, y


class _FakeStreamingSparkDF:
    """Spark-DataFrame stand-in for the DRIVER-STREAMING branch: exposes
    the ``toLocalIterator``/``sparkSession`` duck-type ``_iter_chunks``
    keys on, with no ``.rdd`` (no executor path to prefer).  Counts
    iterator pulls so tests can prove the driver streamed row-by-row
    instead of collecting."""

    def __init__(self, rows):
        self._rows = rows
        self.pulls = 0
        self.sparkSession = object()

    def toLocalIterator(self):
        for r in self._rows:
            self.pulls += 1
            yield r


def _fake_streaming_blobs(n=23, seed=0):
    x, y = _blobs(n=n, d=2)
    x = x.astype(np.float64)
    rows = [_FakeRow({"x0": float(x[i, 0]), "x1": float(x[i, 1]),
                      "label": int(y[i])}) for i in range(n)]
    return _FakeStreamingSparkDF(rows), x, y


def test_driver_streaming_branch_chunks_spark_rows():
    """The ``toLocalIterator`` branch of ``_iter_chunks`` buffers rows to
    ``chunk_rows`` and normalizes each buffer through pandas: 23 rows at
    chunk_rows=10 stream as chunks of 10/10/3, bitwise-preserving row
    order and values, pulling each row from the iterator exactly once."""
    from horovod_tpu.spark.estimator import _iter_chunks

    df, x, y = _fake_streaming_blobs(n=23)
    chunks = list(_iter_chunks(df, ["x0", "x1"], ["label"], chunk_rows=10))
    assert [len(c["features"]) for c in chunks] == [10, 10, 3]
    assert df.pulls == 23
    feats = np.concatenate([c["features"] for c in chunks])
    labels = np.concatenate([c["labels"] for c in chunks])
    np.testing.assert_allclose(feats, x)
    np.testing.assert_array_equal(labels, y)


def test_driver_streaming_branch_exact_chunk_boundary():
    """A row count that divides chunk_rows exactly must not emit a
    trailing empty chunk (the islice sentinel ends the loop)."""
    from horovod_tpu.spark.estimator import _iter_chunks

    df, _x, _y = _fake_streaming_blobs(n=20)
    chunks = list(_iter_chunks(df, ["x0", "x1"], ["label"], chunk_rows=10))
    assert [len(c["features"]) for c in chunks] == [10, 10]


def test_driver_streaming_materializes_shards(tmp_path):
    """End of the streaming pipe: ``_write_shards`` over the driver-
    streamed chunks produces equal-length rank shards holding every kept
    input row exactly once (the Petastorm-scale path without executors)."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import (_iter_chunks, _load_shard,
                                             _write_shards)

    df, x, _y = _fake_streaming_blobs(n=23)
    store = LocalStore(str(tmp_path))
    n_val = _write_shards(
        store, _iter_chunks(df, ["x0", "x1"], ["label"], chunk_rows=10),
        2, 0.0)
    assert n_val == 0
    shards = [_load_shard(store, store.get_train_data_path(r))
              for r in range(2)]
    assert len(shards[0]["features"]) == len(shards[1]["features"]) == 11
    rows_seen = np.concatenate([s["features"] for s in shards])
    assert len(np.unique(rows_seen, axis=0)) == len(rows_seen)
    all_rows = {tuple(r) for r in x}
    assert all(tuple(r) in all_rows for r in rows_seen)


def test_executor_parallel_materialization(tmp_path):
    """SURVEY.md 3.6 (Petastorm writes shards from Spark workers): N
    unequal partitions materialize Store shards through the partition
    tasks -- the driver never iterates rows -- with equal-length rank
    shards, every kept row exactly once, and a working val stripe."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import (_load_shard,
                                             _write_shards_on_executors)

    df, x, y = _fake_spark_blobs(n=97, n_parts=6)
    store = LocalStore(str(tmp_path))
    num_proc = 3
    val = _write_shards_on_executors(store, df, ["x0", "x1"], ["label"],
                                     num_proc, val_fraction=0.1)
    assert val is not None and 0 < val < 40
    shards = [_load_shard(store, store.get_train_data_path(r))
              for r in range(num_proc)]
    lens = [len(s["features"]) for s in shards]
    assert len(set(lens)) == 1, lens              # equal-length shards
    total_train = sum(lens)
    # Accounting: train + val <= all rows, and the equalization trim
    # loses less than one row per partition per rank.
    assert 97 - val - 6 * num_proc <= total_train <= 97 - val
    vals = _load_shard(store, store.get_val_data_path())
    # Every (feature, label) row in the shards is a real input row and no
    # train row is duplicated.
    rows_seen = np.concatenate([s["features"] for s in shards])
    assert len(np.unique(rows_seen, axis=0)) == len(rows_seen)
    all_rows = {tuple(r) for r in x}
    for r_ in rows_seen:
        assert tuple(r_) in all_rows
    for r_ in vals["features"]:
        assert tuple(r_) in all_rows


def test_executor_materialization_matches_driver_training(tmp_path):
    """End-to-end fit() through the executor path trains to the same
    quality as the driver-streamed path on the same data."""
    from horovod_tpu.spark import JaxEstimator, LocalStore

    df, x, y = _fake_spark_blobs(n=64, n_parts=4)
    est = JaxEstimator(model=_FlaxMLP(), loss="xent", lr=0.05,
                       num_proc=2, batch_size=8, epochs=12,
                       feature_cols=["x0", "x1"], label_cols=["label"],
                       store=LocalStore(str(tmp_path)))
    fitted = est.fit(df)     # _FakeSparkDF raises if the driver streams
    assert fitted.history[-1] < fitted.history[0]
    preds = fitted.transform(x).argmax(-1)
    assert (preds == y).mean() > 0.8


def test_executor_val_hash_mixes_partition_id(tmp_path):
    """Regression: a high-bit-shifted partition key vanishes under the
    32-bit hash mask, sending every partition's FIRST row to validation
    and reusing one per-ordinal pattern across partitions.  With a tiny
    fraction, far fewer than one row per partition must be selected."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import _write_shards_on_executors

    df, _x, _y = _fake_spark_blobs(n=97, n_parts=6)
    store = LocalStore(str(tmp_path))
    val = _write_shards_on_executors(store, df, ["x0", "x1"], ["label"],
                                     2, val_fraction=0.01)
    assert val < 6  # old bug: >= one per partition, always


def test_executor_materialization_rejects_empty_shard(tmp_path):
    """More ranks than the partition layout can feed -> loud error, not
    shards trimmed to zero."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import _write_shards_on_executors

    rows = [_FakeRow({"x0": 1.0, "x1": 2.0, "label": 0}) for _ in range(3)]
    df = _FakeSparkDF([rows[:2], rows[2:]])
    with pytest.raises(ValueError, match="zero rows"):
        _write_shards_on_executors(LocalStore(str(tmp_path)), df,
                                   ["x0", "x1"], ["label"], 3, 0.0)


def test_executor_materialization_requires_writable_store(tmp_path):
    """A store the executors cannot write falls back (returns None)."""
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import _write_shards_on_executors

    df, _x, _y = _fake_spark_blobs(n=16, n_parts=2)
    store = LocalStore(str(tmp_path))
    store.executor_writable = False
    assert _write_shards_on_executors(store, df, ["x0", "x1"], ["label"],
                                      2, 0.0) is None
    # And a plain dict input has no RDD: also None.
    writable = LocalStore(str(tmp_path))
    assert _write_shards_on_executors(
        writable, {"features": _x, "labels": _y}, None, None, 2, 0.0) is None


def test_write_shards_validation_stripe(tmp_path):
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.estimator import (_iter_chunks, _load_shard,
                                             _write_shards)
    x = np.arange(2000, dtype=np.float32)[:, None]
    y = np.arange(2000, dtype=np.int32)
    store = LocalStore(str(tmp_path))
    n_val = _write_shards(store, _iter_chunks((x, y), None, None), 2, 0.1)
    # Hash-based selection: ~10% of 2000 rows (deterministic, not exact).
    assert 140 <= n_val <= 260, n_val
    val = _load_shard(store, store.get_val_data_path())
    assert len(val["features"]) == n_val
    train = [_load_shard(store, store.get_train_data_path(r))
             for r in range(2)]
    n_train = (2000 - n_val) // 2
    assert len(train[0]["features"]) == len(train[1]["features"]) == n_train
    # No row is in both train and val.
    overlap = set(val["labels"].tolist()) & set(
        np.concatenate([t["labels"] for t in train]).tolist())
    assert not overlap


@pytest.mark.integration
def test_jax_estimator_fit_transform(tmp_path):
    from horovod_tpu.spark import JaxEstimator, LocalStore
    x, y = _blobs(n=64)
    est = JaxEstimator(model=_FlaxMLP(), loss="xent", lr=0.05,
                       num_proc=2, batch_size=8, epochs=12,
                       store=LocalStore(str(tmp_path)))
    fitted = est.fit({"features": x, "labels": y})
    assert fitted.history[-1] < fitted.history[0]
    preds = fitted.transform(x).argmax(-1)
    assert (preds == y).mean() > 0.8


class _TorchMLP(__import__("torch").nn.Module):
    def __init__(self):
        import torch
        super().__init__()
        self.net = torch.nn.Sequential(
            torch.nn.Linear(4, 16), torch.nn.ReLU(), torch.nn.Linear(16, 3))

    def forward(self, x):
        return self.net(x)


@pytest.mark.integration
def test_torch_estimator_fit_transform(tmp_path):
    from horovod_tpu.spark import LocalStore, TorchEstimator
    x, y = _blobs(n=64)
    est = TorchEstimator(model=_TorchMLP(), loss="xent", lr=0.05,
                         num_proc=2, batch_size=8, epochs=12,
                         store=LocalStore(str(tmp_path)))
    fitted = est.fit({"features": x, "labels": y})
    assert fitted.history[-1] < fitted.history[0]
    preds = fitted.transform(x).argmax(-1)
    assert (preds == y).mean() > 0.8


@pytest.mark.integration
def test_keras_estimator_fit_transform(tmp_path):
    import tensorflow as tf
    from horovod_tpu.spark import KerasEstimator, LocalStore
    x, y = _blobs(n=64)
    model = tf.keras.Sequential([
        tf.keras.layers.Input((4,)),
        tf.keras.layers.Dense(16, activation="relu"),
        # softmax: the keras loss string defaults to from_logits=False
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    est = KerasEstimator(model=model,
                         loss="sparse_categorical_crossentropy",
                         lr=0.05, num_proc=2, batch_size=8, epochs=12,
                         store=LocalStore(str(tmp_path)))
    fitted = est.fit({"features": x, "labels": y})
    assert fitted.history[-1] < fitted.history[0]
    preds = fitted.transform(x).argmax(-1)
    assert (preds == y).mean() > 0.8


# ---------------------------------------------------------------------------
# Elastic Ray executor
# ---------------------------------------------------------------------------


def _elastic_fn(target):
    """Elastic payload: allreduce a counter `target` times, committing
    each batch (mirrors examples/elastic_train.py at function scope)."""
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu import elastic

    hvd.init()

    @elastic.run
    def train(state):
        opt = hvd.DistributedOptimizer(optax.sgd(0.01))
        step_fn = hvd.make_train_step(
            lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2), opt)
        import jax
        params = hvd.replicate(jax.tree.map(jnp.asarray, state.params))
        opt_state = opt.init(params)
        n = hvd.size()
        while state.batch < target:
            batch = hvd.shard_batch((jnp.ones((2 * n, 4)),
                                     jnp.zeros((2 * n, 4))))
            params, opt_state, _ = step_fn(params, opt_state, batch)
            state.params = jax.device_get(params)
            state.batch += 1
            state.commit()
        return state.batch

    state = elastic.JaxState(
        params={"w": jnp.zeros((4, 4), jnp.float32)}, batch=0)
    done = train(state)
    import horovod_tpu as hvd2
    return {"rank": hvd2.rank(), "size": hvd2.size(), "batches": done}


def test_elastic_ray_executor_requires_source_without_ray():
    from horovod_tpu.ray import ElasticRayExecutor
    try:
        import ray  # noqa: F401
        pytest.skip("ray installed; the no-source error path is not hit")
    except ImportError:
        pass
    ex = ElasticRayExecutor(min_workers=1)
    with pytest.raises(ImportError, match="host_file"):
        ex.run(_elastic_fn, args=(1,))


@pytest.mark.integration
def test_elastic_ray_executor_runs_function(tmp_path):
    from horovod_tpu.ray import ElasticRayExecutor
    hosts = tmp_path / "hosts.txt"
    hosts.write_text("a\nb\n")
    ex = ElasticRayExecutor(min_workers=2, cpu=True,
                            host_file=str(hosts))
    results = ex.run(_elastic_fn, args=(6,))
    assert len(results) == 2
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["batches"] == 6 and r["size"] == 2 for r in results)


class _LightningStyleMLP(__import__("torch").nn.Module):
    """LightningModule protocol without the pytorch_lightning dependency."""

    def __init__(self):
        import torch
        super().__init__()
        self.net = torch.nn.Sequential(
            torch.nn.Linear(4, 16), torch.nn.ReLU(), torch.nn.Linear(16, 3))

    def forward(self, x):
        return self.net(x)

    def training_step(self, batch, batch_idx):
        import torch
        x, y = batch
        return {"loss": torch.nn.functional.cross_entropy(self(x), y)}

    def configure_optimizers(self):
        import torch
        return torch.optim.Adam(self.parameters(), lr=0.05)


def test_lightning_estimator_rejects_plain_module():
    from horovod_tpu.spark import LightningEstimator
    with pytest.raises(TypeError, match="training_step"):
        LightningEstimator(model=_TorchMLP())


@pytest.mark.integration
def test_lightning_estimator_fit_transform(tmp_path):
    from horovod_tpu.spark import LightningEstimator, LocalStore
    x, y = _blobs(n=64)
    est = LightningEstimator(model=_LightningStyleMLP(), num_proc=2,
                             batch_size=8, epochs=12,
                             store=LocalStore(str(tmp_path)))
    fitted = est.fit({"features": x, "labels": y})
    assert fitted.history[-1] < fitted.history[0]
    preds = fitted.transform(x).argmax(-1)
    assert (preds == y).mean() > 0.8
