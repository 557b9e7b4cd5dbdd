"""Core lifecycle/identity tests (parity: reference test_torch.py basics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu
from horovod_tpu.core.config import Config, load_config


def test_init_idempotent(hvd):
    assert hvd.is_initialized()
    hvd.init()  # second call is a no-op
    assert hvd.is_initialized()


def test_sizes(hvd, n_devices):
    assert hvd.size() == n_devices
    assert hvd.rank() == 0
    assert hvd.local_size() == n_devices
    assert hvd.local_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.is_homogeneous()


def test_build_probes(hvd):
    assert hvd.tpu_built()
    assert not hvd.nccl_built()
    assert not hvd.mpi_built()


def test_not_initialized_raises():
    horovod_tpu.shutdown()
    with pytest.raises(horovod_tpu.NotInitializedError):
        horovod_tpu.size()


def test_mesh_shape(hvd, n_devices):
    m = hvd.mesh()
    assert int(np.prod([m.shape[a] for a in m.axis_names])) == n_devices


def test_config_env_parsing(monkeypatch):
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("HVD_TPU_CACHE_CAPACITY", "7")
    monkeypatch.setenv("HOROVOD_LOG_LEVEL", "info")
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    cfg = load_config()
    assert cfg.fusion_threshold == 1 << 20
    assert cfg.cache_capacity == 7
    assert cfg.log_level == "info"
    assert cfg.hierarchical_allreduce


def test_hvd_tpu_env_wins(monkeypatch):
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "111")
    monkeypatch.setenv("HVD_TPU_FUSION_THRESHOLD", "222")
    assert load_config().fusion_threshold == 222


def test_hierarchical_mesh_single_process(n_devices):
    horovod_tpu.shutdown()
    horovod_tpu.init(config=Config(hierarchical_allreduce=True))
    m = horovod_tpu.mesh()
    assert m.axis_names == ("dcn", "ici")
    assert m.shape["dcn"] == 1
    assert m.shape["ici"] == n_devices
    horovod_tpu.shutdown()


def test_force_cpu_after_other_backend_raises(monkeypatch):
    """HOROVOD_FORCE_CPU only takes effect before the first backend
    exists; asked for too late it must not carry on on the accelerator."""
    horovod_tpu.shutdown()
    horovod_tpu.init(config=Config(force_cpu=True))  # CPU is up: fine
    horovod_tpu.shutdown()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already initialized"):
        horovod_tpu.init(config=Config(force_cpu=True))
    assert not horovod_tpu.is_initialized()


def test_allgather_object(hvd, n_devices):
    objs = hvd.allgather_object({"rank_data": [1, 2, 3], "s": "hello"})
    assert len(objs) == n_devices
    assert all(o == {"rank_data": [1, 2, 3], "s": "hello"} for o in objs)


def test_allgather_object_torch_shim(hvd):
    import horovod_tpu.torch as thvd
    objs = thvd.allgather_object(("x", 42))
    assert len(objs) == thvd.size()
    assert objs[0] == ("x", 42)


def test_built_probes_and_runtime_timeline(hvd, tmp_path):
    assert not hvd.cuda_built()
    assert not hvd.rocm_built()
    assert hvd.tpu_built()
    # Runtime timeline start/stop (hvd.start_timeline parity).
    for shim in ("torch_api", "tensorflow", "keras", "mxnet"):
        import importlib
        m = importlib.import_module(f"horovod_tpu.{shim}")
        assert callable(m.start_timeline) and callable(m.stop_timeline)
    path = str(tmp_path / "tl.json")
    hvd.start_timeline(path, mark_cycles=True)
    hvd.allreduce(jnp.ones((hvd.size(), 2)), hvd.Sum, name="tl_probe")
    hvd.stop_timeline()
    import json
    with open(path) as f:
        events = json.load(f)
    assert any(e.get("name", "").startswith("tl_probe")
               or "tl_probe" in str(e) for e in events), events[:5]


def test_remove_process_set_accepts_object(hvd):
    import horovod_tpu as h
    ps = h.add_process_set([0], name="rm_by_obj")
    h.remove_process_set(ps)  # reference signature: the ProcessSet itself
    ps2 = h.add_process_set([0, 1] if hvd.size() > 1 else [0],
                            name="rm_by_obj")  # re-register must succeed
    h.remove_process_set("rm_by_obj")  # name form still works


def test_tpu_pod_detection(monkeypatch):
    """Multi-host TPU slice env bootstraps identity unaided (the
    launcher-less pod path: SURVEY 4.4 mpirun-placement analogue)."""
    from horovod_tpu.core.config import (TPU_POD_COORDINATOR_PORT,
                                         detect_tpu_pod)
    for k in ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
              "HOROVOD_RANK", "HOROVOD_SIZE"):
        monkeypatch.delenv(k, raising=False)
    assert detect_tpu_pod() is None               # not on a pod

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1k-w0, t1k-w1 ,t1k-w2")
    assert detect_tpu_pod() is None               # hostnames but no id
    monkeypatch.setenv("TPU_WORKER_ID", "2")
    pod = detect_tpu_pod()
    assert pod == {"addr": "t1k-w0", "port": TPU_POD_COORDINATOR_PORT,
                   "rank": 2, "size": 3}

    cfg = load_config()
    assert cfg.coordinator_addr == "t1k-w0"
    assert cfg.coordinator_port == TPU_POD_COORDINATOR_PORT
    assert cfg.env_rank == 2 and cfg.env_size == 3
    assert cfg.env_cross_rank == 2 and cfg.env_cross_size == 3
    assert cfg.env_local_rank == 0 and cfg.env_local_size == 1

    # Single-host slice: one hostname -> no coordination needed.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1k-w0")
    assert detect_tpu_pod() is None

    # Out-of-range / non-numeric ids are rejected, not crashed on.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "a,b")
    monkeypatch.setenv("TPU_WORKER_ID", "7")
    assert detect_tpu_pod() is None
    monkeypatch.setenv("TPU_WORKER_ID", "not-a-number")
    assert detect_tpu_pod() is None


def test_tpu_pod_detection_precedence(monkeypatch):
    """Explicit launcher identity and coordinator always win; the kill
    switch disables detection outright."""
    from horovod_tpu.core.config import detect_tpu_pod
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1")
    monkeypatch.setenv("TPU_WORKER_ID", "1")

    monkeypatch.setenv("HOROVOD_RANK", "0")
    monkeypatch.setenv("HOROVOD_SIZE", "2")
    cfg = load_config()
    assert cfg.env_rank == 0 and cfg.env_size == 2   # launcher wins
    assert cfg.coordinator_addr == "w0"              # addr still derived

    monkeypatch.setenv("HVD_TPU_COORDINATOR_ADDR", "10.0.0.9")
    monkeypatch.setenv("HVD_TPU_COORDINATOR_PORT", "7777")
    cfg = load_config()
    assert cfg.coordinator_addr == "10.0.0.9"
    assert cfg.coordinator_port == 7777

    monkeypatch.delenv("HVD_TPU_COORDINATOR_ADDR")
    monkeypatch.setenv("HOROVOD_NO_TPU_POD_DETECT", "1")
    assert detect_tpu_pod() is None
    cfg = load_config()
    assert cfg.coordinator_addr is None

    # Older image spelling.
    monkeypatch.delenv("HOROVOD_NO_TPU_POD_DETECT")
    monkeypatch.delenv("TPU_WORKER_ID")
    monkeypatch.setenv("CLOUD_TPU_TASK_ID", "0")
    pod = detect_tpu_pod()
    assert pod is not None and pod["rank"] == 0
