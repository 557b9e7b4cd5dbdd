"""Core lifecycle + identity API: ``init/shutdown/rank/size/...``.

TPU-native analogue of the reference's ctypes surface
(``horovod/common/basics.py::HorovodBasics`` -> ``horovod/common/operations.cc``
C API).  The reference's ``InitializeHorovodOnce`` spawns a background
coordinator thread and boots MPI/Gloo; here ``init()`` (optionally) boots
the JAX distributed runtime, builds the communicator :class:`Mesh` over the
ICI/DCN fabric and registers the global process set.  No background thread
exists -- SPMD makes runtime tensor negotiation unnecessary.

Rank semantics under SPMD (documented divergence from the reference, where
one process == one GPU == one rank):

* ``size()``   -- total number of *devices* (data-parallel workers).
* ``rank()``   -- this controller process's index (``jax.process_index()``).
  In the launcher's one-device-per-process mode this equals the Horovod
  rank exactly; in single-process multi-device mode it is 0 and per-device
  identity is available in-step via ``axis_index()``.
* ``local_rank()/local_size()`` -- position among processes on this host /
  devices owned by this process.
* ``cross_rank()/cross_size()`` -- host (slice) index / count.
"""

from __future__ import annotations

import atexit
import logging
from typing import Optional, Sequence

import jax

from .config import Config, load_config
from .exceptions import NotInitializedError
from .state import global_state
from . import process_sets as _ps
from ..parallel import mesh as _mesh
from ..utils import platform as _platform

logger = logging.getLogger("horovod_tpu")


def _setup_logging(level: str, hide_timestamp: bool = False) -> None:
    lvl = {"trace": logging.DEBUG, "debug": logging.DEBUG,
           "info": logging.INFO, "warning": logging.WARNING,
           "error": logging.ERROR, "fatal": logging.CRITICAL}.get(
               level.lower(), logging.WARNING)
    # HOROVOD_LOG_HIDE_TIMESTAMP parity (reference logging.cc):
    # timestamps on by default, hideable via the parsed config.
    fmt = "%(name)s %(levelname)s: %(message)s" if hide_timestamp else \
        "%(asctime)s %(name)s %(levelname)s: %(message)s"
    logging.basicConfig(level=lvl, format=fmt)
    logger.setLevel(lvl)


def init(
    devices: Optional[Sequence[jax.Device]] = None,
    hierarchical: Optional[bool] = None,
    process_sets: Optional[Sequence[Sequence[int]]] = None,
    config: Optional[Config] = None,
    mesh=None,
) -> None:
    """Initialize the framework (``hvd.init()`` parity).

    Args:
      devices: devices forming the world communicator; default all devices.
      hierarchical: force the 2-D ``(dcn, ici)`` mesh; default: on when
        multiple processes are present or ``HOROVOD_HIERARCHICAL_ALLREDUCE``
        is set.
      process_sets: extra process sets to register, as lists of ranks
        (``hvd.init(process_sets=...)`` parity).
      config: explicit config (tests); default: parsed from environment.
    """
    st = global_state()
    with st.lock:
        if st.initialized:
            return
        cfg = config if config is not None else load_config()
        _setup_logging(cfg.log_level, cfg.log_hide_timestamp)

        if cfg.force_cpu:
            # Only takes effect before the first backend exists; a
            # worker told to run on the CPU must not carry on on
            # whatever other backend is already up.
            if not _platform.backend_initialized():
                jax.config.update("jax_platforms", "cpu")
            elif jax.default_backend() != "cpu":
                raise RuntimeError(
                    "HOROVOD_FORCE_CPU=1 but the jax "
                    f"{jax.default_backend()} backend is already "
                    "initialized; call hvd.init() before the first jax "
                    "device use")

        _platform.configure_compile_cache()

        # Multi-process bootstrap: the launcher hands us a coordinator
        # address (HOROVOD_GLOO_RENDEZVOUS_ADDR analogue) and our process
        # identity; jax.distributed is the rendezvous+control plane.
        if cfg.coordinator_addr and not jax.distributed.is_initialized():
            addr = cfg.coordinator_addr
            if cfg.coordinator_port:
                addr = f"{addr}:{cfg.coordinator_port}"
            kwargs = {}
            if cfg.env_size > 0:
                kwargs["num_processes"] = cfg.env_size
            if cfg.env_rank >= 0:
                kwargs["process_id"] = cfg.env_rank
            logger.info("jax.distributed.initialize(%s, %s)", addr, kwargs)
            jax.distributed.initialize(addr, **kwargs)
            st.owns_distributed = True

        if devices is None:
            devices = jax.devices()
        # Topology spec (HOROVOD_HIERARCHICAL=auto|rows,cols) pins the
        # two-level mesh shape; the legacy boolean only turns it on with
        # the process-grouped layout.
        spec_hier, dcn_size = _mesh.parse_topology_spec(
            cfg.hierarchical, len(devices))
        if hierarchical is None:
            hierarchical = (spec_hier or cfg.hierarchical_allreduce
                            or jax.process_count() > 1)
        st.config = cfg
        st.mesh = mesh if mesh is not None else \
            _mesh.build_mesh(devices, hierarchical=hierarchical,
                             dcn_size=dcn_size if hierarchical else None)
        st.initialized = True
        _ps._install_global_set()
        if process_sets:
            for ranks in process_sets:
                _ps.add_process_set(ranks)

        from ..controller.cache import ExecutableCache
        st.cache = ExecutableCache(capacity=cfg.cache_capacity)
        if cfg.timeline:
            from ..timeline import Timeline
            st.timeline = Timeline(cfg.timeline,
                                   mark_cycles=cfg.timeline_mark_cycles,
                                   rank=jax.process_index())
        if cfg.autotune:
            from ..autotune import Autotuner
            st.autotuner = Autotuner(cfg)
        if cfg.metrics_enabled:
            from ..timeline import metrics as _metrics
            _metrics.install_default_metrics()
            if cfg.metrics_port >= 0:
                from ..run.metrics_server import MetricsServer
                st.metrics_server = MetricsServer(port=cfg.metrics_port)
                logger.info("Prometheus /metrics on port %d",
                            st.metrics_server.port)
        elif cfg.metrics_port >= 0:
            logger.warning("HOROVOD_METRICS_PORT set but HOROVOD_METRICS=0; "
                           "not starting the metrics endpoint")
        # Span layer: tag this process's spans with its rank and mirror
        # them into the timeline when one is open; when metrics are on,
        # arm the straggler monitor on the recorder's step boundary.
        from ..timeline import spans as _spans
        rec = _spans.recorder().configure(rank=jax.process_index(),
                                          timeline=st.timeline)
        if cfg.metrics_enabled:
            from ..timeline.straggler import StragglerMonitor
            st.straggler = StragglerMonitor(
                world=jax.process_count(),
                stall_check_time=cfg.stall_check_time)
            rec.add_listener(st.straggler.observe)
        if cfg.trace_sync:
            _install_trace_plane(st, cfg, rec)
        from . import stall as _stall
        _stall.configure(cfg)
        # Deterministic fault injection (HOROVOD_CHAOS): installed once
        # per process, keyed to the process rank so every worker resolves
        # the same schedule.  No-op without the env var.
        from ..elastic import chaos as _chaos
        _chaos.maybe_install(rank=jax.process_index(),
                             size=jax.process_count())
        global _atexit_registered
        if not _atexit_registered:
            atexit.register(_atexit_shutdown)
            _atexit_registered = True
        logger.info(
            "horovod_tpu initialized: %d device(s), mesh axes %s, "
            "process %d/%d", int(st.mesh.devices.size), st.mesh.axis_names,
            jax.process_index(), jax.process_count())


def _install_trace_plane(st, cfg: Config, rec) -> None:
    """Arm the cross-rank trace plane (HOROVOD_TRACE_SYNC=1): NTP-style
    clock offset against the rendezvous KV server + per-step summary
    publication.  The KV endpoint comes from the elastic assignment URL
    (``HVD_TPU_ELASTIC_ASSIGNMENT=http://...`` + the per-job secret);
    without one this degrades to a warning, never an init failure."""
    import os as _os
    from ..elastic.notify import ASSIGNMENT_ENV
    from ..run.secret import SECRET_ENV
    url = _os.environ.get(ASSIGNMENT_ENV, "")
    secret = _os.environ.get(SECRET_ENV)
    if not url.startswith("http://") or not secret:
        logger.warning(
            "HOROVOD_TRACE_SYNC=1 but no HTTP KV rendezvous is "
            "configured (%s/%s); skipping clock alignment",
            ASSIGNMENT_ENV, SECRET_ENV)
        return
    try:
        from ..run.http_kv import KVClient
        from ..timeline.sync import TracePlane
        kv = KVClient.from_url(url, secret, timeout_s=5.0)
        st.trace_plane = TracePlane(
            kv, rank=jax.process_index(), size=jax.process_count(),
            publish_steps=cfg.trace_publish_steps, monitor=st.straggler)
        rec.add_listener(st.trace_plane.on_summary)
    except Exception as e:  # ConnectionError, auth, ... -- telemetry only
        logger.warning("trace plane disabled: %s", e)


_atexit_registered = False


def _atexit_shutdown() -> None:
    st = global_state()
    if st.initialized:
        try:
            shutdown()
        except Exception:  # pragma: no cover - best effort at interpreter exit
            pass


def shutdown() -> None:
    """Tear down framework state (``hvd.shutdown()`` parity)."""
    import sys
    if "horovod_tpu.torch_api.batching" in sys.modules:
        sys.modules["horovod_tpu.torch_api.batching"].shutdown_batcher()
    from ..collectives import eager as _eager
    _eager.reset_fences()
    st = global_state()
    with st.lock:
        if not st.initialized:
            return
        owns = st.owns_distributed
        st.reset()
        from . import stall as _stall
        _stall.teardown()
    if owns:
        try:
            jax.distributed.shutdown()
        except Exception:  # pragma: no cover
            logger.warning("jax.distributed.shutdown failed", exc_info=True)


def is_initialized() -> bool:
    return global_state().initialized


def _require_init() -> "GlobalStateT":
    st = global_state()
    if not st.initialized:
        raise NotInitializedError()
    return st


def mesh():
    """The world communicator mesh."""
    return _require_init().mesh


def reduce_axes():
    """Axis name(s) collectives reduce over, innermost last."""
    return tuple(_require_init().mesh.axis_names)


def size() -> int:
    """Total number of data-parallel workers (devices)."""
    return int(_require_init().mesh.devices.size)


def rank() -> int:
    _require_init()
    return jax.process_index()


def local_size() -> int:
    _require_init()
    return jax.local_device_count()


def local_rank() -> int:
    st = _require_init()
    if st.config.env_local_rank >= 0:
        return st.config.env_local_rank
    return 0


def cross_size() -> int:
    st = _require_init()
    if st.config.env_cross_size >= 0:
        return st.config.env_cross_size
    return jax.process_count()


def cross_rank() -> int:
    st = _require_init()
    if st.config.env_cross_rank >= 0:
        return st.config.env_cross_rank
    return jax.process_index()


def is_homogeneous() -> bool:
    """True when every process owns the same device count."""
    _require_init()
    return True


# Build-capability probes (parity with HorovodBasics.{nccl,mpi,...}_built).
def nccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def tpu_built() -> bool:
    return True


def mpi_threads_supported() -> bool:
    return False


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start (or restart) timeline capture at runtime
    (``hvd.start_timeline`` parity; the env-driven path is
    ``HOROVOD_TIMELINE`` at init).  Like the reference, requires
    ``init()`` first -- init would otherwise silently replace (and leak)
    a pre-init timeline via its ``HOROVOD_TIMELINE`` path."""
    from ..timeline import Timeline
    from .exceptions import NotInitializedError

    if not is_initialized():
        raise NotInitializedError(
            "hvd.start_timeline() requires hvd.init() first")
    st = global_state()
    with st.lock:
        if st.timeline is not None:
            st.timeline.close()
        st.timeline = Timeline(file_path, mark_cycles=mark_cycles,
                               rank=jax.process_index())
        from ..timeline import spans as _spans
        _spans.recorder().configure(timeline=st.timeline)


def stop_timeline() -> None:
    """Stop timeline capture and finalize the trace file
    (``hvd.stop_timeline`` parity)."""
    st = global_state()
    with st.lock:
        if st.timeline is not None:
            st.timeline.close()
            st.timeline = None
            from ..timeline import spans as _spans
            _spans.recorder().timeline = None
