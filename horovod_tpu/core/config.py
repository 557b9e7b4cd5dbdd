"""Environment-driven configuration.

TPU-native analogue of the reference's env parser
(``horovod/common/utils/env_parser.cc`` -- translates ``HOROVOD_*`` env vars
into global-state flags).  We honour both the historical ``HOROVOD_*`` names
(for drop-in parity) and ``HVD_TPU_*`` overrides (which win when both are
set).

Unlike the reference there is no C++ GlobalState to populate: the config is a
frozen dataclass read once at ``hvd.init()`` time and stored on the
:class:`horovod_tpu.core.state.GlobalState` singleton.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_MiB = 1024 * 1024


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Look up ``HVD_TPU_<name>`` then ``HOROVOD_<name>``."""
    for prefix in ("HVD_TPU_", "HOROVOD_"):
        v = os.environ.get(prefix + name)
        if v is not None:
            return v
    return default


def _env_int(name: str, default: int) -> int:
    v = _env(name)
    return int(v) if v not in (None, "") else default


def _env_float(name: str, default: float) -> float:
    v = _env(name)
    return float(v) if v not in (None, "") else default


def _env_bool(name: str, default: bool = False) -> bool:
    v = _env(name)
    if v in (None, ""):
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class Config:
    """Runtime knobs.

    Mirrors the de-facto public config API of the reference (SURVEY.md
    section 5.6).  Fields that only make sense for a CUDA runtime (NCCL
    stream counts, D2D memcpy batching) are intentionally absent: XLA owns
    scheduling on TPU.
    """

    # Fusion-buffer analogue: gradient bucketing threshold in bytes.
    # Reference: HOROVOD_FUSION_THRESHOLD (default 64 MiB).
    fusion_threshold: int = 64 * _MiB

    # Executable-cache capacity (ResponseCache analogue).
    # Reference: HOROVOD_CACHE_CAPACITY (default 1024).
    cache_capacity: int = 1024

    # Eager-path micro-batch window in milliseconds (HOROVOD_CYCLE_TIME):
    # how long the native scheduler waits to fuse hook-enqueued gradients.
    cycle_time: float = 1.0

    # Two-level DCN x ICI reduction (NCCLHierarchicalAllreduce analogue).
    hierarchical_allreduce: bool = False

    # Two-level mesh topology spec (HOROVOD_HIERARCHICAL):
    # ``auto`` derives the slice axis from the process grouping /
    # elastic assignment; ``rows,cols`` pins explicit (dcn, ici)
    # extents (virtual multi-slice dry runs).  Setting it implies
    # hierarchical_allreduce.  Parsed by
    # ``parallel.mesh.parse_topology_spec``.
    hierarchical: Optional[str] = None

    # Chrome-trace timeline output path (HOROVOD_TIMELINE).
    timeline: Optional[str] = None
    timeline_mark_cycles: bool = False

    # Autotune (HOROVOD_AUTOTUNE / HOROVOD_AUTOTUNE_LOG).
    autotune: bool = False
    autotune_log: Optional[str] = None

    # ZeRO-1 sharded optimizer state (HOROVOD_ZERO=1): default zero_stage
    # for steps built without an explicit argument (optim/zero.py).
    zero_stage: int = 0

    # Steps-per-execution scan loop (HOROVOD_STEPS_PER_EXEC): default k for
    # make_train_loop / make_flax_train_loop built without an explicit
    # steps_per_execution argument.  k steps compile into ONE lax.scan
    # executable, so they cost one host dispatch and one device->host fence.
    steps_per_exec: int = 1

    # Microbatched backward-overlap exchange (HOROVOD_MICROBATCHES):
    # default k for train steps built without an explicit ``microbatches``
    # argument.  The per-step batch splits into k sub-batches inside ONE
    # compiled executable; each sub-batch's gradient buckets reduce-scatter
    # while the next sub-batch's backward pass is still running, so the
    # latency-hiding scheduler can overlap wire time with FLOPs.
    microbatches: int = 1

    # Fused deferred-async flush (HOROVOD_DEFERRED_FUSE, default on).
    # At a flush point, compatible pending ``*_async`` ops (same kind,
    # dtype, process set, codec, pre/postscale) pack into fusion-planner
    # buckets and dispatch ONE collective + ONE fence per bucket instead
    # of one per op -- the eager-path analogue of the reference's
    # fusion-buffer cycle.  Off = round-5 per-op dispatch (still one
    # presence round per flush).
    deferred_fuse: bool = True

    # Per-rank bucket size cap in bytes for the fused deferred flush
    # (HOROVOD_DEFERRED_FUSE_THRESHOLD); 0 = follow fusion_threshold.
    deferred_fuse_threshold: int = 0

    # Default gradient-exchange codec (HOROVOD_COMPRESSION): a spec string
    # parsed by ``collectives.compression.parse_compression`` --
    # none|fp16|bf16|fp8|powersgd:<rank>|topk:<fraction>.  Applies to
    # DistributedOptimizer wraps built without an explicit ``compression``
    # argument; None = no compression.
    compression: Optional[str] = None

    # Error-feedback residual carry for the powersgd/topk codecs
    # (HOROVOD_EF_RESIDUAL, default on).  Off drops each step's
    # compression error instead of feeding it back -- ablation only, it
    # biases convergence.
    ef_residual: bool = True

    # 3-D parallelism defaults for train steps built without explicit
    # arguments (training.py).  HOROVOD_TP: tensor-parallel degree --
    # params shard over the mesh's "model" axis and the TP collectives
    # (row-parallel allreduce) run inside a slice.  HOROVOD_PIPELINE_STAGES:
    # pipeline-stage count over the "pipe" axis.  1 = off (pure DP,
    # bitwise-identical traces to the pre-3D build).
    tp: int = 1
    pipeline_stages: int = 1

    # MoE all-to-all wire codec (HOROVOD_MOE_COMPRESSION): none|bf16|fp16.
    # Casts the dispatch/combine slot buffers before each all_to_all and
    # restores f32 after -- the expert-parallel analogue of the gradient
    # exchange codecs.  The autotuner's MoE axis (HOROVOD_AUTOTUNE_MOE=1)
    # overrides this per sample.
    moe_compression: Optional[str] = None

    # Chunked gradient exchange (HOROVOD_EXCHANGE_CHUNK_MB, megabytes;
    # 0 disables).  Decomposes each fusion bucket's allreduce into
    # chunk-sized reduce-scatter + all-gather pairs so XLA's latency-hiding
    # scheduler can interleave communication with remaining backward
    # compute (all-gather compiles async on this toolchain; a monolithic
    # all-reduce does not).
    exchange_chunk_bytes: int = 0

    # Stall/heartbeat inspector for the launcher/elastic plane.
    stall_check_disable: bool = False
    stall_check_time: float = 60.0
    stall_shutdown_time: float = 0.0
    # Waits older than this latch the elastic preemption notice (a
    # wedged collective becomes an elastic reset, not a hang); 0 = off.
    stall_reset_time: float = 0.0

    # Elastic.
    elastic_timeout: float = 600.0

    # Logging (HOROVOD_LOG_LEVEL, HOROVOD_LOG_HIDE_TIMESTAMP).
    log_level: str = "warning"
    log_hide_timestamp: bool = False

    # Launcher-provided identity (HOROVOD_RANK/SIZE/... parity); -1 = unset.
    env_rank: int = -1
    env_size: int = -1
    env_local_rank: int = -1
    env_local_size: int = -1
    env_cross_rank: int = -1
    env_cross_size: int = -1

    # Coordinator/rendezvous (HOROVOD_GLOO_RENDEZVOUS_ADDR/PORT analogue):
    # address handed to jax.distributed.initialize.
    coordinator_addr: Optional[str] = None
    coordinator_port: int = 0

    # Debug-mode desync checksums (no reference equivalent; SURVEY.md 5.2).
    check_desync: bool = False
    # Consecutive restore+sync attempts before a persistent desync aborts.
    desync_max_retries: int = 3

    # Silent-data-corruption defense plane (core/guard.py).
    # HOROVOD_GUARD=auto|1|0 compiles a cheap numeric screen (global
    # nonfinite count + grad norm, one extra f32[2] psum) into every train
    # step and skips the optimizer update on a poisoned step.  "auto"
    # enables the guard only when a corruption scenario is plausibly in
    # play (chaos injection, desync checks, snapshot ledger) so default
    # traces stay bitwise identical to the unguarded build.
    guard: str = "auto"
    # Skip a step whose global grad norm exceeds this bound even when
    # finite (HOROVOD_GUARD_NORM_LIMIT); 0 = nonfinite screening only.
    guard_norm_limit: float = 0.0
    # Consecutive guard-skipped steps before the anomaly counts as
    # sustained and the rollback ledger engages (HOROVOD_GUARD_STREAK).
    guard_streak: int = 3
    # Snapshot/rollback ledger cadence in committed steps
    # (HOROVOD_SNAPSHOT_STEPS); 0 disables the ring.
    snapshot_steps: int = 0
    # In-band cross-rank corruption tripwire cadence in steps
    # (HOROVOD_DESYNC_CHECK_STEPS); 0 disables.  Unlike check_desync
    # (every commit, debug-only) this samples every N train steps and
    # attributes the corrupt rank for quarantine.
    desync_check_steps: int = 0

    # Driver-side heartbeat eviction (seconds; 0 disables).  Workers whose
    # elastic heartbeat file goes stale longer than this are terminated and
    # blacklisted (HOROVOD_STALL_SHUTDOWN_TIME analogue at process level).
    heartbeat_timeout: float = 0.0

    # Force the XLA:CPU backend before first device use (the launcher's
    # --cpu test mode; the Gloo-CPU-backend analogue).
    force_cpu: bool = False

    # Metrics plane (timeline/metrics.py).  HOROVOD_METRICS=0 disables the
    # registry entirely (family accessors hand back a shared no-op object
    # and the train-step StepReport instrumentation unwraps -- zero
    # overhead).  HOROVOD_METRICS_PORT >= 0 serves Prometheus text on
    # that port at hvd.init() (0 = ephemeral; read the bound port from
    # global_state().metrics_server.port); -1 = no HTTP endpoint.
    metrics_enabled: bool = True
    metrics_port: int = -1

    # Cross-rank trace plane (timeline/sync.py).  HOROVOD_TRACE_SYNC=1:
    # at init() each rank estimates its clock offset to the rendezvous
    # KV server (NTP-style ping over http_kv) and publishes a compact
    # per-step span summary every HOROVOD_TRACE_PUBLISH_STEPS steps;
    # rank 0 merges them and feeds the straggler monitor.  Requires a
    # reachable KV server (elastic/launcher runs); no-op without one.
    trace_sync: bool = False
    trace_publish_steps: int = 10


# The fixed port worker 0 serves the JAX coordination service on when
# the pod environment does not name one (matches jax's own TPU cluster
# detection, so mixed bootstrap paths still rendezvous).
TPU_POD_COORDINATOR_PORT = 8476


def detect_tpu_pod() -> Optional[dict]:
    """Multi-host Cloud TPU slice environment -> process identity.

    On a multi-host TPU slice the runtime exports
    ``TPU_WORKER_HOSTNAMES`` (comma-separated, worker 0 first) and
    ``TPU_WORKER_ID`` (this host's index; older images spell it
    ``CLOUD_TPU_TASK_ID``).  This is the pod-native analogue of the
    launcher's LSF allocation detection (``run/lsf.py``) and of the
    reference inheriting placement from ``mpirun`` (SURVEY.md 4.4):
    ``hvd.init()`` on each pod host bootstraps unaided, with worker 0
    hosting the coordination service.  Explicit ``HOROVOD_RANK``/
    ``HVD_TPU_COORDINATOR_ADDR`` always win; disable detection entirely
    with ``HOROVOD_NO_TPU_POD_DETECT=1``.

    Returns ``{"addr", "port", "rank", "size"}`` or ``None`` when not on
    a multi-host slice (single-host slices need no coordination).
    """
    if _env_bool("NO_TPU_POD_DETECT"):
        return None
    names = [h.strip() for h in
             os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    if len(names) < 2:
        return None
    # Like _env_int, a set-but-empty variable counts as unset (a wrapper
    # exporting TPU_WORKER_ID= must not mask a valid CLOUD_TPU_TASK_ID).
    wid = os.environ.get("TPU_WORKER_ID", "").strip() or \
        os.environ.get("CLOUD_TPU_TASK_ID", "").strip()
    if not wid.isdigit():
        return None
    rank = int(wid)
    if rank >= len(names):
        return None
    return {"addr": names[0], "port": TPU_POD_COORDINATOR_PORT,
            "rank": rank, "size": len(names)}


def load_config() -> Config:
    """Parse the environment into a :class:`Config`."""
    addr = _env("COORDINATOR_ADDR") or _env("GLOO_RENDEZVOUS_ADDR")
    port = _env_int("COORDINATOR_PORT", _env_int("GLOO_RENDEZVOUS_PORT", 0))
    env_rank = _env_int("RANK", -1)
    env_size = _env_int("SIZE", -1)
    env_local_rank = _env_int("LOCAL_RANK", -1)
    env_local_size = _env_int("LOCAL_SIZE", -1)
    env_cross_rank = _env_int("CROSS_RANK", -1)
    env_cross_size = _env_int("CROSS_SIZE", -1)
    if addr is None:
        pod = detect_tpu_pod()
        if pod is not None:
            addr = pod["addr"]
            if port == 0:
                port = pod["port"]
            if env_rank < 0:
                env_rank = pod["rank"]
            if env_size < 0:
                env_size = pod["size"]
            # One process per pod host: host index IS the cross rank.
            if env_cross_rank < 0:
                env_cross_rank = pod["rank"]
            if env_cross_size < 0:
                env_cross_size = pod["size"]
            if env_local_rank < 0:
                env_local_rank = 0
            if env_local_size < 0:
                env_local_size = 1
    return Config(
        fusion_threshold=_env_int("FUSION_THRESHOLD", 64 * _MiB),
        cache_capacity=_env_int("CACHE_CAPACITY", 1024),
        cycle_time=_env_float("CYCLE_TIME", 1.0),
        hierarchical_allreduce=_env_bool("HIERARCHICAL_ALLREDUCE"),
        hierarchical=_env("HIERARCHICAL"),
        timeline=_env("TIMELINE"),
        timeline_mark_cycles=_env_bool("TIMELINE_MARK_CYCLES"),
        autotune=_env_bool("AUTOTUNE"),
        autotune_log=_env("AUTOTUNE_LOG"),
        zero_stage=_env_int("ZERO", 0),
        steps_per_exec=_env_int("STEPS_PER_EXEC", 1),
        microbatches=_env_int("MICROBATCHES", 1),
        tp=_env_int("TP", 1),
        pipeline_stages=_env_int("PIPELINE_STAGES", 1),
        moe_compression=_env("MOE_COMPRESSION"),
        compression=_env("COMPRESSION"),
        ef_residual=_env_bool("EF_RESIDUAL", True),
        deferred_fuse=_env_bool("DEFERRED_FUSE", True),
        deferred_fuse_threshold=_env_int("DEFERRED_FUSE_THRESHOLD", 0),
        exchange_chunk_bytes=_env_int("EXCHANGE_CHUNK_MB", 0) * _MiB,
        stall_check_disable=_env_bool("STALL_CHECK_DISABLE"),
        # Upstream spells these *_TIME_SECONDS; accept both spellings.
        stall_check_time=_env_float(
            "STALL_CHECK_TIME_SECONDS", _env_float("STALL_CHECK_TIME", 60.0)),
        stall_shutdown_time=_env_float(
            "STALL_SHUTDOWN_TIME_SECONDS",
            _env_float("STALL_SHUTDOWN_TIME", 0.0)),
        stall_reset_time=_env_float(
            "STALL_RESET_TIME_SECONDS",
            _env_float("STALL_RESET_TIME", 0.0)),
        elastic_timeout=_env_float("ELASTIC_TIMEOUT", 600.0),
        log_level=_env("LOG_LEVEL", "warning") or "warning",
        log_hide_timestamp=_env_bool("LOG_HIDE_TIMESTAMP"),
        env_rank=env_rank,
        env_size=env_size,
        env_local_rank=env_local_rank,
        env_local_size=env_local_size,
        env_cross_rank=env_cross_rank,
        env_cross_size=env_cross_size,
        coordinator_addr=addr,
        coordinator_port=port,
        check_desync=_env_bool("CHECK_DESYNC"),
        desync_max_retries=_env_int("DESYNC_MAX_RETRIES", 3),
        guard=(_env("GUARD", "auto") or "auto").strip().lower(),
        guard_norm_limit=_env_float("GUARD_NORM_LIMIT", 0.0),
        guard_streak=_env_int("GUARD_STREAK", 3),
        snapshot_steps=_env_int("SNAPSHOT_STEPS", 0),
        desync_check_steps=_env_int("DESYNC_CHECK_STEPS", 0),
        heartbeat_timeout=_env_float("HEARTBEAT_TIMEOUT", 0.0),
        force_cpu=_env_bool("FORCE_CPU"),
        metrics_enabled=_env_bool("METRICS", True),
        metrics_port=_env_int("METRICS_PORT", -1),
        trace_sync=_env_bool("TRACE_SYNC"),
        trace_publish_steps=_env_int("TRACE_PUBLISH_STEPS", 10),
    )
