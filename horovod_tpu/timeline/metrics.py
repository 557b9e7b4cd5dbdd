"""Process-wide metrics plane (the aggregate half of SURVEY.md 5.1).

The Chrome-trace :class:`~horovod_tpu.timeline.Timeline` captures the
*semantic lifecycle* of each operation; this module answers "how is the
job doing right now": a process-wide :class:`MetricsRegistry` of
counters, gauges and fixed-bucket histograms that every telemetry source
in the runtime feeds --

- the per-step :class:`StepReport` sampled host-side around each
  executable call (``training.py``; wall time, exchanged wire bytes,
  codec, microbatches, steps-per-exec),
- ``controller.fusion.plan_cache_stats()`` and
  ``collectives.eager.deferred_fuse_stats()`` (pulled lazily through
  registered collectors so resets stay consistent),
- compression ratio / wire-bytes accounting from ``optim/distributed.py``,
- eager-path op and fence counts from ``collectives/eager.py``,
- elastic rank-change events and autotuner sample decisions.

Rendered two ways: Prometheus text exposition (served by
``run/metrics_server.py`` on ``HOROVOD_METRICS_PORT``) and a plain dict
via :func:`metrics_snapshot`.

Zero-overhead when disabled (``HOROVOD_METRICS=0``): every family
accessor returns a shared null object whose ``inc``/``set``/``observe``
are no-ops, and the train-step instrumentation unwraps entirely.
Nothing here runs inside a traced program -- scan-loop bitwise parity
and buffer donation are untouched by construction.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "MetricsRegistry", "StepReport", "registry", "reset_metrics",
    "metrics_snapshot", "render_prometheus", "last_step_report",
    "record_step_report", "install_default_metrics",
]

# Step wall-time histogram upper bounds (seconds).  Spans sub-ms eager
# dispatches to multi-second big-model scan executables.
DEFAULT_TIME_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label_value(s: str) -> str:
    return (s.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(v: float) -> str:
    """Prometheus sample value: integral floats render without the dot."""
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


# -- metric primitives ----------------------------------------------------

class Counter:
    """Monotonic counter.  ``set_cumulative`` exists for collector-fed
    counters whose source keeps its own running total (plan cache,
    deferred-fuse stats): the collector publishes the absolute value
    instead of diffing."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError(f"counter increment must be >= 0, got {v}")
        with self._lock:
            self._value += v

    def set_cumulative(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Instantaneous value."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._value += v

    def dec(self, v: float = 1.0) -> None:
        self.inc(-v)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram with cumulative Prometheus semantics.

    ``buckets`` are strictly-increasing upper bounds; an implicit
    ``+Inf`` bucket always exists.  ``snapshot()`` returns CUMULATIVE
    per-``le`` counts (each bucket includes everything below it), the
    way the text format and every bucket-arithmetic test expect."""

    __slots__ = ("_lock", "bounds", "_counts", "_sum", "_count")

    def __init__(self, buckets: Sequence[float] = DEFAULT_TIME_BUCKETS):
        bounds = tuple(float(b) for b in buckets)
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        if any(b2 <= b1 for b1, b2 in zip(bounds, bounds[1:])):
            raise ValueError(
                f"bucket bounds must be strictly increasing: {bounds}")
        self._lock = threading.Lock()
        self.bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)  # le semantics: v <= bound
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> dict:
        with self._lock:
            raw = list(self._counts)
            total, s = self._count, self._sum
        cum, acc = {}, 0
        for bound, c in zip(self.bounds, raw):
            acc += c
            cum[_fmt(bound)] = acc
        cum["+Inf"] = total
        return {"buckets": cum, "sum": s, "count": total}


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class _NullMetric:
    """Shared no-op stand-in returned when metrics are disabled: absorbs
    the whole family/child API so call sites never branch."""

    __slots__ = ()

    def inc(self, v: float = 1.0) -> None:
        pass

    def dec(self, v: float = 1.0) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def set_cumulative(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def labels(self, **kv) -> "_NullMetric":
        return self

    @property
    def value(self) -> float:
        return 0.0


NULL_METRIC = _NullMetric()


class _Family:
    """One named metric family, optionally labelled.  An unlabelled
    family proxies the metric API straight to its single child, so
    ``reg.counter("x").inc()`` and ``reg.gauge("y").set(v)`` both read
    naturally."""

    __slots__ = ("kind", "name", "help", "labelnames", "buckets",
                 "_lock", "_children")

    def __init__(self, kind: str, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_TIME_BUCKETS):
        self.kind = kind
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(buckets)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}

    def _make(self):
        if self.kind == "histogram":
            return Histogram(self.buckets)
        return _KINDS[self.kind]()

    def labels(self, **kv):
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(kv)} != "
                f"declared {sorted(self.labelnames)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make()
            return child

    def _solo(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} is labelled {self.labelnames}; use .labels()")
        return self.labels()

    # unlabelled convenience pass-throughs
    def inc(self, v: float = 1.0) -> None:
        self._solo().inc(v)

    def dec(self, v: float = 1.0) -> None:
        self._solo().dec(v)

    def set(self, v: float) -> None:
        self._solo().set(v)

    def set_cumulative(self, v: float) -> None:
        self._solo().set_cumulative(v)

    def observe(self, v: float) -> None:
        self._solo().observe(v)

    def snapshot(self) -> dict:
        return self._solo().snapshot()

    @property
    def value(self) -> float:
        return self._solo().value

    def samples(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return sorted(self._children.items())


# -- the registry ---------------------------------------------------------

class MetricsRegistry:
    """Thread-safe family store + collector callbacks + renderers.

    Enabled-ness is evaluated lazily at family-access time so the
    registry is robust to creation order: before ``hvd.init()`` it
    follows ``HOROVOD_METRICS`` directly, afterwards the frozen
    :class:`~horovod_tpu.core.config.Config` wins."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = {}
        self._collectors: List[Callable[[], None]] = []
        self._last_report: Optional["StepReport"] = None

    @property
    def enabled(self) -> bool:
        from ..core.config import _env_bool
        from ..core.state import global_state
        cfg = global_state().config
        if cfg is not None and hasattr(cfg, "metrics_enabled"):
            return bool(cfg.metrics_enabled)
        return _env_bool("METRICS", True)

    # -- family accessors -------------------------------------------------
    def _family(self, kind: str, name: str, help: str,
                labelnames: Sequence[str],
                buckets: Sequence[float] = DEFAULT_TIME_BUCKETS):
        if not self.enabled:
            return NULL_METRIC
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = _Family(kind, name, help, labelnames, buckets)
                self._families[name] = fam
            elif fam.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}, "
                    f"requested {kind}")
            return fam

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()):
        return self._family("counter", name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()):
        return self._family("gauge", name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS,
                  labelnames: Sequence[str] = ()):
        return self._family("histogram", name, help, labelnames, buckets)

    # -- collectors -------------------------------------------------------
    def add_collector(self, fn: Callable[[], None]) -> None:
        """Register a pull callback run before every render/snapshot.
        Idempotent by identity; use for sources that keep their own
        running totals (plan cache, deferred-fuse stats)."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:  # a broken collector must not kill a scrape
                pass

    # -- step reports ------------------------------------------------------
    def record_step_report(self, report: "StepReport") -> None:
        with self._lock:
            self._last_report = report

    @property
    def last_step_report(self) -> Optional["StepReport"]:
        with self._lock:
            return self._last_report

    # -- rendering ---------------------------------------------------------
    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        self.collect()
        with self._lock:
            families = [self._families[n] for n in sorted(self._families)]
        out: List[str] = []
        for fam in families:
            out.append(f"# HELP {fam.name} {_escape_help(fam.help)}")
            out.append(f"# TYPE {fam.name} {fam.kind}")
            for key, metric in fam.samples():
                base = "".join(
                    f'{n}="{_escape_label_value(v)}",'
                    for n, v in zip(fam.labelnames, key))[:-1]
                if fam.kind == "histogram":
                    snap = metric.snapshot()
                    for le, c in snap["buckets"].items():
                        lbl = (base + "," if base else "") + \
                            f'le="{_escape_label_value(le)}"'
                        out.append(f"{fam.name}_bucket{{{lbl}}} {c}")
                    suffix = f"{{{base}}}" if base else ""
                    out.append(f"{fam.name}_sum{suffix} "
                               f"{_fmt(snap['sum'])}")
                    out.append(f"{fam.name}_count{suffix} {snap['count']}")
                else:
                    suffix = f"{{{base}}}" if base else ""
                    out.append(f"{fam.name}{suffix} {_fmt(metric.value)}")
        return "\n".join(out) + "\n" if out else ""

    def snapshot(self) -> dict:
        """Plain-dict snapshot: unlabelled counter/gauge -> ``value``;
        histogram -> ``count``/``sum``/``buckets``; labelled families ->
        a ``samples`` list."""
        self.collect()
        with self._lock:
            families = dict(self._families)
        snap: Dict[str, dict] = {}
        for name in sorted(families):
            fam = families[name]
            entry: dict = {"type": fam.kind}
            if fam.labelnames:
                entry["samples"] = [
                    {"labels": dict(zip(fam.labelnames, key)),
                     **(m.snapshot() if fam.kind == "histogram"
                        else {"value": m.value})}
                    for key, m in fam.samples()]
            else:
                kids = fam.samples()
                if not kids:
                    entry["value"] = 0.0
                elif fam.kind == "histogram":
                    entry.update(kids[0][1].snapshot())
                else:
                    entry["value"] = kids[0][1].value
            snap[name] = entry
        return snap


# -- process-wide singleton ------------------------------------------------

_registry_lock = threading.Lock()
_registry: Optional[MetricsRegistry] = None


def registry() -> MetricsRegistry:
    """The process-wide registry (created on first use)."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = MetricsRegistry()
        return _registry


def reset_metrics() -> None:
    """Drop every family, collector and step report (tests)."""
    global _registry
    with _registry_lock:
        _registry = None


def metrics_snapshot() -> dict:
    """Public snapshot API: ``horovod_tpu.metrics_snapshot()``."""
    return registry().snapshot()


def render_prometheus() -> str:
    return registry().render()


# -- per-step report -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepReport:
    """Host-side sample of ONE executable dispatch in the train loop.

    ``wall_time_s`` is the dispatch wall time for the whole call (a
    ``steps_per_exec=k`` scan loop covers k optimizer steps).
    ``exchanged_bytes``/``uncompressed_bytes`` are the per-optimizer-step
    wire accounting: for ZeRO-1 they match
    ``zero_report()['zero1_exchanged_bytes_per_chip']`` /
    ``['replicated_allreduce_bytes_per_chip']`` byte-for-byte; for a
    compressed exchange they match the
    ``wire_payload_bytes``-over-``ef_bucket_plan`` accounting.  The
    microbatch overlap factor is intentionally NOT folded in: the figure
    is the equivalent single-exchange payload.  ``packed_bytes`` are the
    gradient bytes a step copies into flat fusion buffers before its
    collectives (and slices back out after them): 0 on the leaf-wise
    exchange, the raw gradient bytes where the exchange needs contiguous
    vectors.  Derived from the exchange's route
    (``training._step_builds_buckets``), not observed in the program."""

    step: int
    wall_time_s: float
    steps_per_exec: int = 1
    microbatches: int = 1
    zero_stage: int = 0
    codec: str = "none"
    exchanged_bytes: int = 0
    uncompressed_bytes: int = 0
    packed_bytes: int = 0


def last_step_report() -> Optional[StepReport]:
    """The most recent :class:`StepReport` (None before the first step)."""
    return registry().last_step_report


def record_step_report(report: StepReport) -> None:
    """Store ``report`` and feed the step-level families."""
    reg = registry()
    if not reg.enabled:
        return
    reg.record_step_report(report)
    k = max(int(report.steps_per_exec), 1)
    reg.counter("horovod_step_total",
                "Optimizer steps completed").inc(k)
    reg.histogram("horovod_step_time_seconds",
                  "Per-step dispatch wall time (scan loops amortize "
                  "one dispatch over k steps)").observe(
                      report.wall_time_s / k)
    reg.counter("horovod_wire_bytes_total",
                "Cumulative per-chip gradient-exchange wire bytes"
                ).inc(report.exchanged_bytes * k)
    reg.gauge("horovod_wire_bytes_per_step",
              "Per-chip exchange wire bytes per optimizer step"
              ).set(report.exchanged_bytes)
    reg.gauge("horovod_uncompressed_bytes_per_step",
              "Equivalent uncompressed exchange bytes per optimizer step"
              ).set(report.uncompressed_bytes)
    reg.gauge("horovod_packed_bytes_per_step",
              "Gradient bytes copied into flat fusion buffers per "
              "optimizer step (0: the leaf-wise exchange builds none)"
              ).set(report.packed_bytes)
    if report.exchanged_bytes > 0 and report.uncompressed_bytes > 0:
        reg.gauge("horovod_compression_ratio",
                  "uncompressed / wire bytes of the gradient exchange"
                  ).set(report.uncompressed_bytes / report.exchanged_bytes)


# -- default families + collectors -----------------------------------------

def _collect_plan_cache() -> None:
    from ..controller.fusion import plan_cache_stats
    reg = registry()
    stats = plan_cache_stats()
    reg.counter("horovod_plan_cache_hits_total",
                "Fusion bucket-plan cache hits"
                ).set_cumulative(stats["hits"])
    reg.counter("horovod_plan_cache_misses_total",
                "Fusion bucket-plan cache misses"
                ).set_cumulative(stats["misses"])
    reg.counter("horovod_plan_cache_evictions_total",
                "Fusion bucket-plan cache evictions"
                ).set_cumulative(stats["evictions"])
    reg.gauge("horovod_plan_cache_size",
              "Fusion bucket-plan cache entries").set(stats["size"])


def _collect_deferred_fuse() -> None:
    from ..collectives.eager import deferred_fuse_stats
    reg = registry()
    stats = deferred_fuse_stats()
    reg.counter("horovod_deferred_flushes_total",
                "Deferred-async flush rounds"
                ).set_cumulative(stats["flushes"])
    reg.counter("horovod_deferred_fused_buckets_total",
                "Fusion-planner buckets dispatched by the deferred flush"
                ).set_cumulative(stats["fused_buckets"])
    reg.counter("horovod_deferred_fused_ops_total",
                "Deferred ops serviced through a fused bucket"
                ).set_cumulative(stats["fused_ops"])
    reg.counter("horovod_deferred_singleton_ops_total",
                "Deferred ops dispatched individually"
                ).set_cumulative(stats["singleton_ops"])


def _collect_eager() -> None:
    from ..collectives.eager import eager_op_stats
    reg = registry()
    stats = eager_op_stats()
    reg.counter("horovod_eager_ops_total",
                "Eager collective dispatches"
                ).set_cumulative(stats["ops"])
    reg.counter("horovod_eager_fences_total",
                "Eager coordination fences (named-barrier rounds)"
                ).set_cumulative(stats["fences"])


def install_default_metrics() -> None:
    """Eagerly create the default families and wire the pull collectors.

    Idempotent; called from ``hvd.init()`` and from the metrics server
    so a scrape during a plain train loop always exposes the full
    family set (>= 8 families) even before every source has fired."""
    reg = registry()
    if not reg.enabled:
        return
    reg.counter("horovod_step_total", "Optimizer steps completed")
    reg.histogram("horovod_step_time_seconds",
                  "Per-step dispatch wall time (scan loops amortize "
                  "one dispatch over k steps)")
    reg.counter("horovod_wire_bytes_total",
                "Cumulative per-chip gradient-exchange wire bytes")
    reg.gauge("horovod_wire_bytes_per_step",
              "Per-chip exchange wire bytes per optimizer step")
    reg.gauge("horovod_uncompressed_bytes_per_step",
              "Equivalent uncompressed exchange bytes per optimizer step")
    reg.gauge("horovod_compression_ratio",
              "uncompressed / wire bytes of the gradient exchange")
    reg.gauge("horovod_plan_buckets",
              "Bucket count of the most recently explained exchange plan")
    reg.counter("horovod_elastic_reset_total",
                "Elastic state resets (rank-change recoveries)")
    reg.counter("horovod_elastic_host_updates_total",
                "Elastic host-set update notifications")
    reg.counter("horovod_elastic_ranks_lost",
                "Ranks lost across elastic recoveries")
    reg.gauge("horovod_elastic_steps_to_recover",
              "Steps rolled back to the last commit during the most "
              "recent elastic recovery")
    reg.counter("horovod_ef_residual_recovered_bytes",
                "Bytes of optimizer/EF carry state reconstructed "
                "checkpointlessly across elastic resizes")
    reg.counter("horovod_ef_residual_zeroed_total",
                "EF residual buckets dropped (zeroed) during an elastic "
                "resize because shapes were irreconcilable")
    reg.counter("horovod_chaos_faults_total",
                "Faults fired by the chaos injector")
    reg.counter("horovod_kv_retries_total",
                "Control-plane requests retried after a transport "
                "failure")
    reg.counter("horovod_autotune_samples_total",
                "Autotuner samples scored (one per sample window)")
    # Serving control-plane decision families (serving.controlplane).
    reg.counter("horovod_ctl_decisions_total",
                "Serving control-plane decisions by action",
                labelnames=("action",))
    reg.counter("horovod_ctl_resizes_total",
                "Decode-mesh resizes executed by the control plane",
                labelnames=("direction",))
    reg.counter("horovod_ctl_evictions_total",
                "Ranks removed from the serving fleet by the control "
                "plane", labelnames=("reason",))
    reg.counter("horovod_ctl_drained_requests_total",
                "In-flight requests carried through a resize, by drain "
                "path", labelnames=("path",))
    reg.counter("horovod_ctl_slo_violation_seconds_total",
                "Seconds the sampled SLO (TTFT p99 / queue depth) was "
                "in violation")
    reg.gauge("horovod_ctl_mesh_size",
              "Current decode-mesh tensor-parallel size")
    reg.gauge("horovod_ctl_healthy_ranks",
              "Devices the control plane still considers usable")
    reg.gauge("horovod_ctl_ttft_p99_seconds",
              "Windowed TTFT p99 as sampled by the control plane")
    reg.add_collector(_collect_plan_cache)
    reg.add_collector(_collect_deferred_fuse)
    reg.add_collector(_collect_eager)


# -- histogram arithmetic --------------------------------------------------

def histogram_window(curr: dict, base: Optional[dict]) -> dict:
    """Subtract a baseline cumulative snapshot from a newer one.

    Both arguments are ``Histogram.snapshot()`` dicts.  The result
    covers only the observations made after ``base`` was taken -- how
    the serving control plane turns the process-lifetime TTFT histogram
    into a per-sampling-window distribution (the registry is
    append-only, so windows are diffs, as with PromQL ``increase()``).
    """
    if not base:
        return curr
    base_buckets = base.get("buckets", {})
    return {
        "buckets": {le: int(c) - int(base_buckets.get(le, 0))
                    for le, c in curr["buckets"].items()},
        "sum": float(curr.get("sum", 0.0)) - float(base.get("sum", 0.0)),
        "count": int(curr.get("count", 0)) - int(base.get("count", 0)),
    }


def histogram_quantile(snap: dict, q: float) -> Optional[float]:
    """Quantile estimate from a cumulative ``Histogram.snapshot()``.

    Prometheus ``histogram_quantile`` semantics: find the first bucket
    whose cumulative count covers rank ``q * count`` and interpolate
    linearly inside it; observations in the ``+Inf`` overflow clamp to
    the highest finite bound.  Returns ``None`` on an empty snapshot.
    """
    total = int(snap.get("count", 0))
    if total <= 0:
        return None
    items = sorted(
        (float("inf") if le == "+Inf" else float(le), int(c))
        for le, c in snap.get("buckets", {}).items())
    rank = max(0.0, min(1.0, float(q))) * total
    prev_bound, prev_count = 0.0, 0
    for bound, count in items:
        if count >= rank and count > prev_count:
            if bound == float("inf"):
                return prev_bound
            frac = (rank - prev_count) / (count - prev_count)
            return prev_bound + (bound - prev_bound) * frac
        prev_count = count
        if bound != float("inf"):
            prev_bound = bound
    return None
