"""SLO-driven elastic serving control plane (autoscale + drain + evict).

The closed loop the sensors and actuators of PRs 6/7/9/10/11 were built
for: a rank-0 controller that *samples* the MetricsRegistry SLO signals
(queue depth, windowed TTFT p99, batch occupancy), *decides* through the
:class:`~horovod_tpu.serving.policy.ScalePolicy` hysteresis/cooldown
policy, and *acts* by resizing the tensor-parallel decode mesh through
the same :func:`horovod_tpu.elastic.run_loop.apply_resize` sequence the
training loop runs after a re-rendezvous.

Transitions are graceful by construction:

* **drain** -- admission pauses, every in-flight slot flips to the
  ``draining`` lifecycle state, and the old mesh keeps decoding for a
  bounded step budget so near-done requests finish with bit-identical
  tokens (the completion path);
* **suspend + re-prefill** -- survivors of the budget are suspended
  (progress = prompt + emitted tokens, KV pages freed exactly) and
  re-prefilled on the post-resize mesh, continuing within sampling
  tolerance (the re-prefill path);
* **eviction** -- a ``kill@`` dead rank forces an immediate resize onto
  the survivors, and a ``slow@`` rank is evicted automatically when the
  :class:`~horovod_tpu.timeline.straggler.StragglerMonitor` lateness
  EWMA crosses ``HOROVOD_CTL_EVICT_LATENESS_S`` (the monitor's eviction
  hook latches the candidate; the policy consumes it).

Every decision lands in the ``horovod_ctl_*`` metric families and as a
span-tagged timeline event (kind ``ctl``, legs ``ctl/<action>/...``), so
the merged Perfetto trace shows *why* the fleet resized, next to the
per-leg decode spans showing *what* it cost.

Chaos faults are interpreted **virtually** over the controller's virtual
ranks: the spec grammar and rank=any resolution are
:class:`~horovod_tpu.elastic.chaos.ChaosInjector`'s own, but ``kill``
marks the device dead instead of ``os._exit`` (one process emulates the
fleet, exactly like ``examples/straggler_probe.py``) and ``slow``
inflates the rank's synthesized step-wall summaries feeding the monitor.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..timeline import metrics as _metrics
from ..timeline import spans as _spans
from ..timeline.straggler import StragglerMonitor
from .engine import ServingEngine, ServingReport, _pct
from .policy import (Decision, PolicyConfig, ScalePolicy, SLOSample,
                     valid_tp_sizes)
from .scheduler import Request

__all__ = ["ServingControlPlane", "ControlPlaneReport", "FleetScaler"]


class _VirtualFaults:
    """Chaos-spec adapter for the single-process serving drill.

    Reuses the injector's parser and deterministic ``rank=any``
    resolution but never calls ``on_step`` -- a real ``kill`` fault
    would ``os._exit(137)`` the *controller*.  Faults are keyed on the
    decode-step index and handed back to the control plane to fire
    virtually.
    """

    def __init__(self, spec: Optional[str], world: int):
        self.faults: list = []
        if spec:
            from ..elastic.chaos import ChaosInjector
            # rank=-1 matches no fault, so even an accidental on_step
            # call could never fire for real.
            self.faults = ChaosInjector(spec, rank=-1, size=world).faults

    def due(self, step: int) -> list:
        out = [f for f in self.faults if not f.fired and f.step <= step]
        for f in out:
            f.fired = True
        return out


class _MeshResizeState:
    """Duck-typed elastic ``State`` carrier handed to ``apply_resize``:
    ``resize`` swaps the serving mesh, ``on_reset`` restores suspended
    requests and re-opens admission.  No training carry anywhere."""

    def __init__(self, plane: "ServingControlPlane"):
        self._plane = plane

    def resize(self, old_size: int, new_size: int):
        return self._plane._do_resize(old_size, new_size)

    def on_reset(self) -> None:
        self._plane._on_reset()


@dataclasses.dataclass
class ControlPlaneReport:
    """One drill's closed-loop outcome, wrapped around the serving
    report.  ``lost_requests`` must be 0: every admissible request
    either completed on the mesh it started on or was re-prefilled and
    completed on a later one."""

    serving: ServingReport
    mesh_size_initial: int
    mesh_size_final: int
    decisions: List[dict]
    decision_counts: Dict[str, int]
    resizes: int
    evicted_ranks: List[int]
    dead_ranks: List[int]
    drained_completed: int
    drained_reprefilled: int
    drain_leaked_pages: int
    slo_violation_s: float
    lost_requests: int

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["serving"] = self.serving.as_dict()
        return d


class ServingControlPlane:
    """Autoscaling controller wrapped around one :class:`ServingEngine`.

    ``devices`` is the virtual fleet (defaults to ``jax.devices()``);
    the decode mesh is always the first ``size`` *healthy* devices, so
    kills and evictions shrink the usable pool and the policy ladder
    adapts.  ``policy`` may be any object with ``decide(sample)`` /
    ``mark_applied(decision, now_s)`` -- tests script it.
    """

    def __init__(self, config, params, *, devices=None,
                 initial_tp: Optional[int] = None,
                 policy=None, policy_config: Optional[PolicyConfig] = None,
                 monitor: Optional[StragglerMonitor] = None,
                 chaos_spec: Optional[str] = None, **engine_kwargs):
        self.config = config
        self.devices = list(devices if devices is not None
                            else jax.devices())
        self.policy_cfg = policy_config or PolicyConfig.from_env()
        sizes = valid_tp_sizes(config, len(self.devices))
        self.policy = policy if policy is not None else ScalePolicy(
            self.policy_cfg, sizes)
        allowed = [s for s in sizes
                   if self.policy_cfg.min_tp <= s <= self.policy_cfg.max_tp]
        if initial_tp is None:
            initial_tp = allowed[-1] if allowed else sizes[-1]
        self.healthy: List[int] = list(range(len(self.devices)))
        self.mesh_ranks: List[int] = self.healthy[:initial_tp]
        self.dead: set = set()
        self.evicted: List[int] = []
        self.engine = ServingEngine(config, params,
                                    mesh=self._mesh(self.mesh_ranks),
                                    **engine_kwargs)
        self.monitor = monitor if monitor is not None else StragglerMonitor(
            world=len(self.devices))
        self.monitor.add_eviction_hook(self.policy_cfg.evict_lateness_s,
                                       self._note_evict_candidate)
        self._evict_candidate: Optional[Tuple[int, float]] = None
        self._faults = _VirtualFaults(chaos_spec, len(self.devices))
        self._slow: Dict[int, float] = {}   # rank -> per-step inflation
        self._handled_dead: set = set()
        self._pending: Optional[Tuple[List[int], List[Request]]] = None
        self._monitor_warmup = 1  # skip the compile-dominated first step

        reg = _metrics.registry()
        self._m_decisions = reg.counter(
            "horovod_ctl_decisions_total",
            "Serving control-plane decisions by action",
            labelnames=("action",))
        self._m_resizes = reg.counter(
            "horovod_ctl_resizes_total",
            "Decode-mesh resizes executed by the control plane",
            labelnames=("direction",))
        self._m_evictions = reg.counter(
            "horovod_ctl_evictions_total",
            "Ranks removed from the serving fleet by the control plane",
            labelnames=("reason",))
        self._m_drained = reg.counter(
            "horovod_ctl_drained_requests_total",
            "In-flight requests carried through a resize, by drain path",
            labelnames=("path",))
        self._m_violation = reg.counter(
            "horovod_ctl_slo_violation_seconds_total",
            "Seconds the sampled SLO (TTFT p99 / queue depth) was in "
            "violation")
        self._m_mesh_size = reg.gauge(
            "horovod_ctl_mesh_size",
            "Current decode-mesh tensor-parallel size")
        self._m_healthy = reg.gauge(
            "horovod_ctl_healthy_ranks",
            "Devices the control plane still considers usable")
        self._m_ttft_p99 = reg.gauge(
            "horovod_ctl_ttft_p99_seconds",
            "Windowed TTFT p99 as sampled by the control plane")
        self._m_prefix_hit = reg.gauge(
            "horovod_ctl_prefix_hit_rate",
            "Radix prefix-cache hit rate as sampled by the control "
            "plane (0 when the cache is off)")
        self._m_mesh_size.set(len(self.mesh_ranks))
        self._m_healthy.set(len(self.healthy))

        # Drill bookkeeping (reset per serve()).
        self.decisions: List[dict] = []
        self._stats: Dict[str, Any] = {}

    # -- mesh helpers ------------------------------------------------------
    def _mesh(self, ranks: Sequence[int]):
        from jax.sharding import Mesh
        devs = [self.devices[r] for r in ranks]
        return Mesh(np.asarray(devs, dtype=object).reshape(len(devs)),
                    ("tp",))

    # -- monitor hook ------------------------------------------------------
    def _note_evict_candidate(self, rank: int, lateness_s: float) -> None:
        self._evict_candidate = (int(rank), float(lateness_s))

    # -- chaos (virtual firing) --------------------------------------------
    def _fire_faults(self, step: int, now_s: float) -> None:
        rec = _spans.recorder()
        for f in self._faults.due(step):
            _metrics.registry().counter(
                "horovod_chaos_faults_total",
                "Faults fired by the chaos injector").inc()
            rec.add("ctl", 0.0, leg=f"ctl/fault/{f.kind}")
            if f.kind == "kill":
                if f.rank in self.healthy:
                    self.healthy.remove(f.rank)
                self.dead.add(f.rank)
                self._slow.pop(f.rank, None)
                # Forget its EWMA now: a dead rank stops reporting, and
                # a frozen stale EWMA would otherwise read as lateness.
                self.monitor.evict(f.rank)
                self._m_healthy.set(len(self.healthy))
            elif f.kind == "slow":
                # A degraded device, not a hiccup: the rank stays slow
                # until the monitor's EWMA gets it evicted.
                self._slow[f.rank] = float(f.secs)

    def _feed_monitor(self, step: int, step_s: float) -> None:
        if self._monitor_warmup > 0:
            # The first step on a (re)built mesh is compile-dominated;
            # its wall says nothing about rank behavior.
            self._monitor_warmup -= 1
            return
        for r in self.mesh_ranks:
            if r in self.dead:
                continue  # a dead rank publishes nothing
            self.monitor.observe({
                "rank": r, "step": step, "t0_us": 0.0,
                "wall_s": step_s + self._slow.get(r, 0.0),
                "spans": {}, "legs": {}})

    # -- decode step (shared by the main loop and the drain) ---------------
    def _decode_once(self, now) -> float:
        # The engine's one round and the catch-up behind it: this loop
        # may rewrite slots and the mesh before the next round (the
        # monitor gets the round's own dispatch-to-fetch seconds).  The
        # DRAIN path always runs plain decode: a draining mesh is about
        # to lose ranks and the verify step's wider dispatch buys
        # nothing on the way down.
        self.engine.decode_once(self._stats, now)
        return self.engine.catch_up(self._stats, now)

    # -- controller tick ---------------------------------------------------
    def _sample(self, now_s: float) -> SLOSample:
        sched = self.engine.scheduler
        p99 = None
        snap_fn = getattr(sched._m_ttft, "snapshot", None)
        if snap_fn is not None:
            curr = snap_fn()
            win = _metrics.histogram_window(curr, self._stats["ttft_base"])
            self._stats["ttft_base"] = curr
            p99 = _metrics.histogram_quantile(win, 0.99)
        prefix = getattr(self.engine, "_prefix", None)
        hit_rate = prefix.hit_rate if prefix is not None else None
        return SLOSample(
            now_s=now_s, queue_depth=len(sched.queue), ttft_p99_s=p99,
            occupancy=sched.occupancy, mesh_size=len(self.mesh_ranks),
            mesh_ranks=tuple(self.mesh_ranks),
            healthy=tuple(self.healthy),
            dead_ranks=tuple(sorted(self.dead)),
            evict_candidate=self._evict_candidate,
            prefix_hit_rate=hit_rate)

    def _tick(self, now) -> None:
        now_s = now()
        st = self._stats
        if now_s - st["last_tick"] < self.policy_cfg.interval_s:
            return
        sample = self._sample(now_s)
        self._m_ttft_p99.set(sample.ttft_p99_s or 0.0)
        self._m_prefix_hit.set(sample.prefix_hit_rate or 0.0)
        violated = (sample.queue_depth >= self.policy_cfg.queue_high
                    or (sample.ttft_p99_s is not None
                        and sample.ttft_p99_s > self.policy_cfg.ttft_slo_s))
        if violated:
            dt = max(now_s - st["last_tick"], 0.0)
            st["slo_violation_s"] += dt
            self._m_violation.inc(dt)
        st["last_tick"] = now_s

        decision = self.policy.decide(sample)
        self._m_decisions.labels(action=decision.action).inc()
        self.decisions.append({
            "step": st["decode_steps"], "now_s": round(now_s, 4),
            "action": decision.action, "reason": decision.reason,
            "target_size": decision.target_size,
            "evict_rank": decision.evict_rank})
        rec = _spans.recorder()
        self._evict_candidate = None  # consumed by this decision
        if decision.is_hold:
            rec.add("ctl", 0.0, leg="ctl/hold")
            return
        with rec.span("ctl", name=f"decision:{decision.action}",
                      leg=f"ctl/{decision.action}/{decision.reason}"):
            self._apply(decision, now)
        self.policy.mark_applied(decision, now_s)

    # -- decision execution ------------------------------------------------
    def _apply(self, decision: Decision, now) -> None:
        if decision.evict_rank is not None:
            r = decision.evict_rank
            if r in self.healthy:
                self.healthy.remove(r)
            self.evicted.append(r)
            self.monitor.evict(r)
            self._slow.pop(r, None)
            self._m_evictions.labels(reason="straggler").inc()
            self._m_healthy.set(len(self.healthy))
        if decision.reason.startswith("rank-dead"):
            for r in sorted(self.dead - self._handled_dead):
                self._handled_dead.add(r)
                self.monitor.evict(r)
                self._m_evictions.labels(reason="dead").inc()
        # A dead rank invalidates the old mesh: no completion drain, go
        # straight to suspend + re-prefill on the survivors.  Growth
        # should add capacity now, not after a drain.  Only a voluntary
        # shrink (and a straggler eviction, whose old mesh is merely
        # slow) earns the completion budget.
        hard = decision.reason.startswith("rank-dead")
        budget = 0 if (hard or decision.action == "grow") \
            else self.policy_cfg.drain_steps
        self._transition(decision, now, drain_budget=budget,
                         decode_ok=not hard)

    def _transition(self, decision: Decision, now, *,
                    drain_budget: int, decode_ok: bool) -> None:
        eng = self.engine
        sched = eng.scheduler
        st = self._stats
        old_ranks = list(self.mesh_ranks)
        new_ranks = self.healthy[:decision.target_size]

        sched.pause_admission()
        for slot in list(sched.active):
            sched.mark_draining(slot)
        # A prompt still going in chunk by chunk has no token to be
        # suspended by: it goes in whole first.
        while eng._chunking:
            eng.join(st, (), now)
        eng.catch_up(st, now)
        done_before = len(st["completed"])
        steps = 0
        while sched.active and decode_ok and steps < drain_budget:
            self._decode_once(now)
            steps += 1
        finished = len(st["completed"]) - done_before
        st["drained_completed"] += finished
        if finished:
            self._m_drained.labels(path="completed").inc(finished)

        suspended = [sched.suspend(slot)
                     for slot in sorted(sched.active)]
        st["drained_reprefilled"] += len(suspended)
        # Exact-release check: suspension freed every slot's pages, so
        # a sweep over the old pool must recover nothing.
        st["drain_leaked_pages"] += eng.cache.release_all()

        self._pending = (new_ranks, suspended)
        from ..elastic.run_loop import apply_resize
        if len(new_ranks) == len(old_ranks):
            # Same size, different devices (a spare replaced a dead or
            # evicted rank): apply_resize's size gate would skip the
            # swap, so rebuild first; it still runs on_reset.
            self._rebuild(new_ranks, direction="swap")
        apply_resize(_MeshResizeState(self), len(old_ranks),
                     len(new_ranks))

    def _do_resize(self, old_size: int, new_size: int) -> str:
        new_ranks, _ = self._pending
        direction = "grow" if new_size > old_size else "shrink"
        self._rebuild(new_ranks, direction=direction)
        return (f"serving mesh {direction} {old_size} -> {new_size} "
                f"(ranks {list(new_ranks)})")

    def _rebuild(self, new_ranks: List[int], *, direction: str) -> None:
        # Ranks leaving the mesh stop reporting; forget their EWMAs so
        # a stale-fast spare doesn't inflate everyone else's lateness
        # (and a stale-slow one doesn't read as a straggler forever).
        for r in set(self.mesh_ranks) - set(new_ranks):
            self.monitor.evict(r)
        self.mesh_ranks = list(new_ranks)
        self.engine.rebuild_mesh(self._mesh(new_ranks))
        self._monitor_warmup = 1  # next step pays the recompile
        self._m_resizes.labels(direction=direction).inc()
        self._m_mesh_size.set(len(new_ranks))
        self._stats["resizes"] += 1

    def _on_reset(self) -> None:
        if self._pending is None:
            return
        _, suspended = self._pending
        self._pending = None
        eng = self.engine
        sched = eng.scheduler
        st = self._stats
        for req in suspended:
            slot = sched.restore(req)
            st["last_tokens"][slot] = eng.re_prefill(slot, req)
            st["adapter_ids"][slot] = req.adapter_id
            self._m_drained.labels(path="reprefill").inc()
        sched.resume_admission()

    # -- the closed loop ---------------------------------------------------
    def serve(self, requests: Sequence[Request]) -> ControlPlaneReport:
        eng = self.engine
        sched = eng.scheduler
        mesh_size_initial = len(self.mesh_ranks)
        pending = sorted(requests, key=lambda r: r.arrival_s)
        rejected = 0
        waiting: List[Request] = []
        for req in pending:
            if req.prompt_len + req.max_new_tokens > eng.max_len:
                rejected += 1
                sched._m_requests.labels(event="rejected").inc()
            else:
                waiting.append(req)

        start = time.monotonic()
        skip = [0.0]

        def now() -> float:
            return time.monotonic() - start + skip[0]

        snap_fn = getattr(sched._m_ttft, "snapshot", None)
        self.decisions = []
        st = self._stats = dict(
            eng.run_state(), last_tick=0.0, slo_violation_s=0.0,
            drained_completed=0, drained_reprefilled=0,
            drain_leaked_pages=0, resizes=0,
            ttft_base=snap_fn() if snap_fn is not None else None)
        i = 0

        while True:
            while i < len(waiting) and waiting[i].arrival_s <= now():
                sched.submit(waiting[i])
                i += 1
            if not sched.has_work():
                if i >= len(waiting):
                    break
                gap = waiting[i].arrival_s - now()
                if gap > 0:
                    skip[0] += gap
                self._tick(now)
                continue

            eng.join(st, [(slot, req, jnp.asarray(req.prompt, jnp.int32))
                          for slot, req in sched.admit(now())], now)
            if eng._decode_slots():
                step = st["decode_steps"] + 1
                self._fire_faults(step, now())
                step_s = self._decode_once(now)
                self._feed_monitor(step, step_s)
            else:
                # Nothing to dispatch: what joined is done with its
                # first token, or is still going in chunk by chunk.
                eng.catch_up(st, now)
            self._tick(now)

        wall_s = max(time.monotonic() - start, 1e-9)
        completed = st["completed"]
        new_tokens = sum(len(r.tokens) for r in completed)
        ttfts = [r.ttft_s for r in completed if r.ttft_s is not None]
        lats = [g for r in completed for g in r.token_gaps]
        serving = ServingReport(
            num_requests=len(requests), completed=len(completed),
            rejected=rejected,
            prompt_tokens=sum(r.prompt_len for r in completed),
            new_tokens=new_tokens, wall_s=wall_s,
            decode_steps=st["decode_steps"],
            tokens_per_s=new_tokens / wall_s,
            ttft_p50_s=_pct(ttfts, 50), ttft_p99_s=_pct(ttfts, 99),
            token_latency_p50_s=_pct(lats, 50),
            token_latency_p99_s=_pct(lats, 99),
            mean_occupancy=(float(np.mean(st["occ_samples"]))
                            if st["occ_samples"] else 0.0))
        counts: Dict[str, int] = {}
        for d in self.decisions:
            counts[d["action"]] = counts.get(d["action"], 0) + 1
        return ControlPlaneReport(
            serving=serving,
            mesh_size_initial=mesh_size_initial,
            mesh_size_final=len(self.mesh_ranks),
            decisions=list(self.decisions),
            decision_counts=counts,
            resizes=st["resizes"],
            evicted_ranks=list(self.evicted),
            dead_ranks=sorted(self.dead),
            drained_completed=st["drained_completed"],
            drained_reprefilled=st["drained_reprefilled"],
            drain_leaked_pages=st["drain_leaked_pages"],
            slo_violation_s=st["slo_violation_s"],
            lost_requests=(len(requests) - rejected - len(completed)))


class FleetScaler:
    """Grow-by-adding-capacity controller for a disaggregated fleet.

    The per-engine :class:`ServingControlPlane` resizes ONE engine's tp
    mesh; the fleet scaler watches the SAME SLO signals summed across
    every decode engine and, on a sustained breach, asks the fleet to
    commission a whole new decode engine under live traffic
    (``fleet.add_decode_worker``).  The fleet object is duck-typed --
    anything with ``schedulers()`` (name -> Scheduler), ``num_engines``
    and ``add_decode_worker(reason)`` works -- so this module never
    imports :mod:`.fleet` (which imports us for exactly this class).

    TTFT p99 is windowed fleet-wide: all engines observe into the one
    shared ``horovod_serving_ttft_seconds`` histogram, and the scaler
    keeps its own snapshot base so each tick sees only the TTFTs that
    landed since the previous tick (the ``ServingControlPlane._sample``
    pattern).
    """

    def __init__(self, fleet, policy: Optional["FleetPolicy"] = None):
        from .policy import FleetPolicy
        self.fleet = fleet
        self.policy = policy or FleetPolicy()
        self.decisions: List[dict] = []
        self.slo_violation_s = 0.0
        self._last_tick = 0.0
        self._ttft_base: Any = None
        reg = _metrics.registry()
        self._m_decisions = reg.counter(
            "horovod_fleet_decisions_total",
            "Fleet scaler decisions by action", labelnames=("action",))
        self._m_violation = reg.counter(
            "horovod_fleet_slo_violation_seconds_total",
            "Cumulative seconds the fleet spent outside its SLO")
        self._m_ttft_p99 = reg.gauge(
            "horovod_fleet_ttft_p99_seconds",
            "Fleet-wide windowed TTFT p99 seen by the scaler")

    def _fleet_p99(self) -> Optional[float]:
        scheds = list(self.fleet.schedulers().values())
        if not scheds:
            return None
        snap_fn = getattr(scheds[0]._m_ttft, "snapshot", None)
        if snap_fn is None:
            return None
        curr = snap_fn()
        win = _metrics.histogram_window(curr, self._ttft_base)
        self._ttft_base = curr
        return _metrics.histogram_quantile(win, 0.99)

    def sample(self, now_s: float) -> "FleetSample":
        from .policy import FleetSample
        scheds = self.fleet.schedulers()
        queued = sum(len(s.queue) for s in scheds.values())
        occ = (float(np.mean([s.occupancy for s in scheds.values()]))
               if scheds else 0.0)
        p99 = self._fleet_p99()
        self._m_ttft_p99.set(p99 or 0.0)
        return FleetSample(now_s=now_s, queue_depth=queued,
                           ttft_p99_s=p99, occupancy=occ,
                           engines=self.fleet.num_engines)

    def tick(self, now_s: float) -> Decision:
        cfg = self.policy.config
        if now_s - self._last_tick < cfg.interval_s:
            return Decision("hold", "interval")
        sample = self.sample(now_s)
        violated = (sample.queue_depth >= cfg.queue_high
                    or (sample.ttft_p99_s is not None
                        and sample.ttft_p99_s > cfg.ttft_slo_s))
        if violated:
            dt = max(now_s - self._last_tick, 0.0)
            self.slo_violation_s += dt
            self._m_violation.inc(dt)
        self._last_tick = now_s

        decision = self.policy.decide(sample)
        self._m_decisions.labels(action=decision.action).inc()
        self.decisions.append({
            "now_s": round(now_s, 4), "action": decision.action,
            "reason": decision.reason,
            "target_size": decision.target_size,
            "queue_depth": sample.queue_depth,
            "ttft_p99_s": sample.ttft_p99_s})
        if decision.is_hold:
            return decision
        rec = _spans.recorder()
        with rec.span("ctl", name=f"fleet:{decision.action}",
                      leg=f"ctl/{decision.action}/{decision.reason}"):
            self.fleet.add_decode_worker(decision.reason)
        self.policy.mark_applied(decision, now_s)
        return decision
