"""Disaggregated serving fleet: prefill workers + decode workers.

Prefill is compute-bound (one big batched matmul over the whole
prompt); decode is bandwidth-bound (one token per step over resident
KV).  Colocating them on one mesh serializes the two regimes: every
admitted kilotoken prompt stalls the decode batch for a full prefill.
The fleet splits them -- prefill workers on their own (virtual) mesh
run the model's prefill (``LayerSpec.prefill``) and EXPORT the finished pages;
decode workers import those pages into their own
:class:`~.kvcache.PagedKVCache` and never burn a step on prompt math.

The only coupling is data: pages travel as :mod:`.kvwire` payloads
over the rendezvous KV plane (``run/http_kv.py`` chunked PUT/GET,
riding the PR 7 ``RetryPolicy``), and the f32 wire tier is bitwise, so
a disaggregated decode stream is bit-for-bit the colocated engine's
stream (per-slot logits are independent of batch composition -- the
PR 12 invariant -- and the imported pool bytes are identical).

Handoff lifecycle on the decode side::

    queued -> prefill -> handoff -> decode -> done
                 |          |
                 |          +-- pages in flight; slot occupied but
                 |              excluded from the decode batch
                 +-- admission assigned the slot; the fleet dispatched
                     the prompt to a prefill worker

A dead prefill worker (chaos ``kill``) degrades, never wedges: its
un-imported tickets' KV entries vanish, the decode worker's import
sees no manifest and falls back to a LOCAL prefill of the same prompt
(``handoffs_local``) -- the stream stays correct, only the offload is
lost.

The fleet's wall-clock model: workers are separate hosts, so one
driver-process iteration that runs prefill worker A 3ms and decode
worker B 5ms models 5ms of fleet time, not 8ms.  The serve loop keeps
the engines' virtual-clock discipline and *rebates* the serialized
remainder each iteration (``skip -= iter_real - max(per-host busy)``),
so tokens/s is measured against modeled concurrent wall with REAL
kernel timings.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..timeline import spans as _spans
from ..timeline.metrics import registry as _registry
from .controlplane import FleetScaler
from .decode import greedy_sample
from .engine import ServingEngine, _pct
from .kvwire import decode_kv, encode_kv, import_pages, wire_tier
from .layerspec import layer_spec
from .router import FleetRouter
from .scheduler import Request

__all__ = ["HandoffTicket", "PrefillWorker", "DecodeWorker",
           "ServingFleet", "FleetReport"]

_SCOPE = "pages"


@dataclasses.dataclass
class HandoffTicket:
    """One published prefill: the decode side needs only this to join
    the request into its batch (the pages themselves live in the KV
    plane under ``key``)."""

    rid: int
    key: str
    first: int                 # greedy first token (prefill's argmax)
    nbytes: int                # framed payload size on the wire
    worker: str                # prefill worker that produced it
    published_s: float         # virtual-clock publish instant


class PrefillWorker:
    """Prompt-only worker: runs the prefill forward, frames the K/V
    through :mod:`.kvwire`, and publishes it as a chunked KV object.

    The jitted forward mirrors ``ServingEngine._prefill`` exactly
    (same adapter-arg closure, same ``lora_alpha``), so its logits --
    and therefore the first sampled token and every exported K/V byte
    -- are bitwise what a colocated engine would have computed.
    """

    def __init__(self, name: str, config, params, kv, *,
                 page_size: int, dtype=jnp.float32,
                 tier: Optional[str] = None):
        self.name = name
        self.config = config
        self.params = params
        self.kv = kv
        self.page_size = int(page_size)
        self.tier = tier or wire_tier()
        self.alive = True
        self.prefills = 0
        self.busy_s = 0.0

        spec = layer_spec(config)
        spec.require(handoff=True)
        if spec.slot_state is not None:
            raise NotImplementedError(
                "handoff: the KV plane moves pages only, and this model's "
                f"slots keep state beside their pages "
                f"({spec.slot_state_holds})")

        def _fwd(p, toks, ad, aid):
            return spec.prefill(p, toks, dtype=dtype, adapters=ad,
                                adapter_id=aid, lora_alpha=16.0)

        self._fwd = jax.jit(_fwd)

    def run(self, req: Request, prompt_dev, now_s: float
            ) -> HandoffTicket:
        """Prefill ``req``'s prompt and publish its pages; returns the
        ticket the decode side imports against."""
        if not self.alive:
            raise RuntimeError(f"prefill worker {self.name} is dead")
        t0 = time.monotonic()
        with _spans.recorder().span("dispatch", name="fleet_prefill",
                                    leg="serving_fleet_prefill"):
            # Every plane of the prompt's pages (``LayerSpec.planes``
            # leading entries a pool; the second pool may be None).
            logits, kl, vl = self._fwd(self.params, prompt_dev[None],
                                       None, None)
            first = int(greedy_sample(logits[:, -1, :])[0])
            buf = encode_kv(np.asarray(kl[:, 0]),
                            None if vl is None else np.asarray(vl[:, 0]),
                            page_size=self.page_size, tier=self.tier)
        key = f"r{req.rid}"
        self.kv.put_large(_SCOPE, key, buf)
        self.busy_s += time.monotonic() - t0
        self.prefills += 1
        return HandoffTicket(rid=req.rid, key=key, first=first,
                             nbytes=len(buf), worker=self.name,
                             published_s=now_s)


class DecodeWorker:
    """One decode engine plus its per-run state and the import path."""

    def __init__(self, name: str, engine: ServingEngine, kv):
        self.name = name
        self.engine = engine
        self.kv = kv
        self.busy_s = 0.0
        engine.spec.require(handoff=True)
        if engine.cache.state is not None:
            raise NotImplementedError(
                "handoff: the KV plane moves pages only, and this model's "
                "slots keep state beside their pages "
                f"({engine.spec.slot_state_holds})")
        # The auditor's serving configs read step metadata; tag the
        # role so a fleet trace distinguishes decode meshes from the
        # colocated baseline.
        engine.step._meta["fleet_role"] = "decode"
        self.st: Dict[str, Any] = engine.run_state()

    @property
    def scheduler(self):
        return self.engine.scheduler

    def complete_handoff(self, slot: int, req: Request,
                         ticket: HandoffTicket, now) -> Optional[int]:
        """Import a published payload into ``slot`` and join the
        request into the decode batch.  Returns the imported byte
        count, or None when the object is gone (publisher died and its
        entries were reaped) -- the caller falls back to
        :meth:`local_prefill`."""
        t0 = time.monotonic()
        with _spans.recorder().span("dispatch", name="handoff_import",
                                    leg="serving_handoff_import"):
            buf = self.kv.get_large(_SCOPE, ticket.key)
            if buf is None:
                return None
            wp = decode_kv(buf)
            import_pages(self.engine.cache, slot, wp)
            self.engine._join_decode(self.st, slot, req, ticket.first,
                                     now)
        self.kv.delete_large(_SCOPE, ticket.key)
        self.busy_s += time.monotonic() - t0
        return len(buf)

    def local_prefill(self, joins: Sequence[tuple], now) -> float:
        """Fallback: compute here (colocated-style, the engine's one
        join) the prompts no prefill worker can serve, ``(slot, request,
        prompt on the device)`` each, and push this engine's chunked
        prefills forward by a chunk.  Returns the seconds it took."""
        t0 = time.monotonic()
        self.engine.join(self.st, joins, now)
        dt = time.monotonic() - t0
        self.busy_s += dt
        return dt

    def decode_step(self, now) -> float:
        """The engine's one round and the catch-up behind it: the fleet
        imports pages into slots between rounds, so nothing stays in
        flight.  Without a slot to decode, the catch-up alone (a join
        that is done with its first token)."""
        t0 = time.monotonic()
        if self.engine._decode_slots():
            self.engine.decode_once(self.st, now)
        self.engine.catch_up(self.st, now)
        dt = time.monotonic() - t0
        self.busy_s += dt
        return dt


@dataclasses.dataclass
class FleetReport:
    """One fleet run's outcome."""

    num_requests: int
    completed: int
    rejected: int
    prompt_tokens: int
    new_tokens: int
    wall_s: float                      # modeled concurrent wall
    tokens_per_s: float
    ttft_p50_s: float
    ttft_p99_s: float
    decode_steps: int
    engines: int                       # decode engines at end of run
    handoffs_streamed: int
    handoffs_local: int
    migrated: int
    kv_bytes_out: int
    kv_bytes_in: int
    slo_violation_s: float
    leaked_pages: Dict[str, int]       # per decode engine, must be all 0
    refcounts_balanced: bool
    per_engine_completed: Dict[str, int]

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ServingFleet:
    """Router + prefill workers + decode workers on one virtual clock."""

    def __init__(self, prefill_workers: Sequence[PrefillWorker],
                 decode_workers: Sequence[DecodeWorker], kv, *,
                 router: Optional[FleetRouter] = None,
                 scaler_policy=None,
                 engine_factory: Optional[Callable[[], ServingEngine]]
                 = None):
        if not decode_workers:
            raise ValueError("a fleet needs at least one decode worker")
        self.prefill_workers = list(prefill_workers)
        self.decode = {w.name: w for w in decode_workers}
        self.kv = kv
        self.router = router or FleetRouter()
        for name, w in self.decode.items():
            self.router.register(name, w.scheduler)
        self.engine_factory = engine_factory
        self.scaler = (FleetScaler(self, policy=scaler_policy)
                       if scaler_policy is not None else None)
        self.migrated = 0
        self._rr = 0  # round-robin cursor over alive prefill workers
        reg = _registry()
        self._m_handoffs = reg.counter(
            "horovod_fleet_handoffs_total",
            "Prefill->decode handoffs by outcome (streamed = imported "
            "over the KV plane, local = fallback prefill on the decode "
            "mesh)", labelnames=("outcome",))
        self._m_kv_bytes = reg.counter(
            "horovod_fleet_kv_bytes_total",
            "Framed KV-page bytes moved over the rendezvous plane",
            labelnames=("direction",))
        self._m_handoff_lat = reg.histogram(
            "horovod_fleet_handoff_latency_seconds",
            "Publish-to-import latency of streamed handoffs")
        self._m_migrated = reg.counter(
            "horovod_fleet_migrated_total",
            "Queued requests migrated to a freshly commissioned decode "
            "engine")

    # -- FleetScaler duck-type surface -------------------------------------
    def schedulers(self) -> Dict[str, Any]:
        return {n: w.scheduler for n, w in self.decode.items()}

    @property
    def num_engines(self) -> int:
        return len(self.decode)

    def add_decode_worker(self, reason: str = "manual") -> str:
        """Grow-by-adding-capacity: commission a decode engine UNDER
        LIVE TRAFFIC.  The new engine is built by ``engine_factory``
        (same mesh spec as its siblings, so the exchange-plan compile
        cache makes the bring-up a fingerprint hit), registered with
        the router, and seeded by migrating half of the most-loaded
        sibling's queue -- arrivals it has not started are the only
        thing that moves; in-flight slots stay put."""
        if self.engine_factory is None:
            raise RuntimeError(
                "fleet has no engine_factory; cannot add capacity")
        name = f"decode{len(self.decode)}"
        worker = DecodeWorker(name, self.engine_factory(), self.kv)
        self.decode[name] = worker
        self.router.register(name, worker.scheduler)
        donor = max((w for n, w in self.decode.items() if n != name),
                    key=lambda w: len(w.scheduler.queue))
        moved = 0
        dq, nq = donor.scheduler.queue, worker.scheduler.queue
        for _ in range(len(dq) // 2):
            nq.append(dq.pop())   # newest arrivals re-home
            moved += 1
        donor.scheduler._update_gauges()
        worker.scheduler._update_gauges()
        self.migrated += moved
        self._m_migrated.inc(moved)
        _spans.recorder().add("ctl", 0.0,
                              leg=f"ctl/add-engine/{reason}")
        return name

    def kill_prefill(self, name: str) -> int:
        """Chaos: a prefill host dies.  Published-but-unimported
        objects it owns are reaped from the KV plane (their manifests
        vanish mid-handoff), so the decode side exercises the
        lost-object fallback.  Returns how many tickets were reaped."""
        reaped = 0
        for w in self.prefill_workers:
            if w.name == name and w.alive:
                w.alive = False
                for h in self._in_flight:
                    if h["ticket"].worker == name and not h["done"]:
                        self.kv.delete_large(_SCOPE, h["ticket"].key)
                        reaped += 1
        return reaped

    def _alive_prefill(self) -> List[PrefillWorker]:
        return [w for w in self.prefill_workers if w.alive]

    # -- the serve loop ----------------------------------------------------
    def serve(self, requests: Sequence[Request], *,
              kill_prefill_at_step: Optional[int] = None,
              kill_prefill_name: Optional[str] = None) -> FleetReport:
        """Run the open-loop stream across the fleet to completion."""
        pending = sorted(requests, key=lambda r: r.arrival_s)
        rejected = 0
        admissible: List[Request] = []
        for req in pending:
            cap = min(w.engine.max_len for w in self.decode.values())
            if req.prompt_len + req.max_new_tokens > cap:
                rejected += 1
            else:
                admissible.append(req)
        feed = list(admissible)
        fi = 0

        # Worker state persists across serve() calls (sessions may span
        # runs); the report must cover THIS run only, so snapshot the
        # accumulators and count deltas.
        base_completed = {n: len(w.st["completed"])
                          for n, w in self.decode.items()}
        base_steps = {n: w.st["decode_steps"]
                      for n, w in self.decode.items()}
        base_migrated = self.migrated

        start = time.monotonic()
        skip = 0.0

        def now() -> float:
            return time.monotonic() - start + skip

        prompts_dev: Dict[int, Any] = {}
        # Streamed handoffs move through three iteration phases:
        # dispatched (this iter) -> imported (next iter) -> done.  The
        # one-iteration gap keeps the ``handoff`` slot state visible
        # across at least one decode round, like a real network hop.
        self._in_flight: List[dict] = []
        handoffs_streamed = 0
        handoffs_local = 0
        kv_out = 0
        kv_in = 0
        overhead = 0.0   # serialized-in-driver time rebated each iter
        step = 0

        while True:
            step += 1
            iter_t0 = time.monotonic()
            busy: Dict[str, float] = {}

            # 1. Arrivals: route each due request to a decode engine.
            while fi < len(feed) and feed[fi].arrival_s <= now():
                req = feed[fi]
                fi += 1
                prompts_dev[req.rid] = jax.device_put(
                    jnp.asarray(req.prompt, jnp.int32))
                engine, _reason = self.router.route(req)
                self.decode[engine].scheduler.submit(req)

            # 2. Chaos fault.
            if kill_prefill_at_step is not None \
                    and step == kill_prefill_at_step:
                victim = (kill_prefill_name
                          or self.prefill_workers[0].name)
                self.kill_prefill(victim)

            # 3. Import last iteration's in-flight pages.  ``local``: the
            # joins each decode engine computes itself this iteration.
            local: Dict[str, List[tuple]] = {n: [] for n in self.decode}
            for h in self._in_flight:
                w = self.decode[h["engine"]]
                t0 = time.monotonic()
                got = w.complete_handoff(h["slot"], h["req"],
                                         h["ticket"], now)
                if got is None:
                    # Publisher died and its object was reaped: the
                    # prompt is re-computed locally; the stream stays
                    # correct, only the offload is lost.
                    local[h["engine"]].append(
                        (h["slot"], h["req"], prompts_dev[h["req"].rid]))
                    handoffs_local += 1
                    self._m_handoffs.labels(outcome="local").inc()
                else:
                    kv_in += got
                    self._m_kv_bytes.labels(direction="in").inc(got)
                    handoffs_streamed += 1
                    self._m_handoffs.labels(outcome="streamed").inc()
                    self._m_handoff_lat.observe(
                        max(now() - h["ticket"].published_s, 0.0))
                prompts_dev.pop(h["req"].rid, None)
                h["done"] = True
                busy[h["engine"]] = busy.get(h["engine"], 0.0) \
                    + (time.monotonic() - t0)
            self._in_flight.clear()

            # 4. Admissions: new slots go to handoff (remote prefill)
            # or straight to a local prefill when no worker is alive.
            dispatch: List[dict] = []
            for name, w in self.decode.items():
                for slot, req in w.scheduler.admit(now()):
                    if self._alive_prefill():
                        w.scheduler.note_handoff(req)
                        dispatch.append({"engine": name, "slot": slot,
                                         "req": req})
                    else:
                        local[name].append(
                            (slot, req, prompts_dev.pop(req.rid)))
                        handoffs_local += 1
                        self._m_handoffs.labels(outcome="local").inc()
                busy[name] = busy.get(name, 0.0) \
                    + w.local_prefill(local[name], now)

            # 5. Dispatch prefills round-robin over alive workers.
            for d in dispatch:
                workers = self._alive_prefill()
                w = workers[self._rr % len(workers)]
                self._rr += 1
                t0 = time.monotonic()
                ticket = w.run(d["req"], prompts_dev[d["req"].rid],
                               now())
                kv_out += ticket.nbytes
                self._m_kv_bytes.labels(direction="out").inc(
                    ticket.nbytes)
                host = f"prefill:{w.name}"
                busy[host] = busy.get(host, 0.0) \
                    + (time.monotonic() - t0)
                d["ticket"] = ticket
                d["done"] = False
                self._in_flight.append(d)

            # 6. One decode round per engine with live decode slots.
            for name, w in self.decode.items():
                busy[name] = busy.get(name, 0.0) + w.decode_step(now)

            # 7. Fleet controller.
            if self.scaler is not None:
                self.scaler.tick(now())

            # 8. Clock rebate: hosts ran concurrently, so the fleet
            # only aged by the busiest host's time this iteration.
            iter_real = time.monotonic() - iter_t0
            model = min(max(busy.values(), default=0.0), iter_real)
            overhead += iter_real - model
            skip -= (iter_real - model)

            has_work = (self._in_flight
                        or any(w.scheduler.has_work()
                               for w in self.decode.values()))
            if not has_work:
                if fi >= len(feed):
                    break
                gap = feed[fi].arrival_s - now()
                if gap > 0:
                    skip += gap

        wall_s = max(time.monotonic() - start - overhead, 1e-9)
        # End-of-run leak gate, per decode engine: drop the radix
        # tree's own refs, then every page must return to the pool.
        leaked: Dict[str, int] = {}
        balanced = True
        per_engine: Dict[str, int] = {}
        completed: List[Request] = []
        for name, w in self.decode.items():
            if w.engine._prefix is not None:
                w.engine._prefix.drop_all()
            leaked[name] = w.engine.cache.release_all()
            balanced = balanced and w.engine.cache.refcounts_balanced()
            done = w.st["completed"][base_completed.get(name, 0):]
            per_engine[name] = len(done)
            completed.extend(done)

        new_tokens = sum(len(r.tokens) for r in completed)
        ttfts = [r.ttft_s for r in completed if r.ttft_s is not None]
        return FleetReport(
            num_requests=len(requests), completed=len(completed),
            rejected=rejected,
            prompt_tokens=sum(r.prompt_len for r in completed),
            new_tokens=new_tokens, wall_s=wall_s,
            tokens_per_s=new_tokens / wall_s,
            ttft_p50_s=_pct(ttfts, 50), ttft_p99_s=_pct(ttfts, 99),
            decode_steps=sum(w.st["decode_steps"] - base_steps.get(n, 0)
                             for n, w in self.decode.items()),
            engines=len(self.decode),
            handoffs_streamed=handoffs_streamed,
            handoffs_local=handoffs_local,
            migrated=self.migrated - base_migrated,
            kv_bytes_out=kv_out, kv_bytes_in=kv_in,
            slo_violation_s=(self.scaler.slo_violation_s
                             if self.scaler else 0.0),
            leaked_pages=leaked, refcounts_balanced=balanced,
            per_engine_completed=per_engine)
