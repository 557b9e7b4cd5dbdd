"""Online autotuning of fusion threshold + cycle time (ParameterManager).

The reference (``horovod/common/parameter_manager.cc`` driving the GP
Bayesian optimization in ``optim/bayesian_optimization.cc``) tunes the
fusion threshold and cycle time against observed throughput, with rank 0
deciding and broadcasting so every rank applies identical values.  Same
architecture here:

* the tunable surface is the gradient bucket size (``fusion_threshold``)
  and -- when the native cycle scheduler is active (torch shim) -- the
  cycle time.  The bucket size shapes a step only where buckets are
  built (``fusion.exchange_needs_vector``, ZeRO-1, the microbatched
  step); on the leaf-wise exchange it is inert, and a sample of such a
  step scores every candidate that differs in the threshold alone
  (``record_step(threshold_inert=True)``);
* scoring is observed bytes/sec over ``steps_per_sample`` steps;
* the search is expected-improvement Bayesian optimization over a
  discrete grid (:mod:`horovod_tpu.autotune.gp`), seeded with a strided
  warmup.  Discrete because every distinct fusion threshold costs one
  XLA retrace -- a continuum would thrash the executable cache;
* in multi-process mode rank 0's decisions are pickle-broadcast at
  sample boundaries (the reference's coordinator-decides model), so SPMD
  processes never cut divergent buckets while tuning;
* ``HOROVOD_AUTOTUNE=1`` enables, ``HOROVOD_AUTOTUNE_LOG`` persists the
  sampled configurations as CSV and warm-starts the next run (reference
  warm-start file behavior).

Round 3 widened the surface to the reference ParameterManager's other
knobs where a real choice survives under XLA:

* **hierarchical allreduce** (on 2-axis (dcn, ici) meshes only): XLA's
  own schedule for a both-axes ``psum`` vs the explicit two-level
  reduce-scatter/DCN-allreduce/allgather
  (:func:`~horovod_tpu.collectives.ops.hierarchical_allreduce`);
* **compression codec** (OPT-IN via ``HOROVOD_AUTOTUNE_COMPRESSION=1``,
  because it changes wire numerics): configured default vs bf16 vs fp16
  vs fp8 (e4m3 exchange-level codec, ``compression.py``).  PR 5 extends
  the same axis with error-feedback codec candidates via
  ``HOROVOD_AUTOTUNE_CODEC=powersgd:<r>,topk:<f>,...`` (probed in their
  stateless form -- see ``Autotuner.__init__``);
* **ZeRO exchange** (OPT-IN via ``HOROVOD_AUTOTUNE_ZERO=1`` on a
  ``HOROVOD_ZERO=1`` run): reduce-scatter + allgather vs allreduce
  gradient exchange over the sharded arena (``optim/zero.py``) -- the
  state layout is fixed at step build time, so the axis only opens when
  the run is zero-configured.

PR 2 adds the latency-hiding axes:

* **exchange chunk size** (OPT-IN via ``HOROVOD_AUTOTUNE_CHUNK=1``,
  because scatter-reduce chunks change reduction order): 0 (monolithic
  bucket allreduce) vs chunked reduce-scatter + all-gather exchange
  (``collectives/ops.py::chunked_allreduce``).  Trace-time: flows
  through :meth:`Autotuner.trace_key`.
* **steps per execution** (OPT-IN via
  ``HOROVOD_AUTOTUNE_STEPS_PER_EXEC=1``): how many train steps
  ``make_train_loop`` compiles into one ``lax.scan`` executable.  This
  is a BUILD-time structural knob -- it changes the loop's input shapes
  -- so it is NOT part of ``trace_key()``; ``make_train_loop`` reads
  :meth:`Autotuner.steps_per_exec` when it is (re)built, and the score
  loop in ``training._maybe_tuned`` normalizes per-step time by k.

PR 3 adds the backward-overlap axis:

* **microbatches** (OPT-IN via ``HOROVOD_AUTOTUNE_MICROBATCH=1``): how
  many sub-batches the train step splits the batch into for the
  per-bucket comm/compute overlap (``training.py``, ``microbatches=``).
  BUILD-time like steps-per-exec (k changes the unrolled step
  structure), so it is excluded from ``trace_key()``; closed on
  zero-configured runs (the two exchanges are build-time exclusive).

The response-cache toggle stays collapsed: an executable-cache hit is
always strictly cheaper than a retrace, so there is nothing to search.
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

from .gp import BayesianOptimizer

_MiB = 1024 * 1024
_THRESHOLDS = [2 * _MiB, 8 * _MiB, 32 * _MiB, 64 * _MiB, 128 * _MiB]
_CYCLES_MS = [0.5, 1.0, 5.0]
MAX_SAMPLES = 12
# Compression axis encoding (grid value -> codec); 0 keeps whatever the
# optimizer was configured with.  Codes >= COMP_CODEC_BASE are
# error-feedback codec candidates from HOROVOD_AUTOTUNE_CODEC, positional
# in that comma list (see Autotuner.__init__).
COMP_DEFAULT, COMP_BF16, COMP_FP16, COMP_FP8 = 0, 1, 2, 3
COMP_CODEC_BASE = 4
# Hierarchical DCN-leg codec axis encoding (grid member 8): what rides
# the cross-slice hop of the two-level exchange when the hierarchical
# axis is on.  0 keeps the sample's plain codec on every leg.
HIER_DCN_NONE, HIER_DCN_BF16, HIER_DCN_FP16, HIER_DCN_FP8 = 0, 1, 2, 3
# MoE all_to_all codec axis encoding (grid member 9): the wire dtype of
# the dispatch/combine shuffle in ``parallel.moe.moe_ffn`` (PR 18).
MOE_NONE, MOE_BF16, MOE_FP16 = 0, 1, 2
_MOE_CODES = {MOE_NONE: "none", MOE_BF16: "bf16", MOE_FP16: "fp16"}


def _grid(thresholds, cycles, hiers, comps, zeros, chunks, steps, micros,
          hcodecs, moes) -> List[Tuple[int, float, int, int, int, int, int,
                                       int, int, int]]:
    # A DCN-leg codec without the hierarchical schedule is meaningless
    # (there is no separate DCN hop to compress), so those combinations
    # are pruned rather than burning sample budget re-measuring the flat
    # exchange.
    return [(t, c, h, k, z, ch, sp, mb, hc, mo) for t in thresholds
            for c in cycles for h in hiers for k in comps for z in zeros
            for ch in chunks for sp in steps for mb in micros
            for hc in hcodecs for mo in moes if not (h == 0 and hc != 0)]


def modeled_exchange_seconds(payload_bytes: float, *, n_dcn: int,
                             n_ici: int, hierarchical: bool,
                             ici_bw: float, dcn_bw: float,
                             ici_wire_scale: float = 1.0,
                             dcn_wire_scale: float = 1.0,
                             quantize_s: float = 0.0,
                             phase_overhead_s: float = 0.0) -> float:
    """Analytic per-link ring cost of one gradient exchange.

    The candidate scorer for the hierarchical/per-leg-codec axes when no
    wall clock is trustworthy (dry runs, the committed autotune demo):
    a flat ring moves ``2 (n-1)/n * bytes`` over the SLOWEST link it
    crosses, while the two-level schedule moves the full payload over ICI
    and only the ``1/n_ici`` shard over DCN -- with each leg's wire bytes
    scaled by that leg's codec (``*_wire_scale``).  ``quantize_s`` prices
    the codec's cast/quantize work, ``phase_overhead_s`` one collective
    launch (the hierarchical schedule pays two extra phases).
    """
    n = n_dcn * n_ici
    if hierarchical and n_dcn > 1:
        return (2 * (n_ici - 1) / n_ici * payload_bytes * ici_wire_scale
                / ici_bw
                + 2 * (n_dcn - 1) / n_dcn
                * (payload_bytes * dcn_wire_scale / n_ici) / dcn_bw
                + 2 * phase_overhead_s + quantize_s)
    return (2 * (n - 1) / n * payload_bytes * ici_wire_scale
            / min(ici_bw, dcn_bw) + phase_overhead_s + quantize_s)


def _mesh_is_two_level() -> bool:
    """True when the initialized mesh has two non-trivial axes (a real
    dcn x ici factorization) -- otherwise the hierarchical knob has
    nothing to choose between."""
    from ..core.state import global_state
    mesh = global_state().mesh
    return (mesh is not None and len(mesh.axis_names) == 2
            and all(s > 1 for s in mesh.devices.shape))


class Autotuner:
    """Feed ``record_step(seconds, nbytes)`` per training step; read the
    current ``fusion_threshold()`` / ``cycle_time_ms()``."""

    def __init__(self, config, steps_per_sample: int = 10,
                 candidates: Optional[List[int]] = None,
                 max_samples: int = MAX_SAMPLES,
                 cycle_candidates: Optional[List[float]] = None):
        self.candidates = list(candidates or _THRESHOLDS)
        if config.fusion_threshold not in self.candidates:
            self.candidates.append(config.fusion_threshold)
        import sys
        # The cycle-time axis only matters when the native cycle scheduler
        # (torch shim grad batching) is in play; tuning it in a pure-JAX
        # run would burn most of the sample budget re-measuring identical
        # configurations under noise.  ``cycle_candidates`` pins the axis
        # explicitly (the resident-module heuristic sees every import the
        # process ever made, not whether THIS run drives the shim).
        if cycle_candidates is not None:
            cycles = list(cycle_candidates)
        else:
            torch_shim = ("horovod_tpu.torch_api" in sys.modules
                          or "horovod_tpu.torch" in sys.modules)
            cycles = list(_CYCLES_MS) if torch_shim else []
        if config.cycle_time not in cycles:
            cycles.append(config.cycle_time)
        # Hierarchical-allreduce choice only exists on a true 2-level
        # mesh; compression retuning is opt-in (it changes numerics).
        hiers = [0, 1] if _mesh_is_two_level() else \
            [1 if config.hierarchical_allreduce else 0]
        from ..core.config import _env, _env_bool
        comps = [COMP_DEFAULT, COMP_BF16, COMP_FP16, COMP_FP8] \
            if _env_bool("AUTOTUNE_COMPRESSION") else [COMP_DEFAULT]
        # Error-feedback codec candidates (HOROVOD_AUTOTUNE_CODEC, a comma
        # list of "powersgd:<rank>" / "topk:<fraction>" specs): each spec
        # extends the compression axis with its own code from
        # COMP_CODEC_BASE upward, mapped back to the compressor by
        # ``compression_override``.  The probe samples run the STATELESS
        # form of the codec (no residual state threads through the tuner),
        # so the score measures wire/ortho cost, not converged quality.
        # Codes above the fixed four are positional in the env list --
        # reorder the list between runs and a warm-start log's codec rows
        # re-seed a different candidate, so keep the list stable.
        self._codec_axis = {}
        codec_spec = _env("AUTOTUNE_CODEC")
        if codec_spec:
            from ..collectives.compression import parse_compression
            for i, tok in enumerate(
                    t.strip() for t in codec_spec.split(",") if t.strip()):
                code = COMP_CODEC_BASE + i
                self._codec_axis[code] = parse_compression(tok)
                if code not in comps:
                    comps.append(code)
        # ZeRO exchange axis (opt-in, HOROVOD_AUTOTUNE_ZERO=1): only a
        # zero-configured run can switch -- the sharded state layout is
        # fixed at step build time, so the searchable pair is the
        # reduce-scatter+allgather exchange (1) vs the allreduce exchange
        # (0) over the same arena (optim/zero.py::_use_reducescatter).
        configured_zero = 1 if getattr(config, "zero_stage", 0) else 0
        self.tunes_zero = bool(_env_bool("AUTOTUNE_ZERO") and
                               configured_zero)
        zeros = [0, 1] if self.tunes_zero else [configured_zero]
        # Chunked-exchange axis (opt-in, HOROVOD_AUTOTUNE_CHUNK=1: scatter-
        # reduce chunks change reduction order): monolithic vs chunked
        # RS+AG exchange (collectives/ops.py::chunked_allreduce).
        configured_chunk = int(getattr(config, "exchange_chunk_bytes", 0))
        if _env_bool("AUTOTUNE_CHUNK"):
            chunks = sorted({0, 4 * _MiB, 16 * _MiB, configured_chunk})
        else:
            chunks = [configured_chunk]
        # Steps-per-execution axis (opt-in,
        # HOROVOD_AUTOTUNE_STEPS_PER_EXEC=1): build-time knob read by
        # make_train_loop, not a trace_key member (it changes the loop's
        # input shapes, so the loop must be rebuilt to apply it).
        configured_steps = max(1, int(getattr(config, "steps_per_exec", 1)))
        if _env_bool("AUTOTUNE_STEPS_PER_EXEC"):
            steps = sorted({1, 4, 16, configured_steps})
        else:
            steps = [configured_steps]
        # Microbatch axis (opt-in, HOROVOD_AUTOTUNE_MICROBATCH=1): the
        # backward-overlap exchange's k (training.py, microbatches=).
        # BUILD-time like steps-per-exec -- k changes the unrolled step
        # structure, so the step is rebuilt, not retraced, and the axis is
        # excluded from trace_key.  Zero-configured runs pin k=1 (the two
        # exchanges are mutually exclusive at build time).
        configured_micro = max(1, int(getattr(config, "microbatches", 1)))
        if _env_bool("AUTOTUNE_MICROBATCH") and not configured_zero:
            micros = sorted({1, 2, 4, configured_micro})
        else:
            micros = [configured_micro]
        # Hierarchical DCN-leg codec axis (opt-in, HOROVOD_AUTOTUNE_HIER=1
        # on a two-level mesh; it changes wire numerics on the cross-slice
        # hop only): which codec rides the DCN leg of the two-level
        # exchange (collectives/ops.py::hierarchical_allreduce's
        # ``dcn_codec``).  The ICI legs keep the sample's plain codec --
        # contended DCN with fast ICI is exactly where per-leg compression
        # pays.
        self.tunes_hier_codec = bool(_env_bool("AUTOTUNE_HIER")
                                     and _mesh_is_two_level())
        hcodecs = [HIER_DCN_NONE, HIER_DCN_BF16, HIER_DCN_FP16,
                   HIER_DCN_FP8] if self.tunes_hier_codec \
            else [HIER_DCN_NONE]
        # MoE all_to_all codec axis (opt-in, HOROVOD_AUTOTUNE_MOE=1; it
        # narrows the expert shuffle's wire numerics): which codec the
        # dispatch/combine all_to_all pair of ``parallel.moe.moe_ffn``
        # casts its slot tensors to.  Trace-time -- the cast is part of
        # the traced step -- so it rides trace_key.  Without the opt-in
        # the axis pins to the configured HOROVOD_MOE_COMPRESSION.
        configured_moe = {v: k for k, v in _MOE_CODES.items()}.get(
            str(getattr(config, "moe_compression", None) or "none").lower(),
            MOE_NONE)
        self.tunes_moe = bool(_env_bool("AUTOTUNE_MOE"))
        moes = [MOE_NONE, MOE_BF16, MOE_FP16] if self.tunes_moe \
            else [configured_moe]
        self.grid = _grid(sorted(self.candidates), sorted(cycles), hiers,
                          comps, zeros, chunks, steps, micros, hcodecs,
                          moes)
        self.steps_per_sample = steps_per_sample
        self.max_samples = min(max_samples, len(self.grid))
        self.log_path = config.autotune_log
        self.warm_start_skipped = 0
        self._opt = BayesianOptimizer(
            [(float(t), c, float(h), float(k), float(z), float(ch),
              float(sp), float(mb), float(hc), float(mo))
             for t, c, h, k, z, ch, sp, mb, hc, mo in self.grid])
        self._samples: List[tuple] = []
        self._best: Optional[Tuple[int, float]] = None
        self._step = 0
        self._accum_s = 0.0
        self._accum_bytes = 0
        # Discard the first recorded step of every sample: a config
        # switch retraces, so that first step carries the XLA
        # compile -- folding it into the score
        # would bury the signal (the reference's ParameterManager
        # likewise scores warm cycles only).
        self._skip_next = True
        self._warm_start()
        self._idx = self._next_index()

    # -- current knobs ----------------------------------------------------
    def _current(self) -> Tuple[int, float, int, int, int, int, int, int,
                                int, int]:
        return self._best or self.grid[self._idx]

    def fusion_threshold(self) -> int:
        return self._current()[0]

    def cycle_time_ms(self) -> float:
        return self._current()[1]

    def hierarchical_explicit(self) -> bool:
        """Use the explicit two-level (dcn, ici) allreduce schedule."""
        return bool(self._current()[2])

    def hier_dcn_codec(self):
        """DCN-leg codec of the current sample (None = no per-leg codec).
        Only meaningful when the hierarchical axis is on -- the grid
        prunes the other combinations."""
        code = int(self._current()[8])
        if not code or not self.hierarchical_explicit():
            return None
        from ..collectives.compression import Compression
        return {HIER_DCN_BF16: Compression.bf16,
                HIER_DCN_FP16: Compression.fp16,
                HIER_DCN_FP8: Compression.fp8}[code]

    def compression_override(self, configured):
        """The codec this sample runs with (``configured`` unless the
        opt-in compression axis picked another).  When the hier DCN-codec
        axis is active, the result is the per-leg composite: the plain
        codec (psum-compatible) on the ICI legs, the axis's codec on the
        DCN hop."""
        from ..collectives.compression import Compression
        k = self._current()[3]
        if k == COMP_BF16:
            override = Compression.bf16
        elif k == COMP_FP16:
            override = Compression.fp16
        elif k == COMP_FP8:
            override = Compression.fp8
        elif k >= COMP_CODEC_BASE:
            override = self._codec_axis[k]
        else:
            override = configured
        hc = self.hier_dcn_codec()
        if hc is not None:
            from ..collectives.compression import (hier_leg_compressor,
                                                   is_hier_legs)
            if is_hier_legs(override):
                return override  # configured per-leg codec wins
            ici = override if (override is not None and getattr(
                override, "wire_format", "") == "") else "none"
            return hier_leg_compressor(ici, hc)
        return override

    def zero_stage(self) -> int:
        """The ZeRO exchange value of the current sample (0 = allreduce
        exchange, 1 = reduce-scatter + allgather; optim/zero.py)."""
        return int(self._current()[4])

    def exchange_chunk_bytes(self) -> int:
        """Chunked-exchange size of the current sample (0 = monolithic
        bucket allreduce; collectives/ops.py::chunked_allreduce)."""
        return int(self._current()[5])

    def steps_per_exec(self) -> int:
        """Scan-loop steps-per-execution of the current sample.  Applied
        when ``make_train_loop`` is (re)built -- a BUILD-time knob, not
        part of :meth:`trace_key` (it changes the loop's input shapes)."""
        return int(self._current()[6])

    def microbatches(self) -> int:
        """Backward-overlap microbatch count of the current sample.
        Applied when a train step is (re)built (``training.microbatches``
        resolver) -- a BUILD-time knob like :meth:`steps_per_exec`, not
        part of :meth:`trace_key`."""
        return int(self._current()[7])

    def moe_codec(self) -> str:
        """MoE all_to_all wire codec of the current sample
        (``"none"``/``"bf16"``/``"fp16"``; ``parallel.moe.moe_ffn``)."""
        return _MOE_CODES[int(self._current()[9])]

    def trace_key(self) -> tuple:
        """The TRACE-TIME knobs of the current sample (the compiled step
        cache in ``training.make_train_step`` keys on this).  Cycle time
        is deliberately excluded: it is a RUNTIME knob applied through
        ``_apply_to_batcher``, and keying on it would recompile an
        identical trace for every cycle-axis sample.  Steps-per-exec and
        microbatches are likewise excluded (build-time structural knobs).
        The MoE codec IS a member: the cast is part of the traced step."""
        thr, _cyc, hier, comp, zero, chunk, _sp, _mb, hc, mo = \
            self._current()
        return (thr, hier, comp, zero, chunk, hc, mo)

    @property
    def done(self) -> bool:
        return self._best is not None

    # -- sampling loop ----------------------------------------------------
    def record_step(self, seconds: float, nbytes: int,
                    threshold_inert: bool = False) -> None:
        """Report one training step's wall time and gradient bytes.

        ``threshold_inert``: the sampled step builds no fusion bucket (the
        leaf-wise exchange of ``allreduce_gradients``: XLA's combiner
        groups its all-reduces), so the candidates that differ from this
        sample in the fusion threshold alone are the same compiled step.
        They take this sample's score and are never run."""
        if self._best is not None:
            return
        if self._skip_next:
            self._skip_next = False  # compile/retrace step: not scored
            return
        self._accum_s += seconds
        self._accum_bytes += nbytes
        self._step += 1
        if self._step < self.steps_per_sample:
            return
        score = self._accum_bytes / max(self._accum_s, 1e-9)  # bytes/s
        self._opt.observe(self._idx, score)
        self._samples.append(self.grid[self._idx] + (score,))
        if threshold_inert:
            here = self.grid[self._idx]
            for j, point in enumerate(self.grid):
                if point[1:] == here[1:] and not self._opt.observed(j):
                    self._opt.observe(j, score)
        from ..timeline import metrics as _metrics
        reg = _metrics.registry()
        reg.counter("horovod_autotune_samples_total",
                    "Autotuner samples scored (one per sample window)"
                    ).inc()
        reg.gauge("horovod_autotune_score_bytes_per_second",
                  "Most recent autotuner sample score").set(score)
        self._step = 0
        self._accum_s = 0.0
        self._accum_bytes = 0
        self._idx = self._next_index()
        self._skip_next = True
        self._apply_to_batcher()

    def _next_index(self) -> int:
        """Pick the next configuration (rank 0 decides; others follow)."""
        if self._opt.n_observed >= self.max_samples:
            self._finish()
            return self._opt.best_index or 0
        nxt = self._sync(self._opt.suggest())
        if nxt is None:
            self._finish()
            return self._opt.best_index or 0
        return nxt

    def _sync(self, value):
        """Broadcast rank 0's decision in multi-process mode (the
        reference's coordinator-decides model): per-rank scores differ,
        and diverging fusion thresholds would cut mismatched buckets."""
        import jax
        if jax.process_count() == 1:
            return value
        from ..optim.functions import broadcast_object
        return broadcast_object(value, root_rank=0)

    def _finish(self) -> None:
        if self._best is not None:
            return
        best = self._sync(self._opt.best_index)
        self._best = self.grid[best if best is not None else 0]
        self._write_log()
        self._apply_to_batcher()

    def _apply_to_batcher(self) -> None:
        """Push current knobs into the native cycle scheduler (torch
        shim), mirroring the ParameterManager owning the C++ knobs."""
        import sys
        mod = sys.modules.get("horovod_tpu.torch_api.batching")
        if mod is None:
            return
        b = mod._batcher
        if b is not None:
            b._sched.update_tuning(self.cycle_time_ms(),
                                   self.fusion_threshold())

    # -- warm start / log -------------------------------------------------
    def _warm_start(self) -> None:
        """Seed the optimizer from the previous run's log.

        Only rank 0 reads the file (it may exist on rank 0's filesystem
        alone); the observation list is broadcast so every process sees
        the identical sampling schedule -- a rank-local read would desync
        the broadcast protocol and deadlock.
        """
        obs: List[tuple] = []
        skipped = 0
        if self.log_path and os.path.exists(self.log_path):
            try:
                with open(self.log_path) as f:
                    lines = list(f)
            except OSError:  # pragma: no cover - unreadable log
                lines = []
            for line in lines:
                if line.startswith(("fusion", "#")) or not line.strip():
                    continue
                parts = line.strip().split(",")
                # Each malformed row is SKIPPED, never fatal: one corrupt
                # line (a half-written row after a crash, a hand edit, a
                # future format) must not throw away the whole warm start
                # or crash the tuner.  Skips are counted and warned once.
                try:
                    if len(parts) == 3:     # pre-round-3 log format
                        cfg = (int(float(parts[0])), float(parts[1]),
                               0, COMP_DEFAULT, 0, 0, 1, 1, 0, 0)
                        score = float(parts[2])
                    elif len(parts) == 5:   # rounds 3-5: no zero axis
                        cfg = (int(float(parts[0])), float(parts[1]),
                               int(float(parts[2])),
                               int(float(parts[3])), 0, 0, 1, 1, 0, 0)
                        score = float(parts[4])
                    elif len(parts) == 6:   # PR-1: zero, no chunk/steps
                        cfg = (int(float(parts[0])), float(parts[1]),
                               int(float(parts[2])),
                               int(float(parts[3])),
                               int(float(parts[4])), 0, 1, 1, 0, 0)
                        score = float(parts[5])
                    elif len(parts) == 8:   # PR-2: chunk + steps axes
                        cfg = (int(float(parts[0])), float(parts[1]),
                               int(float(parts[2])),
                               int(float(parts[3])),
                               int(float(parts[4])),
                               int(float(parts[5])),
                               int(float(parts[6])), 1, 0, 0)
                        score = float(parts[7])
                    elif len(parts) in (9, 10, 11):
                        # PR-3: microbatch axis; PR-11 appends the hier
                        # DCN-codec axis; PR-18 appends the MoE codec
                        # axis.  Positional: missing trailing axes load
                        # as their pre-widening default (0).
                        cfg = (int(float(parts[0])), float(parts[1]),
                               int(float(parts[2])),
                               int(float(parts[3])),
                               int(float(parts[4])),
                               int(float(parts[5])),
                               int(float(parts[6])),
                               int(float(parts[7])),
                               int(float(parts[8]))
                               if len(parts) >= 10 else 0,
                               int(float(parts[9]))
                               if len(parts) == 11 else 0)
                        score = float(parts[-1])
                    else:                   # unknown column count
                        skipped += 1
                        continue
                except ValueError:          # non-numeric cell
                    skipped += 1
                    continue
                if not np.isfinite(score):
                    # A NaN/inf score would poison the GP posterior (every
                    # expected-improvement comparison turns NaN).
                    skipped += 1
                    continue
                if cfg in self.grid:
                    obs.append((self.grid.index(cfg), score))
        if skipped:
            import warnings
            warnings.warn(
                f"autotune warm start: skipped {skipped} unusable row(s) "
                f"in {self.log_path} (unknown column count or NaN/inf "
                "score)", RuntimeWarning, stacklevel=2)
        self.warm_start_skipped = skipped
        obs = self._sync(obs)
        for idx, score in obs:
            self._opt.observe(idx, score)
            # Keep warm rows in _samples so _write_log preserves them --
            # otherwise a warm-started run truncates the log and the
            # warm start survives exactly one restart.
            self._samples.append(self.grid[idx] + (score,))

    def _write_log(self) -> None:
        if not self.log_path:
            return
        with open(self.log_path, "w") as f:
            f.write("fusion_threshold_bytes,cycle_time_ms,hierarchical,"
                    "compression,zero,exchange_chunk_bytes,steps_per_exec,"
                    "microbatches,hier_dcn_codec,moe_codec,"
                    "score_bytes_per_s\n")
            for thr, cyc, hier, comp, zero, chunk, sp, mb, hc, mo, score \
                    in self._samples:
                f.write(f"{thr},{cyc},{hier},{comp},{zero},{chunk},{sp},"
                        f"{mb},{hc},{mo},{score}\n")
            f.write("# best," + ",".join(str(v) for v in self._best) + "\n")
