"""Latency-hiding libtpu flag pack for the backward-overlap exchange.

The microbatched train step (``training.py``, ``microbatches=k``) emits the
per-bucket ``reduce-scatter`` of microbatch *i* between the backward segments
of microbatch *i+1*, but the emitted schedule only turns into *wall-clock*
overlap when the compiler (a) runs collectives asynchronously and (b) uses
the latency-hiding scheduler to sink compute between collective-start and
collective-done.  On TPU those behaviours sit behind libtpu flags that
must be set **before** the backend initialises.

This module assembles the recommended pack and applies it to the process
environment, returning an inspectable :class:`FlagReport` of what was
applied vs. rejected and why.  Design rules:

* **No-op on CPU.**  The flags are TPU-only; on the CPU backend (tests,
  laptops) every flag is rejected with reason ``"cpu backend"`` and the
  environment is left untouched.
* **User flags win.**  A flag the user already set in
  ``LIBTPU_INIT_ARGS`` is never overridden (reason ``"user-set"``).
* **Too late is an error, not a surprise.**  If the JAX backend is already
  initialised the pack cannot take effect; every flag is rejected with
  reason ``"backend already initialized"`` rather than silently exported.

Typical use (before ``horovod_tpu.init()``)::

    from horovod_tpu.core import xla_flags
    report = xla_flags.apply_xla_flags()
    print(report.summary())
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Mapping, MutableMapping, Optional, Tuple

# The pack.  Keyed by the environment variable each flag belongs to;
# values are the full ``--flag=value`` strings appended (space-separated)
# to the variable.  Every flag here is a libtpu flag and rides
# ``LIBTPU_INIT_ARGS``: jaxlib 0.9.0 aborts the process on any of them in
# ``XLA_FLAGS`` ("Unknown flag in XLA_FLAGS"), libtpu 0.0.34 accepts all
# nine and rejects an unknown name ("Unknown command line flag").
XLA_FLAG_PACK: Dict[str, Tuple[str, ...]] = {
    "LIBTPU_INIT_ARGS": (
        # Sink independent compute between collective start/done pairs.
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        # Run all-gathers (the microbatch finalize's single AG) async.
        "--xla_enable_async_all_gather=true",
        "--xla_enable_async_collective_permute=true",
        # Fuse the per-bucket reduce-scatters with surrounding compute into
        # async pairs so backward(i+1) runs during exchange(i).
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
        # Let the tensor cores keep computing while the collective engine
        # drains the wire (the hardware side of backward-overlap).
        "--xla_tpu_overlap_compute_collective_tc=true",
        "--xla_tpu_enable_data_parallel_all_reduce_opt=true",
        "--xla_tpu_data_parallel_opt_different_sized_ops=true",
    ),
}


def _flag_name(flag: str) -> str:
    """``--xla_foo=true`` -> ``--xla_foo`` (identity for valueless flags)."""
    return flag.split("=", 1)[0]


@dataclasses.dataclass(frozen=True)
class FlagReport:
    """What :func:`apply_xla_flags` did, flag by flag.

    ``applied`` maps env-var name to the tuple of flags appended to it;
    ``rejected`` maps each skipped flag to its reason (``"cpu backend"``,
    ``"user-set"``, or ``"backend already initialized"``).
    """

    platform: str
    applied: Dict[str, Tuple[str, ...]]
    rejected: Dict[str, str]

    @property
    def applied_flags(self) -> Tuple[str, ...]:
        return tuple(f for flags in self.applied.values() for f in flags)

    @property
    def is_noop(self) -> bool:
        return not self.applied_flags

    def summary(self) -> str:
        lines = [f"xla_flags: platform={self.platform} "
                 f"applied={len(self.applied_flags)} "
                 f"rejected={len(self.rejected)}"]
        for var, flags in sorted(self.applied.items()):
            for f in flags:
                lines.append(f"  + {var}: {f}")
        for f, why in sorted(self.rejected.items()):
            lines.append(f"  - {f}  ({why})")
        return "\n".join(lines)


def detect_platform(env: Optional[Mapping[str, str]] = None) -> str:
    """Best-effort platform guess from the environment, without importing
    jax (importing jax can itself initialise a backend).

    ``JAX_PLATFORMS`` / ``JAX_PLATFORM_NAME`` win when set; otherwise the
    presence of a libtpu install marks TPU, else ``"cpu"``.
    """
    env = os.environ if env is None else env
    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME"):
        val = env.get(var, "").strip().lower()
        if val:
            # "tpu,cpu" means TPU-first; take the first entry.
            return val.split(",")[0].strip()
    try:
        import importlib.util
        if importlib.util.find_spec("libtpu") is not None:
            return "tpu"
    except (ImportError, ValueError):
        pass
    return "cpu"


def apply_xla_flags(
    env: Optional[MutableMapping[str, str]] = None,
    platform: Optional[str] = None,
    pack: Optional[Mapping[str, Tuple[str, ...]]] = None,
) -> FlagReport:
    """Append the latency-hiding pack to ``env``, honouring the rules in
    the module docstring.  Returns a :class:`FlagReport`; mutates ``env``
    (default ``os.environ``) only for applied flags.
    """
    real_env = env is None
    env = os.environ if env is None else env
    pack = XLA_FLAG_PACK if pack is None else pack
    platform = detect_platform(env) if platform is None else platform
    all_flags = [(var, f) for var, flags in pack.items() for f in flags]

    if platform != "tpu":
        return FlagReport(platform=platform, applied={},
                          rejected={f: "cpu backend" for _, f in all_flags})

    # Only probe the live backend when operating on the real environment;
    # an explicit env dict is a dry run / test harness.
    if real_env:
        from ..utils.platform import backend_initialized
        if backend_initialized():
            return FlagReport(
                platform=platform, applied={},
                rejected={f: "backend already initialized"
                          for _, f in all_flags})

    applied: Dict[str, Tuple[str, ...]] = {}
    rejected: Dict[str, str] = {}
    for var, flags in pack.items():
        existing = env.get(var, "")
        present = {_flag_name(tok) for tok in existing.split() if tok}
        added = []
        for f in flags:
            if _flag_name(f) in present:
                rejected[f] = "user-set"
            else:
                added.append(f)
        if added:
            env[var] = (existing + " " + " ".join(added)).strip()
            applied[var] = tuple(added)
    return FlagReport(platform=platform, applied=applied, rejected=rejected)


_last_report: Optional[FlagReport] = None


def apply(env: Optional[MutableMapping[str, str]] = None,
          platform: Optional[str] = None) -> FlagReport:
    """Convenience wrapper that records the report for later inspection
    via :func:`last_report`."""
    global _last_report
    _last_report = apply_xla_flags(env=env, platform=platform)
    return _last_report


def last_report() -> Optional[FlagReport]:
    return _last_report
