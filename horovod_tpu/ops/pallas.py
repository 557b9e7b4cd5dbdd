"""Unified Pallas kernel switch + the kernel contract registry.

One environment flag, ``HOROVOD_PALLAS`` (``HVD_TPU_PALLAS``), gates
every Pallas kernel family in the package:

- ``auto`` (default): on TPU the families Mosaic has compiled at their
  callers' full-width shapes run as kernels (``flash``,
  ``flash_decode``, ``mla_decode``, ``moe_gmm``, ``ssm_decode``);
  ``fused_update`` and ``bn_bwd`` resolve to the XLA path (see
  ``_AUTO_XLA``).  Off TPU every family takes the XLA reference;
- ``1``: force the kernels everywhere (off-TPU they run in the Pallas
  interpreter -- slow, but numerically the kernel path; this is what the
  CPU parity tests and the CI step audit use);
- ``0``: force the XLA reference everywhere.

Per-family overrides (``HOROVOD_PALLAS_FLASH``, ``HOROVOD_PALLAS_DECODE``,
``HOROVOD_PALLAS_FUSED_UPDATE``, ``HOROVOD_PALLAS_BN``) take the same
values and win over the global flag, so a single family can be pinned
on/off while the rest follow ``HOROVOD_PALLAS``.

The legacy ``HVD_TPU_FLASH`` flag (PR 10) is subsumed: it is still
honored for the ``flash`` family (with a one-shot ``DeprecationWarning``)
but loses to ``HOROVOD_PALLAS_FLASH`` when both are set.

Kernel contracts
----------------

Pallas kernels lower to custom calls that are opaque to anything reading
the step at the HLO level, so each family registers its collective/wire
contract here: the collectives it is allowed to emit (none -- every
exchange stays in XLA where the planner, the PR 8 auditor, and the PR 9
span recorder can see it) and whether it changes any exchange's wire
bytes (never).  ``analysis.stepmodel`` reads this registry to annotate
audited steps instead of declining them, and ``analysis.trace_audit``
enforces the collective-free claim by walking every ``pallas_call``
sub-jaxpr in the traced step.
"""

from __future__ import annotations

import os
import warnings

import jax

# Kernel family -> contract.  ``collectives`` is the multiset of
# collective legs the kernel itself may emit (empty: the exchange stays
# in XLA); ``wire_delta_bytes`` is how the family changes any exchange's
# on-wire payload (always 0 -- e.g. fused_update keeps the PowerSGD P/Q
# factor psums outside the kernels, untouched).
KERNEL_CONTRACTS = {
    "flash": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.attention.flash_attention",
        "note": "flash fwd/bwd kernels, two sets chosen by shape at "
                "trace time (ops.attention._flash_path): blocked "
                "(hvd_flash_fwd / _bwd_dq / _bwd_dkv) and, where one "
                "block holds the sequence, head-group (hvd_flash_hg_fwd "
                "/ _hg_bwd); exchange untouched",
    },
    "flash_decode": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.attention.decode_attention",
        "note": "split-KV decode kernel over a gathered slot view (the "
                "dense verify step and the fp8 path); the serving step's "
                "two row-parallel psums per layer stay in XLA",
    },
    "mla_decode": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.attention.mla_decode_attention",
        "note": "decode kernel that walks the page table of a pool with no "
                "head dim: absorbed latent attention (hvd_mla_decode) and, "
                "general over key/value heads, ops.attention."
                "cca_decode_attention (hvd_cca_decode), which also takes "
                "keys and values from two pools under one table: the "
                "dense decode step calls it so inside its shard_map, "
                "local to a tp shard's heads, the step's two row-parallel "
                "psums a layer staying in XLA; no exchange of its own.  "
                "One jitted function for every layer (the plane "
                "a prefetched scalar), one program instance that loops over "
                "the live (row, block) items with every row's query and "
                "result resident in VMEM; blocks sized from a VMEM budget "
                "(three of 64 pages in flight, two of 32 under wide "
                "resident rows); a turn issues the page copies of the item "
                "ahead in straight-line runs of eight (ids clamped in the "
                "wrapper, Mosaic's per-DMA bounds checks off), waits for "
                "its own block with one semaphore wait a power-of-two run "
                "of pages, and computes the live sub-blocks of 512 keys in "
                "one run",
    },
    "moe_gmm": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.moe.grouped_matmul",
        "note": "grouped matmul over the experts a chip holds; the layer "
                "runs without its exchange on one chip",
    },
    "ssm_decode": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.ssm.ssm_decode_update",
        "note": "one step of a state-space recurrence for every live slot "
                "of a decode round, over the cache's float32 slot-state "
                "array in place (hvd_ssm_decode: the array aliased input "
                "to output, the groups of eight slots that hold a live one "
                "visited, the ids prefetched); no exchange of its own",
    },
    "fused_update": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "collectives.ops.powersgd_allreduce",
        "note": "matricize/orthonormalize/EF-residual fused; the two "
                "P/Q factor psums stay in XLA between the kernels",
    },
    "bn_bwd": {
        "collectives": (),
        "wire_delta_bytes": 0,
        "site": "ops.bn.fused_bn_backward",
        "note": "two-pass BN backward; gradient exchange untouched",
    },
}

# Per-family override env suffix (``HOROVOD_PALLAS_<suffix>``).
_FAMILY_ENV = {
    "flash": "PALLAS_FLASH",
    "flash_decode": "PALLAS_DECODE",
    "mla_decode": "PALLAS_DECODE",     # the decode kernels share a switch
    "ssm_decode": "PALLAS_DECODE",
    "fused_update": "PALLAS_FUSED_UPDATE",
    "bn_bwd": "PALLAS_BN",
}

# Families whose ``auto`` resolves to the XLA path even on TPU; an
# explicit ``1`` still runs the kernels.  ``bn_bwd``: XLA's fused
# backward beat the two-pass kernel's 7N-byte floor at the RN50 sites
# (an earlier runtime's reading; ``examples/bn_bwd_probe.py`` repeats
# the comparison).  ``fused_update``: Mosaic refuses the
# kernels at RN50's PowerSGD bucket shapes -- ``(256, c)`` f32 row blocks
# overflow the 16 MB scoped-VMEM limit at c = 4096, and a near-square
# dim with no 128-multiple divisor has no legal lane tiling.
_AUTO_XLA = frozenset({"bn_bwd", "fused_update"})

_warned_legacy = False


def _read(name: str):
    """Read ``HVD_TPU_<name>`` then ``HOROVOD_<name>`` (the package's
    standard env precedence, mirroring ``core.config._env``)."""
    v = os.environ.get("HVD_TPU_" + name)
    if v is None:
        v = os.environ.get("HOROVOD_" + name)
    return v


def _legacy_flash_flag():
    """The pre-unification ``HVD_TPU_FLASH`` flag, deprecation-warned."""
    global _warned_legacy
    v = os.environ.get("HVD_TPU_FLASH")
    if v is not None and not _warned_legacy:
        _warned_legacy = True
        warnings.warn(
            "HVD_TPU_FLASH is deprecated; use HOROVOD_PALLAS (all kernel "
            "families) or HOROVOD_PALLAS_FLASH (this family only)",
            DeprecationWarning, stacklevel=3)
    return v


def pallas_enabled(family: str) -> bool:
    """Whether the ``family`` kernels should run for the current call.

    Resolution order: the per-family override, then (for ``flash``) the
    legacy ``HVD_TPU_FLASH`` flag, then the global ``HOROVOD_PALLAS``,
    then ``auto`` (TPU only, minus ``_AUTO_XLA``).  Read per call: tests
    flip the env between traces.
    """
    if family not in KERNEL_CONTRACTS:
        raise ValueError(f"unknown pallas kernel family {family!r}; "
                         f"known: {sorted(KERNEL_CONTRACTS)}")
    # ``moe_gmm`` has no switch of its own: it follows the global one.
    flag = _read(_FAMILY_ENV[family]) if family in _FAMILY_ENV else None
    if flag is None and family == "flash":
        flag = _legacy_flash_flag()
    if flag is None:
        flag = _read("PALLAS")
    if flag in (None, "", "auto"):
        return (jax.default_backend() == "tpu"
                and family not in _AUTO_XLA)
    return flag != "0"


def interpret_mode() -> bool:
    """Pallas kernels interpret off-TPU (CPU tests, the CI step audit).

    Keyed on the process default backend, like ``pallas_enabled``: a
    kernel forced on (``=1``) and then run on a non-default device fails
    to lower loudly; it never silently interprets on a TPU process.
    ``chip_smoke.py`` counts the Mosaic custom calls in each lowered step
    to prove the compiled path is the one that ran.
    """
    return jax.default_backend() != "tpu"


def registered_kernels():
    return tuple(sorted(KERNEL_CONTRACTS))


def kernel_contract(family: str) -> dict:
    return dict(KERNEL_CONTRACTS[family])


def active_kernels():
    """The families whose kernels would dispatch right now."""
    return tuple(k for k in registered_kernels() if pallas_enabled(k))
