"""Pre-init platform forcing shared by the launcher, tests, examples, and
the driver entry.

Running a multi-rank test/dry-run on one host needs an N-device virtual CPU
backend (the analogue of the reference's ``mpirun -np N`` localhost test
strategy, SURVEY.md section 4/7).  Both knobs involved --
``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` and
``jax_platforms`` -- only take effect if applied BEFORE jax initializes its
first backend, so every entry point that needs the virtual mesh must do the
same dance; this module is the single implementation.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _strip_count_flag(xla_flags: str):
    """Remove every occurrence of the count flag; return (rest, counts)."""
    pattern = re.escape(_COUNT_FLAG) + r"=(\d+)"
    counts = [int(v) for v in re.findall(pattern, xla_flags)]
    rest = " ".join(re.sub(pattern, "", xla_flags).split())
    return rest, counts


def merge_host_device_flag(xla_flags: str, n: int) -> str:
    """Return ``xla_flags`` with the host-device-count flag at least ``n``.

    All existing occurrences are collapsed into one (duplicate-flag
    precedence is an XLA implementation detail we refuse to rely on) set to
    max(existing..., n).
    """
    rest, counts = _strip_count_flag(xla_flags)
    return (rest + f" {_COUNT_FLAG}={max(counts + [n])}").strip()


def set_host_device_flag(xla_flags: str, n: int) -> str:
    """Return ``xla_flags`` with the host-device-count flag EXACTLY ``n``.

    For per-worker envs (launcher slots): the worker must see its slot
    count, not whatever larger count the parent environment carried.
    """
    rest, _ = _strip_count_flag(xla_flags)
    return (rest + f" {_COUNT_FLAG}={n}").strip()


def backend_initialized() -> bool:
    """Has jax already created a live backend in this process?

    jax 0.9 exposes no public probe; ``backends_are_initialized`` is the
    function jax's own config validators call.
    """
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def force_host_device_count(n: int, cpu: bool = True,
                            exact: bool = False) -> None:
    """Arrange for an ``n``-device virtual CPU backend.

    Must run before jax's first backend initialization.  With ``cpu=True``
    (the default) the default jax platform is forced to cpu as well, so
    plain ``jax.devices()`` returns the virtual mesh even when a TPU plugin
    is installed.  ``exact=True`` overrides a larger inherited count (an
    explicit user request like ``--cpu-devices 2`` means exactly 2);
    the default keeps at-least-``n`` semantics (a dryrun/test needs >= n).
    """
    fn = set_host_device_flag if exact else merge_host_device_flag
    os.environ["XLA_FLAGS"] = fn(os.environ.get("XLA_FLAGS", ""), n)
    if cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")


# <checkout>/.jax_cache: fixed by the package's location, so every run of
# the same checkout finds what the last one compiled.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set, jax has already read it and no directory is set in code.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
    The persistence thresholds are jax's own (compiles of 1.0 s and up,
    any size).  Call before the process's first compile -- jax latches
    whether the cache is in use at that point.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          _DEFAULT_COMPILE_CACHE)
    return jax.config.jax_compilation_cache_dir
