"""Unit tests for the shared pre-init platform-forcing helper."""

import os
import subprocess
import sys

from horovod_tpu.utils.platform import (backend_initialized,
                                        merge_host_device_flag)

FLAG = "--xla_force_host_platform_device_count"


def test_merge_appends_when_absent():
    assert merge_host_device_flag("", 8) == f"{FLAG}=8"
    assert merge_host_device_flag("--xla_foo=1", 8) == f"--xla_foo=1 {FLAG}=8"


def test_merge_replaces_smaller_count():
    # A pre-existing smaller count must be raised, not kept (round-1 style
    # failure: inherited =4 would leave an 8-device dryrun short).
    assert merge_host_device_flag(f"{FLAG}=4", 8) == f"{FLAG}=8"
    assert merge_host_device_flag(f"--xla_foo=1 {FLAG}=4 --xla_bar=2", 8) \
        == f"--xla_foo=1 --xla_bar=2 {FLAG}=8"


def test_merge_keeps_larger_count():
    assert merge_host_device_flag(f"{FLAG}=16", 8) == f"{FLAG}=16"


def test_merge_collapses_duplicates_to_max():
    # Inherited envs can carry duplicated flags (the pre-refactor launcher
    # blind-appended).  XLA duplicate precedence is an implementation
    # detail; collapse to a single occurrence with the max count.
    assert merge_host_device_flag(f"{FLAG}=16 {FLAG}=4", 8) == f"{FLAG}=16"
    assert merge_host_device_flag(f"{FLAG}=2 --xla_foo=1 {FLAG}=4", 8) \
        == f"--xla_foo=1 {FLAG}=8"


def test_set_is_exact():
    from horovod_tpu.utils.platform import set_host_device_flag
    # Worker envs need the slot count exactly, even when the parent env
    # carries a larger one.
    assert set_host_device_flag(f"{FLAG}=8", 2) == f"{FLAG}=2"
    assert set_host_device_flag("--xla_foo=1", 2) == f"--xla_foo=1 {FLAG}=2"


def test_backend_initialized_reports_true_under_conftest():
    # conftest initialized the 8-device CPU backend for this process.
    import jax
    jax.devices()
    assert backend_initialized()


# ---------------------------------------------------------------------------
# Compile cache placement (configure_compile_cache, shared by hvd.init()
# and chip_smoke.py).
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_defaults_to_checkout(hvd, monkeypatch):
    """Unset, the cache is ``<checkout>/.jax_cache`` -- computed from the
    package location, so two inits (and two runs) give the same path."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        paths = []
        for _ in range(2):
            hvd.shutdown()
            hvd.init()
            paths.append(jax.config.jax_compilation_cache_dir)
        assert paths == [os.path.join(REPO, ".jax_cache")] * 2
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, no code path sets another
    directory: jax read the variable at import and hvd.init() leaves it."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, horovod_tpu as hvd\n"
         "hvd.init(); first = jax.config.jax_compilation_cache_dir\n"
         "hvd.shutdown(); hvd.init()\n"
         "print(first); print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert proc.stdout.split() == [str(tmp_path)] * 2
