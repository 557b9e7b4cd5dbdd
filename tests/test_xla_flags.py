"""Latency-hiding flag pack tests (:mod:`horovod_tpu.core.xla_flags`).

All tests drive :func:`apply_xla_flags` with explicit env dicts and
platforms -- never the process environment -- so they are hermetic and
run identically on the CPU backend.
"""

import pytest

from horovod_tpu.core import xla_flags


def _all_pack_flags():
    return [f for flags in xla_flags.XLA_FLAG_PACK.values() for f in flags]


def test_cpu_platform_is_noop():
    env = {"JAX_PLATFORMS": "cpu"}
    report = xla_flags.apply_xla_flags(env=env)
    assert report.platform == "cpu"
    assert report.is_noop
    assert report.applied == {}
    assert set(report.rejected) == set(_all_pack_flags())
    assert all(why == "cpu backend" for why in report.rejected.values())
    # env untouched: no flag vars created.
    assert env == {"JAX_PLATFORMS": "cpu"}


def test_tpu_platform_applies_full_pack():
    env = {}
    report = xla_flags.apply_xla_flags(env=env, platform="tpu")
    assert not report.is_noop
    assert report.rejected == {}
    assert set(report.applied_flags) == set(_all_pack_flags())
    for var, flags in xla_flags.XLA_FLAG_PACK.items():
        for f in flags:
            assert f in env[var].split()
    # jaxlib aborts on any of these in XLA_FLAGS; the whole pack rides
    # LIBTPU_INIT_ARGS.
    assert "XLA_FLAGS" not in env


def test_user_set_flag_wins():
    user = "--xla_tpu_enable_latency_hiding_scheduler=false"
    env = {"LIBTPU_INIT_ARGS": user}
    report = xla_flags.apply_xla_flags(env=env, platform="tpu")
    assert report.rejected == {
        "--xla_tpu_enable_latency_hiding_scheduler=true": "user-set"}
    # The user's value is preserved verbatim, pack flags appended after.
    assert env["LIBTPU_INIT_ARGS"].split()[0] == user
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" \
        not in env["LIBTPU_INIT_ARGS"].split()
    assert "--xla_enable_async_all_gather=true" \
        in env["LIBTPU_INIT_ARGS"].split()


def test_apply_is_idempotent():
    env = {}
    xla_flags.apply_xla_flags(env=env, platform="tpu")
    snapshot = dict(env)
    second = xla_flags.apply_xla_flags(env=env, platform="tpu")
    # Second application rejects everything as user-set; env unchanged.
    assert second.is_noop
    assert all(why == "user-set" for why in second.rejected.values())
    assert env == snapshot


def test_detect_platform_prefers_env_vars():
    assert xla_flags.detect_platform({"JAX_PLATFORMS": "tpu,cpu"}) == "tpu"
    assert xla_flags.detect_platform({"JAX_PLATFORM_NAME": "CPU"}) == "cpu"
    # No override: falls back to the libtpu-install probe.
    import importlib.util
    expected = "tpu" if importlib.util.find_spec("libtpu") else "cpu"
    assert xla_flags.detect_platform({}) == expected


def test_report_summary_lists_applied_and_rejected():
    env = {"LIBTPU_INIT_ARGS": "--xla_enable_async_all_gather=false"}
    report = xla_flags.apply_xla_flags(env=env, platform="tpu")
    text = report.summary()
    assert "platform=tpu" in text
    assert ("+ LIBTPU_INIT_ARGS: "
            "--xla_tpu_enable_latency_hiding_scheduler=true") in text
    assert "- --xla_enable_async_all_gather=true  (user-set)" in text


def test_apply_records_last_report():
    env = {"JAX_PLATFORMS": "cpu"}
    report = xla_flags.apply(env=env)
    assert xla_flags.last_report() is report
    assert report.is_noop


def test_real_env_apply_on_cpu_backend_is_noop(monkeypatch):
    """Applying to os.environ under the test harness (JAX_PLATFORMS=cpu)
    must not mutate the environment."""
    import os
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before_xla = os.environ.get("XLA_FLAGS")
    before_libtpu = os.environ.get("LIBTPU_INIT_ARGS")
    report = xla_flags.apply_xla_flags()
    assert report.is_noop
    assert os.environ.get("XLA_FLAGS") == before_xla
    assert os.environ.get("LIBTPU_INIT_ARGS") == before_libtpu
