"""Elastic subsystem tests: state objects, sampler, notifier, discovery,
and a live rescale integration run with a mutating discovery script
(reference ``test/integration/test_elastic_torch.py`` pattern)."""

import glob
import json
import os
import stat
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hv
from horovod_tpu import elastic
from horovod_tpu.elastic.notify import (Notifier, read_assignment,
                                        write_assignment)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_object_state_commit_restore(hvd):
    s = elastic.ObjectState(count=1, name="a")
    s.count = 5
    s.restore()
    assert s.count == 1
    s.count = 7
    s.commit()
    s.count = 9
    s.restore()
    assert s.count == 7


def test_jax_state_commit_restore_sync(hvd):
    s = elastic.JaxState(params={"w": jnp.ones((3,))}, batch=0)
    s.params = {"w": jnp.zeros((3,))}
    s.batch = 4
    s.restore()
    np.testing.assert_allclose(np.asarray(s.params["w"]), 1.0)
    assert s.batch == 0
    s.params = {"w": jnp.full((3,), 2.0)}
    s.batch = 2
    s.commit()
    s.sync()  # single process: broadcast from rank 0 is identity
    np.testing.assert_allclose(np.asarray(s.params["w"]), 2.0)
    assert s.batch == 2


def test_elastic_sampler_reshards_remaining():
    s = elastic.ElasticSampler(num_samples=10, shuffle=False)
    s.set_rank_and_size(0, 2)
    first = list(s)[:2]
    s.record_batch(first)
    # Rescale 2 -> 1: remaining indices exclude processed ones.
    s.set_rank_and_size(0, 1)
    rest = list(s)
    assert set(first).isdisjoint(rest)
    assert set(first) | set(rest) == set(range(10))
    state = s.state_dict()
    s2 = elastic.ElasticSampler(num_samples=10, shuffle=False)
    s2.load_state_dict(state)
    assert set(s2.remaining) == set(rest)


def test_notifier_epoch_tracking(tmp_path):
    path = str(tmp_path / "assign.json")
    write_assignment(path, epoch=0, size=2, port=1000,
                     ranks={"h:0": 0, "h:1": 1})
    n = Notifier(path=path, worker_id="h:0")
    assert n.current_epoch == 0
    assert n.updated() is None
    write_assignment(path, epoch=1, size=1, port=1001, ranks={"h:0": 0})
    doc = n.updated()
    assert doc and doc["size"] == 1
    n.accept(doc)
    assert n.updated() is None
    assert read_assignment(str(tmp_path / "missing.json")) is None


def test_discovery_script_parsing(tmp_path):
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\necho host1:2\necho host2\n")
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    d = elastic.HostDiscoveryScript(str(script), default_slots=3)
    assert d.find_available_hosts_and_slots() == {"host1": 2, "host2": 3}
    bad = elastic.HostDiscoveryScript(str(tmp_path / "nope.sh"))
    assert bad.find_available_hosts_and_slots() == {}


def test_discovery_parser_edge_cases(tmp_path):
    d = elastic.HostDiscoveryScript("unused", default_slots=2)
    assert d._parse_line("host:4") == ("host", 4)
    assert d._parse_line("host") == ("host", 2)
    assert d._parse_line("::1") == ("::1", 2)          # bare IPv6
    assert d._parse_line("[::1]") == ("::1", 2)
    assert d._parse_line("[::1]:8") == ("::1", 8)
    assert d._parse_line("host:gpu") == ("host:gpu", 2)  # non-int suffix


def test_commit_raises_hosts_updated(tmp_path, hvd):
    path = str(tmp_path / "assign.json")
    write_assignment(path, epoch=0, size=1, port=1, ranks={"h:0": 0})
    s = elastic.ObjectState(x=1)
    s._hvd_notifier = Notifier(path=path, worker_id="h:0")
    s.commit()  # no change: fine
    write_assignment(path, epoch=1, size=2, port=2,
                     ranks={"h:0": 0, "h:1": 1})
    s.x = 42
    with pytest.raises(hv.HostsUpdatedInterrupt):
        s.commit()
    s.restore()
    assert s.x == 42  # commit snapshots BEFORE the interrupt check


def _write_hosts(path, content):
    """Atomic rewrite: the driver polls `cat hosts.txt` every second, and a
    read of a truncated-but-unwritten file is a legal 'zero hosts' listing
    that would abort the job below min-np."""
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as f:
        f.write(content)
    os.replace(tmp, str(path))


def _run_elastic_live(tmp_path, initial, mutated, expect_final, target=40,
                      extra_args=(), env_extra=None, delay="0.4",
                      mutate_on=" batch 5 "):
    """Shared live-rescale harness: start the elastic launcher, mutate the
    discovery listing once training demonstrably progresses (pass
    ``mutated=None`` for a static-membership run), assert the run
    finishes at the expected final size."""
    import threading

    hosts = tmp_path / "hosts.txt"
    _write_hosts(hosts, initial)
    disc = tmp_path / "disc.sh"
    disc.write_text(f"#!/bin/sh\ncat {hosts}\n")
    disc.chmod(disc.stat().st_mode | stat.S_IEXEC)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_TARGET_BATCHES"] = str(target)
    env["ELASTIC_BATCH_DELAY_S"] = delay
    if env_extra:
        env.update(env_extra)
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run",
         "--host-discovery-script", str(disc), "--min-np", "2",
         *extra_args, "--cpu",
         sys.executable, os.path.join(REPO, "examples",
                                      "elastic_train.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    # Watchdog: readline blocks, so a silently wedged child would hang the
    # test forever; killing the child makes the reader see EOF.
    watchdog = threading.Timer(240, proc.kill)
    watchdog.start()
    lines = []
    mutated_flag = False
    try:
        for line in proc.stdout:
            lines.append(line)
            if mutated is not None and not mutated_flag \
                    and mutate_on in line:
                _write_hosts(hosts, mutated)
                mutated_flag = True
        proc.wait(timeout=60)
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)
        proc.stdout.close()
    out = "".join(lines)
    assert mutated is None or mutated_flag, out[-4000:]
    assert proc.returncode == 0, out[-4000:]
    assert f"final size {expect_final}" in out, out[-4000:]
    return out


@pytest.mark.integration
def test_elastic_scale_down_live(tmp_path):
    """3 workers -> discovery drops one -> survivors re-rendezvous at size
    2 and finish."""
    _run_elastic_live(tmp_path, "a\nb\nc\n", "a\nb\n", expect_final=2,
                      target=60)


@pytest.mark.integration
def test_elastic_network_rendezvous_live(tmp_path):
    """Same scale-down flow, but membership + heartbeats ride the
    HMAC-signed HTTP KV rendezvous instead of the assignment file."""
    _run_elastic_live(tmp_path, "a\nb\nc\n", "a\nb\n", expect_final=2,
                      extra_args=("--network-rendezvous",
                                  "--heartbeat-timeout", "30"))


@pytest.mark.integration
def test_elastic_scale_up_live(tmp_path):
    """2 workers -> discovery adds a third -> everyone re-rendezvouses at
    size 3 and finishes together (newcomer adopts survivors' progress)."""
    _run_elastic_live(tmp_path, "a\nb\n", "a\nb\nc\n", expect_final=3)


def test_preemption_notice_interrupts_at_commit(tmp_path, hvd):
    """A latched preemption notice converts the NEXT commit into
    HostsUpdatedInterrupt -- state snapshotted first (SURVEY.md 5.3)."""
    from horovod_tpu.elastic import preemption

    s = elastic.ObjectState(x=1)
    try:
        s.commit()
        preemption.trigger("test")
        s.x = 7
        with pytest.raises(hv.HostsUpdatedInterrupt):
            s.commit()
        s.restore()
        assert s.x == 7  # snapshot happened before the interrupt
    finally:
        preemption.reset()


def test_driver_reads_preempted_markers_file_and_kv(tmp_path):
    """Driver-side marker ingestion on both transports: new markers are
    returned once and consumed; blacklisted/seen wids are filtered and
    their stale markers cleaned up rather than re-read every poll."""
    from horovod_tpu.elastic.driver import ElasticDriver

    disc = tmp_path / "d.sh"
    disc.write_text("#!/bin/sh\necho a\n")
    disc.chmod(disc.stat().st_mode | stat.S_IEXEC)
    d = ElasticDriver(["true"], str(disc))
    d._ever_spawned.update({"a:0", "b:0", "c:0"})

    # File transport: markers written the way Notifier.mark_preempted does.
    for wid in ("a:0", "b:0"):
        safe = wid.replace(":", "_")
        with open(f"{d.assignment_path}.preempted.{safe}", "w") as f:
            f.write(wid)
    d.blacklist.add("b:0")
    new = d._read_preempted()
    assert new == {"a:0"}
    # Both markers consumed: the new one and the blacklisted stale one.
    assert not glob.glob(d.assignment_path + ".preempted.*")
    d._preempted_seen.add("a:0")
    assert d._read_preempted() == set()

    # KV transport: a fake store behind the same accessor the heartbeats
    # use.
    class _KV:
        def __init__(self):
            self.store = {("preempted", "c:0"): b"1"}

        def get(self, scope, key):
            return self.store.get((scope, key))

        def delete(self, scope, key):
            self.store.pop((scope, key), None)

    d._kv = _KV()
    assert d._read_preempted() == {"c:0"}
    assert ("preempted", "c:0") not in d._kv.store  # consumed


def test_gce_poll_stops_without_metadata_server(monkeypatch):
    """With no reachable metadata server the poll errors a few times and
    stops itself without latching a notice.  The URL is pinned to an
    unroutable address so the test behaves the same ON a GCE host."""
    from horovod_tpu.elastic import preemption

    monkeypatch.setattr(preemption, "GCE_PREEMPTED_URL",
                        "http://127.0.0.1:9/preempted")
    preemption.reset()
    t = preemption.start_gce_poll(interval_s=0.01, max_failures=2)
    t.join(timeout=30)
    assert not t.is_alive()
    assert not preemption.notice_received()


def test_comm_failure_classifier_requires_runtime_type():
    """A user ValueError mentioning 'connection' must NOT be classified
    as a recoverable comm failure (type check first)."""
    from horovod_tpu.core.exceptions import HorovodInternalError
    from horovod_tpu.elastic.run_loop import _looks_like_comm_failure

    assert not _looks_like_comm_failure(
        ValueError("bad connection string in config"))
    assert _looks_like_comm_failure(
        RuntimeError("DEADLINE_EXCEEDED: barrier timed out"))
    assert _looks_like_comm_failure(HorovodInternalError("x"))
    try:
        from jax.errors import JaxRuntimeError
        assert _looks_like_comm_failure(
            JaxRuntimeError("UNAVAILABLE: connection reset by peer"))
    except ImportError:
        pass


@pytest.mark.integration
def test_preemption_sigterm_live(tmp_path):
    """A real SIGTERM to one worker mid-training: it leaves via the
    commit-boundary interrupt (graceful marker printed, state committed),
    the survivors re-rendezvous and finish -- not crash-and-restart of
    the noticed worker."""
    out = _run_elastic_live(
        tmp_path, "a\nb\nc\n", "a\nc\n", expect_final=2, target=60,
        env_extra={"ELASTIC_SELF_SIGTERM_AT": "4",
                   "ELASTIC_SIGTERM_HOST": "b"},
        # Drop the preempted host from discovery as soon as it announces
        # its graceful exit (what a reclaimed VM looks like).
        mutate_on="preempted: exiting gracefully")
    assert "preempted: exiting gracefully after commit" in out, out[-4000:]


def test_discovery_failure_keeps_last_known_hosts(tmp_path):
    """A crashing/slow discovery script must not read as 'zero hosts'."""
    import stat as _stat
    from horovod_tpu.elastic.discovery import HostDiscoveryScript
    script = tmp_path / "d.sh"
    script.write_text("#!/bin/sh\ncat %s\n" % (tmp_path / "hosts"))
    script.chmod(script.stat().st_mode | _stat.S_IEXEC)
    (tmp_path / "hosts").write_text("a\nb\n")
    d = HostDiscoveryScript(str(script))
    assert d.find_available_hosts_and_slots() == {"a": 1, "b": 1}
    script.write_text("#!/bin/sh\nexit 3\n")  # transient failure
    assert d.find_available_hosts_and_slots() == {"a": 1, "b": 1}
    script.write_text("#!/bin/sh\ncat %s\n" % (tmp_path / "hosts"))
    (tmp_path / "hosts").write_text("a\n")  # genuine scale-down
    assert d.find_available_hosts_and_slots() == {"a": 1}


@pytest.mark.integration
def test_elastic_resnet50_variant(tmp_path):
    """BASELINE's elastic-RN50 workload: the flax ResNet-50 behind the
    same commit/restore protocol (static 2-host membership smoke)."""
    _run_elastic_live(tmp_path, "a\nb\n", None, expect_final=2, target=2,
                      env_extra={"ELASTIC_MODEL": "resnet50",
                                 "ELASTIC_IMAGE_SIZE": "32"},
                      delay="0.05")


def test_elastic_sampler_state_roundtrip_across_resize():
    """Mid-epoch rank/size change: the processed set survives a
    state_dict JSON roundtrip into a NEW world, and the survivors split
    the remainder with no sample dropped or duplicated."""
    n = 23
    world0 = [elastic.ElasticSampler(n, shuffle=True, seed=5)
              for _ in range(4)]
    for r, s in enumerate(world0):
        s.set_epoch(2)
        s.set_rank_and_size(r, 4)
    # Every rank consumes its first 3 samples, then rank 3 dies.  As in
    # the training loop, each rank records the GLOBAL batch (its own
    # shard allgathered with everyone else's) so any survivor's state
    # carries the full progress.
    shards = [list(s)[:3] for s in world0]
    processed = set()
    for shard in shards:
        assert not processed & set(shard)  # ranks were already disjoint
        processed |= set(shard)
    for s in world0:
        s.record_batch(sorted(processed))
    blob = json.dumps(world0[0].state_dict())  # what commit() would ship
    world1 = [elastic.ElasticSampler(n, shuffle=True, seed=5)
              for _ in range(2)]
    remainder = []
    for r, s in enumerate(world1):
        s.load_state_dict(json.loads(blob))
        s.set_rank_and_size(r, 2)
        part = list(s)
        assert not set(part) & processed      # nothing replayed
        assert not set(part) & set(remainder)  # no cross-rank duplicate
        remainder.extend(part)
    assert set(remainder) | processed == set(range(n))
    assert len(remainder) + len(processed) == n


def test_gce_poll_stop_idempotent_and_reset_stops_it(monkeypatch):
    """start_gce_poll must be idempotent while alive, stoppable, safe to
    stop twice, and torn down by a global runtime reset -- a leaked
    poller from a previous epoch would latch a stale preemption notice
    into the next one."""
    from horovod_tpu.core.state import global_state
    from horovod_tpu.elastic import preemption
    # An unroutable metadata server: the poll thread idles on failures
    # (max_failures keeps it alive) without ever latching a notice.
    monkeypatch.setattr(preemption, "GCE_PREEMPTED_URL",
                        "http://127.0.0.1:9/preempted")
    try:
        t1 = preemption.start_gce_poll(interval_s=30.0,
                                       max_failures=10**6)
        assert t1 is not None and t1.is_alive()
        assert preemption.start_gce_poll(interval_s=30.0,
                                         max_failures=10**6) is t1
        preemption.stop_gce_poll()
        assert not t1.is_alive()
        preemption.stop_gce_poll()  # idempotent: no poller, no error
        t2 = preemption.start_gce_poll(interval_s=30.0,
                                       max_failures=10**6)
        assert t2 is not t1 and t2.is_alive()
        global_state().reset()  # runtime teardown stops the poller too
        t2.join(timeout=7.0)
        assert not t2.is_alive()
        assert not preemption.notice_received()
    finally:
        preemption.stop_gce_poll()
        preemption.reset()


@pytest.mark.integration
@pytest.mark.slow
def test_chaos_kill_rank_live(tmp_path):
    """Deterministic chaos kill: HOROVOD_CHAOS SIGKILLs rank 1 at step
    5; the driver evicts the dead worker and the survivors finish at
    size 2 through the same rollback/rendezvous path a real rank loss
    takes."""
    _run_elastic_live(
        tmp_path, "a\nb\nc\n", None, expect_final=2, target=40,
        env_extra={"HOROVOD_CHAOS": "seed=1;kill@step=5,rank=1"})
