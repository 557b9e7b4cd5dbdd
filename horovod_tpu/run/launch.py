"""``hvdrun``: the launcher CLI (``horovodrun`` analogue).

Reference: ``horovod/runner/launch.py`` (arg surface: ``-np``, hosts,
``--timeline-filename``, ``--autotune``, ``--check-build``, verbosity,
elastic flags) + ``gloo_run.py`` (per-slot env: ``HOROVOD_RANK/SIZE/...``,
rendezvous address, controller selection).

TPU-native inversion: instead of SSH+mpirun fan-out, the launcher starts N
local controller processes (one per host would be one per TPU-pod worker
VM; locally they are test processes) and hands each the JAX coordination
service address (``jax.distributed.initialize``) -- the direct analogue of
the Gloo rendezvous address.  On real multi-host TPU pods, each worker VM's
agent runs the same per-process entry with the coordinator on worker 0.

Usage::

    python -m horovod_tpu.run -np 4 --cpu python train.py --epochs 1

``-np N`` above 1 needs ``--cpu``: a TPU chip belongs to one process, and
one process drives all of a host's chips (``hvd.size()`` is the chip
count), so on a TPU host the job is ``python train.py``.
"""

from __future__ import annotations

import argparse
import os
import socket
import sys
import threading
from typing import List, Optional

from .exec_util import TaggedProcess, wait_all


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu job: one controller process per "
                    "host/worker, coordinated via the JAX distributed "
                    "runtime.")
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="number of controller processes to launch "
                        "(default: total slots of -H/--hostfile, else 1)")
    p.add_argument("-H", "--hosts", default=None,
                   help="comma-separated host[:slots] list (reference "
                        "-H h1:4,h2:4 syntax)")
    p.add_argument("--hostfile", default=None,
                   help="file with one 'host [slots=N]' or host:N per line")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend in workers (testing); each "
                        "worker gets --slots virtual devices")
    p.add_argument("--slots", type=int, default=1,
                   help="devices per worker process in --cpu mode")
    p.add_argument("--coordinator", default="127.0.0.1",
                   help="coordinator host handed to jax.distributed")
    p.add_argument("--coordinator-port", type=int, default=0,
                   help="coordinator port (0 = pick a free one)")
    p.add_argument("--timeline-filename", default=None,
                   help="write a Chrome-trace timeline per rank "
                        "(rank suffix appended)")
    p.add_argument("--timeline-mark-cycles", action="store_true",
                   help="mark scheduler cycles in the timeline "
                        "(HOROVOD_TIMELINE_MARK_CYCLES)")
    p.add_argument("--autotune", action="store_true",
                   help="enable fusion-threshold autotuning in workers")
    p.add_argument("--fusion-threshold-mb", type=int, default=None,
                   help="override HOROVOD_FUSION_THRESHOLD (MiB)")
    p.add_argument("--verbose", "-v", action="count", default=0)
    p.add_argument("--log-level", default=None,
                   choices=("trace", "debug", "info", "warning", "error",
                            "fatal"),
                   help="worker HOROVOD_LOG_LEVEL (overrides -v mapping)")
    p.add_argument("--check-build", action="store_true",
                   help="print build capabilities and exit")
    p.add_argument("--explain-plan", action="store_true",
                   help="render the exchange planner's bucket decision "
                        "for a synthetic parameter set (honours "
                        "HOROVOD_FUSION_THRESHOLD / HOROVOD_COMPRESSION) "
                        "and exit")
    p.add_argument("--no-tag-output", action="store_true",
                   help="do not prefix worker output with [rank]<stream>")
    p.add_argument("--probe", action="store_true",
                   help="pre-launch handshake: every worker slot reports "
                        "its build/runtime versions and the driver fails "
                        "fast on skew (reference driver/task service)")
    # Elastic flags (wired to horovod_tpu.elastic driver).
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None,
                   help="executable printing one host[:slots] per line; "
                        "enables elastic mode")
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   help="evict elastic workers whose heartbeat file goes "
                        "stale for this many seconds (default: "
                        "HOROVOD_HEARTBEAT_TIMEOUT env or disabled)")
    p.add_argument("--network-rendezvous", action="store_true",
                   help="elastic mode: publish membership + heartbeats "
                        "over the HMAC-signed HTTP KV store instead of a "
                        "shared assignment file (multi-host)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="program and args to launch per worker")
    return p


def check_build() -> str:
    import jax
    import horovod_tpu
    lines = [
        f"horovod_tpu v{horovod_tpu.__version__}",
        "",
        "Available backends:",
        "    [X] XLA:TPU collectives (ICI/DCN mesh)",
        "    [X] XLA:CPU collectives (gloo, multi-process test backend)",
        "    [ ] NCCL (not applicable: no GPU in the loop)",
        "    [ ] MPI  (not applicable: JAX coordination service instead)",
        "Available features:",
        "    [X] fused allreduce / grouped_allreduce[_async] /",
        "        allgather(+ragged) / broadcast / alltoall /",
        "        reducescatter / barrier / sparse allreduce (torch)",
        "    [X] Adasum (flat + hierarchical dcn x ici)",
        "    [X] fp16/bf16 gradient compression",
        "    [X] autotune (fusion threshold, GP Bayesian)",
        "    [X] timeline (Chrome trace, runtime start/stop)",
        "    [X] elastic (commit/restore + rescale)",
        "    [X] checkpointing (rank-0 npz + orbax sharded)",
        "    [X] sequence parallelism (ring + Ulysses attention)",
        f"jax {jax.__version__}",
    ]
    from ..core.config import detect_tpu_pod
    pod = detect_tpu_pod()
    if pod is not None:
        lines.append(
            f"TPU pod slice detected: worker {pod['rank']}/{pod['size']}, "
            f"coordinator {pod['addr']}:{pod['port']}")
    return "\n".join(lines)


def explain_plan_cli() -> str:
    """``--explain-plan``: render the planner's decision for a synthetic
    ResNet-ish parameter mix (a few big f32 matrices plus small bias
    vectors) under the CONFIGURED threshold and codec -- no ``hvd.init``
    needed, ``plan_buckets`` works uninitialized.  Gives operators a
    zero-setup view of what the exchange stack would decide; pointed at a
    real job, ``fusion.explain_plan(params)`` does the same in-process.
    """
    import jax
    from ..controller import fusion
    from ..core.config import load_config

    cfg = load_config()
    shapes = [(1000, 1000), (512, 512), (4096, 256), (256,), (1000,),
              (64, 3, 7, 7), (512,)]
    leaves = [jax.ShapeDtypeStruct(s, "float32") for s in shapes]
    rows = fusion.explain_plan(leaves,
                               threshold_bytes=cfg.fusion_threshold,
                               compression=cfg.compression,
                               register=False)
    header = (f"# exchange plan: {len(leaves)} synthetic f32 leaves, "
              f"threshold {cfg.fusion_threshold} bytes, "
              f"codec {cfg.compression or 'none'}")
    return header + "\n" + fusion.render_plan(rows)


def run_command(args: Optional[List[str]] = None) -> int:
    parser = build_parser()
    opts = parser.parse_args(args)
    if opts.check_build:
        print(check_build())
        return 0
    if opts.explain_plan:
        print(explain_plan_cli())
        return 0

    if opts.timeline_mark_cycles and not (
            opts.timeline_filename or os.environ.get("HOROVOD_TIMELINE")
            or os.environ.get("HVD_TPU_TIMELINE")):
        print("# warning: --timeline-mark-cycles has no effect without "
              "--timeline-filename (or HOROVOD_TIMELINE)", file=sys.stderr)

    cmd = list(opts.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        parser.error("no command given")

    np_ = opts.num_proc
    if opts.hosts or opts.hostfile:
        if opts.host_discovery_script:
            parser.error("-H/--hostfile is a static host list; it cannot "
                         "be combined with --host-discovery-script "
                         "(elastic membership comes from the script)")
        from .hosts import (all_local, parse_host_spec, parse_hostfile,
                            total_slots)
        try:
            hosts = parse_host_spec(opts.hosts) if opts.hosts else \
                parse_hostfile(opts.hostfile)
        except (ValueError, OSError) as e:
            parser.error(str(e))
        if not all_local(hosts):
            parser.error(
                "remote hosts in -H/--hostfile: this launcher spawns "
                "processes locally (on TPU pods each worker VM runs ONE "
                "process -- `hvdrun -np 1` -- pointed at the same "
                "--coordinator with its own HOROVOD_RANK). "
                f"Got: {', '.join(h for h, _ in hosts)}")
        if np_ is None:
            np_ = total_slots(hosts)
    elif np_ is None and not opts.host_discovery_script:
        # No explicit -np/-H: inside an LSF allocation, derive the process
        # count from the scheduler like the reference's horovodrun does
        # (util/lsf.py).  An explicit -np always wins, so per-VM launches
        # with a shared --coordinator stay possible on multi-host jobs.
        from .lsf import get_compute_hosts, using_lsf
        if using_lsf():
            from .hosts import all_local, total_slots
            try:
                hosts = get_compute_hosts()
            except ValueError as e:
                parser.error(str(e))
            if not all_local(hosts):
                parser.error(
                    "LSF allocation spans multiple hosts: run `hvdrun -np "
                    "1` on each worker VM with a shared --coordinator. "
                    "Hosts: "
                    f"{', '.join(h for h, _ in hosts)}")
            np_ = total_slots(hosts)
    if np_ is None:
        np_ = 1
    if np_ > 1 and not opts.cpu and not opts.host_discovery_script:
        # A TPU chip belongs to one process.  N unpinned workers would each
        # open every local chip: the first wins, the rest fail or hang.
        parser.error(
            f"-np {np_} without --cpu would start {np_} processes on this "
            "host that each open every local TPU chip, and a chip belongs "
            "to one process. Supported: ONE process drives all local "
            "chips (`python train.py`, or `hvdrun -np 1 python train.py`; "
            "hvd.size() is the chip count); across hosts, one process per "
            "host (hvd.init() bootstraps from TPU_WORKER_HOSTNAMES, or run "
            "`hvdrun -np 1` on each host with a shared --coordinator and "
            "HOROVOD_RANK/HOROVOD_SIZE); `--cpu -np N` starts N CPU "
            "workers for tests.")
    if opts.host_discovery_script:
        from ..core.config import load_config
        from ..elastic.driver import ElasticDriver
        heartbeat = opts.heartbeat_timeout
        if heartbeat is None:
            heartbeat = load_config().heartbeat_timeout
        # Per-worker env flags ride extra_env so elastic workers honor
        # the same CLI surface as the static spawn loop (the per-rank
        # timeline suffix is applied at each spawn).
        extra = {}
        if opts.log_level:
            extra["HOROVOD_LOG_LEVEL"] = opts.log_level
        elif opts.verbose:
            extra["HOROVOD_LOG_LEVEL"] = ("debug" if opts.verbose > 1
                                          else "info")
        if opts.autotune:
            extra["HOROVOD_AUTOTUNE"] = "1"
        if opts.fusion_threshold_mb is not None:
            extra["HOROVOD_FUSION_THRESHOLD"] = str(
                opts.fusion_threshold_mb << 20)
        if opts.timeline_filename:
            extra["HOROVOD_TIMELINE"] = opts.timeline_filename
        if opts.timeline_mark_cycles:
            extra["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
        driver = ElasticDriver(
            command=cmd,
            discovery_script=opts.host_discovery_script,
            min_np=opts.min_np or 1,
            max_np=opts.max_np,
            cpu=opts.cpu,
            slots=opts.slots,
            verbose=opts.verbose,
            heartbeat_timeout_s=heartbeat,
            rendezvous=opts.network_rendezvous,
            extra_env=extra,
        )
        return driver.run()

    if opts.probe:
        from .probe import DriverProbe
        probe = DriverProbe()
        wids = [f"slot{r}" for r in range(np_)]
        procs_ = [probe.spawn_local_probe(w) for w in wids]
        try:
            reports = probe.collect(wids)
            probe.validate(reports)
            if opts.verbose:
                for w, r in reports.items():
                    print(f"# probe {w}: {r['hostname']} "
                          f"hvd={r['framework_version']} "
                          f"jax={r['jax_version']}")
        finally:
            # Reap best-effort: a hung probe child must not mask the real
            # collect/validate error or leak the rendezvous server.
            for pr in procs_:
                try:
                    pr.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pr.kill()
            probe.stop()

    port = opts.coordinator_port or free_port()
    lock = threading.Lock()
    procs: List[TaggedProcess] = []
    for rank in range(np_):
        env = dict(os.environ)
        env.update(worker_env(
            rank=rank, size=np_, coordinator=opts.coordinator, port=port,
            cpu=opts.cpu, slots=opts.slots))
        apply_timeline_env(env, rank, opts.timeline_filename)
        if opts.timeline_mark_cycles:
            # The timeline may come from the CLI flag or inherited env;
            # config ignores mark-cycles when no timeline is active.
            env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
        if opts.autotune:
            env["HOROVOD_AUTOTUNE"] = "1"
        if opts.fusion_threshold_mb is not None:
            env["HOROVOD_FUSION_THRESHOLD"] = str(
                opts.fusion_threshold_mb << 20)
        if opts.log_level:
            env["HOROVOD_LOG_LEVEL"] = opts.log_level
        elif opts.verbose:
            env["HOROVOD_LOG_LEVEL"] = "debug" if opts.verbose > 1 else "info"
        procs.append(TaggedProcess(rank, cmd, env, lock=lock,
                                   tag=not opts.no_tag_output))
    return wait_all(procs)


def apply_timeline_env(env: dict, suffix,
                       cli_filename: Optional[str] = None) -> None:
    """Point this worker's timeline at a per-rank file.

    A shared path would have every worker ``open(path, 'w')`` the SAME
    file and interleave/truncate each other's trace.  The CLI flag wins
    (and clears any inherited spelling, since config resolves HVD_TPU_
    first); otherwise inherited HOROVOD_TIMELINE/HVD_TPU_TIMELINE values
    get the suffix.  The static spawn loop suffixes by rank; the elastic
    driver by the STABLE worker id (ranks are reassigned on rescale).
    """
    if cli_filename:
        env.pop("HVD_TPU_TIMELINE", None)
        env["HOROVOD_TIMELINE"] = f"{cli_filename}.{suffix}"
        return
    for var in ("HOROVOD_TIMELINE", "HVD_TPU_TIMELINE"):
        if env.get(var):
            env[var] = f"{env[var]}.{suffix}"


def worker_env(rank: int, size: int, coordinator: str, port: int,
               cpu: bool, slots: int = 1, local_rank: Optional[int] = None,
               local_size: Optional[int] = None) -> dict:
    """Per-worker environment (the gloo_run per-slot env analogue)."""
    env = {
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(local_rank if local_rank is not None
                                  else rank),
        "HOROVOD_LOCAL_SIZE": str(local_size if local_size is not None
                                  else size),
        "HOROVOD_CROSS_RANK": "0",
        "HOROVOD_CROSS_SIZE": "1",
        "HVD_TPU_COORDINATOR_ADDR": coordinator,
        "HVD_TPU_COORDINATOR_PORT": str(port),
    }
    if cpu:
        from ..utils.platform import set_host_device_flag
        env["HVD_TPU_FORCE_CPU"] = "1"
        env["XLA_FLAGS"] = set_host_device_flag(
            os.environ.get("XLA_FLAGS", ""), slots)
    return env


def main() -> None:  # console entry
    sys.exit(run_command())
