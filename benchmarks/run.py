"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is found by name, from data: ``BENCHMARK.json``
(next to this directory) names the cell's configuration and traffic; the
configuration's ``file`` names its ``kind`` and ``family``, which are
imported from ``benchmarks/kinds/`` and ``benchmarks/families/``; the
traffic is ``benchmarks/traffic/<traffic>.json``; each per-layer metric is
read by ``benchmarks/readers/<name up to its first dot>.py``.  See
``benchmarks/README.md``.

The run refuses to start without a TPU or with fewer chips than the cell
asks for (exit 1, no result line).  The last line of standard output is
the result, and it has passed ``benchmarks/lib/validate.py``.
"""

import time

T_START = time.time()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import types             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """Everything the data says about one cell."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(
        root, "benchmarks", "traffic", cell["traffic"] + ".json"))
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic}


def reader_for(metric: str):
    return importlib.import_module(
        "benchmarks.readers." + metric.split(".", 1)[0])


def per_layer_values(bench: dict, workload: str, rctx, log) -> dict:
    """Each per-layer metric of the cell through its reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and workload not in cells:
            continue
        rctx.metric = m
        value = reader_for(m["name"]).read(rctx)
        if value is None:
            log(f"reader {m['name']}: nothing to read")
            continue
        out[m["name"]] = float(value)
    return out


def open_chips(chips: int, what: str):
    """Point jax's persistent compile cache at its fixed place and return
    the ``chips`` TPU devices, or None (with a word on stderr) where jax
    finds another platform or fewer chips."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # Every program goes to the cache, the sub-second ones too: a second
    # run of a cell must compile nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{what} needs {chips} TPU chip(s); jax found {len(devices)} "
              f"x {devices[0].platform!r}. The benchmark never runs on "
              f"another platform.", file=sys.stderr)
        return None
    return devices[:chips]


def make_context(data: dict, seed: int, seconds: float, trace_dir: str,
                 devices, family, log):
    """What a kind's ``run`` gets.  ``trace_dir`` empty: no traced
    sub-window."""
    from benchmarks.lib import tracing
    return types.SimpleNamespace(
        cell=data["cell"], config=data["config"], traffic=data["traffic"],
        chips=len(devices), seed=seed, seconds=seconds, family=family,
        log=log,
        tracer=(tracing.SubWindowTrace(trace_dir) if trace_dir else None),
        compiles=tracing.CompileCounter(),
        setup_done=lambda: time.time() - T_START,
        memory_peak=lambda: max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's xplane file here")
    args = ap.parse_args(argv)
    data = load_cell(ROOT, args.workload)
    bench, cell = data["bench"], data["cell"]
    config, traffic = data["config"], data["traffic"]
    chips = int(cell["chips"])

    def log(msg: str) -> None:
        print("bench " + msg, flush=True)

    devices = open_chips(chips, args.workload)
    if devices is None:
        return 1
    import jax

    import horovod_tpu as hvd
    from benchmarks.lib import checks, peaks, validate, xplane
    hvd.init(devices=devices)
    kind = importlib.import_module("benchmarks.kinds." + config["kind"])
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} kind {config['kind']} family "
        f"{config['family']} devices {len(devices)} x "
        f"{devices[0].device_kind!r} cache "
        f"{jax.config.jax_compilation_cache_dir}")

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    ctx = make_context(data, args.seed, args.seconds,
                       trace_dir if args.trace else "", devices, family,
                       log)
    result = kind.run(ctx)

    for c in result["checks"]:
        log(c.line())
    values = dict(result["end_to_end"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(result["memory_peak_bytes"])}
    line = {"correct": checks.all_ok(result["checks"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        path = xplane.find_xplane(trace_dir)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(
                args.keep_trace, args.workload + ".xplane.pb"))
        trace = xplane.load_trace(path)
        busy_s, window_s = xplane.busy_and_window_s(trace)
        device.update(window_s=window_s, busy_s=busy_s)
        rctx = types.SimpleNamespace(
            trace=trace, counters=result["counters"], cell=cell,
            config=config, traffic=traffic, family=family, chips=chips,
            peaks=peaks.peaks_for(devices[0].device_kind),
            busy_s=busy_s, window_s=window_s, log=log, metric=None)
        values.update(per_layer_values(bench, args.workload, rctx, log))
        line["breakdown"] = {"device_ops": xplane.top_ops(trace),
                             "idle_gaps": xplane.idle_gaps(trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    expected = validate.expected_metrics(bench, args.workload,
                                         bool(args.trace))
    line["metrics"] = {name: {"value": values[name], "unit": unit}
                       for name, unit in expected.items() if name in values}
    line["device"] = device
    text = validate.dump_line(line)
    validate.validate_line(text, expected, trace=bool(args.trace),
                           chips=chips)
    hvd.shutdown()
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
