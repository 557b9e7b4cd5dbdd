"""Record a SMALL trace of one cell on the chip, for the tests under
``tests/benchmark/data``: the cell's own run with the traced sub-window
cut to a few rounds (served) or steps (trained), the xplane file gzipped,
the kind's counters beside it and, for a served cell, the ``request``
records the program filed.

    python benchmarks/tools/record.py <cell> <rounds or steps> <seconds> \
        <seed> <outdir>
"""

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(cell: str, n: int, seconds: float, seed: int, outdir: str) -> int:
    import importlib

    from benchmarks import run
    from benchmarks.lib import xplane
    data = run.load_cell(ROOT, cell)
    data["traffic"].update(trace_rounds=n, trace_steps=n)
    devices = run.open_chips(int(data["cell"]["chips"]), cell)
    if devices is None:
        return 1
    import horovod_tpu as hvd
    from horovod_tpu.timeline import spans
    hvd.init(devices=devices)
    config = data["config"]
    kind = importlib.import_module("benchmarks.kinds." + config["kind"])
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    trace_dir = os.path.join(ROOT, ".bench_trace", cell + ".record")
    ctx = run.make_context(data, seed, seconds, trace_dir, devices, family,
                           lambda msg: print("record " + msg, flush=True))
    result = kind.run(ctx)
    os.makedirs(outdir, exist_ok=True)
    stem = os.path.join(outdir, cell + ".spans")
    with open(xplane.find_xplane(trace_dir), "rb") as f, \
            gzip.open(stem + ".xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(trace_dir, ignore_errors=True)
    counters = {k: v for k, v in result["counters"].items()
                if isinstance(v, (int, float, list)) or v is None}
    with open(stem + ".counters.json", "w") as f:
        json.dump(counters, f)
    requests = [r.attrs for r in spans.recorder().records(name="request")]
    if requests:
        with open(stem + ".requests.json", "w") as f:
            json.dump(requests, f)
    print(f"record {cell}: {os.path.getsize(stem + '.xplane.pb.gz')} bytes "
          f"of trace, {len(requests)} request records, correct "
          f"{all(c.ok for c in result['checks'])}")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                  int(sys.argv[4]), sys.argv[5]))
