"""A configuration, a traffic mix, a cell and a per-layer metric are each
added as new files and entries, editing no file that is there: shown in a
temporary copy of ``BENCHMARK.json`` and ``benchmarks/``.  The copy holds
nothing else, so it also shows that a run there prints no result and
exits non-zero."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

READER = '''"""Decode rounds a request: a count from the benchmark's wrapper."""


def read(ctx):
    rounds = ctx.counters.get("rounds")
    return None if not rounds else rounds / ctx.counters["requests"]
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(d, p), "rb").read()
              for d, _, files in os.walk(root / "benchmarks")
              for p in files for d in [d]}
    bench = json.load(open(root / "BENCHMARK.json"))
    # 1. a configuration: its file of sizes, naming its kind and family
    cfg = json.load(open(root / "benchmarks/configs/mistral_7b_v03.json"))
    cfg.update(name="mistral_7b_v03_8l", num_hidden_layers=8)
    json.dump(cfg, open(root / "benchmarks/configs/mistral_7b_v03_8l.json",
                        "w"))
    bench["configs"].append({
        "name": "mistral_7b_v03_8l", "source": cfg["source"],
        "file": "benchmarks/configs/mistral_7b_v03_8l.json",
        "reduced": ["num_hidden_layers", "tie_word_embeddings"],
        "why": "a test's configuration"})
    # 2. a traffic mix: a data file the one generator reads
    mix = json.load(open(root / "benchmarks/traffic/chat_steady.json"))
    mix.update(prefix_share=0.75, num_prefixes=4, prefix_lens=[512])
    json.dump(mix, open(root / "benchmarks/traffic/shared_prefix.json", "w"))
    # 3. a cell: an entry that names both
    bench["workloads"].append({
        "name": "mistral_8l_shared_prefix", "config": "mistral_7b_v03_8l",
        "traffic": "shared_prefix", "chips": 1, "why": "a test's cell"})
    # 4. a per-layer metric: an entry and a reader of its own
    (root / "benchmarks/readers/rounds_per_request.py").write_text(READER)
    bench["per_layer"].append({
        "name": "rounds_per_request.shared", "unit": "rounds",
        "better": "lower", "source": "program_counter",
        "layer": "serving engine", "moves": "ttft_p95_ms",
        "workloads": ["mistral_8l_shared_prefix"]})
    # 5. an end-to-end metric the serve kind offers and no cell has yet
    bench["end_to_end"].append({
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["mistral_8l_shared_prefix"]})
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("mistral_8l_shared_prefix")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    after = {p: open(os.path.join(d, p), "rb").read()
             for d, _, files in os.walk(root / "benchmarks")
             for p in files for d in [d]}
    assert all(after[p] == before[p] for p in before), \
        "a file that was there was edited"
    return root


def _python(root, code):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_new_cell_is_found_by_name(copy):
    proc = _python(copy, (
        "import json, sys; sys.path.insert(0, '.')\n"
        "from benchmarks import run\n"
        "d = run.load_cell('.', 'mistral_8l_shared_prefix')\n"
        "from benchmarks.lib import loadgen, validate\n"
        "reqs = loadgen.generate(d['traffic'], 5, 20, 32768)\n"
        "exp = validate.expected_metrics(d['bench'], d['cell']['name'], "
        "True)\n"
        "print(json.dumps({'layers': d['config']['num_hidden_layers'],"
        " 'kind': d['config']['kind'], 'longest': max(len(r.prompt) "
        "for r in reqs), 'metrics': sorted(exp)}))\n"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["layers"] == 8 and out["kind"] == "serve"
    assert out["longest"] == 1024 + 512
    assert "rounds_per_request.shared" in out["metrics"]
    assert "ttft_p95_ms" in out["metrics"]
    assert "serve_tokens_per_s" not in out["metrics"]


def test_new_metric_is_read_by_its_own_reader(copy):
    proc = _python(copy, (
        "import json, sys, types; sys.path.insert(0, '.')\n"
        "from benchmarks import run\n"
        "d = run.load_cell('.', 'mistral_8l_shared_prefix')\n"
        "bench = dict(d['bench'], per_layer=[m for m in "
        "d['bench']['per_layer'] if m['name'].startswith('rounds')])\n"
        "ctx = types.SimpleNamespace(counters={'rounds': 500, "
        "'requests': 50}, metric=None)\n"
        "print(json.dumps(run.per_layer_values(bench, "
        "'mistral_8l_shared_prefix', ctx, print)))\n"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"rounds_per_request.shared": 10.0}


def test_a_run_without_a_tpu_prints_no_result(copy):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths``, and on a machine without a TPU: non-zero, no line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral_8l_shared_prefix", "--seed", "2147483659", "--seconds",
         "1", "--trace", "0"], cwd=copy, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_an_unknown_cell_is_refused(copy):
    proc = _python(copy, (
        "import sys; sys.path.insert(0, '.')\n"
        "from benchmarks import run\n"
        "run.load_cell('.', 'no_such_cell')\n"))
    assert proc.returncode != 0 and "no_such_cell" in proc.stderr
