"""Smoke-run every example workload on the CPU mesh (reference CI runs
its examples per framework; BASELINE.json names these five configs)."""

import ast
import importlib
import inspect
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable] + args, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


@pytest.mark.integration
def test_bert_pretrain_example_cpu():
    out = _run([os.path.join(REPO, "examples", "bert_pretrain.py"),
                "--cpu-devices", "4", "--steps", "6"])
    assert "final loss" in out


@pytest.mark.integration
def test_llama_lora_example_cpu():
    out = _run([os.path.join(REPO, "examples", "llama_lora.py"),
                "--cpu-devices", "4", "--steps", "6"])
    assert "final loss" in out


@pytest.mark.integration
def test_synthetic_benchmark_resnet50_cpu():
    out = _run([os.path.join(REPO, "examples", "synthetic_benchmark.py"),
                "--model", "resnet50", "--cpu-devices", "4",
                "--image-size", "64", "--batch-size", "2",
                "--num-iters", "2", "--fp32"])
    assert "images/s/chip" in out


@pytest.mark.integration
def test_long_context_example_cpu():
    out = _run([os.path.join(REPO, "examples", "long_context.py"),
                "--cpu-devices", "8", "--seq-len", "256", "--steps", "8",
                "--compare-single-device"])
    assert "PARITY OK" in out


@pytest.mark.integration
def test_long_context_example_ulysses_cpu():
    out = _run([os.path.join(REPO, "examples", "long_context.py"),
                "--cpu-devices", "8", "--seq-len", "256", "--steps", "8",
                "--mode", "ulysses"])
    assert "final loss" in out


@pytest.mark.integration
def test_long_context_example_packed_cpu():
    """Packed x2 sequences with segment isolation through the sp mesh,
    parity-checked against the single-device segment reference."""
    out = _run([os.path.join(REPO, "examples", "long_context.py"),
                "--cpu-devices", "8", "--seq-len", "256", "--steps", "8",
                "--packed", "--compare-single-device"])
    assert "PARITY OK" in out
    assert "packed x2" in out


@pytest.mark.integration
def test_metrics_probe_example_cpu():
    out = _run([os.path.join(REPO, "examples", "metrics_probe.py"),
                "--cpu-devices", "2", "--steps", "3"])
    assert "metrics probe OK" in out
    assert "horovod_step_total 3" in out
    assert "exchange plan" in out


@pytest.mark.integration
def test_straggler_probe_example_cpu(tmp_path):
    """8-rank virtual-mesh drill: the chaos `slow` fault stalls one
    rank, the straggler monitor and the merged-trace report must both
    name it with a dispatch_gap-dominated step (the probe asserts this
    internally; the entry it writes is checked here).  The stall falls
    before the LAST step: the monitor names the rank by an average that
    forgets (0.3 s at step 4 of 10 is 11 ms of it by the end, less than a
    busy host's jitter) and the span by the rank's last step, which then
    is the stalled one.  It is 1.5 s long: the merged report calls a rank
    host-bound by its gaps against its compute over the whole run, and
    ten steps' compute is 0.15 s alone and 0.5 s beside five other test
    workers."""
    bench = tmp_path / "straggler.json"
    out = _run([os.path.join(REPO, "examples", "straggler_probe.py"),
                "--steps", "10", "--slow-rank", "3", "--slow-step", "9",
                "--slow-secs", "1.5", "--bench-json", str(bench)])
    assert "straggler probe OK" in out
    assert "straggler: rank 3" in out
    assert "dispatch_gap" in out
    assert "host-bound" in out
    doc = json.loads(bench.read_text())
    st = doc["parsed"]["straggler"]
    assert st["detected_rank"] == 3 and st["injected_rank"] == 3
    assert st["merged_ranks"] == st["world"] == 8
    assert "slow@step=9" in st["spec"] and st["dominant_span"]


@pytest.mark.integration
def test_llama_lora_multi_adapter_serving_cpu():
    """Three LoRA adapters share one base model in a single decode
    batch; each slot's stream must match a dedicated engine running
    that adapter merged into the base weights (asserted internally)."""
    out = _run([os.path.join(REPO, "examples", "llama_lora.py"),
                "--serve-adapters", "3", "--cpu-devices", "1"])
    assert "multi-LoRA serve OK: 3 adapters" in out
    assert "adapter 2: 10 tokens match merged-weight reference" in out


@pytest.mark.integration
def test_serving_probe_example_cpu(tmp_path):
    """8-device virtual-mesh serving drill: the probe scrapes its own
    /metrics endpoint and asserts the request-lifecycle families and
    span attribution (internally); the entry it writes is checked here."""
    bench = tmp_path / "serving.json"
    out = _run([os.path.join(REPO, "examples", "serving_probe.py"),
                "--requests", "12", "--bench-json", str(bench)])
    assert "serving probe OK" in out
    assert "tokens/s" in out
    doc = json.loads(bench.read_text())
    sv = doc["parsed"]["serving"]
    assert sv["world"] == 8 and sv["slots"] >= 1
    assert sv["completed"] == sv["requests"] - sv["rejected"] == 12
    assert 0 < sv["batch_occupancy"] <= 1


@pytest.mark.integration
def test_serving_probe_long_prompts_cpu():
    """Kilotoken-mixture drill through chunked flash prefill: the probe
    asserts internally that the serving_prefill_chunk span leg fired
    (long admissions sliced and interleaved with decode) alongside the
    whole-prompt serving_prefill leg for the short end of the mix."""
    out = _run([os.path.join(REPO, "examples", "serving_probe.py"),
                "--long-prompts", "--requests", "4"])
    assert "serving probe OK" in out


@pytest.mark.integration
def test_autoscale_probe_example_cpu(tmp_path):
    """Closed-loop chaos drill: kill@ forces a drain + shrink, slow@
    gets the rank auto-evicted, zero requests lost; the probe asserts
    the horovod_ctl_* families against its own /metrics endpoint
    (internally) and the entry it writes is checked here."""
    bench = tmp_path / "autoscale.json"
    out = _run([os.path.join(REPO, "examples", "autoscale_probe.py"),
                "--requests", "32", "--bench-json", str(bench)])
    assert "autoscale probe OK" in out
    assert "0 lost" in out
    doc = json.loads(bench.read_text())
    a = doc["parsed"]["autoscale"]
    assert a["lost_requests"] == 0 and a["drain_leaked_pages"] == 0
    assert 1 <= a["final_tp"] < a["initial_tp"]
    assert a["completed"] == a["requests"] - a["rejected"]
    assert a["decisions"]["shrink"] >= 1 and a["decisions"]["evict"] >= 1
    assert a["dead_ranks"] and a["evicted_ranks"]


@pytest.mark.integration
def test_torch_resnet50_example_cpu():
    out = _run([os.path.join(REPO, "examples", "torch_resnet50.py"),
                "--cpu-devices", "2", "--image-size", "64",
                "--batch-size", "2", "--steps", "2"])
    assert "torch resnet50 OK" in out


@pytest.mark.integration
def test_tf2_resnet50_example_cpu():
    out = _run([os.path.join(REPO, "examples", "tf2_resnet50.py"),
                "--cpu-devices", "2", "--image-size", "64",
                "--batch-size", "2", "--steps", "2"])
    assert "tf2 resnet50 OK" in out


@pytest.mark.integration
def test_allreduce_benchmark_cpu():
    out = _run([os.path.join(REPO, "examples", "allreduce_benchmark.py"),
                "--cpu-devices", "4", "--sizes-mb", "1", "--iters", "2"])
    assert "bus>=" in out


@pytest.mark.integration
def test_tensorflow2_mnist_two_process():
    out = _run(["-m", "horovod_tpu.run", "-np", "2", "--cpu",
                sys.executable,
                os.path.join(REPO, "examples", "tensorflow2_mnist.py"),
                "--steps", "12"])
    assert "avg final loss" in out


# -- every example reaches only for names the package has -------------------

def _package_names_reached(tree):
    """``(lineno, "horovod_tpu.a.b")`` for every name of the package a
    file reaches for: what it imports from it, and every attribute chain
    rooted at an alias of it (``hvd.x.y``)."""
    aliases, reached = {}, []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "horovod_tpu":
                    reached.append((node.lineno, a.name))
                    # without `as`, `import horovod_tpu.x` binds the top
                    aliases[a.asname or "horovod_tpu"] = \
                        a.name if a.asname else "horovod_tpu"
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and (node.module or "").split(".")[0] == "horovod_tpu":
            for a in node.names:
                reached.append((node.lineno, f"{node.module}.{a.name}"))
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    for node in ast.walk(tree):
        chain, cur = [], node
        while isinstance(cur, ast.Attribute):
            chain.append(cur.attr)
            cur = cur.value
        if chain and isinstance(cur, ast.Name) and cur.id in aliases:
            reached.append((node.lineno, ".".join(
                [aliases[cur.id]] + chain[::-1])))
    return reached


def _first_missing(dotted):
    """The shortest prefix of ``dotted`` the package does not have, or
    None.  A module or a class must have the next name (a submodule may
    still need importing); below any other object (an instance, a
    function) the walk stops: those are not the package's to promise."""
    parts = dotted.split(".")
    obj = importlib.import_module(parts[0])
    for i, part in enumerate(parts[1:], 2):
        if not (inspect.ismodule(obj) or inspect.isclass(obj)):
            return None
        if not hasattr(obj, part):
            try:
                importlib.import_module(".".join(parts[:i]))
            except ImportError:
                return ".".join(parts[:i])
        obj = getattr(obj, part)
    return None


EXAMPLES = sorted(f for f in os.listdir(os.path.join(REPO, "examples"))
                  if f.endswith(".py"))


@pytest.mark.parametrize("example", EXAMPLES)
def test_example_reaches_only_for_names_the_package_has(example):
    path = os.path.join(REPO, "examples", example)
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    missing = sorted({(line, name) for line, name in
                      _package_names_reached(tree)
                      if _first_missing(name) is not None})
    assert not missing, f"{example} reaches for names horovod_tpu does " \
                        f"not have: {missing}"
