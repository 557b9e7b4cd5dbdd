"""Smoke-run every example workload on the CPU mesh (reference CI runs
its examples per framework; BASELINE.json names these five configs)."""

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
    internally; the bench entry is validated here)."""
    bench = tmp_path / "BENCH_r99.json"
    out = _run([os.path.join(REPO, "examples", "straggler_probe.py"),
                "--steps", "10", "--slow-rank", "3", "--slow-step", "4",
                "--slow-secs", "0.3", "--bench-json", str(bench)])
    assert "straggler probe OK" in out
    assert "straggler: rank 3" in out
    assert "dispatch_gap" in out
    assert "host-bound" in out
    doc = json.loads(bench.read_text())
    st = doc["parsed"]["straggler"]
    assert st["detected_rank"] == 3 and st["injected_rank"] == 3
    assert st["merged_ranks"] == 8
    from test_bench_guard import scan_straggler_entries
    assert scan_straggler_entries(str(tmp_path)) == []


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
    span attribution (internally); the bench entry is validated here."""
    bench = tmp_path / "BENCH_r98.json"
    out = _run([os.path.join(REPO, "examples", "serving_probe.py"),
                "--requests", "12", "--bench-json", str(bench)])
    assert "serving probe OK" in out
    assert "tokens/s" in out
    doc = json.loads(bench.read_text())
    sv = doc["parsed"]["serving"]
    assert sv["world"] == 8 and sv["completed"] == sv["requests"]
    from test_bench_guard import scan_serving_entries
    assert scan_serving_entries(str(tmp_path)) == []


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
    (internally) and the bench entry is validated here."""
    bench = tmp_path / "BENCH_r99.json"
    out = _run([os.path.join(REPO, "examples", "autoscale_probe.py"),
                "--requests", "32", "--bench-json", str(bench)])
    assert "autoscale probe OK" in out
    assert "0 lost" in out
    doc = json.loads(bench.read_text())
    a = doc["parsed"]["autoscale"]
    assert a["lost_requests"] == 0 and a["drain_leaked_pages"] == 0
    assert a["final_tp"] < a["initial_tp"]
    from test_bench_guard import scan_autoscale_entries
    assert scan_autoscale_entries(str(tmp_path)) == []


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
