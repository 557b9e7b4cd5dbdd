"""CPU rehearsal of ``chip_smoke.py``: its three phase functions at tiny
configs on the 8-device virtual mesh, its refusal to run without a TPU,
and the import contract its one-process-per-chip rule rests on."""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from horovod_tpu.models import BERT_TINY
from horovod_tpu.models.resnet import BasicBlock, ResNet
from horovod_tpu.models.transformer import LLAMA_SERVE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_rn50_tiny(hvd, n_devices):
    # A one-stage ResNet: the phase's code path at a size the CPU runs.
    model = ResNet(stage_sizes=[1], block_cls=BasicBlock, num_filters=8,
                   num_classes=100, dtype=jnp.bfloat16)
    out = chip_smoke.phase_rn50(model, (32, 32, 3), 100, batch_per_chip=4,
                                steps=3)
    assert out["steps"] == 1 + chip_smoke.WARM_STEPS + 2 * 3
    assert out["last_loss"] < out["first_loss"]
    assert out["spread"]["size"] == n_devices
    assert out["spread"]["psum_axis_index"] == n_devices * (n_devices - 1) // 2


def test_phase_bert_tiny(hvd):
    out = chip_smoke.phase_bert(BERT_TINY, jnp.float32, batch_per_chip=2,
                                seq=32, steps=3, mosaic_calls=0)
    assert out["last_loss"] < out["first_loss"]


def test_phase_bert_counts_mosaic_calls(hvd):
    """Off TPU the flash dispatcher takes the XLA reference; a smoke that
    expects kernels must fail, not assume."""
    with pytest.raises(AssertionError, match="0 Mosaic calls, expected 6"):
        chip_smoke.phase_bert(BERT_TINY, jnp.float32, batch_per_chip=2,
                              seq=32, steps=1, mosaic_calls=6)


def test_phase_server_tiny(n_devices):
    out = chip_smoke.phase_server(
        LLAMA_SERVE, slots=4, page_size=8, max_len=64, prompt_lens=(4, 8),
        output_lens=(4, 8), num_requests=6, mosaic_calls=0)
    assert out["completed"] == 6 and out["rejected"] == 0
    assert out["live_pages"] == 0 and out["tp"] == n_devices
    parity = out["greedy_parity"]
    assert parity["agree"] == parity["tokens"]


def test_imports_touch_no_backend_and_main_refuses_cpu():
    """One subprocess, two contracts.  (1) The launcher parent and every
    script that spawns workers import the package before starting
    children, and a parent that has touched jax's backend holds the chip
    its child needs: importing ``horovod_tpu``,
    ``horovod_tpu.serving`` and ``horovod_tpu.run.launch`` (and
    ``chip_smoke`` itself) must initialize no backend -- which is also what
    lets conftest, the examples and the driver dryrun force a device count
    after importing ``utils.platform``.  (2) ``chip_smoke.main()`` exits
    non-zero without a TPU and runs no phase."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "import horovod_tpu, horovod_tpu.serving, horovod_tpu.run.launch\n"
         "from horovod_tpu.utils.platform import backend_initialized\n"
         "assert not backend_initialized(), 'import initialized a backend'\n"
         "print('IMPORT_CLEAN')\n"
         "sys.exit(chip_smoke.main())"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "JAX_PLATFORMS": "cpu"})
    assert "IMPORT_CLEAN" in proc.stdout, (proc.stdout[-2000:],
                                           proc.stderr[-2000:])
    assert proc.returncode == 1
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and "smoke A" not in proc.stdout
