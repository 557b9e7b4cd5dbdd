"""The controls of ``correct`` at a size a test run can hold: the plain
reference put in the program's place and computed in the nearest
precision below the configurations' bfloat16 (int8 for the trained model,
fp8 for the served one, as on the chip) comes out NOT correct
through the same comparison, where the sound program comes out correct.
The chip-size readings that the real limits were set from are in PERF.md
section 2; ``benchmarks/tools/limits.py --control int8|fp8`` reads them again."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import bert, llama_dense
from benchmarks.kinds import train
from benchmarks.lib import checks, weights
from test_benchmark_rehearsal import TINY_BERT, TINY_LLAMA, TINY_TRAIN

BF16_BERT = dict(TINY_BERT, compute_dtype="bfloat16")


@pytest.fixture(scope="module")
def train_readings():
    """Program (bfloat16 compute) and int8 control against the float32
    reference (one seed here; the chip's readings cover 22)."""
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init()
    chips = len(jax.devices())
    sound, control = [], []
    for seed in (2 ** 31 + 4,):
        prog = bert.Program(BF16_BERT, TINY_TRAIN, chips, seed)
        got = train.drive_first_steps(prog)
        shapes = prog.shapes
        prog.free()
        ref = bert.ref_first_steps(BF16_BERT, TINY_TRAIN, chips, seed,
                                   shapes)
        ctl = bert.ref_first_steps(BF16_BERT, TINY_TRAIN, chips, seed,
                                   shapes, quant="int8")
        loose = {k: 1e9 for k in ("loss_rel_gap", "grad_norm_gap",
                                  "change_norm_gap")}
        sound.append({c.name: c.value for c in
                      train.first_step_checks(got, ref, loose)})
        control.append({c.name: c.value for c in
                        train.first_step_checks(ctl, ref, loose)})
    hvd.shutdown()
    return sound, control


def test_int8_control_fails_the_training_comparison(train_readings):
    sound, control = train_readings
    names = sound[0].keys()
    largest = {n: max(s[n] for s in sound) for n in names}
    smallest = {n: min(c[n] for c in control) for n in names}
    # A limit at three times the sound runs' largest: the sound runs
    # pass every number, the control fails at least one.
    separated = [n for n in names if smallest[n] > 3 * largest[n]]
    assert separated, (largest, smallest)
    assert any(n.startswith("first_grad_norm") for n in separated)


def test_sound_training_numbers_are_small(train_readings):
    sound, _ = train_readings
    for s in sound:
        assert s["loss_step1_rel_gap"] < 5e-3
        assert s["first_grad_norm_p99_leaf_gap"] < 5e-2
        assert s["param_change_norm_total_gap"] < 5e-2


def test_fp8_control_fails_the_served_comparison():
    from horovod_tpu.models import LlamaLM
    cfg = llama_dense.program_config(TINY_LLAMA)
    model = LlamaLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        params = weights.make_weights(seed, shapes, jnp.float32)
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for _ in range(1):
            prompt = rng.randint(0, 256, (24,))
            ctx = list(prompt)
            for _ in range(16):     # the program's greedy stream
                logits = model.apply(params, jnp.asarray(ctx)[None])[0, -1]
                ctx.append(int(jnp.argmax(logits)))
            sample.append((prompt, ctx[24:]))
        out = llama_dense.served_gaps(TINY_LLAMA, params, sample, pad_to=48,
                                      with_control=True)
        worst_sound = max(worst_sound, out["served_logit_gap_max"])
        least_control = min(least_control, out["control_logit_gap_max"])
    assert worst_sound < 1e-3
    assert least_control > 3 * max(worst_sound, 1e-3)
    limit = TINY_LLAMA["limits"]["served_logit_gap_max"]
    assert checks.Check("served", worst_sound, limit).ok
    assert not checks.Check("served", least_control, limit).ok


def test_a_check_with_a_nan_is_not_ok():
    assert not checks.Check("x", float("nan"), 1.0).ok
    assert not checks.all_ok([])
    assert checks.all_ok([checks.Check("x", 0.0, 0)])
