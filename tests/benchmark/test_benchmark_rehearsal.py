"""CPU rehearsal of the benchmark's two kinds at a tiny size: the same
``kinds/train.py`` and ``kinds/serve.py`` that ``run.py`` drives on the
chip, imported as ``tests/test_chip_smoke.py`` imports the smoke's phases
(``run.py`` has no CPU mode).  Shows control flow and counts; no number
here is a device metric."""

import jax

from benchmarks import run as bench_run
from benchmarks.families import bert, llama_dense
from benchmarks.kinds import serve, train
from benchmarks.lib import checks

TINY_BERT = {
    "kind": "train", "family": "bert", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 128, "max_position_embeddings": 128,
    "type_vocab_size": 2, "param_dtype": "float32",
    "compute_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 1e-4, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-6, "weight_decay": 0.01},
    "exchange": {"op": "average", "compression": "fp16"},
    "limits": {"loss_rel_gap": 1e-3, "grad_norm_gap": 2e-2,
               "change_norm_gap": 2e-2}}
TINY_TRAIN = {"seq_len": 32, "sequences_per_chip": 2,
              "masked_per_sequence": 5, "ring_batches": 4, "trace_steps": 2}
TINY_LLAMA = {
    "kind": "serve", "family": "llama_dense", "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 8, "head_dim": 16,
    "max_position_embeddings": 128, "rope_theta": 1e6,
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 8, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3}}
TINY_SERVE = {"arrival": "poisson", "prompt_lens": [8, 16],
              "output_lens": [4, 8], "rate_rps": 50.0, "num_requests": 10,
              "trace_rounds": 4}


def _ctx(config, traffic, family, devices, seed=3, seconds=0.5):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "", devices, family,
                                 logs.append)
    return ctx, logs


def test_train_kind_tiny(hvd):
    ctx, logs = _ctx(TINY_BERT, TINY_TRAIN, bert, jax.devices())
    out = train.run(ctx)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    by_name = {c.name: c for c in out["checks"]}
    # The CPU's memory_stats reports nothing; every other check holds.
    assert by_name["replica_leaves_that_differ"].value == 0
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]
    assert out["counters"]["wire_bytes_per_step"] > 0


def test_train_kind_catches_a_step_that_returns_its_state(hvd, monkeypatch):
    """The timed path broken underneath: a step that hands its state back
    unchanged.  The rest of the run is driven and ``correct`` is false."""
    real = bert.Program.call

    def stuck(self, i):
        p, o = self.params, self.opt_state
        loss = real(self, i)
        if i >= 1:
            self.params, self.opt_state = p, o
        return loss

    # Donation would free the kept state: rebuild the step without it.
    import horovod_tpu
    make = horovod_tpu.make_train_step
    monkeypatch.setattr(horovod_tpu, "make_train_step",
                        lambda f, o: make(f, o, donate=False))
    monkeypatch.setattr(bert.Program, "call", stuck)
    ctx, _ = _ctx(TINY_BERT, TINY_TRAIN, bert, jax.devices())
    out = train.run(ctx)
    assert not checks.all_ok(out["checks"])
    bad = [c.name for c in out["checks"] if not c.ok]
    assert "param_change_norm_total_gap" in bad


def test_serve_kind_tiny():
    ctx, logs = _ctx(TINY_LLAMA, TINY_SERVE, llama_dense, jax.devices()[:1])
    out = serve.run(ctx)
    assert out["attempted"] == 10 and out["failed"] == 0
    e = out["end_to_end"]
    assert e["serve_tokens_per_s"] > 0 and e["ttft_p95_ms"] > 0
    assert e["tpot_p95_ms"] > 0
    # Untraced, every request was admitted "before the trace".
    assert len(out["counters"]["ttft_ms"]) == 10
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]


def test_serve_kind_catches_an_altered_token(monkeypatch):
    """The timed path broken underneath: the sampler's token altered
    where it is produced.  ``correct`` comes out false."""
    from horovod_tpu.serving import engine

    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 256)
    ctx, _ = _ctx(TINY_LLAMA, TINY_SERVE, llama_dense, jax.devices()[:1])
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok
    assert not checks.all_ok(out["checks"])
