"""The families' plain references against ``models/`` and ``serving/`` at
a tiny size on the CPU, and their operation and byte counts against
arithmetic done by hand."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import bert, llama_dense
from benchmarks.lib import weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


TINY_BERT = dict(config("bert_large"), vocab_size=256, hidden_size=64,
                 num_hidden_layers=3, num_attention_heads=4,
                 intermediate_size=128, max_position_embeddings=64)
TINY_LLAMA = dict(config("mistral_7b_v03"), vocab_size=256, hidden_size=64,
                  intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=8, num_key_value_heads=4, head_dim=8,
                  max_position_embeddings=128)


@pytest.fixture(scope="module")
def bert_case():
    from horovod_tpu.models import Bert
    model = Bert(bert.program_config(TINY_BERT), dtype=jnp.float32)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (3, 32)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)
    params = weights.make_weights(11, shapes, jnp.float32)
    return model, params, toks


def test_bert_reference_forward_matches_the_model(bert_case):
    model, params, toks = bert_case
    mlm, nsp = model.apply(params, toks)
    rmlm, rnsp = bert.ref_forward(bert.stack_layers(params), toks,
                                  TINY_BERT["num_attention_heads"])
    assert float(jnp.max(jnp.abs(mlm - rmlm))) < 2e-4
    assert float(jnp.max(jnp.abs(nsp - rnsp))) < 2e-4


def test_bert_reference_gradient_matches_the_models(bert_case):
    import optax
    model, params, toks = bert_case
    labels = jnp.roll(toks, 1, axis=1)
    w = (jnp.arange(32) % 4 == 0).astype(jnp.float32)[None].repeat(3, 0)
    nsp_y = jnp.asarray([0, 1, 1])

    def loss(p):
        mlm, nsp = model.apply(p, toks)
        x = optax.softmax_cross_entropy_with_integer_labels(mlm, labels)
        return ((x * w).sum() / w.sum()
                + optax.softmax_cross_entropy_with_integer_labels(
                    nsp, nsp_y).mean())

    want = bert.leaf_norms(jax.grad(loss)(params))
    got_loss, got = jax.value_and_grad(bert.ref_block_loss)(
        bert.stack_layers(params), (toks, labels, w, nsp_y),
        TINY_BERT["num_attention_heads"], 3, 8, None)
    got = bert.stacked_norms(got)
    assert float(got_loss) == pytest.approx(float(loss(params)), rel=1e-5)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=2e-3, abs=1e-7), k


def test_bert_counts_by_hand():
    cfg = config("bert_large")
    assert bert.matmul_params(cfg) == (
        24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 1024 * 1024
        + 30522 * 1024)
    per_token = bert.flops_per_token(cfg, 128)
    assert per_token == pytest.approx(2.0447e9, rel=1e-3)
    cost = bert.flash_attention_cost(cfg, 32, 128)
    # 24 layers x 32 sequences x 16 heads of 64: 12 T^2 D operations and
    # 11 tensors of T x D bf16 a head.
    assert cost["flops"] == 12 * 128 * 128 * 64 * 24 * 32 * 16
    assert cost["bytes"] == 11 * 128 * 64 * 2 * 24 * 32 * 16
    # Bound by bytes at sequence 128 on a v5e.
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9


def test_bert_batches_of_a_seed():
    t = {"ring_batches": 3, "seq_len": 32, "sequences_per_chip": 4,
         "masked_per_sequence": 5}
    a = bert.make_batches(TINY_BERT, t, 2, 2 ** 31 + 5)
    b = bert.make_batches(TINY_BERT, t, 2, 2 ** 31 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    toks_in, labels, w, nsp = a
    assert toks_in.shape == (3, 8, 32) and nsp.shape == (3, 8)
    assert np.all(w.sum(axis=-1) == 5)
    assert np.all(toks_in[w > 0] == bert.MASK_ID)
    assert np.all(toks_in[w == 0] == labels[w == 0])
    rows = labels.reshape(-1, 32)
    assert len({r.tobytes() for r in rows}) == len(rows)


@pytest.fixture(scope="module")
def llama_case():
    from horovod_tpu.models import LlamaLM
    cfg = llama_dense.program_config(TINY_LLAMA)
    model = LlamaLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    params = weights.make_weights(13, shapes, jnp.float32)
    ctx = np.random.RandomState(1).randint(0, 256, (40,))
    return cfg, model, params, ctx


def test_llama_reference_matches_the_model(llama_case):
    cfg, model, params, ctx = llama_case
    want = model.apply(params, jnp.asarray(ctx)[None])[0]
    ref = llama_dense.Reference(TINY_LLAMA, params, pad_to=48)
    got = ref.logits(ctx, 10, 20)
    assert float(jnp.max(jnp.abs(got - want[10:30]))) < 2e-4


def test_llama_reference_matches_the_serving_prefill(llama_case):
    from horovod_tpu.serving.decode import prefill_forward
    cfg, model, params, ctx = llama_case
    want, _, _ = prefill_forward(params, cfg, jnp.asarray(ctx)[None])
    got = llama_dense.Reference(TINY_LLAMA, params, pad_to=40).logits(
        ctx, 0, 40)
    assert float(jnp.max(jnp.abs(got - want[0]))) < 2e-4


def test_llama_counts_by_hand():
    cfg = config("mistral_7b_v03")
    assert llama_dense.kv_bytes_per_token(cfg) == 64 * 1024
    layer = (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
             + 2 * 4096)
    assert llama_dense.weight_bytes(cfg) == 2 * (
        16 * layer + 32768 * 4096 + 4096)
    assert llama_dense.weight_bytes(cfg) == pytest.approx(7.25e9, rel=2e-3)
    s = cfg["serving"]
    pool = (s["slots"] * s["max_len"] + s["page_size"]) \
        * llama_dense.kv_bytes_per_token(cfg)
    assert pool == pytest.approx(3.0 * 2 ** 30, rel=2e-3)


def test_served_gaps_read_zero_for_the_references_own_tokens(llama_case):
    cfg, model, params, ctx = llama_case
    logits = np.asarray(model.apply(params, jnp.asarray(ctx)[None])[0])
    served = logits[19:39].argmax(-1)       # teacher-forced greedy tokens
    full = np.concatenate([ctx[:20], served])
    logits = np.asarray(model.apply(params, jnp.asarray(full)[None])[0])
    served = logits[19:39].argmax(-1)
    out = llama_dense.served_gaps(
        TINY_LLAMA, params, [(full[:20], list(served))], pad_to=48,
        with_control=True)
    assert out["tokens_compared"] == 20
    # Only row 0 is sure to be greedy under its own context; the gap of a
    # greedy token is zero, and the widest cannot be negative.
    assert out["served_logit_gap_max"] >= 0.0
    wrong = (served + 1) % 256
    worse = llama_dense.served_gaps(
        TINY_LLAMA, params, [(full[:20], list(wrong))], pad_to=48)
    assert worse["served_logit_gap_max"] > out["served_logit_gap_max"]
    assert worse["served_logit_gap_max"] > 0.1


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 1, 2 ** 40 + 3])
def test_weights_of_a_seed(seed):
    shapes = {"params": {"tok_embed": jax.ShapeDtypeStruct((64, 32),
                                                           jnp.float32),
                         "l": {"kernel": jax.ShapeDtypeStruct(
                             (256, 128), jnp.float32),
                             "bias": jax.ShapeDtypeStruct((128,),
                                                          jnp.float32),
                             "scale": jax.ShapeDtypeStruct((128,),
                                                           jnp.float32)}}}
    a = weights.make_weights(seed, shapes, jnp.bfloat16)
    b = weights.make_weights(seed, shapes, jnp.bfloat16)
    c = weights.make_weights(seed + 1, shapes, jnp.bfloat16)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == jnp.bfloat16 and bool(jnp.all(x == y))
    k = np.asarray(a["params"]["l"]["kernel"], np.float32)
    assert abs(k.std() * 16 - 1) < 0.05 and abs(k.mean()) < 0.01
    assert bool(jnp.all(a["params"]["l"]["scale"] == 1))
    assert bool(jnp.all(a["params"]["l"]["bias"] == 0))
    assert not bool(jnp.all(a["params"]["l"]["kernel"]
                            == c["params"]["l"]["kernel"]))
