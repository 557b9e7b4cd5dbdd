"""Wire accounting of the model zoo, from shapes alone (no compile).

For every model of the zoo and every cast codec, the step report's
accounting of the default (leaf-wise) gradient exchange must read:
raw bytes = 4 x the model's parameter count, wire bytes = raw x the
codec's ratio, packed bytes 0 -- and the same at every world size.  The
parameter count is pinned from outside the program: the published count
where the zoo follows a published layout, a closed form over the config
written out here otherwise.
"""

import functools

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import models as M
from horovod_tpu import training
from horovod_tpu.collectives.compression import Compression

IMG = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
TOK = jax.ShapeDtypeStruct((1, 16), jnp.int32)


def _bert_params(c):
    """Bert as the zoo builds it: pre-LN, pooler, MLM transform over the
    tied embedding (no output bias), NSP head."""
    d, f = c.d_model, c.ffn_hidden
    emb = (c.vocab_size + c.max_seq_len + c.type_vocab_size) * d + 2 * d
    layer = 4 * (d * d + d) + (d * f + f) + (f * d + d) + 2 * 2 * d
    heads = (d * d + d) + (d * d + d + 2 * d) + 2 * d + (2 * d + 2)
    return emb + c.num_layers * layer + heads


def _llama_projections(c):
    """(fan_in, fan_out) of a layer's seven projections."""
    d, q, kv = c.d_model, c.num_heads * c.head_dim, \
        c.num_kv_heads * c.head_dim
    return [(d, q), (d, kv), (d, kv), (q, d),
            (d, c.ffn_hidden), (d, c.ffn_hidden), (c.ffn_hidden, d)]


def _llama_params(c):
    """LlamaLM with the head tied to the embedding."""
    layer = sum(i * o for i, o in _llama_projections(c)) + 2 * c.d_model
    return c.vocab_size * c.d_model + c.num_layers * layer + c.d_model


def _lora_params(c, rank):
    """Rank-``rank`` adapters on all seven projections of every layer."""
    return c.num_layers * sum(rank * (i + o)
                              for i, o in _llama_projections(c))


def _init_shapes(model, *args, **kw):
    tree = jax.eval_shape(lambda k, *a: model.init(k, *a, **kw),
                          jax.random.PRNGKey(0), *args)
    return tree["params"]


def _lora_trainable():
    model = M.LlamaLM(M.LLAMA3_8B, lora_rank=8, base_dtype="int8")
    trainable, frozen = M.split_frozen(_init_shapes(model, TOK))
    assert all(jax.tree.leaves(M.lora_mask(trainable)))
    assert not any(jax.tree.leaves(M.lora_mask(frozen)))
    return trainable


# name -> (builder of the trainable shapes, parameter count, its origin)
ZOO = {
    "lenet": (lambda: _init_shapes(
        M.LeNet(), jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32)),
        156 + 2416 + 30840 + 10164 + 850, "its five layers at 28x28"),
    "resnet18": (lambda: _init_shapes(M.ResNet18(), IMG, train=False),
                 11_689_512, "torchvision"),
    "resnet34": (lambda: _init_shapes(M.ResNet34(), IMG, train=False),
                 21_797_672, "torchvision"),
    "resnet50": (lambda: _init_shapes(M.ResNet50(), IMG, train=False),
                 25_557_032, "torchvision"),
    "resnet101": (lambda: _init_shapes(M.ResNet101(), IMG, train=False),
                  44_549_160, "torchvision"),
    "resnet152": (lambda: _init_shapes(M.ResNet152(), IMG, train=False),
                  60_192_808, "torchvision"),
    "vgg16": (lambda: _init_shapes(M.VGG16(), IMG, train=False),
              138_357_544, "torchvision"),
    "vgg19": (lambda: _init_shapes(M.VGG19(), IMG, train=False),
              143_667_240, "torchvision"),
    "inception_v3": (lambda: _init_shapes(
        M.InceptionV3(), jax.ShapeDtypeStruct((1, 299, 299, 3),
                                              jnp.float32), train=False),
        23_834_568, "torchvision, aux_logits=False"),
    "bert_base": (lambda: _init_shapes(M.Bert(M.BERT_BASE), TOK),
                  _bert_params(M.BERT_BASE), "closed form"),
    "bert_large": (lambda: _init_shapes(M.Bert(M.BERT_LARGE), TOK),
                   _bert_params(M.BERT_LARGE), "closed form"),
    "llama_1b": (lambda: _init_shapes(M.LlamaLM(M.LLAMA_1B), TOK),
                 _llama_params(M.LLAMA_1B), "closed form"),
    "llama3_8b_lora": (_lora_trainable, _lora_params(M.LLAMA3_8B, 8),
                       "closed form; PEFT reports 20,971,520"),
}

# codec -> wire bytes over raw bytes for float32 gradients
CODECS = {"none": (Compression.none, 1.0), "fp16": (Compression.fp16, 0.5),
          "bf16": (Compression.bf16, 0.5)}


@functools.cache
def _shapes(name):
    """One trace a model, shared by its three codec cases."""
    return ZOO[name][0]()


def _accounting(shapes, codec, world):
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), compression=codec)
    return training._step_exchange_accounting(
        shapes, {"optimizer": opt, "world": world})


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("name", ZOO)
def test_zoo_wire_bytes_follow_parameter_count(name, codec):
    _, count, origin = ZOO[name]
    shapes = _shapes(name)
    leaves = jax.tree.leaves(shapes)
    assert {str(x.dtype) for x in leaves} == {"float32"}, name
    assert sum(int(x.size) for x in leaves) == count, (name, origin)
    comp, ratio = CODECS[codec]
    rows = {w: _accounting(shapes, comp, w) for w in (2, 8, 256)}
    label, wire, raw, packed = rows[2]
    assert label == comp.__name__
    assert raw == 4 * count
    assert wire == int(raw * ratio)
    assert packed == 0          # the leaf-wise exchange builds no buffer
    assert rows[8] == rows[2] == rows[256]      # mesh-size invariant


def test_bert_large_fp16_wire_is_the_ledgers():
    """`bert_large_dp4` reads `exchange_wire_mb_per_step` 672.4 on every
    ledger line since PR 23 (bytes / 1e6, as the reader divides)."""
    _, wire, raw, _ = _accounting(_shapes("bert_large"),
                                  Compression.fp16, 4)
    assert round(wire / 1e6, 1) == 672.4
    assert raw == 1_344_790_536     # PERF.md section 3, packed_bytes row
