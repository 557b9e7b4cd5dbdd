"""The ``bert`` family: the BERT encoder with MLM + NSP heads.

What the harness takes from here: how the train step and its state are
built from the program's own entry points (``Bert``,
``hvd.DistributedOptimizer``, ``hvd.make_train_step``), the batches of a
seed, the operation and byte counts, the names its kernels carry in a
device trace, and the plain reference.

The reference (``ref_*``) is straight ``jax.numpy`` in float32 at
``highest`` matmul precision: no kernels, no flax, nothing imported from
``horovod_tpu``.  It follows the program's architecture, which departs
from the published one (Devlin et al., arXiv:1810.04805) in three places,
all noted in the configuration file: layer norm BEFORE each sub-block
(published: after), the tanh approximation of GELU, and no dropout.
"""

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import loadgen, weights
from ..lib.lowprec import MATMUL

MASK_ID = 103                       # [MASK] in the uncased vocabulary
# The step's only Pallas kernels are flash attention's (forward, dq,
# dk/dv): the reader takes every Mosaic call on the ops line.


# -- sizes ------------------------------------------------------------------

def program_config(config: dict):
    from horovod_tpu.models.transformer import BertConfig
    return BertConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        d_model=config["hidden_size"],
        ffn_hidden=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"])


def matmul_params(config: dict) -> int:
    """Parameters that a token is multiplied by: the blocks, the MLM
    transform and the tied readout (the pooler and NSP head see one
    position a sequence and are left out)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return (config["num_hidden_layers"] * (4 * d * d + 2 * d * f)
            + d * d + config["vocab_size"] * d)


def flops_per_token(config: dict, seq: int) -> float:
    """Operations the forward and backward passes need for one token:
    6 x matmul parameters, and attention's QK^T and PV (2 * seq * d each
    forward, twice that backward).  Recomputation is not counted."""
    d = config["hidden_size"]
    return (6.0 * matmul_params(config)
            + 12.0 * config["num_hidden_layers"] * seq * d)


def flash_attention_cost(config: dict, sequences: int, seq: int) -> dict:
    """Operations and bytes the attention of one step on one chip needs,
    forward and backward over every layer: 4 T^2 D forward and 8 T^2 D
    backward a head; q, k, v, o moved once forward and q, k, v, do in and
    dq, dk, dv out backward, in the compute type (2 bytes)."""
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    units = config["num_hidden_layers"] * sequences * heads
    return {"flops": 12.0 * seq * seq * hd * units,
            "bytes": 11.0 * seq * hd * 2 * units}


# -- batches ------------------------------------------------------------------

def make_batches(config: dict, traffic: dict, chips: int, seed: int):
    """The ring of batches of a seed, on the host: every row differs.
    ``(tokens_in, labels, mlm_weight, nsp_label)`` stacked on a leading
    ring axis.  Exactly ``masked_per_sequence`` positions a row are
    masked, so that every shard's loss has the same denominator and the
    mean of the shards' losses is the loss of the whole batch."""
    rng = loadgen.seed_rng(seed, stream=1)
    ring, seq = int(traffic["ring_batches"]), int(traffic["seq_len"])
    rows = int(traffic["sequences_per_chip"]) * chips
    masked = int(traffic["masked_per_sequence"])
    # Ids below 1000 are the vocabulary's special and unused entries.
    toks = rng.randint(min(1000, config["vocab_size"] // 2),
                       config["vocab_size"],
                       size=(ring, rows, seq)).astype(np.int32)
    order = np.argsort(rng.rand(ring, rows, seq), axis=-1)
    w = np.zeros((ring, rows, seq), np.float32)
    np.put_along_axis(w, order[..., :masked], 1.0, axis=-1)
    toks_in = np.where(w > 0, MASK_ID, toks).astype(np.int32)
    nsp = rng.randint(0, 2, size=(ring, rows)).astype(np.int32)
    return toks_in, toks, w, nsp


# -- the program's side ---------------------------------------------------------

@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def leaf_norms(tree) -> dict:
    """``{path: norm}`` of a tree's leaves: one small program a distinct
    shape, one fetch for all."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    vals = jax.device_get([_norm(x) for _, x in flat])
    return {weights.path_name(p): float(v) for (p, _), v in zip(flat, vals)}


class Program:
    """The compiled step with its state, built once and handed to the
    window."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 log=lambda msg: None):
        import time

        import optax

        import horovod_tpu as hvd
        from horovod_tpu.models import Bert
        from horovod_tpu.training import replicated_sharding, shard_batch

        self.config, self.traffic, self.chips = config, traffic, chips
        self.seq = int(traffic["seq_len"])
        self.rows = int(traffic["sequences_per_chip"]) * chips
        self.tokens_per_step = self.rows * self.seq
        dtype = jnp.dtype(config["compute_dtype"])
        model = Bert(program_config(config), dtype=dtype)
        self.shapes = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jnp.zeros((1, self.seq), jnp.int32))
        self.sharding = replicated_sharding()
        self.seed = seed
        self.param_dtype = jnp.dtype(config["param_dtype"])
        t0 = time.perf_counter()
        self.params = weights.make_weights(
            seed, self.shapes, self.param_dtype, self.sharding)
        jax.block_until_ready(self.params)
        log(f"weights made in {time.perf_counter() - t0:.2f} s")

        def loss_fn(p, batch):
            toks, labels, w, nsp_y = batch
            mlm, nsp = model.apply(p, toks)
            xent = optax.softmax_cross_entropy_with_integer_labels(
                mlm, labels)
            return ((xent * w).sum() / w.sum()
                    + optax.softmax_cross_entropy_with_integer_labels(
                        nsp, nsp_y).mean())

        o = config["optimizer"]
        self.b1 = float(o["b1"])
        ex = config["exchange"]
        self.opt = hvd.DistributedOptimizer(
            optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                        eps=o["eps"], weight_decay=o["weight_decay"]),
            compression=getattr(hvd.Compression, ex["compression"]))
        self.step = hvd.make_train_step(loss_fn, self.opt)
        self.opt_state = self.opt.init(self.params)
        host = make_batches(config, traffic, chips, seed)
        self.ring = [shard_batch(tuple(jnp.asarray(a[i]) for a in host))
                     for i in range(host[0].shape[0])]

    def call(self, i: int):
        """Step ``i`` through the window's own call and feed; returns the
        loss on the device."""
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, self.ring[i % len(self.ring)])
        return loss

    def first_gradient_norms(self) -> dict:
        """Per-leaf norm of the first gradient as the optimizer got it,
        from Adam's first moment after one step: mu = (1 - b1) g."""
        mus = [n for n in jax.tree_util.tree_leaves(
            self.opt_state, is_leaf=lambda n: hasattr(n, "mu"))
            if hasattr(n, "mu")]
        if not mus:
            raise RuntimeError("no Adam state in the optimizer's state")
        return {k: v / (1.0 - self.b1)
                for k, v in leaf_norms(mus[0].mu).items()}

    def change_norms(self) -> dict:
        """Per-leaf norm of params - seeded params; the seeded leaf is made
        again inside the small program that subtracts it."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.params)
        out = []
        for i, (path, x) in enumerate(flat):
            out.append(_change_norm(weights.leaf_kind(
                weights.path_name(path)), x.shape, self.param_dtype)(
                x, jnp.uint32(weights.leaf_salt(self.seed, i))))
        vals = jax.device_get(out)
        return {weights.path_name(p): float(v)
                for (p, _), v in zip(flat, vals)}

    def replica_spread(self) -> int:
        """Leaves whose replicas differ between chips (a checksum of the
        bits, chip by chip): 0 where the exchange kept them equal."""
        if self.chips == 1:
            return 0
        bad = 0
        for leaf in jax.tree.leaves(self.params):
            sums = jax.device_get(
                [_bits_sum(s.data) for s in leaf.addressable_shards])
            bad += len({int(x) for x in sums}) > 1
        return bad

    def wire_bytes_per_step(self):
        from horovod_tpu.timeline import metrics
        rep = metrics.last_step_report()
        return None if rep is None else int(rep.exchanged_bytes)

    def memory_analysis(self):
        """XLA's account of the compiled step (the cached lowering)."""
        lowered = self.step.lower(self.params, self.opt_state, self.ring[0])
        return lowered.compile().memory_analysis()

    def free(self):
        self.params = self.opt_state = self.ring = None


@functools.lru_cache(maxsize=None)
def _change_norm(kind: str, shape, dtype):
    return jax.jit(lambda x, salt: _norm(
        x - weights.leaf_values(salt, kind, shape, dtype)))


@jax.jit
def _bits_sum(x):
    return jnp.sum(jax.lax.bitcast_convert_type(
        x.astype(jnp.float32), jnp.uint32))


# -- the plain reference ----------------------------------------------------------

def _ln(x, node, eps=1e-12):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * node["scale"] + node["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def stack_layers(tree) -> dict:
    """``{"layers": every layer's leaves stacked on a leading axis,
    "rest": the others}``: the reference walks the layers with one
    compiled body (``lax.scan``), so it compiles in seconds."""
    p = tree["params"]
    names = sorted((k for k in p if k.startswith("layer_")),
                   key=lambda k: int(k.split("_")[1]))
    return {"layers": jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[p[k] for k in names]),
            "rest": {k: v for k, v in p.items() if k not in names}}


def ref_forward(sp, toks, heads: int, quant=None):
    """``(mlm_logits, nsp_logits)`` of the plain model over stacked
    parameters.  ``quant`` computes every matrix product in that lower
    precision, forward and backward (the control)."""
    mm = MATMUL[quant]

    def dense(x, node):
        return mm(x, node["kernel"]) + node["bias"]

    p = sp["rest"]
    b, t = toks.shape
    emb = p["tok_embed"]
    d = emb.shape[1]
    hd = d // heads
    x = emb[toks] + p["pos_embed"][None, :t] + p["type_embed"][0]
    x = _ln(x, p["embed_norm"])

    def block(x, blk):
        h = _ln(x, blk["attn_norm"])
        qh, kh, vh = (dense(h, blk[n]).reshape(b, t, heads, hd)
                      .transpose(0, 2, 1, 3) for n in ("wq", "wk", "wv"))
        s = mm(qh, kh.transpose(0, 1, 3, 2)) / math.sqrt(hd)
        o = mm(jax.nn.softmax(s, axis=-1), vh)
        x = x + dense(o.transpose(0, 2, 1, 3).reshape(b, t, d), blk["wo"])
        h = _ln(x, blk["mlp_norm"])
        return x + dense(_gelu(dense(h, blk["w_in"])), blk["w_out"]), None

    x, _ = jax.lax.scan(block, x, sp["layers"])
    x = _ln(x, p["final_norm"])
    h = _ln(_gelu(dense(x, p["mlm_transform"])), p["mlm_norm"])
    mlm = mm(h, emb.T)
    cls = jnp.tanh(dense(x[:, 0], p["pooler"]))
    return mlm, dense(cls, p["nsp"])


def _xent(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    return logz - jnp.take_along_axis(logits, labels[..., None],
                                      axis=-1)[..., 0]


def ref_block_loss(sp, batch, heads, total_rows, masked, quant):
    """This block of rows' part of the whole batch's loss."""
    toks, labels, w, nsp_y = batch
    mlm, nsp = ref_forward(sp, toks, heads, quant)
    return ((_xent(mlm, labels) * w).sum() / (masked * total_rows)
            + _xent(nsp, nsp_y).sum() / total_rows)


@jax.jit
def _stacked_norms(sp):
    def rows(x):        # one norm a layer
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return {"layers": jax.tree.map(rows, sp["layers"]),
            "rest": jax.tree.map(_norm, sp["rest"])}


def stacked_norms(sp) -> dict:
    """``{path: norm}`` under the paths the program's tree gives its
    leaves."""
    n = jax.device_get(_stacked_norms(sp))
    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(n["layers"])
    for path, vec in flat:
        for i, v in enumerate(vec):
            out[f"params/layer_{i}/{weights.path_name(path)}"] = float(v)
    flat, _ = jax.tree_util.tree_flatten_with_path(n["rest"])
    for path, v in flat:
        out[f"params/{weights.path_name(path)}"] = float(v)
    return out


def ref_first_steps(config: dict, traffic: dict, chips: int, seed: int,
                    shapes, steps: int = 3, quant=None,
                    block_rows: int = 8, log=lambda msg: None) -> dict:
    """Follow the first ``steps`` steps in plain float32: each step's loss,
    the per-leaf norms of the first gradient and of the parameters' change
    after the last step.  Gradients are accumulated over blocks of rows so
    that the reference fits; AdamW is written out.  ``shapes`` is the tree
    of parameter shapes (names and sizes only, nothing the program
    computed)."""
    import time
    heads = config["num_attention_heads"]
    o = config["optimizer"]
    lr, b1, b2, eps, wd = (float(o[k]) for k in (
        "learning_rate", "b1", "b2", "eps", "weight_decay"))
    rows = int(traffic["sequences_per_chip"]) * chips
    masked = int(traffic["masked_per_sequence"])
    t0 = time.perf_counter()
    p0 = stack_layers(weights.make_weights(seed, shapes, jnp.float32))
    host = make_batches(config, traffic, chips, seed)

    grad_block = jax.jit(jax.value_and_grad(partial(
        ref_block_loss, heads=heads, total_rows=rows, masked=masked,
        quant=quant)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(p, m, v, g, t):
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), p, m, v)
        return p, m, v

    p = jax.tree.map(jnp.copy, p0)
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    losses, gnorms = [], None
    for step in range(steps):
        batch = tuple(a[step % host[0].shape[0]] for a in host)
        loss, grads = [], None
        for lo in range(0, rows, block_rows):
            blk = tuple(jnp.asarray(a[lo:lo + block_rows]) for a in batch)
            lb, gb = grad_block(p, blk)
            loss.append(lb)
            grads = gb if grads is None else add(grads, gb)
        losses.append(float(sum(jax.device_get(loss))))
        log(f"reference step {step + 1} done at "
            f"{time.perf_counter() - t0:.2f} s")
        if step == 0:
            gnorms = stacked_norms(grads)
        p, m, v = adamw(p, m, v, grads, jnp.float32(step + 1))
    dnorms = stacked_norms(jax.tree.map(jnp.subtract, p, p0))
    return {"losses": losses, "grad_norms": gnorms, "change_norms": dnorms}
