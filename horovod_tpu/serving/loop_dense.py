"""A dense decoder whose layers run several times a token over one set of
weights, with an exit gate read after every pass: the looped language
model (arXiv:2510.25741), served.

The fourth instance of :class:`~horovod_tpu.serving.layerspec.LayerSpec`
and the first with ``passes`` > 1.  ``x`` is the residual stream
(float32), ``E`` the model width, ``H`` query and ``H_kv`` key/value
heads of ``d`` columns, ``L`` layers, ``T`` passes::

    x = E[token]
    for t in 0..T-1:                      # the same L blocks every pass
        for l in 0..L-1:
            a = x + N2_l( Attn_l( N1_l(x) ) )
            x = a + N4_l( W_down_l( silu(W_gate_l N3_l(a)) * W_up_l N3_l(a) ) )
        x = N_f(x)                        # the final norm, between passes too
        h_t = x ;  lambda_t = sigmoid(w_g . h_t + b_g)
    p_t = lambda_t * prod_{j<t} (1 - lambda_j)   (t < T-1)
    p_{T-1} = prod_{j<T-1} (1 - lambda_j)
    logits = W_head h_{T-1}

``N1 .. N4`` are four RMSNorms a layer with weights of their own (one
before AND one after each sub-block), no projection has a bias, ``Attn``
is causal softmax attention at scale ``1/sqrt(d)`` whose queries and keys
are rotated by the token's position (RoPE over all ``d`` columns, half
against half; the position is the same in every pass).  A token of pass
``t`` attends to the keys and values that pass ``t`` wrote, never another
pass's: the cache holds ``T * L`` PLANES, pass ``t`` of layer ``l`` in
plane ``t * L + l``, and a cached token costs ``T`` times what it costs a
model of the same layers.  The head is untied.

``p`` is the distribution over the pass at which a token would leave the
loop; a token leaves at the first pass whose running sum of ``p`` reaches
``early_exit_threshold``.  At the published threshold of 1 that is the
last pass for every token: every pass is always run, and this module
builds nothing else (tokens of one batch leaving at different passes,
later planes never written: refused by name).  The gate is computed
every pass all the same, and the decode step carries the mass of ``p`` a
pass, summed over its live tokens (``LayerSpec.step_state``; the counter
``loop.exit_mass``).

What is kept, and where: ONE pool, a row ``[k (H_kv d) | v (H_kv d)]``
with no head dim a token a plane, keys after RoPE, both rounded to the
engine's ``dtype`` exactly where the prefill rounds them.  Prefill makes
all ``T`` passes over the prompt (one rolled loop around the ``L`` layer
bodies; ``flash_attention`` causal) and hands back ``[T * L, b, t, 2 H_kv
d]`` rows; decode makes them for one token a slot
(``stepparts.build_one_chip_step(passes=T)``) with ``hvd_cca_decode``
walking the page table of the pass's own plane.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import rotary_embedding
from ..ops.attention import cca_decode_attention, flash_attention
from . import stepparts
from .decode import ServingDecodeStep, _dense, _rmsnorm, one_trace
from .layerspec import LayerSpec
from .stepparts import dense_out as _dense_out

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LoopDenseConfig:
    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_hidden: int
    passes: int = 4                      # total_ut_steps
    exit_threshold: float = 1.0          # early_exit_threshold
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 65536

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2 \
                or self.passes < 1:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads of {self.head_dim}, {self.passes} passes")

    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def page_width(self) -> int:
        """Columns of a cached row: the keys and, beside them, the
        values."""
        return 2 * self.kv_width

    def layer_spec(self) -> LayerSpec:
        cfg = self
        if cfg.exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {cfg.exit_threshold}: leaving the "
                "loop before the last pass (tokens of one batch at "
                "different passes, later planes never written) is not "
                "built; at a threshold of 1 every token makes every pass")

        def prefill(params, tokens, **kw):
            return prefill_forward(params, cfg, tokens, **kw)

        def build_step(mesh, **kw):
            return build_decode_step(cfg, mesh, **kw)

        why_past = ("the engine's continuation from cached pages "
                    "(gather_pages, the chunk loop) reads a second pool, "
                    "and this model keeps its keys and values in one row")
        return LayerSpec(
            attention="gqa",
            page=((cfg.page_width,), None),
            page_holds=("the rotated keys of every key/value head side by "
                        "side, beside them the values; one plane a pass a "
                        "layer", None),
            ffn=("dense",) * cfg.num_layers, tied_head=False,
            max_seq_len=cfg.max_seq_len, tp_page_dim=None,
            prefill=prefill, build_step=build_step,
            param_specs=lambda params: jax.tree.map(lambda _: P(), params),
            unsupported={
                "tp": "the rolled loop over the passes is one program on "
                      "one chip: tp = 1 only",
                "lora": "no adapter banks over a looped block",
                "spec_decode": "no verify step: a draft of k tokens would "
                               "write k rows in every plane",
                "kv_compress": "no fp8 cold pool for rows with no head "
                               "dim",
                "prefill_chunk": why_past,
                "prefix_cache": why_past},
            step_state=lambda: (jnp.zeros((cfg.passes,), jnp.float32),),
            publish_state=lambda state: stepparts.publish_exit_mass(
                state[0]),
            passes=cfg.passes)


# ---------------------------------------------------------------------------
# The parameter tree.
# ---------------------------------------------------------------------------

NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")


def param_shapes(config: LoopDenseConfig, dtype=jnp.float32):
    """The tree of ``jax.ShapeDtypeStruct`` leaves (``{"params": ...}``):
    ONE set of layers, whatever the number of passes."""
    c = config
    d, f = c.d_model, c.ffn_hidden

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def kernel(*shape):
        return {"kernel": leaf(*shape)}

    def layer():
        out = {name: {"scale": leaf(d)} for name in NORMS}
        out["attn"] = {"wq": kernel(d, c.q_width),
                       "wk": kernel(d, c.kv_width),
                       "wv": kernel(d, c.kv_width),
                       "wo": kernel(c.q_width, d)}
        out["mlp"] = {"w_gate": kernel(d, f), "w_up": kernel(d, f),
                      "w_down": kernel(f, d)}
        return out

    tree = {f"layer_{i}": layer() for i in range(c.num_layers)}
    tree.update(tok_embed=leaf(c.vocab_size, d),
                final_norm={"scale": leaf(d)},
                lm_head=kernel(d, c.vocab_size),
                exit_gate={"kernel": leaf(d, 1), "bias": leaf(1)})
    return {"params": tree}


def init_params(config: LoopDenseConfig, key, dtype=jnp.float32,
                spread: float = 0.1):
    """Random parameters for tests: kernels normal over the fan-in, the
    embedding at 0.02, every norm's scale ``spread`` off one and the
    gate's bias ``spread`` off zero (a program that forgets a norm, or
    takes one for another, then computes something else)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config, dtype))
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        n = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        if name == "scale":
            v = 1.0 + spread * n
        elif name == "bias":
            v = spread * n
        elif name == "tok_embed":
            v = 0.02 * n
        else:
            v = n / math.sqrt(s.shape[0])
        leaves.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Shared mathematics.
# ---------------------------------------------------------------------------


def _plane(first, li: int):
    """The pool's plane of layer ``li`` in the pass whose first plane is
    ``first``."""
    return first + li


def _post(y, blk, name: str, cfg):
    """A sub-block's float32 result through the norm that follows it:
    the residual stream's float32 addend."""
    return _rmsnorm(y, blk[name]["scale"], jnp.float32, cfg.rms_eps)


def _mlp(a, blk, cfg, dtype):
    h = _rmsnorm(a, blk["mlp_norm"]["scale"], dtype, cfg.rms_eps)
    mlp = blk["mlp"]
    y = _dense_out(jax.nn.silu(_dense(h, mlp["w_gate"], dtype))
                   * _dense(h, mlp["w_up"], dtype), mlp["w_down"], dtype)
    return a + _post(y, blk, "post_mlp_norm", cfg)


def after_pass(x, p, cfg):
    """What stands between two passes: the model's final norm (float32:
    the residual stream goes on from here) and, beside it, the gate:
    ``(h_t, lambda_t)``."""
    f32 = jnp.float32
    h = _rmsnorm(x, p["final_norm"]["scale"], f32, cfg.rms_eps)
    gate = p["exit_gate"]
    z = jnp.matmul(h, gate["kernel"].astype(f32), precision=_HI)[..., 0]
    return h, jax.nn.sigmoid(z + gate["bias"].astype(f32)[0])


def exit_distribution(leave):
    """``leave`` ``[T, ...]`` (``lambda_t`` a pass) -> ``p`` ``[T, ...]``:
    the share of a token that leaves the loop after pass ``t``; what is
    left, at the last pass."""
    stay = jnp.cumprod(1.0 - leave, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(leave * before)[:-1], before[-1:]], axis=0)


# ---------------------------------------------------------------------------
# Prefill.
# ---------------------------------------------------------------------------


def prefill_forward(params, config: LoopDenseConfig, tokens, positions=None,
                    *, dtype=jnp.float32, adapters=None, adapter_id=None,
                    lora_alpha=16.0, past=None, last_only: bool = True,
                    with_exit: bool = False):
    """Forward a prompt batch ``tokens`` ``[b, t]`` through every pass;
    returns ``(logits, rows, None)``: float32 logits of the LAST row
    (``[b, 1, vocab]``; every row with ``last_only=False``), what the
    cache holds of the prompt ``[passes * num_layers, b, t, page_width]``
    (pass ``t`` of layer ``l`` at ``t * num_layers + l``), and None for
    the second pool this model does not keep.  ``with_exit``: a fourth,
    the exit distribution of every token, ``[passes, b, t]``."""
    del adapter_id, lora_alpha
    if adapters is not None or past is not None:
        raise NotImplementedError(
            "this prefill takes neither adapter banks nor a continuation "
            "from cached rows")
    cfg = config
    p = params["params"] if "params" in params else params
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    h_q, h_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def heads(z, n):
        return z.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    @one_trace
    def layer(x, blk, positions):
        attn = blk["attn"]
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        q = rotary_embedding(heads(_dense(h, attn["wq"], dtype), h_q),
                             positions, cfg.rope_theta)
        k = rotary_embedding(heads(_dense(h, attn["wk"], dtype), h_kv),
                             positions, cfg.rope_theta)
        v = _dense(h, attn["wv"], dtype)
        row = jnp.concatenate(
            [k.transpose(0, 2, 1, 3).reshape(b, t, cfg.kv_width), v],
            axis=-1)
        o = flash_attention(q, k, heads(v, h_kv), causal=True,
                            scale=d ** -0.5)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_width)
        a = x + _post(_dense_out(o, attn["wo"], dtype), blk,
                      "post_attn_norm", cfg)
        return _mlp(a, blk, cfg, dtype), row

    def one_pass(x, _):
        rows = []
        for li in range(cfg.num_layers):
            x, row = layer(x, p[f"layer_{li}"], positions)
            rows.append(row)
        x, leave = after_pass(x, p, cfg)
        return x, (jnp.stack(rows), leave)

    x, (rows, leave) = jax.lax.scan(
        one_pass, stepparts.embed(p, tokens), None, length=cfg.passes)
    if last_only:
        x = x[:, -1:]
    out = (stepparts.readout(x, p, cfg.rms_eps, dtype, tied=False,
                             normed=True),
           rows.reshape(cfg.passes * cfg.num_layers, *rows.shape[2:]), None)
    return out + (exit_distribution(leave),) if with_exit else out


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


def build_decode_step(config: LoopDenseConfig, mesh, *, slots: int,
                      page_size: int, pages_per_slot: int,
                      dtype=jnp.float32, width: int = 1,
                      with_lora: bool = False, lora_alpha: float = 16.0,
                      compress: bool = False) -> ServingDecodeStep:
    """Compile the batched one-token decode step.

    Signature of the returned step::

        logits, pool, None, exit_mass, told = step(
            params, pool, None, tokens, positions, page_table, active,
            exit_mass, prev)

    as ``mla_moe.build_decode_step``'s without the routed histogram and
    with ``exit_mass`` (``[passes]`` float32) in its place: the running
    mass of the exit distribution a pass, summed over the live slots of
    every round (``stepparts.build_one_chip_step``).  ``pool`` is
    ``[passes * num_layers, pages + 1, page_size, page_width]``.  The step
    CONSUMES ``pool`` and ``exit_mass``.
    """
    del lora_alpha
    cfg = config
    stepparts.refuse_beyond_one_chip(
        "looped dense", mesh, width=width, with_lora=with_lora,
        compress=compress)
    h_q, h_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def rope(z, n, rnd):
        z = z.reshape(z.shape[0], n, 1, d)
        return rotary_embedding(z, rnd.positions[:, None],
                                cfg.rope_theta)[:, :, 0]

    def layer(li, blk, x, pool, carried, local, rnd):
        s = x.shape[0]
        attn = blk["attn"]
        plane = _plane(rnd.first_plane, li)
        h = _rmsnorm(x, blk["attn_norm"]["scale"], dtype, cfg.rms_eps)
        q = rope(_dense(h, attn["wq"], dtype), h_q, rnd)
        k = rope(_dense(h, attn["wk"], dtype), h_kv, rnd)
        row = jnp.concatenate([k.reshape(s, cfg.kv_width),
                               _dense(h, attn["wv"], dtype)], axis=-1)
        pool = pool.at[plane, rnd.page, rnd.off].set(row.astype(pool.dtype))
        o = cca_decode_attention(
            q, pool, rnd.page_table, layer=plane, lengths=rnd.lengths,
            kv_heads=h_kv, scale=d ** -0.5)
        a = x + _post(_dense_out(o.reshape(s, cfg.q_width), attn["wo"],
                                 dtype), blk, "post_attn_norm", cfg)
        return _mlp(a, blk, cfg, dtype), pool, carried, local, None, None

    return stepparts.build_one_chip_step(
        "loop_dense_step", layer, num_layers=cfg.num_layers,
        eps=cfg.rms_eps, tied=False, page_size=page_size,
        scratch=slots * pages_per_slot, dtype=dtype, tells=(), carried=0,
        routed=False, passes=cfg.passes,
        after_pass=lambda x, p: after_pass(x, p, cfg),
        meta={"arch": "loop_dense", "d_model": cfg.d_model,
              "slots": int(slots)})
