"""What the serving tests share, in a module that is no test file: the
tiny configurations of the seven served families (and the second
instance of the window-and-full block), ONE table of them, the loop that drives an
engine a round at a time, and the two lowering helpers.  No test file
imports another; each takes these from here."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM

# -- the tiny configurations, as the benchmark's families read them -----------

TINY_MLA = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128}

TINY_CCA = {
    "vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 1, "router_hidden_size": 16, "cca_time0": 2,
    "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0}},
    "rms_norm_eps": 1e-5, "max_position_embeddings": 128}

TINY_LOOP = {
    "vocab_size": 97, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "total_ut_steps": 3,
    "early_exit_threshold": 1, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 128}

TINY_SWA = {
    "kind": "serve", "family": "exaone_swa_moe", "vocab_size": 32,
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "sliding_window": 8,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "max_position_embeddings": 64,
    "published": {"vocab_size": 64, "num_experts": 16},
    "share": {"first_expert": 4, "experts_held": 4},
    "compute_dtype": "float32",
    "serving": {"slots": 3, "page_size": 4, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3, "routing_margin_min": 0.0,
               "routing_branches_max": 1}}

# A state-space mixer beside attention in every layer (Falcon-H1's
# block): every multiplier off one, a chunk that divides no prompt of
# the tests' lengths.
TINY_SSM = {
    "kind": "serve", "family": "falcon_h1_hybrid", "vocab_size": 64,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 4, "mamba_expand": 0.5, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "hidden_act": "silu", "attn_layer_indices": None, "rope_scaling": None,
    "tie_word_embeddings": False, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 128,
    "embedding_multiplier": 5.0, "lm_head_multiplier": 0.125,
    "attention_in_multiplier": 1.5, "attention_out_multiplier": 0.25,
    "key_multiplier": 0.5, "ssm_in_multiplier": 0.75,
    "ssm_out_multiplier": 0.5, "mlp_multipliers": [0.75, 0.25],
    "ssm_multipliers": [0.5, 1.5, 0.75, 1.25, 0.6],
    "compute_dtype": "float32",
    "serving": {"slots": 3, "page_size": 4, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3}}

# EVA attention (EvaByte's block) at a size that keeps its structure: a
# window of 64 bytes in chunks and pages of 16 (four pooled rows a window:
# a quarter of a growing page), 4 heads of 32, 8 x 320 head columns.
TINY_EVA = {
    "kind": "serve", "family": "eva_dense", "vocab_size": 320,
    "hidden_size": 128, "intermediate_size": 192, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "window_size": 64,
    "chunk_size": 16, "num_chunks": None, "num_pred_heads": 8,
    "attention_bias": False, "attention_class": "eva",
    "hidden_act": "silu", "norm_add_unit_offset": True,
    "fp32_logits": True, "fp32_skip_add": True, "mixedp_attn": True,
    "rope_scaling": None, "tie_word_embeddings": False,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "compute_dtype": "float32",
    "serving": {"slots": 3, "page_size": 16, "max_len": 256},
    "limits": {"served_logit_gap_max": 1e-3}}

# The window-and-full block's second instance (SmallThinker's variant).
EARLY_KINDS = ("full", "window", "window", "window")
EARLY_WINDOW, EARLY_THETA, EARLY_EPS, EARLY_TOP_K = 8, 10000.0, 1e-6, 3


# -- ``(config, params)`` of each ---------------------------------------------

def dense():
    return LLAMA_SERVE, LlamaLM(LLAMA_SERVE, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def mla_moe():
    from benchmarks.families import joyai_mla_moe
    from horovod_tpu.serving import mla_moe
    cfg = joyai_mla_moe.program_config(TINY_MLA)
    return cfg, mla_moe.init_params(cfg, jax.random.PRNGKey(0))


def cca_moe():
    from benchmarks.families import zaya_cca_moe
    from horovod_tpu.serving import cca_moe
    cfg = zaya_cca_moe.program_config(TINY_CCA)
    return cfg, cca_moe.init_params(cfg, jax.random.PRNGKey(0))


def loop_dense():
    from benchmarks.families import ouro_loop
    from horovod_tpu.serving import loop_dense
    cfg = ouro_loop.program_config(TINY_LOOP)
    return cfg, loop_dense.init_params(cfg, jax.random.PRNGKey(0))


def swa_moe(**over):
    from benchmarks.families import exaone_swa_moe
    from horovod_tpu.serving import swa_moe
    cfg = exaone_swa_moe.program_config(dict(TINY_SWA, **over))
    return cfg, swa_moe.init_params(cfg, jax.random.PRNGKey(0))


def ssm_hybrid(**over):
    from benchmarks.families import falcon_h1_hybrid
    from horovod_tpu.serving import ssm_hybrid
    cfg = falcon_h1_hybrid.program_config(dict(TINY_SSM, **over))
    return cfg, ssm_hybrid.init_params(cfg, jax.random.PRNGKey(0))


def eva_dense(**over):
    from benchmarks.families import eva_dense as family
    from horovod_tpu.serving import eva_dense
    cfg = family.program_config(dict(TINY_EVA, **over))
    return cfg, eva_dense.init_params(cfg, jax.random.PRNGKey(0))


def early_route(**over):
    from horovod_tpu.serving import swa_moe
    cfg = swa_moe.SwaMoeConfig(**dict(dict(
        vocab_size=64, d_model=32, num_heads=14, num_kv_heads=2, head_dim=16,
        ffn_hidden=0, moe_hidden=16, num_experts=8,
        experts_per_token=EARLY_TOP_K, attn_kinds=EARLY_KINDS,
        ffn_kinds=("moe",) * 4, window=EARLY_WINDOW, num_shared_experts=0,
        rope_theta=EARLY_THETA, rms_eps=EARLY_EPS, max_seq_len=64,
        qk_norm=False, router="topk_softmax", route_from="layer_input",
        gate_act="relu"), **over))
    return cfg, swa_moe.init_params(cfg, jax.random.PRNGKey(0))


# The seven served families, a tiny engine's worth each.
# (EVA attention with a window of 16 in chunks of 8 here: the engines
# these tests share have pages of 8 and of 4 and contexts of 32, which
# then span two windows, and a chunk of one page and of two.)
FAMILIES = {"dense": dense, "mla_moe": mla_moe, "cca_moe": cca_moe,
            "loop_dense": loop_dense, "swa_moe": swa_moe,
            "ssm_hybrid": ssm_hybrid,
            "eva_dense": functools.partial(eva_dense, window_size=16,
                                           chunk_size=8)}
# ... and the window-and-full routed block in both its instances.
FAMILIES_AND_EARLY_ROUTE = dict(FAMILIES, swa_moe_early_route=early_route)


# -- a join's first token, taken without the engine's hand-over ---------------

def host_first_tokens(eng, reqs):
    """The reference for what a join books first: each prompt alone
    through the engine's prefill program, its last row's argmax taken by
    the host (``greedy_sample``), with no ``_hand_over``, ``told`` vector
    or ``_settle_joins`` on the way."""
    from horovod_tpu.serving.engine import greedy_sample
    out = []
    for r in reqs:
        aid = None if eng.adapters is None else jnp.int32(r.adapter_id)
        logits = eng._prefill(eng.params,
                              jnp.asarray(r.prompt, jnp.int32)[None],
                              eng.adapters, aid)[0]
        out.append(int(greedy_sample(logits[:, -1, :])[0]))
    return out


# -- a loop that has every round's tokens before the next ---------------------

def round_by_round(eng, reqs):
    """Drive ``eng`` as the control plane and the fleet's decode worker
    do: the engine's one join, its one round, and the catch-up behind
    each round, so that the host has a round's tokens before it
    dispatches the next; every first token is checked against
    :func:`host_first_tokens`.  Returns the run's state."""
    sched, st = eng.scheduler, eng.run_state()
    t0 = time.monotonic()

    def now():
        return time.monotonic() - t0

    for req in reqs:
        sched.submit(req)
    while sched.has_work():
        eng.join(st, [(slot, req, jnp.asarray(req.prompt, jnp.int32))
                      for slot, req in sched.admit(now())], now)
        if eng._decode_slots():
            eng.decode_once(st, now)
        eng.catch_up(st, now)
    # What the joins booked, against the host's own reading of each
    # prompt's last row: this loop and the code under test share the
    # hand-over, so it is held to a reference that does not.
    assert [r.tokens[0] for r in reqs] == host_first_tokens(eng, reqs)
    return st


# -- lowerings ----------------------------------------------------------------

def lowered_for_tpu(fn, *args, kernels: bool = True):
    """``fn`` lowered for the TPU, each Mosaic body printed as MLIR
    without source locations (the recipe of
    ``.claude/skills/verify/SKILL.md``).  ``kernels``: the text must
    hold a Mosaic call (a tiny dense prefill holds none)."""
    import base64
    import re

    from jax._src.interpreters import mlir as jmlir
    from jaxlib.mlir import ir

    def body(match):
        with jmlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            return ir.Module.parse(base64.b64decode(
                match.group(1))).operation.get_asm(enable_debug_info=False)

    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text or not kernels
    return re.sub(r'(?<=body\\22: \\22)([A-Za-z0-9+/=]+)(?=\\22)', body,
                  text)


def bf16_prefill_gaps(prefill_forward, cfg, params, toks, want):
    """A family's prefill computing in bfloat16, under whatever kernel
    switch the environment has NOW: the jaxpr's text and every row's
    distance from ``want``, the family's float32 reference logits."""
    traced = jax.jit(lambda p, x: prefill_forward(
        p, cfg, x, dtype=jnp.bfloat16, last_only=False)[0][0]).trace(
            params, toks)
    return str(traced.jaxpr), np.abs(np.asarray(
        traced.lower().compile()(params, toks)) - want)
