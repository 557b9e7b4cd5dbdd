"""Llama LoRA fine-tune: large bf16 allreduce / tensor-fusion stress.

BASELINE.json config: "Llama-3 8B LoRA fine-tune (large bf16 allreduce,
tensor-fusion stress)".  Only the rank-r adapters train (frozen base via
``optax.multi_transform``), but the gradient pytree still spans every
projection -- exactly the many-small-tensors pattern the fusion buffer
exists for.  ``--8b`` selects the real Llama-3 8B architecture with the
frozen base quantized to int8 (one f32 scale per output channel): LoRA
needs no base gradients or master weights, so ~8 GB of int8 base + bf16
activations (remat) + full-precision adapters/optimizer fits a single
16 GB v5e chip.  The adapter gradients (hundreds of small tensors across
every projection) still ride the fused allreduce.

``--serve-adapters N`` switches from fine-tuning to the serving data
plane: N independently-trained LoRA adapters are stacked into banked
``[N, ...]`` leaves and served over ONE shared base model, with each
decode slot gathering its own adapter inside the step -- heterogeneous
adapters coexist in the same continuous decode batch.  The drill
parity-checks every stream against a dedicated engine running the same
adapter merged into the base weights.

Run::

    python examples/llama_lora.py [--steps 30] [--cpu-devices 8] [--8b]
    python examples/llama_lora.py --serve-adapters 3 --cpu-devices 1
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse

from _harness import require_tpu, setup_devices, timed_training


def serve_multi_lora(args):
    """N adapters, one base model, one continuous decode batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import LLAMA_SERVE, LlamaLM
    from horovod_tpu.serving import Request, ServingEngine, stack_adapters

    cfg = LLAMA_SERVE
    n_adapters = args.serve_adapters
    model = LlamaLM(cfg, dtype=jnp.float32, lora_rank=args.rank)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))

    # Stand-ins for N independently fine-tuned adapter sets: same base,
    # different task vectors.  Only the lora_a/lora_b leaves differ.
    def adapter_tree(key):
        template = stack_adapters([params["params"]])
        leaves, treedef = jax.tree.flatten(template)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            0.05 * jax.random.normal(kk, l.shape[1:], l.dtype)
            for kk, l in zip(keys, leaves)])

    adapters = [adapter_tree(jax.random.PRNGKey(100 + j))
                for j in range(n_adapters)]
    banks = stack_adapters(adapters)

    def merged(adapter):
        """Base params with ONE adapter's lora leaves swapped in."""
        out = jax.tree.map(lambda x: x, params)

        def walk(dst, src):
            for k, v in src.items():
                if k in ("lora_a", "lora_b"):
                    dst[k] = v
                else:
                    walk(dst[k], v)
        walk(out["params"], adapter)
        return out

    # Identical prompts so any divergence between streams is the
    # per-slot adapter gather, not the data.
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
    new_tokens = 10
    reqs = [Request(rid=j, prompt=prompt, max_new_tokens=new_tokens,
                    adapter_id=j) for j in range(n_adapters)]

    engine = ServingEngine(cfg, params, slots=max(4, n_adapters),
                           page_size=8, max_len=64, adapters=banks)
    report = engine.serve(reqs)
    assert report.completed == n_adapters, report
    streams = {r.rid: list(r.tokens)
               for r in reqs}

    # Distinct adapters must steer the shared base differently...
    assert len({tuple(s) for s in streams.values()}) > 1, streams
    # ...and each stream must equal a dedicated single-adapter engine
    # running that adapter merged into the base weights (no banks).
    for j in range(n_adapters):
        ref_engine = ServingEngine(cfg, merged(adapters[j]), slots=4,
                                   page_size=8, max_len=64)
        ref = [Request(rid=0, prompt=prompt, max_new_tokens=new_tokens)]
        ref_engine.serve(ref)
        assert streams[j] == list(ref[0].tokens), (
            f"adapter {j}: banked decode diverged from merged-weight "
            f"reference: {streams[j]} vs {list(ref[0].tokens)}")
        print(f"adapter {j}: {len(streams[j])} tokens match "
              f"merged-weight reference")

    print(f"multi-LoRA serve OK: {n_adapters} adapters shared one base "
          f"({report.new_tokens} tokens, {report.decode_steps} decode "
          f"steps, {report.tokens_per_s:.1f} tokens/s)")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=0)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--rank", type=int, default=8, help="LoRA rank")
    p.add_argument("--lr", type=float, default=1e-3)
    size = p.add_mutually_exclusive_group()
    size.add_argument("--1b", dest="mid", action="store_true",
                      help="~0.9B single-chip config")
    size.add_argument("--8b", dest="full", action="store_true",
                   help="real Llama-3 8B (needs TPU HBM)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize blocks (long-seq memory trade)")
    p.add_argument("--serve-adapters", type=int, default=0, metavar="N",
                   help="serve N LoRA adapters over one shared base "
                        "model in a single decode batch (skips training)")
    p.add_argument("--cpu-devices", type=int, default=0)
    args = p.parse_args()

    setup_devices(args.cpu_devices)
    if args.full or args.mid:
        require_tpu("--8b" if args.full else "--1b", args.cpu_devices)
    if args.serve_adapters:
        serve_multi_lora(args)
        return
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import (LLAMA3_8B, LLAMA_1B, LLAMA_TINY,
                                    LlamaLM, lora_mask, merge_frozen,
                                    split_frozen)

    hvd.init()
    cfg = LLAMA3_8B if args.full else (
        LLAMA_1B if args.mid else LLAMA_TINY)
    dtype = jnp.bfloat16 if jax.devices()[0].platform == "tpu" \
        else jnp.float32
    # The 8B runs with an int8 frozen base (+ remat): the only layout
    # that fits 16 GB HBM.  Smaller configs keep the f32 base so the
    # full-tree fusion path stays exercised.
    base_dtype = "int8" if args.full else None
    model = LlamaLM(cfg, dtype=dtype, lora_rank=args.rank,
                    remat=args.remat or args.full, base_dtype=base_dtype)
    batch = args.batch_size or 2 * hvd.size()
    seq = min(args.seq_len, cfg.max_seq_len)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:1])
    mask = lora_mask(params)
    if hvd.rank() == 0:
        n = sum(x.size for x in jax.tree.leaves(params))
        n_lora = sum(x.size for x, m in zip(
            jax.tree.leaves(params), jax.tree.leaves(mask)) if m)
        print(f"devices={hvd.size()} params={n/1e6:.1f}M "
              f"trainable(LoRA)={n_lora/1e3:.1f}K batch={batch} seq={seq} "
              f"base={base_dtype or 'f32'}")

    data = hvd.shard_batch(tokens)

    def xent(logits, toks):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], toks[:, 1:]).mean()

    if base_dtype == "int8":
        # Grads/optimizer/allreduce span ONLY the adapters; the int8 base
        # rides as a replicated, non-donated, never-differentiated arg.
        trainable, frozen = split_frozen(params, mask)
        opt = hvd.DistributedOptimizer(optax.adamw(args.lr),
                                       compression=hvd.Compression.bf16)
        trainable = hvd.replicate(trainable)
        frozen = hvd.replicate(frozen)
        opt_state = opt.init(trainable)

        def loss_fn(tp, fz, toks):
            return xent(model.apply(merge_frozen(tp, fz), toks), toks)

        full_step = hvd.make_train_step(loss_fn, opt, with_frozen=True)
        step = lambda p, o, d: full_step(p, o, d, frozen)  # noqa: E731
        params, opt_state = trainable, opt_state
    else:
        # bf16 wire compression + frozen base: the allreduce still
        # carries the full adapter set (hundreds of small tensors),
        # stressing fusion.
        inner = optax.multi_transform(
            {"lora": optax.adamw(args.lr), "frozen": optax.set_to_zero()},
            jax.tree.map(lambda m: "lora" if m else "frozen", mask))
        opt = hvd.DistributedOptimizer(inner,
                                       compression=hvd.Compression.bf16)
        params = hvd.replicate(params)
        opt_state = opt.init(params)

        def loss_fn(p, toks):
            return xent(model.apply(p, toks), toks)

        step = hvd.make_train_step(loss_fn, opt)

    timed_training(step, params, opt_state, data, args.steps, hvd.rank(),
                   items_per_step=batch)
    hvd.shutdown()


if __name__ == "__main__":
    main()
