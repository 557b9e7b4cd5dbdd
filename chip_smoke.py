"""Chip smoke: the train step and the serving engine on a TPU, end to end.

    python chip_smoke.py

One process, all local chips, three phases through the entry points a
user calls, at the full width of models the repo ships:

A. ResNet-50 (bf16, space-to-depth stem, batch 256/chip) through
   ``hvd.DistributedOptimizer`` + ``make_flax_train_step``;
B. BERT-Large (bf16, batch 32/chip, seq 128) through
   ``hvd.DistributedAdasumOptimizer`` (fp16 wire) + ``hvd.make_train_step``;
C. ``ServingEngine`` on ``LLAMA_1B`` (8 slots, 16-token pages, max_len
   1024) over a ``("tp",)`` mesh of every chip, a dozen generated
   requests, one greedy stream checked against ``LlamaLM.apply``.

Every figure printed is a ``smoke`` reading -- proof the system starts and
computes the right thing on the chip -- not a benchmark.  The script fails
(non-zero exit, no result line) when jax finds no TPU, and on any failed
check.  The last line of stdout on success is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The CPU rehearsal is ``tests/test_chip_smoke.py``, which imports the three
phase functions with tiny configs; it is not a mode of this script.
"""

import json
import os
import sys
import time
from importlib.metadata import version

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import _core, serving
from horovod_tpu.models import Bert, LlamaLM
from horovod_tpu.ops.attention import cca_decode_attention
from horovod_tpu.training import make_flax_train_step
from horovod_tpu.utils.platform import configure_compile_cache

WARM_STEPS = 5
# Greedy parity: the engine's token must equal the reference's argmax
# wherever the reference's top-2 logit margin exceeds the largest logit
# difference between the reference at jax's default matmul precision (TPU:
# one bf16 pass for f32 operands) and at "highest" -- a margin the
# precision in use can decide -- plus a floor for backends where the two
# coincide.  On the v5e the difference measured 0.06 against logits of
# unit scale, which decided 45 of 64 checked positions.
MARGIN_FLOOR = 1e-5


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"smoke check failed: {what}")


def _mosaic_calls(lowered) -> int:
    """Mosaic kernels in a lowered step.  A Pallas kernel that ran the
    interpreter, or a dispatcher that fell back to the XLA reference,
    leaves no ``tpu_custom_call`` behind."""
    return lowered.as_text().count("tpu_custom_call")


def _peak_bytes() -> list:
    """``peak_bytes_in_use`` per local device (process high-water mark;
    the CPU backend reports none)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()]


def _cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))


def _train(step, state, batch, steps: int, loss_index: int,
           mosaic_calls: int):
    """Compile + ``WARM_STEPS`` + two ``steps``-long windows of ``step``;
    the first window is fenced with ``jax.block_until_ready``, the second
    with a host fetch of the loss.  Returns ``(readings, final state)``."""
    # flash_attention returns the XLA reference without a word when the
    # blocks do not divide or the switch resolves off: count, don't assume.
    mosaic = _mosaic_calls(step.lower(*state, batch))
    _check(mosaic == mosaic_calls,
           f"lowered step has {mosaic} Mosaic calls, expected "
           f"{mosaic_calls}")
    losses = []

    def run(n):
        nonlocal state
        for _ in range(n):
            out = step(*state, batch)
            state = out[:loss_index]
            losses.append(out[loss_index])
        return out

    t0 = time.perf_counter()
    float(run(1)[loss_index])
    compile_s = time.perf_counter() - t0
    float(run(WARM_STEPS)[loss_index])
    t0 = time.perf_counter()
    jax.block_until_ready(run(steps))
    bur_s = (time.perf_counter() - t0) / steps
    t0 = time.perf_counter()
    float(run(steps)[loss_index])
    fetch_s = (time.perf_counter() - t0) / steps
    losses = [float(x) for x in losses]
    _check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall: first {losses[0]} last {losses[-1]}")
    return {"compile_s": compile_s, "s_per_step_block_until_ready": bur_s,
            "s_per_step_host_fetch": fetch_s, "first_loss": losses[0],
            "last_loss": losses[-1], "steps": len(losses),
            "mosaic_calls": mosaic, "peak_bytes": _peak_bytes()}, state


def _check_spread(params) -> dict:
    """The work is spread over every chip of ``hvd.mesh()``: size, leaf
    placement, an in-step psum, and -- the gradient exchange itself --
    bitwise-equal parameter replicas after training on different shards."""
    mesh = hvd.mesh()
    devices = set(mesh.devices.flat)
    n = len(devices)
    _check(hvd.size() == n == len(jax.devices()),
           f"hvd.size() {hvd.size()} != device count {len(jax.devices())}")
    leaves = jax.tree.leaves(params)
    _check(all(set(x.sharding.device_set) == devices for x in leaves),
           "a parameter leaf does not cover every mesh device")
    for x in leaves:
        first, *rest = [np.asarray(s.data) for s in x.addressable_shards]
        _check(all(first.tobytes() == r.tobytes() for r in rest),
               "parameter replicas diverged: gradient exchange missing")
    axes = hvd.reduce_axes()
    total = int(jax.jit(jax.shard_map(
        lambda: lax.psum(lax.axis_index(axes), axes), mesh=mesh,
        in_specs=(), out_specs=P()))())
    _check(total == n * (n - 1) // 2,
           f"psum(axis_index) {total} != {n * (n - 1) // 2}")
    return {"size": n, "leaves": len(leaves), "psum_axis_index": total}


def phase_rn50(model, image_shape, num_classes: int, batch_per_chip: int,
               steps: int, mosaic_calls: int = 0) -> dict:
    """Trainer A: ``DistributedOptimizer`` + ``make_flax_train_step``."""
    n = hvd.size()
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch_per_chip * n, *image_shape),
                          jnp.bfloat16)
    y = jax.random.randint(key, (batch_per_chip * n,), 0, num_classes,
                           jnp.int32)
    variables = model.init(key, x[:2].astype(jnp.float32), train=True)
    params = hvd.replicate(variables["params"])
    batch_stats = hvd.replicate(variables["batch_stats"])
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = hvd.replicate(opt.init(params))
    step = make_flax_train_step(model.apply, opt)
    out, state = _train(step, (params, batch_stats, opt_state),
                        hvd.shard_batch((x, y)), steps, 3, mosaic_calls)
    out["spread"] = _check_spread(state[0])
    return out


def phase_bert(config, dtype, batch_per_chip: int, seq: int, steps: int,
               mosaic_calls: int) -> dict:
    """Trainer B: the path ``examples/bert_pretrain.py`` drives."""
    n = hvd.size()
    model = Bert(config, dtype=dtype)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, config.vocab_size, (batch_per_chip * n, seq)))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch_per_chip * n,)))
    params = hvd.replicate(model.init(jax.random.PRNGKey(0), tokens[:1]))

    def loss_fn(p, batch):
        toks, nsp_y = batch
        mlm, nsp = model.apply(p, toks)
        return (optax.softmax_cross_entropy_with_integer_labels(
                    mlm, toks).mean()
                + optax.softmax_cross_entropy_with_integer_labels(
                    nsp, nsp_y).mean())

    opt = hvd.DistributedAdasumOptimizer(
        optax.adamw(1e-3), compression=hvd.Compression.fp16)
    step = hvd.make_train_step(loss_fn, opt)
    return _train(step, (params, opt.init(params)),
                  hvd.shard_batch((tokens, nsp_labels)), steps, 2,
                  mosaic_calls)[0]


def _decode_kernel_parity(config, tp: int, slots: int, page_size: int,
                          max_len: int) -> float:
    """The page walk over two pools against the XLA reference at the
    decode step's per-chip shape, dead slot and full slot included."""
    h, h_kv, d = config.num_heads // tp, config.num_kv_heads // tp, \
        config.head_dim
    pps = max_len // page_size
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (slots, h, d), jnp.float32)
    shape = (2, slots * pps + 1, page_size, h_kv * d)
    keys = jax.random.normal(kk, shape, jnp.float32)
    values = jax.random.normal(kv, shape, jnp.float32)
    table = jnp.asarray(np.random.RandomState(3).permutation(
        slots * pps).reshape(slots, pps), jnp.int32)
    lengths = jnp.asarray(
        np.linspace(0, max_len, slots).astype(np.int32))

    # Fresh closures: jit caches traces by function identity.
    def walk(force_reference):
        return jax.jit(lambda q, k, v, t, n: cca_decode_attention(
            q, k, t, layer=1, lengths=n, kv_heads=h_kv, scale=d ** -0.5,
            values=v, force_reference=force_reference))(
                q, keys, values, table, lengths)

    got, ref = walk(False), walk(True)
    _check(bool(jnp.all(got[0] == 0.0)), "dead slot output is not zero")
    return float(jnp.max(jnp.abs(got - ref)))


def _greedy_parity(model, params, reqs) -> dict:
    """Greedy streams of ``reqs`` (same prompt and output lengths, one
    batch) against ``LlamaLM.apply`` over the full context, teacher-forced
    on the engine's own tokens."""
    plen = reqs[0].prompt_len
    tokens = np.asarray([r.tokens for r in reqs])
    ctx = jnp.asarray([list(r.prompt) + list(r.tokens) for r in reqs],
                      jnp.int32)
    # Fresh closure per precision setting (read at trace time).
    logits = jax.jit(lambda p, t: model.apply(p, t))(params, ctx)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(lambda p, t: model.apply(p, t))(params, ctx)
    # Row i predicts context token i + 1.
    rows = np.asarray(logits[:, plen - 1:-1], np.float64)
    exact = np.asarray(exact[:, plen - 1:-1], np.float64)
    tol = float(np.max(np.abs(rows - exact))) + MARGIN_FLOOR
    top2 = np.sort(rows, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > tol
    agree = rows.argmax(-1) == tokens
    _check(bool(np.all(agree[decided])),
           f"greedy streams disagree with LlamaLM.apply at "
           f"{int((decided & ~agree).sum())} decided positions")
    _check(int(decided.sum()) * 4 >= tokens.size,
           f"only {int(decided.sum())}/{tokens.size} positions clear the "
           f"margin {tol:.3g}: the parity check has no teeth")
    return {"requests": len(reqs), "tokens": tokens.size,
            "decided": int(decided.sum()), "agree": int(agree.sum()),
            "margin_tol": tol}


def phase_server(config, slots: int, page_size: int, max_len: int,
                 prompt_lens, output_lens, num_requests: int,
                 mosaic_calls: int) -> dict:
    """Server: ``ServingEngine.serve`` on a ``("tp",)`` mesh over every
    local chip."""
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("tp",))
    model = LlamaLM(config, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    eng = serving.ServingEngine(config, params, mesh=mesh, slots=slots,
                                page_size=page_size, max_len=max_len)
    _check(set(eng.cache.k.sharding.device_set) == set(devices),
           "the KV pool's shards do not sit on every tp device")
    cache = eng.cache
    mosaic = _mosaic_calls(jax.jit(eng.step._fn).lower(
        eng._decode_params, cache.k, cache.v,
        jnp.zeros((slots,), jnp.int32), cache.lengths_device(),
        cache.table_device(), jnp.zeros((slots,), bool), eng._told))
    _check(mosaic == mosaic_calls,
           f"decode step has {mosaic} Mosaic calls, expected "
           f"{mosaic_calls} (the page walk: one function for every layer)")
    spec = serving.LoadSpec(
        num_requests=num_requests, rate_rps=50.0, prompt_lens=prompt_lens,
        output_lens=output_lens, vocab_size=config.vocab_size, seed=11)

    # Same stream twice: the first pass compiles the decode step and
    # every prompt-length prefill, the second is the reading.
    t0 = time.perf_counter()
    eng.serve(serving.generate(spec))
    compile_s = time.perf_counter() - t0
    requests = serving.generate(spec)
    report = eng.serve(requests)
    _check(report.completed == num_requests and report.rejected == 0,
           f"{report.completed}/{num_requests} completed, "
           f"{report.rejected} rejected")
    _check(all(len(r.tokens) == r.max_new_tokens for r in requests),
           "a request finished short of its output length")
    _check(cache.live_pages == 0 and cache.refcounts_balanced(),
           f"pool did not drain: {cache.live_pages} live pages")

    kernel_err = _decode_kernel_parity(config, len(devices), slots,
                                       page_size, max_len)
    _check(kernel_err < 2e-2 if mosaic_calls else kernel_err == 0.0,
           f"page walk vs reference: max abs err {kernel_err}")
    longest = max(requests, key=lambda r: r.prompt_len + len(r.tokens))
    checked = [r for r in requests
               if (r.prompt_len, len(r.tokens))
               == (longest.prompt_len, len(longest.tokens))]
    return {"compile_s": compile_s, "wall_s": report.wall_s,
            "s_per_decode_step": report.wall_s / report.decode_steps,
            "decode_steps": report.decode_steps,
            "completed": report.completed, "rejected": report.rejected,
            "new_tokens": report.new_tokens, "tp": len(devices),
            "live_pages": cache.live_pages, "mosaic_calls": mosaic,
            "decode_kernel_max_abs_err": kernel_err,
            "greedy_parity": _greedy_parity(model, params, checked),
            "peak_bytes": _peak_bytes()}


def _fmt(d: dict) -> str:
    return " ".join(
        f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in d.items())


def main() -> int:
    cache_dir = configure_compile_cache()
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev0.platform!r}); this script never runs on the CPU",
              file=sys.stderr)
        return 1
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    print(f"smoke python={sys.version.split()[0]} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={version('libtpu')}")
    print(f"smoke platform={device['platform']} "
          f"device_kind={device['kind']!r} devices={device['count']}")
    entries_before = _cache_entries(cache_dir)
    print(f"smoke compile_cache={cache_dir} entries={entries_before}")
    print("smoke _core=" + ("native library" if _core.available()
                            else f"python twin "
                                 f"({_core.unavailable_reason()})"))
    print(f"smoke matmul_precision="
          f"{jax.config.jax_default_matmul_precision or 'default'} "
          f"(f32 operands take bf16 MXU passes) "
          f"greedy_margin_tol=max|logits_default - logits_highest|"
          f"+{MARGIN_FLOOR}")

    from horovod_tpu.models import BERT_LARGE, ResNet50
    from horovod_tpu.models.transformer import LLAMA_1B
    hvd.init()
    a = phase_rn50(
        ResNet50(num_classes=1000, dtype=jnp.bfloat16, space_to_depth=True),
        (224, 224, 3), 1000, batch_per_chip=256, steps=20)
    print("smoke A rn50 batch=256/chip " + _fmt(a), flush=True)
    bur, fetch = (a["s_per_step_block_until_ready"],
                  a["s_per_step_host_fetch"])
    print(f"smoke A fence block_until_ready={bur:.5f} s/step "
          f"host_fetch={fetch:.5f} s/step ratio={bur / fetch:.3f} "
          f"agree_within_5pct={abs(bur / fetch - 1) <= 0.05}")
    _check(all(b > 0 for b in a["peak_bytes"]),
           "a device reports zero peak memory after training")
    # One block holds T = 128: the head-group flash forward and its one
    # backward (dq, dk, dv together) per layer.
    b = phase_bert(BERT_LARGE, jnp.bfloat16, batch_per_chip=32, seq=128,
                   steps=10, mosaic_calls=2 * BERT_LARGE.num_layers)
    print("smoke B bert-large batch=32/chip seq=128 " + _fmt(b), flush=True)
    c = phase_server(LLAMA_1B, slots=8, page_size=16, max_len=1024,
                     prompt_lens=(32, 64, 128), output_lens=(16, 32),
                     num_requests=12, mosaic_calls=1)
    print("smoke C llama-1b slots=8 page=16 max_len=1024 " + _fmt(c),
          flush=True)
    hvd.shutdown()
    print(f"smoke compile_cache entries_added="
          f"{_cache_entries(cache_dir) - entries_before}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
