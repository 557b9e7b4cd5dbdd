"""Deviceless topology-AOT worker (spawned by test_scaling.py).

``<topology>``: compiles a tiny shard_map program (one matmul + one psum
+ one ppermute) against a real TPU topology via
``jax.experimental.topologies`` -- no TPU attached -- and prints one JSON
line describing the compiled SCHEDULE.  This is the CI gate for what
``utils.scaling.schedule_overlap_report`` reads: if the toolchain stops
emitting scheduled modules, async collective-permute pairs, or sync
all-reduces, this worker's output changes and the test fails.

``<topology> kernels``: compiles each Pallas family that ``auto``
enables on TPU through Mosaic (``interpret=False``) at the shapes
``chip_smoke.py`` and the benchmark's cells run, and prints ``{case:
mosaic_call_count}`` and, under ``head_group``, the cases that took
flash attention's head-group kernels.  A
kernel Mosaic refuses raises here, in the sandbox, before any chip time
is spent on it.

``<topology> exchange``: compiles ``hvd.allreduce_gradients`` (fp16 wire,
``Average``) over the 32 gradient leaves of two BERT-Large layers and
prints what the exchange became: how many all-reduces, how many operands
the widest takes, and how many ``concatenate`` / ``dynamic-update-slice``
/ ``copy`` ops the module holds.  The exchange emits one psum a leaf;
that XLA's all-reduce combiner makes ONE many-operand all-reduce of them,
each operand in its own tiled layout, and puts no buffer back, is the
compiler's doing and is held here.

``<topology> dense_step [layers [tp]]``: compiles the dense decode step
(``serving/decode.py``) at Mistral-7B's widths (32/8 heads of 128, FFN
14,336, 32 slots, 96 pages of 16 tokens a slot, bfloat16; 4 layers by
default) for ONE chip of the topology (or ``tp`` of them, each with its
shard of the heads) and prints how it reads and writes
its cache: its Mosaic calls, whether both pools are aliased in place,
the module's temporary bytes, and every instruction of the optimized
module cut out of a pool (one of its dims is the pool's page count) whose
result is at least a pool PLANE in size, other than the in-place row
writes, whose result IS the pool (``pool[layer]`` materialised, a
gathered view, a relayout: there must be none).

``<topology> swa_step [slots [prompt ...]]``: compiles, for ONE chip,
the decode step of ``serving/swa_moe.py`` at K-EXAONE-236B-A23B's widths
and this repo's cut of it (8 layers ``L L L G L L L G``, the dense layer
first, 16 of 128 experts held, 19,200 rows of the vocabulary, 32 slots
by default, 16-token pages, contexts to 9,216, bfloat16) and its prefill
at each ``prompt`` length (8,192 by default), and prints what each
holds: Mosaic calls by name, the pools aliased in place, argument,
temporary and total bytes against the chip's 16 GiB.
``<topology> small_step [slots [prompt ...]]``: the same for the block's
second instance at SmallThinker-21BA3B-Instruct's widths as
``benchmarks/configs/smallthinker_21b_a3b.json`` cuts it (8 layers ``G L
L L G L L L``, every layer routed over all 64 experts, the whole
vocabulary, a window ring of 257 pages a slot, 64 slots by default).

``<topology> ssm_step [slots [prompt[xgroup] ...]]``: compiles, for ONE
chip, the decode step of ``serving/ssm_hybrid.py`` at
Falcon-H1-34B-Instruct's widths as ``benchmarks/configs/falcon_h1_34b
.json`` cuts it (6 layers, a Mamba-2 mixer beside attention in each, the
whole vocabulary, 80 slots by default, contexts to 1,024) and its
prefill at each ``prompt`` length (``256x4``: a group of four), and
prints what each holds, as ``swa_step`` does; the slot state ``[6,
slots, 1,063,936]`` float32 must be aliased in place and nothing as
large as one of its planes may be a temporary.

``<topology> pool_write``: compiles, for ONE chip, the program that
follows every prefill (``serving/kvcache.py:_pool_set``) at Mistral's
pool ``[16, 3073, 16, 1024]`` with a 512-token prompt (32 whole pages)
and at SmallThinker's window pool ``[6, 16449, 16, 512]`` with the 4,095
rows a window plane keeps of an 8,192-token prompt (15 rows, then 255
pages), and prints what each became: its scatters (updates and the
window one update writes, from the lowered text; how many the optimized
module holds), whether the pool is aliased input to output, the
temporary bytes, and the copies whose result is at least a pool plane in
size (there must be none: a page write that re-lays the pool out is
worse than the rows it replaces).

Must run in its own process: the TPU compiler takes a host-wide libtpu
lock, and the test process itself is pinned to the CPU backend.
"""

import json
import os
import sys
from os.path import abspath, dirname

sys.path.insert(0, dirname(dirname(abspath(__file__))))


def main(topology: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import topologies
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.utils import scaling

    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    devs = list(td.devices)
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("d",))

    def f(x, w):
        y = x @ w
        g = lax.psum(y, "d")
        perm = [(i, (i + 1) % n) for i in range(n)]
        z = lax.ppermute(y, "d", perm)
        return g + z

    fs = jax.jit(jax.shard_map(f, mesh=mesh,
                               in_specs=(P("d"), P()), out_specs=P("d")))
    x = jax.ShapeDtypeStruct((n * 128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    text = fs.lower(x, w).compile().as_text()
    rep = scaling.schedule_overlap_report(text, n_devices=n)
    print(json.dumps({
        "is_scheduled": "is_scheduled=true" in text,
        "n": n,
        "sync_ops": sorted({o for o, _, _ in rep.sync_collectives}),
        "async_ops": sorted({o for o, _, _, _ in rep.async_collectives}),
        "n_async": len(rep.async_collectives),
        "async_eq_payload": rep.async_eq_payload(),
    }))
    return 0


def kernels(topology: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import attention, moe, pallas, ssm

    # This process's default backend is the CPU (no chip attached), so
    # the package would pick the XLA reference and, forced on, the
    # interpreter.  Force the kernels on through the package's own
    # switch and pin the interpreter off: what lowers below is what
    # lowers on the chip.
    pallas.interpret_mode = lambda: False

    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    sharding = SingleDeviceSharding(td.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def flash_fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: attention.flash_attention(*a).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    def decode(q, k, v, lengths):
        return attention.decode_attention(q, k, v, lengths=lengths)

    def mla_decode(q, pool, page_table, lengths):
        return attention.mla_decode_attention(
            q, pool, page_table, layer=3, lengths=lengths, value_dim=512,
            scale=192 ** -0.5)

    def cca_decode(q, pool, page_table, lengths):
        return attention.cca_decode_attention(
            q, pool, page_table, layer=3, lengths=lengths, kv_heads=2,
            scale=128 ** -0.5)

    def dense_decode(q, keys, values, page_table, lengths):
        return attention.cca_decode_attention(
            q, keys, page_table, layer=3, lengths=lengths, kv_heads=8,
            scale=128 ** -0.5, values=values)

    def swa_decode(q, keys, values, page_table, lengths):
        return attention.cca_decode_attention(
            q, keys, page_table, layer=3, lengths=lengths, kv_heads=8,
            scale=128 ** -0.5, values=values, window=128)

    def swa_prefill(q, k, v):
        return attention.flash_attention(q, k, v, causal=True, window=128)

    def swa_decode_h28(q, keys, values, page_table, lengths):
        return attention.cca_decode_attention(
            q, keys, page_table, layer=3, lengths=lengths, kv_heads=4,
            scale=128 ** -0.5, values=values, window=4096)

    def full_decode_h28(q, keys, values, page_table, lengths):
        return attention.cca_decode_attention(
            q, keys, page_table, layer=1, lengths=lengths, kv_heads=4,
            scale=128 ** -0.5, values=values)

    def swa_prefill_w4096(q, k, v):
        return attention.flash_attention(q, k, v, causal=True, window=4096)

    def gmm_relu(x, w_gate, w_up, w_down, tile_expert, active):
        act = moe.grouped_matmul(x, (w_gate, w_up), tile_expert, active,
                                 tm=16, gate_act="relu")
        return moe.grouped_matmul(act, (w_down,), tile_expert, active, tm=16)

    def loop_decode(q, pool, page_table, lengths):
        # The plane is a traced scalar: pass t of layer 5 of 48, inside a
        # rolled loop over four passes.
        def one_pass(t, acc):
            return acc + attention.cca_decode_attention(
                q, pool, page_table, layer=t * 48 + 5, lengths=lengths,
                kv_heads=16, scale=128 ** -0.5)
        return jax.lax.fori_loop(0, 4, one_pass,
                                 jnp.zeros(q.shape, jnp.float32))

    def prefill(q, k, v):
        return attention.flash_attention(q, k, v, causal=True)

    def gmm(tm):
        def run(x, w_gate, w_up, w_down, tile_expert, active):
            act = moe.grouped_matmul(x, (w_gate, w_up), tile_expert,
                                     active, tm=tm)
            return moe.grouped_matmul(act, (w_down,), tile_expert, active,
                                      tm=tm)
        return run

    def gmm_args(rows, tm, experts=256, width=768):
        bf = jnp.bfloat16
        return [spec((rows, 2048), bf), spec((experts, 2048, width), bf),
                spec((experts, 2048, width), bf),
                spec((experts, width, 2048), bf),
                spec((rows // tm,), jnp.int32), spec((1,), jnp.int32)]

    cases = {
        # The latent-attention decode of 64 slots out of the page pool
        # (5 layers of 34,817 pages of 16 rows): 32 heads, a cached row
        # of latent 512 + rotated key 64 in 640 columns (bfloat16), 544
        # pages a slot.
        "mla_decode_b64": (mla_decode, [
            spec((64, 32, 640), jnp.bfloat16),
            spec((5, 34817, 16, 640), jnp.bfloat16),
            spec((64, 544), jnp.int32), spec((64,), jnp.int32)]),
        # Its expanded prefill: queries and keys 192 wide, values 128.
        "flash_mla_prefill_8k": (
            prefill, [spec((1, 32, 8192, 192), jnp.bfloat16)] * 2
            + [spec((1, 32, 8192, 128), jnp.bfloat16)]),
        # 256 experts of 2048 x 768: a decode round's 64 x 8 pairs in
        # tiles of 16 rows, an 8,192-token prefill's in tiles of 128.
        "moe_gmm_decode": (gmm(16), gmm_args(4352, 16)),
        "moe_gmm_prefill_8k": (gmm(128), gmm_args(98048, 128)),
        # 16 experts of 2048 x 2048, top 1: gate and up in column slices
        # of 1,024 (32 MB whole, double-buffered), down whole; a decode
        # round's 96 rows in tiles of 16, a 512-token prompt's in 32.
        "moe_gmm_wide_decode": (gmm(16), gmm_args(336, 16, 16, 2048)),
        "moe_gmm_wide_prefill_512": (gmm(32), gmm_args(1024, 32, 16, 2048)),
        # The grouped-query decode of 96 slots out of rows that hold two
        # key and two value heads of 128 side by side (24 layers of
        # 9,217 pages of 16 rows, 96 pages a slot), and its 512-token
        # prefill (8 query heads over 2): one block, the head-group
        # forward.
        "cca_decode_b96": (cca_decode, [
            spec((96, 8, 128), jnp.bfloat16),
            spec((24, 9217, 16, 512), jnp.bfloat16),
            spec((96, 96), jnp.int32), spec((96,), jnp.int32)]),
        "flash_cca_prefill_512": (prefill, [
            spec((1, 8, 512, 128), jnp.bfloat16),
            spec((1, 2, 512, 128), jnp.bfloat16),
            spec((1, 2, 512, 128), jnp.bfloat16)]),
        # The looped model's page walk: 20 slots out of rows that hold 16
        # key and 16 value heads of 128 side by side (192 planes of 321
        # pages of 16 rows, 16 pages a slot), one query row a key head,
        # the plane traced; and its 128-token prefill, 16 heads over 16.
        "loop_decode_b20": (loop_decode, [
            spec((20, 16, 128), jnp.bfloat16),
            spec((192, 321, 16, 4096), jnp.bfloat16),
            spec((20, 16), jnp.int32), spec((20,), jnp.int32)]),
        "flash_loop_prefill_128": (
            prefill, [spec((1, 16, 128, 128), jnp.bfloat16)] * 3),
        # BERT-Large, batch 32/chip, seq 128: 16 heads of 64.  One block
        # holds the sequence: the head-group kernels, forward and one
        # backward, all 16 heads a grid step.
        "flash_bert_large": (
            flash_fwd_bwd, [spec((32, 16, 128, 64), jnp.bfloat16)] * 3),
        # Mistral-7B's prefill of a 512-token prompt (GQA 32/8, d 128,
        # causal): the widest head-group step a served cell takes; its
        # 1,024-token prompt is two blocks and keeps the blocked kernel.
        "flash_mistral_prefill_512": (prefill, [
            spec((1, 32, 512, 128), jnp.bfloat16),
            spec((1, 8, 512, 128), jnp.bfloat16),
            spec((1, 8, 512, 128), jnp.bfloat16)]),
        "flash_mistral_prefill_1024": (prefill, [
            spec((1, 32, 1024, 128), jnp.bfloat16),
            spec((1, 8, 1024, 128), jnp.bfloat16),
            spec((1, 8, 1024, 128), jnp.bfloat16)]),
        # Mistral-7B's decode of 32 slots out of TWO pools (16 layers of
        # 3,073 pages of 16 rows, 96 pages a slot), a row of eight key
        # heads of 128 in one and eight value heads in the other; and
        # LLAMA_1B's (chip_smoke.py: float32, 8 slots, 64 pages a slot).
        "dense_decode_b32": (dense_decode, [
            spec((32, 32, 128), jnp.bfloat16),
            spec((16, 3073, 16, 1024), jnp.bfloat16),
            spec((16, 3073, 16, 1024), jnp.bfloat16),
            spec((32, 96), jnp.int32), spec((32,), jnp.int32)]),
        "dense_decode_f32_b8": (dense_decode, [
            spec((8, 16, 128), jnp.float32),
            spec((16, 513, 16, 1024), jnp.float32),
            spec((16, 513, 16, 1024), jnp.float32),
            spec((8, 64), jnp.int32), spec((8,), jnp.int32)]),
        # K-EXAONE's window layers (PR 39): the walk over a slot's ring of
        # nine pages out of the window group's two pools, 64 query heads
        # over eight, under its own name; and the banded prefill kernel
        # over 8,192 tokens (two key blocks of 512 a query block) and
        # over 512 (one block: the blocked kernel, never the head-group
        # one, which knows no window).
        "swa_decode_b32": (swa_decode, [
            spec((32, 64, 128), jnp.bfloat16),
            spec((6, 289, 16, 1024), jnp.bfloat16),
            spec((6, 289, 16, 1024), jnp.bfloat16),
            spec((32, 9), jnp.int32), spec((32,), jnp.int32)]),
        "flash_swa_prefill_8k": (swa_prefill, [
            spec((1, 64, 8192, 128), jnp.bfloat16),
            spec((1, 8, 8192, 128), jnp.bfloat16),
            spec((1, 8, 8192, 128), jnp.bfloat16)]),
        "flash_swa_prefill_512": (swa_prefill, [
            spec((1, 64, 512, 128), jnp.bfloat16),
            spec((1, 8, 512, 128), jnp.bfloat16),
            spec((1, 8, 512, 128), jnp.bfloat16)]),
        # SmallThinker's shapes (PR 42): SEVEN query heads a key/value
        # head (28 over 4: no whole sublane tile), the walk over a ring of
        # 257 window pages a slot and over 576 pages of a full layer out
        # of two pools of 512-column rows, 64 slots; the banded prefill
        # over 8,192 tokens under a window of 4,096 (nine key blocks a
        # query block); the grouped matmul's ReLU epilogue at a decode
        # round's 64 x 6 pairs over 64 experts of 2560 x 768.
        "swa_decode_b64_h28": (swa_decode_h28, [
            spec((64, 28, 128), jnp.bfloat16),
            spec((6, 16449, 16, 512), jnp.bfloat16),
            spec((6, 16449, 16, 512), jnp.bfloat16),
            spec((64, 257), jnp.int32), spec((64,), jnp.int32)]),
        "full_decode_b64_h28": (full_decode_h28, [
            spec((64, 28, 128), jnp.bfloat16),
            spec((2, 36865, 16, 512), jnp.bfloat16),
            spec((2, 36865, 16, 512), jnp.bfloat16),
            spec((64, 576), jnp.int32), spec((64,), jnp.int32)]),
        "flash_swa_prefill_8k_w4096": (swa_prefill_w4096, [
            spec((1, 28, 8192, 128), jnp.bfloat16),
            spec((1, 4, 8192, 128), jnp.bfloat16),
            spec((1, 4, 8192, 128), jnp.bfloat16)]),
        "moe_gmm_relu_decode": (gmm_relu, [
            spec((1344, 2560), jnp.bfloat16),
            spec((64, 2560, 768), jnp.bfloat16),
            spec((64, 2560, 768), jnp.bfloat16),
            spec((64, 768, 2560), jnp.bfloat16),
            spec((84,), jnp.int32), spec((1,), jnp.int32)]),
        # PR 48: one step of the state-space recurrence for 80 slots, 32
        # heads of 128 columns over a state of 256 in 2 groups, in place
        # over plane 3 of six planes of 1,063,936-value float32 rows.
        "ssm_decode_b80": (
            lambda st, x, dt, a, b, c, d, live: ssm.ssm_decode_update(
                st, x, dt, a, b, c, d, live, plane=3), [
            spec((6, 80, 1063936), jnp.float32),
            spec((80, 32, 128), jnp.float32), spec((80, 32), jnp.float32),
            spec((32,), jnp.float32), spec((80, 2, 256), jnp.float32),
            spec((80, 2, 256), jnp.float32), spec((32,), jnp.float32),
            spec((80,), jnp.bool_)]),
        # LLAMA_1B decode, 8 slots, GQA 16/8, S 1024, D 128.
        "flash_decode_b8": (decode, [
            spec((8, 16, 1, 128), jnp.float32),
            spec((8, 8, 1024, 128), jnp.float32),
            spec((8, 8, 1024, 128), jnp.float32),
            spec((8,), jnp.int32)]),
    }
    out, head_group = {}, []
    for name, (fn, args) in cases.items():
        lowered = jax.jit(fn).lower(*args)
        lowered.compile()   # Mosaic refusals raise here
        text = lowered.as_text()
        out[name] = text.count("tpu_custom_call")
        if "hvd_flash_hg_fwd" in text:
            head_group.append(name)
        if name.startswith(("swa_", "flash_swa_")):
            assert ("hvd_swa_decode" in text) == name.startswith("swa_")
            assert ("hvd_flash_swa_fwd" in text) == name.startswith("flash_")
    out["head_group"] = head_group
    print(json.dumps(out))
    return 0


def dense_step(topology: str, layers: int = 4, tp: int = 1) -> int:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.controller import fusion
    from horovod_tpu.models.transformer import LlamaConfig, LlamaLM
    from horovod_tpu.ops import pallas
    from horovod_tpu.serving import build_decode_step
    from horovod_tpu.serving.decode import decode_param_specs, no_round

    pallas.interpret_mode = lambda: False
    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    mesh = Mesh(np.asarray(td.devices[:tp]), ("tp",))
    cfg = LlamaConfig(vocab_size=32768, num_layers=layers, num_heads=32,
                      num_kv_heads=8, head_dim=128, d_model=4096,
                      ffn_hidden=14336, rope_theta=1e6, max_seq_len=32768)
    slots, page, pps = 32, 16, 96
    bf = jnp.bfloat16

    def on(spec):
        return NamedSharding(mesh, spec)

    shapes = jax.eval_shape(LlamaLM(cfg, dtype=bf).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    params = jax.tree.map(
        lambda z, spec: jax.ShapeDtypeStruct(z.shape, bf, sharding=on(spec)),
        shapes, decode_param_specs(shapes))
    pool = jax.ShapeDtypeStruct(
        (layers, slots * pps + 1, page, cfg.num_kv_heads * cfg.head_dim),
        bf, sharding=on(P(None, None, None, "tp")))

    def whole(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on(P()))

    args = (params, pool, pool, whole((slots,), jnp.int32),
            whole((slots,), jnp.int32), whole((slots, pps), jnp.int32),
            whole((slots,), jnp.bool_), whole(no_round(slots).shape,
                                              jnp.int32))
    # The step builds its jitted program at its first call: take what it
    # builds instead of calling it.
    built = []
    fusion.plan_executable = lambda plan, build, extra=(): built.append(
        build()) or (lambda *a: None)
    step = build_decode_step(cfg, mesh, slots=slots, page_size=page,
                             pages_per_slot=pps, dtype=bf)
    step(*args)
    lowered = built[0].lower(*args)
    compiled = lowered.compile()
    text = compiled.as_text()
    plane = (slots * pps + 1) * page * cfg.num_kv_heads * cfg.head_dim // tp
    local = pool.shape[:3] + (pool.shape[3] // tp,)
    big, writes = [], 0
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (\w+)\[([\d,]*)\]\S* (\S+?)\(",
                     line)
        if not m or m.group(4) in ("parameter", "get-tuple-element",
                                   "tuple", "bitcast"):
            continue
        dims = tuple(int(d) for d in m.group(3).split(",") if d)
        if slots * pps + 1 not in dims or int(np.prod(dims)) < plane:
            continue
        if dims == local and m.group(4) in ("fusion", "scatter"):
            writes += m.group(4) == "fusion"
        else:
            big.append(f"{m.group(4)} {m.group(2)}[{m.group(3)}]")
    header = text[:text.index("\n")]
    print(json.dumps({
        "attention": step.meta["attention"],
        "mosaic_calls": lowered.as_text().count("tpu_custom_call"),
        "aliased_params": sorted(int(i) for i in re.findall(
            r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)),
        "pool_params": [len(jax.tree.leaves(params)),
                        len(jax.tree.leaves(params)) + 1],
        "pool_writes": writes,
        "plane_sized": sorted(set(big)),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
    }))
    return 0


def swa_step(topology: str, slots: int = 32, *prompts: int,
             small: bool = False) -> int:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import pallas
    from horovod_tpu.serving import swa_moe
    from horovod_tpu.serving.decode import no_round

    pallas.interpret_mode = lambda: False
    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    mesh = Mesh(np.asarray(td.devices[:1]), ("tp",))
    if small:
        from benchmarks.families import smallthinker_swa_moe as family
        with open(os.path.join(dirname(dirname(abspath(__file__))),
                               "benchmarks", "configs",
                               "smallthinker_21b_a3b.json")) as f:
            cfg = family.program_config(json.load(f))
    else:
        cfg = swa_moe.SwaMoeConfig(
            vocab_size=153600, d_model=6144, num_heads=64, num_kv_heads=8,
            head_dim=128, ffn_hidden=18432, moe_hidden=2048,
            num_experts=128, experts_per_token=8,
            attn_kinds=("window",) * 3 + ("full",) + ("window",) * 3
            + ("full",), ffn_kinds=("dense",) + ("moe",) * 7, window=128,
            routed_scale=2.5, max_seq_len=262144, experts_held=16,
            vocab_held=19200)
    page, max_len, bf = 16, 9216, jnp.bfloat16
    pps, ring = max_len // page, -(-cfg.window // page) + 1
    on = NamedSharding(mesh, P())

    def whole(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    params = jax.tree.map(lambda z: whole(z.shape, bf),
                          swa_moe.param_shapes(cfg, bf))
    weights = sum(int(np.prod(z.shape)) * 2 for z in jax.tree.leaves(params))
    pool = whole((2, slots * pps + 1, page, cfg.kv_width), bf)
    wpool = whole((6, slots * ring + 1, page, cfg.kv_width), bf)
    out = {"weight_bytes": weights,
           "cache_bytes": 2 * 2 * (int(np.prod(pool.shape))
                                   + int(np.prod(wpool.shape)))}

    def report(name, lowered):
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = lowered.as_text()
        header = compiled.as_text()
        header = header[:header.index("\n")]
        out[name] = {
            "mosaic_calls": {k: text.count(f'kernel_name = "{k}"') for k in (
                "hvd_cca_decode", "hvd_swa_decode", "hvd_moe_gmm",
                "hvd_flash_fwd", "hvd_flash_swa_fwd", "hvd_flash_hg_fwd")},
            "aliased_params": sorted(int(i) for i in re.findall(
                r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

    step = swa_moe.build_decode_step(cfg, mesh, slots=slots, page_size=page,
                                     pages_per_slot=pps, dtype=bf)
    report("decode", step._fn.lower(
        params, pool, pool, whole((slots,), jnp.int32),
        whole((slots,), jnp.int32), whole((slots, pps), jnp.int32),
        whole((slots,), jnp.bool_), whole((slots, ring), jnp.int32),
        wpool, wpool,
        whole((len(cfg.moe_layers), cfg.num_experts), jnp.int32),
        whole(no_round(slots, 1).shape, jnp.int32)))
    out["decode"]["pool_params"] = [
        len(jax.tree.leaves(params)) + i for i in (0, 1, 7, 8)]

    def prefill(p, toks):
        return swa_moe.prefill_forward(p, cfg, toks, dtype=bf)

    for t in prompts or (8192,):
        report(f"prefill_{t}", jax.jit(prefill).lower(
            params, whole((1, t), jnp.int32)))
        # Beside the prefill: the weights and both groups of pools.
        out[f"prefill_{t}"]["resident_with_cache"] = (
            out["cache_bytes"] + out[f"prefill_{t}"]["argument_bytes"]
            + out[f"prefill_{t}"]["temp_bytes"]
            + out[f"prefill_{t}"]["output_bytes"])
    print(json.dumps(out))
    return 0


def ssm_step(topology: str, slots: int = 80, *prompts: str) -> int:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.families import falcon_h1_hybrid as family
    from horovod_tpu.ops import pallas
    from horovod_tpu.serving import ssm_hybrid
    from horovod_tpu.serving.decode import no_round

    pallas.interpret_mode = lambda: False
    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    mesh = Mesh(np.asarray(td.devices[:1]), ("tp",))
    with open(os.path.join(dirname(dirname(abspath(__file__))),
                           "benchmarks", "configs",
                           "falcon_h1_34b.json")) as f:
        config = json.load(f)
    cfg = family.program_config(config)
    serving = config["serving"]
    page, max_len, bf = serving["page_size"], serving["max_len"], jnp.bfloat16
    pps = max_len // page
    on = NamedSharding(mesh, P())

    def whole(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    params = jax.tree.map(lambda z: whole(z.shape, bf),
                          ssm_hybrid.param_shapes(cfg, bf))
    weights = sum(int(np.prod(z.shape)) * 2 for z in jax.tree.leaves(params))
    pool = whole((cfg.num_layers, slots * pps + 1, page, cfg.kv_width), bf)
    state = whole((cfg.num_layers, slots, cfg.slot_state_width), jnp.float32)
    out = {"weight_bytes": weights,
           "cache_bytes": 2 * 2 * int(np.prod(pool.shape))
           + 4 * int(np.prod(state.shape)),
           "state_plane_bytes": 4 * slots * cfg.slot_state_width}

    def report(name, lowered):
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = lowered.as_text()
        header = compiled.as_text()
        header = header[:header.index("\n")]
        out[name] = {
            "mosaic_calls": {k: text.count(f'kernel_name = "{k}"') for k in (
                "hvd_cca_decode", "hvd_ssm_decode", "hvd_flash_fwd",
                "hvd_flash_hg_fwd")},
            "aliased_params": sorted(int(i) for i in re.findall(
                r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

    step = ssm_hybrid.build_decode_step(cfg, mesh, slots=slots,
                                        page_size=page, pages_per_slot=pps,
                                        dtype=bf)
    report("decode", step._fn.lower(
        params, pool, pool, whole((slots,), jnp.int32),
        whole((slots,), jnp.int32), whole((slots, pps), jnp.int32),
        whole((slots,), jnp.bool_), state,
        whole(no_round(slots).shape, jnp.int32)))
    out["decode"]["pool_params"] = [
        len(jax.tree.leaves(params)) + i for i in (0, 1, 6)]

    def prefill(p, toks):
        return ssm_hybrid.prefill_forward(p, cfg, toks, dtype=bf)

    for what in prompts or ("512", "256x4"):
        t, _, b = what.partition("x")
        report(f"prefill_{what}", jax.jit(prefill).lower(
            params, whole((int(b or 1), int(t)), jnp.int32)))
        # Beside the prefill: the weights, the pools and the slot state.
        out[f"prefill_{what}"]["resident_with_cache"] = (
            out["cache_bytes"] + out[f"prefill_{what}"]["argument_bytes"]
            + out[f"prefill_{what}"]["temp_bytes"]
            + out[f"prefill_{what}"]["output_bytes"])
    print(json.dumps(out))
    return 0


def eva_step(topology: str, slots: int = 0, *prompts: int) -> int:
    """``serving/eva_dense.py``'s decode step and prefills at EvaByte's
    widths from ``benchmarks/configs/evabyte_6_5b.json``: both groups'
    pages in ONE pair of pools, aliased in place; the walk under its own
    name; what a prefill holds beside weights and cache."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.families import eva_dense as family
    from horovod_tpu.ops import pallas
    from horovod_tpu.serving import eva_dense
    from horovod_tpu.serving.decode import no_round
    from horovod_tpu.serving.kvcache import CacheConfig

    pallas.interpret_mode = lambda: False
    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    mesh = Mesh(np.asarray(td.devices[:1]), ("tp",))
    with open(os.path.join(dirname(dirname(abspath(__file__))),
                           "benchmarks", "configs",
                           "evabyte_6_5b.json")) as f:
        config = json.load(f)
    cfg = family.program_config(config)
    serving = config["serving"]
    slots = slots or serving["slots"]
    bf = jnp.bfloat16
    spec = cfg.layer_spec()
    cc = CacheConfig(
        num_layers=spec.planes, slots=slots, page_size=serving["page_size"],
        max_len=serving["max_len"], dtype="bfloat16", page=spec.page,
        window_layers=spec.window_planes, window=spec.window,
        row_tokens=spec.row_tokens)
    on = NamedSharding(mesh, P())

    def whole(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    params = jax.tree.map(lambda z: whole(z.shape, bf),
                          eva_dense.param_shapes(cfg, bf))
    weights = sum(int(np.prod(z.shape)) * 2 for z in jax.tree.leaves(params))
    pool = whole(cc.layout()["kv_shape"], bf)
    out = {"weight_bytes": weights,
           "cache_bytes": 2 * 2 * int(np.prod(pool.shape)),
           "plane_bytes": 2 * int(np.prod(pool.shape[1:])),
           "pool_shape": list(pool.shape)}

    def report(name, lowered):
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = lowered.as_text()
        header = compiled.as_text()
        header = header[:header.index("\n")]
        out[name] = {
            "mosaic_calls": {k: text.count(f'kernel_name = "{k}"') for k in (
                "hvd_eva_decode", "hvd_cca_decode", "hvd_flash_fwd",
                "hvd_flash_hg_fwd")},
            "aliased_params": sorted(int(i) for i in re.findall(
                r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

    step = eva_dense.build_decode_step(
        cfg, mesh, slots=slots, page_size=cc.page_size,
        pages_per_slot=cc.pages_per_slot, dtype=bf)
    report("decode", step._fn.lower(
        params, pool, pool, whole((slots,), jnp.int32),
        whole((slots,), jnp.int32), whole((slots, cc.pages_per_slot),
                                          jnp.int32),
        whole((slots,), jnp.bool_),
        whole((slots, cc.window_pages_per_slot), jnp.int32),
        whole(no_round(slots).shape, jnp.int32)))
    out["decode"]["pool_params"] = [
        len(jax.tree.leaves(params)) + i for i in (0, 1)]

    def prefill(p, toks):
        return spec.prefill(p, toks, dtype=bf)

    for t in prompts:
        report(f"prefill_{t}", jax.jit(prefill).lower(
            params, whole((1, int(t)), jnp.int32)))
        out[f"prefill_{t}"]["resident_with_cache"] = (
            out["cache_bytes"] + out[f"prefill_{t}"]["argument_bytes"]
            + out[f"prefill_{t}"]["temp_bytes"]
            + out[f"prefill_{t}"]["output_bytes"])
    print(json.dumps(out))
    return 0


def pool_write(topology: str) -> int:
    import math
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.serving import kvcache

    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    one = SingleDeviceSharding(td.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    out = {}
    # name: (pool, rows of the prompt, whole pages, single rows before).
    for name, (pool, t, pages, head) in {
            "mistral_512": ((16, 3073, 16, 1024), 512, 32, 0),
            "smallthinker_window_8192": ((6, 16449, 16, 512), 4095, 255,
                                         15)}.items():
        lowered = kvcache._pool_set.lower(
            shape(pool, jnp.bfloat16),
            shape((pool[0], t, pool[3]), jnp.bfloat16),
            shape((pages,), jnp.int32),
            shape((2, t - 16 * pages), jnp.int32) if t > 16 * pages
            else None, head=head)
        scatters = []
        for dims, updates in re.findall(
                r'"stablehlo\.scatter".*?update_window_dims = \[([\d, ]*)\]'
                r".*?\}\) : \(tensor<[^>]*>, tensor<[^>]*>, "
                r"tensor<([^>]*)>\)", lowered.as_text(), re.S):
            window = [int(d) for d in dims.split(",")]
            sizes = [int(n) for n in updates.split("x")[:-1]]
            scatters.append({
                "updates": math.prod(n for i, n in enumerate(sizes)
                                     if i not in window),
                "window": [sizes[i] for i in window]})
        compiled = lowered.compile()
        text = compiled.as_text()
        plane = math.prod(pool[1:])
        out[name] = {
            "scatters": scatters,
            "compiled_scatters": len(re.findall(r"= \S+ scatter\(", text)),
            "aliased": "input_output_alias={ {}: (0, {}" in text,
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "plane_sized_copies": [
                dims for dims in re.findall(
                    r"= \w+\[([\d,]+)\]\S* copy\(", text)
                if math.prod(int(n) for n in dims.split(",")) >= plane],
        }
    print(json.dumps(out))
    return 0


def exchange(topology: str) -> int:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    td = topologies.get_topology_desc(platform="tpu",
                                      topology_name=topology)
    mesh = Mesh(np.asarray(td.devices), ("d",))
    d, f = 1024, 4096
    layer = [(d, d), (d,)] * 4 + [(d, f), (f,), (f, d), (d,)] \
        + [(d,)] * 4
    shapes = layer * 2

    def local(*grads):
        # A producer and a consumer, so that the casts have fusions to
        # join as they do between the backward and the optimizer.
        grads = [g * 2.0 for g in grads]
        out = hvd.allreduce_gradients(
            grads, hvd.Average, compression=hvd.Compression.fp16,
            axes=("d",))
        return tuple(o + 1.0 for o in out)

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
    rep = NamedSharding(mesh, P())
    text = fn.lower(*[jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
                      for s in shapes]).compile().as_text()
    ars = [line.split(" all-reduce")[0] for line in text.splitlines()
           if re.search(r"= .* all-reduce(-start)?\(", line)]
    print(json.dumps({
        "leaves": len(shapes),
        "all_reduces": len(ars),
        "operands": max(len(re.findall(r"\[[\d,]*\]", a)) for a in ars),
        "tiled_operands": sum("T(4,128)" in a for a in ars),
        **{op: len(re.findall(r"= \S+ %s\(" % op, text))
           for op in ("concatenate", "dynamic-update-slice", "copy")},
    }))
    return 0


if __name__ == "__main__":
    topo = sys.argv[1] if len(sys.argv) > 1 else "v5e:2x4"
    if sys.argv[2:] == ["exchange"]:
        sys.exit(exchange(topo))
    if sys.argv[2:] == ["pool_write"]:
        sys.exit(pool_write(topo))
    if sys.argv[2:3] == ["dense_step"]:
        os.environ["HOROVOD_PALLAS"] = "1"
        sys.exit(dense_step(topo, *(int(a) for a in sys.argv[3:5])))
    if sys.argv[2:3] in (["swa_step"], ["small_step"]):
        os.environ["HOROVOD_PALLAS"] = "1"
        small = sys.argv[2] == "small_step"
        sys.exit(swa_step(topo, *(int(a) for a in sys.argv[3:]
                                  or (("64",) if small else ())),
                          small=small))
    if sys.argv[2:3] == ["ssm_step"]:
        os.environ["HOROVOD_PALLAS"] = "1"
        sys.exit(ssm_step(topo, *(int(a) for a in sys.argv[3:4]),
                          *sys.argv[4:]))
    if sys.argv[2:3] == ["eva_step"]:
        os.environ["HOROVOD_PALLAS"] = "1"
        sys.exit(eva_step(topo, *(int(a) for a in sys.argv[3:])))
    if sys.argv[2:] == ["kernels"]:
        os.environ["HOROVOD_PALLAS"] = "1"
        sys.exit(kernels(topo))
    sys.exit(main(topo))
