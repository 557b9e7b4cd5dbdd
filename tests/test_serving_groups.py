"""A band's short prompts are prefilled together: the plain joins of one
length that ONE admission brought go through one prefill program, up to
four of them (``serving/engine.py``: ``group_joins``, ``_begin_prefill``,
``_do_prefill``).  Held here, on tiny engines of every served family: a
grouped queue is served what the same queue is served a prompt at a time
(tokens, lengths, the rows the cache holds of every prompt); the rule as
a pure function, with the sizes the served cells get; what is never
grouped; that a group compiles nothing when it forms; and what the
``serve.account`` record and a ``serve.prefill`` span say of a group."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.tracing import CompileCounter
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import Request, ServingEngine, stack_adapters
from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.engine import group_joins, group_size
from horovod_tpu.timeline import spans
# The five served families, the window-and-full routed block in both its
# instances: K-EXAONE's and the one with a router that reads the layer's
# input ahead of attention (SmallThinker's).
from serving_families import FAMILIES_AND_EARLY_ROUTE as FAMILIES
from serving_families import host_first_tokens

SLOTS, PAGE, MAX_LEN = 8, 4, 32


# -- the rule -----------------------------------------------------------------

def _sizes(lengths, max_len):
    groups = group_joins([(n, 0) for n in lengths], min(max_len, 2048))
    assert sorted(i for g in groups for i in g) == list(range(len(lengths)))
    assert all(len({lengths[i] for i in g}) == 1 for g in groups)
    return [(lengths[g[0]], len(g)) for g in groups]


@pytest.mark.parametrize("lengths, max_len, want", [
    # ouro_2_6b (max_len 256): 64 x 4; four of 128 would be 512 rows.
    ([64] * 4, 256, [(64, 4)]),
    ([128] * 4, 256, [(128, 1)] * 4),
    # zaya1_8b and mistral_7b (1,536): 128 x 4 and 256 x 4.
    ([128] * 4, 1536, [(128, 4)]),
    ([256] * 8, 1536, [(256, 4), (256, 4)]),
    ([512] * 4, 1536, [(512, 1)] * 4),
    ([1024] * 2, 1536, [(1024, 1), (1024, 1)]),
    # k_exaone (9,216): 512 x 4, every longer prompt alone.
    ([512] * 4, 9216, [(512, 4)]),
    ([1024] * 4, 9216, [(1024, 1)] * 4),
    ([2048] * 2, 9216, [(2048, 1), (2048, 1)]),
    ([8192] * 2, 9216, [(8192, 1), (8192, 1)]),
    # Fewer than four go alone, and so does what four leave over.
    ([64] * 3, 256, [(64, 1)] * 3),
    ([64] * 7, 256, [(64, 4)] + [(64, 1)] * 3),
    ([128] * 9, 1536, [(128, 4), (128, 4), (128, 1)]),
    # One join alone, whatever its length against the rows.
    ([300], 256, [(300, 1)]),
    ([], 256, []),
])
def test_the_sizes_of_the_groups(lengths, max_len, want):
    assert _sizes(lengths, max_len) == want


@pytest.mark.parametrize("prompt_len, max_len, want", [
    (64, 256, 4), (128, 256, 1), (128, 1536, 4), (256, 1536, 4),
    (512, 1536, 1), (512, 9216, 4), (513, 9216, 1), (1024, 8704, 1),
    (2048, 9216, 1), (3584, 9216, 1)])
def test_a_length_goes_four_at_a_time_or_alone(prompt_len, max_len, want):
    assert group_size(prompt_len, min(max_len, 2048)) == want


def test_groups_go_in_the_order_their_first_members_were_admitted():
    lengths = [128, 256, 128, 128, 256, 128, 128, 256, 256]
    groups = group_joins([(n, 0) for n in lengths], 1536)
    assert groups == [[0, 2, 3, 5], [1, 4, 7, 8], [6]]
    # A join that may not share a program (None) goes alone, in its
    # place; two adapter ids of one length are two groups.
    keys = [(64, 0), None, (64, 1), (64, 0), (64, 0), (64, 1), (64, 0),
            (64, 1), None, (64, 1)]
    assert group_joins(keys, 256) == [[0, 3, 4, 6], [1], [2, 5, 7, 9], [8]]


# -- a grouped queue against the same queue a prompt at a time ---------------

def _band_requests(cfg):
    """Sixteen requests, all there at t = 0, over eight slots.  The first
    admission brings five prompts of 6 tokens and three of 10: four of
    the five in one program, everything else alone (four of 10 would be
    40 rows, over ``MAX_LEN``).  Their outputs end in bands, so a later
    admission brings four of 6 tokens at once too."""
    rng = np.random.RandomState(7)
    lens = [6, 10, 6, 6, 10, 6, 6, 10] + [6, 6, 6, 6, 10, 10, 10, 6]
    outs = [3, 3, 3, 3, 5, 5, 5, 5] + [4, 4, 4, 4, 2, 2, 3, 3]
    return [Request(rid=i, prompt=rng.randint(0, min(60, cfg.vocab_size),
                                              size=n).astype(np.int32),
                    max_new_tokens=o, arrival_s=0.0)
            for i, (n, o) in enumerate(zip(lens, outs))]


def _held(eng, slot):
    """What the cache holds of ``slot``'s sequence: its length, its rows
    in every plane of each pool (a row a token, or a pooled row a whole
    chunk of ``row_tokens``), its ring's rows in the window planes (which
    may lie in the pools themselves), its row of the slot state."""
    from horovod_tpu.serving.kvcache import window_rows_from
    cache, c = eng.cache, eng.cache_config
    n = int(cache.lengths[slot])
    pos = np.arange(n // c.row_tokens)
    pages = cache.page_table[slot][pos // c.page_size]
    out = {"length": n, "k": np.asarray(cache.k)[:, pages, pos % c.page_size]}
    if cache.v is not None:
        out["v"] = np.asarray(cache.v)[:, pages, pos % c.page_size]
    if cache.window_table is not None:
        first = window_rows_from(n, c.window, c.window_aligned)
        pos = np.arange(first, n)
        pages = cache.window_table[slot][
            pos // c.page_size % c.window_pages_per_slot]
        wk, wv = (cache.k, cache.v) if c.window_in_pool \
            else (cache.wk, cache.wv)
        out["wk"] = np.asarray(wk)[:, pages, pos % c.page_size]
        out["wv"] = np.asarray(wv)[:, pages, pos % c.page_size]
    if cache.state is not None:
        out["state"] = np.asarray(cache.state)[:, slot]
    return out


def ALONE(keys, rows):
    """``group_joins`` as it would be without groups."""
    return [[i] for i in range(len(keys))]


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """One family's tiny engine after a benchmark's warm-up (ONE request
    a prompt length), serving :func:`_band_requests` twice: every join
    through a program of its own, as before there were groups, and then
    grouped.  Of each call: the requests, what the cache held of each as
    its prefill had been written, the ``serve.prefill`` spans, the
    account and what the call lowered."""
    cfg, params = FAMILIES[request.param]()
    eng = ServingEngine(cfg, params, slots=SLOTS, page_size=PAGE,
                        max_len=MAX_LEN, dtype=jnp.float32)
    pages = eng.cache.free_pages
    warm = [Request(rid=i, prompt=np.arange(n, dtype=np.int32) % 7,
                    max_new_tokens=4, arrival_s=0.0)
            for i, n in enumerate((6, 10))]
    assert eng.serve(warm).completed == len(warm)
    held = {}
    do_prefill = eng._do_prefill

    def watching(slot, req, dev, *args, others=(), **kwargs):
        out = do_prefill(slot, req, dev, *args, others=others, **kwargs)
        for s, r, _ in [(slot, req, dev), *others]:
            held[r.rid] = _held(eng, s)
        return out

    eng._do_prefill = watching
    rec = spans.recorder()
    # (Lowerings, not backend compiles: a persistent cache would hide
    # those.)
    lowerings = CompileCounter()
    lowerings.EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    out = {}
    for how in ("alone", "grouped"):
        mp = pytest.MonkeyPatch()
        if how == "alone":
            mp.setattr(engine_mod, "group_joins", ALONE)
        rec.reset()
        held.clear()
        lowerings.count = 0
        reqs = _band_requests(cfg)
        try:
            with lowerings.counting():
                report = eng.serve(reqs)
        finally:
            mp.undo()
        assert report.completed == len(reqs)
        assert eng.cache.free_pages == pages and not eng.cache.lengths.any()
        assert not any(r.in_flight for r in reqs)
        account, = rec.records(name="serve.account")
        out[how] = dict(reqs=reqs, held=dict(held), account=account.attrs,
                        prefills=rec.records(name="serve.prefill"),
                        lowered=lowerings.count)
    return dict(out, eng=eng, cfg=cfg)


def test_a_group_is_served_what_its_prompts_are_served_alone(served):
    want, got = served["alone"], served["grouped"]
    assert [r.tokens for r in got["reqs"]] == [r.tokens for r in want["reqs"]]
    # ... and each first token, a member of four's too, is the one the
    # host reads off the prompt's own prefill (no hand-over on the way).
    assert [r.tokens[0] for r in got["reqs"]] \
        == host_first_tokens(served["eng"], got["reqs"])
    for r in got["reqs"]:
        assert len(r.tokens) == r.max_new_tokens
        assert r.admit_s <= r.prefill_start_s <= r.first_token_s
        mine, theirs = got["held"][r.rid], want["held"][r.rid]
        assert mine["length"] == theirs["length"] == r.prompt_len
        assert mine.keys() == theirs.keys()
        for name in set(mine) - {"length"}:
            assert mine[name].shape == theirs[name].shape
            np.testing.assert_allclose(mine[name], theirs[name],
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def test_a_prefill_span_covers_its_group(served):
    reqs, prefills = served["grouped"]["reqs"], served["grouped"]["prefills"]
    # The first admission: five of 6 tokens as 4 + 1 and three of 10,
    # each alone, in the order of their first members.
    first = [(p.attrs["prompt_len"], p.attrs["rids"]) for p in prefills[:5]]
    assert first == [(6, (0, 2, 3, 5)), (10, (1,)), (10, (4,)), (6, (6,)),
                     (10, (7,))]
    for p in prefills:
        a = p.attrs
        assert a["group"] == len(a["rids"]) == len(a["slots"])
        assert len(set(a["slots"])) == a["group"]
        assert (a["rid"], a["slot"]) == (a["rids"][0], a["slots"][0])
        assert {reqs[rid].prompt_len for rid in a["rids"]} \
            == {a["prompt_len"]}
        assert a["group"] * a["prompt_len"] <= MAX_LEN or a["group"] == 1
        assert a["deferred"] is True
    assert sorted(rid for p in prefills for rid in p.attrs["rids"]) \
        == list(range(len(reqs)))
    assert {p.attrs["group"] for p in prefills} == {1, 4}
    assert all(p.attrs["group"] == 1 and p.attrs["rids"] == (p.attrs["rid"],)
               for p in served["alone"]["prefills"])


def test_the_account_counts_prompts_programs_and_shared_programs(served):
    got, alone = served["grouped"], served["alone"]
    account, prefills, n = got["account"], got["prefills"], len(got["reqs"])
    assert account["prefills"] == alone["account"]["prefills"] == n
    assert account["prefill_groups"] == len(prefills) < n
    assert account["prefills_grouped"] == sum(
        p.attrs["group"] for p in prefills if p.attrs["group"] > 1) >= 8
    assert account["first_tokens_deferred"] == n
    assert alone["account"]["prefill_groups"] == len(alone["prefills"]) == n
    assert alone["account"]["prefills_grouped"] == 0


def test_a_group_compiles_nothing_when_it_forms(served):
    """The warm-up met each length ONCE, a prompt alone: the engine
    prepared that length's group programs then, and the calls that
    follow, where groups of four form, lower nothing."""
    assert served["alone"]["lowered"] == 0
    assert served["grouped"]["lowered"] == 0


def test_a_re_prefill_restores_what_the_join_wrote(served):
    """A request that has its first token and nothing more is rebuilt
    from its prompt alone: ``re_prefill`` runs the join's own program
    and writes, so the pools' rows, the window planes' rows and the
    slot state come back as the join left them, bit for bit."""
    eng, cfg = served["eng"], served["cfg"]
    req = _band_requests(cfg)[1]
    assert req.prompt_len == 10
    st = eng.run_state()
    eng.scheduler.submit(req)
    (slot, _), = eng.scheduler.admit(0.0)
    eng.join(st, [(slot, req, jnp.asarray(req.prompt))], lambda: 0.0)
    eng.catch_up(st, lambda: 0.0)
    assert len(req.tokens) == 1 and not st["joins"]
    want = _held(eng, slot)
    # What the cache holds goes bad, every row of it.
    c = eng.cache
    c.k = c.k + 1e3
    for name in ("v", "wk", "wv", "state"):
        if getattr(c, name) is not None:
            setattr(c, name, getattr(c, name) + 1e3)
    assert not np.array_equal(_held(eng, slot)["k"], want["k"])
    assert eng.re_prefill(slot, req) == req.tokens[-1]
    got = _held(eng, slot)
    assert got.keys() == want.keys() and got["length"] == 10
    for name in set(want) - {"length"}:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    eng.scheduler.release(slot, 0.0)
    assert not eng.cache.lengths.any()


# -- what is never grouped -----------------------------------------------------

@pytest.fixture(scope="module")
def dense():
    return LLAMA_SERVE, LlamaLM(LLAMA_SERVE, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _same_length(n, length, outs=3, vocab=60, seed=3, **kw):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, vocab, size=length)
                    .astype(np.int32), max_new_tokens=outs, arrival_s=0.0,
                    **kw) for i in range(n)]


def _served_groups(eng, reqs):
    rec = spans.recorder()
    rec.reset()
    report = eng.serve(reqs)
    assert report.completed == len(reqs)
    account, = rec.records(name="serve.account")
    return ([p.attrs for p in rec.records(name="serve.prefill")],
            account.attrs, report)


def test_a_lone_join_is_a_group_of_one(dense):
    cfg, params = dense
    eng = ServingEngine(cfg, params, slots=4, page_size=PAGE,
                        max_len=MAX_LEN)
    # Distinct lengths at t = 0, and two of one length that arrive one
    # after the other is done: no admission brings two of a length.
    reqs = _same_length(2, 6, outs=2)
    reqs[1].arrival_s = 1e3
    reqs += [Request(rid=2 + i, prompt=np.arange(n, dtype=np.int32),
                     max_new_tokens=2, arrival_s=0.0)
             for i, n in enumerate((5, 7))]
    prefills, account, _ = _served_groups(eng, reqs)
    assert [a["group"] for a in prefills] == [1] * 4
    assert all(a["rids"] == (a["rid"],) and a["slots"] == (a["slot"],)
               for a in prefills)
    assert (account["prefills"], account["prefill_groups"],
            account["prefills_grouped"]) == (4, 4, 0)


def test_a_prefix_hit_is_never_grouped(dense):
    cfg, params = dense
    eng = ServingEngine(cfg, params, slots=4, page_size=PAGE,
                        max_len=MAX_LEN, prefix_cache=True)
    # Four prompts of one length and one first page in one admission:
    # each enters the tree as it is prefilled and the next one hits it.
    reqs = _same_length(4, 10)
    for r in reqs[1:]:
        r.prompt[:2 * PAGE] = reqs[0].prompt[:2 * PAGE]
    prefills, account, report = _served_groups(eng, reqs)
    assert [a["group"] for a in prefills] == [1] * 4
    assert report.prefix_hits == 3
    assert (account["prefill_groups"], account["prefills_grouped"]) == (4, 0)


def test_a_chunked_prompt_is_never_grouped(dense):
    cfg, params = dense
    eng = ServingEngine(cfg, params, slots=6, page_size=PAGE,
                        max_len=MAX_LEN, prefill_chunk=4)
    # Four prompts of 3 tokens (under the chunk: one program for them)
    # and two of 10, which go chunk by chunk, each alone.
    reqs = _same_length(4, 3) + _same_length(2, 10, seed=4)
    for i, r in enumerate(reqs):
        r.rid = i
    rec = spans.recorder()
    prefills, account, _ = _served_groups(eng, reqs)
    assert [(a["prompt_len"], a["rids"]) for a in prefills] \
        == [(3, (0, 1, 2, 3))]
    chunks = rec.records(name="prefill_chunk")
    assert len(chunks) == 2 * 3
    assert (account["prefills"], account["prefill_groups"],
            account["prefills_grouped"]) == (6, 1, 4)
    # The chunked ones' tokens stay on the chip as the group's do.
    assert account["first_tokens_deferred"] == 6


def test_two_adapter_ids_are_never_one_group():
    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32, lora_rank=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))

    def bank(key):
        leaves, treedef = jax.tree.flatten(params["params"])
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            0.05 * jax.random.normal(k, x.shape, x.dtype)
            for k, x in zip(keys, leaves)])

    banks = stack_adapters([bank(jax.random.PRNGKey(1)),
                            bank(jax.random.PRNGKey(2))])
    ids = [0, 1, 0, 1, 0, 0, 1, 0]

    def serve(alone):
        eng = ServingEngine(cfg, params, slots=8, page_size=PAGE,
                            max_len=MAX_LEN, adapters={"params": banks})
        reqs = _same_length(len(ids), 6, outs=6)
        reqs[1].prompt = reqs[0].prompt.copy()
        for r, aid in zip(reqs, ids):
            r.adapter_id = aid
        mp = pytest.MonkeyPatch()
        if alone:
            mp.setattr(engine_mod, "group_joins",
                       lambda keys, rows: [[i] for i in range(len(keys))])
        try:
            prefills, _, _ = _served_groups(eng, reqs)
        finally:
            mp.undo()
        return reqs, prefills

    want, _ = serve(alone=True)
    got, prefills = serve(alone=False)
    # Five of adapter 0 as 4 + 1; three of adapter 1, fewer than the
    # four a group of 6 tokens takes, each alone.
    assert [a["rids"] for a in prefills] \
        == [(0, 2, 4, 5), (1,), (3,), (6,), (7,)]
    assert [r.tokens for r in got] == [r.tokens for r in want]
    # The adapters differ: requests 0 and 1 have ONE prompt and are
    # served other tokens, so a group that mixed the ids would show.
    assert want[0].tokens != want[1].tokens


# -- the slot state's own type left the other blocks' programs alone -----------

# The decode step, one prompt's prefill (10 tokens) and a group's (four of
# 6) of each of the five other served blocks (the window-and-full block
# in both its instances), a tiny engine each (8 slots, pages of 4, max_len
# 32, float32), lowered for the TPU with the kernels on: recorded on PR
# 47's tree, the parent of the PR that gave the slot state a type of its
# own (``LayerSpec.slot_state_dtype``, ``CacheConfig.slot_state_dtype``),
# ``stepparts.build_one_chip_step`` its ``embed_scale`` and
# ``logit_scale``, ``decode.round`` its ``state_bytes`` and
# ``PagedKVCache.free_slot`` its one row of zeros.
_OTHER_BLOCKS_LOWERED = {
    "dense.step":
        "fe7f83bb467ddaffccb03b85f1167988f4e8012139e93898acb40276aa28ef57",
    "dense.prefill":
        "00b87a8f124ce1aece08c141b52d54d833b35bd38c75afa5749be1c58653ab60",
    "dense.group":
        "1275953e8aacd6b837923fddda1e29ffbd5a4e0afdc6570b88c3d56d70021e40",
    "mla_moe.step":
        "7e2564801b069ccddc3855cf1159dd602e61397259f56b307bfd1ccc501f88d6",
    # Re-recorded on PR 49's tree, these two alone: the latent-attention
    # prefill hands ``flash_attention`` its values at their own width,
    # so the ``pad`` before the call and the cut after it are gone (the
    # two texts differ in nothing else but the numbers of the values).
    "mla_moe.prefill":
        "935126be570cb0a0793f6aa3516a062138da0fa343c6364f0ac9fc6332e359da",
    "mla_moe.group":
        "f60c8c60351dd166e0569479323920c80295bdbe17cdf847f4cc24a33b496985",
    "cca_moe.step":
        "14fdd64f502e2d712bf7cf54775cf398bcbf869c70dd52be97e4c0588064a790",
    "cca_moe.prefill":
        "644cbba322aa162a7743362f8d361323ef81f4c59348a4deb272d19a24fa2341",
    "cca_moe.group":
        "96902cae85fbea5d3b8d578f786737a2c1e3620c2c9b82350ed3e6cbcb5ce50d",
    "loop_dense.step":
        "966b483edabfcb588e355130d6a217fb7dd36eb73e7323a78588bced31fb6c4b",
    "loop_dense.prefill":
        "c42848df04321299c17783c55a58ef09da323e063cf893004f3ee89afb562b62",
    "loop_dense.group":
        "c17c0ac61f9ad9ef41a38e94a53266506eb835906b7719e944a8591d0978f089",
    "swa_moe.step":
        "79b08d93224e0eb051ddd5c92b0e3412701bce40488c58edbbf680e6e715d37c",
    "swa_moe.prefill":
        "06e36c77d091015be00d84cc7f58b2735d7ff14fd297dfeca52ec655fb6b7de3",
    "swa_moe.group":
        "750ef11490a7e723a98fd2925bbbfbb650e7f7d0de1e02a23b0b828fe2fa2dfb",
    "swa_moe_early_route.step":
        "84a376c4e35cc2e9c9186f208f4841587b3f5ca62f900d0b185c0f3df9a5560b",
    "swa_moe_early_route.prefill":
        "18ef9d952af93b08234ba9e7cd2e72001b31f3a2c0828a9e0fe0edef1b90dae1",
    "swa_moe_early_route.group":
        "9ef30123ed09dad6e656fed69efb1453f34a63ec9503ec95b283f27f0001e81d"}


@pytest.fixture(scope="module")
def tiny_engine():
    """A family's tiny engine, built once for its three lowerings."""
    built = {}

    def engine(family):
        if family not in built:
            cfg, params = FAMILIES[family]()
            built[family] = (params, ServingEngine(
                cfg, params, slots=8, page_size=4, max_len=32,
                dtype=jnp.float32))
        return built[family]

    return engine


@pytest.mark.parametrize("case", list(_OTHER_BLOCKS_LOWERED))
def test_the_state_s_own_type_left_the_other_blocks_programs_as_they_were(
        monkeypatch, tiny_engine, case):
    import hashlib

    from horovod_tpu.ops import pallas
    from serving_families import lowered_for_tpu
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(pallas, "interpret_mode", lambda: False)
    family, what = case.rsplit(".", 1)
    params, eng = tiny_engine(family)

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    if what == "step":
        c = eng.cache
        args = [eng._decode_params, c.k, c.v, jnp.zeros((8,), jnp.int32),
                c.lengths_device(), c.table_device(), jnp.zeros((8,), bool)]
        if c.window_table is not None:
            args.append(c.window_table_device())
        args += [*c.carried, *eng._step_state, eng._told]
        text = lowered_for_tpu(eng.step._fn, *shapes(args), kernels=False)
    else:
        fn, rows = ((eng._prefill, (1, 10)) if what == "prefill"
                    else (eng._prefill_group, (4, 6)))
        text = lowered_for_tpu(
            lambda p, t: fn(p, t, None, None), shapes(params),
            jax.ShapeDtypeStruct(rows, jnp.int32), kernels=False)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _OTHER_BLOCKS_LOWERED[case]
