"""One clock: the spans the program records are kept (name, start, end,
id, parent, attributes) and lie in the profiler's trace; the serve loop
and a decode round are spans with children; a request carries a
timestamp a token; kernels and exchange stages have names in the HLO.
"""

import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu as hv
from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
from horovod_tpu.serving import Request, ServingEngine
from horovod_tpu.timeline import spans
from serving_families import FAMILIES

CFG = LLAMA_SERVE
ROUND_CHILDREN = {"decode.reserve", "decode.args", "decode.dispatch",
                  "decode.sample_fetch", "decode.bookkeep"}


# -- the record ring --------------------------------------------------------

def _nested(rec):
    with rec.span(spans.PHASE, name="outer", round=3):
        with rec.span("dispatch", name="inner", leg="serving_decode"):
            pass
        with rec.span(spans.PHASE, name="sibling"):
            pass
    by = {r.name: r for r in rec.records()}
    assert by["inner"].parent == by["outer"].id
    assert by["sibling"].parent == by["outer"].id
    assert by["outer"].parent is None
    assert by["outer"].attrs == {"round": 3}
    assert by["inner"].attrs == {"leg": "serving_decode"}
    # Filed as it closes: children before their parent.
    assert [r.name for r in rec.records()] == ["inner", "sibling", "outer"]
    assert by["outer"].start_ns <= by["inner"].start_ns \
        <= by["inner"].end_ns <= by["sibling"].start_ns \
        <= by["outer"].end_ns


def _threads(rec):
    inside = threading.Event()
    done = threading.Event()

    def other():
        with rec.span(spans.PHASE, name="other"):
            inside.set()
            assert done.wait(10)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(10)
    with rec.span(spans.PHASE, name="mine"):
        pass
    done.set()
    t.join(10)
    assert not t.is_alive()
    by = {r.name: r for r in rec.records()}
    assert by["mine"].parent is None and by["other"].parent is None


def _bounded(rec):
    for i in range(spans.RECORD_RING + 10):
        with rec.span(spans.PHASE, name="s", i=i):
            pass
    got = rec.records()
    assert len(got) == spans.RECORD_RING
    assert got[-1].attrs["i"] == spans.RECORD_RING + 9
    assert got[0].attrs["i"] == 10
    ids = [r.id for r in got]
    assert len(set(ids)) == len(ids)


def _filed(rec):
    assert rec.file("request", under="serve", rid=0).parent is None
    with rec.span(spans.PHASE, name="serve"):
        with rec.span(spans.PHASE, name="decode.round"):
            under = rec.file("request", under="serve", rid=1,
                             token_times=[0.1, 0.2])
            innermost = rec.file("note")
            nowhere = rec.file("request", under="absent")
    by = {r.name: r for r in rec.records() if r.start_ns != r.end_ns}
    assert under.parent == by["serve"].id
    assert innermost.parent == by["decode.round"].id
    assert nowhere.parent is None
    assert under.start_ns == under.end_ns
    assert under.attrs == {"rid": 1, "token_times": [0.1, 0.2]}


def _filters(rec):
    with rec.span(spans.PHASE, name="a"):
        pass
    with rec.span(spans.PHASE, name="b"):
        pass
    b = rec.records(name="b")
    assert [r.name for r in b] == ["b"]
    assert [r.name for r in rec.records(since_ns=b[0].start_ns)] == ["b"]
    rec.reset()
    assert rec.records() == []


def _sums_unchanged(rec):
    """The per-step sums take the kinds they took; a phase adds none."""
    rec.set_step(4)
    with rec.span(spans.PHASE, name="decode.round"):
        with rec.span("dispatch", name="decode.dispatch",
                      leg="serving_decode"):
            pass
    summary = rec.step_boundary(4, 1.0)
    assert set(summary["spans"]) == {"dispatch"}
    assert summary["legs"]["serving_decode"]["count"] == 1


def _dur(r):
    return r.end_ns - r.start_ns


def _self_time(rec):
    """A span's self time is its duration less its children's; the self
    times of a closed tree add up to the root's duration, to the
    nanosecond, and a name's spans are summed."""
    with rec.phase("outer") as outer:
        with rec.phase("inner"):
            with rec.phase("leaf"):
                pass
        assert outer.child_ns == _dur(rec.records(name="inner")[0])
        with rec.phase("inner"):
            pass
        assert outer.id == rec.records(name="inner")[0].parent
    by = {}
    for r in rec.records():
        by.setdefault(r.name, []).append(r)
    totals = rec.totals()
    assert outer.start_ns == by["outer"][0].start_ns
    assert totals["inner"][:2] == (2, sum(map(_dur, by["inner"])))
    assert totals["leaf"] == (1, _dur(by["leaf"][0]), _dur(by["leaf"][0]))
    assert totals["inner"][2] == totals["inner"][1] - totals["leaf"][1]
    assert totals["outer"] == (
        1, _dur(by["outer"][0]),
        _dur(by["outer"][0]) - totals["inner"][1])
    assert sum(t[2] for t in totals.values()) == _dur(by["outer"][0])
    # A copy: the caller's edits stay the caller's.
    totals["outer"] = None
    assert rec.totals()["outer"] is not None


def _self_time_by_thread(rec):
    """A span's children are those of its own thread: one that another
    thread closes meanwhile takes nothing off it."""
    inside = threading.Event()
    done = threading.Event()

    def other():
        with rec.phase("other"):
            inside.set()
            assert done.wait(10)

    with rec.phase("mine") as mine:
        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with rec.phase("child"):
            pass
        done.set()
        t.join(10)
        assert mine.child_ns == _dur(rec.records(name="child")[0])
    totals = rec.totals()
    assert totals["other"][1] == totals["other"][2] > 0
    assert totals["mine"][2] == totals["mine"][1] - totals["child"][1]


def _self_time_through_an_exception(rec):
    """A span that an exception leaves is closed and counted, and comes
    off its parent like any other."""
    with rec.phase("outer"):
        with pytest.raises(KeyError):
            with rec.phase("failing"):
                with rec.phase("deep"):
                    raise KeyError("x")
        with rec.phase("after"):
            pass
    by = {r.name: r for r in rec.records()}
    assert by["after"].parent == by["outer"].id
    totals = rec.totals()
    assert {n: t[0] for n, t in totals.items()} == {
        "outer": 1, "failing": 1, "deep": 1, "after": 1}
    assert totals["outer"][2] == _dur(by["outer"]) - _dur(by["failing"]) \
        - _dur(by["after"])
    assert sum(t[2] for t in totals.values()) == _dur(by["outer"])


def _filed_and_dropped(rec):
    """What the ring cannot say once it has wrapped: how many records
    were filed, how many of them it has pushed out; the totals keep
    counting.  A point record is filed and has no time to total."""
    assert (rec.filed, rec.dropped) == (0, 0)
    rec.file("note", rid=1)
    for i in range(spans.RECORD_RING):
        with rec.phase("s", i=i):
            pass
    assert (rec.filed, rec.dropped) == (spans.RECORD_RING + 1, 1)
    assert len(rec.records()) == spans.RECORD_RING
    assert rec.records()[0].attrs == {"i": 0}        # the note went
    assert set(rec.totals()) == {"s"}
    assert rec.totals()["s"][0] == spans.RECORD_RING
    rec.reset()
    assert (rec.filed, rec.dropped, rec.totals()) == (0, 0, {})


@pytest.mark.parametrize("case", [_nested, _threads, _bounded, _filed,
                                  _filters, _sums_unchanged, _self_time,
                                  _self_time_by_thread,
                                  _self_time_through_an_exception,
                                  _filed_and_dropped],
                         ids=lambda f: f.__name__.strip("_"))
def test_span_records(case):
    case(spans.SpanRecorder())


def test_spans_lie_in_the_profilers_trace(tmp_path):
    """While a trace is on, a span is a host event ``hvd.<name>`` with
    its attributes as the event's stats, nested as the records are."""
    from jax.profiler import ProfileData
    rec = spans.SpanRecorder()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with rec.span(spans.PHASE, name="decode.round", round=7, slots=2,
                      live_tokens=11):
            with rec.span("dispatch", name="decode.dispatch",
                          leg="serving_decode"):
                jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hvd."):
                    events[e.name] = (e.start_ns, e.start_ns + e.duration_ns,
                                      dict(e.stats))
    assert set(events) == {"hvd.decode.round", "hvd.decode.dispatch"}
    r0, r1, stats = events["hvd.decode.round"]
    d0, d1, dstats = events["hvd.decode.dispatch"]
    assert stats == {"round": 7, "slots": 2, "live_tokens": 11}
    assert dstats == {"leg": "serving_decode"}
    assert r0 <= d0 <= d1 <= r1
    # The ring's interval and the trace's are one interval.
    kept = rec.records(name="decode.round")[0]
    assert abs((kept.end_ns - kept.start_ns) - (r1 - r0)) < 2e6


# -- the serving engine -----------------------------------------------------

def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1], dtype=object).reshape(1),
                ("tp",))


@pytest.fixture(scope="module")
def params():
    return LlamaLM(CFG, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _requests(lens, outs, arrivals=None, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(
                        0, CFG.vocab_size, size=n).astype(np.int32),
                    max_new_tokens=o,
                    arrival_s=0.0 if arrivals is None else arrivals[i])
            for i, (n, o) in enumerate(zip(lens, outs))]


@pytest.mark.parametrize("spec_decode", [False, True],
                         ids=["plain", "speculative"])
def test_serve_leaves_a_tree_of_spans(params, spec_decode):
    eng = ServingEngine(CFG, params, mesh=_mesh1(), slots=3, page_size=8,
                        max_len=64, spec_decode=spec_decode, spec_k=2)
    # What the round's attention reads, counted from outside before each
    # round as the benchmark's wrapper counts it.
    counted = []
    round_fn = "spec_round" if spec_decode else "decode_once"
    inner = getattr(eng, round_fn)

    def counting(st, now):
        counted.append(sum(int(eng.cache.lengths[s]) + 1
                           for s in eng._decode_slots()))
        return inner(st, now)

    setattr(eng, round_fn, counting)
    rec = spans.recorder()
    rec.reset()
    reqs = _requests([4, 9, 6, 5], [5, 3, 6, 1])
    report = eng.serve(reqs)
    assert report.completed == 4

    serve = rec.records(name="serve")
    assert len(serve) == 1
    serve = serve[0]
    records = rec.records(since_ns=serve.start_ns)
    by_id = {r.id: r for r in records}

    def root_of(r):
        while r.parent is not None:
            r = by_id[r.parent]
        return r

    assert all(root_of(r) is serve for r in records)
    names = {r.name for r in records}
    assert {"serve.arrivals", "serve.admit", "serve.prefill",
            "prefill.dispatch", "prefill.write_kv", "prefill.hand_over",
            "prefill.sample_fetch", "decode.round"} | ROUND_CHILDREN \
        <= names

    rounds = rec.records(name="decode.round")
    assert len(rounds) == report.decode_steps == len(counted)
    assert [r.attrs["live_tokens"] for r in rounds] == counted
    assert [r.attrs["round"] for r in rounds] == list(range(len(rounds)))
    for rnd in rounds:
        kids = [r for r in records if r.parent == rnd.id]
        # The plain loop runs one round ahead: a round reads the round
        # before it, and one with none in flight has nothing to read yet
        # (the loop reads the last round when it has none to dispatch).
        want = ROUND_CHILDREN if spec_decode or rnd.attrs["ahead"] \
            else ROUND_CHILDREN - {"decode.sample_fetch", "decode.bookkeep"}
        # A first token is left on the chip and read with the round in
        # flight; a round dispatched behind prefills with none in flight
        # reads theirs alone, once it is dispatched.
        alone = [k for k in kids if k.name == "prefill.sample_fetch"]
        assert len(alone) <= (not spec_decode and not rnd.attrs["ahead"])
        assert sorted(k.name for k in kids if k not in alone) \
            == sorted(want)
        if alone:
            dispatch, = (k for k in kids if k.name == "decode.dispatch")
            assert dispatch.end_ns <= alone[0].start_ns
        for k in kids:
            assert rnd.start_ns <= k.start_ns <= k.end_ns <= rnd.end_ns
        assert 1 <= rnd.attrs["slots"] <= 3
    # However the loop ran, every round was dispatched once, read once
    # and booked once, and no second program screens it.
    for name in ("decode.dispatch", "decode.sample_fetch",
                 "decode.bookkeep"):
        assert sum(r.name == name for r in records) == len(rounds)
    assert "decode.finite_fetch" not in names
    if spec_decode:
        # A speculative round reads and books itself.
        for r in records:
            if r.name in ("decode.sample_fetch", "decode.bookkeep"):
                assert r.attrs["round"] == by_id[r.parent].attrs["round"]
    assert report.rounds_ahead == sum(r.attrs["ahead"] for r in rounds)
    assert (report.rounds_ahead > 0) == (not spec_decode)

    prefills = rec.records(name="serve.prefill")
    assert sorted(p.attrs["rid"] for p in prefills) == [0, 1, 2, 3]
    assert {p.attrs["prompt_len"] for p in prefills} == {4, 9, 6, 5}
    # The host waits for no first token under a prefill's own span: the
    # three of the first turn are read together, behind round 0 where
    # the loop runs ahead, before the speculative round else.
    assert all(p.attrs["deferred"] is True for p in prefills)
    waits = [by_id[r.parent] for r in records
             if r.name == "prefill.sample_fetch"]
    assert waits and waits[0] is (serve if spec_decode else rounds[0])
    assert all(w.name != "serve.prefill" for w in waits)

    filed = {r.attrs["rid"]: r for r in rec.records(name="request")}
    assert sorted(filed) == [0, 1, 2, 3]
    for req in reqs:
        a = filed[req.rid].attrs
        assert filed[req.rid].parent == serve.id
        assert len(a["token_times"]) == len(req.tokens) \
            == req.max_new_tokens
        assert a["token_times"] == sorted(a["token_times"])
        assert a["token_times"][0] == a["first_token_s"] \
            == req.first_token_s
        assert a["arrival_s"] <= a["admit_s"] <= a["first_token_s"] \
            <= a["token_times"][-1] <= a["done_s"] == req.done_s
        assert a["prompt_len"] == req.prompt_len
    if spec_decode:
        # The tokens one speculative round emits share its timestamp.
        assert report.accepted_tokens == sum(
            g == 0.0 for r in reqs for g in r.token_gaps)


def test_token_latency_sees_a_prefill_that_stalls_the_batch(params,
                                                            monkeypatch):
    """One long prompt arrives while short requests decode: its prefill
    falls between two rounds of every running request, their gap across
    it is the largest of their gaps, and the token latency's tail shows
    it.  On a clock that only the work moves (a tick a reading, a tick a
    prompt token a prefill, two a round), so that no two durations of a
    loaded host are compared: what is held is WHERE the loop stamps."""
    from horovod_tpu.serving import engine as engine_mod
    eng = ServingEngine(CFG, params, mesh=_mesh1(), slots=4, page_size=8,
                        max_len=64)
    tick = 1e-3

    class WorkClock:
        t = 0.0

        @classmethod
        def monotonic(cls):
            cls.t += tick
            return cls.t

    prefill, step = eng._prefill, eng.step

    def prefilling(p, toks, *rest):
        WorkClock.t += tick * toks.shape[1]
        return prefill(p, toks, *rest)

    def stepping(*args):
        WorkClock.t += 2 * tick
        return step(*args)

    eng._prefill, eng.step = prefilling, stepping
    monkeypatch.setattr(engine_mod, "time", WorkClock)
    reqs = _requests([4, 4, 4, 33], [24, 24, 24, 2],
                     arrivals=[0.0, 0.0, 0.0, 0.01], seed=1)
    report = eng.serve(reqs)
    assert report.completed == 4
    long_req = reqs[3]
    assert long_req.prefill_start_s >= 0.01
    stalled = []
    for r in reqs[:3]:
        # The three were decoding when it came, and went on after it.
        assert r.token_times[0] < long_req.prefill_start_s \
            < long_req.first_token_s < r.token_times[-1]
        across = [g for t0, g in zip(r.token_times, r.token_gaps)
                  if t0 < long_req.first_token_s <= t0 + g]
        assert len(across) == 1, "the prefill fell between two rounds"
        assert across[0] == max(r.token_gaps) > tick * 33
        assert sorted(r.token_gaps)[-2] < tick * 33
        stalled += across
    assert report.token_latency_p99_s >= min(stalled) \
        > tick * 33 > report.token_latency_p50_s


# -- the serve loop's account of itself, on every served family ---------------

@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """One ``serve`` call of a tiny engine of the family, the look-ahead
    on: eight requests over three slots, all there at t = 0.  The first
    three end with round 0, so the loop catches up (every live slot's
    last token is in flight) and the next three are prefilled with no
    round ahead of them; the last two join mid-stream, behind a round
    in flight."""
    cfg, params = FAMILIES[request.param]()
    eng = ServingEngine(cfg, params, slots=3, page_size=8, max_len=32,
                        dtype=jnp.float32)
    rng = np.random.RandomState(11)
    reqs = [Request(rid=i, prompt=rng.randint(0, min(90, cfg.vocab_size),
                                              size=n)
                    .astype(np.int32), max_new_tokens=o, arrival_s=0.0)
            for i, (n, o) in enumerate(zip([5, 9, 12, 4, 7, 6, 10, 8],
                                           [2, 2, 2, 6, 3, 9, 4, 5]))]
    rec = spans.recorder()
    rec.reset()
    report = eng.serve(reqs)
    assert report.completed == len(reqs) and report.rounds_ahead > 0
    serve, = rec.records(name="serve")
    return report, serve, rec.records(since_ns=serve.start_ns), reqs


def _named(records, name):
    return [r for r in records if r.name == name]


def test_a_fetch_and_a_bookkeep_carry_the_round_they_retire(served):
    report, serve, records, _ = served
    by_id = {r.id: r for r in records}
    rounds = _named(records, "decode.round")
    assert [r.attrs["round"] for r in rounds] \
        == list(range(report.decode_steps))
    # A dispatch says no round of its own: it lies under its round's span.
    dispatches = _named(records, "decode.dispatch")
    assert [by_id[d.parent].attrs["round"] for d in dispatches] \
        == list(range(report.decode_steps))
    assert not any("round" in d.attrs for d in dispatches)
    caught_up = 0
    for name in ("decode.sample_fetch", "decode.bookkeep"):
        retired = _named(records, name)
        # Each round is retired once, in the order it was dispatched.
        assert [r.attrs["round"] for r in retired] \
            == list(range(report.decode_steps))
        for r in retired:
            parent = by_id[r.parent]
            if parent.name == "decode.round":
                # Under round n's span the loop reads round n - 1.
                assert r.attrs["round"] == parent.attrs["round"] - 1
                assert parent.attrs["ahead"] == 1
            else:
                # A catch-up, under the root: the last round dispatched.
                assert parent is serve
                assert r.attrs["round"] == max(
                    x.attrs["round"] for x in rounds
                    if x.end_ns <= r.start_ns)
                caught_up += 1
    assert caught_up >= 2
    # The round after a catch-up has none in flight before it.
    after = {r.attrs["round"] + 1
             for r in _named(records, "decode.sample_fetch")
             if by_id[r.parent] is serve}
    assert {r.attrs["round"] for r in rounds if not r.attrs["ahead"]} \
        == ({0} | after) & set(range(report.decode_steps))


def test_a_prefill_says_which_round_it_queued_behind(served):
    report, _, records, reqs = served
    by_id = {r.id: r for r in records}
    dispatched = {by_id[r.parent].attrs["round"]: r
                  for r in _named(records, "decode.dispatch")}
    fetched = {r.attrs["round"]: r
               for r in _named(records, "decode.sample_fetch")}
    prefills = _named(records, "serve.prefill")
    assert len(prefills) == len(reqs)
    for p in prefills:
        before = [n for n, d in dispatched.items()
                  if d.end_ns <= p.start_ns]
        last = max(before, default=-1)
        in_flight = last >= 0 and fetched[last].end_ns > p.start_ns
        assert p.attrs["behind"] == (last if in_flight else -1)
    behind = [p.attrs["behind"] for p in prefills]
    # The first turn, and the turn after the catch-up that read round 0:
    # nothing ahead of them.  The last two queue behind a round.
    assert behind[:6] == [-1] * 6
    assert all(b >= 0 for b in behind[6:])


def test_serve_files_its_account_and_the_report_carries_it(served):
    report, serve, records, reqs = served
    account, = _named(records, "serve.account")
    assert account.parent == serve.id
    assert account.start_ns == account.end_ns <= serve.end_ns
    a = account.attrs
    assert a["rounds"] == report.decode_steps
    assert a["prefills"] == len(reqs)
    kept = [r for r in records if r is not serve and r is not account]
    assert (a["filed"], a["dropped"]) == (len(kept), 0)
    # Every span of the call, by name, as the ring has them.
    closed = [r for r in kept if r.end_ns > r.start_ns]
    assert set(a["spans"]) == {r.name for r in closed} | {"serve"}
    for name, t in a["spans"].items():
        if name != "serve":
            mine = _named(closed, name)
            assert (t["count"], t["total_ns"]) == (
                len(mine), sum(r.end_ns - r.start_ns for r in mine))
    # The identity: every nanosecond of the call is some span's own.
    own = sum(t["self_ns"] for t in a["spans"].values())
    assert abs(own - a["wall_ns"]) <= 1e-3 * a["wall_ns"]
    assert a["spans"]["serve"]["total_ns"] == a["wall_ns"] \
        <= serve.end_ns - serve.start_ns
    assert report.loop_s == {name: t["self_ns"] / 1e9
                             for name, t in a["spans"].items()}
    assert sum(report.loop_s.values()) == pytest.approx(
        a["wall_ns"] / 1e9, rel=1e-3)
    assert report.as_dict()["loop_s"] == report.loop_s


def test_a_request_says_when_its_prefill_began(served):
    _, _, records, reqs = served
    filed = {r.attrs["rid"]: r.attrs for r in _named(records, "request")}
    assert sorted(filed) == [r.rid for r in reqs]
    for req in reqs:
        a = filed[req.rid]
        assert a["prefill_start_s"] == req.prefill_start_s
        assert a["admit_s"] <= a["prefill_start_s"] <= a["first_token_s"]


@pytest.mark.parametrize("how", ["chunked", "prefix_hit"])
def test_a_last_chunk_and_a_prefix_hit_hand_their_token_over(params, how):
    """A chunked prefill's stall is one ``prefill_chunk`` tree: the pool
    write and the first token's HAND-OVER lie under the last chunk's
    span, so the account has no hole there; a prefix hit's tail is a
    ``serve.prefill`` like any.  Neither waits for its token: no
    ``prefill.sample_fetch`` lies under either span, and every first
    token of the call was left on the chip."""
    kw = dict(prefill_chunk=8) if how == "chunked" else dict(
        prefix_cache=True)
    eng = ServingEngine(CFG, params, mesh=_mesh1(), slots=2, page_size=8,
                        max_len=64, **kw)
    rec = spans.recorder()
    rec.reset()
    reqs = _requests([20, 5], [3, 3])
    if how == "prefix_hit":
        # The second prompt is the first one's first two pages and a
        # tail of its own, and comes when the first is in the tree.
        reqs[1].prompt = np.concatenate([reqs[0].prompt[:16],
                                         reqs[1].prompt])
        reqs[1].arrival_s = 1e3
    report = eng.serve(reqs)
    assert report.completed == 2
    if how == "chunked":
        under = rec.records(name="prefill_chunk")
        assert len(under) == 3
        kids = [r for r in rec.records()
                if r.parent in {c.id for c in under}]
        assert [(k.name, k.parent) for k in kids] == [
            ("prefill.write_kv", under[-1].id),
            ("prefill.hand_over", under[-1].id)]
    else:
        assert report.prefix_hits == 1
        under = rec.records(name="serve.prefill")
        assert [p.attrs["deferred"] for p in under] == [True, True]
        kids = [r for r in rec.records() if r.parent == under[-1].id]
        assert [k.name for k in kids] == [
            "prefill.dispatch", "prefill.write_kv", "prefill.hand_over"]
    ids = {r.id for r in under}
    fetches = rec.records(name="prefill.sample_fetch")
    assert fetches and not any(f.parent in ids for f in fetches)
    account, = rec.records(name="serve.account")
    assert account.attrs["first_tokens_deferred"] \
        == account.attrs["prefills"] == 2
    own = sum(t["self_ns"] for t in account.attrs["spans"].values())
    assert abs(own - account.attrs["wall_ns"]) \
        <= 1e-3 * account.attrs["wall_ns"]
    filed = {r.attrs["rid"]: r.attrs for r in rec.records(name="request")}
    assert filed[0]["admit_s"] <= filed[0]["prefill_start_s"] \
        <= filed[0]["first_token_s"]


# -- names in the HLO -------------------------------------------------------

def _train_step_text():
    hv.shutdown()
    hv.init(devices=jax.devices()[:2])
    try:
        rng = np.random.RandomState(0)
        p0 = {"w": rng.randn(16, 4).astype(np.float32),
              "b": np.zeros((4,), np.float32)}
        opt = hv.DistributedOptimizer(optax.sgd(0.05), compression="fp16")
        step = hv.make_train_step(
            lambda p, x: jnp.mean((x @ p["w"] + p["b"]) ** 2), opt)
        x = hv.shard_batch(np.asarray(rng.randn(4, 16), np.float32))
        return step.lower(hv.replicate(p0), hv.replicate(opt.init(p0)),
                          x).as_text(debug_info=True)
    finally:
        hv.shutdown()


def _decode_step_text():
    from horovod_tpu.serving import (CacheConfig, PagedKVCache,
                                     build_decode_step, cache_sharding)
    from horovod_tpu.serving.decode import no_round
    mesh = _mesh1()
    params = LlamaLM(CFG, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    ccfg = CacheConfig(num_layers=CFG.num_layers,
                       num_kv_heads=CFG.num_kv_heads,
                       head_dim=CFG.head_dim, slots=2, page_size=8,
                       max_len=32)
    cache = PagedKVCache(ccfg, cache_sharding(mesh))
    step = build_decode_step(CFG, mesh, slots=2, page_size=8,
                             pages_per_slot=ccfg.pages_per_slot)
    # The step builds its jitted program at its first call: trace that.
    return jax.jit(step._fn).lower(
        params, cache.k, cache.v, jnp.zeros((2,), jnp.int32),
        cache.lengths_device(), cache.table_device(),
        jnp.ones((2,), bool), no_round(2)).as_text(debug_info=True)


_STAGES = ["hvd_exchange/compress", "hvd_exchange/collective",
           "hvd_exchange/decompress"]


@pytest.mark.parametrize("text_of, env, names, absent", [
    # fp16 around the flat psum is elementwise: the bucket's leaves ride
    # as a group, nothing is packed.
    (_train_step_text, {}, _STAGES,
     ["hvd_exchange/pack", "hvd_exchange/unpack"]),
    # The chunked exchange splits a vector: the same step packs.
    (_train_step_text, {"HOROVOD_EXCHANGE_CHUNK_MB": "1"},
     ["hvd_exchange/pack"] + _STAGES + ["hvd_exchange/unpack"], []),
    # The dense decode step reads attention by the page walk: the
    # split-KV kernel over a gathered view is the verify step's.
    (_decode_step_text, {"HOROVOD_PALLAS_DECODE": "1"},
     ["hvd_cca_decode"], ["hvd_flash_decode"]),
], ids=["train_step", "train_step_packed", "decode_step"])
def test_lowered_text_carries_the_names(monkeypatch, text_of, env, names,
                                        absent):
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    text = text_of()
    for name in names:
        assert name in text, name
    for name in absent:
        assert name not in text, name
