"""``ServingEngine.serve`` runs one decode round ahead: the step samples
and screens on the chip, and the host reads round n while round n + 1 is
queued.  What it serves is, token for token, what a loop serves that
catches up behind every round (the control plane's and the fleet's: the
same ``decode_once``, then ``catch_up``), for each of the five families;
the time stamps are taken when the host has the token.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serving import Request, ServingEngine
from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.decode import no_round, read_told
from horovod_tpu.timeline import metrics, spans
from serving_families import FAMILIES, round_by_round

SLOTS, PAGE, MAX_LEN = 3, 8, 32
CAPPED, POISONED = 2, 4          # the rids of two requests, see _requests


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


@pytest.fixture(autouse=True)
def _no_poison_left_in_the_registry():
    """A poisoned round reaches what a step publishes of itself (the
    looped block's ``loop.exit_mass`` is NaN after one), and the
    registry is the process's: no later file's rendering may find it."""
    yield
    metrics.reset_metrics()


def _engine(family):
    cfg, params = family
    return ServingEngine(cfg, params, slots=SLOTS, page_size=PAGE,
                         max_len=MAX_LEN, dtype=jnp.float32)


def _requests(cfg):
    """Seven requests over three slots, all there at t = 0: four join
    mid-stream as slots come free, they finish at different rounds, one
    is done with its prefill's token, and ``CAPPED`` fills its slot to
    ``max_len`` (see :func:`_lift_count`)."""
    rng = np.random.RandomState(11)
    lens = [5, 9, 12, 4, 7, 6, 10]
    outs = [6, 3, 20, 1, 9, 4, 5]
    assert lens[CAPPED] + outs[CAPPED] == MAX_LEN
    return [Request(rid=i, prompt=rng.randint(
                        0, min(256, cfg.vocab_size), size=n)
                    .astype(np.int32), max_new_tokens=o, arrival_s=0.0)
            for i, (n, o) in enumerate(zip(lens, outs))]


def _lift_count(eng, rid: int = CAPPED):
    """``serve`` turns away a request whose count would pass ``max_len``,
    so the engine's own cap never ends one it let in.  Lift this
    request's count once it is admitted: now the cap does."""
    admit = eng.scheduler.admit

    def lifted(now_s):
        out = admit(now_s)
        for _, req in out:
            if req.rid == rid:
                req.max_new_tokens = 2 * MAX_LEN
        return out

    eng.scheduler.admit = lifted


def _poison_once(eng, rid: int = POISONED, position: int = 9):
    """The round in which request ``rid`` writes ``position`` reads a
    resident row that has gone bad: the logits the step computes, samples
    from and screens are NaN for that slot, once."""
    real, fired = eng.step, []

    def step(params, k, v, tokens, positions, table, active, *rest):
        for slot, req in eng.scheduler.active.items():
            if (req.rid == rid and not fired and np.asarray(active)[slot]
                    and int(np.asarray(positions)[slot]) == position):
                fired.append(slot)
                page = int(np.asarray(table)[slot, 0])
                k = k.at[:, page, 0].set(jnp.nan)
                v = None if v is None else v.at[:, page, 0].set(jnp.nan)
        return real(params, k, v, tokens, positions, table, active, *rest)

    eng.step = step
    return fired


def _reprefills() -> float:
    return metrics.registry().counter(
        "horovod_guard_serving_reprefills_total").value


def _drained(eng, reqs, total_pages):
    assert eng.cache.free_pages == total_pages
    assert not eng.cache.lengths.any()
    assert not eng.scheduler.active and not any(r.in_flight for r in reqs)


def test_one_round_ahead_serves_what_round_by_round_serves(family,
                                                           monkeypatch):
    # The engine's clock on the spans' clock, to compare stamps with
    # spans: ``serve``'s first reading is its zero.
    readings = []

    class SpanClock:
        @staticmethod
        def monotonic():
            readings.append(time.perf_counter_ns())
            return readings[-1] / 1e9

    # -- round by round: the tokens to match --------------------------------
    cfg = family[0]
    eng = _engine(family)
    total_pages = eng.cache.free_pages
    _lift_count(eng)
    fired = _poison_once(eng)
    rec = spans.recorder()
    rec.reset()
    want = _requests(cfg)
    before = _reprefills()
    st = round_by_round(eng, want)
    assert len(st["completed"]) == len(want) and len(fired) == 1
    assert _reprefills() - before == 1
    _drained(eng, want, total_pages)
    assert [r.attrs["ahead"] for r in rec.records(name="decode.round")] \
        == [0] * st["decode_steps"]
    cap = MAX_LEN - want[CAPPED].prompt_len + 1
    assert len(want[CAPPED].tokens) == cap < want[CAPPED].max_new_tokens
    assert [len(r.tokens) for r in want if r.rid != CAPPED] \
        == [r.max_new_tokens for r in want if r.rid != CAPPED]

    # -- one round ahead ------------------------------------------------------
    eng = _engine(family)
    _lift_count(eng)
    fired = _poison_once(eng)
    counted, paged, caught_up, booked = [], [], [], []
    decode_once, catch_up = eng.decode_once, eng.catch_up
    note = eng.scheduler.note_decode_token

    def counting(st, now):
        # As the benchmark's wrapper counts what the round reads.
        counted.append(sum(int(eng.cache.lengths[s]) + 1
                           for s in eng._decode_slots()))
        # Whole pages of one plane that the round's page walk copies: a
        # slot's live tokens', or (pooled rows) its window's exact rows'
        # and those of the pooled rows of the windows before.
        spec, size = eng.spec, eng.page_size
        paged.append(sum(
            -(-n // size) if spec.row_tokens == 1 else
            -(-((n - 1) % spec.window + 1) // size)
            + -(-((n - 1) // spec.window * (spec.window // spec.row_tokens))
                // size)
            for n in (int(eng.cache.lengths[s]) + 1
                      for s in eng._decode_slots())))
        return decode_once(st, now)

    def catching_up(st, now, dropped=()):
        if st["in_flight"] is not None:
            caught_up.append(st["decode_steps"])
        return catch_up(st, now, dropped)

    def noting(req, now_s):
        booked.append((now_s, sum(
            r.name == "decode.sample_fetch" for r in rec.records())))
        return note(req, now_s)

    eng.decode_once, eng.catch_up = counting, catching_up
    eng.scheduler.note_decode_token = noting
    monkeypatch.setattr(engine_mod, "time", SpanClock)
    rec.reset()
    got = _requests(cfg)
    before = _reprefills()
    report = eng.serve(got)
    monkeypatch.undo()
    assert report.completed == len(got) and report.rejected == 0
    assert len(fired) == 1 and _reprefills() - before == 1
    _drained(eng, got, total_pages)

    # Same tokens, same order, same requests finished.
    assert [r.tokens for r in got] == [r.tokens for r in want]

    # Every round but the first, and the one after each catch-up (the
    # quarantine's, and where every live slot's last token was in
    # flight), was dispatched while the round before was in flight.
    rounds = rec.records(name="decode.round")
    assert len(rounds) == report.decode_steps == len(counted)
    behind = {0} | {n for n in caught_up if n < report.decode_steps}
    assert [r.attrs["ahead"] for r in rounds] == [
        int(n not in behind) for n in range(len(rounds))]
    assert report.rounds_ahead == len(rounds) - len(behind)
    assert 1 < len(behind) < len(rounds) / 2
    assert [r.attrs["live_tokens"] for r in rounds] == counted
    assert [r.attrs["pages"] for r in rounds] == paged
    size = eng.page_size
    if eng.spec.row_tokens == 1:
        assert all(p * size >= n > (p - r.attrs["slots"]) * size
                   for r, p, n in zip(rounds, paged, counted))
    assert any(p * size > n for p, n in zip(paged, counted))

    # One program and one fetch a round.
    names = [r.name for r in rec.records()]
    assert names.count("decode.dispatch") == len(rounds)
    assert names.count("decode.sample_fetch") == len(rounds)
    assert names.count("decode.bookkeep") == len(rounds)
    assert "decode.finite_fetch" not in names

    # A token is stamped when the host has it: after the fetch that
    # brought it has returned, before its bookkeeping is over.
    fetches = rec.records(name="decode.sample_fetch")
    books = rec.records(name="decode.bookkeep")
    zero_ns = readings[0]
    assert len(booked) == sum(len(r.tokens) - 1 for r in got)
    for now_s, nth in booked:
        stamp_ns = zero_ns + now_s * 1e9
        assert fetches[nth - 1].end_ns - 1e3 <= stamp_ns \
            <= books[nth - 1].end_ns + 1e3
    stamps = sorted(now_s for now_s, _ in booked)
    assert sorted(t for r in got for t in r.token_times[1:]) == stamps
    for r in got:
        assert r.token_times == sorted(r.token_times)
        assert r.first_token_s == r.token_times[0] <= r.done_s


def test_the_step_samples_and_screens_its_own_logits(family):
    """``told`` is ``[tokens | finite | tells]`` over the logits the step
    returns; a slot given ``-1`` takes its token from ``prev``, and sits
    the round out where ``prev`` screened it as not finite."""
    eng = _engine(family)
    reqs, st = _requests(family[0])[:2], eng.run_state()
    for req in reqs:
        eng.scheduler.submit(req)
    eng.join(st, [(slot, req, jnp.asarray(req.prompt))
                  for slot, req in eng.scheduler.admit(0.0)], lambda: 0.0)
    eng.catch_up(st, lambda: 0.0)
    cache, tells = eng.cache, len(eng.spec.step_tells)

    def run(tokens, prev):
        for slot in (0, 1):
            n = int(cache.lengths[slot])
            cache.reserve(slot, n + 1, writable_from=n)
        # (The step donates its pools and its state: copies.)
        window = () if cache.window_table is None \
            else (cache.window_table_device(),)
        state = tuple(jnp.copy(x)
                      for x in (*cache.carried, *eng._step_state))
        out = eng.step(
            eng._decode_params, jnp.copy(cache.k),
            None if cache.v is None else jnp.copy(cache.v),
            jnp.asarray(tokens, jnp.int32), cache.lengths_device(),
            cache.table_device(), jnp.asarray([True, True, False]),
            *window, *state, prev)
        return np.asarray(out[0]), out[1], read_told(out[-1], SLOTS)

    held = st["last_tokens"].copy()
    logits, _, (sampled, finite, told) = run(held, no_round(SLOTS, tells))
    # (A head of several predictions side by side: the next token's
    # columns lead.)
    assert list(sampled[:2]) == list(np.argmax(
        logits[:2, :eng.config.vocab_size], axis=-1))
    assert finite[:2].all() and told.shape == (tells,)

    # The same tokens, left on the chip.
    prev = np.zeros((2 * SLOTS + tells,), np.int32)
    prev[:SLOTS], prev[SLOTS:2 * SLOTS] = held, 1
    again, pool, (resampled, _, _) = run([-1, -1, 0], jnp.asarray(prev))
    np.testing.assert_array_equal(again[:2], logits[:2])
    assert list(resampled[:2]) == list(sampled[:2])

    # Slot 1's round before was not finite: it writes nothing this round
    # (the host re-prefills it), slot 0 is as it was.
    prev[SLOTS + 1] = 0
    third, pool2, _ = run([-1, -1, 0], jnp.asarray(prev))
    np.testing.assert_array_equal(third[0], logits[0])
    row = int(cache.lengths[1])

    def page_of(row):
        # (Pooled rows: a token's own row lies in the slot's ring.)
        if eng.spec.row_tokens > 1:
            return int(cache.window_table[1, row // PAGE
                                          % cache.window_table.shape[1]])
        return int(cache.page_table[1, row // PAGE])

    page = page_of(row)
    assert np.asarray(pool[:, page, row % PAGE]).any()
    assert not np.asarray(pool2[:, page, row % PAGE]).any()

    # A row gone bad shows in that slot's flag alone (a row the round
    # attends: the first of all, or the newest of the window).
    gone = 0 if eng.spec.row_tokens == 1 else row - 1
    bad = page_of(gone)
    cache.k = cache.k.at[:, bad, gone % PAGE].set(jnp.nan)
    if cache.v is not None:
        cache.v = cache.v.at[:, bad, gone % PAGE].set(jnp.nan)
    logits, _, (_, finite, _) = run(held, no_round(SLOTS, tells))
    assert list(finite[:2]) == [True, False]
    assert np.isfinite(logits[0]).all() and not np.isfinite(logits[1]).all()
