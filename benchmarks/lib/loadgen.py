"""The benchmark's one traffic generator for served cells.

A traffic mix is a data file of parameters (``benchmarks/traffic/*.json``);
this module turns one and a seed into a request stream.  It follows
``horovod_tpu/serving/loadgen.py`` (``LoadSpec``/``generate``: seeded, open
loop, discrete length sets with weights, shared prefixes, sessions) with
one deliberate difference: every seed gets THE SAME multiset of
(prompt length, output length) pairs and of arrival gaps, dealt in another
order.  Drawing lengths by weight per request, as the program's generator
does, makes the total work of a run swing by several percent from seed to
seed, and the driver draws new seeds for every check.

Parameters (all from the traffic file, ``*`` required):

* ``prompt_lens``*, ``prompt_weights``, ``output_lens``*,
  ``output_weights``: discrete sets; the pair counts are the products of
  the weights, rounded by largest remainder to ``num_requests``.
* ``arrival``*: ``"at_zero"`` (every request is there at t = 0) or
  ``"poisson"`` (open loop at ``rate_rps``*: the gaps are the
  (i + 0.5)/n quantiles of the exponential distribution, shuffled).
* ``order``: ``"by_seed"`` (default: the seed deals sizes and gaps in its
  own order) or ``"fixed"`` (one order for every seed; the seed still
  makes the token ids).  With every request there at t = 0 the order of
  the queue decides how long the last, half-empty wave lasts: the
  offline mix read 367 and 414 tokens/s on two seeds and within 1% on
  one (PERF.md), so there the order is fixed.
* ``num_requests`` or ``requests_per_second_of_window`` (then
  ``num_requests = round(seconds * that)``).
* ``prefix_share``, ``num_prefixes``, ``prefix_lens``: that share of the
  requests (a fixed count) prepend one of the fixed shared prefixes.
* ``session_share``, ``session_turns``: that share of the requests open
  a session; a later request continues the oldest open session by
  extending its prompt, until it has ``session_turns`` turns.
"""

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class GenRequest:
    rid: int
    prompt: np.ndarray            # int32 [t]
    max_new_tokens: int
    arrival_s: float
    session_id: Optional[int] = None


def apportion(weights: Sequence[float], n: int) -> List[int]:
    """Whole counts summing to ``n`` in proportion to ``weights`` (largest
    remainder; ties go to the earlier entry)."""
    w = np.asarray(weights, np.float64)
    if w.ndim != 1 or not len(w) or np.any(w < 0) or w.sum() <= 0:
        raise ValueError(f"weights must be non-negative with mass: {weights}")
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    rest = n - int(counts.sum())
    order = sorted(range(len(w)), key=lambda i: (-(exact[i] - counts[i]), i))
    for i in order[:rest]:
        counts[i] += 1
    return [int(c) for c in counts]


def _weights(lens, weights, what):
    if not lens or any(int(x) < 1 for x in lens):
        raise ValueError(f"{what}_lens must be positive: {lens}")
    if weights is None:
        return [1.0] * len(lens)
    if len(weights) != len(lens):
        raise ValueError(f"{what}_weights has {len(weights)} entries for "
                         f"{len(lens)} lengths")
    return [float(x) for x in weights]


def num_requests(traffic: dict, seconds: float) -> int:
    if "num_requests" in traffic:
        n = int(traffic["num_requests"])
    else:
        n = int(round(seconds * float(
            traffic["requests_per_second_of_window"])))
    if n < 1:
        raise ValueError(f"the traffic gives {n} requests")
    return n


def seed_rng(seed: int, stream: int = 0) -> np.random.RandomState:
    """A RandomState for any whole-number seed (``--seed`` can pass 2**31,
    and RandomState wants 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.RandomState(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream])


def generate(traffic: dict, seed: int, seconds: float,
             vocab_size: int) -> List[GenRequest]:
    """The request stream of ``traffic`` for ``seed``, sorted by arrival."""
    n = num_requests(traffic, seconds)
    rng = seed_rng(seed)
    plens = [int(x) for x in traffic["prompt_lens"]]
    olens = [int(x) for x in traffic["output_lens"]]
    pw = _weights(plens, traffic.get("prompt_weights"), "prompt")
    ow = _weights(olens, traffic.get("output_weights"), "output")
    pairs = [(p, o) for p in plens for o in olens]
    counts = apportion([a * b for a in pw for b in ow], n)
    sizes = [pair for pair, c in zip(pairs, counts) for _ in range(c)]
    order = traffic.get("order", "by_seed")
    if order not in ("by_seed", "fixed"):
        raise ValueError(f"order must be by_seed or fixed: {order!r}")
    deal = rng if order == "by_seed" else seed_rng(0, stream=3)
    sizes = [sizes[i] for i in deal.permutation(n)]

    arrival = traffic["arrival"]
    if arrival == "at_zero":
        times = np.zeros(n)
    elif arrival == "poisson":
        rate = float(traffic["rate_rps"])
        if rate <= 0:
            raise ValueError(f"rate_rps must be > 0: {rate}")
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        times = np.cumsum(gaps[deal.permutation(n)])
    else:
        raise ValueError(f"arrival must be at_zero or poisson: {arrival!r}")

    share = float(traffic.get("prefix_share", 0.0))
    if not 0.0 <= share <= 1.0:
        raise ValueError(f"prefix_share must be in [0, 1]: {share}")
    prefixes: List[np.ndarray] = []
    shared = np.zeros(n, bool)
    if share > 0:
        plist = [int(x) for x in traffic.get("prefix_lens", (64,))]
        for i in range(int(traffic.get("num_prefixes", 1))):
            prefixes.append(rng.randint(
                0, vocab_size, size=plist[i % len(plist)]).astype(np.int32))
        shared[rng.permutation(n)[:int(round(share * n))]] = True
    turns = int(traffic.get("session_turns", 1))
    sshare = float(traffic.get("session_share", 0.0))
    opens = np.zeros(n, bool)
    if sshare > 0 and turns > 1:
        opens[rng.permutation(n)[:int(round(sshare * n))]] = True
    open_sessions: List[dict] = []
    next_sid = 0

    out: List[GenRequest] = []
    for rid in range(n):
        plen, olen = sizes[rid]
        tail = rng.randint(0, vocab_size, size=plen).astype(np.int32)
        sid = None
        if open_sessions and not opens[rid] and rid % 2:
            cont = open_sessions.pop(0)
            prompt = np.concatenate([cont["ctx"], tail])
            sid = cont["sid"]
            cont["turns"] += 1
            cont["ctx"] = prompt
            if cont["turns"] < turns:
                open_sessions.append(cont)
        else:
            prompt = tail
            if shared[rid]:
                prompt = np.concatenate(
                    [prefixes[int(rng.randint(len(prefixes)))], tail])
            if opens[rid]:
                sid = next_sid
                next_sid += 1
                open_sessions.append({"sid": sid, "ctx": prompt, "turns": 1})
        out.append(GenRequest(rid=rid, prompt=prompt, max_new_tokens=olen,
                              arrival_s=float(times[rid]), session_id=sid))
    return out


def describe(requests: Sequence[GenRequest]) -> dict:
    """The generator's own figures, for the lines before the result."""
    last = max(r.arrival_s for r in requests)
    return {"requests": len(requests),
            "prompt_tokens": int(sum(len(r.prompt) for r in requests)),
            "output_tokens": int(sum(r.max_new_tokens for r in requests)),
            "longest_context": int(max(len(r.prompt) + r.max_new_tokens
                                       for r in requests)),
            "last_arrival_s": float(last),
            "offered_rps": (len(requests) / last if last > 0
                            else math.inf)}
