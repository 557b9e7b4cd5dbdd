"""99th percentile of the gap between a request's consecutive tokens, on
the engine's arrival-faithful clock: ``token_times[i] - token_times[i-1]``
over the ``request`` records the program filed under its newest ``serve``
span (``horovod_tpu.timeline.spans.recorder().records()``), tokens
emitted before the traced sub-window opened only (the profiler's start
stalls the serve loop for seconds).  ``tpot_p95_ms`` is a request's mean,
so a prefill that stalls the batch shows there diluted; here it is one
gap in every running request.

A program that files no such record (one from before PR 24) offers only
its ``horovod_serving_token_latency_seconds`` histogram, which timed one
dispatch and fetch a token: the reader then gives that histogram's 99th
percentile and says so.
"""

from benchmarks.lib import stats


def gaps_ms(requests, before=None):
    """Gaps between consecutive tokens of each request's ``token_times``
    (seconds), both tokens emitted before ``before``, in ms."""
    out = []
    for attrs in requests:
        t = [x for x in attrs["token_times"]
             if before is None or x < before]
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return out


def newest_serve_requests():
    """The attributes of the ``request`` records under the newest
    ``serve`` span, or None where the program keeps no records."""
    from horovod_tpu.timeline import spans
    rec = spans.recorder()
    if not hasattr(rec, "records"):
        return None
    serves = rec.records(name="serve")
    if not serves:
        return []
    return [r.attrs for r in rec.records(name="request")
            if r.parent == serves[-1].id]


def histogram_p99_ms():
    from horovod_tpu.timeline import metrics
    snap = metrics.registry().snapshot().get(
        "horovod_serving_token_latency_seconds")
    q = metrics.histogram_quantile(snap, 0.99) if snap else None
    return None if q is None else q * 1e3


def read(ctx):
    requests = newest_serve_requests()
    if requests is None:
        ctx.log("token gap: the program files no request records (it is "
                "from before PR 24); giving the 99th percentile of its "
                "token-latency histogram, which timed one dispatch")
        return histogram_p99_ms()
    gaps = gaps_ms(requests, ctx.counters.get("trace_started_at"))
    if not gaps:
        return None
    p99 = stats.percentile(gaps, 99)
    ctx.log("token gap: %d gaps of %d requests, median %.4f ms, p99 %.4f "
            "ms, largest %.4f ms" % (len(gaps), len(requests),
                                     stats.median(gaps), p99, max(gaps)))
    return p99
