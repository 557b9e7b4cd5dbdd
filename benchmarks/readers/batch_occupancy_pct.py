"""Mean share of the decode batch's slots that held a request, over the
scheduler's samples (one a decode round)."""


def read(ctx):
    occ = ctx.counters.get("mean_occupancy")
    return None if occ is None else 100.0 * occ
