"""Device time of the chunk-pooled decode walk a traced decode round: the
``hvd_eva_decode`` calls inside the programs of the traced window's whole
rounds (``lib/rounds.py:whole``) over their count."""

from benchmarks.readers import eva_decode_roofline


def read(ctx):
    found, _, n, ns = eva_decode_roofline.walk_calls(ctx)
    if not found or not n:
        return None
    return ns / len(found) / 1e6
