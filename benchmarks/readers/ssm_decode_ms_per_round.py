"""Device time of the state-space decode update a traced decode round:
the ``hvd_ssm_decode`` calls inside the programs of the traced window's
whole rounds (``lib/rounds.py:whole``) over their count."""

from benchmarks.readers import ssm_decode_roofline


def read(ctx):
    found, _, n, ns = ssm_decode_roofline.state_calls(ctx)
    if not found or not n:
        return None
    return ns / len(found) / 1e6
