"""Bytes a chip puts on the wire for one step's gradient exchange, as the
program's own step report counts them from its bucket plan and codec."""


def read(ctx):
    wire = ctx.counters.get("wire_bytes_per_step")
    if not wire:
        return None
    return wire / 1e6
