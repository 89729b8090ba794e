"""Share of the traced window of a learning cell in which no operation ran
on the chip (1 - union of op intervals / window), averaged over chips."""

from bench import trace


def read(ctx):
    return trace.idle_pct(ctx.trace)
