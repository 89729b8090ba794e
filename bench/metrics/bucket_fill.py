"""Queries per micro-batch: answered queries over the server's flushes in
the window (``AsyncPGMServer.stats()["flushes"]``, every trigger)."""


def read(ctx):
    flushes = ctx.counters.get("flushes", 0)
    return ctx.counters["answered"] / flushes if flushes else None
