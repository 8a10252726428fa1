"""Combiner round, CNR: logs a round appended to, the mean of the
program's `cnr.round.logs` histogram over the window (delta of sum over
delta of count). Higher means more logs share a round's one append and
one replay program. None where the program has no such histogram."""


def read(ctx):
    h = ctx.get("reg", {}).get("cnr.round.logs")
    return h["sum"] / h["count"] if h and h["count"] else None
