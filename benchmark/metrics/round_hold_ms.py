"""Combiner round: the mean time a served round holds the combiner lock,
from its acquisition to its release, as the program records it over the
window (`nr.round.hold_s`): append, replay and the responses' readback.
None where the program does not record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("nr.round.hold_s")
    return 1000.0 * h["sum"] / h["count"] if h and h["count"] else None
