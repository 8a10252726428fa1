"""Combiner round: the mean wait for the combiner lock of a served round,
from the call of `execute_mut_batch` to the lock's acquisition, as the
program records it over the window (`nr.round.lock_wait_s`, 0 for an
uncontended round). With `round_hold_ms` it makes up `round_ms`. None
where the program does not record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("nr.round.lock_wait_s")
    return 1000.0 * h["sum"] / h["count"] if h and h["count"] else None
