"""Read path: the mean wait of a read for the combiner lock, from the call
of `execute` to the lock's acquisition, as the program records it over the
window (`nr.read.lock_wait_s`, 0 for an uncontended read). A read's own
waits alone, in a mix with rounds too. None where the program does not
record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("nr.read.lock_wait_s")
    return 1000.0 * h["sum"] / h["count"] if h and h["count"] else None
