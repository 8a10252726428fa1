"""Read path: the mean time a get holds the combiner lock, from its
acquisition to its release, as the program records it over the window
(`nr.read.hold_s`): the cursor reads, any read sync and the read itself,
device and host. None where the program does not record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("nr.read.hold_s")
    return 1e6 * h["sum"] / h["count"] if h and h["count"] else None
