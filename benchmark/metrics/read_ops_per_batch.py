"""Read path: reads answered per batched read program, the mean of the
program's `nr.read.batch_size` histogram over the window (delta of sum
over delta of count). None where the program has no such histogram, or
answered no read through it."""


def read(ctx):
    h = ctx.get("reg", {}).get("nr.read.batch_size")
    return h["sum"] / h["count"] if h and h["count"] else None
