"""Front end: the mean wait of a served request from its submit to the
assembly of its batch, as the program records it over the window
(`serve.request.queue_s`, one observation per request the round
carries). None where the program does not record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("serve.request.queue_s")
    return 1000.0 * h["sum"] / h["count"] if h and h["count"] else None
