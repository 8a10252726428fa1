"""Host process: the share of the window the interpreter spent in garbage
collection, which stops every thread (`py.gc.pause_s`, timed from a
`gc.callbacks` entry), over the window's length on the host clock. None
where the program does not record it."""


def read(ctx):
    h = ctx.get("reg", {}).get("py.gc.pause_s")
    win = ctx.get("win") or {}
    t0, t1 = win.get("window", (0.0, 0.0))
    if not h or t1 <= t0:
        return None
    return 100.0 * h["sum"] / (t1 - t0)
