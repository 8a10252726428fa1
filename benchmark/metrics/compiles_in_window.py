"""Host process: the jaxpr traces the program counted inside the window
(`nr.compile.traces`, fed by a `jax.monitoring` listener); each is a
compile or a cache load, which should all happen in set-up. The
counter's listener is installed with the GC timer, so a window whose
registry holds `py.gc.pause_s` and no traces read 0; a program without
either hook reads None."""


def read(ctx):
    reg = ctx.get("reg", {})
    if "py.gc.pause_s" not in reg:
        return None
    return reg.get("nr.compile.traces", 0)
