"""Log + replay + model, CNR: device time of the round's programs
(`jit_cnr_append:*` and `jit_cnr_exec:*` ops) in the profiled
sub-window, over the replay programs run in it (delta of
`cnr.exec.rounds`). None where the trace or the counter has none."""

from benchmark.roofline_cnr import program_s


def read(ctx):
    tr = ctx.get("trace")
    rounds = ctx.get("reg_traced", {}).get("cnr.exec.rounds")
    if tr is None or not rounds:
        return None
    dev = program_s(tr)
    return 1000.0 * dev / rounds if dev else None
