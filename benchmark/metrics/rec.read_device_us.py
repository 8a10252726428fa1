"""Read path, record store: device time of the record-read program
(`jit_read_stacked:*` ops) in the profiled sub-window, over the record
reads the program counted there (`nr.rec.reads`). None where the
program has neither."""

from benchmark.roofline_records import program_s

PROGRAM = "jit_read_stacked:"


def read(ctx):
    tr, reads = ctx.get("trace"), ctx.get("reg_traced", {}).get("nr.rec.reads")
    if tr is None or not reads:
        return None
    s = program_s(tr, PROGRAM)
    return 1e6 * s / reads if s > 0 else None
