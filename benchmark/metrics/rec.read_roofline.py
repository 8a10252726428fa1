"""Read path, record store: the 1,000 B each record read must move
(`roofline_records.read_bytes`), at the chip's peak HBM bandwidth, over
the device time of the record-read program (`jit_read_stacked:*` ops),
for the reads the program counted in the profiled sub-window
(`nr.rec.reads`). None where the program has neither."""

from benchmark.roofline import roofline_pct
from benchmark.roofline_records import program_s, read_bytes

PROGRAM = "jit_read_stacked:"


def read(ctx):
    tr, reads = ctx.get("trace"), ctx.get("reg_traced", {}).get("nr.rec.reads")
    if tr is None or not reads:
        return None
    return roofline_pct(read_bytes(reads), program_s(tr, PROGRAM),
                        ctx["peaks"]["hbm_bytes_per_s"])
