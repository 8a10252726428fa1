"""Log + replay + model, CNR: the least bytes the profiled rounds must
move (`roofline_cnr.round_bytes`: written slots on every replica, the
log entries, the responses; the puts answered in the profiled
sub-window), at the chip's peak HBM bandwidth, over the device time of
the round's programs (`jit_cnr_append:*`, `jit_cnr_exec:*`). None where
the trace holds none of them."""

from benchmark.roofline import roofline_pct
from benchmark.roofline_cnr import program_s, round_bytes

ARG_WIDTH = 3  # the hashmap's log entry: key, value, pad


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work_traced", {})
    if tr is None or not work.get("puts"):
        return None
    dev = program_s(tr)
    if not dev:
        return None
    puts = work["puts"]
    return roofline_pct(round_bytes(puts, puts, work["replicas"], ARG_WIDTH),
                        dev, ctx["peaks"]["hbm_bytes_per_s"])
