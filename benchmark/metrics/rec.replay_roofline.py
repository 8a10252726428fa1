"""Log + replay + model, record store: the least bytes the profiled
update rounds must move (`roofline_records.round_bytes`: log entries
read, each merged (key, field) row read once and written to every
replica), at the chip's peak HBM bandwidth, over the device time of the
round program (`jit_log_exec:*` ops). Entries and rows are the
program's counters over the profiled sub-window
(`nr.rec.window_entries`, `nr.rec.window_rows`). None where the program
does not count them, or the trace holds no round."""

from benchmark.roofline import roofline_pct
from benchmark.roofline_records import program_s, round_bytes

PROGRAM = "jit_log_exec:"


def read(ctx):
    tr, reg = ctx.get("trace"), ctx.get("reg_traced", {})
    entries, rows = reg.get("nr.rec.window_entries"), reg.get("nr.rec.window_rows")
    if tr is None or not entries or rows is None:
        return None
    return roofline_pct(round_bytes(entries, rows, ctx["work_traced"]["replicas"]),
                        program_s(tr, PROGRAM), ctx["peaks"]["hbm_bytes_per_s"])
