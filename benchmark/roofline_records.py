"""The least bytes the record store's rounds and reads must move, from
their semantics (`models/records.py` at YCSB's width).

`rec.replay_roofline` and `rec.read_roofline` divide the time these
bytes take at the chip's peak HBM bandwidth (`peaks.json`) by the
device time of the programs that move them. The counts depend on the
operations and the shapes only, never on the engine:

- a log entry is an opcode and 27 argument words (key, field, the
  field's 25 words): 112 B, read once when the window is replayed;
- a merged row is one (key, field) pair's last update in a window: its
  100 B are read once from the window and written into every replica;
- a read answers a whole record: 1,000 B.
"""

from __future__ import annotations

WORD = 4
ENTRY_BYTES = (1 + 2 + 25) * WORD  # opcode, key, field, 25 words
ROW_BYTES = 25 * WORD  # one field
READ_BYTES = 10 * ROW_BYTES  # one record


def round_bytes(entries: int, rows: int, n_replicas: int) -> int:
    """Least bytes of the update rounds that replayed `entries` log
    entries and merged `rows` distinct (key, field) rows."""
    return (entries * ENTRY_BYTES
            + rows * ROW_BYTES
            + n_replicas * rows * ROW_BYTES)


def read_bytes(reads: int) -> int:
    return reads * READ_BYTES


def program_s(trace: dict, prefix: str) -> float:
    """Device seconds of the trace's ops of one program (`jit_<name>:`)."""
    return sum(s for name, s in trace["ops"] if name.startswith(prefix))
