"""The least bytes a CNR hashmap round must move, from its semantics.

`cnr.replay_roofline` divides the time these bytes take at the chip's
peak HBM bandwidth (`peaks.json`) by the device time of the round's two
programs (`jit_cnr_append`, `jit_cnr_exec`). Counted as
`roofline.step_bytes` counts a hashmap step, without its gets (a served
get is its own program):

- every replica's copy of each written slot is set once: R * D * 5 B;
- every put returns one int32 response: P * 4 B;
- every put is appended to its log once, opcode and arguments:
  P * (1 + A) * 4 B.

In the served cells a round never writes a slot twice: a client has one
operation outstanding and is the one writer of its keys, so the D
distinct slots of the rounds are their P puts.
"""

from __future__ import annotations

from benchmark.roofline import SLOT_BYTES, WORD

PROGRAMS = ("jit_cnr_append:", "jit_cnr_exec:")


def round_bytes(puts: int, distinct: int, n_replicas: int,
                arg_width: int) -> int:
    """Least bytes of the rounds that appended and replayed `puts` puts
    to `distinct` distinct slots."""
    return (n_replicas * distinct * SLOT_BYTES
            + puts * WORD
            + puts * (1 + arg_width) * WORD)


def program_s(trace: dict) -> float:
    """Device seconds of the trace's ops of the round's programs."""
    return sum(s for name, s in trace["ops"] if name.startswith(PROGRAMS))
