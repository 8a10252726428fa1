#!/usr/bin/env python3
"""The control of the YCSB cells: the plain reference put in the program's
place, with replication broken. The correctness check must call it not
correct.

Every replica starts from the load phase, as the program's do, but each
update reaches only the replica it was issued through. A read on another
replica then misses updates acknowledged before it was sent, and the
replicas differ after the window.

    python3 benchmark/control_ycsb.py --workload <cell> --seed <n> --seconds <s>

runs a cell with the control on the chip at the cell's own size and
prints the run's result line (its `checks` are what the limits were set
from): it is `control.py`'s command, with this control for the YCSB
driver. The control's stores are numpy on the host. The benchmark's own
runs never run it.
"""

from __future__ import annotations

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark.references.records import (  # noqa: E402
    FIELD_WORDS,
    RecordsReference,
    prefill_words,
)


class ControlRecords:
    """A served system made of one reference store per replica, with no
    replication between them."""

    def __init__(self, cfg: dict, seed: int):
        self.n_replicas = int(cfg["n_replicas"])
        self.seed = seed
        self.reps = [RecordsReference(int(cfg["n_records"]), seed)
                     for _ in range(self.n_replicas)]

    def warm(self, batches, reads) -> None:
        """The warm-up writes fields' prefills back: nothing to do on the
        host's stores."""

    def update(self, k, f, words, rid, timeout):
        return self.reps[rid].update(k, f, words)

    def read(self, k, rid):
        return self.reps[rid].read(k)

    def table_counts(self):
        fields = set().union(*(r.written for r in self.reps))
        first = self.reps[0]
        apart = sum(int(np.sum(r.field(k, f) != first.field(k, f)))
                    for r in self.reps[1:] for k, f in fields)
        base = {(k, f): prefill_words([k], self.seed)[0, f] for k, f in fields}
        off = [sum(int(np.sum(w != base[kf])) for kf, w in r.written.items())
               for r in self.reps]
        return np.array(off), apart

    def fields(self, ks, fs):
        want = prefill_words(ks, self.seed)[np.arange(len(ks)), fs]
        got = np.array([[r.field(k, f) for k, f in zip(ks, fs)] for r in self.reps],
                       np.int32).reshape(self.n_replicas, len(ks), FIELD_WORDS)
        return got, np.sum(got != want[None], axis=(1, 2))

    def close(self) -> None:
        pass


HOOKS = {"ycsb_closed": {"system": lambda cfg, seed: ControlRecords(cfg, seed)}}


def hooks_for(traffic: dict) -> dict:
    return HOOKS[traffic["driver"]]


def main(argv=None) -> int:
    from benchmark import control

    control.HOOKS.update(HOOKS)
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
