"""Plain reference of the replicated record store: numpy, sequential semantics.

A copy of the semantics of `sim/oracle.RecordsOracle` (the program's own
oracle), kept with the benchmark so that it shares no code with the
system under test. YCSB's records: `fieldcount=10` fields of 25 int32
words (100 B), keyed by the record number, which maps to a record by
`k % n_records`.

- UPDATE k f w[25]: field `f % 10` of the record takes the 25 words;
  response 0.
- READ k: the record's 250 words, field after field.
- A window of updates in log order: the last update to each field wins.

The store starts as YCSB's load phase leaves it: every record present,
every word hashed from the seed and the word's place, `prefill_words`;
the reference computes it itself and takes nothing the program made.
(Records are never absent here, so the program's absent marker does not
arise.) The store is sparse: a field reads the prefill until an update
writes it, and the written fields are kept apart, so a store of 2^21
records (2.1 GB dense) costs only what the run wrote.
"""

from __future__ import annotations

import numpy as np

from benchmark.common import fmix32_np, seed32

N_FIELDS = 10
FIELD_WORDS = 25
RECORD_WORDS = N_FIELDS * FIELD_WORDS


def prefill_words(keys: np.ndarray, seed: int) -> np.ndarray:
    """int32[len(keys), 10, 25]: the seeded words of each record; word w
    of field f of record k is hashed from `k * 250 + f * 25 + w`."""
    k = np.asarray(keys, np.int64).reshape(-1, 1)
    idx = (k * RECORD_WORDS + np.arange(RECORD_WORDS, dtype=np.int64))
    x = (idx.astype(np.uint32) * np.uint32(0x9E3779B1)
         + np.uint32(seed32(seed)))
    words = (fmix32_np(x) >> np.uint32(1)).astype(np.int32)
    return words.reshape(-1, N_FIELDS, FIELD_WORDS)


class RecordsReference:
    """One copy of the store: every replica must equal it."""

    def __init__(self, n_records: int, seed: int):
        self.n = int(n_records)
        self.seed = seed
        self.written: dict[tuple[int, int], np.ndarray] = {}

    def _base(self, k: int) -> np.ndarray:
        return prefill_words([k], self.seed)[0]

    def update(self, k: int, f: int, words) -> int:
        slot = int(k) % self.n
        self.written[(slot, int(f) % N_FIELDS)] = np.array(
            words, np.int32).reshape(FIELD_WORDS)
        return 0

    def update_window(self, ops) -> list[int]:
        """Apply `(k, f, words)` updates in log order."""
        return [self.update(k, f, w) for k, f, w in ops]

    def field(self, k: int, f: int) -> np.ndarray:
        slot, f = int(k) % self.n, int(f) % N_FIELDS
        got = self.written.get((slot, f))
        return got.copy() if got is not None else self._base(slot)[f]

    def read(self, k: int) -> np.ndarray:
        slot = int(k) % self.n
        rec = self._base(slot)
        for f in range(N_FIELDS):
            got = self.written.get((slot, f))
            if got is not None:
                rec[f] = got
        return rec.reshape(RECORD_WORDS)

    def dense(self) -> np.ndarray:
        """int32[n, 10, 25]: the whole store, for small n."""
        fields = prefill_words(np.arange(self.n), self.seed)
        for (slot, f), words in self.written.items():
            fields[slot, f] = words
        return fields
