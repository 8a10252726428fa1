"""YCSB workload A: closed-loop clients through `ServeFrontend` over
`NodeReplicated`, on the record store (`models/records.py`).

Each of `clients` threads is bound to replica `c % R` and sends its next
operation as soon as the last one is answered. An operation updates one
field (`write_pct` percent; the field uniform over the 10) or reads a
whole record. Keys follow a scrambled zipfian: a zipf rank over the
records (`common.draw_keys`), scrambled by a seeded hash of the rank
modulo the record count, as YCSB's `ScrambledZipfianGenerator` hashes
its rank. YCSB draws that rank over 10^10 items, not over the records,
so its hottest record takes less of the traffic (~3.8% at 0.99, against
~6.2% here over 2^21 records). Any client may update any key. An update writes a tag
`(client, seq)` in its first word and 24 words hashed from the tag, so a
torn or mixed field does not pass for a written one.

Set-up is YCSB's load phase: every word of every record is hashed from
the seed on the device, in one jitted call (`references/records.py`
computes the same words in numpy, independently).

After the window every client's in-flight operation is waited for, and:

- each field of each read is checked as a multi-writer register: its
  origin is an update to that (key, field) sent before the read returned,
  or the prefill; and no update to that (key, field) was sent after the
  origin's acknowledgement and acknowledged before the read was sent;
- the 64 hottest records and 16 untouched ones are read back from every
  replica through the plain read path;
- every replica equals every other, word for word; every word of every
  untouched field equals the prefill (on the device, against words
  regenerated there, and a sample of records against the reference);
  every touched field holds an update that no later-sent update
  supersedes, with that update's hashed words.

Host times stamp a send early and an acknowledgement or an answer late,
so each bound errs wide.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import threading
import time
from collections import Counter

import numpy as np

from benchmark.common import draw_keys, fmix32_np, seed32
from benchmark.drivers import served_closed
from benchmark.references.records import (
    FIELD_WORDS,
    N_FIELDS,
    RECORD_WORDS,
    RecordsReference,
    prefill_words,
)
from node_replication_tpu.models import REC_READ, REC_UPDATE

CHUNK = 256  # operations a client draws at a time
SEQ_BITS = 23  # tag = client << SEQ_BITS | seq
SAMPLE_RECORDS = 1024  # untouched records compared with the reference
CHECK_ROWS = 1 << 16  # at most, rows of every replica compared per device call


def scramble(ranks: np.ndarray, seed: int, n: int) -> np.ndarray:
    """A zipf rank's record: a seeded hash of the rank, modulo `n`."""
    x = (np.asarray(ranks).astype(np.uint32) * np.uint32(0x9E3779B1)
         ^ np.uint32(seed32(seed) ^ 0x5BD1E995))
    return (fmix32_np(x) % np.uint32(n)).astype(np.int64)


def update_words(tags: np.ndarray, seed: int) -> np.ndarray:
    """int32[len(tags), 25]: word 0 is the tag, words 1..24 hashed from
    the tag and the seed."""
    t = np.asarray(tags, np.int64).reshape(-1, 1)
    w = np.arange(1, FIELD_WORDS, dtype=np.uint32)
    x = (t.astype(np.uint32) * np.uint32(0x85EBCA6B)
         + w * np.uint32(0x9E3779B1)) ^ np.uint32(seed32(seed + 1))
    rest = (fmix32_np(x) >> np.uint32(1)).astype(np.int32)
    return np.concatenate([t.astype(np.int32), rest], axis=1)


def seeded_records(cfg: dict, seed: int):
    """The record store, its every word prefilled on the device from the
    seed in one jitted call (the hash is `references.records`')."""
    import jax
    import jax.numpy as jnp

    from node_replication_tpu.models import make_records
    from node_replication_tpu.models.records import ROW_WORDS

    n = int(cfg["n_records"])
    d = make_records(n)
    if cfg["prefill"] != "seeded":
        raise ValueError(f"unknown prefill {cfg['prefill']!r}")
    s = np.uint32(seed32(seed))

    @jax.jit
    def prefill(s):
        return {"fields": table_words(n, ROW_WORDS, s),
                "present": jnp.ones((n,), jnp.bool_)}

    return dataclasses.replace(d, make_state=lambda: prefill(s))


def prefill_word(k, c, s):
    """`prefill_words` in jnp: the word at column c (< 250) of record k,
    hashed from `k * 250 + c` (uint32 arrays) and the folded seed s."""
    import jax.numpy as jnp

    x = (k * jnp.uint32(RECORD_WORDS) + c) * jnp.uint32(0x9E3779B1) + s
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 1).astype(jnp.int32)


def table_words(n: int, row_words: int, s):
    """The prefilled table, int32[n, row_words]: 0 in each row's padding."""
    import jax.numpy as jnp
    from jax import lax

    k = lax.broadcasted_iota(jnp.uint32, (n, row_words), 0)
    c = lax.broadcasted_iota(jnp.uint32, (n, row_words), 1)
    return jnp.where(c < RECORD_WORDS, prefill_word(k, c, s), 0)


class RecordSystem:
    """The system under test: `ServeFrontend` over `NodeReplicated`."""

    def __init__(self, cfg: dict, seed: int):
        from node_replication_tpu import NodeReplicated
        from node_replication_tpu.serve import ServeConfig, ServeFrontend

        self.n = int(cfg["n_records"])
        self.seed = seed
        self.n_replicas = int(cfg["n_replicas"])
        self.nr = NodeReplicated(seeded_records(cfg, seed),
                                 n_replicas=self.n_replicas,
                                 engine=cfg["engine"],
                                 exec_window=int(cfg["exec_window"]))
        self.fe = ServeFrontend(self.nr, ServeConfig(**cfg["serve"]))

    def warm(self, batches: list[list[tuple]], reads: list[tuple]) -> None:
        """Compile (or load from the cache) every program the window
        runs: one combiner round per append window size, one read. The
        batches are `served_closed`'s warm puts `(op, k, v)`; each
        becomes an update of field `v % 10` of record k that writes the
        field's prefill back, so the state does not change."""
        for i, ops in enumerate(batches):
            ks = np.array([op[1] for op in ops], np.int64)
            fs = np.array([op[2] for op in ops], np.int64) % N_FIELDS
            words = prefill_words(ks, self.seed)[np.arange(len(ops)), fs]
            self.nr.execute_mut_batch(
                [(REC_UPDATE, int(k), int(f), *w.tolist())
                 for k, f, w in zip(ks, fs, words)],
                rid=i % self.n_replicas)
        for rid, k in reads:
            self.fe.read((REC_READ, k), rid=rid)

    def update(self, k: int, f: int, words: list, rid: int,
               timeout: float) -> int:
        return self.fe.submit((REC_UPDATE, k, f, *words), rid=rid).result(timeout)

    def read(self, k: int, rid: int) -> np.ndarray:
        return self.fe.read((REC_READ, k), rid=rid)

    def table_counts(self) -> tuple[np.ndarray, int]:
        """On the device, over every word and presence flag: per replica,
        the words (padding included) that differ from the prefill
        regenerated there, plus absent records; and the words and flags
        of replicas 1.. that differ from replica 0's. A block of rows at
        a time: over the whole table at once, the comparison would take
        a temporary as large as a replica."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        n = self.n
        s = np.uint32(seed32(self.seed))
        block = math.gcd(n, CHECK_ROWS)  # divides n: each row once

        @jax.jit
        def counts(st, start):
            f = lax.dynamic_slice_in_dim(st["fields"], start, block, axis=1)
            p = lax.dynamic_slice_in_dim(st["present"], start, block, axis=1)
            k = lax.broadcasted_iota(jnp.uint32, f.shape[1:], 0) + start.astype(jnp.uint32)
            c = lax.broadcasted_iota(jnp.uint32, f.shape[1:], 1)
            want = jnp.where(c < RECORD_WORDS, prefill_word(k, c, s), 0)
            off = jnp.sum(f != want[None], axis=(1, 2)) + jnp.sum(~p, axis=1)
            apart = jnp.sum(f[1:] != f[:1]) + jnp.sum(p[1:] != p[:1])
            return off, apart

        outs = [counts(self.nr.states, np.int32(i)) for i in range(0, n, block)]
        return (sum(np.asarray(o) for o, _ in outs),
                int(sum(int(a) for _, a in outs)))

    def fields(self, ks: np.ndarray, fs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Fields (ks, fs) of every replica, int32[R, len(ks), 25], and
        per replica the words among them that differ from the prefill
        regenerated on the device. A replica at a time: a gather over
        the stacked table takes a copy of it in another layout."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        s = np.uint32(seed32(self.seed))

        @jax.jit
        def fields_of(st, r, ks, cols):
            one = jax.vmap(lambda k, c: lax.dynamic_slice(
                st["fields"], (r, k, c), (1, 1, FIELD_WORDS))[0, 0])
            got = one(ks, cols)
            span = cols[:, None] + jnp.arange(FIELD_WORDS, dtype=jnp.int32)
            want = prefill_word(ks[:, None].astype(jnp.uint32),
                                span.astype(jnp.uint32), s)
            return got, jnp.sum(got != want)

        ks, cols = np.asarray(ks, np.int32), np.asarray(fs, np.int32) * FIELD_WORDS
        outs = [fields_of(self.nr.states, np.int32(r), ks, cols)
                for r in range(self.n_replicas)]
        return (np.stack([np.asarray(g) for g, _ in outs]),
                np.array([int(o) for _, o in outs]))

    def close(self) -> None:
        self.fe.close()


class History:
    """Every acknowledged update, indexed for the register checks."""

    def __init__(self, updates: list[tuple], seed: int):
        # updates: (t0 sent, t1 acked, k, f, tag)
        self.by_tag = {u[4]: u for u in updates}
        tags = np.array([u[4] for u in updates], np.int64)
        words = update_words(tags, seed) if len(tags) else np.zeros((0, FIELD_WORDS), np.int32)
        self.words = {int(t): w for t, w in zip(tags, words)}
        per: dict[tuple[int, int], list] = {}
        for u in updates:
            per.setdefault((u[2], u[3]), []).append(u)
        self.kf = {}
        for kf, us in per.items():
            us.sort(key=lambda u: u[1])
            acked = [u[1] for u in us]
            pmax = np.maximum.accumulate([u[0] for u in us]).tolist()
            self.kf[kf] = (acked, pmax)

    def admissible(self, k: int, f: int, v: np.ndarray, base: np.ndarray,
                   t0: float = float("inf"), t1: float = float("inf")) -> bool:
        """Whether field (k, f) may read `v` for a read sent at t0 and
        answered at t1 (both infinite: the state after the window).
        `base` is the field's prefill."""
        h = self.kf.get((k, f))
        if np.array_equal(v, base):
            return h is None or h[0][0] >= t0  # no update acked before t0
        u = self.by_tag.get(int(v[0]))
        if u is None or (u[2], u[3]) != (k, f) or u[0] >= t1:
            return False
        if not np.array_equal(v, self.words[u[4]]):
            return False  # torn or mixed
        i = bisect.bisect_left(h[0], t0)  # updates acked before t0
        return i == 0 or h[1][i - 1] <= u[1]  # none sent after u's ack


class Run(served_closed.Run):
    """One run of a YCSB cell, on `served_closed.Run`'s window loop and
    warm-up: set-up in the constructor, then `window`, then (after the
    harness read the memory peak) `check`. Only the stream of
    operations, a client's call and the check are the cell's own."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, probe,
                 hooks: dict | None = None):
        self.n = int(cfg["n_records"])
        hooks = {"system": RecordSystem, **(hooks or {})}
        super().__init__({**cfg, "n_keys": self.n, "keyspace": self.n},
                         traffic, seed, probe, hooks)

    # ---------------------------------------------------------- the window
    def _ops(self, rng: np.random.Generator):
        """A client's endless stream of (is_update, key, field)."""
        while True:
            kinds = rng.random(CHUNK) * 100 < self.write_pct
            keys = draw_keys(rng, CHUNK, self.n, self.traffic)
            if self.traffic.get("scramble"):
                keys = scramble(keys, self.seed, self.n)
            fs = rng.integers(0, N_FIELDS, CHUNK)
            yield from zip(kinds.tolist(), keys.tolist(), fs.tolist())

    def _client(self, c: int, go: threading.Event) -> None:
        """Records (True, sent, acked, k, f, tag, resp) per update and
        (False, sent, answered, k, words) per read."""
        rng = np.random.default_rng([self.seed, c])
        rid = c % self.R
        out = self.records[c]
        span = self.probe.span
        timeout = float(self.traffic["result_timeout_s"])
        seq = 0
        go.wait()
        t_end = self.t_end
        for is_upd, k, f in self._ops(rng):
            t0 = time.perf_counter()
            if t0 >= t_end:
                return
            try:
                if is_upd:
                    seq += 1
                    tag = c << SEQ_BITS | seq
                    words = update_words([tag], self.seed)[0].tolist()
                    with span("bench.put"):
                        resp = self.sys.update(k, f, words, rid, timeout)
                    out.append((True, t0, time.perf_counter(), k, f, tag, resp))
                else:
                    with span("bench.get"):
                        got = self.sys.read(k, rid)
                    out.append((False, t0, time.perf_counter(), k, got))
            except Exception as e:  # counted as failed; the run is not correct
                self.errors.append(f"client {c}: {type(e).__name__}: {e}")
                return

    def work(self, t0: float, t1: float, win: dict) -> dict:
        """`served_closed`'s counts, and the replica count for the
        record store's roofline."""
        return {**super().work(t0, t1, win), "replicas": self.R}

    # ----------------------------------------------------------- the check
    def check(self) -> dict:
        """Every answer of the window, a read-back from every replica,
        and every word of every replica. Returns {name: (value, limit)}."""
        ups = [r[1:] for rec in self.records for r in rec if r[0]]
        rds = [r[1:] for rec in self.records for r in rec if not r[0]]
        hist = History([u[:5] for u in ups], self.seed)
        rng = np.random.default_rng([self.seed, 1 << 21])
        heat = Counter(u[2] for u in ups)
        hot = [k for k, _ in heat.most_common(int(self.traffic["readback_hot"]))]
        cold = set()
        want_cold = int(self.traffic["readback_untouched"]) + SAMPLE_RECORDS
        while len(cold) < min(want_cold, self.n - len(heat)):
            k = int(rng.integers(0, self.n))
            if k not in heat:
                cold.add(k)
        cold = sorted(cold)
        rb_cold = cold[: int(self.traffic["readback_untouched"])]
        got_rb = {}
        for rid in range(self.R):
            for k in hot + rb_cold:
                try:
                    got_rb[(rid, k)] = self.sys.read(k, rid)
                except Exception as e:
                    self.errors.append(f"readback r{rid} k{k}: {type(e).__name__}: {e}")
        self.sys.close()

        ref = RecordsReference(self.n, self.seed)
        wrong_reads = self._wrong_reads(rds, hist)
        wrong_rb = 0
        for (rid, k), v in got_rb.items():
            base = ref.read(k).reshape(N_FIELDS, FIELD_WORDS)
            v = np.asarray(v).reshape(N_FIELDS, FIELD_WORDS)
            wrong_rb += sum(not hist.admissible(k, f, v[f], base[f])
                            for f in range(N_FIELDS))

        touched = sorted(hist.kf)
        sample = [(k, f) for k in cold for f in range(N_FIELDS)]
        ks = np.array([k for k, _ in touched + sample], np.int64)
        fs = np.array([f for _, f in touched + sample], np.int64)
        off_all, replicas_off = self.sys.table_counts()
        got, off_in = self.sys.fields(ks, fs)
        t = len(touched)
        base = prefill_words(ks, self.seed)[np.arange(len(ks)), fs]
        touched_off = sum(
            not hist.admissible(int(ks[i]), int(fs[i]), got[r, i], base[i])
            for r in range(self.R) for i in range(t))
        untouched_off = (int(np.sum(off_all) - np.sum(off_in))
                         + int(np.sum(got[:, t:] != base[None, t:])))
        return {
            "unanswered": (self.unanswered, 0),
            "errors": (len(self.errors), 0),
            "wrong_update_resps": (int(sum(u[5] != 0 for u in ups)), 0),
            "wrong_reads": (int(wrong_reads), 0),
            "wrong_readbacks": (int(wrong_rb), 0),
            "replicas_off": (replicas_off, 0),
            "untouched_off": (untouched_off, 0),
            "touched_off": (int(touched_off), 0),
        }

    def _wrong_reads(self, rds: list[tuple], hist: History) -> int:
        """Fields of the window's reads with no admissible origin."""
        if not rds:
            return 0
        bases = prefill_words(np.array([r[2] for r in rds], np.int64), self.seed)
        wrong = 0
        for (t0, t1, k, got), base in zip(rds, bases):
            v = np.asarray(got).reshape(N_FIELDS, FIELD_WORDS)
            wrong += sum(not hist.admissible(k, f, v[f], base[f], t0, t1)
                         for f in range(N_FIELDS))
        return wrong
