"""Closed-loop clients through `ServeFrontend` over `MultiLogReplicated`.

`served_closed.Run` as it is, clients, window and checks, with the CNR
system in its `system` hook: the same seeded hashmap behind `nlogs`
logs, each op routed to log `key % nlogs` (upstream's CNR hashmap,
`benches/chashbench.rs:91-100`). The reference is the same: a key's ops
share a log and a read syncs its mapped log, so every key is a
linearizable register and the single-writer register check, the
read-back from every replica and the whole-table compare hold as they
are. One check more: `logs_off`, the logs whose cursor on any replica
differs from the log's tail after a final sync of every log.
"""

from __future__ import annotations

import numpy as np

from benchmark.common import slot_values_np
from benchmark.drivers import served_closed
from node_replication_tpu.models import HM_PUT


class CnrSystem(served_closed.ServedSystem):
    """The system under test: `ServeFrontend` over `MultiLogReplicated`."""

    def __init__(self, cfg: dict, seed: int):
        from node_replication_tpu.core.cnr import MultiLogReplicated
        from node_replication_tpu.models import hashmap_log_mapper
        from node_replication_tpu.serve import ServeConfig, ServeFrontend

        if cfg.get("wrapper") != "cnr" or cfg.get("log_mapper") != "key % nlogs":
            raise ValueError("served_closed_cnr runs a configuration with "
                             "wrapper 'cnr' and log_mapper 'key % nlogs'")
        self.cfg, self.seed = cfg, seed
        self.n_replicas = int(cfg["n_replicas"])
        self.nr = MultiLogReplicated(served_closed.seeded_dispatch(cfg, seed),
                                     hashmap_log_mapper,
                                     nlogs=int(cfg["nlogs"]),
                                     n_replicas=self.n_replicas,
                                     log_entries=int(cfg["log_entries"]),
                                     gc_slack=int(cfg["gc_slack"]),
                                     exec_window=int(cfg["exec_window"]),
                                     engine=cfg["engine"])
        self.fe = ServeFrontend(self.nr, ServeConfig(**cfg["serve"]))

    def warm(self, batches: list[list[tuple]], reads: list[tuple]) -> None:
        """`ServedSystem.warm`, then one round per power-of-two append
        width up to the batch bound, all its puts on log 0: the append
        program's width is that of the round's largest per-log share,
        which the warm batches' uniform keys may not reach. Each put
        writes its slot's prefill value back (no change of state)."""
        super().warm(batches, reads)
        nlogs = int(self.cfg["nlogs"])
        n_keys = int(self.cfg["n_keys"])
        rng = np.random.default_rng([self.seed, 1 << 22])
        m, bmax = 1, int(self.cfg["serve"]["batch_max_ops"])
        while m <= bmax:
            ks = rng.integers(0, int(self.cfg["keyspace"]) // nlogs, m) * nlogs
            vs = (slot_values_np(ks % n_keys, self.seed)
                  if self.cfg["prefill"] == "seeded" else np.zeros(m, np.int32))
            self.nr.execute_mut_batch([(HM_PUT, int(k), int(v))
                                       for k, v in zip(ks, vs)], rid=0)
            m *= 2

    def logs_off(self) -> int:
        """Logs whose cursor on some replica is not the log's tail after
        every log is synced."""
        import jax

        self.nr.sync()
        lt, tail = jax.device_get((self.nr.ml.ltails, self.nr.ml.tail))
        return int(np.sum((np.asarray(lt) != np.asarray(tail)[:, None]).any(axis=1)))


class Run(served_closed.Run):
    """A served run of the CNR system; see the module's docstring."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, probe,
                 hooks: dict | None = None):
        hooks = {"system": CnrSystem, **(hooks or {})}
        super().__init__(cfg, traffic, seed, probe, hooks)

    def work(self, t0: float, t1: float, win: dict) -> dict:
        """`served_closed`'s counts, and the replica count for the
        replay's roofline."""
        return {**super().work(t0, t1, win), "replicas": self.R}

    def check(self) -> dict:
        checks = super().check()
        checks["logs_off"] = (self.sys.logs_off(), 0)
        return checks
