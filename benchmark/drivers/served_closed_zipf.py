"""`served_closed` with zipf keys, its key tables built before the window.

The clients draw keys through `common.draw_keys`, whose zipf tables
(`common._zipf_cdf`, one inverse CDF per keyspace, 400 MB for 50M keys)
are cached per process on first use. Built there, the first use is 192
client threads at once as the window opens, each building its own copy.
This driver builds both tables, the clients' own keys' and the whole
keyspace's, in set-up; the run is `served_closed.Run`'s as it is.
"""

from __future__ import annotations

from benchmark.common import _zipf_cdf
from benchmark.drivers import served_closed


class Run(served_closed.Run):
    def __init__(self, cfg: dict, traffic: dict, seed: int, probe,
                 hooks: dict | None = None):
        if traffic.get("keys") != "zipf":
            raise ValueError("served_closed_zipf draws zipf keys")
        super().__init__(cfg, traffic, seed, probe, hooks)
        theta = float(traffic["zipf_theta"])
        _zipf_cdf(self.n_class, theta)
        _zipf_cdf(self.keyspace, theta)
