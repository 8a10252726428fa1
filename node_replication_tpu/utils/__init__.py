"""Utilities: the injectable clock (tracing lives in `obs/`)."""

from node_replication_tpu.utils.clock import (
    Clock,
    RealClock,
    SimClock,
    get_clock,
    set_clock,
)

__all__ = [
    "Clock",
    "RealClock",
    "SimClock",
    "get_clock",
    "set_clock",
]
