"""Process-wide metrics registry: counters, gauges, fixed-bucket histograms.

The metrics half of the observability layer (the trace/metrics split
production runtimes use; the flight recorder in `obs/recorder.py` is the
trace half). The reference's only numeric observability is the harness's
per-second throughput counters (`benches/mkbench.rs:755-761`); this module
generalizes that into named process-wide instruments the runtime hot paths
update:

- `Counter` — monotonically increasing int (`inc`).
- `Gauge` — last-write-wins float (`set`).
- `Histogram` — fixed exponential buckets with Prometheus-style
  interpolated percentiles (`observe`, `percentile`).

Cost contract: every instrument checks ONE flag (`registry.enabled`)
before touching its lock, so a disabled registry costs one attribute load
+ one branch per call site and allocates nothing — cheap enough to leave
instrumentation compiled into `_exec_round`/`combine` unconditionally.
Instrument handles are created once (at wrapper construction or module
import) and cached; `counter()`/`gauge()`/`histogram()` are get-or-create
and thread-safe.

Enable with `NR_TPU_METRICS=1` or `get_registry().enable()`. `snapshot()`
returns a plain-dict view suitable for JSON (`NodeReplicated.snapshot()`
and `MultiLogReplicated.snapshot()` embed it).

The first enable of the process-wide registry also installs two hooks on
what stalls the whole process (`_install_process_hooks`), each inert
while the registry is off: a `jax.monitoring` listener that counts jaxpr
traces (`nr.compile.traces`) and times backend compiles
(`nr.compile.backend_s`), and a `gc.callbacks` entry that times each
collection (`py.gc.pause_s`) and spans it with a profiler annotation
`nr.gc`, so a pause lines up with the device trace.
"""

from __future__ import annotations

import bisect
import gc
import os
import threading
import time

from node_replication_tpu.analysis.locks import make_lock

# `jax.monitoring` duration events (jax/_src/dispatch.py)
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Default histogram buckets for durations in seconds: 1us .. ~100s,
# roughly x4 per step (14 buckets; small enough to snapshot cheaply).
DURATION_BUCKETS_S = tuple(1e-6 * 4**i for i in range(14))

# Default buckets for counts (batch sizes, rounds): powers of two 1 .. 64Ki.
COUNT_BUCKETS = tuple(float(1 << i) for i in range(17))


class Counter:
    """Monotonic counter. `inc` is one branch when the registry is off."""

    __slots__ = ("name", "_reg", "_lock", "_value")

    def __init__(self, name: str, reg: "MetricsRegistry"):
        self.name = name
        self._reg = reg
        self._lock = make_lock("Counter._lock")
        self._value = 0

    def inc(self, n: int = 1) -> None:
        if not self._reg.enabled:
            return
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        # nrcheck: unshared — single int load, GIL-atomic; approximate
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0

    def _snapshot(self):
        with self._lock:  # scrape path: exact, not approximate
            return self._value


class Gauge:
    """Last-write-wins value. `set` is one branch when the registry is off."""

    __slots__ = ("name", "_reg", "_value")

    def __init__(self, name: str, reg: "MetricsRegistry"):
        self.name = name
        self._reg = reg
        self._value = 0.0

    def set(self, v: float) -> None:
        if not self._reg.enabled:
            return
        self._value = float(v)  # single store: atomic under the GIL

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        self._value = 0.0

    def _snapshot(self):
        return self._value


class Histogram:
    """Fixed-bucket histogram with interpolated percentile estimates.

    `buckets` are ascending upper bounds; observations above the last
    bound land in a +Inf overflow bucket. `percentile(p)` walks the
    cumulative counts and linearly interpolates within the winning bucket
    (the `histogram_quantile` estimator), clamped to the observed
    min/max so small-sample estimates never leave the data's range.
    """

    __slots__ = ("name", "_reg", "_lock", "_bounds", "_counts", "_count",
                 "_sum", "_min", "_max")

    def __init__(self, name: str, reg: "MetricsRegistry",
                 buckets=DURATION_BUCKETS_S):
        self.name = name
        self._reg = reg
        self._lock = make_lock("Histogram._lock")
        self._bounds = tuple(float(b) for b in buckets)
        if list(self._bounds) != sorted(set(self._bounds)):
            raise ValueError(f"{name}: bucket bounds must strictly ascend")
        self._counts = [0] * (len(self._bounds) + 1)  # + overflow
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = float("-inf")

    def observe(self, v: float) -> None:
        if not self._reg.enabled:
            return
        v = float(v)
        i = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        # nrcheck: unshared — single int load, GIL-atomic; approximate
        return self._count

    @property
    def sum(self) -> float:
        # nrcheck: unshared — single float load, GIL-atomic; approximate
        return self._sum

    def percentile(self, p: float) -> float:
        """Estimate the p-quantile (p in [0, 1]) from the bucket counts.

        Reads are deliberately lock-free: percentile() is an
        approximate estimator and may tear against a concurrent
        `observe`; the exact path is `_snapshot`, which holds the
        lock around the same arithmetic (`_snapshot_locked`).
        """
        if not 0.0 <= p <= 1.0:
            raise ValueError(f"percentile {p} outside [0, 1]")
        if self._count == 0:  # nrcheck: unshared — approximate read
            return 0.0
        rank = p * self._count  # nrcheck: unshared — approximate read
        cum = 0
        # nrcheck: unshared — approximate read
        for i, c in enumerate(self._counts):
            if c == 0:
                continue
            if cum + c >= rank:
                lo = self._bounds[i - 1] if i > 0 else 0.0
                hi = (self._bounds[i] if i < len(self._bounds)
                      else self._max)  # nrcheck: unshared — approx read
                frac = (rank - cum) / c
                est = lo + (hi - lo) * max(0.0, min(1.0, frac))
                # nrcheck: unshared — approximate read
                return max(self._min, min(self._max, est))
            cum += c
        return self._max  # nrcheck: unshared — approximate read

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self._bounds) + 1)
            self._count = 0
            self._sum = 0.0
            self._min = float("inf")
            self._max = float("-inf")

    def _snapshot(self):
        # scrape path: hold the lock so count/sum/percentiles agree
        # with each other (the lock-free properties may tear; an
        # exported snapshot must not)
        with self._lock:
            if self._count == 0:
                return {"count": 0, "sum": 0.0}
            return self._snapshot_locked()

    # the lock is held (`_snapshot`); percentile() reads are exact here
    # guarded-by: _lock
    def _snapshot_locked(self):
        return {
            "count": self._count,
            "sum": self._sum,
            "min": self._min,
            "max": self._max,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
        }


class MetricsRegistry:
    """Named instruments behind one process-wide enable flag."""

    def __init__(self, enabled: bool = False, on_first_enable=None):
        # nrcheck: lock-order MetricsRegistry._lock -> Counter._lock — reset() zeroes instruments under the registry lock
        # nrcheck: lock-order MetricsRegistry._lock -> Histogram._lock — reset() zeroes instruments under the registry lock
        self._lock = make_lock("MetricsRegistry._lock")
        self._metrics: dict[str, Counter | Gauge | Histogram] = {}
        self.enabled = False
        #: called once with the registry the first time it is enabled
        self._on_first_enable = on_first_enable
        if enabled:
            self.enable()

    def enable(self) -> None:
        self.enabled = True
        with self._lock:
            hook, self._on_first_enable = self._on_first_enable, None
        if hook is not None:
            hook(self)

    def disable(self) -> None:
        self.enabled = False

    def _get_or_create(self, name: str, factory, kind):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            elif not isinstance(m, kind):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {kind.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get_or_create(
            name, lambda: Counter(name, self), Counter
        )

    def gauge(self, name: str) -> Gauge:
        return self._get_or_create(name, lambda: Gauge(name, self), Gauge)

    def histogram(self, name: str,
                  buckets=DURATION_BUCKETS_S) -> Histogram:
        return self._get_or_create(
            name, lambda: Histogram(name, self, buckets), Histogram
        )

    def remove(self, name: str, instrument=None) -> bool:
        """Unregister an instrument so it stops appearing in
        `snapshot()` (and therefore in exporter scrapes). The retire
        path for per-entity instruments whose entity is gone — e.g. a
        serve replica's `serve.queue_depth.r<rid>` gauge after
        failover retires the replica (`ServeFrontend._fail_replica`);
        without removal every replica ever served haunts the registry
        forever. A still-cached handle keeps working but writes to a
        detached instrument; re-creating the name (`gauge(...)` etc.)
        registers a fresh one.

        Pass `instrument` to make the removal OWNED: the name is only
        dropped when the registered instrument IS that handle. Names
        are get-or-create and process-global, so two owners (two
        frontends serving the same rid in one process) can hold the
        same gauge — an unconditional remove by the first to retire
        would silently detach the survivor's live instrument.
        Returns True when something was removed."""
        with self._lock:
            cur = self._metrics.get(name)
            if cur is None:
                return False
            if instrument is not None and cur is not instrument:
                return False
            del self._metrics[name]
            return True

    def names(self) -> list[str]:
        """Registered instrument names (sorted; includes untouched
        instruments `snapshot()` would skip)."""
        with self._lock:
            return sorted(self._metrics)

    def reset(self) -> None:
        """Zero every instrument (names and handles stay registered, so
        cached call-site handles remain valid)."""
        with self._lock:
            for m in self._metrics.values():
                m._reset()

    def snapshot(self) -> dict:
        """Plain-dict view of every non-empty instrument (JSON-safe)."""
        with self._lock:
            items = list(self._metrics.items())
        out = {}
        for name, m in sorted(items):
            v = m._snapshot()
            if v == 0 or v == 0.0 or (isinstance(v, dict)
                                      and not v.get("count")):
                continue  # keep snapshots readable: skip untouched
            out[name] = v
        return out


class _GcPauses:
    """`gc.callbacks` entry: each collection's pause into `hist`, and an
    `nr.gc` profiler annotation from its start to its stop. Collections
    never overlap (the interpreter runs one at a time), so one pending
    start suffices; a start seen with the registry off is not timed."""

    def __init__(self, reg: "MetricsRegistry", profiler):
        self._reg = reg
        self._hist = reg.histogram("py.gc.pause_s")
        self._profiler = profiler
        self._t0: float | None = None
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if not self._reg.enabled:
                return
            self._ann = self._profiler.TraceAnnotation(
                "nr.gc", generation=info.get("generation", -1))
            self._ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self._hist.observe(time.perf_counter() - self._t0)
            self._ann.__exit__(None, None, None)
            self._t0 = self._ann = None


def _install_process_hooks(reg: "MetricsRegistry") -> None:
    """The compile listener and the GC timer of the module docstring;
    installed once, on the process-wide registry's first enable."""
    import jax.monitoring
    import jax.profiler

    traces = reg.counter("nr.compile.traces")
    backend = reg.histogram("nr.compile.backend_s")

    def on_duration(event: str, duration: float, **_kw) -> None:
        if not reg.enabled:
            return
        if event == JAXPR_TRACE_EVENT:
            traces.inc()
        elif event == BACKEND_COMPILE_EVENT:
            backend.observe(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    gc.callbacks.append(_GcPauses(reg, jax.profiler))


_registry = MetricsRegistry(
    enabled=os.environ.get("NR_TPU_METRICS", "") == "1",
    on_first_enable=_install_process_hooks,
)


def get_registry() -> MetricsRegistry:
    return _registry
