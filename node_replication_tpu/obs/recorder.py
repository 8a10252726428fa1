"""Flight recorder: structured JSONL events + fence-accurate timing spans.

The trace half of the observability layer (`obs/metrics.py` is the
metrics half). The reference's observability story is the `log` crate
facade plus spin-loop diagnostics every WARN_THRESHOLD iterations
(`nr/src/lib.rs:80-81`, `nr/src/log.rs:351-358`) and the harness's
per-second throughput counters (`benches/mkbench.rs:755-761`). This module
is the TPU build's equivalent: a process-wide `Tracer` that appends JSONL
events (`{"ts", "mono", "event", ...fields}`) to a file, collects them in
an unbounded buffer, or keeps the last N in a ring (flight-recorder
mode — always-on tracing whose memory cost is bounded, dump on incident).

Every event carries both a wall-clock `ts` (time.time, for correlating
with external logs) and a monotonic `mono` (time.monotonic, immune to
clock steps — what the report CLI uses to order and bucket events).

Spans: `span("name", **fields)` times a section and emits `duration_s`
on exit. JAX dispatches asynchronously, so a naive span around device
work measures the ENQUEUE, not execution (see `utils/fence.py`). Opt
into fence-accurate spans with `NR_TPU_TRACE_FENCE=1`
(or `get_tracer().fence_spans = True`) and tell the span what to fence:

    with span("exec-round") as sp:
        log, states = run_device_work(...)
        sp.fence(log, states)          # fenced at exit when opted in

At exit the span runs `utils/fence.py:fence()` over the registered
pytrees before taking the end timestamp, so `duration_s` covers actual
device execution; the emitted event carries `fenced: true`. Without the
opt-in, `sp.fence` only records that a fence target existed (zero device
cost) and spans measure host wall time as before.

Disabled by default: `emit` is one branch, and `span` yields a shared
no-op singleton without reading the clock or allocating an event record
(asserted by tests/test_obs.py). Enable with `NR_TPU_TRACE=<path>`
(file), `NR_TPU_TRACE=mem` (in-memory; bound it with
`NR_TPU_TRACE_RING=<n>`), or `get_tracer().enable(...)`.

Phases: the metrics registry (`obs/metrics.py`, `NR_TPU_METRICS=1` or
`get_registry().enable()`) is a second switch on the same spans. While
it is on, `span("x", **fields)` also opens
`jax.profiler.TraceAnnotation("nr.x", **fields)`, which a running JAX
profiler records on its host plane, on the device trace's clock, and
observes its duration (`time.perf_counter`) into the histogram `nr.x_s`.
The JSONL event depends on the tracer alone, as before.

Per-record sampling (`NR_TPU_TRACE_SAMPLE=1/N` or `=N`): the fleet
trace plane (`obs/export.py` / `obs/collect.py`) joins events across
processes on a record's log position `pos`, so per-record hop events
(repl-ship, relay-forward, repl-apply, ...) must agree on which
records they narrate. `pos_sampled(pos)` is that agreement: it keeps
a record iff `pos % N == 0` — a pure function of the position, so
every process samples the SAME records and a sampled record's chain
is always complete (never a partial hop sequence), while unsampled
records are dropped wholesale. N=1 (the default) keeps everything.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import time
from typing import Any

from node_replication_tpu.analysis.locks import make_lock
from node_replication_tpu.obs.metrics import get_registry

#: prefix of a span's profiler annotation and registry histogram
PHASE_PREFIX = "nr."


class _Span:
    """Mutable per-span holder the `span` context manager yields: attach
    late fields with `add(...)`, register device pytrees to fence with
    `fence(...)`."""

    __slots__ = ("fields", "fence_args")

    def __init__(self):
        self.fields: dict[str, Any] = {}
        self.fence_args: tuple | None = None

    def add(self, **fields: Any) -> None:
        self.fields.update(fields)

    def fence(self, *trees: Any) -> None:
        self.fence_args = trees


class _NullSpan:
    """Shared do-nothing span for the disabled path (no allocation)."""

    __slots__ = ()

    def add(self, **fields: Any) -> None:
        pass

    def fence(self, *trees: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    def __init__(self):
        self._lock = make_lock("Tracer._lock")
        self._fh = None
        self._buffer: "collections.deque[dict] | list[dict] | None" = None
        #: total events ever emitted to the current sink — with
        #: `len(buffer)` this locates the ring's window in the global
        #: event sequence (`events_since`, the exporter's cursor)
        self._emitted = 0
        self.enabled = False
        # fence-accurate span mode (see module docstring); mutable at
        # runtime so tests and notebooks can flip it per section
        self.fence_spans = (
            os.environ.get("NR_TPU_TRACE_FENCE", "") == "1"
        )

    def enable(self, path: str | None = None,
               ring: int | None = None) -> None:
        """Write events to `path`; with `path=None` buffer in memory —
        unbounded by default, or the last `ring` events when given
        (flight-recorder mode)."""
        with self._lock:
            if self._fh:
                self._fh.close()
            if path:
                self._fh = open(path, "a", buffering=1)
                self._buffer = None
            else:
                self._fh = None
                self._buffer = (
                    collections.deque(maxlen=int(ring))
                    if ring else []
                )
            self._emitted = 0
            self.enabled = True

    def disable(self) -> None:
        with self._lock:
            if self._fh:
                self._fh.close()
            self._fh = None
            self._buffer = None
            self.enabled = False

    def emit(self, event: str, **fields: Any) -> None:
        # racy-but-benign fast path: one word read; worst case one
        # event races an enable/disable
        # nrlint: disable=lock-discipline
        if not self.enabled:  # nrcheck: unshared — racy-but-benign one-word read
            return
        rec = {
            "ts": time.time(),  # nrlint: disable=wall-clock-time — correlation field; `mono` below is the ordering clock
            "mono": time.monotonic(),
            "event": event,
            **fields,
        }
        with self._lock:
            if self._fh is not None:
                self._fh.write(json.dumps(rec) + "\n")
                self._emitted += 1
            elif self._buffer is not None:
                self._buffer.append(rec)
                self._emitted += 1

    @property
    def buffered(self) -> bool:
        """True in memory/ring mode — the modes `events()`/
        `events_since()` (and therefore exporter scrapes) can serve
        from. A file-mode tracer exports nothing: the file is the
        export."""
        with self._lock:
            return self._buffer is not None

    def events(self) -> list[dict]:
        """Buffered events (memory/ring mode only), oldest first."""
        with self._lock:
            return list(self._buffer or [])

    def events_since(self, seq: int) -> tuple[int, list[dict]]:
        """Incremental read of the memory/ring buffer: events the
        caller has not seen yet, given the cursor `seq` a previous
        call returned (0 for "from the start"). Returns
        `(new_cursor, events)`; events evicted by the ring before they
        were read are simply gone (flight-recorder semantics — the
        exporter's scrape interval bounds the loss). File-mode tracers
        return `(cursor, [])`: the file itself is the export."""
        with self._lock:
            buf = list(self._buffer or [])
            total = self._emitted
        missed = total - int(seq)
        if missed <= 0:
            return total, []
        return total, buf[max(0, len(buf) - missed):]


_tracer = Tracer()
_env = os.environ.get("NR_TPU_TRACE")
if _env:
    _ring = os.environ.get("NR_TPU_TRACE_RING")
    if _env in ("mem", ":mem:"):
        _tracer.enable(None, ring=int(_ring) if _ring else None)
    else:
        _tracer.enable(_env)


def get_tracer() -> Tracer:
    return _tracer


def _parse_sample(spec: str | None) -> int:
    """`"1/N"` or `"N"` -> N (keep one record in N); anything
    unparsable or < 1 means no sampling (keep all)."""
    if not spec:
        return 1
    s = spec.strip()
    if "/" in s:
        s = s.split("/", 1)[1]
    try:
        n = int(s)
    except ValueError:
        return 1
    return n if n >= 1 else 1


_sample_n = _parse_sample(os.environ.get("NR_TPU_TRACE_SAMPLE"))


def trace_sample_n() -> int:
    """The configured per-record sampling modulus N (1 = keep all)."""
    return _sample_n


def set_trace_sample(n: int) -> None:
    """Override the sampling modulus at runtime (tests, notebooks)."""
    global _sample_n
    _sample_n = max(1, int(n))


def pos_sampled(pos: int) -> bool:
    """Should per-record trace events narrate the record at `pos`?

    Deterministic in the position alone (`pos % N == 0`), so every
    process in a fleet keeps the SAME records and a sampled record's
    cross-process hop chain is complete — never partial (module
    docstring). Callers still guard on `tracer.enabled` first; this
    only thins the per-record firehose."""
    return _sample_n <= 1 or int(pos) % _sample_n == 0


class _NullContext:
    """Shared context of a span with both switches off."""

    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return _NULL_SPAN

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CONTEXT = _NullContext()
_registry = get_registry()


def span(event: str, **fields: Any):
    """Time a section; emits `<event>` with `duration_s` on exit.

    Yields a `_Span`: call `sp.add(...)` for fields only known inside the
    section and `sp.fence(*pytrees)` to make the span fence device work
    before the end timestamp under `NR_TPU_TRACE_FENCE=1` (see module
    docstring). With the registry on, the section is also the phase
    `nr.<event>`: a profiler annotation carrying `fields`, and a
    histogram of its durations. Tracer and registry both off: returns a
    shared no-op context, reads no clock, allocates no record.
    """
    if not (_tracer.enabled or _registry.enabled):
        return _NULL_CONTEXT
    return _live_span(event, fields)


def _annotation(name: str, fields: dict):
    # deferred: importing this module must not import jax
    import jax.profiler

    return jax.profiler.TraceAnnotation(name, **fields)


@contextlib.contextmanager
def _live_span(event: str, fields: dict):
    t = _tracer
    log = t.enabled
    ann = None
    if _registry.enabled:
        ann = _annotation(PHASE_PREFIX + event, fields)
        ann.__enter__()
    sp = _Span()
    t0 = time.perf_counter()
    try:
        yield sp
    finally:
        fenced = False
        if log and t.fence_spans and sp.fence_args is not None:
            # import at call time: utils.fence pulls in jax
            from node_replication_tpu.utils.fence import fence

            fence(*sp.fence_args)
            fenced = True
        dur = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
            _registry.histogram(PHASE_PREFIX + event + "_s").observe(dur)
        if log:
            t.emit(event, duration_s=dur, fenced=fenced, **fields,
                   **sp.fields)
