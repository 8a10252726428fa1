"""The readers of the program's phases: the interval arithmetic of
`device_idle_locked_pct` on synthetic busy intervals and hold spans, its
trace walk on the recorded v5e trace, and every new reader on an empty
or a partial context. Passes on the CPU."""

import os
import shutil

import pytest

from benchmark.common import load_module
from benchmark.tests.tiny import ROOT
from benchmark.trace_reduce import reduce_trace

METRICS = os.path.join(ROOT, "benchmark", "metrics")
NEW = ["fe.request_queue_ms", "round_lock_wait_ms", "round_hold_ms",
       "read_hold_us", "device_idle_locked_pct", "compiles_in_window",
       "gc_pause_pct"]
TRACE = os.path.join(ROOT, "benchmark", "testdata", "pr21_v5e.xplane.pb")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"), "phase_" + name)


@pytest.fixture(scope="module")
def locked():
    return reader("device_idle_locked_pct")


@pytest.mark.parametrize("busy,holds,want", [
    ([(0, 10), (20, 30)], [(12, 18)], 6),      # a gap under a hold
    ([(0, 10), (20, 30)], [(31, 40)], 0),      # a hold after the last busy
    ([(0, 10), (20, 30), (40, 50)], [(2, 8), (22, 28)], 0),  # holds inside busy
    ([(0, 10), (20, 30)], [(5, 15)], 5),       # a hold straddling a busy edge
    ([(0, 10), (20, 30)], [(5, 25)], 10),      # a hold spanning a whole gap
    ([(0, 10), (20, 30), (40, 50)], [(8, 22), (28, 42)], 20),  # two gaps, two holds
    ([(20, 30), (0, 12), (5, 10)], [(15, 16), (11, 14)], 3),   # unsorted, overlapping busy
    ([(0, 10)], [(0, 100)], 0),                # one busy interval: no gap
    ([], [(0, 10)], 0),
])
def test_idle_under(locked, busy, holds, want):
    assert locked.idle_under(busy, holds) == want


def test_idle_under_is_at_most_the_gaps(locked):
    busy = [(i * 10, i * 10 + 7) for i in range(50)]
    gaps = 49 * 3
    assert locked.idle_under(busy, [(0, 500)]) == gaps
    assert locked.idle_under(busy, [(0, 250), (240, 600)]) == gaps


def test_trace_walk_on_the_recorded_v5e_trace(locked, tmp_path):
    busy, holds = locked.intervals(TRACE)
    assert holds == []  # PR 21's program wrote no hold spans
    red = reduce_trace(TRACE)
    union = sum(e - s for s, e in locked.union_intervals(busy))
    assert union / 1e9 == pytest.approx(red["busy_s"], abs=1e-9)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / "host.xplane.pb")
    ctx = {"trace": red, "window_s": 5.0}
    assert locked.read(ctx, trace_dir=str(tmp_path)) is None  # no span: silent


@pytest.mark.parametrize("name", NEW)
def test_empty_context_reads_nothing(name):
    assert reader(name).read({}) in (None, 0)


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_phases_reads_nothing(name):
    """The parent's registry: the older instruments, none of the new."""
    ctx = {"reg": {"serve.batch.duration_s": {"count": 4, "sum": 2.0},
                   "nr.lock.wait_s": {"count": 9, "sum": 1.0}},
           "win": {"window": (10.0, 61.0)}, "trace": None, "window_s": 12.0}
    assert reader(name).read(ctx) is None


def test_registry_readers_on_a_window():
    ctx = {"reg": {"serve.request.queue_s": {"count": 4, "sum": 2.0},
                   "nr.round.lock_wait_s": {"count": 10, "sum": 30.0},
                   "nr.round.hold_s": {"count": 10, "sum": 11.0},
                   "nr.read.hold_s": {"count": 200, "sum": 1.0},
                   "py.gc.pause_s": {"count": 30, "sum": 0.051}},
           "win": {"window": (10.0, 61.0)}}
    got = {n: reader(n).read(ctx) for n in NEW}
    assert got["fe.request_queue_ms"] == pytest.approx(500.0)
    assert got["round_lock_wait_ms"] == pytest.approx(3000.0)
    assert got["round_hold_ms"] == pytest.approx(1100.0)
    assert got["read_hold_us"] == pytest.approx(5000.0)
    assert got["gc_pause_pct"] == pytest.approx(0.1)
    assert got["compiles_in_window"] == 0  # hooks on, no trace counted
    assert got["device_idle_locked_pct"] is None  # no device trace
    ctx["reg"]["nr.compile.traces"] = 3
    assert reader("compiles_in_window").read(ctx) == 3
