"""The reader of `read_ops_per_batch` on synthetic registry deltas: the
mean reads a batched read program answered over the window, and nothing
where the program has no `nr.read.batch_size` (the parent's) or answered
no read through it. Passes on the CPU."""

import os

import pytest

from benchmark.common import load_module
from benchmark.tests.tiny import ROOT

READER = load_module(os.path.join(ROOT, "benchmark", "metrics", "read_ops_per_batch.py"),
                     "reader_read_ops_per_batch")


def test_mean_reads_per_batch_over_the_window():
    ctx = {"reg": {"nr.read.batch_size": {"count": 40, "sum": 1900.0},
                   "nr.read.hold_s": {"count": 40, "sum": 0.1}}}
    assert READER.read(ctx) == pytest.approx(47.5)


@pytest.mark.parametrize("ctx", [
    {},
    {"reg": {}},
    {"reg": {"nr.read.hold_s": {"count": 200, "sum": 0.4},
             "nr.lock.wait_s": {"count": 9, "sum": 1.0}}},
    {"reg": {"nr.read.batch_size": {"count": 0, "sum": 0.0}}},
], ids=["empty", "no_registry", "parent", "no_reads"])
def test_nothing_to_read_reads_nothing(ctx):
    assert READER.read(ctx) is None
