"""The trace reduction on a trace recorded on a TPU v5e: two one-round
calls of the lab round at N = 2,048 inside ``bench.run.Tracer``, each in a
``bench.call`` span (``bench/tests/data/lab_small.xplane.pb.gz``).
Reading it needs no chip."""
from __future__ import annotations

import os

import pytest

from bench import kernel_cost, trace_reduce
from bench.tests.cells import ROOT

TRACE = os.path.join(ROOT, "bench", "tests", "data",
                     "lab_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_within_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    gaps = sum(s for _, s in reduced["gaps"])
    assert gaps + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)


def test_ops_and_kernels(reduced):
    assert sum(reduced["op_s"].values()) >= 0.99 * reduced["busy_s"]
    sec, n = trace_reduce.op_time(
        reduced, kernel_cost.compact_ids_patterns(2048, 1024))
    assert n > 0 and 0 < sec < reduced["busy_s"]
    top = trace_reduce.top_ops(reduced)
    assert len(top) == 10 and top[0][1] >= top[-1][1]


def test_gaps_attributed_to_host_spans(reduced):
    table = trace_reduce.gap_table(reduced["gaps"])
    labels = [name for name, _ in table]
    assert any(name.startswith("bench.call/") for name in labels), labels
    assert all(sec >= 0 for _, sec in table)


def test_union_and_clip():
    assert trace_reduce._union([(3, 5), (0, 2), (1, 4), (7, 8)]) == \
        [[0, 5], [7, 8]]
    assert trace_reduce._clip([(0, 2), (3, 9)], 1, 5) == [(1, 2), (3, 5)]
