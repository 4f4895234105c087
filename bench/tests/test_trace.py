"""The trace reduction against a small trace recorded on one v5e
(``data/record_trace.py``): three steps of two matmul fusions, each followed
by a 20 ms sleep inside ``bench.step_hook``, all inside ``bench.window``.

The device's first step lies about a millisecond before the window opens on
the host's clock, so it falls outside: two steps count.
"""
import pytest

from conftest import BENCH
from lib import trace

SMALL = BENCH / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(SMALL)


def test_window_and_busy(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert trace.window_s(tr) == pytest.approx(0.065718667, abs=1e-9)
    # 2 steps x (89953 + 90873 ns of fusions + 15 ns of copies)
    assert trace.busy_s(tr) == pytest.approx(361682e-9, abs=1e-12)


def test_union_merges_and_clips():
    got = trace.union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert got == [(1, 3), (5, 10)]


def test_kernel_events_by_op_name(tr):
    evs = trace.kernel_events(tr, "convolution_tanh_fusion")
    assert [e.op for e in evs] == ["convolution_tanh_fusion"] * 2
    assert sum(e.end - e.start for e in evs) == 2 * 89953
    assert trace.kernel_events(tr, "no_such_kernel") == []


def test_breakdown(tr):
    ops = trace.top_ops(tr)
    assert ops[0][0].startswith("fusion = bf16[2048,2048] fusion(")
    assert ops[0][1] == pytest.approx(2 * 90873e-9)
    gaps = trace.idle_gaps(tr)
    assert [g[0] for g in gaps[:3]] == ["bench.step_hook"] * 3
    assert all(0.02 < g[1] < 0.024 for g in gaps[:3])
    idle = trace.window_s(tr) - trace.busy_s(tr)
    assert sum(g[1] for g in gaps) == pytest.approx(idle, rel=1e-6)
