"""The trace reduction against a small recorded trace whose busy, idle and
collective intervals are known (``data/small_trace.pbtxt``, times in ns).

Chip 0 runs three step programs, [1000, 5000), [6000, 10000) and
[12000, 16000). Its ops leave it idle 500 ns inside the first step, 1000
and 2000 ns between steps; a collective-permute runs alone for 500 ns and
an all-reduce overlaps a fusion for all but 500 ns; the second step's ops
sit inside a ``while`` op that spans the step; one op after the last step
lies outside the window. Some ops carry their whole instruction text as
their name, as a TPU trace gives it. Chip 1 is busy through each of the same
steps. The host runs ``make_batch`` and ``TransferToDevice`` in the gaps.
"""
import os

import pytest

from bench import trace
from bench.harness import RunRecord, STEP_PROGRAM
from bench.metrics import grad_sync_exposed_ms, host_gap_ms, idle_share, mfu

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.pbtxt")


@pytest.fixture(scope="module")
def windows():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        tr = trace.from_profile(ProfileData.from_text_proto(f.read()))
    return tr, trace.device_windows(tr, STEP_PROGRAM)


def test_windows_span_the_step_programs(windows):
    _, ws = windows
    assert sorted(ws) == [0, 1]
    assert (ws[0].lo, ws[0].hi, ws[0].window_ns) == (1000, 16000, 15000)
    assert ws[0].busy_ns == 11500          # 3500 + 4000 + 4000
    assert ws[1].busy_ns == 12000
    assert trace.step_period_ns(ws[0]) == 5500


def test_gaps_and_collectives(windows):
    _, ws = windows
    assert trace.gap_idle_ns(ws[0]) == [1000, 2000]
    assert trace.exposed_collective_ns(ws[0]) == 1000
    assert trace.exposed_collective_ns(ws[1]) == 0


def test_breakdown_names_ops_and_gaps(windows):
    tr, ws = windows
    ops = dict(trace.top_ops(ws))
    assert ops["fusion.7"] == pytest.approx(12000 / 2 / 1e9)
    assert ops["fusion.1"] == pytest.approx(6000 / 2 / 1e9)
    assert "fusion.9" not in ops and "while.3" not in ops
    # the idle end of step 1 and the gap after it are one gap, 4500-6000
    assert trace.idle_gaps(tr, ws[0], skip=("bench_window",)) == [
        ("TransferToDevice", 2e-6), ("make_batch", 1.5e-6)]


def test_metric_readers(windows):
    tr, ws = windows
    run = RunRecord(cell=None, seed=0, chips=2,
                    peak={"bf16_flops_per_s": 1e12},
                    flops_per_step=5.5e6, trace=tr, windows=ws)
    assert mfu.read(run) == pytest.approx(50.0)
    assert idle_share.read(run) == pytest.approx(
        100 * (3500 / 15000 + 3000 / 15000) / 2)
    assert host_gap_ms.read(run) == pytest.approx(1500 / 1e6)
    assert grad_sync_exposed_ms.read(run) == pytest.approx(1000 / 3 / 2 / 1e6)


def test_union_and_subtract():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.covered([(0, 4), (5, 6)], 2, 5.5) == 2.5
