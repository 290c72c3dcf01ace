"""The trace reduction on a small recorded trace (TPU v5e, the solo driver at
rehearsal size, 50 ms of window; ``record_fixture.py`` made it)."""

import gzip
import os

import pytest

import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures", "solo_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "solo_small.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f:
        raw.write_bytes(f.read())
    return xplane.summarize(str(raw))


def test_busy_plus_idle_is_the_window(summary):
    assert summary["devices"] == ["/device:TPU:0"]
    assert 0 < summary["busy_s"] < summary["window_s"]
    assert summary["busy_s"] + summary["idle_s"] == pytest.approx(
        summary["window_s"], rel=1e-9)
    gaps = sum(summary["idle_by_span_s"].values())
    assert gaps == pytest.approx(summary["idle_s"], rel=1e-6)


def test_op_table_sums_to_busy(summary):
    assert summary["op_total_s"] == pytest.approx(summary["busy_s"], rel=1e-6)
    top = summary["breakdown"]["device_ops"]
    assert 0 < len(top) <= 10 and all(len(n) <= 64 for n, _ in top)


def test_harness_spans_hold_the_device_time(summary):
    spans = summary["spans"]["bench.dispatch"]
    assert spans and all(0 <= s["busy_s"] <= s["dur_s"] for s in spans)
    # a dispatch ends in block_until_ready: nearly all device time is inside
    inside = sum(s["busy_s"] for s in spans)
    assert inside <= summary["busy_s"] * (1 + 1e-9)


def test_interval_arithmetic():
    b = xplane.Busy(xplane.merge([(0, 10), (5, 20), (30, 40)]))
    assert b.iv == [(0, 20), (30, 40)]
    assert b.covered(0, 40) == 30 and b.covered(15, 35) == 10
    assert b.covered(20, 30) == 0 and b.covered(-5, 5) == 5
    assert b.gaps(0, 50) == [(20, 30), (40, 50)]
    table = xplane.self_times([("while", 0, 100), ("a", 10, 30),
                               ("b", 40, 50), ("c", 120, 130)], 0, 200)
    assert table == {"while": 70, "a": 20, "b": 10, "c": 10}
