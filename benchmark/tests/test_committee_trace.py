"""``committee_trace.py``: the reduction on a crafted trace (two tile-ticks of
a committee stack as ``mesh_trace.load`` hands them over), and on the
recorded TPU fixtures of programs WITHOUT the committee tier's scopes and
spans (the parent's case): every reader returns nothing and none raises."""

import os

import pytest

import committee_trace
import mesh_trace

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
STACK, TILE = "topo.committee.stack", "topo.committee.tile"


def crafted():
    """Two tile-ticks, 100 ns each from t = 1000: per tick a scan ``while``
    (no op_name, encloses the rest), a pop (40 ns), a stripped fusion inside
    a taken gate's ``while`` that takes its caller's path (30 ns, of it a
    sampler's 10 ns), the gate's own reduction (5 ns); 25 ns of the loop's
    own.  One stack ``while`` around both; a readback and an outer span."""
    tick = (STACK, TILE)
    ops = [("while.1", (), False, 1000.0, 1200.0)]
    for k in range(2):
        t = 1000.0 + 100 * k
        ops += [
            ("while.2", (), False, t, t + 100),
            ("fusion.pop", tick + ("pbft.tick.pop", "ops.ring.ring_pop"),
             False, t, t + 40),
            ("fusion.any", tick + ("ops.gate.any_lane",), False, t + 40, t + 45),
            ("fusion.arm", tick + ("gate.pbft.tick_taken", "pbft.tick.commit",
                                   "ops.delivery.bcast"), False, t + 50, t + 80),
            ("fusion.draw", tick + ("gate.pbft.tick_taken", "pbft.tick.commit",
                                    "ops.delay.sample_edge_delays"),
             False, t + 60, t + 70),
        ]
    host = [("topo.committee.readback", 1010.0, 1030.0, {"committees": 200}),
            ("topo.committee.outer", 1030.0, 1090.0, {"committees": 200}),
            ("topo.committee.outer", 900.0, 1005.0, {})]  # not wholly inside
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": host, "window": (1000.0, 1200.0)}


@pytest.fixture
def run(monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(mesh_trace, "load", lambda *a, **k: crafted())
    return {"traffic": {"driver": "committee_solo"},
            "trace": {"path": str(path)},
            "window": {"counters": {"committee.tiles": 6.0,
                                    "committee.tile_lanes": 600.0}}}


def test_crafted_trace_reduces_to_its_tile_ticks(run):
    t = committee_trace.of_run(run)
    assert t["tile_ticks"] == 2 and t["events"] == 11
    assert t["busy_s"] == pytest.approx(200e-9)
    # everything but the loops' own 2 x 25 ns carries a scope of its own
    assert t["scoped_s"] == pytest.approx(150e-9)
    assert t["under_tile_s"] == t["under_stack_s"] == pytest.approx(150e-9)
    assert committee_trace.tile_tick_us(run) == pytest.approx(0.075)
    assert committee_trace.inner_us(run, "ops.ring.") == pytest.approx(0.040)
    assert committee_trace.inner_us(run, "ops.delivery.") == pytest.approx(0.020)
    assert committee_trace.inner_us(run, "ops.delay.") == pytest.approx(0.010)
    assert committee_trace.inner_us(run, "ops.gate.") == pytest.approx(0.005)
    assert committee_trace.inner_us(run, "ops.mesh.") is None
    assert committee_trace.scoped_pct(run) == pytest.approx(75.0)
    # the engine's phases, the committee tier's own scopes looked through
    assert t["by_phase_s"]["pbft.tick.pop"] == pytest.approx(80e-9)
    assert t["by_phase_s"]["gate.pbft.tick_taken"] == pytest.approx(60e-9)
    assert committee_trace.span_median_ms(
        run, "topo.committee.readback") == pytest.approx(20e-6)
    assert committee_trace.span_median_ms(
        run, "topo.committee.outer") == pytest.approx(60e-6)
    assert committee_trace.tile_lanes(run) == 100.0


def test_another_drivers_run_reads_nothing(run):
    run["traffic"]["driver"] = "solo"
    assert committee_trace.of_run(run) is None
    assert committee_trace.tile_lanes(run) is None
    assert committee_trace.tile_tick_us(run) is None


@pytest.mark.parametrize("fixture", ("solo_small", "served_small",
                                     "mixed_small"))
def test_a_program_without_the_scopes_reads_nothing(fixture):
    """The parent's case: a TPU trace with ``pbft.*`` / ``ops.*`` scopes but
    no committee tier: no tile-tick can be counted, so no per-tile-tick
    reader reads, no span is found, and no counter is there."""
    run = {"traffic": {"driver": "committee_solo"}, "window": {},
           "trace": {"path": os.path.join(FIXTURES,
                                          fixture + ".xplane.pb.gz")}}
    assert committee_trace.of_run(run)["tile_ticks"] == 0
    assert committee_trace.tile_tick_us(run) is None
    for prefix in ("ops.delivery.", "ops.delay.", "ops.ring.", "ops.gate."):
        assert committee_trace.inner_us(run, prefix) is None
    for span in ("topo.committee.readback", "topo.committee.outer"):
        assert committee_trace.span_median_ms(run, span) is None
    assert committee_trace.tile_lanes(run) is None


def test_an_unreadable_trace_reads_nothing_and_does_not_raise(tmp_path):
    bad = tmp_path / "x.xplane.pb"
    bad.write_bytes(b"not a trace")
    run = {"traffic": {"driver": "committee_solo"}, "window": {},
           "trace": {"path": str(bad)}}
    assert committee_trace.tile_tick_us(run) is None
    assert committee_trace.scoped_pct(run) is None
