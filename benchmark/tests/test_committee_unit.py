"""The unit of work of ``pbftcomm100k.solo``: a run yields the MEAN over its
committees of ``blocks_final_all_nodes`` (``committee_checks.rounds``), the
minimum over them stays a guarantee and a note, and an answer altered where
it is produced still comes out not ``correct``."""

import pytest

import committee_checks
import run as bench

SPEC = bench.load_json(bench.ROOT, "BENCHMARK.json")
CELL = "pbftcomm100k.solo"


def row(final, view_changes=None):
    """A run's metrics as far as the unit and the guarantees read them."""
    c = len(final)
    vc = view_changes or [int(f < 8) for f in final]
    return {"agreement_ok": True, "committees": c, "committees_decided": c,
            "per_committee": {
                "blocks_final_all_nodes": list(final), "view_changes": vc,
                "rounds_sent": [11] * c, "last_commit_ms": [500.0] * c,
                "mean_time_to_finality_ms": [170.0] * c,
                "agreement_ok": [True] * c}}


@pytest.mark.parametrize("final,unit", (
    ([8, 8, 8, 8], 8.0),          # no committee changed view: the mean is 8
    ([8, 8, 5, 8], 7.25),         # one did and lost three rounds
    ([8, 4, 7, 8, 8, 6], 41 / 6),
    ([8] * 179 + [6] * 15 + [5] * 5 + [4], 7.755)))  # a run of the cell
def test_a_runs_unit_is_the_mean_over_its_committees(final, unit):
    m = row(final)
    got = committee_checks.rounds(m)
    assert isinstance(got, float) and got == pytest.approx(unit)
    assert min(final) <= got <= max(final) == 8
    if min(final) < 8:  # a view change costs what it cost, not the run's floor
        assert min(final) < got < 8 and got != int(got)


def test_the_floor_is_held_over_every_committee_of_every_run():
    fields = {"committees": 4}
    held = {c["name"]: c for c in committee_checks.guarantees(
        [row([8, 8, 8, 8]), row([8, 4, 8, 8])], fields)}
    assert held["blocks_final_min"]["ok"]
    assert held["blocks_final_min"]["value"] == 4
    # one committee of one run finalized nothing: the mean hides it (5.5 of
    # 8), the guarantee does not
    stalled = [row([8, 8, 8, 8]), row([8, 0, 6, 8])]
    assert committee_checks.rounds(stalled[1]) == 5.5
    held = {c["name"]: c for c in committee_checks.guarantees(stalled, fields)}
    assert not held["blocks_final_min"]["ok"]


def test_an_altered_answer_is_rejected_and_the_notes_say_what_fell(
        monkeypatch):
    """A whole rehearsal with one committee's count altered where it is
    produced: the run's unit moves by a 1/C share of it, the notes report
    the run's minimum beside the mean, and the run is not ``correct``."""
    from blockchain_simulator_tpu.models import base

    real = base.sim_metrics
    calls = {"n": 0}

    def altered(cfg, final):
        m = real(cfg, final)
        calls["n"] += "per_committee" in m  # a stack's row, not a flat run's
        if calls["n"] == 3:  # the window's first run (set-up made two)
            calls["n"] += 1
            m["per_committee"]["blocks_final_all_nodes"][0] -= 3
        return m

    monkeypatch.setattr(base, "sim_metrics", altered)
    ctx = bench.make_ctx(SPEC, CELL, 2_147_483_659, False, on_chip=False)
    run, comps = bench.drive(ctx, 0.5, bench.CompileCounter())
    comps = {c["name"]: c for c in comps}
    assert not comps["blocks_final_all_nodes_vs_reference_max"]["ok"], comps
    assert comps["blocks_final_min"]["ok"]
    w = run["window"]
    c = run["fields"]["committees"]
    full = max(s["units"] for s in w["samples"])
    assert w["samples"][0]["units"] == pytest.approx(full - 3 / c)
    notes = w["notes"]
    lowest = int(full) - 3
    assert notes["blocks_final_min_any_committee"] == lowest
    assert f"{lowest}:1" in notes["run_min_histogram"].split()
    assert f"{round(full - 3 / c, 3)}:1" in notes["units_histogram"].split()
