"""What the cell ``mixed256x1k.solo`` (BASELINE config 5) rests on, on the CPU
at the configuration file's own rehearsal size: 8 shards of 256 nodes, the
smallest shard at which every first election succeeds on every seed (at 16
nodes one run in ten splits its first vote).

- the shard batch as a lane batch (``base.lane_vmap``) gives the metrics dict
  of the plain ``jax.vmap`` lowering, same seed for same seed;
- the program against the plain per-message reference through the cell's own
  checks (``benchmark/mixed_checks.py``): counts exact, the four timing gaps
  within the file's limits;
- each of the configuration's three controls (the program with one guarantee
  broken) fails by the check it names and by no other;
- a row that lacks a milestone is not ``correct``, and the ``mixed_solo``
  driver refuses, before it builds anything, a ``models.mixed`` that does not
  export the milestone tuple.

The limits are the configuration file's (``reference`` with
``rehearsal_reference`` laid over it, which says why each is what it is): the
two sides draw from independent random streams, so a milestone that is a
threshold crossing over m, or a maximum over S, delay draws moves by a
millisecond or two; at 256 nodes the first timer fires within a few ms of
150 (election limit 8 ms); over 8 representatives the last commit is a
maximum of 8 draws (tail limit 3 ms).  Counts have no tolerance.
"""

import importlib
import json
import os
import random

import jax
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import mixed
from blockchain_simulator_tpu.models.base import sim_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEEDS = (2_147_483_659, 7)  # one past 2**31, as the driver's are


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (they import each other by bare name) and the
    cell's configuration at its rehearsal size."""
    import sys

    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("run", "program", "checks", "mixed_checks")}
        spec = mods["run"].load_json(ROOT, "BENCHMARK.json")
        ctx = mods["run"].make_ctx(spec, "mixed256x1k.solo", SEEDS[0], False,
                                   on_chip=False)
        yield {**mods, "spec": spec, "ctx": ctx}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def reference(bench, shared):
    """The plain reference's milestones at the rehearsal size."""
    ctx = bench["ctx"]
    try:
        return shared("zzmixed_cell.reference", lambda: bench[
            "mixed_checks"].reference_milestones(
                ctx["config"], ctx["reference_fields"], SEEDS[0]))
    except (OSError, RuntimeError) as e:
        pytest.skip(f"the reference cannot be compiled here: {e}")


def rows_of(bench, fields: dict, batch=None) -> list[dict]:
    """The program's metrics dicts for ``fields`` over ``SEEDS``, built past
    the registry so that ``batch`` (what ``mixed.step`` batches the shards
    with) is the one traced."""
    cfg = bench["program"].sim_config(fields)
    sim = runner.make_sim_fn.__wrapped__(cfg)
    if batch is not None:
        real, mixed.lane_vmap = mixed.lane_vmap, batch
    try:
        return [sim_metrics(cfg, sim(jax.random.key(s))) for s in SEEDS]
    finally:
        if batch is not None:
            mixed.lane_vmap = real


def compare(bench, reference: dict, rows: list[dict]) -> dict:
    ctx, mc = bench["ctx"], bench["mixed_checks"]
    cfg = bench["program"].sim_config(ctx["reference_fields"])
    out = mc.guarantees(rows, ctx["reference_fields"], cfg.raft_max_blocks,
                        cfg.pbft_max_rounds)
    out += mc.against_reference(rows, reference, ctx["config"],
                                cfg.pbft_block_interval_ms)
    return {c["name"]: c for c in out}


@pytest.fixture(scope="module")
def sound_rows(bench, shared):
    # one compile of the rehearsal a run of the suite (tests/conftest.py)
    return shared("zzmixed_cell.sound_rows",
                  lambda: rows_of(bench, bench["ctx"]["fields"]))


def test_sound_rows_are_correct_against_the_reference(bench, reference,
                                                      sound_rows):
    comps = compare(bench, reference, sound_rows)
    assert all(c["ok"] for c in comps.values()), comps
    assert comps["rows_with_timing"]["value"] == len(SEEDS)
    for row in sound_rows:
        assert set(mixed.MILESTONES) <= set(row)
        assert row["shards_with_leader"] == 8 and row["raft_blocks_min"] == 50


def test_lane_batch_gives_the_plain_vmaps_metrics(bench, sound_rows):
    assert mixed.lane_vmap is not jax.vmap
    assert rows_of(bench, bench["ctx"]["fields"], batch=jax.vmap) == sound_rows


def _controls():
    with open(os.path.join(BENCH, "configs", "mixed-raft256x1k-pbft.json")) as f:
        return [pytest.param(c, id=c["name"]) for c in json.load(f)["controls"]]


@pytest.mark.parametrize("control", _controls())
def test_control_fails_by_the_check_it_names_and_no_other(bench, reference,
                                                          control):
    fields = {**bench["ctx"]["fields"],
              **control.get("rehearsal_fields", control["fields"])}
    comps = compare(bench, reference, rows_of(bench, fields))
    failed = sorted(name for name, c in comps.items() if not c["ok"])
    assert failed == [control["must_fail"]], comps


def test_a_row_without_a_milestone_is_not_correct(bench, reference):
    """Rows that are the reference's own answers are ``correct``; take one
    milestone from one of them and they are not, by ``rows_with_timing``."""
    rows = [dict(reference), dict(reference)]
    assert all(c["ok"] for c in compare(bench, reference, rows).values())
    del rows[1][mixed.MILESTONES[0]]
    comps = compare(bench, reference, rows)
    assert [n for n, c in comps.items() if not c["ok"]] == ["rows_with_timing"]
    assert comps["rows_with_timing"]["value"] == len(rows) - 1


@pytest.mark.parametrize("export", (None, mixed.MILESTONES[:-1]),
                         ids=("no-tuple", "tuple-lacks-a-key"))
def test_driver_refuses_before_building(bench, monkeypatch, export):
    """A ``models.mixed`` that cannot say which milestones it reports: the
    driver's ``setup()`` raises before ``make_sim_fn`` is ever called."""
    if export is None:
        monkeypatch.delattr(mixed, "MILESTONES")
    else:
        monkeypatch.setattr(mixed, "MILESTONES", export)
    built = []
    monkeypatch.setattr(runner, "make_sim_fn",
                        lambda cfg: built.append(cfg) or (lambda key: None))
    ctx = dict(bench["ctx"], rng=random.Random(1))
    driver = bench["run"].load_module("drivers", "mixed_solo").Driver(ctx)
    with pytest.raises(AttributeError, match="guarantee 'timing'.*lacks"):
        driver.setup()
    assert built == []
