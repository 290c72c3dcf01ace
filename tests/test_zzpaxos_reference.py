"""Single-decree Paxos over a gossip relay (BASELINE config 3) against its
plain reference, ``benchmark/reference/paxos_gossip_engine.py``: a per-message
event heap that imports nothing from the program and takes the overlay as
data.  At 256 nodes on an 8-out digraph, on ONE device and on a 4-shard
virtual mesh (``tests/conftest.py`` gives 8 devices).

The two sides cannot share a draw.  Counts are compared exactly (every
acceptor executes, one command decided, nobody gives up).  Times are
compared as the cell compares them (``benchmark/paxos_checks.py``): the
median over the program's seeds against the reference's run, on milestones
that do not depend on which window won, within the rehearsal limits of the
configuration file, which says why each is what it is (at 256 nodes a quorum
is 129 replies and a flood's take-off rides on 8 first edges, so one window
moves by +-15 ms between seeds).
"""

import importlib
import os
import sys

import jax
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import paxos
from blockchain_simulator_tpu.models.base import sim_metrics
from blockchain_simulator_tpu.parallel import shard
from blockchain_simulator_tpu.parallel.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEEDS = (2_147_483_659, 7, 11)  # one past 2**31, as the driver's are
CELL = "paxos10k.mesh4"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (they import each other by bare name) and the
    cell's configuration at its rehearsal size."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("run", "program", "checks", "paxos_checks")}
        spec = mods["run"].load_json(ROOT, "BENCHMARK.json")
        ctx = mods["run"].make_ctx(spec, CELL, SEEDS[0], False, on_chip=False)
        yield {**mods, "spec": spec, "ctx": ctx}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def engine(bench):
    return bench["checks"]._engine("paxos_gossip_engine")


@pytest.fixture(scope="module")
def reference(bench, shared):
    ctx = bench["ctx"]
    return shared("zzpaxos_reference.reference", lambda: bench[
        "paxos_checks"].reference_milestones(
            ctx["config"], ctx["reference_fields"], SEEDS[0]))


def rows_of(bench, shards: int) -> list[dict]:
    cfg = bench["program"].sim_config(bench["ctx"]["fields"])
    if shards == 1:
        sim = runner.make_sim_fn(cfg)
        return [sim_metrics(cfg, sim(jax.random.key(s))) for s in SEEDS]
    mesh = make_mesh(n_node_shards=shards, devices=jax.devices()[:shards])
    sim = shard.make_sharded_sim_fn(cfg, mesh)
    return [sim_metrics(cfg, shard.readback(cfg, mesh, sim(jax.random.key(s))))
            for s in SEEDS]


@pytest.fixture(scope="module", params=(1, 4), ids=("one-device", "mesh4"))
def rows(request, bench, shared):
    if len(jax.devices()) < request.param:
        pytest.skip(f"needs {request.param} devices")
    # one compile a layout a run of the suite (tests/conftest.py ``shared``)
    return shared(f"zzpaxos_reference.rows.{request.param}",
                  lambda: rows_of(bench, request.param))


def test_program_is_correct_against_the_reference(bench, reference, rows):
    ctx, pc = bench["ctx"], bench["paxos_checks"]
    fields = ctx["reference_fields"]
    comps = pc.guarantees(rows, fields) + pc.against_reference(
        rows, reference, ctx["config"], fields)
    assert all(c["ok"] for c in comps), comps
    assert {c["name"]: c for c in comps}["rows_with_timing"]["value"] == len(rows)


def test_every_acceptor_executes_with_the_proposers_on_shard_0(bench, rows):
    """Proposers 0-2 are rows of the first shard: an acceptor of another
    shard executes only if the flood crossed shards."""
    n = bench["ctx"]["fields"]["n"]
    for m in rows:
        assert m["acceptor_executes"] == n and m["agreement_ok"]
        assert m["decided_command"] in (0, 1, 2) and m["gave_up"] == 0
        assert m["n_committed_proposers"] >= 1
        assert set(paxos.MILESTONES) <= set(m)
        assert m["first_execute_lag_ms"] == 0.0
        assert 0 < m["commit_flood_ms"] <= pc_horizon(bench)


def pc_horizon(bench) -> int:
    return bench["paxos_checks"].flood_horizon_ms(bench["ctx"]["fields"])


def test_reference_counts_and_milestones(bench, reference):
    f = bench["ctx"]["reference_fields"]
    assert reference["acceptor_executes"] == f["n"] and reference["agreement_ok"]
    assert reference["decided_command"] in (0, 1, 2)
    assert reference["gave_up"] == 0 and reference["n_committed_proposers"] >= 1
    assert bench["paxos_checks"].calm(reference, f)
    # a window is three floods and three reply quorums; one flood reaches
    # its last node well inside the hop budget's horizon
    assert 3 * f.get("link_delay_ms", 3) * 2 < reference["solo_window_ms"] < 600
    assert reference["events"] > f["n"] * f["degree"]


def test_reference_is_deterministic_in_its_seed(bench, engine):
    f = {**bench["ctx"]["reference_fields"], "sim_ms": 1200}
    nbrs = bench["paxos_checks"].overlay_of(f)
    assert engine.run(f, 5, nbrs) == engine.run(f, 5, nbrs)
    assert engine.run(f, 5, nbrs) != engine.run(f, 6, nbrs)


def test_reference_takes_the_overlay_as_data_and_checks_it(bench, engine):
    f = bench["ctx"]["reference_fields"]
    nbrs = bench["paxos_checks"].overlay_of(f)
    got = engine.check_overlay(nbrs, f["n"], f["degree"])
    # the builder's permutation columns have fixed points and repeats: they
    # are counted and carried, not refused
    assert got["self_loops"] == sum(1 for i, r in enumerate(nbrs) if i in r)
    with pytest.raises(ValueError, match="out-edges"):
        engine.check_overlay([r[:-1] for r in nbrs], f["n"], f["degree"])
    with pytest.raises(ValueError, match="outside"):
        engine.check_overlay([[f["n"]] * f["degree"]] * f["n"], f["n"],
                             f["degree"])
    with pytest.raises(ValueError, match="rows"):
        engine.check_overlay(nbrs[:-1], f["n"], f["degree"])


def test_reference_with_one_proposer_has_one_clean_window(bench, engine):
    """No competition: the lone proposer's first window commits, every
    acceptor executes from its one commit flood."""
    f = {**bench["ctx"]["reference_fields"], "paxos_n_proposers": 1,
         "sim_ms": 800}
    m = engine.run(f, 3, bench["paxos_checks"].overlay_of(f))
    assert m["n_committed_proposers"] == 1 and m["retries"] == 0
    assert m["acceptor_executes"] == f["n"] and m["decided_command"] == 0
    assert m["solo_window_ms"] == m["winner_window_ms"] == m["winner_commit_ms"]


def test_reference_refuses_what_it_does_not_implement(bench, engine):
    f = bench["ctx"]["reference_fields"]
    nbrs = bench["paxos_checks"].overlay_of(f)
    with pytest.raises(ValueError, match="gossip relay only"):
        engine.run({**f, "topology": "full"}, 1, nbrs)
    with pytest.raises(ValueError, match="gossip relay only"):
        engine.run({**f, "fidelity": "reference"}, 1, nbrs)
