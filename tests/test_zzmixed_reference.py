"""The mixed deployment (Raft shards under PBFT finality, BASELINE config 5)
against its plain reference, ``benchmark/reference/mixed_engine.py``: a
per-message event-heap simulation that imports nothing from the program and
simulates the election -> membership coupling.

Counts are compared exactly.  Times are compared within a stated tolerance:
the two sides draw from independent random streams, so a milestone that is a
threshold crossing over m (or a maximum over S) delay draws moves by a tick
or two; at 16 nodes a shard the *election* time itself is anywhere in
U[150,300) ms, so it is held to the window it can fall in and everything
after it is measured from it.

Seeds: at 16 or 32 nodes a shard the first election is a PRNG race in either
engine (a split vote re-runs it past the proposal horizon:
tests/test_mixed.py::test_mixed_end_to_end says the same), so each case names
seeds on which every shard of both sides elects at its first attempt.  At the
benchmark's 1,024 nodes a shard the first attempt always succeeds (PERF.md
section 6, PR 28).
"""

import importlib.util
import os

import pytest

from blockchain_simulator_tpu import SimConfig, run_simulation
from blockchain_simulator_tpu.utils.config import FaultConfig

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference", "mixed_engine.py")

COUNTS = ("shards_with_leader", "raft_blocks_min", "raft_blocks_total",
          "global_rounds_sent", "global_blocks_final", "agreement_ok")
# tolerance in ms, and why
TIMES = {
    # majority crossing of ~m acks over the U{3,4,5}+U{3,4,5} round trip
    "raft_commit_tail_ms_max": 2.0,
    # mean over 40 slots of a maximum over S=8 representatives' commits
    "global_mean_ttf_ms": 1.0,
}
STAT = dict(n=128, mixed_shards=8, delivery="stat", model_serialization=False,
            sim_ms=4000)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("mixed_engine_ref", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        mod.build()
    except (OSError, RuntimeError) as e:
        pytest.skip(f"the reference cannot be compiled here: {e}")
    return mod


def both(reference, fields: dict, seed: int):
    """(program's metrics, reference's milestones), view changes off on both
    sides: a view change is drawn from the stream."""
    f = dict(fields)
    faults = f.pop("faults", {})
    cfg = SimConfig(protocol="mixed", pbft_view_change_num=0,
                    faults=FaultConfig(**faults), **f)
    f.pop("schedule", None)  # how the program steps is not the deployment's
    ref = reference.run({"protocol": "mixed", "faults": faults, **f}, seed,
                        pbft_view_change_num=0)
    return run_simulation(cfg, seed=seed), ref


CASES = {
    "8x16-stat-fast": (STAT, (1, 3)),
    "8x16-stat-tick": ({**STAT, "schedule": "tick"}, (1, 3)),
    # upstream's serialized blocks: 49 of 50 Raft blocks, ttf ~164 ms
    "8x16-stat-serialized": ({**STAT, "model_serialization": True}, (1, 3)),
    "8x16-edge-serialized": ({**STAT, "delivery": "edge",
                              "model_serialization": True}, (1, 3)),
    # PBFT over 4 representatives never finalizes (the proposer takes no
    # part in its own slot's votes, so a commit hears 2 of the 3 it needs):
    # 40 rounds sent, none final, on both sides
    "4x32-stat-fast": ({**STAT, "mixed_shards": 4}, (1, 3)),
    "4x32-edge-tick": ({**STAT, "mixed_shards": 4, "delivery": "edge"}, (3,)),
}


def both_of_case(shared, reference, case: str) -> dict:
    """``{seed: both(...)}`` for every seed of ``CASES[case]``, made once a
    run of the suite (tests/conftest.py ``shared``): a case's seeds share
    one compiled program (tens of seconds; a run is a fraction of one), so
    the process that builds it runs them all."""
    fields, seeds = CASES[case]
    return shared(f"zzmixed_reference.{case}", lambda: {
        seed: both(reference, fields, seed) for seed in seeds})


@pytest.mark.parametrize("case,seed", [
    pytest.param(name, seed, id=f"{name}-seed{seed}")
    for name, (_, seeds) in CASES.items() for seed in seeds])
def test_milestones_equal_the_references(shared, reference, case, seed):
    fields = CASES[case][0]
    m, ref = both_of_case(shared, reference, case)[seed]
    for key in COUNTS:
        assert m[key] == ref[key], (key, m, ref)
    assert m["shards_with_leader"] == fields["mixed_shards"]
    for key, tol in TIMES.items():
        assert abs(m[key] - ref[key]) <= tol, (key, m, ref)
    # an election ends inside the window it can fall in, on both sides
    for side in (m, ref):
        assert 150 + 6 <= side["leader_elected_ms_max"] < 322, side
    # the finality layer waits for shard 0's election: the first block goes
    # out on the first block tick after it, and the last commit follows the
    # first proposal by the rounds' own time (a maximum over S commits: 3 ms)
    assert m["global_first_propose_ms"] == ref["global_first_propose_ms"]
    if ref["global_blocks_final"]:
        assert abs((m["global_last_commit_ms"] - m["global_first_propose_ms"])
                   - (ref["global_last_commit_ms"]
                      - ref["global_first_propose_ms"])) <= 3.0, (m, ref)


def test_crashed_majority_never_joins_the_quorum(reference):
    """Where the coupling matters: 14 of 16 nodes crashed in every shard, no
    shard can elect, no representative is ever alive, nothing is proposed —
    in the program and in the reference."""
    fields = {**STAT, "sim_ms": 1500, "faults": {"n_crashed": 14}}
    m, ref = both(reference, fields, 1)
    for side in (m, ref):
        assert side["shards_with_leader"] == 0, side
        assert side["global_rounds_sent"] == 0, side
        assert side["global_blocks_final"] == 0, side
        assert side["raft_blocks_total"] == 0, side


@pytest.mark.parametrize("seed", (1, 3))
def test_crashed_minority_changes_nothing(shared, reference, seed):
    """3 of 16 nodes crashed in every shard: every count is that of the
    uncrashed deployment, on both sides."""
    crashed = shared("zzmixed_reference.crashed-minority", lambda: {
        s: both(reference, {**STAT, "faults": {"n_crashed": 3}}, s)
        for s in (1, 3)})
    m, ref = crashed[seed]
    whole, _ = both_of_case(shared, reference, "8x16-stat-fast")[seed]
    for key in COUNTS:
        assert m[key] == ref[key] == whole[key], (key, m, ref, whole)
    for key, tol in TIMES.items():
        assert abs(m[key] - ref[key]) <= tol, (key, m, ref)


def test_reference_imports_nothing_from_the_program():
    for name in ("mixed_engine.py", "mixed_engine.cpp"):
        with open(os.path.join(os.path.dirname(_PATH), name)) as f:
            src = f.read()
        assert "blockchain_simulator_tpu" not in src, name
        assert "import jax" not in src, name
