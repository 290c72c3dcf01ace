"""``scope_table.py`` on a small recorded trace (TPU v5e, the ``mixed_solo``
driver far below rehearsal size, 0.25 s of window; ``record_mixed_fixture.py``
made it), and the mixed cell's readers on it and on a trace of a program that
has no scopes at all (``solo_small``)."""

import os

import pytest

import run as bench
import scope_table

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
MIXED = os.path.join(FIXTURES, "mixed_small.xplane.pb.gz")
SOLO = os.path.join(FIXTURES, "solo_small.xplane.pb.gz")
# what record_mixed_fixture.py's FIELDS make of a run
SETUP = {"prefix_ticks": 26, "steady_ticks": 34, "hb_steps": 8}
READERS = ("mixed_prefix_tick_us", "mixed_raft_shards_us",
           "mixed_finality_tick_us", "mixed_raft_hb_us", "mixed_fallback_pct",
           "device_scoped_pct.mixed")


@pytest.fixture(scope="module")
def mixed():
    return scope_table.summarize(MIXED)


def fake_run(path: str, driver: str = "mixed_solo") -> dict:
    return {"traffic": {"driver": driver}, "trace": {"path": path},
            "setup": dict(SETUP), "window": {}}


def test_scope_path_reads_all_four_families_off_an_op_name():
    path = ("jit(sim_mixed)/mixed.prefix/while/body/closed_call/"
            "mixed.tick.raft_shards/vmap(raft.tick.vote_rx)/cond/branch_1_fun/"
            "ops.delivery.push_bucket_counts/add")
    assert scope_table.scope_path(path) == (
        "mixed.prefix", "mixed.tick.raft_shards", "raft.tick.vote_rx",
        "ops.delivery.push_bucket_counts")
    assert scope_table.scope_path(
        "jit(sim_mixed)/mixed.steady.finality/while/body/pbft.tick.pop/"
        "ops.ring.ring_pop/mul") == (
        "mixed.steady.finality", "pbft.tick.pop", "ops.ring.ring_pop")
    assert scope_table.scope_path("jit(sim_mixed)/while/body/mul") == ()
    assert scope_table.scope_path("") == ()


def test_tables_sum_to_the_devices_busy_time(mixed):
    assert 0 < mixed["busy_s"] < mixed["window_s"]
    assert 0 < mixed["scoped_s"] <= mixed["busy_s"] * (1 + 1e-6)
    assert mixed["main_runs"] >= 1
    assert sum(mixed["runs_by_path_s"].values()) == pytest.approx(
        mixed["runs_busy_s"], rel=1e-6)
    assert mixed["runs_busy_s"] <= mixed["main_runs_s"] * (1 + 1e-6)


def test_time_under_a_scope_holds_what_nests_inside_it(mixed):
    under = mixed["runs_under_s"]
    # the prefix holds the tick's three parts, the shard batch the raft
    # phases, the finality scopes the pbft phases
    parts = sum(under[k] for k in ("mixed.tick.raft_shards",
                                   "mixed.tick.membership",
                                   "mixed.tick.finality") if k in under)
    assert 0 < parts <= under["mixed.prefix"] * (1 + 1e-6)
    raft = sum(v for k, v in under.items() if k.startswith("raft.tick."))
    assert 0 < raft <= under["mixed.tick.raft_shards"] * (1 + 1e-6)
    assert under["mixed.steady.finality"] > 0
    assert under["mixed.steady.raft_hb"] >= under["raft.hb.step"] > 0
    assert any(k.startswith("pbft.tick.") for k in under)
    assert any(k.startswith("ops.") for k in under)
    # a sound run never takes the per-tick arm
    assert under.get("mixed.fallback", 0.0) == 0.0


def test_every_reader_of_the_mixed_cell_reads_the_fixture():
    run = fake_run(MIXED)
    got = {name: bench.load_module("layer_metrics", name).read(run)
           for name in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["mixed_fallback_pct"] == 0.0
    assert 0 < got["mixed_raft_shards_us"] <= got["mixed_prefix_tick_us"]
    assert 0 < got["device_scoped_pct.mixed"] <= 100.0
    # the reduction is made once for all the readers of a process
    assert run["_scope_table"]["main_runs"] >= 1


def test_readers_return_nothing_for_another_driver_or_without_scopes():
    other = fake_run(MIXED, driver="solo")
    bare = fake_run(SOLO)  # a program with no scopes at all
    untraced = {**fake_run(MIXED), "trace": None}
    for name in READERS:
        read = bench.load_module("layer_metrics", name).read
        assert read(other) is None, name
        assert read(untraced) is None, name
        assert read(bare) is None, name
