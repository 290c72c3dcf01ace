"""Raft with terms (``SimConfig.raft_terms``, ``models/raft.py`` "Terms"),
held from five sides.

(a) **Against the plain reference** (``benchmark/reference/
    raft_terms_engine.py``: a per-message event heap, a term on every
    message, Figure 2's rules), through the very comparisons the benchmark
    cell runs (``benchmark/raftgroups_checks.py``).  Elections, at 2,048 x 5,
    512 x 3 and 256 x 7 over the first 700 ms, the stack as one tile through
    the device-memory stub: exactly, in every group, ``term_conflicts`` 0 and
    at most one leader a term; as distributions over groups, the first
    successful election (mean, 90th percentile) and the share of groups whose
    first leader has term 1 / 2 / >= 3.  The whole run, at 128 x 5 and the
    deployment's ``sim_ms``: exactly, the guarantees of the configuration
    file; within limits, last commit minus first election and the failover.
    A group's run is a draw, so the limits are set from readings: each is
    written beside the reading that set it (``LIMITS``).
(b) **The controls fail**, each by the check the configuration file names
    for it: terms off breaks one-leader, a shifted election window the
    election-time limit, a narrowed one the share of first leaders by term,
    early proposals the tail limit.
(c) **Against the flat program**: a stack with terms is bit-equal, group by
    group, to the flat program of the group's key: lone, under ``lane_vmap``
    (a seed sweep) and under ``tile_vmap`` with T > 1.
(d) **Terms off nothing moves**: ``run_simulation`` rows of flat Raft (edge
    and stat) and of gossip Raft are dict-equal to what the parent of the PR
    that brought terms gives (the mixed deployment's rows are pinned in
    ``tests/test_zzmixed_cell.py`` / ``test_mixed.py``, and every program's
    trace without terms is the parent's, ``GRAPH_BASELINE.json``).
(e) **Every arm without terms refuses them by name.**
"""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import base, raft
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.topo import committee
from blockchain_simulator_tpu.utils import telemetry
from blockchain_simulator_tpu.utils.config import SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 2_147_483_659  # one past 2**31, as the driver's are
CELL = "raftgroups100k.solo"
PREFIX_MS = 700
SHAPES = {"2048x5": (2048, 5), "512x3": (512, 3), "256x7": (256, 7)}
WHOLE = (128, 5)
REF_GROUPS = {"2048x5": 4096, "512x3": 4096, "256x7": 4096, "whole": 2000}

# Each limit between the two readings that set it (this host, XLA:CPU, the
# seed above; program over its groups against the reference over REF_GROUPS):
# what the sound program reads, and what the control that breaks the number
# reads: "lo140" is the configuration file's election_window_shifted
# (raft_election_lo_ms=140), "hi170" its election_window_narrowed
# (raft_election_hi_ms=170).  A p90 is a whole number of ms.
LIMITS = {
    # elections over the first 700 ms
    "2048x5": {"first_election_mean_limit_ms": 3.0,   # 0.39; lo140 7.93
               "first_election_p90_limit_ms": 6.0,    # 3.0; hi170 44.0
               "first_term_share_limit": 0.01},       # 0.0012; hi170 0.0803
    "512x3": {"first_election_mean_limit_ms": 3.0,    # 0.15; lo140 5.82
              "first_election_p90_limit_ms": 10.0,    # 0.0; hi170 67.0
              "first_term_share_limit": 0.02},        # 0.0022; hi170 0.0803
    "256x7": {"first_election_mean_limit_ms": 4.0,    # 0.46; lo140 8.70
              "first_election_p90_limit_ms": 10.0,    # 1.0; hi170 115.0
              "first_term_share_limit": 0.02},        # 0.0022; hi170 0.1311
    # the whole run at 128 x 5; raft_proposal_delay_ms=950 reads 49.97 ms on
    # the tail, raft_terms=False 119 groups of 128 with two leaders at
    # 4,500 ms
    "whole": {"first_election_mean_limit_ms": 6.0,    # 1.22; lo140 12.96
              "first_election_p90_limit_ms": 10.0,    # 3.0; lo140 17.0
              "first_term_share_limit": 0.03,         # 0.0063; hi170 0.1079
              "tail_mean_limit_ms": 2.0,              # 0.020; 950: 49.97
              "failover_mean_limit_ms": 6.0,          # 1.97; lo140 12.10
              "failover_p90_limit_ms": 5.0},          # 2.0; lo140 8.0
}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (they import each other by bare name) and the
    cell's configuration."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("run", "program", "checks", "raftgroups_checks")}
        spec = mods["run"].load_json(ROOT, "BENCHMARK.json")
        ctx = mods["run"].make_ctx(spec, CELL, SEED, False, on_chip=False)
        yield {**mods, "ctx": ctx}
    finally:
        sys.path.remove(BENCH)


def _one_tile(cfg, monkeypatch, most=None):
    """A fresh jit of the dyn stack on a device that holds ``most`` groups at
    once (None: all of them, one tile)."""
    canon = canonical_fault_cfg(cfg)
    state = sweep._lane_state_bytes(committee.inner_cfg(canon))
    most = cfg.committees if most is None else most
    monkeypatch.setattr(sweep, "_device_bytes",
                        lambda: int(most * sweep._TEMP_FACTOR * state) + 1)
    return jax.jit(functools.partial(committee.run_stacked, canon))


def _row(bench, fields, monkeypatch, seed=SEED):
    cfg = bench["program"].sim_config(fields)
    final = _one_tile(cfg, monkeypatch)(
        jax.random.key(seed), jnp.int32(0), jnp.int32(0))
    assert committee.ran_as(cfg) == {"lanes": cfg.committees, "tiles": 1}
    return base.sim_metrics(cfg, final)


def _fields(bench, c, m, sim_ms=None, **over):
    fields = {**bench["ctx"]["config"]["fields"], "n": c * m, "committees": c,
              **over}
    if sim_ms is not None:
        fields["sim_ms"] = sim_ms
    return fields


def _config(bench, name):
    config = bench["ctx"]["config"]
    return {**config, "reference": {
        **config["reference"], **LIMITS[name], "groups": REF_GROUPS[name]}}


def _by_name(records):
    return {r["name"]: r for r in records}


# ------------------------------------------- (a) against the plain reference


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_elections_hold_against_the_reference(name, bench, monkeypatch):
    c, m = SHAPES[name]
    rc = bench["raftgroups_checks"]
    fields = _fields(bench, c, m, PREFIX_MS)
    rows = [_row(bench, fields, monkeypatch)]
    config = _config(bench, name)
    ref = rc.reference_groups(config, fields, SEED)
    got = _by_name(rc.election_safety(rows) + rc.elections_against_reference(
        rows, ref, config["reference"]))
    assert ref["group_size"] == m and max(
        ref["per_group"]["leaders_of_one_term_max"]) == 1
    assert sorted(got) == sorted((
        "groups_with_two_leaders", "terms_reported", "term_conflicts_total",
        "groups_with_two_leaders_of_a_term", "first_election_mean_gap_ms", "first_election_p90_gap_ms",
        "first_term_share_gap_max"))
    assert all(r["ok"] for r in got.values()), got


@pytest.fixture(scope="module")
def whole(bench, shared):
    """The deployment's whole run at 128 x 5: one row, the reference's
    sample, and the cell's comparisons by name; made once a run of the
    suite (tests/conftest.py ``shared``)."""
    def build():
        rc = bench["raftgroups_checks"]
        fields = _fields(bench, *WHOLE)
        with pytest.MonkeyPatch.context() as mp:
            rows = [_row(bench, fields, mp)]
        config = _config(bench, "whole")
        ref = rc.reference_groups(config, fields, SEED)
        got = rc.guarantees(rows, ref) + rc.against_reference(rows, ref, config)
        return {"rows": rows, "ref": ref, "fields": fields, "config": config,
                "checks": _by_name(got)}

    return shared("zzraft_terms.whole", build)


def test_whole_run_holds_against_the_reference(whole):
    got = whole["checks"]
    for name in (
            "term_conflicts_total", "groups_with_two_leaders",
            "groups_with_two_leaders_of_a_term", "agreement_violations",
            "blocks_vs_reference_gap_max", "blocks_not_by_first_leader_max",
            "first_election_mean_gap_ms", "commit_tail_mean_gap_ms",
            "failover_mean_gap_ms", "failover_p90_gap_ms"):
        assert name in got
    assert all(r["ok"] for r in got.values()), got
    pg = whole["rows"][0]["per_committee"]
    # every group commits the stop rule's 50 and fails over in a higher term
    assert set(pg["blocks"]) == {50} and min(pg["term_final"]) >= 2
    assert all(t >= 0 for t in pg["failover_ms"])
    assert set(whole["ref"]["per_group"]["blocks"]) == {50}


# ------------------------------------------------- (b) the controls fail


def test_control_fails_by_the_check_it_names(whole, bench, monkeypatch):
    """Each control of the configuration file, the program with one
    guarantee broken against the reference of the deployment as stated."""
    rc = bench["raftgroups_checks"]
    controls = {c["name"]: c for c in bench["ctx"]["config"]["controls"]}
    assert sorted(controls) == ["election_window_narrowed",
                                "election_window_shifted",
                                "proposals_start_early", "terms_off"]
    for name in ("terms_off", "proposals_start_early"):
        rows = [_row(bench, {**whole["fields"], **controls[name]["fields"]},
                     monkeypatch)]
        got = _by_name(rc.guarantees(rows, whole["ref"])
                       + rc.against_reference(rows, whole["ref"],
                                              whole["config"]))
        assert not got[controls[name]["must_fail"]]["ok"], (name, got)
    # the election window: the 8 ms it moves the first election by shows
    # over 2,048 groups, not over 128
    c, m = SHAPES["2048x5"]
    fields = _fields(bench, c, m, PREFIX_MS)
    config = _config(bench, "2048x5")
    ref = rc.reference_groups(config, fields, SEED)
    for name in ("election_window_shifted", "election_window_narrowed"):
        control = controls[name]
        rows = [_row(bench, {**fields, **control["fields"]}, monkeypatch)]
        got = _by_name(rc.elections_against_reference(
            rows, ref, config["reference"]))
        assert not got[control["must_fail"]]["ok"], (name, got)
    # the narrowed window is also the upper reading of the 90th percentile
    assert not got["first_election_p90_gap_ms"]["ok"], got


# ------------------------------------------- (c) against the flat program

C, M = 5, 5
STACK = SimConfig(protocol="raft", raft_terms=True, n=C * M,
                  topology="committee", committees=C,
                  model_serialization=False, sim_ms=PREFIX_MS)


def _flat_finals(key):
    icfg = committee.inner_cfg(canonical_fault_cfg(STACK))
    flat = jax.jit(runner.make_dyn_sim_fn(icfg))
    keys = committee._committee_keys(key, C)
    return [flat(keys[i], jnp.int32(0), jnp.int32(0)) for i in range(C)]


def _assert_equals_flat(stacked, flats):
    for i, flat in enumerate(flats):
        got = jax.tree.map(lambda x: x[i], stacked)
        assert got.term is not None and flat.term is not None
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(flat),
                        strict=True):
            assert a.dtype == b.dtype and np.array_equal(a, b), i


@pytest.mark.parametrize("most,plan", (
    (None, {"lanes": 1, "tiles": 5}), (5, {"lanes": 5, "tiles": 1}),
    (2, {"lanes": 2, "tiles": 3})), ids=("lone", "one-tile", "tiles-of-2"))
def test_stack_with_terms_equals_the_flat_program_per_group(
        most, plan, monkeypatch):
    if most is None:
        monkeypatch.setattr(sweep, "_device_bytes", lambda: None)
        sim = jax.jit(functools.partial(
            committee.run_stacked, canonical_fault_cfg(STACK)))
    else:
        sim = _one_tile(STACK, monkeypatch, most)
    key = jax.random.key(SEED)
    stacked = sim(key, jnp.int32(0), jnp.int32(0))
    assert committee.ran_as(STACK) == plan
    flats = _flat_finals(key)
    _assert_equals_flat(stacked, flats)
    # something happened in them: every group elected, in a term >= 1
    assert all(int(np.asarray(f.term).max()) >= 1 for f in flats)


@pytest.mark.parametrize("nc,sim_ms", ((0, 4500), (7, 4500), (0, 330)),
                         ids=("whole", "tail-crashed", "mid-election"))
def test_metrics_of_a_stack_at_once_are_the_groups_own(nc, sim_ms,
                                                       monkeypatch):
    """``raft.metrics_stacked`` over a stack (what ``committee.metrics``
    reads 20,000 groups with) is ``raft.metrics`` of each group alone (a
    stack of one), entry for entry: after whole runs, with the last group
    wholly crashed and two nodes of the one before (no quorum there: no
    leader, no block), and cut in the middle of the elections (groups
    without a leader yet).  What the entries must be is held on hand-written
    states below."""
    cfg = STACK.with_(n=8 * M, committees=8, sim_ms=sim_ms)
    finals = _one_tile(cfg, monkeypatch)(
        jax.random.key(SEED), jnp.int32(nc), jnp.int32(0))
    icfg = committee.inner_cfg(cfg)
    host = jax.device_get(base.metric_leaves(cfg, finals))
    at_once = raft.metrics_stacked(icfg, host, cfg.committees)
    one_by_one = [raft.metrics(icfg, row)
                  for row in base.host_rows(finals, host, cfg.committees)]
    assert at_once == one_by_one
    assert [list(m) for m in at_once] == [list(m) for m in one_by_one]
    assert all(type(a[k]) is type(b[k]) for a, b in zip(at_once, one_by_one)
               for k in a)
    assert base.sim_metrics(cfg, finals)["per_committee"]["failover_ms"] == [
        m["failover_ms"] for m in one_by_one]
    if nc:
        assert at_once[-1]["n_leaders"] == 0 and at_once[-1]["leader"] == -1


def _hand_state(**fields):
    """A 3-node group's final state with terms, every leaf at its start but
    ``fields``."""
    cfg = SimConfig(protocol="raft", raft_terms=True, n=3,
                    model_serialization=False, sim_ms=PREFIX_MS)
    state, _ = raft.init(cfg)
    return cfg, state.replace(**{
        k: np.asarray(v, np.asarray(getattr(state, k)).dtype)
        for k, v in fields.items()})


def _ticks(*at):
    row = np.full(50, -1, np.int32)
    row[:len(at)] = at
    return row


HAND = {
    # nobody has won yet: one candidate of term 1
    "no-leader-yet": (dict(term=[1, 0, 0], elections=[1, 0, 0]), dict(
        n_leaders=0, leader=-1, leader_elected_ms=-1.0, blocks=0, rounds=0,
        elections=1, last_block_ms=-1.0, mean_block_interval_ms=-1.0,
        agreement_ok=True, term_final=1, leader_term=0,
        n_leaders_term_final=0, term_conflicts=0, step_downs=0,
        first_leader_ms=-1.0, first_leader_term=0, first_leader_blocks=0,
        failover_ms=-1.0)),
    # node 1 led term 1 from 200 and committed two blocks, went silent after
    # its heartbeat of 1300; node 2 won term 2 at 1500 and deposed it
    "failed-over": (dict(
        term=[2, 2, 2], is_leader=[False, False, True],
        leader_tick=[-1, 200, 1500], won_tick=[-1, 200, 1500],
        lead_term0=[0, 1, 2], last_hb=[-1, 1300, 1500], step_downs=[0, 1, 0],
        elections=[0, 1, 1], block_num=[0, 2, 0], round=[0, 2, 0],
        block_tick=[_ticks(), _ticks(1210, 1260), _ticks()],
        m_value=[1, -1, 1]), dict(
        n_leaders=1, leader=2, leader_elected_ms=1500.0, blocks=2, rounds=2,
        elections=2, last_block_ms=1260.0, mean_block_interval_ms=50.0,
        agreement_ok=True, term_final=2, leader_term=2,
        n_leaders_term_final=1, term_conflicts=0, step_downs=1,
        first_leader_ms=200.0, first_leader_term=1, first_leader_blocks=2,
        failover_ms=200.0)),
    # node 0 led term 1 from 180, went silent after 1200 and won again, term
    # 3, at 1450 (a split term 2 between); node 2 stored a value that names
    # node 1, which never led, and the oracle counted a conflict
    "re-elected-and-unsound": (dict(
        term=[3, 3, 3], is_leader=[True, False, False],
        leader_tick=[180, -1, -1], won_tick=[1450, -1, -1],
        lead_term0=[1, 0, 0], last_hb=[1200, -1, -1], step_downs=[1, 0, 0],
        elections=[2, 1, 0], block_num=[1, 0, 0], round=[1, 0, 0],
        block_tick=[_ticks(1190), _ticks(), _ticks()], m_value=[-1, 0, 1],
        term_conflicts=[1, 0, 0]), dict(
        n_leaders=1, leader=0, leader_elected_ms=1450.0, blocks=1, rounds=1,
        elections=3, last_block_ms=1190.0, mean_block_interval_ms=-1.0,
        agreement_ok=False, term_final=3, leader_term=3,
        n_leaders_term_final=1, term_conflicts=1, step_downs=1,
        first_leader_ms=180.0, first_leader_term=1, first_leader_blocks=1,
        failover_ms=250.0)),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_metrics_with_terms_of_a_hand_written_state(name):
    """The rules of ``raft.metrics_stacked`` (its one implementation) on
    states written by hand, each key beside the value it must have."""
    fields, want = HAND[name]
    cfg, state = _hand_state(**fields)
    got = raft.metrics(cfg, state)
    assert got == {"protocol": "raft", "n": 3, **want}
    assert list(got) == ["protocol", "n", *want]


def test_a_seed_sweep_with_terms_equals_its_flat_runs():
    """``lane_vmap`` (the sweeps' batch) around the flat 5-node program."""
    cfg = SimConfig(protocol="raft", raft_terms=True, n=5,
                    model_serialization=False, sim_ms=PREFIX_MS)
    seeds = (SEED, 7, 11)
    rows = sweep.run_seed_sweep(cfg, seeds)
    solo = [runner.run_simulation(cfg, seed=s) for s in seeds]
    strip = lambda m: {k: v for k, v in m.items()  # noqa: E731
                       if k not in ("seed", "manifest")}
    assert [strip(m) for m in rows] == [strip(m) for m in solo]
    assert all(m["term_conflicts"] == 0 and m["n_leaders"] == 1 for m in solo)


# ------------------------------------------- (d) terms off, nothing moves

# what the parent of the PR that brought terms (73efdbb) gives
PARENT_ROWS = {
    "flat-edge-5": (
        dict(protocol="raft", n=5, sim_ms=4500, model_serialization=False,
             seed=7),
        {"protocol": "raft", "n": 5, "n_leaders": 2, "leader": 4,
         "leader_elected_ms": 182.0, "blocks": 50, "rounds": 50,
         "elections": 14, "last_block_ms": 3639.0,
         "mean_block_interval_ms": 50.0, "agreement_ok": True}),
    "flat-edge-16": (
        dict(protocol="raft", n=16, sim_ms=4000, seed=3),
        {"protocol": "raft", "n": 16, "n_leaders": 1, "leader": 9,
         "leader_elected_ms": 159.0, "blocks": 49, "rounds": 50,
         "elections": 5, "last_block_ms": 3621.0,
         "mean_block_interval_ms": 50.0, "agreement_ok": True}),
    "flat-stat-64": (
        dict(protocol="raft", n=64, sim_ms=4000, delivery="stat",
             stat_sampler="exact", schedule="tick", seed=3),
        {"protocol": "raft", "n": 64, "n_leaders": 1, "leader": 9,
         "leader_elected_ms": 159.0, "blocks": 49, "rounds": 50,
         "elections": 8, "last_block_ms": 3621.0,
         "mean_block_interval_ms": 50.0, "agreement_ok": True}),
    "gossip-64": (
        dict(protocol="raft", n=64, topology="gossip", delivery="stat",
             degree=8, sim_ms=3000, seed=3),
        {"protocol": "raft", "n": 64, "n_leaders": 1, "leader": 0,
         "leader_elected_ms": 167.0, "blocks": 35, "rounds": 37,
         "elections": 6, "last_block_ms": 2988.0,
         "mean_block_interval_ms": 50.0, "agreement_ok": True}),
}


@pytest.mark.parametrize("name", sorted(PARENT_ROWS))
def test_terms_off_rows_are_the_parents(name):
    fields, want = PARENT_ROWS[name]
    assert runner.run_simulation(SimConfig(**fields)) == want


def test_a_state_without_terms_carries_no_leaf_for_them():
    off = jax.eval_shape(lambda: raft.init(
        SimConfig(protocol="raft", n=5), jax.random.key(0)))[0]
    on = jax.eval_shape(lambda: raft.init(
        SimConfig(protocol="raft", n=5, raft_terms=True,
                  model_serialization=False), jax.random.key(0)))[0]
    assert all(getattr(off, f) is None for f in raft.TERM_FIELDS)
    assert all(getattr(on, f).shape == (5,) for f in raft.TERM_FIELDS)
    assert len(jax.tree.leaves(on)) - len(jax.tree.leaves(off)) \
        == len(raft.TERM_FIELDS)
    # the metric leaves of terms are read where a state has them
    assert set(raft.TERM_FIELDS) - {"is_cand"} <= set(raft.METRIC_FIELDS)
    assert "term" not in base.metric_leaves(SimConfig(protocol="raft"), off)


# ------------------------------------------------------- (e) the refusals

TERMS = dict(protocol="raft", raft_terms=True, n=16, model_serialization=False)


@pytest.mark.parametrize("over,error,names", (
    (dict(delivery="stat"), NotImplementedError, "delivery='stat'"),
    (dict(delivery="stat", schedule="round", n=4096), NotImplementedError,
     "raft_hb"),
    (dict(topology="gossip", delivery="stat"), NotImplementedError,
     "delivery='stat'"),
    (dict(topology="kregular", degree=4), NotImplementedError, "kregular"),
    (dict(queued_links=True, model_serialization=True), NotImplementedError,
     "queued_links"),
), ids=("stat", "raft_hb", "gossip", "kregular", "queued_links"))
def test_an_arm_without_terms_refuses_them_by_name(over, error, names):
    cfg = SimConfig(**{**TERMS, **over})
    for build in (runner.make_sim_fn.__wrapped__, runner.make_dyn_sim_fn):
        with pytest.raises(error, match="raft_terms") as e:
            build(cfg)
        assert names in str(e.value)


@pytest.mark.parametrize("over,names", (
    (dict(protocol="mixed", n=32, mixed_shards=4), "mixed"),
    (dict(protocol="pbft"), "pbft"),
    (dict(fidelity="reference"), "fidelity"),
), ids=("mixed", "pbft", "reference-fidelity"))
def test_the_configuration_refuses_terms_where_nothing_has_them(over, names):
    with pytest.raises(ValueError, match="raft_terms") as e:
        SimConfig(**{**TERMS, **over})
    assert names in str(e.value)


def test_a_mesh_axis_and_the_cpp_engine_refuse_terms():
    from blockchain_simulator_tpu import engine

    cfg = SimConfig(**TERMS)
    with pytest.raises(NotImplementedError, match="mesh axis"):
        raft.init(cfg.with_(mesh_axis="nodes"))
    with pytest.raises(NotImplementedError, match="C\\+\\+ engine"):
        engine.run_cpp(cfg)
    # what the term-less count channels stand on is checked where a state
    # is made, as pbft.init checks its window
    with pytest.raises(ValueError, match="raft_election_lo_ms"):
        raft.init(cfg.with_(raft_election_lo_ms=10, raft_election_hi_ms=20))


# ------------------------------------------------ scopes and counters


def test_the_term_scope_is_on_the_lowered_program_and_only_with_terms():
    def names(cfg):
        text = runner.make_sim_fn.__wrapped__(cfg).lower(
            jax.random.key(0)).as_text(debug_info=True)
        return {s for s in raft.SCOPES if s in text}

    on = names(SimConfig(**{**TERMS, "n": 5, "sim_ms": 50}))
    off = names(SimConfig(protocol="raft", n=5, sim_ms=50))
    assert "raft.tick.term" in raft.SCOPES
    # what a crash schedule adds is in a program under one alone
    # (tests/test_zzraft_crash.py)
    rest = set(raft.SCOPES) - {"raft.tick.fault", raft.FAULT_SCOPE}
    assert on == rest and off == rest - {"raft.tick.term"}


def test_metrics_with_terms_count_groups_and_terms():
    cfg = SimConfig(**{**TERMS, "n": 5, "sim_ms": 4500})
    names = telemetry.RAFT_COUNTERS
    assert names[:4] == ("raft.groups", "raft.term_bumps", "raft.step_downs",
                         "raft.term_conflicts")
    before = telemetry.metrics.snapshot()["counters"]
    m = runner.run_simulation(cfg, seed=3)
    after = telemetry.metrics.snapshot()["counters"]
    moved = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in names}
    # the counters of a crash schedule (names[4:]) stay where they are
    assert moved == {"raft.groups": 1, "raft.term_bumps": m["term_final"],
                     "raft.step_downs": m["step_downs"],
                     "raft.term_conflicts": 0, **dict.fromkeys(names[4:], 0)}
    # one leader of the highest term; the 50 blocks are the first leader's,
    # who is not the leader at the end: the group failed over
    assert (m["n_leaders"], m["n_leaders_term_final"]) == (1, 1)
    assert m["blocks"] == m["first_leader_blocks"] == 50
    assert m["leader_term"] == m["term_final"] > m["first_leader_term"] >= 1
    assert m["failover_ms"] >= cfg.raft_election_lo_ms
    assert m["leader_elected_ms"] > m["last_block_ms"] > m["first_leader_ms"]


def test_a_probed_run_with_terms_is_the_plain_run_and_its_monitors_hold():
    """obsim's raft monitors know the leaves of terms: with terms the leader
    at the end is not the node whose id the followers stored, and what must
    hold is election safety (the oracle, no two leaders of one term)."""
    from blockchain_simulator_tpu.obsim import host as obsim_host

    cfg = SimConfig(**{**TERMS, "n": 5, "sim_ms": 4500})
    m, summary = obsim_host.run_probed(cfg, seed=3)
    assert m == runner.run_simulation(cfg, seed=3)
    assert m["term_final"] >= 2 and m["n_leaders"] == 1
    assert summary["violations"] == 0
    assert summary["monitors"]["viol_agreement"] == 0
    assert summary["monitors"]["viol_quorum"] == 0


def test_the_reference_imports_nothing_from_the_package():
    src = open(os.path.join(BENCH, "reference", "raft_terms_engine.py")).read()
    imports = [ln for ln in src.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import heapq",
                       "import random"]
