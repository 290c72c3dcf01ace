"""A crash schedule over Raft with terms (``FaultConfig.crashes``,
``models/raft.py`` "Crash schedule"), held from six sides.

(a) **Against the plain reference** (``benchmark/reference/
    raft_crash_engine.py``: a per-message event heap, the crash and the
    restart as events of their own), through the very comparisons the
    benchmark cell runs (``benchmark/raftcrash_checks.py``): at 512 x 5 over a
    cluster start and two crashes, and at 256 x 3 and 128 x 7 (group sizes the
    cell does not hold) over one, the stack as one tile through the
    device-memory stub, under the cell's fields with a shorter schedule.  A
    group's run is a draw, so the limits are set from readings, each written
    beside the reading that set it (``LIMITS``).
(b) **A hand-written schedule on one group, event by event**: the leader
    dies on its tick, sends nothing after, what it had in flight still lands,
    what reaches it while it is down is lost, its term and its vote survive,
    its restart arms a timer; and a node that voted before its crash denies a
    second request of that term after its restart.
(c) **Against the flat program**: a stack under a schedule is bit-equal,
    group by group, to the flat program of the group's key (its phase a draw
    from that key), as one tile and as tiles of 2.
(d) **The metrics** of a stack at once are the groups' own, on three
    hand-written states (crashed and not replaced yet, replaced after two
    elections, a crash that found no leader).
(e) **Without a schedule nothing is there**: no leaf, no ``raft.tick.fault``
    in the lowered text (the rows ``tests/test_zzraft_terms.py`` pins stand).
(f) **Every arm that cannot run a schedule refuses it by name**; the
    counters; obsim's monitors.

What this file adds to tier-1 (this host, ``-n 6``): see ``CHANGES.md``.
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
from blockchain_simulator_tpu.utils import prng, telemetry
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 2_147_483_659  # one past 2**31, as the driver's are
CELL = "raftcrash100k.solo"
# the cell's fields under a shorter schedule: kills at 450 + 600 k + phase,
# phase U{0..599}, back 250 ms later; (groups, group size, kills, sim_ms)
TIMES = dict(first_ms=450, period_ms=600, downtime_ms=250)
SHAPES = {"512x5": (512, 5, 2, 1950), "256x3": (256, 3, 1, 1400),
          "128x7": (128, 7, 1, 1400)}
REF_GROUPS = 3000

# Each limit between the two readings that set it (this host, XLA:CPU, the
# seed above; the program over its crashes against the reference over
# REF_GROUPS groups): what the sound program reads, and what the program
# reads under the configuration file's control that breaks the number
# ("lo140" election_window_shifted, "hi170" election_window_narrowed, "hb100"
# heartbeat_slow; read once, at 512 x 5, not run here: the controls are held
# by ``benchmark/tests`` at the rehearsal's size and by the chip readings in
# the configuration file; ``downtime_short`` read 3.12 ms on the mean and
# moved nothing else).  A median or a p90 is a whole number of ms.  The
# shares of crashes without a leader or a replacement are not 0 here as they
# are in the cell: a kill 450 ms into a run can find a cluster start whose
# first vote split, and the run ends 350 ms after the last kill.
LIMITS = {
    "512x5": {"failover_mean_limit_ms": 5.0,       # 0.08; lo140 8.70
              "failover_median_limit_ms": 5.0,     # 1.0; hb100 10.0
              "failover_p90_limit_ms": 6.0,        # 0.0; lo140 9.0, hi170 108
              "multi_election_share_limit": 0.05,  # 0.0089; hi170 0.6631
              "no_leader_share_limit": 0.006,      # 0.0; hi170 0.0127
              "unreplaced_share_limit": 0.012},    # 0.0; hi170 0.0264
    # the two sizes the cell does not hold, one kill: the sound readings
    # alone (256 and 128 crashes: the mean's sampling error is 3 and 4 ms)
    "256x3": {"failover_mean_limit_ms": 8.0,       # 0.52
              "failover_median_limit_ms": 9.0,     # 2.0
              "failover_p90_limit_ms": 16.0,       # 1.0
              "multi_election_share_limit": 0.06,  # 0.0113
              "no_leader_share_limit": 0.03,       # 0.0
              "unreplaced_share_limit": 0.03},     # 0.0039
    "128x7": {"failover_mean_limit_ms": 10.0,      # 1.63
              "failover_median_limit_ms": 11.0,    # 3.0
              "failover_p90_limit_ms": 20.0,       # 2.0
              "multi_election_share_limit": 0.10,  # 0.0150
              "no_leader_share_limit": 0.03,       # 0.0
              "unreplaced_share_limit": 0.05},     # 0.0
}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (they import each other by bare name) and the
    cell's configuration."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("run", "program", "checks", "raftcrash_checks")}
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


def _by_name(records):
    return {r["name"]: r for r in records}


# ------------------------------------------- (a) against the plain reference


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_failovers_hold_against_the_reference(name, bench, shared):
    """The cell's own checks, all of them, on one run of the stack."""
    c, m, kills, sim_ms = SHAPES[name]
    rc = bench["raftcrash_checks"]
    config = bench["ctx"]["config"]
    fields = {**config["fields"], "n": c * m, "committees": c,
              "sim_ms": sim_ms, "faults": {"crashes": kills, **TIMES}}
    config = {**config, "reference": {
        **config["reference"], **LIMITS[name], "groups": REF_GROUPS}}

    def build():
        cfg = bench["program"].sim_config(fields)
        with pytest.MonkeyPatch.context() as mp:
            final = _one_tile(cfg, mp)(
                jax.random.key(SEED), jnp.int32(0), jnp.int32(0))
        assert committee.ran_as(cfg) == {"lanes": c, "tiles": 1}
        return base.sim_metrics(cfg, final)

    rows = [shared(f"zzraft_crash.row.{name}", build)]
    ref = rc.reference_groups(config, fields, SEED)
    got = _by_name(rc.guarantees(rows, ref, fields)
                   + rc.against_reference(rows, ref, config))
    assert ref["group_size"] == m
    assert sorted(got) == sorted((
        "groups_with_two_leaders", "terms_reported", "term_conflicts_total",
        "groups_with_two_leaders_of_a_term", "schedule_reported",
        "dead_acts_total", "double_votes_total", "agreement_violations",
        "crashes_vs_schedule_gap_max",
        "reference_crashes_vs_schedule_gap_max", "reference_term_conflicts",
        "reference_leaders_of_one_term_max", "reference_dead_acts",
        "group_size_gap_max", "crashes_found_no_leader_share",
        "crashes_unreplaced_share", "failover_mean_gap_ms",
        "failover_median_gap_ms", "failover_p90_gap_ms",
        "multi_election_share_gap"))
    assert all(r["ok"] for r in got.values()), got
    pg = rows[0]["per_committee"]
    # nearly every kill was replaced, in a higher term each time, and every
    # killed node came back
    assert sum(pg["failovers"]) >= 0.95 * kills * c
    assert rc.rounds(rows[0]) == sum(pg["failovers"]) / c
    assert min(pg["term_final"]) >= 1 + min(pg["failovers"])
    hit = sum(pg["crashes"]) - sum(pg["crashes_found_no_leader"])
    assert hit - c <= sum(pg["restarts"]) <= hit


# ------------------------- (b) a hand-written schedule, event by event

ONE = SimConfig(protocol="raft", raft_terms=True, n=5,
                model_serialization=False, sim_ms=1200, raft_heartbeat_ms=75,
                link_delay_ms=7,
                faults=FaultConfig(crashes=1, first_ms=400, period_ms=400,
                                   downtime_ms=250))
WATCHED = ("alive", "is_leader", "is_cand", "term", "has_voted",
           "election_deadline", "next_hb", "elections", "restart_tick",
           "vote_success", "dead_acts", "double_votes", "crash_tick",
           "crash_node", "replaced_tick", "crash_elections", "restarts")


@pytest.fixture(scope="module")
def trajectory():
    """``run(state, bufs, key) -> {leaf: [ticks, ...]}``: the flat 5-node
    program one tick after another, the watched leaves after every tick
    (one compile; the phase is data)."""
    @jax.jit
    def run(state, bufs, key):
        def tick(carry, t):
            st, bf = raft.step(ONE, *carry, t, prng.tick_key(key, t))
            return (st, bf), {f: getattr(st, f) for f in WATCHED}

        return jax.lax.scan(tick, (state, bufs), jnp.arange(ONE.ticks))[1]

    return lambda state, bufs, key: jax.tree.map(
        np.asarray, run(state, bufs, key))


def test_a_leader_crash_event_by_event(trajectory):
    key = jax.random.key(7)
    state, bufs = raft.init(ONE, jax.random.fold_in(key, 0x1217))
    f = ONE.faults
    assert 0 <= int(state.crash_phase) < f.period_ms
    # a dry run with the kill as late as it goes: who leads, and when it
    # sends its heartbeats
    dry = trajectory(state.replace(crash_phase=jnp.int32(f.period_ms - 1)),
                     bufs, key)
    lead = int(np.argmax(dry["is_leader"][f.first_ms]))
    assert dry["is_leader"][f.first_ms].sum() == 1
    sent = [t for t in range(f.first_ms, f.first_ms + 200)
            if dry["next_hb"][t, lead] != dry["next_hb"][t - 1, lead]]
    # the kill falls two ticks after a heartbeat left: that one is in flight
    hb, c = sent[0], sent[0] + 2
    got = trajectory(state.replace(crash_phase=jnp.int32(c - f.first_ms)),
                     bufs, key)
    others = [i for i in range(5) if i != lead]
    back = c + f.downtime_ms
    # --- it dies on its tick: no role, no schedule, no timer; term and the
    # vote of it survive
    assert got["alive"][c - 1, lead] and not got["alive"][c, lead]
    assert got["is_leader"][c - 1, lead] and not got["is_leader"][c, lead]
    assert got["next_hb"][c, lead] == raft.DISARM
    assert got["election_deadline"][c, lead] == raft.DISARM
    assert got["restart_tick"][c, lead] == back
    assert got["term"][c, lead] == got["term"][c - 1, lead] == 1
    assert got["has_voted"][c, lead] and got["has_voted"][c - 1, lead]
    assert int(got["crash_tick"][c][0]) == c
    assert int(got["crash_node"][c][0]) == lead
    # --- what it had in flight still lands: the heartbeat of tick ``hb``
    # re-arms every follower's timer 7-9 ticks later
    first_fire = int(np.argmax(got["elections"].sum(axis=1)
                               > got["elections"][c].sum()))
    assert first_fire >= hb + 7 + ONE.raft_election_lo_ms
    for i in others:
        moved = [t for t in range(c, c + 300) if got["election_deadline"][
            t, i] != got["election_deadline"][t - 1, i]]
        assert moved and hb + 7 <= moved[0] <= hb + 9, (i, moved)
        # --- and it sends nothing after: no timer moves again before the
        # first of them fires, a whole timeout after that last heartbeat
        assert [t for t in moved if t < first_fire] == moved[:1], (i, moved)
    # --- what reaches it while it is down is lost: the survivors elect in
    # term 2 while it still holds term 1, the vote it cast and no role
    won = int(got["replaced_tick"][-1][0])
    assert c < won < back and got["crash_elections"][-1][0] >= 1
    assert (got["term"][won, others] >= 2).all()
    down = slice(c, back)
    assert (got["term"][down, lead] == 1).all()
    assert got["has_voted"][down, lead].all()
    assert not got["alive"][down, lead].any()
    assert not got["is_leader"][down, lead].any()
    assert (got["elections"][down, lead] == got["elections"][c, lead]).all()
    # --- the restart arms a timer, a follower's, U[150, 300) from now
    assert got["alive"][back, lead] and got["restarts"][back, lead] == 1
    assert got["restart_tick"][back, lead] == raft.DISARM
    armed = got["election_deadline"][back, lead]
    assert back + 150 <= armed < back + 300
    # and the new leader's next heartbeat makes it a follower of term 2
    caught = int(np.argmax(got["term"][back:, lead] >= 2)) + back
    assert back <= caught <= back + ONE.raft_heartbeat_ms + 9
    assert not got["is_leader"][back:, lead].any()
    # --- the oracles, and one leader at the end
    assert got["dead_acts"].sum() == 0 and got["double_votes"].sum() == 0
    assert got["is_leader"][-1].sum() == 1


@pytest.mark.parametrize("voted", (True, False), ids=("voted", "had-not"))
def test_a_vote_survives_a_restart(voted, trajectory):
    """Node 1 voted (or did not) in term 3, crashed, and is back at tick 5;
    candidate 2's request of term 3 reaches it at tick 8.  It grants only if
    it had not voted: a grant re-arms its timer a second time."""
    key = jax.random.key(11)
    state, bufs = raft.init(ONE, jax.random.fold_in(key, 0x1217))
    never = jnp.full((5,), raft.DISARM)
    state = state.replace(
        crash_phase=jnp.int32(ONE.faults.period_ms - 1),
        term=jnp.full((5,), 3, jnp.int32),
        has_voted=jnp.asarray([False, voted, True, False, False]),
        voted_term=jnp.asarray([0, 3 * voted, 3, 0, 0], jnp.int32),
        is_cand=jnp.asarray([False, False, True, False, False]),
        alive=jnp.asarray([True, False, True, True, True]),
        restart_tick=never.at[1].set(5), election_deadline=never)
    bufs = bufs.replace(vreq=bufs.vreq.at[8 % ONE.ring_depth, 1, 2].set(3))
    got = trajectory(state, bufs, key)
    assert got["alive"][5, 1] and not got["alive"][4, 1]
    armed = got["election_deadline"][5, 1]
    assert 5 + 150 <= armed < 5 + 300
    assert got["has_voted"][8, 1] and got["term"][8, 1] == 3
    moved_again = got["election_deadline"][8, 1] != armed
    assert moved_again == (not voted)
    assert got["double_votes"].sum() == 0 and got["dead_acts"].sum() == 0
    if not voted:  # the grant reaches candidate 2 one delay later
        assert got["vote_success"][8 + 9, 2] == 1
    else:
        assert got["vote_success"][8 + 9, 2] == 0


# ------------------------------------------- (c) against the flat program

C, M = 5, 5
STACK = SimConfig(protocol="raft", raft_terms=True, n=C * M,
                  topology="committee", committees=C,
                  model_serialization=False, sim_ms=1000,
                  raft_heartbeat_ms=75, link_delay_ms=7,
                  faults=FaultConfig(crashes=2, first_ms=300, period_ms=300,
                                     downtime_ms=120))


@pytest.mark.parametrize("most,plan", (
    (5, {"lanes": 5, "tiles": 1}), (2, {"lanes": 2, "tiles": 3})),
    ids=("one-tile", "tiles-of-2"))
def test_stack_under_a_schedule_equals_the_flat_program_per_group(
        most, plan, monkeypatch, shared):
    def build_flats():
        icfg = committee.inner_cfg(canonical_fault_cfg(STACK))
        flat = jax.jit(runner.make_dyn_sim_fn(icfg))
        keys = committee._committee_keys(jax.random.key(SEED), C)
        return [jax.tree.map(np.asarray, flat(
            keys[i], jnp.int32(0), jnp.int32(0))) for i in range(C)]

    stacked = _one_tile(STACK, monkeypatch, most)(
        jax.random.key(SEED), jnp.int32(0), jnp.int32(0))
    assert committee.ran_as(STACK) == plan
    flats = shared("zzraft_crash.flats", build_flats)
    for i, flat in enumerate(flats):
        got = jax.tree.map(lambda x: x[i], stacked)
        assert got.crash_tick is not None and flat.crash_tick is not None
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(flat),
                        strict=True):
            assert a.dtype == b.dtype and np.array_equal(a, b), i
    # something happened in them: both kills fell in every group, at the
    # group's own phase, and nearly all hit a leader and were replaced
    ticks = np.stack([f.crash_tick for f in flats])
    phases = np.asarray([int(f.crash_phase) for f in flats])
    assert (ticks == 300 + phases[:, None] + [0, 300]).all()
    assert len(set(phases.tolist())) > 1
    assert sum(int((f.replaced_tick >= 0).sum()) for f in flats) >= C


# ------------------------------------------------------- (d) the metrics


def _hand_state(**fields):
    """A 3-node group's final state under a schedule of two kills, every
    leaf at its start but ``fields``."""
    cfg = SimConfig(protocol="raft", raft_terms=True, n=3,
                    model_serialization=False, sim_ms=3000,
                    faults=FaultConfig(crashes=2, first_ms=1000,
                                       period_ms=1000, downtime_ms=500))
    state, _ = raft.init(cfg)
    return cfg, state.replace(**{
        k: np.asarray(v, np.asarray(getattr(state, k)).dtype)
        for k, v in fields.items()})


SCHEDULE_KEYS = (
    "crashes", "crashes_found_no_leader", "crashes_unreplaced", "failovers",
    "failovers_multi_election", "failover_mean_ms", "failover_median_ms",
    "failover_p90_ms", "failover_max_ms", "restarts", "dead_acts",
    "double_votes", "crash0_failover_ms", "crash0_elections",
    "crash1_failover_ms", "crash1_elections")
HAND = {
    # node 0 led term 1 and was killed at 1,400; one timer has fired since,
    # nobody has won yet, the second kill has not fallen
    "crashed-not-replaced-yet": (dict(
        term=[1, 2, 1], alive=[False, True, True], leader_tick=[200, -1, -1],
        won_tick=[200, -1, -1], lead_term0=[1, 0, 0], last_hb=[1350, -1, -1],
        elections=[1, 1, 0], crash_tick=[1400, -1], crash_node=[0, -1],
        crash_elections=[1, 0], restart_tick=[1900, raft.DISARM, raft.DISARM]),
        dict(crashes=1, crashes_found_no_leader=0, crashes_unreplaced=1,
             failovers=0, failovers_multi_election=0, failover_mean_ms=-1.0,
             failover_median_ms=-1.0, failover_p90_ms=-1.0,
             failover_max_ms=-1.0, restarts=0, dead_acts=0, double_votes=0,
             crash0_failover_ms=-1.0, crash0_elections=1,
             crash1_failover_ms=-1.0, crash1_elections=0)),
    # killed at 1,400, replaced at 1,890 after a split vote (two timers),
    # back at 1,900; the second leader killed at 2,400, replaced at 2,560
    "replaced-after-two-elections": (dict(
        term=[4, 4, 4], is_leader=[False, False, True],
        leader_tick=[200, 1890, 2560], won_tick=[200, 1890, 2560],
        lead_term0=[1, 3, 4], last_hb=[1350, 2390, 2935],
        elections=[1, 2, 1], step_downs=[0, 1, 0],
        crash_tick=[1400, 2400], crash_node=[0, 1],
        replaced_tick=[1890, 2560], crash_elections=[2, 1],
        restarts=[1, 0, 0], restart_tick=[raft.DISARM, 2900, raft.DISARM],
        alive=[True, False, True]),
        dict(crashes=2, crashes_found_no_leader=0, crashes_unreplaced=0,
             failovers=2, failovers_multi_election=1, failover_mean_ms=325.0,
             failover_median_ms=325.0, failover_p90_ms=490.0,
             failover_max_ms=490.0, restarts=1, dead_acts=0, double_votes=0,
             crash0_failover_ms=490.0, crash0_elections=2,
             crash1_failover_ms=160.0, crash1_elections=1)),
    # the first kill was replaced only at 2,450, so the second, at 2,400,
    # found no leader and killed nobody; an oracle counted twice
    "a-crash-that-found-no-leader": (dict(
        term=[5, 5, 5], is_leader=[False, True, False],
        leader_tick=[200, 2450, -1], won_tick=[200, 2450, -1],
        lead_term0=[1, 5, 0], last_hb=[1350, 2975, -1], elections=[1, 3, 2],
        crash_tick=[1400, 2400], crash_node=[0, -1], replaced_tick=[-1, -1],
        crash_elections=[4, 0], restarts=[1, 0, 0], dead_acts=[0, 0, 2]),
        dict(crashes=2, crashes_found_no_leader=1, crashes_unreplaced=1,
             failovers=0, failovers_multi_election=0, failover_mean_ms=-1.0,
             failover_median_ms=-1.0, failover_p90_ms=-1.0,
             failover_max_ms=-1.0, restarts=1, dead_acts=2, double_votes=0,
             crash0_failover_ms=-1.0, crash0_elections=4,
             crash1_failover_ms=-1.0, crash1_elections=0)),
}


def test_metrics_under_a_schedule_of_hand_written_states():
    """``raft.metrics_stacked`` over the three states at once is
    ``raft.metrics`` of each alone, and both are what the state says, key
    by key; the keys of a run without a schedule come first, unchanged."""
    states = {name: _hand_state(**fields)
              for name, (fields, _) in HAND.items()}
    alone = {}
    for name, (cfg, state) in states.items():
        alone[name] = got = raft.metrics(cfg, state)
        assert {k: got[k] for k in SCHEDULE_KEYS} == HAND[name][1], name
        assert list(got)[-len(SCHEDULE_KEYS):] == list(SCHEDULE_KEYS)
        assert list(got)[:-len(SCHEDULE_KEYS)] == list(raft.metrics(
            *_terms_only(cfg, state)))
    cfg = next(iter(states.values()))[0]
    host = {f: np.stack([np.asarray(getattr(s, f))
                         for _, s in states.values()])
            for f in raft.METRIC_FIELDS}
    assert raft.metrics_stacked(cfg, host, 3) == list(alone.values())
    # the first state: node 0 is dead, so nobody leads; the second: the
    # leader at the end is the third to lead
    assert alone["crashed-not-replaced-yet"]["n_leaders"] == 0
    assert alone["replaced-after-two-elections"]["leader"] == 2
    assert alone["replaced-after-two-elections"]["term_final"] == 4


def _terms_only(cfg, state):
    """The same state without the schedule's leaves, under the same
    configuration without a schedule."""
    return (cfg.with_(faults=FaultConfig()),
            state.replace(**{f: None for f in raft.CRASH_FIELDS}))


# ------------------------------- (e) without a schedule nothing is there

TERMS = dict(protocol="raft", raft_terms=True, n=5, model_serialization=False)
KILLS = FaultConfig(crashes=2, first_ms=300, period_ms=300, downtime_ms=120)


def test_a_state_without_a_schedule_carries_no_leaf_for_it():
    off = jax.eval_shape(lambda: raft.init(
        SimConfig(**TERMS), jax.random.key(0)))[0]
    on = jax.eval_shape(lambda: raft.init(
        SimConfig(**TERMS, faults=KILLS), jax.random.key(0)))[0]
    assert all(getattr(off, f) is None for f in raft.CRASH_FIELDS)
    shapes = {f: getattr(on, f).shape for f in raft.CRASH_FIELDS}
    assert shapes == {
        "crash_phase": (), "crash_tick": (2,), "crash_node": (2,),
        "replaced_tick": (2,), "crash_elections": (2,), "restart_tick": (5,),
        "restarts": (5,), "voted_term": (5,), "dead_acts": (5,),
        "double_votes": (5,)}
    assert len(jax.tree.leaves(on)) - len(jax.tree.leaves(off)) \
        == len(raft.CRASH_FIELDS)
    assert "crash_tick" not in base.metric_leaves(SimConfig(**TERMS), off)
    assert "crash_tick" in base.metric_leaves(
        SimConfig(**TERMS, faults=KILLS), on)


def test_the_fault_scope_is_on_the_lowered_program_only_under_a_schedule():
    def names(cfg):
        text = runner.make_sim_fn.__wrapped__(cfg).lower(
            jax.random.key(0)).as_text(debug_info=True)
        return {s for s in raft.SCOPES if f"{s}/" in text}

    on = names(SimConfig(**TERMS, sim_ms=50, faults=KILLS))
    off = names(SimConfig(**TERMS, sim_ms=50))
    fault = {"raft.tick.fault", raft.FAULT_SCOPE}
    assert fault <= set(raft.SCOPES)
    assert on == set(raft.SCOPES) and off == set(raft.SCOPES) - fault


# ------------------------------------------------------- (f) the refusals

SCHEDULED = dict(**TERMS, faults=KILLS) | {"n": 16}


@pytest.mark.parametrize("over,names", (
    (dict(raft_terms=False), "raft_terms=False"),
    (dict(delivery="stat"), "delivery='stat'"),
    (dict(delivery="stat", schedule="round", n=4096), "raft_hb"),
    (dict(raft_terms=False, topology="gossip", delivery="stat"),
     "topology='gossip'"),
    (dict(topology="kregular", degree=4), "topology='kregular'"),
    (dict(queued_links=True, model_serialization=True), "queued_links"),
    (dict(raft_terms=False, protocol="mixed", n=32, mixed_shards=4),
     "protocol='mixed'"),
    (dict(raft_terms=False, protocol="pbft"), "protocol='pbft'"),
    (dict(raft_terms=False, protocol="paxos"), "protocol='paxos'"),
    (dict(raft_terms=False, fidelity="reference"), "fidelity='reference'"),
), ids=("terms-off", "stat", "raft_hb", "gossip", "kregular", "queued_links",
        "mixed", "pbft", "paxos", "reference-fidelity"))
def test_an_arm_that_cannot_run_a_schedule_refuses_it_by_name(over, names):
    """Where a program is built, the schedule's refusal speaks before that
    of terms: it names the arm whatever else is off."""
    cfg = SimConfig(**{**SCHEDULED, **over})
    builds = (runner.make_sim_fn.__wrapped__, runner.make_dyn_sim_fn)
    if cfg.protocol == "mixed":  # which has no dyn program to refuse it
        builds = builds[:1]
    for build in builds:
        with pytest.raises(NotImplementedError, match="crash schedule") as e:
            build(cfg)
        assert names in str(e.value) and "check_schedule" in str(e.value)


def test_a_mesh_axis_the_cpp_engine_and_a_bad_schedule_are_refused():
    from blockchain_simulator_tpu import engine

    cfg = SimConfig(**SCHEDULED)
    with pytest.raises(NotImplementedError, match="crash schedule") as e:
        raft.init(cfg.with_(mesh_axis="nodes"))
    assert "a mesh axis" in str(e.value)
    with pytest.raises(NotImplementedError, match="C\\+\\+ engine") as e:
        engine.run_cpp(cfg)
    assert "crash schedule" in str(e.value)
    # a node must be back before the next kill
    with pytest.raises(ValueError, match="downtime_ms"):
        FaultConfig(crashes=2, first_ms=0, period_ms=300, downtime_ms=300)
    with pytest.raises(ValueError, match="crashes"):
        FaultConfig(crashes=-1)
    # and stay down past every reply that was on its way to it
    with pytest.raises(ValueError, match="downtime_ms=10"):
        raft.init(SimConfig(**TERMS, faults=FaultConfig(
            crashes=1, first_ms=100, period_ms=300, downtime_ms=10)))


# ------------------------------------------------ counters and monitors

RUN = SimConfig(**TERMS, sim_ms=1000, raft_heartbeat_ms=75, link_delay_ms=7,
                faults=KILLS)


def test_metrics_under_a_schedule_count_crashes_and_failovers():
    names = telemetry.RAFT_COUNTERS
    assert names[4:] == ("raft.crashes", "raft.restarts", "raft.failovers",
                         "raft.crashes_no_leader")
    before = telemetry.metrics.snapshot()["counters"]
    m = runner.run_simulation(RUN, seed=3)
    after = telemetry.metrics.snapshot()["counters"]
    moved = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in names}
    assert moved == {
        "raft.groups": 1, "raft.term_bumps": m["term_final"],
        "raft.step_downs": m["step_downs"], "raft.term_conflicts": 0,
        "raft.crashes": 2, "raft.restarts": m["restarts"],
        "raft.failovers": m["failovers"],
        "raft.crashes_no_leader": m["crashes_found_no_leader"]}
    assert m["crashes"] == 2 and m["failovers"] >= 1
    assert m["term_final"] >= 1 + m["failovers"]
    assert m["dead_acts"] == m["double_votes"] == m["term_conflicts"] == 0
    # a flat run without a schedule moves none of the schedule's counters
    # and reports none of its keys
    plain = runner.run_simulation(RUN.with_(faults=FaultConfig()), seed=3)
    last = telemetry.metrics.snapshot()["counters"]
    assert all(last.get(k, 0.0) == after.get(k, 0.0) for k in names[4:])
    assert "crashes" not in plain and list(plain) == list(m)[:len(plain)]


def test_a_probed_run_under_a_schedule_is_the_plain_run():
    """obsim's raft monitors read a state whose ``alive`` moved during the
    run, and the schedule's oracles join ``viol_agreement``."""
    from blockchain_simulator_tpu.obsim import host as obsim_host

    m, summary = obsim_host.run_probed(RUN, seed=3)
    assert m == runner.run_simulation(RUN, seed=3)
    assert summary["violations"] == 0
    assert summary["monitors"]["viol_agreement"] == 0
    assert summary["monitors"]["viol_quorum"] == 0


def test_the_reference_imports_nothing_from_the_package():
    src = open(os.path.join(BENCH, "reference", "raft_crash_engine.py")).read()
    imports = [ln for ln in src.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import heapq",
                       "import random"]
