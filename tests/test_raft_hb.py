"""Raft heartbeat-blocked fast path (models/raft_hb.py) vs the tick engine.

Same contract family as tests/test_pbft_round.py: the fast path must
reproduce the tick engine's consensus milestones for every accepted
configuration, with commit ticks inside the +/-1 bucket-quantile jitter.
Post-completion election churn is a documented divergence (module
docstring): ``elections`` is compared only where the window ends before
replication completes.
"""

import pytest

from blockchain_simulator_tpu.runner import (
    make_sim_fn,
    run_simulation,
    use_round_schedule,
)
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

BASE = dict(protocol="raft", n=16, sim_ms=10_000, delivery="stat")

CONSENSUS = ("n_leaders", "leader", "leader_elected_ms", "blocks", "rounds",
             "agreement_ok")


def both(kw, seed=None):
    tick = run_simulation(SimConfig(**kw, schedule="tick"), seed=seed)
    hb = run_simulation(SimConfig(**kw, schedule="round"), seed=seed)
    return tick, hb


def test_default_run_matches_tick_engine_exactly():
    # reference defaults (serialized 20 KB proposals): the window ends at
    # 49/50 blocks, so there is no churn phase and EVERY metric must agree
    tick, hb = both(BASE)
    for k in CONSENSUS + ("elections",):
        assert hb[k] == tick[k], k
    assert tick["blocks"] == 49  # acks one heartbeat window behind (ser=54)
    assert abs(hb["last_block_ms"] - tick["last_block_ms"]) <= 1
    assert abs(hb["mean_block_interval_ms"]
               - tick["mean_block_interval_ms"]) <= 0.1


def test_serialization_off_completes_and_matches():
    # ser = 0: every ack bin lands inside its own heartbeat step (the
    # same-step injection path) and replication completes mid-window —
    # consensus milestones match; `elections` is churn-affected (docstring)
    kw = {**BASE, "sim_ms": 6000, "model_serialization": False}
    tick, hb = both(kw)
    for k in CONSENSUS:
        assert hb[k] == tick[k], k
    assert hb["blocks"] == 50
    assert abs(hb["last_block_ms"] - tick["last_block_ms"]) <= 1


def test_crash_faults_match():
    kw = {**BASE, "sim_ms": 8000, "faults": FaultConfig(n_crashed=5)}
    tick, hb = both(kw)
    for k in CONSENSUS:
        assert hb[k] == tick[k], k
    assert abs(hb["last_block_ms"] - tick["last_block_ms"]) <= 1


def test_byzantine_acks_match():
    # Byzantine followers flip SUCCESS acks to FAILED: the majority count
    # sees only honest acks; with 4 liars of 16, 11 honest followers + self
    # still clear the N/2+1 = 9 threshold in both engines
    kw = {**BASE, "sim_ms": 8000, "faults": FaultConfig(n_byzantine=4)}
    tick, hb = both(kw)
    for k in CONSENSUS:
        assert hb[k] == tick[k], k


def test_byzantine_majority_falls_back_to_tick_engine():
    # 9 liars of 16 flip election votes too: denials become grants and TWO
    # candidates win (the no-terms split brain raft.metrics documents).  The
    # handoff check sees n_leaders != 1, flags not-ok, and the traced cond
    # falls back to the tick engine — so the 'fast path' result must be the
    # tick engine's, bit for bit, on every metric (the checked-handoff
    # contract: never silently wrong).  seed=1: the election race is PRNG-
    # dependent and this jax's draws split the default seed's election
    # cleanly instead (covered by the crash/byz tests above); seed 1 splits.
    kw = {**BASE, "sim_ms": 6000, "seed": 1,
          "faults": FaultConfig(n_byzantine=9)}
    tick, hb = both(kw)
    assert hb == tick
    assert tick["n_leaders"] == 2
    assert not tick["agreement_ok"]


def test_milestones_match_across_seeds():
    # the seed is the key operand of ONE pair of programs (the default
    # run's), not a field of three more configurations to compile
    for seed in (3, 11, 42):
        tick, hb = both(BASE, seed=seed)
        for k in CONSENSUS + ("elections",):
            assert hb[k] == tick[k], (seed, k)


def test_small_proposal_delay_falls_back_disarm_regression():
    # ADVICE r5 (high): with raft_proposal_delay_ms=50 setProposal fires
    # INSIDE the election prefix, leaving proposal_tick = DISARM (1<<30) at
    # the handoff — which trivially satisfies the old not-yet-proposing
    # check `proposal_tick[lead] > t_e + hb` and made phase 2 never propose
    # (1 block vs 49, silently wrong).  The ok-check now rejects DISARM and
    # the traced cond falls back to the tick engine: EVERY metric must be
    # the tick engine's, bit for bit.
    kw = {**BASE, "raft_proposal_delay_ms": 50}
    tick, hb = both(kw)
    assert hb == tick
    assert tick["blocks"] == 49  # proposals really ran (not the 1-block bug)


ROUND_4S = SimConfig(**{**BASE, "sim_ms": 4000}, schedule="round")


def _solo_round_4s(shared):
    """Seeds 0, 1, 2 of the unsharded fast path, one run of the suite
    (tests/conftest.py ``shared``): the sweep and the sharded test below
    both compare against them, each from the worker it lands on."""
    return shared("raft_hb.round-4s", lambda: [
        run_simulation(ROUND_4S, seed=s) for s in (0, 1, 2)])


def test_round_schedule_vmaps_in_seed_sweeps(shared):
    # the traced handoff (lax.cond) must lower under vmap: a batched
    # round-schedule sweep returns exactly the per-seed single runs
    from blockchain_simulator_tpu.parallel.sweep import run_seed_sweep

    assert run_seed_sweep(ROUND_4S, [0, 1, 2]) == _solo_round_4s(shared)


def test_schedule_resolution_and_gates():
    big = SimConfig(**{**BASE, "n": 8192})
    assert use_round_schedule(big)                      # auto at n >= 4096
    assert not use_round_schedule(SimConfig(**BASE))    # n < 4096 -> tick
    assert use_round_schedule(SimConfig(**BASE, schedule="round"))
    with pytest.raises(ValueError, match="raft"):
        make_sim_fn(SimConfig(**{**BASE, "delivery": "edge"},
                              schedule="round"))
    with pytest.raises(ValueError, match="raft"):
        make_sim_fn(SimConfig(**BASE, schedule="round",
                              fidelity="reference"))
    with pytest.raises(ValueError, match="raft"):
        make_sim_fn(SimConfig(**BASE, schedule="round",
                              faults=FaultConfig(drop_prob=0.01)))


def test_sharded_round_schedule_matches_sharded_tick_and_unsharded(shared):
    """The heartbeat fast path under shard_map (the handoff reductions ride
    psum/pmax; the steady scan is replicated O(1) work).  Contract: the
    sharded round schedule must reproduce the sharded tick engine's
    consensus milestones bit-for-bit — the prefix IS the sharded tick
    engine, and ack counts are deterministic — and, at this operating point,
    the unsharded fast path's full metrics dict as well (the election
    settles identically under the shard-folded delay keys)."""
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.shard import run_sharded

    cfg = ROUND_4S
    mesh = make_mesh(n_node_shards=4)
    m_round = run_sharded(cfg, mesh)
    m_tick = run_sharded(cfg.with_(schedule="tick"), mesh)
    for k in CONSENSUS + ("elections",):
        assert m_round[k] == m_tick[k], k
    # bit-equal to the unsharded fast path
    assert m_round == _solo_round_4s(shared)[cfg.seed]
