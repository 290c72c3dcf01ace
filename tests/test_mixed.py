"""Mixed-protocol shard sim tests (BASELINE config 5: raft shards with
cross-shard PBFT finality — a capability the reference lacks entirely)."""

import numpy as np
import pytest

from blockchain_simulator_tpu import SimConfig, run_simulation
from blockchain_simulator_tpu.runner import final_state
from blockchain_simulator_tpu.utils.config import FaultConfig


CFG = SimConfig(protocol="mixed", n=48, mixed_shards=8, sim_ms=3000)


def test_mixed_end_to_end():
    # seed=1: the 6-node shard elections are a PRNG race, and the outcome is
    # jax-version dependent (seed 0's shard 3 loses its first election on
    # this jax's draws and only re-elects at ~2.2 s — past the proposal
    # horizon, which also starves the all-nodes finality count below).  Seed
    # 1 settles every shard by ~200 ms, the operating point this end-to-end
    # pin is about.
    m = run_simulation(CFG.with_(seed=1))
    # every shard elects a raft leader and replicates blocks internally
    assert m["shards_with_leader"] == 8
    assert m["raft_blocks_min"] >= 20
    # the cross-shard PBFT layer finalizes all 40 global blocks
    assert m["global_blocks_final"] == 40
    assert m["agreement_ok"]
    # global finality waits for shard elections (~200 ms) at the start
    assert 0 < m["global_mean_ttf_ms"] < 1000


def test_mixed_determinism():
    assert run_simulation(CFG) == run_simulation(CFG)


def test_mixed_shard_streams_independent():
    st = final_state(CFG)
    # distinct per-shard PRNG streams: election outcomes differ across shards
    lt = np.asarray(st.raft.leader_tick).max(axis=1)
    assert len(set(lt.tolist())) > 1


def test_mixed_membership_follows_raft_health():
    # crash a majority inside every shard: no shard can elect, the PBFT layer
    # has no quorum, nothing finalizes
    cfg = CFG.with_(faults=FaultConfig(n_crashed=4), sim_ms=1500)
    m = run_simulation(cfg)
    assert m["shards_with_leader"] == 0
    assert m["global_blocks_final"] == 0


def test_mixed_minority_shard_crashes_tolerated():
    # 1 crashed node per shard (faults apply within each shard): elections
    # still succeed and global consensus proceeds
    cfg = CFG.with_(faults=FaultConfig(n_crashed=1), sim_ms=3000)
    m = run_simulation(cfg)
    assert m["shards_with_leader"] == 8
    assert m["global_blocks_final"] >= 30
    assert m["agreement_ok"]


def test_mixed_validation():
    with pytest.raises(ValueError, match="divisible"):
        run_simulation(SimConfig(protocol="mixed", n=50, mixed_shards=8, sim_ms=100))
    with pytest.raises(ValueError, match="shard size"):
        run_simulation(SimConfig(protocol="mixed", n=16, mixed_shards=8, sim_ms=100))


def test_mixed_sharded_shard_count_validated():
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.shard import run_sharded

    with pytest.raises(ValueError, match="mixed_shards"):
        run_sharded(CFG.with_(mixed_shards=6, n=48), make_mesh(n_node_shards=4))


STAT = CFG.with_(delivery="stat", model_serialization=False)


# the benchmark cell's rehearsal size (benchmark/configs/mixed-raft256x1k-
# pbft.json: 8 shards of 256 nodes), cut to a window that holds the election
# prefix and the first Raft commits
REHEARSAL = STAT.with_(n=2048, sim_ms=1500)


def fast_and_tick(shared, name, cfg):
    """(fast path's metrics, tick engine's) of ``cfg``, one pair of compiles
    a run of the suite (tests/conftest.py ``shared``; the fast path alone
    compiles for over a minute on XLA:CPU and runs in a third of a second)."""
    return shared(f"mixed.{name}", lambda: (
        run_simulation(cfg), run_simulation(cfg.with_(schedule="tick"))))


@pytest.mark.parametrize("cfg,final", [(STAT, 40), (REHEARSAL, 26)],
                         ids=["8x6", "8x256"])
def test_mixed_fast_path_matches_tick_engine(shared, request, cfg, final):
    # stat delivery makes the raft shards heartbeat-schedulable: schedule
    # 'auto' resolves to the fast path (mixed.scan_fast), whose metrics must
    # equal the per-tick engine's exactly — the PBFT layer steps with
    # identical keys/alive masks and raft counts follow the raft_hb bit
    # contract
    import jax

    from blockchain_simulator_tpu.models import mixed
    from blockchain_simulator_tpu.runner import use_round_schedule

    assert use_round_schedule(cfg)
    assert not use_round_schedule(CFG)  # edge delivery stays per-tick
    m_fast, m_tick = fast_and_tick(shared, request.node.callspec.id, cfg)
    # raft commit TICKS carry the +/-1 bucket-quantile jitter of the two
    # engines' independent draws (raft_hb's milestone contract); every
    # other key is equal
    tail = "raft_commit_tail_ms_max"
    assert abs(m_fast.pop(tail) - m_tick.pop(tail)) <= 1
    assert m_fast == m_tick
    assert m_fast["global_blocks_final"] == final
    assert m_fast["shards_with_leader"] == 8
    assert m_fast["raft_blocks_min"] >= 7
    # the quiet prefix: no shard commits before the handoff (proposals start
    # 1 s after an election), so raft.step's commit gate never takes its
    # trip there and the table reaches the handoff as init made it
    key = jax.random.key(cfg.seed)
    state, bufs = mixed.init(cfg, jax.random.fold_in(key, 0x1217))
    (st, _), ok_all, h_s = jax.jit(
        lambda s, b: mixed.prefix_handoff(cfg, s, b, key))(state, bufs)
    assert bool(ok_all)
    assert (np.asarray(h_s.bn0) == 0).all()
    assert (np.asarray(st.raft.block_tick) == -1).all()


def test_mixed_fast_path_crash_majority_falls_back():
    # no shard can elect: every per-shard handoff fails, the traced cond
    # continues the per-tick engine from the prefix carry — bit-identical
    cfg = STAT.with_(faults=FaultConfig(n_crashed=4), sim_ms=1500)
    assert run_simulation(cfg) == run_simulation(cfg.with_(schedule="tick"))


def test_mixed_fast_path_explicit_round_gates():
    import jax
    import pytest as _pytest

    from blockchain_simulator_tpu.runner import make_sim_fn

    with _pytest.raises(ValueError, match="mixed"):
        make_sim_fn(CFG.with_(schedule="round"))  # edge delivery: ineligible

    # the explicit schedule IS the program 'auto' resolves to, lowered text
    # for lowered text (so every metric of it is that run's: the test above
    # holds them), and not the tick engine's; a lowering is seconds where
    # the compile it used to take is over a minute
    def text(schedule):
        return jax.jit(make_sim_fn(STAT.with_(schedule=schedule))).lower(
            jax.random.key(0)).as_text()

    assert text("round") == text("auto") != text("tick")


def test_mixed_fast_path_sharded_matches_unsharded(shared):
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.shard import run_sharded

    # per-shard steady-scan keys fold the GLOBAL shard id, so the sharded
    # fast path is bit-identical to the single-device fast path
    m8 = run_sharded(STAT, make_mesh(n_node_shards=8))
    assert m8 == fast_and_tick(shared, "8x6", STAT)[0]


def test_mixed_sharded_matches_unsharded():
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.shard import run_sharded
    from blockchain_simulator_tpu.runner import run_simulation

    cfg = SimConfig(protocol="mixed", n=48, mixed_shards=8, sim_ms=2000)
    m1 = run_simulation(cfg)
    # raft shards row-shard over the mesh; per-shard PRNG keys on the GLOBAL
    # shard id and the replicated PBFT layer uses unsharded keys, so the
    # sharded run is bit-identical to the single-device run
    m8 = run_sharded(cfg, make_mesh(n_node_shards=8))
    assert m8 == m1
    assert m1["global_blocks_final"] > 0
