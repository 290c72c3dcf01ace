"""Round-blocked PBFT fast path (models/pbft_round.py) vs the tick engine.

The fast path must reproduce the tick engine's milestones for every accepted
configuration: same rounds/finality counts (delivery is an aggregate model in
both, so counts match exactly under no faults), same view-change sequence
(the VC draw uses the identical PRNG channel at each block tick), and
time-to-finality within the delay distribution's tick-quantization slack.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pbft_round_stacked as stacked  # tests/: the round as it stood before PR 45
from blockchain_simulator_tpu.models import pbft_round
from blockchain_simulator_tpu.runner import make_sim_fn, run_simulation, use_round_schedule
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

# serialization off: the round fast path requires rounds to be closed waves
BASE = dict(protocol="pbft", n=64, sim_ms=2500, delivery="stat",
            model_serialization=False)

MILESTONES = ("rounds_sent", "blocks_final_all_nodes", "view_changes",
              "block_num_max", "agreement_ok")


def both(cfg_kw, seed=None):
    tick = run_simulation(SimConfig(**cfg_kw, schedule="tick"), seed=seed)
    rnd = run_simulation(SimConfig(**cfg_kw, schedule="round"), seed=seed)
    return tick, rnd


@pytest.mark.parametrize("fidelity", ["clean", "reference"])
def test_milestones_match_tick_engine(fidelity):
    tick, rnd = both(dict(**BASE, fidelity=fidelity))
    for k in MILESTONES:
        assert rnd[k] == tick[k], k
    assert abs(rnd["mean_time_to_finality_ms"] - tick["mean_time_to_finality_ms"]) < 3.0
    assert abs(rnd["last_commit_ms"] - tick["last_commit_ms"]) <= 50.0


def test_crash_faults_match():
    kw = dict(**BASE, faults=FaultConfig(n_crashed=8))
    tick, rnd = both(kw)
    for k in MILESTONES:
        assert rnd[k] == tick[k], k


def test_byzantine_slows_but_commits_under_2f1():
    kw = dict(**BASE, quorum_rule="2f1", faults=FaultConfig(n_byzantine=21))
    tick, rnd = both(kw)
    for k in MILESTONES:
        assert rnd[k] == tick[k], k
    assert rnd["agreement_ok"]


def test_byzantine_majority_stalls_both():
    # 40 Byzantine of 64: honest voters (24) < N/2 prepare quorum -> no commits
    kw = dict(**BASE, faults=FaultConfig(n_byzantine=40))
    tick, rnd = both(kw)
    assert tick["blocks_final_all_nodes"] == 0
    assert rnd["blocks_final_all_nodes"] == 0


def test_quorum_starved_stalls_both():
    # crash 6 of 8 (crashes take the last ids, leader 0 stays alive): the two
    # survivors cannot reach the N/2 prepare quorum -> no finality either way
    kw = dict(BASE, n=8, faults=FaultConfig(n_crashed=6))
    tick, rnd = both(kw)
    assert rnd["blocks_final_all_nodes"] == tick["blocks_final_all_nodes"] == 0


def test_truncated_final_wave_matches():
    # sim window ends 15 ticks after the last block tick: the tick engine
    # sends that round (rounds_sent counts it, its view-change die is cast)
    # but its commit wave is cut mid-flight; the round path must reproduce
    # the same truncation, not drop the round.
    #
    # Contract pinned here (root cause of the former exact-equality failure,
    # round 3): per-slot COUNTS are bit-equal between engines — delivery in
    # both is the same aggregate model, so every message lands exactly once —
    # but the *tick* of the last arrival inside a wave is drawn with per-round
    # keys on the fast path vs per-tick [N, W]-shaped keys on the tick engine,
    # so it carries +/-1-tick tail jitter in EVERY round (both directions; not
    # a truncation bug — reproducing the tick engine's draws bit-for-bit would
    # need the very O(N*W)-shaped per-tick sampling the fast path removes).
    import numpy as np

    from blockchain_simulator_tpu.runner import final_state

    kw = dict(BASE, sim_ms=2465, pbft_max_rounds=60)
    tick, rnd = both(kw)
    for k in MILESTONES:
        assert rnd[k] == tick[k], k
    assert abs(rnd["last_commit_ms"] - tick["last_commit_ms"]) <= 2.0
    st_t = final_state(SimConfig(**kw, schedule="tick"))
    st_r = final_state(SimConfig(**kw, schedule="round"))
    np.testing.assert_array_equal(st_r.slot_commits, st_t.slot_commits)
    np.testing.assert_array_equal(st_r.slot_propose_tick, st_t.slot_propose_tick)
    # the final proposed slot (block tick 2450, wave cut at 2465) must be
    # proposed-but-uncommitted in BOTH engines
    pt = np.asarray(st_t.slot_propose_tick)
    last_slot = int(np.nonzero(pt < np.iinfo(np.int32).max)[0].max())
    assert pt[last_slot] == 2450
    assert int(np.asarray(st_t.slot_commits)[last_slot]) == 0
    assert int(np.asarray(st_r.slot_commits)[last_slot]) == 0
    # committed slots' finality ticks agree within the tail jitter
    ct_t = np.asarray(st_t.slot_commit_tick)
    ct_r = np.asarray(st_r.slot_commit_tick)
    done = np.asarray(st_t.slot_commits) > 0
    assert int(np.abs(ct_t - ct_r)[done].max()) <= 1


def test_drops_on_round_path_match_tick_engine():
    # drops are eligible when view changes are off (single leader forever).
    # Thinning draws are independent between engines, but the N/2 thresholds
    # make moderate drops outcome-deterministic: p=0.05 keeps every wave far
    # above quorum (~57 of the needed 32/33 votes) -> 40/40 in both engines;
    # p=0.4 starves the prepare quorum (~23 expected replies) -> 0 in both.
    for p, want in ((0.05, 40), (0.4, 0)):
        kw = dict(**BASE, pbft_view_change_num=0,
                  faults=FaultConfig(drop_prob=p))
        tick, rnd = both(kw)
        assert tick["blocks_final_all_nodes"] == want, p
        assert rnd["blocks_final_all_nodes"] == want, p
        assert rnd["rounds_sent"] == tick["rounds_sent"] == 40
        assert rnd["agreement_ok"] and tick["agreement_ok"]
        if want:
            assert abs(rnd["mean_time_to_finality_ms"]
                       - tick["mean_time_to_finality_ms"]) < 4
    # drops + view changes stays on the tick engine
    assert not use_round_schedule(
        SimConfig(**BASE, faults=FaultConfig(drop_prob=0.05)).with_(n=8192))
    # drops + windowed vote table too (the tick engine's stale-tenant /
    # unattributed bookkeeping has no round-path counterpart)
    assert not use_round_schedule(
        SimConfig(**BASE, pbft_view_change_num=0,
                  faults=FaultConfig(drop_prob=0.05)).with_(
                      n=8192, pbft_window=8))


def test_schedule_round_rejects_ineligible():
    with pytest.raises(ValueError, match="schedule='round'"):
        make_sim_fn(SimConfig(**BASE, schedule="round",
                              faults=FaultConfig(drop_prob=0.01)))
    with pytest.raises(ValueError, match="schedule='round'"):
        make_sim_fn(SimConfig(protocol="pbft", n=64, sim_ms=2500,
                              delivery="edge", schedule="round"))


def test_auto_resolution():
    small = SimConfig(**BASE)
    big = SimConfig(protocol="pbft", n=8192, sim_ms=2500, delivery="stat",
                    model_serialization=False)
    dropped = big.with_(faults=FaultConfig(drop_prob=0.01))
    serialized = big.with_(model_serialization=True)
    assert not use_round_schedule(small)   # n < 4096 -> tick
    assert use_round_schedule(big)
    assert not use_round_schedule(dropped)     # ineligible -> tick
    # at the 50 ms reference interval, ser=134 > interval: waves span rounds
    assert not use_round_schedule(serialized)
    # raising the interval alone CANNOT help: the reference's block size
    # scales with the interval (num = tx_speed/(1000/timeout),
    # pbft-node.cc:377), and at 1000 tx/s x 1 KB the offered load (8 Mbit/s)
    # exceeds the 3 Mbps link, so ser grows faster than the interval
    assert not use_round_schedule(
        serialized.with_(pbft_block_interval_ms=200, sim_ms=8000))
    # a sustainable tx rate (300 tx/s = 2.4 Mbit/s < 3 Mbps) with the interval
    # past ser + horizon closes the rounds again: ser=160, offset<=32, <200
    ser_wide = serialized.with_(pbft_block_interval_ms=200, pbft_tx_speed=300,
                                sim_ms=8000)
    assert use_round_schedule(ser_wide)


def test_serialization_offset_matches_tick_engine():
    # Constant block-serialization latency (model_serialization=True) with the
    # interval widened past ser + horizon: the fast path must shift the whole
    # wave by ser and reproduce the tick engine's milestones AND per-slot
    # finality ticks (same +/-1 tail-jitter contract as the ser=0 case).
    import numpy as np

    from blockchain_simulator_tpu.runner import final_state

    kw = dict(protocol="pbft", n=64, sim_ms=4200, delivery="stat",
              model_serialization=True, pbft_block_interval_ms=200,
              pbft_tx_speed=300)
    ser = SimConfig(**kw).serialization_ticks(SimConfig(**kw).pbft_block_bytes)
    assert ser == 160  # 60 KB at 3 Mbps (blockchain-simulator.cc:22-24)
    tick, rnd = both(kw)
    for k in MILESTONES:
        assert rnd[k] == tick[k], k
    # commits land ser later than the propose tick: ttf must exceed ser
    assert rnd["mean_time_to_finality_ms"] > ser
    assert abs(rnd["mean_time_to_finality_ms"] - tick["mean_time_to_finality_ms"]) < 3.0
    st_t = final_state(SimConfig(**kw, schedule="tick"))
    st_r = final_state(SimConfig(**kw, schedule="round"))
    np.testing.assert_array_equal(st_r.slot_commits, st_t.slot_commits)
    np.testing.assert_array_equal(st_r.slot_propose_tick, st_t.slot_propose_tick)
    ct_t = np.asarray(st_t.slot_commit_tick)
    ct_r = np.asarray(st_r.slot_commit_tick)
    done = np.asarray(st_t.slot_commits) > 0
    assert done.any()
    assert int(np.abs(ct_t - ct_r)[done].max()) <= 1


def test_serialization_truncated_wave_matches():
    # window end falls INSIDE the ser-shifted wave (block tick 4000, wave
    # spans [4166, 4192]): both engines must truncate identically
    kw = dict(protocol="pbft", n=64, sim_ms=4180, delivery="stat",
              model_serialization=True, pbft_block_interval_ms=200,
              pbft_tx_speed=300, pbft_max_rounds=60)
    tick, rnd = both(kw)
    for k in MILESTONES:
        assert rnd[k] == tick[k], k


def test_milestones_match_across_seeds():
    # the bit-equal milestone contract must hold for EVERY seed, not the
    # default one — a seed-dependent divergence (e.g. a view-change pattern
    # only some keys produce) would slip past the single-seed pins above
    # (the seed is the key operand of ONE pair of programs, not a field of
    # four more configurations to compile)
    for seed in (1, 7, 23, 1217):
        tick, rnd = both(BASE, seed=seed)
        for k in MILESTONES:
            assert rnd[k] == tick[k], (seed, k)


def test_exact_sampler_round_mode():
    # stat_sampler="exact" must work on the fast path too (auto picks normal
    # only at large n; force both and compare milestones)
    a = run_simulation(SimConfig(**BASE, schedule="round", stat_sampler="exact"))
    b = run_simulation(SimConfig(**BASE, schedule="round", stat_sampler="normal"))
    for k in MILESTONES:
        assert a[k] == b[k], k


# --------------------------------------------------------------------------- #
# the row form (PR 45): every per-bucket quantity of a round is B rows [N],   #
# never a [B, N] array assembled from them                                    #
# --------------------------------------------------------------------------- #

_SER = dict(sim_ms=4200, model_serialization=True, pbft_block_interval_ms=200,
            pbft_tx_speed=300)
ROW_CASES = {
    "no_faults": dict(pbft_view_change_num=0),
    "crash_faults": dict(faults=FaultConfig(n_crashed=8)),
    "drops_no_view_change": dict(pbft_view_change_num=0,
                                 faults=FaultConfig(drop_prob=0.05)),
    "serialization_offset": _SER,
    "truncated_final_wave": dict(sim_ms=2465, pbft_max_rounds=60),
    # 200 rounds at upstream's 1/100: both seeds below change view
    "view_changes_1_100": dict(sim_ms=10100, pbft_max_rounds=200,
                               pbft_max_slots=208),
}


def _finals(monkeypatch, cfg, step, keys, wrap=lambda sim: sim):
    """The final states, one a key, of the ONE round program of ``cfg`` built
    around ``step`` (the engine's ``step_round`` or the stacked
    reference's), outside the registry, which would hand one form's program
    to the other."""
    monkeypatch.setattr(pbft_round, "step_round", step)

    def sim(key):
        state, _ = pbft_round.init(cfg, key)
        return pbft_round.scan_rounds(cfg, state, key)

    run = jax.jit(wrap(sim))
    return [jax.block_until_ready(run(key)) for key in keys]


def _final(monkeypatch, cfg, step, wrap=lambda sim: sim, key=None):
    key = jax.random.key(cfg.seed) if key is None else key
    return _finals(monkeypatch, cfg, step, [key], wrap)[0]


def _assert_leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) == 12
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("seed", [3, 4])
@pytest.mark.parametrize("case", list(ROW_CASES))
@pytest.mark.parametrize("sampler", ["normal", "exact"])
@pytest.mark.parametrize("fidelity", ["clean", "reference"])
def test_rows_bit_equal_to_stacked(monkeypatch, shared, fidelity, sampler,
                                   case, seed):
    cfg = SimConfig(**{**BASE, **ROW_CASES[case]}, schedule="round",
                    fidelity=fidelity, stat_sampler=sampler)
    assert pbft_round.eligible(cfg)

    def build():
        # the seed is the key operand of one pair of programs (a compile
        # each, ten times a run): whichever seed's case asks first runs
        # both seeds through the pair, once a run of the suite
        # (tests/conftest.py ``shared``), and hands over host arrays
        keys = [jax.random.key(s) for s in (3, 4)]
        step = pbft_round.step_round
        want = _finals(monkeypatch, cfg, stacked.step_round, keys)
        got = _finals(monkeypatch, cfg, step, keys)
        return {s: jax.tree.map(np.asarray, pair)
                for s, pair in zip((3, 4), zip(got, want))}

    got, want = shared(
        f"pbft_round.rows.{fidelity}.{sampler}.{case}", build)[seed]
    _assert_leaves_equal(got, want)
    # the case does what its name says: blocks commit (none is a dead run),
    # and the view-change case changes view
    assert int(np.asarray(got.slot_commits).sum()) > 0
    if case == "view_changes_1_100":
        assert int(np.asarray(got.view_changes).sum()) > 0


def test_rows_bit_equal_under_mesh_axis(monkeypatch):
    # two node shards: _psum / _pmax over rows, one collective for `totals`
    from blockchain_simulator_tpu.parallel import shard
    from blockchain_simulator_tpu.parallel.mesh import make_mesh

    cfg = SimConfig(**BASE, schedule="round", stat_sampler="normal",
                    faults=FaultConfig(n_crashed=8), seed=5)
    mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
    step, out = pbft_round.step_round, {}
    for name, fn in (("stacked", stacked.step_round), ("rows", step)):
        monkeypatch.setattr(pbft_round, "step_round", fn)
        sim = shard._make_sharded_round_fn.__wrapped__(cfg, mesh)
        out[name] = jax.block_until_ready(sim(jax.random.key(cfg.seed)))
    _assert_leaves_equal(out["rows"], out["stacked"])
    assert int(np.asarray(out["rows"].slot_commits).sum()) > 0


def test_rows_bit_equal_under_lane_batch(monkeypatch):
    # the lane-batched round sweep (parallel/sweep._batched_fn): four seeds
    # as lanes of one program
    from blockchain_simulator_tpu.models.base import lane_vmap

    cfg = SimConfig(**BASE, schedule="round", stat_sampler="normal",
                    fidelity="reference")
    keys = jax.vmap(jax.random.key)(jnp.arange(4, dtype=jnp.uint32))
    step = pbft_round.step_round
    want = _final(monkeypatch, cfg, stacked.step_round, lane_vmap, keys)
    got = _final(monkeypatch, cfg, step, lane_vmap, keys)
    _assert_leaves_equal(got, want)
    assert np.asarray(got.slot_commits).shape[0] == 4


def test_round_program_assembles_no_bucket_array():
    # the lowered text (jax's own StableHLO, the same on every platform) of
    # the round program at n = 4,096: no concatenate and no pad whose result
    # has n as its minor dimension (six and six before PR 45); the PRNG
    # keys' own tensor<2xui32> concatenates stay
    n = 4096
    cfg = SimConfig(protocol="pbft", n=n, sim_ms=1100, delivery="stat",
                    model_serialization=False, schedule="round")
    assert cfg.eff_stat_sampler == "normal" and cfg.fidelity == "clean"

    def sim(key):
        state, _ = pbft_round.init(cfg, key)
        return pbft_round.scan_rounds(cfg, state, key)

    text = jax.jit(sim).lower(jax.random.key(0)).as_text()
    wide = re.compile(
        r"stablehlo\.(concatenate|pad)\b.*->\s*tensor<(?:\d+x)*%dx\w+>" % n)
    found = [m.group(1) for m in map(wide.search, text.splitlines()) if m]
    assert found == [], found
    assert "stablehlo.concatenate" in text  # the keys' own


def _rows(*cols):
    """B rows [N] from N columns of B arrivals each."""
    return [jnp.asarray(r, jnp.int32) for r in zip(*cols)]


@pytest.mark.parametrize("clean", [True, False])
def test_crossing_loop_by_hand(clean):
    # need = 5 over four buckets; one node a column
    rows = _rows(
        (5, 0, 0, 0),   # crosses in the first bucket
        (1, 1, 1, 2),   # crosses in the last
        (1, 1, 1, 1),   # never
        (0, 0, 7, 0),   # one batch past the threshold, mid-wave
        (4, 0, 0, 1),   # quiet buckets hold the count: crosses on arrival
    )
    crossed, n_cross, first = pbft_round._crossing_loop(rows, 5, clean)
    assert len(crossed) == 4 and all(c.dtype == bool for c in crossed)
    np.testing.assert_array_equal(
        np.stack(crossed).T,
        [[1, 0, 0, 0], [0, 0, 0, 1], [0, 0, 0, 0], [0, 0, 1, 0], [0, 0, 0, 1]])
    np.testing.assert_array_equal(n_cross, [1, 1, 0, 1, 1])
    np.testing.assert_array_equal(first, [0, 3, 4, 2, 3])


def test_crossing_loop_two_crossings():
    # 3, 3, 3, 3 against need = 5: the reference resets on a crossing and
    # crosses again; clean latches the first
    rows = _rows((3, 3, 3, 3), (6, 0, 6, 0), (2, 2, 0, 0))
    crossed, n_cross, first = pbft_round._crossing_loop(rows, 5, False)
    np.testing.assert_array_equal(
        np.stack(crossed).T, [[0, 1, 0, 1], [1, 0, 1, 0], [0, 0, 0, 0]])
    np.testing.assert_array_equal(n_cross, [2, 2, 0])
    np.testing.assert_array_equal(first, [1, 0, 4])
    crossed, n_cross, first = pbft_round._crossing_loop(rows, 5, True)
    np.testing.assert_array_equal(
        np.stack(crossed).T, [[0, 1, 0, 0], [1, 0, 0, 0], [0, 0, 0, 0]])
    np.testing.assert_array_equal(n_cross, [1, 1, 0])
    np.testing.assert_array_equal(first, [1, 0, 4])


@pytest.mark.parametrize("clean", [True, False])
def test_crossing_loop_start_carried_in(clean):
    # a counter carried in: 4 of the 5 are already there
    rows = _rows((1, 0), (0, 1), (0, 0))
    start = jnp.asarray([4, 4, 4], jnp.int32)
    crossed, n_cross, first = pbft_round._crossing_loop(rows, 5, clean, start)
    np.testing.assert_array_equal(
        np.stack(crossed).T, [[1, 0], [0, 1], [0, 0]])
    np.testing.assert_array_equal(n_cross, [1, 1, 0])
    np.testing.assert_array_equal(first, [0, 1, 2])


@pytest.mark.parametrize("clean", [True, False])
def test_crossing_loop_equals_stacked(clean):
    rng = np.random.default_rng(45)
    mat = rng.integers(0, 4, size=(9, 257)).astype(np.int32)
    mat[rng.random(mat.shape) < 0.4] = 0
    start = jnp.asarray(rng.integers(0, 3, size=257), jnp.int32)
    for st in (None, start):
        want = stacked._crossing_loop(jnp.asarray(mat), 6, clean, st)
        got = pbft_round._crossing_loop(list(jnp.asarray(mat)), 6, clean, st)
        np.testing.assert_array_equal(np.stack(got[0]), want[0])
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[2], want[2])
