"""Multi-seed Monte Carlo tick batching (ISSUE 13): the scatter-free
``lax.map`` executable (parallel/sweep.multi_seed_fn), its
``runner.run_multi_seed`` entrypoint, and the sweeps' ``multi_seed=`` arm.

Late-alphabet name: these tests compile tick-engine programs (the tier-1
window rule from tests/test_zsweep_cache.py applies)."""

import jax
import jax.numpy as jnp
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.parallel import partition, sweep
from blockchain_simulator_tpu.utils import aotcache
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig


def _cfg(**kw):
    # small tick-engine config; stat_sampler pinned "exact" so rows are
    # bit-stable across the differently-compiled dispatch arms
    # (parallel/sweep.py CLT float caveat)
    base = dict(protocol="pbft", n=48, sim_ms=300, schedule="tick",
                delivery="stat", model_serialization=False,
                stat_sampler="exact", pbft_max_rounds=5, pbft_max_slots=16)
    base.update(kw)
    return SimConfig(**base)


def test_run_multi_seed_rows_bit_equal_sequential():
    cfg = _cfg()
    seeds = (0, 1, 5)
    batched = runner.run_multi_seed(cfg, seeds, record=False)
    solo = [runner.run_simulation(cfg, seed=s) for s in seeds]
    assert batched == solo


def test_multi_seed_one_executable_fresh_seeds_hit():
    cfg = _cfg(n=32, sim_ms=200, pbft_max_rounds=3, pbft_max_slots=8)
    s0 = aotcache.registry.stats()
    runner.run_multi_seed(cfg, (0, 1), record=False)
    s1 = aotcache.registry.stats()
    assert s1["misses"] - s0["misses"] >= 1  # fresh structure compiled once
    # fresh seed VALUES ride the key operand: zero new executables
    runner.run_multi_seed(cfg, (7, 11), record=False)
    s2 = aotcache.registry.stats()
    assert s2["misses"] == s1["misses"]
    assert s2["hits"] > s1["hits"]
    # a different seed COUNT is a different batch shape: its own entry
    runner.run_multi_seed(cfg, (0, 1, 2), record=False)
    s3 = aotcache.registry.stats()
    assert s3["misses"] - s2["misses"] == 1


def test_fault_sweep_multi_seed_arm_bit_equal_default():
    cfg = _cfg()
    fcs = [FaultConfig(n_byzantine=f) for f in (0, 2)]
    seeds = (0, 3)
    default = sweep.run_fault_sweep(cfg, fcs, seeds)
    ms = sweep.run_fault_sweep(cfg, fcs, seeds, multi_seed=True)
    assert default == ms


def test_run_multi_seed_refuses_mixed():
    cfg = SimConfig(protocol="mixed", n=32, mixed_shards=2, sim_ms=200,
                    schedule="tick", stat_sampler="exact")
    with pytest.raises(runner.UnbatchableConfigError):
        runner.run_multi_seed(cfg, (0, 1), record=False)


def test_multi_seed_body_scatter_free():
    """The #0i pin at the jaxpr level: the lax.map multi-seed body contains
    NO plain `scatter` primitive (vmap's DUS lowering) — only the inherent
    scatter-add/max/min window-event accumulators survive, exactly like the
    mesh arm's per-device body.  The vmapped program over the same sim is
    the positive control.  (lint/graph baselines pin the same contract in
    CI via the multi_seed.* budget entries.)"""
    cfg = canonical_fault_cfg(_cfg(n=16, sim_ms=120, pbft_max_rounds=2,
                                   pbft_max_slots=8))
    fn = runner.make_dyn_sim_fn(cfg)
    keys = jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32))
    cnt = jnp.zeros((2,), jnp.int32)

    from blockchain_simulator_tpu.lint.graph.ir import iter_eqns

    def prims(closed):
        return [eqn.primitive.name for eqn in iter_eqns(closed)]

    seq_prims = prims(jax.make_jaxpr(partition.seq_map(fn))(keys, cnt, cnt))
    assert "scatter" not in seq_prims
    vmap_prims = prims(jax.make_jaxpr(jax.vmap(fn))(keys, cnt, cnt))
    assert "scatter" in vmap_prims  # the hazard the map arm removes


def test_run_dyn_points_multi_seed_mixed_fault_counts():
    """A sweep tile's points differ in fault COUNTS: the mapped operands
    carry them, rows bit-equal to the default vmapped dispatch."""
    cfg = _cfg()
    canon = canonical_fault_cfg(cfg)
    points = [
        (cfg.with_(faults=FaultConfig(n_byzantine=0)), 0),
        (cfg.with_(faults=FaultConfig(n_byzantine=3)), 1),
    ]
    default = sweep.run_dyn_points(canon, points, record=False)
    ms = sweep.run_dyn_points(canon, points, record=False, multi_seed=True)
    assert default == ms


# ------------------------------ the code's own choice of the map (PR 49) ---


def _large_lanes(monkeypatch, cfg):
    """A device on which every lane of ``cfg`` is a large share of the memory
    it reports (XLA:CPU reports none: the rule then never engages)."""
    canon = canonical_fault_cfg(cfg)
    state = sweep._lane_state_bytes(canon)
    monkeypatch.setattr(sweep, "_device_bytes", lambda: 16 * state)
    assert state > sweep._MAP_LANE_SHARE * 16 * state
    return canon


@pytest.mark.parametrize("forced", (False, True), ids=("placed", "forced"))
def test_placed_map_is_the_forced_maps_executable(forced, monkeypatch):
    """The default on a device of large lanes and ``multi_seed=True``
    anywhere run ONE registry entry (``multi-seed-tick``), rows bit-equal to
    solo runs; only the placed one stands under a ``sweep.tile`` span."""
    from blockchain_simulator_tpu.utils import telemetry

    cfg = _cfg(n=32, sim_ms=200, pbft_max_rounds=3, pbft_max_slots=8)
    canon = _large_lanes(monkeypatch, cfg)
    points = [(cfg, s) for s in (0, 1)]
    sweep.run_dyn_points(canon, points, record=False, multi_seed=True)
    s0 = aotcache.registry.stats()
    with telemetry.capture() as spans:
        rows, meta = sweep.run_dyn_points(canon, points, record=False,
                                          multi_seed=forced, with_index=True)
    assert aotcache.registry.stats()["misses"] == s0["misses"]
    assert rows == [runner.run_simulation(cfg, seed=s) for s in (0, 1)]
    tiles = [s["attrs"] for s in spans if s["name"] == "sweep.tile"]
    if forced:
        assert tiles == [] and meta["tile"] is None
    else:
        assert [(t["lanes"], t["points"]) for t in tiles] == [(1, 2)]
        assert meta["tile"]["program"] == "lax.map"
    assert (meta["dispatches"], meta["lanes"], meta["pad"]) == (1, 2, 0)


def test_placed_map_takes_the_probed_twin(monkeypatch):
    """An armed flush follows the same choice through the same branch: the
    ``lax.map`` twin of the probed program, summaries and rows those of the
    armed lane batch."""
    from blockchain_simulator_tpu.obsim import build, schema

    cfg = _cfg(n=16, sim_ms=160, pbft_max_rounds=2, pbft_max_slots=8)
    canon = canonical_fault_cfg(cfg)
    pcfg = schema.ProbeConfig(windows=4)
    points = [(cfg.with_(faults=FaultConfig(n_byzantine=b)), 3)
              for b in (0, 1, 2)]
    batch = sweep.run_dyn_points(canon, points, record=False, probe=pcfg)
    _large_lanes(monkeypatch, cfg)
    asked = []
    real = build.probed_batched_fn

    def spy(canon, probe, multi_seed=False):
        asked.append(multi_seed)
        return real(canon, probe, multi_seed=multi_seed)

    monkeypatch.setattr(build, "probed_batched_fn", spy)
    mapped = sweep.run_dyn_points(canon, points, record=False, probe=pcfg)
    assert asked == [True] and mapped == batch
    assert all("probe" in m for m in mapped)
