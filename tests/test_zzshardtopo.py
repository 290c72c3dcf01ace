"""Mesh-sharded topology programs (parallel/sweep.sharded_topo_sim_fn).

The ISSUE 16 contracts, pinned:

- sharded kregular/committee runs are BIT-EQUAL to the single-device PR 15
  programs at equal (n, k, faults, seed) under ``stat_sampler="exact"`` —
  including an uneven node count (tail-shard table padding) and the
  mesh-size-1 identity arm (which must literally be the single-device
  program);
- the [N, K+1] overlay tables ride as OPERANDS, not baked trace constants:
  tables-as-operands vs tables-as-constants bit-equality, and the traced
  sharded jaxpr carries no multi-hundred-KB constants (the KNOWN_ISSUES
  #0n escape hatch, implemented);
- ONE executable per (protocol, topology, fault structure, mesh): fault
  COUNTS ride the operands and never mint a second registry entry;
- the committee arm shards whole committees (``committees % shards == 0``
  required — a typed refusal otherwise);
- PR 13's multi-seed tick batching composes with the topo axis:
  ``run_multi_seed`` on kregular/committee canons is bit-equal to
  per-seed ``run_simulation`` (the ISSUE 16 satellite — previously
  untested).

Everything here pins ``stat_sampler="exact"`` + ``edge_sampler="threefry"``
(the parallel/sweep.py bit-equality caveat: the normal CLT float path has
tick latitude across differently-compiled programs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils import aotcache
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

BASE = dict(fidelity="clean", stat_sampler="exact", edge_sampler="threefry")


def _rows_equal(a: dict, b: dict) -> bool:
    return {k: str(v) for k, v in a.items()} == {k: str(v) for k, v in b.items()}


@pytest.fixture(scope="module")
def mesh2():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    return make_mesh(n_node_shards=2, n_sweep=1, devices=jax.devices()[:2])


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(n_node_shards=1, n_sweep=1, devices=jax.devices()[:1])


def _kreg_cfg(**kw):
    base = dict(protocol="pbft", n=12, sim_ms=400, topology="kregular",
                degree=10, **BASE)
    base.update(kw)
    return SimConfig(**base)


# ------------------------------------------------- sharded == single-device


@pytest.mark.parametrize("cfg", [
    _kreg_cfg(),
    _kreg_cfg(protocol="raft", sim_ms=1000, degree=9, delivery="stat",
              raft_proposal_delay_ms=300),
    _kreg_cfg(protocol="paxos", sim_ms=800, degree=8),
    _kreg_cfg(faults=FaultConfig(n_crashed=3)),
], ids=["pbft", "raft", "paxos", "pbft_crashed"])
def test_sharded_kregular_bit_equal(cfg, mesh2):
    single = runner.run_simulation(cfg)
    sharded = sweep.run_sharded_topo(cfg, mesh2)
    assert _rows_equal(single, sharded)


def test_sharded_uneven_n_bit_equal(mesh2):
    # 13 % 2 != 0: the factory zero-pads the table operands to the next
    # shard multiple and slices them back inside the program — results
    # must stay bit-equal to the unpadded single-device run
    cfg = _kreg_cfg(n=13, degree=11)
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, mesh2)
    )


def test_sharded_committee_bit_equal(mesh2):
    cfg = SimConfig(protocol="pbft", n=16, sim_ms=400, topology="committee",
                    committees=4, faults=FaultConfig(n_crashed=4), **BASE)
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, mesh2)
    )


def test_sharded_committee_readback_claims_no_lone_plan(mesh2):
    """A stack spread over a mesh ran each device's slice by a plan of its
    own: its one readback carries no ``tiles`` / ``tile_lanes`` and moves no
    counter, although the lone stack of the same configuration was traced
    in this process and wrote its plan down."""
    from blockchain_simulator_tpu.topo import committee
    from blockchain_simulator_tpu.utils import telemetry

    cfg = SimConfig(protocol="pbft", n=16, sim_ms=400, topology="committee",
                    committees=4, faults=FaultConfig(n_crashed=4), **BASE)
    runner.run_simulation(cfg)
    assert committee.ran_as(cfg) is not None
    before = telemetry.metrics.snapshot()["counters"]
    with telemetry.capture() as spans:
        sweep.run_sharded_topo(cfg, mesh2)
    after = telemetry.metrics.snapshot()["counters"]
    rb = [s for s in spans if s["name"] == "topo.committee.readback"]
    assert len(rb) == 1 and "tile_lanes" not in rb[0]["attrs"]
    assert all(after.get(k, 0) == before.get(k, 0)
               for k in committee.COUNTERS)
    # the slice a device ran is written down under its own length
    assert (canonical_fault_cfg(cfg), 2, 1, True) in committee._traced


def test_mesh_size_1_identity(mesh1):
    # the degenerate arm IS the single-device program: same results, and
    # the factory returns a jitted make_dyn_sim_fn (no partition machinery)
    cfg = _kreg_cfg()
    sim = sweep.sharded_topo_sim_fn(canonical_fault_cfg(cfg), mesh1)
    assert not hasattr(sim, "partitioned")
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, mesh1)
    )


# ------------------------------------------------------ tables as operands


def test_tables_as_operands_bit_equal_to_constants():
    # the same engine, tables threaded as operands vs baked as trace
    # constants (runner.make_dyn_sim_fn) — bit-equal finals per leaf
    from blockchain_simulator_tpu.ops import gatherdeliv as gd

    cfg = canonical_fault_cfg(_kreg_cfg())
    tables = gd.table_operands(cfg, inslot=runner.topo_tables_inslot(cfg))
    key = jax.random.key(cfg.seed)
    nc = nb = jnp.int32(0)
    const_final = jax.jit(runner.make_dyn_sim_fn(cfg))(key, nc, nb)
    oper_final = jax.jit(runner.make_topo_dyn_sim_fn(cfg))(
        key, nc, nb, *tables
    )
    assert all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(jax.tree.leaves(const_final),
                        jax.tree.leaves(oper_final))
    )


def test_sharded_jaxpr_carries_no_table_constants(mesh2):
    # the audit's large-jaxpr-constant bound, asserted directly on the
    # sharded program at a size where baked tables would blow it: n=4096,
    # K+1=9 -> two ~147 KB int32 tables as constants if they were baked
    cfg = canonical_fault_cfg(_kreg_cfg(n=4096, degree=8, delivery="edge",
                                        sim_ms=100))
    sim = sweep.sharded_topo_sim_fn(cfg, mesh2)
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    cnt = jax.ShapeDtypeStruct((), jnp.int32)
    traced = sim.partitioned.trace(key_sds, cnt, cnt, *sim.table_avals)
    const_bytes = sum(
        getattr(c, "nbytes", 0) for c in traced.jaxpr.consts
    )
    assert const_bytes < 64 * 1024, const_bytes


def test_make_topo_dyn_sim_fn_rejects_non_kregular():
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=200, **BASE)
    with pytest.raises(ValueError, match="kregular"):
        runner.make_topo_dyn_sim_fn(cfg)


def test_local_tables_wrong_arity():
    from blockchain_simulator_tpu.ops import gatherdeliv as gd

    cfg = _kreg_cfg()
    ids = jnp.arange(cfg.n)
    with pytest.raises(ValueError, match="expected 3 tables"):
        gd.local_tables(cfg, ids, inslot=True,
                        tables=gd.table_operands(cfg, inslot=False))


# ------------------------------------------------------------ registry pins


def _entries() -> int:
    snap = aotcache.registry.stats_snapshot()
    return snap["by_factory"].get("shard-topo-sim", 0)


def test_one_executable_per_fault_structure(mesh2):
    # fault COUNTS ride the operands: two crash levels over one overlay
    # build at most one new registry entry, and a repeat run builds none
    before = _entries()
    for nc in (1, 2):
        sweep.run_sharded_topo(
            _kreg_cfg(faults=FaultConfig(n_crashed=nc)), mesh2
        )
    assert _entries() - before <= 1
    mid = _entries()
    sweep.run_sharded_topo(
        _kreg_cfg(faults=FaultConfig(n_crashed=2)), mesh2
    )
    assert _entries() == mid


def test_committee_shard_divisibility_refusal(mesh2):
    cfg = SimConfig(protocol="pbft", n=18, sim_ms=400, topology="committee",
                    committees=3, **BASE)
    with pytest.raises(ValueError, match="committees=3 not divisible"):
        sweep.sharded_topo_sim_fn(canonical_fault_cfg(cfg), mesh2)


def test_dense_topology_refusal(mesh2):
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=200, **BASE)
    with pytest.raises(ValueError, match="no node-dim topo structure"):
        sweep.sharded_topo_sim_fn(canonical_fault_cfg(cfg), mesh2)


# ------------------------------------------- multi-seed x topo (ISSUE 16 s1)


def test_multi_seed_kregular_bit_equal():
    cfg = _kreg_cfg()
    rows = runner.run_multi_seed(cfg, seeds=(0, 1, 2))
    for seed, row in zip((0, 1, 2), rows):
        solo = runner.run_simulation(cfg.with_(seed=seed))
        assert _rows_equal(solo, row), seed


def test_multi_seed_committee_bit_equal():
    cfg = SimConfig(protocol="pbft", n=16, sim_ms=400, topology="committee",
                    committees=4, **BASE)
    rows = runner.run_multi_seed(cfg, seeds=(0, 1))
    for seed, row in zip((0, 1), rows):
        solo = runner.run_simulation(cfg.with_(seed=seed))
        assert _rows_equal(solo, row), seed
