"""Shard-local neighbor exchange (parallel/partition.NeighborExchange +
topo/spec.owner_bucket_plan) — the ISSUE 20 contracts, pinned:

- ``layout="exchange"`` (the sharded_topo_sim_fn default) is BIT-EQUAL to
  the single-device program at mesh sizes 1/2/4/8, including an uneven
  node count (pad rows cross the exchange untouched) and the ``k = N-1``
  degenerate overlay where every shard reads every other shard's whole
  slice;
- exchange is also bit-equal leaf-for-leaf to ``layout="regather"`` (the
  pre-exchange GSPMD path kept for the locality bench) — same trace, same
  RNG draws, only the data movement differs;
- the compiled exchange program contains NO all-gather: cross-shard
  neighbor reads lower to ``all-to-all`` islands (the retired
  table-regather / prologue-global-gather debt, asserted on the HLO);
- ``owner_bucket_plan`` reconstructs ``x[table]`` exactly through a
  host-simulated send/all_to_all/position-gather round trip, and an
  explicitly undersized capacity is REFUSED loudly (overflow is a checked
  invariant, never silent truncation);
- ``local_tables`` honors the shard-offset ids + ``base`` mode and the
  ``ids=None`` pass-through documented in its layout contract.

Named test_zz* for the same reason as its siblings: the SPMD compiles
land at the very end of the tier-1 alphabetical order.  Everything pins
``stat_sampler="exact"`` + ``edge_sampler="threefry"`` (the
parallel/sweep.py bit-equality caveat).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.ops import gatherdeliv as gd
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.topo import spec as topo_spec
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

BASE = dict(fidelity="clean", stat_sampler="exact", edge_sampler="threefry")


def _rows_equal(a: dict, b: dict) -> bool:
    return {k: str(v) for k, v in a.items()} == {k: str(v) for k, v in b.items()}


def _mesh(n_shards: int):
    if len(jax.devices()) < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    return make_mesh(n_node_shards=n_shards, n_sweep=1,
                     devices=jax.devices()[:n_shards])


def _kreg_cfg(**kw):
    base = dict(protocol="pbft", n=12, sim_ms=400, topology="kregular",
                degree=10, **BASE)
    base.update(kw)
    return SimConfig(**base)


# ------------------------------------------- exchange == single-device


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
def test_exchange_bit_equal_every_mesh_size(n_shards):
    # n=12 over 8 shards also exercises the pad path (12 % 8 != 0)
    cfg = _kreg_cfg(faults=FaultConfig(n_crashed=2))
    assert _rows_equal(
        runner.run_simulation(cfg),
        sweep.run_sharded_topo(cfg, _mesh(n_shards)),
    )


def test_exchange_uneven_n_bit_equal():
    # 13 % 4 = 1: three zero-pad rows ride the exchange as owner-shard
    # row 0 copies and are sliced away before any primitive reads them
    cfg = _kreg_cfg(n=13, degree=11)
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, _mesh(4))
    )


def test_exchange_full_mesh_degenerate_bit_equal():
    # k = N-1: every node reads every other node, so each receiver's
    # buckets cover every owner's whole slice (capacity C == n_loc)
    cfg = _kreg_cfg(n=8, degree=7)
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, _mesh(2))
    )


def test_exchange_raft_unicast_bit_equal():
    # raft drives the column-indexed exchange variant (unicast replies
    # read one inslot column of the neighbor row, not the whole row)
    cfg = _kreg_cfg(protocol="raft", sim_ms=1000, degree=9, delivery="stat",
                    raft_proposal_delay_ms=300)
    assert _rows_equal(
        runner.run_simulation(cfg), sweep.run_sharded_topo(cfg, _mesh(4))
    )


# ------------------------------------------- exchange == regather layout


def test_exchange_bit_equal_to_regather_layout():
    # same trace, same RNG draw shapes — only the data movement differs,
    # so the finals must agree leaf-for-leaf, bitwise
    canon = canonical_fault_cfg(_kreg_cfg())
    mesh = _mesh(2)
    key = jax.random.key(canon.seed)
    nc = nb = jnp.int32(0)
    fx = sweep.sharded_topo_sim_fn(canon, mesh)
    assert fx.exchange_layout == "exchange"
    fr = sweep.sharded_topo_sim_fn(canon, mesh, layout="regather")
    assert fr.exchange_layout == "regather"
    a = jax.block_until_ready(fx(key, nc, nb))
    b = jax.block_until_ready(fr(key, nc, nb))
    assert all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_bad_layout_refused():
    with pytest.raises(ValueError, match="layout must be"):
        sweep.sharded_topo_sim_fn(
            canonical_fault_cfg(_kreg_cfg()), _mesh(2), layout="bogus"
        )


# ------------------------------------------------- the HLO-level contract


# tools/gather_locality_bench.py --quick's rung (its _kreg_cfg at n=4096)
_SMOKE = dict(
    n=4096, degree=8, sim_ms=120, delivery="edge", edge_sampler="rbg",
    schedule="tick", model_serialization=False, link_delay_ms=1,
    pbft_delay_lo=1, pbft_delay_hi=3, pbft_window=8,
)


@pytest.mark.parametrize("kw, n_shards", [
    pytest.param(dict(n=8, degree=4, sim_ms=200), 2, id="n8x2"),
    pytest.param(_SMOKE, 8, id="smoke-n4096x8", marks=pytest.mark.xfail(
        strict=True,
        reason="4 all-gathers of u32[2048,9] (73,728 B each, 294,912 B a "
               "device) in the tick loop, jax 0.9.0 XLA:CPU SPMD: the rbg "
               "edge sampler's power-of-two-span arm concatenates two "
               "half-height bit fields along the sharded node axis "
               "(KNOWN_ISSUES.md #0p)")),
])
def test_exchange_hlo_has_no_all_gather(kw, n_shards):
    # THE tentpole pin: the compiled exchange program moves neighbor rows
    # through all-to-all islands only — zero all-gathers anywhere, so no
    # per-device value ever scales with global N
    cfg = canonical_fault_cfg(_kreg_cfg(**kw))
    mesh = _mesh(n_shards)
    sim = sweep.sharded_topo_sim_fn(cfg, mesh)
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    cnt = jax.ShapeDtypeStruct((), jnp.int32)
    text = sim.partitioned.lower(
        key_sds, cnt, cnt, *sim.table_avals
    ).compile().as_text()
    assert "all-gather" not in text
    assert "all-to-all" in text


# --------------------------------------------------- owner_bucket_plan


def _simulate_exchange(x, table, pos, send, n_shards):
    """Host replay of the device exchange: per-owner take, all_to_all
    re-block, flatten, position gather — must reproduce ``x[table]``."""
    n = x.shape[0]
    n_loc = n // n_shards
    cap = send.shape[2]
    out = np.empty(table.shape + x.shape[1:], x.dtype)
    for d in range(n_shards):                     # receiver shard
        flat = np.zeros((n_shards * cap,) + x.shape[1:], x.dtype)
        for o in range(n_shards):                 # owner shard
            flat[o * cap:(o + 1) * cap] = x[send[o, d] + o * n_loc]
        out[d * n_loc:(d + 1) * n_loc] = flat[pos[d * n_loc:(d + 1) * n_loc]]
    return out


def test_owner_bucket_plan_reconstructs_rows():
    rng = np.random.RandomState(7)
    n, k, d = 24, 5, 4
    table = rng.randint(0, n, size=(n, k)).astype(np.int32)
    pos, send = topo_spec.owner_bucket_plan(table, d)
    x = rng.randint(0, 1000, size=(n, 3)).astype(np.int32)
    assert np.array_equal(_simulate_exchange(x, table, pos, send, d),
                          x[table])
    # the single-shard plan is still a valid (identity-ish) exchange
    pos1, send1 = topo_spec.owner_bucket_plan(table, 1)
    assert np.array_equal(_simulate_exchange(x, table, pos1, send1, 1),
                          x[table])


def test_owner_bucket_plan_overflow_refused():
    table = np.arange(16, dtype=np.int32).reshape(8, 2) % 8
    pos, send = topo_spec.owner_bucket_plan(table, 2)
    required = send.shape[2]
    assert required >= 1
    with pytest.raises(ValueError, match="refusing to truncate"):
        topo_spec.owner_bucket_plan(table, 2, capacity=required - 1)
    # an explicit capacity >= required widens the buffers instead
    pos2, send2 = topo_spec.owner_bucket_plan(table, 2,
                                              capacity=required + 3)
    assert send2.shape[2] == required + 3
    x = np.arange(8, dtype=np.int32)[:, None]
    assert np.array_equal(_simulate_exchange(x, table, pos2, send2, 2),
                          x[table])


def test_owner_bucket_plan_rejects_bad_inputs():
    table = np.zeros((9, 2), np.int32)
    with pytest.raises(ValueError, match="not divisible"):
        topo_spec.owner_bucket_plan(table, 2)
    bad = np.full((8, 2), 9, np.int32)
    with pytest.raises(ValueError, match="outside"):
        topo_spec.owner_bucket_plan(bad, 2)


# ------------------------------------------------- local_tables contract


def test_local_tables_shard_offset_and_passthrough():
    cfg = _kreg_cfg()
    tables = gd.table_operands(cfg, inslot=False)
    lo, hi = 4, 8
    by_global = gd.local_tables(cfg, jnp.arange(lo, hi), tables=tables)
    by_offset = gd.local_tables(cfg, jnp.arange(hi - lo), tables=tables,
                                base=lo)
    for a, b in zip(by_global, by_offset):
        assert bool(jnp.array_equal(a, b))
    passthrough = gd.local_tables(cfg, None, tables=tables)
    for a, t in zip(passthrough, tables):
        assert bool(jnp.array_equal(a, jnp.asarray(t)))
