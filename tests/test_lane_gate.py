"""``models/base.gated`` under a lane batch (``lane_vmap``): the gate branches
on "any lane active" and stays a conditional, every lane's row is its solo
run's, and no program without the lane axis moves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import SimConfig, run_simulation
from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import base, paxos, pbft, raft
from blockchain_simulator_tpu.models.base import canonical_fault_cfg, sim_metrics
from blockchain_simulator_tpu.parallel import shard, sweep
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils.config import FaultConfig

PBFT = SimConfig(protocol="pbft", n=8, sim_ms=1500)
SWEPT = {
    "pbft-edge": SimConfig(protocol="pbft", n=8, sim_ms=400),
    "pbft-stat": SimConfig(protocol="pbft", n=8, sim_ms=400, delivery="stat",
                           schedule="tick"),
    "raft": SimConfig(protocol="raft", n=8, sim_ms=1200),
    "paxos": SimConfig(protocol="paxos", n=8, sim_ms=1200),
}


def _keys(seeds):
    return jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))


def _plain_gated(pred, fn, zeros, axis=None):
    """``gated`` as it was before the lane rule: ``lax.cond`` written out."""
    if axis is not None:
        pred = jax.lax.pmax(pred.astype(jnp.int32), axis) > 0
    return jax.lax.cond(pred, fn, lambda: zeros)


def _everywhere(monkeypatch, fn):
    for mod in (pbft, raft, paxos):
        monkeypatch.setattr(mod, "gated", fn)


# ------------------------------------------------------------ (a) (b) rows


@pytest.mark.parametrize("name", list(SWEPT))
def test_seed_sweep_rows_equal_solo_runs(name):
    cfg = SWEPT[name]
    seeds = [3, 11, 2147483777 % (2**31 - 1)]
    rows = sweep.run_seed_sweep(cfg, seeds)
    assert rows == [run_simulation(cfg, seed=s) for s in seeds]


def test_lanes_that_differ_in_fault_level_equal_solo_runs():
    """Lanes of a fault sweep or a served bucket have predicates of their
    own (a crashed or Byzantine sender is not active): the select inside the
    taken arm keeps each lane's result its own."""
    canon = canonical_fault_cfg(PBFT.with_(sim_ms=600))
    seeds, nc, nb = [5, 6, 7, 8], [0, 2, 0, 1], [0, 0, 2, 1]
    finals = sweep.dyn_batched_fn(canon)(
        _keys(seeds), jnp.asarray(nc, jnp.int32), jnp.asarray(nb, jnp.int32))
    solo = jax.jit(runner.make_dyn_sim_fn(canon))
    for i, s in enumerate(seeds):
        cfg_i = canon.with_(faults=FaultConfig(n_crashed=nc[i],
                                               n_byzantine=nb[i]))
        row = sim_metrics(cfg_i, jax.tree.map(lambda x: x[i], finals))
        want = sim_metrics(cfg_i, solo(jax.random.key(s), jnp.int32(nc[i]),
                                       jnp.int32(nb[i])))
        assert row == want, i
    assert len({str(sim_metrics(canon, jax.tree.map(lambda x: x[i], finals)))
                for i in range(4)}) > 1


def test_one_lane_with_a_view_change_among_lanes_without():
    """The view-change gate is taken for the batch on the ticks of ONE lane;
    the other lanes must come out as if it had not been."""
    seeds = [1, 2, 7, 3]
    solo = [run_simulation(PBFT, seed=s) for s in seeds]
    assert [m["view_changes"] for m in solo] == [0, 0, 1, 0]
    assert sweep.run_seed_sweep(PBFT, seeds) == solo


# ------------------------------------------------------- (c) (d) lowering


def test_batched_pbft_program_keeps_a_conditional_per_gated_site(monkeypatch):
    calls = []

    def counting(pred, fn, zeros, axis=None):
        calls.append(1)
        return base.gated(pred, fn, zeros, axis)

    _everywhere(monkeypatch, counting)
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=350)  # traced nowhere else
    # the lone program first, through the SAME cached solo factory: jit's
    # trace cache must not hand the lone jaxpr to the lane batch
    lone = runner.make_sim_fn(cfg).lower(jax.random.key(0)).as_text()
    n_lone = len(calls)
    text = sweep._batched_fn.__wrapped__(cfg, None).lower(_keys([1, 2])).as_text(
        debug_info=True)
    n_sites = len(calls) - n_lone
    assert n_sites == n_lone >= 4
    assert text.count("stablehlo.case") >= n_sites
    assert lone.count("stablehlo.case") == n_sites
    assert f"{base.GATE_SCOPE}/" in text
    dyn = sweep.dyn_batched_fn.__wrapped__(canonical_fault_cfg(cfg)).lower(
        _keys([1, 2]), jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
    assert dyn.as_text().count("stablehlo.case") >= n_sites


def _lone_and_mesh_texts(calls):
    """Lowered text of every program that binds NO lane axis — the lone
    static and dynamic programs and the three mesh arms — and how many
    ``gated`` calls each one traced (``calls`` grows by one per call)."""
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=100)
    canon = canonical_fault_cfg(cfg)
    key, cnt = jax.random.key(0), jnp.int32(0)
    k2, c2 = _keys([1, 2]), jnp.zeros(2, jnp.int32)
    # the one cached factory a mesh arm builds through: start it cold, so
    # that its program is traced under the ``gated`` in force
    shard.make_sharded_sim_fn.cache_clear()
    mesh_dyn = sweep.mesh_dyn_batched_fn.__wrapped__
    builds = {
        "make_sim_fn": lambda: runner.make_sim_fn.__wrapped__(cfg).lower(key),
        "make_dyn_sim_fn": lambda: jax.jit(runner.make_dyn_sim_fn(canon)).lower(
            key, cnt, cnt),
        "sweep-batched, mesh": lambda: sweep._batched_fn.__wrapped__(
            cfg, make_mesh(n_node_shards=2, n_sweep=2)).lower(k2),
        "partition-dyn-sweep, sweep mesh": lambda: mesh_dyn(
            canon, make_mesh(n_node_shards=1, n_sweep=2)).lower(k2, c2, c2),
        "partition-dyn-sweep, nodes mesh": lambda: mesh_dyn(
            canon, make_mesh(n_node_shards=2, n_sweep=1)).lower(
                k2[:1], c2[:1], c2[:1]),
    }
    out = {}
    for name, build in builds.items():
        before = len(calls)
        out[name] = (build().as_text(), len(calls) - before)
    return out


def test_programs_without_a_lane_axis_are_the_plain_cond_programs(monkeypatch):
    calls = []

    def counted(gate):
        def fn(pred, fn_, zeros, axis=None):
            calls.append(1)
            return gate(pred, fn_, zeros, axis)
        return fn

    _everywhere(monkeypatch, counted(base.gated))
    with_rule = _lone_and_mesh_texts(calls)
    _everywhere(monkeypatch, counted(_plain_gated))
    plain = _lone_and_mesh_texts(calls)
    for name, (text, n_gates) in with_rule.items():
        # both were traced anew (no trace cache answered for the other)
        assert n_gates == plain[name][1] >= 4, name
        assert text == plain[name][0], name
        assert base.GATE_SCOPE not in text


def test_gated_under_an_unnamed_vmap_is_still_a_select():
    def f(x):
        return base.gated(x.sum() > 0, lambda: x * 2, jnp.zeros_like(x))

    xs = jnp.asarray([[0, 0], [1, 2], [0, 3]], jnp.int32)
    want = np.asarray([[0, 0], [2, 4], [0, 6]])
    unnamed = jax.jit(jax.vmap(f))
    assert "stablehlo.case" not in unnamed.lower(xs).as_text()
    np.testing.assert_array_equal(unnamed(xs), want)
    named = jax.jit(base.lane_vmap(f))
    assert named.lower(xs).as_text().count("stablehlo.case") == 1
    np.testing.assert_array_equal(named(xs), want)
    np.testing.assert_array_equal(named(jnp.zeros_like(xs)), 0 * want)
    assert "stablehlo.case" in jax.jit(f).lower(xs[0]).as_text()


def test_taken_arm_keeps_zeros_of_inactive_lanes_leaf_by_leaf():
    """``zeros`` may be live state (the stat call sites pass the ring): an
    inactive lane keeps ITS leaf when another lane takes the arm."""
    def f(x, ring):
        return base.gated(x > 0, lambda: (ring + x, {"n": x}),
                          (ring, {"n": jnp.int32(-1)}))

    x = jnp.asarray([0, 5, 0], jnp.int32)
    ring = jnp.arange(6, dtype=jnp.int32).reshape(3, 2)
    got_ring, got = jax.jit(base.lane_vmap(f))(x, ring)
    np.testing.assert_array_equal(got_ring, [[0, 1], [7, 8], [4, 5]])
    np.testing.assert_array_equal(got["n"], [-1, 5, -1])


# ------------------------------------------------------ (e) the benchmark


def test_benchmark_table_holds_the_gate_metric_and_its_reader(monkeypatch):
    """``BENCHMARK.json`` keeps to the contract's shapes with the
    ``ops_gate_us.sweep`` entry, its reader is there, and on a run without
    a trace (or of a program without the scope) it reads nothing."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    here = os.path.join(root, "benchmark")
    monkeypatch.syspath_prepend(here)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert importlib.import_module("selftest").validate(spec, root, here) == []
    entry = [m for m in spec["per_layer"] if m["name"] == "ops_gate_us.sweep"]
    assert entry == [{
        "name": "ops_gate_us.sweep", "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "ops", "moves": "points_per_s",
        "workloads": ["pbft1k.mc"]}]
    mod = importlib.util.spec_from_file_location(
        "ops_gate_us_sweep",
        os.path.join(here, "layer_metrics", "ops_gate_us.sweep.py"))
    reader = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(reader)
    assert reader.read({"trace": None, "traffic": {"driver": "sweep"}}) is None
    assert base.GATE_SCOPE.startswith("ops.gate.")
