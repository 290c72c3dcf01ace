"""``ops/delivery.reply_count_by_target``: stat Raft's per-target reply
totals.  Up to ``REPLY_COUNT_DENSE_MAX_N`` the count is a compare and a sum
(no scatter: one fusion, which a lane batch widens); above it the scatter-add
the engine always ran.  Integer counts, so the two forms are bit-equal, and a
run counted one way ends in the state of a run counted the other way."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from blockchain_simulator_tpu import SimConfig
from blockchain_simulator_tpu.models import base, raft
from blockchain_simulator_tpu.models.base import sim_metrics
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.runner import make_sim_fn
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils.config import FaultConfig

N = 96
FORMS = {"dense": 2 * N, "scatter": N - 1}  # the bound on either side of N


def _form(monkeypatch, form):
    monkeypatch.setattr(dv, "REPLY_COUNT_DENSE_MAX_N", FORMS[form])


def _inputs(seed, lanes=None):
    """Random repliers: targets over ``[-1, N]``, both ends out of range."""
    rng = np.random.default_rng(seed)
    shape = (N,) if lanes is None else (lanes, N)
    target = rng.integers(-1, N + 1, shape).astype(np.int32)
    # few candidates and many, as an election storm and a quiet tick have
    target[..., : N // 2] = rng.choice([3, 17, N - 1, N, -1], shape)[..., : N // 2]
    return rng.random(shape) < 0.6, target


def _reference(wire, target):
    """What the op must count, by the definition."""
    ok = wire & (target >= 0) & (target < N)
    return np.bincount(target[ok], minlength=N).astype(np.int32)


def _the_scatter_it_replaces(wire, target):
    """``models/raft.py``'s inline closure before the op, letter for letter."""
    return jnp.zeros((N,), jnp.int32).at[target].add(
        wire.astype(jnp.int32), mode="drop")


def _lone(wire, target):
    got = jax.jit(lambda w, t: dv.reply_count_by_target(w, t, N))(wire, target)
    return got[None], wire[None], target[None]


def _batched(vmap):
    def run(wire, target):
        fn = jax.jit(vmap(lambda w, t: dv.reply_count_by_target(w, t, N)))
        return fn(wire, target), wire, target
    return run


def _sharded(wire, target):
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
    axis = mesh.axis_names[-1]
    fn = jax.shard_map(
        lambda w, t: dv.reply_count_by_target(w, t, N, axis),
        mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis))
    return jax.jit(fn)(wire, target)[None], wire[None], target[None]


PROGRAMS = {
    "lone": (_lone, None),
    "vmap": (_batched(jax.vmap), 5),
    "lane_vmap": (_batched(base.lane_vmap), 5),
    "sharded": (_sharded, None),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("form", list(FORMS))
def test_counts_equal_the_definition_and_the_scatter(monkeypatch, form,
                                                     program, seed):
    _form(monkeypatch, form)
    run, lanes = PROGRAMS[program]
    wire, target = _inputs(seed, lanes)
    if form == "scatter":
        # a row aimed at -1 has its wire clear, as the engine's callers have
        # it (``.at[-1]`` wraps before ``mode="drop"`` looks; at n it
        # drops); the dense form counts a set one nowhere by itself
        wire = wire & (target >= 0)
    got, wire, target = run(wire, target)
    assert got.dtype == jnp.int32 and got.shape == wire.shape
    for g, w, t in zip(np.asarray(got), wire, target):
        np.testing.assert_array_equal(g, _reference(w, t))
        np.testing.assert_array_equal(
            g, _the_scatter_it_replaces(w & (t >= 0), t))


def test_the_scope_is_listed():
    assert "ops.delivery.reply_count_by_target" in dv.SCOPES


# --------------------------------------------------------------- whole runs


STAT = SimConfig(protocol="raft", n=N, sim_ms=700, schedule="tick",
                 delivery="stat", raft_proposal_delay_ms=100,
                 model_serialization=False, stat_sampler="exact")
RUNS = {
    "clean": STAT.with_(fidelity="clean"),
    "reference": STAT.with_(fidelity="reference"),
    "drop": STAT.with_(fidelity="clean", faults=FaultConfig(drop_prob=0.1)),
    "byzantine": STAT.with_(
        fidelity="clean", faults=FaultConfig(n_byzantine=N // 4)),
    "byzantine-drop": STAT.with_(
        fidelity="reference",
        faults=FaultConfig(n_byzantine=N // 4, drop_prob=0.05)),
    "gossip-acks": STAT.with_(
        fidelity="clean", topology="gossip", sim_ms=1500,
        faults=FaultConfig(n_byzantine=N // 8)),
    "gossip-acks-drop": STAT.with_(
        fidelity="clean", topology="gossip", sim_ms=1500,
        faults=FaultConfig(drop_prob=0.05)),
    "mixed": SimConfig(protocol="mixed", n=4 * N // 2, mixed_shards=4,
                       sim_ms=1200, delivery="stat",
                       model_serialization=False),
}


def _assert_states_equal(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def _runs(monkeypatch, form, cfg, seeds):
    """Whole runs traced with the count in one form (a fresh program: the
    registry would hand the second form the first one's)."""
    with monkeypatch.context() as m:
        n = cfg.n // cfg.mixed_shards if cfg.protocol == "mixed" else cfg.n
        m.setattr(dv, "REPLY_COUNT_DENSE_MAX_N",
                  2 * n if form == "dense" else n - 1)
        sim = make_sim_fn.__wrapped__(cfg)
        return [sim(jax.random.key(seed)) for seed in seeds]


@pytest.mark.parametrize("name", list(RUNS))
def test_a_run_ends_where_the_scatter_run_ends(monkeypatch, name):
    cfg = RUNS[name]
    seeds = (3, 11, 42)
    for dense, scatter in zip(_runs(monkeypatch, "dense", cfg, seeds),
                              _runs(monkeypatch, "scatter", cfg, seeds)):
        _assert_states_equal(dense, scatter)
        m = sim_metrics(cfg, dense)
        assert m == sim_metrics(cfg, scatter)
        # the window holds what the count feeds: an election won on replies
        if cfg.protocol == "mixed":
            assert m["shards_with_leader"] == cfg.mixed_shards
        else:
            assert m["n_leaders"] >= 1
        if cfg.topology == "gossip":  # acks counted: proposals commit
            assert m["blocks"] >= 1


# ----------------------------------------------------------------- lowering


def _scatters_under(compiled, scope):
    """The ``op_name`` of every ``scatter`` of the compiled text that stands
    under ``scope``."""
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if re.search(r"= \S+ scatter\(", line)]
    return [name for name in names if f"{scope}/" in name]


def _tick_program(cfg, lanes):
    """The tick scan of ``runner.make_sim_fn`` under a lane batch."""
    def sim(key):
        state, bufs = raft.init(cfg, jax.random.fold_in(key, 0x1217))

        def body(carry, t):
            return raft.step(cfg, *carry, t, prng.tick_key(key, t)), ()

        return jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))[0][0]

    keys = jax.vmap(jax.random.key)(jnp.arange(lanes, dtype=jnp.uint32))
    return jax.jit(base.lane_vmap(sim)).lower(keys).compile()


@pytest.mark.parametrize("form,scatters", [("dense", 0), ("scatter", 2)])
def test_vote_rx_holds_a_scatter_only_above_the_bound(monkeypatch, form,
                                                      scatters):
    """Below the bound the batched tick program's vote-reply arm counts
    without a scatter; above it it holds the two it always held."""
    _form(monkeypatch, form)
    compiled = _tick_program(STAT.with_(sim_ms=50), lanes=4)
    found = _scatters_under(compiled, "raft.tick.vote_rx")
    assert len(found) == scatters, found
    for path in found:
        assert "ops.delivery.reply_count_by_target" in path


def test_the_bound_sits_between_the_mixed_cell_and_the_100k_run():
    """The benchmark's shards (n = 1,024) count densely; a standalone
    full-mesh run at 100,000 keeps the scatter (1e10 compares a taken tick
    against 0.87 ms)."""
    assert 1024 <= dv.REPLY_COUNT_DENSE_MAX_N < 100_000
