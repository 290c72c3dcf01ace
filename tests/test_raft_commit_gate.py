"""``models/raft.step``'s commit gate: ``block_tick`` is written only on a
tick on which some node's commit lands, in one ``while`` of at most one trip
(``base.gated_body``), and every final state is bit-equal to the
unconditional one-hot select, which is what the programs that cannot branch
(a mesh axis, ``select_vmap``) still run on every tick."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import SimConfig, run_simulation
from blockchain_simulator_tpu.models import base, raft
from blockchain_simulator_tpu.parallel import shard
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.runner import make_sim_fn
from blockchain_simulator_tpu.utils import prng

# proposals 100 ms after the election instead of 1 s: the first commits fall
# inside a window short enough to step tick by tick; the exact sampler, so
# that a stat lane draws what its solo run draws
EDGE = SimConfig(protocol="raft", n=8, sim_ms=700, schedule="tick",
                 raft_proposal_delay_ms=100, model_serialization=False,
                 stat_sampler="exact")
STAT = EDGE.with_(delivery="stat")
CASES = {
    "edge-clean": EDGE.with_(fidelity="clean"),
    "edge-reference": EDGE.with_(fidelity="reference"),
    "stat-clean": STAT.with_(fidelity="clean"),
    "stat-reference": STAT.with_(fidelity="reference"),
}


def _scan(cfg, key, record=None):
    """The tick scan of ``runner.make_sim_fn``, with something kept a tick."""
    state, bufs = raft.init(cfg, jax.random.fold_in(key, 0x1217))

    def body(carry, t):
        st, bf = raft.step(cfg, *carry, t, prng.tick_key(key, t))
        return (st, bf), (record(st) if record else ())

    (state, _), ys = jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))
    return state, ys


def _keys(seeds):
    return jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))


def _ungated(monkeypatch, fn, *args, **kwargs):
    """``fn`` traced with the gate's eyes closed: the select on every tick,
    the form of the programs that cannot branch."""
    with monkeypatch.context() as m:
        m.setattr(raft, "can_branch", lambda axis=None: False)
        return fn(*args, **kwargs)


def _assert_states_equal(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def _oracle(block_num, blocks):
    """``block_tick`` as the per-tick ``block_num`` series ``[T, N]`` says it
    must be: the tick on which node i went b -> b + 1, -1 elsewhere."""
    want = np.full((block_num.shape[1], blocks), -1, np.int32)
    prev = np.zeros(block_num.shape[1], np.int32)
    for t, now in enumerate(block_num):
        assert ((now == prev) | (now == prev + 1)).all()
        for i in np.flatnonzero(now > prev):
            want[i, prev[i]] = t
        prev = now
    return want


# ------------------------------------------------------------------ oracle


@pytest.mark.parametrize("name", list(CASES))
def test_block_tick_is_the_tick_of_each_commit(name):
    """Needs no switch: whatever writes the table, entry (i, b) is the tick
    on which ``block_num[i]`` went from b to b + 1."""
    cfg = CASES[name]
    state, block_num = jax.jit(
        lambda k: _scan(cfg, k, lambda st: st.block_num))(jax.random.key(3))
    block_num = np.asarray(block_num)
    assert block_num[-1].max() >= 3, "the window must hold three commits"
    want = _oracle(block_num, cfg.raft_max_blocks)
    np.testing.assert_array_equal(state.block_tick, want)
    assert (want >= 0).sum() == block_num[-1].sum()


@pytest.mark.parametrize("name", list(CASES))
def test_final_state_equals_the_unconditional_select(monkeypatch, name):
    cfg = CASES[name]
    run = lambda: jax.jit(lambda k: _scan(cfg, k)[0])(jax.random.key(5))
    _assert_states_equal(run(), _ungated(monkeypatch, run))


# -------------------------------------------------------------- lane batch


@pytest.mark.parametrize("delivery", ["edge", "stat"])
def test_every_lane_equals_its_solo_run(delivery):
    """Lanes elect, and so commit, on different ticks: the trip is taken
    when any lane's commit lands and is the identity on the others."""
    cfg = EDGE.with_(delivery=delivery)
    seeds = [1, 2, 7, 3]
    sim = make_sim_fn(cfg)
    lanes = jax.jit(base.lane_vmap(sim))(_keys(seeds))
    ticks = np.asarray(lanes.block_tick).max(axis=1)  # [lanes, B], per lane
    first = ticks[:, 0]
    assert (first >= 0).all() and len(set(first.tolist())) > 1, first
    # a tick on which one lane commits and another does not
    assert not np.isin(ticks[0][ticks[0] >= 0], ticks[1]).all()
    for i, seed in enumerate(seeds):
        solo = sim(jax.random.key(seed))
        _assert_states_equal(jax.tree.map(lambda x: x[i], lanes), solo)


# ---------------------------------------------------------------- lowering


def _whiles(lowered):
    return lowered.as_text().count("stablehlo.while")


def _lone(cfg):
    return jax.jit(lambda k: _scan(cfg, k)[0]).lower(jax.random.key(0))


def _lanes(cfg):
    return jax.jit(base.lane_vmap(lambda k: _scan(cfg, k)[0])).lower(
        _keys([0, 1]))


def _select(cfg):
    return jax.jit(base.select_vmap(lambda k: _scan(cfg, k)[0])).lower(
        _keys([0, 1]))


def _sharded(cfg):
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
    return shard.make_sharded_sim_fn.__wrapped__(cfg, mesh).lower(
        jax.random.key(0))


@pytest.mark.parametrize("delivery", ["edge", "stat"])
@pytest.mark.parametrize("program,new", [
    (_lone, 1), (_lanes, 1), (_select, 0), (_sharded, 0)])
def test_one_new_while_where_the_program_can_branch_and_none_elsewhere(
        monkeypatch, program, new, delivery):
    cfg = EDGE.with_(delivery=delivery, sim_ms=210)
    gated = program(cfg)
    ungated = _ungated(monkeypatch, program, cfg)
    assert _whiles(gated) == _whiles(ungated) + new
    if not new:  # the parent's form, letter for letter
        assert gated.as_text() == ungated.as_text()


@pytest.mark.parametrize("delivery", ["edge", "stat"])
def test_select_vmap_lanes_equal_their_solo_runs(delivery):
    cfg = EDGE.with_(delivery=delivery)
    seeds = [1, 2]
    lanes = jax.jit(base.select_vmap(lambda k: _scan(cfg, k)[0]))(_keys(seeds))
    assert np.asarray(lanes.block_num).max(axis=1).min() >= 3
    for i, seed in enumerate(seeds):
        solo = jax.jit(lambda k: _scan(cfg, k)[0])(jax.random.key(seed))
        _assert_states_equal(jax.tree.map(lambda x: x[i], lanes), solo)


def test_sharded_run_commits_what_the_solo_run_commits():
    """Under a mesh axis the select runs on every tick, as it always did
    (the sharded draws fold the shard index, so the comparison is
    ``tests/test_parallel.py``'s: the same leader count, blocks within 2),
    and the leader's row of the table is one rising tick a block."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    cfg = EDGE.with_(n=16, sim_ms=900)
    mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
    state = shard.readback(cfg, mesh, shard.make_sharded_sim_fn(cfg, mesh)(
        jax.random.key(cfg.seed)))
    m_s, m_u = raft.metrics(cfg, state), run_simulation(cfg)
    assert m_s["n_leaders"] == m_u["n_leaders"] == 1
    assert m_s["blocks"] >= 3 and abs(m_s["blocks"] - m_u["blocks"]) <= 2
    row = np.asarray(state.block_tick)[m_s["leader"]]
    assert (np.diff(row[:m_s["blocks"]]) > 0).all() and row[0] > 0
    assert (row[m_s["blocks"]:] == -1).all()
    others = np.delete(np.asarray(state.block_tick), m_s["leader"], axis=0)
    assert (others == -1).all()


# --------------------------------------------------------------- structure


def _selects_of_the_table(jaxpr, shape, inside=False):
    """For every ``select_n`` with an operand of ``shape``, through every
    nested jaxpr: whether it stands inside a ``while`` body."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and any(
                getattr(v.aval, "shape", None) == shape for v in eqn.invars):
            out.append(inside)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _selects_of_the_table(
                sub, shape, inside or eqn.primitive.name == "while")
    return out


@pytest.mark.parametrize("name", list(CASES))
def test_no_select_over_the_table_outside_the_gate(monkeypatch, name):
    cfg = CASES[name]
    state, bufs = raft.init(cfg, jax.random.key(0))
    shape = (cfg.n, cfg.raft_max_blocks)

    def where():
        # a function of its own each time: a trace is cached by identity
        def tick(state, bufs):
            return raft.step(cfg, state, bufs, jnp.int32(5),
                             prng.tick_key(jax.random.key(0), 5))

        return _selects_of_the_table(
            jax.make_jaxpr(tick)(state, bufs).jaxpr, shape)

    assert where() == [True]
    assert _ungated(monkeypatch, where) == [False]


def test_taken_scope_sits_under_the_ack_phase():
    """The trip's operations read ``raft.tick.ack_rx/.../gate.raft.commit_
    taken`` on their scope path: the phase stays the outermost program scope,
    the gate's name is outside the ``raft.`` / ``ops.`` families, and the
    device events under it are the ticks on which the table was written."""
    text = _lone(STAT.with_(sim_ms=210)).as_text(debug_info=True)
    taken = raft.COMMIT_SCOPE
    assert taken in raft.SCOPES and not taken.startswith(("raft.", "ops."))
    paths = re.findall(rf'loc\("([^"]*{re.escape(taken)}/[^"]*)"', text)
    under = [p for p in paths if p.startswith("raft.tick.ack_rx/")]
    # the stamp's own operations: the one-hot, the mask, the select
    assert {p.rsplit("/", 1)[1] for p in under} >= {
        "jit(_one_hot)", "and", "jit(_where)"}
    for phase in raft.SCOPES:
        if phase != taken:
            assert not any(f"{taken}/{phase}" in p for p in paths), phase
