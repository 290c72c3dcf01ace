"""``models/pbft.step``'s quiet-tick gate: the four ring pops and the phases
run only on a tick on which something is due (a block tick, a due ring slot,
a queued block), in one ``while`` of at most one trip, and every final state
and metric is bit-equal to the ungated form of the same tick, which is what
the programs that cannot branch (a mesh axis, ``select_vmap``) run on every
tick."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import SimConfig
from blockchain_simulator_tpu.models import base, pbft
from blockchain_simulator_tpu.models.base import (
    apply_fault_masks,
    canonical_fault_cfg,
    dyn_fault_masks,
    sim_metrics,
)
from blockchain_simulator_tpu.parallel import shard
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils.config import FaultConfig
from test_lane_gate import _ring_shapes, _structure  # tests/: ring updates by place

EDGE = SimConfig(protocol="pbft", n=8, sim_ms=330, stat_sampler="exact")
STAT = EDGE.with_(delivery="stat", schedule="tick")
FORGE = STAT.with_(n=12, model_serialization=False,
                   faults=FaultConfig(byz_forge=True, byz_copies=3))
# name -> (config, seeds, n_byzantine per lane; one lane = the lone program)
CASES = {
    "lone-edge": (EDGE, [3], [0]),
    "lone-stat": (STAT, [3], [0]),
    "lanes-edge": (EDGE, [1, 2, 7, 3], [0, 0, 1, 0]),
    "lanes-stat": (STAT, [1, 2, 7, 3], [0, 2, 0, 1]),
    "window": (EDGE.with_(pbft_window=8, pbft_max_slots=16, sim_ms=700),
               [5], [0]),
    "clean": (EDGE.with_(fidelity="clean"), [5], [0]),
    "clean-stat-lanes": (STAT.with_(fidelity="clean"), [5, 6], [0, 1]),
    "reference": (EDGE.with_(fidelity="reference"), [5], [0]),
    "forge-lanes": (FORGE, [4, 5, 6], [0, 2, 4]),
    "forge-2f1": (FORGE.with_(quorum_rule="2f1"), [4, 5], [0, 3]),
    "queued": (EDGE.with_(queued_links=True, sim_ms=500), [9], [0]),
    "queued-lanes": (EDGE.with_(queued_links=True, sim_ms=500), [9, 10], [0, 0]),
    "gossip": (EDGE.with_(n=16, topology="gossip", degree=4), [2], [0]),
    "kregular": (EDGE.with_(n=16, topology="kregular", degree=6), [2], [0]),
    "kregular-stat": (STAT.with_(n=16, topology="kregular", degree=15,
                                 model_serialization=False), [2, 3], [0, 1]),
    "view-change-early": (EDGE.with_(pbft_view_change_den=1), [2], [0]),
    "view-change-lanes": (STAT.with_(pbft_view_change_den=2), [2, 8], [0, 0]),
}


def _scan(cfg, key, n_byz, record=None):
    """The tick scan of ``runner.make_dyn_sim_fn``, keeping the rings."""
    state, bufs = pbft.init(cfg, jax.random.fold_in(key, 0x1217))
    state = apply_fault_masks(
        cfg, state, *dyn_fault_masks(cfg.n, jnp.int32(0), n_byz))

    def body(carry, t):
        st, bf = pbft.step(cfg, *carry, t, prng.tick_key(key, t))
        return (st, bf), (record(st, bf) if record else ())

    return jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))


def _run(cfg, seeds, n_byz, record=None):
    canon = canonical_fault_cfg(cfg)
    keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))
    n_byz = jnp.asarray(n_byz, jnp.int32)
    if len(seeds) == 1:
        return jax.jit(lambda: _scan(canon, keys[0], n_byz[0], record))()
    return jax.jit(base.lane_vmap(
        lambda k, b: _scan(canon, k, b, record)))(keys, n_byz)


def _ungated(monkeypatch, fn, *args, **kwargs):
    """``fn`` traced with the gate's eyes closed: the phases on every tick,
    the form of the programs that cannot branch."""
    with monkeypatch.context() as m:
        m.setattr(pbft, "can_branch", lambda axis=None: False)
        return fn(*args, **kwargs)


@pytest.mark.parametrize("name", list(CASES))
def test_final_state_rings_and_metrics_equal_the_ungated_tick(monkeypatch, name):
    cfg, seeds, n_byz = CASES[name]
    (state, bufs), _ = _run(cfg, seeds, n_byz)
    (want_state, want_bufs), _ = _ungated(monkeypatch, _run, cfg, seeds, n_byz)
    for field in ("pp", "prep_rt", "commit", "vc"):
        np.testing.assert_array_equal(
            getattr(bufs, field), getattr(want_bufs, field), err_msg=field)
    for got, want in zip(jax.tree.leaves(state), jax.tree.leaves(want_state)):
        np.testing.assert_array_equal(got, want)
    # the ungated form never marks, the gated one leaves no stale mark behind
    # a pop: only slots still ahead of the last tick may be due
    assert not np.asarray(want_bufs.due).any()
    canon = canonical_fault_cfg(cfg)
    rows = []
    for i, nb in enumerate(n_byz):
        cfg_i = canon.with_(faults=FaultConfig(
            n_byzantine=nb, byz_forge=cfg.faults.byz_forge,
            byz_copies=cfg.faults.byz_copies))
        pick = (lambda x: x[i]) if len(seeds) > 1 else (lambda x: x)
        rows.append(sim_metrics(cfg_i, jax.tree.map(pick, state)))
        assert rows[-1] == sim_metrics(cfg_i, jax.tree.map(pick, want_state))
    assert all(r["rounds_sent"] > 0 for r in rows)
    if name.startswith("view-change"):
        assert all(r["view_changes"] > 0 for r in rows)
    if name.startswith("forge"):
        assert [r["forged_commits"] > 0 for r in rows] == [b > 0 for b in n_byz] \
            or cfg.quorum_rule == "2f1"


def test_a_bare_vmap_of_the_tick_is_still_correct():
    """Under an unnamed ``vmap`` the gate's predicate is batched and the
    ``while`` selects its whole carry per lane (the trap of KNOWN_ISSUES
    #0b'): slow, and every row is still the lane batch's."""
    cfg, seeds, n_byz = CASES["lanes-stat"]
    canon = canonical_fault_cfg(cfg)
    keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))
    (got, _), _ = jax.jit(jax.vmap(lambda k, b: _scan(canon, k, b)))(
        keys, jnp.asarray(n_byz, jnp.int32))
    (want, _), _ = _run(cfg, seeds, n_byz)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------- due bits


def _record(st, bf):
    rings = (bf.pp, bf.prep_rt, bf.commit, bf.vc)
    return {"due": bf.due,
            "holds": jnp.stack([(r != 0).any(axis=tuple(range(1, r.ndim)))
                                for r in rings])}


@pytest.mark.parametrize("name", ["lone-edge", "lone-stat", "gossip",
                                  "view-change-early"])
def test_due_bits_are_exact(monkeypatch, name):
    """After every tick: no slot that is not due holds a value, and a slot is
    due iff a push arm ran for it since it was last popped (the arms'
    predicates are read from the ungated form, whose ``gated_push`` sites
    are traced at the scan body's own level)."""
    cfg, seeds, n_byz = CASES[name]
    _, got = _run(cfg, seeds, n_byz, _record)
    due, holds = np.asarray(got["due"]), np.asarray(got["holds"])
    assert not (holds & ~due).any()

    preds = []

    def spying(pred, *args, **kwargs):
        preds.append(pred)
        return base.gated_push(pred, *args, **kwargs)

    def sent(st, bf):
        out = jnp.stack(preds)
        preds.clear()
        return out

    with monkeypatch.context() as m:
        m.setattr(pbft, "gated_push", spying)
        _, ran = _ungated(monkeypatch, _run, cfg, seeds, n_byz, sent)
    ran = np.asarray(ran)  # [T, 4] in call-site order
    lo, hi = cfg.one_way_range()
    rt_lo, rt_hi = cfg.roundtrip_range()
    ser = cfg.serialization_ticks(cfg.pbft_block_bytes)
    # (row of ``due``, first bucket, buckets) by call site: PREPARE_RES,
    # COMMIT, PRE_PREPARE, VIEW_CHANGE
    sites = [(1, rt_lo, rt_hi - rt_lo), (2, lo, hi - lo),
             (0, lo + ser, hi - lo), (3, lo, hi - lo)]
    d = due.shape[-1]
    want = np.zeros((4, d), bool)
    active = 0
    for t in range(cfg.ticks):
        active += bool(want[:, t % d].any() or (t > 0 and t % cfg.pbft_block_interval_ms == 0))
        want[:, t % d] = False
        for (row, first, buckets), on in zip(sites, ran[t]):
            if on:
                want[row, (t + first + np.arange(buckets)) % d] = True
        np.testing.assert_array_equal(due[t], want, err_msg=f"tick {t}")
    assert ran.any(axis=0)[:3].all()
    # the share of ticks with anything to do is what the gate is for
    assert 0 < active < cfg.ticks * 0.6, active


def test_active_ticks_equal_the_schedule(monkeypatch):
    """With one-bucket delays the schedule is arithmetic: per block the
    block tick, the PRE_PREPARE's arrival, the replies' and the COMMITs',
    four taken trips; the four pops and the phases run on those ticks and no
    others."""
    cfg = STAT.with_(pbft_delay_lo=3, pbft_delay_hi=3, pbft_view_change_num=0,
                     model_serialization=False, sim_ms=330)
    lo, hi = cfg.one_way_range()
    assert hi - lo == 1 and cfg.roundtrip_range() == (2 * lo, 2 * lo + 1)
    ticks, pops = [], []
    phases, ring_pop = pbft._phases, pbft.ring_pop

    def counted(cfg_, state, bufs, popped, t, *args, **kwargs):
        jax.debug.callback(lambda t: ticks.append(int(t)), t)
        return phases(cfg_, state, bufs, popped, t, *args, **kwargs)

    def counted_pop(buf, t):
        jax.debug.callback(lambda t: pops.append(int(t)), t)
        return ring_pop(buf, t)

    with monkeypatch.context() as m:
        m.setattr(pbft, "_phases", counted)
        m.setattr(pbft, "ring_pop", counted_pop)
        (state, _), _ = jax.block_until_ready(_run(cfg, [1], [0]))
    jax.effects_barrier()
    bt = cfg.pbft_block_interval_ms
    want = sorted(b + off for b in range(bt, cfg.ticks, bt)
                  for off in (0, lo, 3 * lo, 4 * lo) if b + off < cfg.ticks)
    assert sorted(ticks) == want
    assert sorted(pops) == sorted(want * 4)
    assert len(want) == 4 * ((cfg.ticks - 1) // bt)
    assert sim_metrics(cfg, state)["blocks_final_all_nodes"] == (cfg.ticks - 1) // bt


# --------------------------------------------------------------- lowering


def _whiles(traced):
    return traced.lower().as_text().count("stablehlo.while")


def _lone(cfg):
    canon = canonical_fault_cfg(cfg)
    return jax.jit(lambda k: _scan(canon, k, jnp.int32(0))[0][0]).trace(
        jax.random.key(0))


def _lanes(cfg):
    canon = canonical_fault_cfg(cfg)
    return jax.jit(base.lane_vmap(
        lambda k: _scan(canon, k, jnp.int32(0))[0][0])).trace(
            jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32)))


def _select(cfg):
    canon = canonical_fault_cfg(cfg)
    return jax.jit(base.select_vmap(
        lambda k: _scan(canon, k, jnp.int32(0))[0][0])).trace(
            jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32)))


def _sharded(cfg):
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
    return shard.make_sharded_sim_fn.__wrapped__(cfg, mesh).trace(
        jax.random.key(0))


@pytest.mark.parametrize("delivery", ["edge", "stat"])
@pytest.mark.parametrize("program,new", [
    (_lone, 1), (_lanes, 1), (_select, 0), (_sharded, 0)])
def test_one_new_while_where_the_program_can_branch_and_none_elsewhere(
        monkeypatch, program, new, delivery):
    cfg = (EDGE if delivery == "edge" else STAT).with_(sim_ms=210)
    assert _whiles(program(cfg)) == \
        _ungated(monkeypatch, lambda: _whiles(program(cfg))) + new


@pytest.mark.parametrize("program,gated", [
    (_lone, True), (_lanes, True), (_select, False)])
def test_no_ring_is_updated_at_the_scan_bodys_level_where_the_program_can_branch(
        program, gated):
    """Pops and pushes alike stand inside the gate's ``while`` (the pushes
    in loops of their own within it); the program that cannot branch pops
    and pushes at the body's own level."""
    cfg = EDGE.with_(sim_ms=210)
    selects, outside, inside = _structure(program(cfg).jaxpr, _ring_shapes(cfg))
    assert selects == []
    if gated:
        assert outside == 0 and inside > 4
    else:
        assert outside > 4 and inside == 0


def test_taken_scope_wraps_the_phases_and_leaves_them_outermost():
    """Every phase, the pops' own ring work too, sits under the taken trip's
    scope and nowhere else; that scope is outside the ``pbft.`` / ``ops.``
    families: a reader that takes the first such scope of a path still reads
    the phase."""
    text = _lone(EDGE.with_(sim_ms=210)).lower().as_text(debug_info=True)
    taken = pbft.TAKEN_SCOPE
    assert not taken.startswith(("pbft.", "ops."))
    for phase in pbft.SCOPES:
        if phase in (taken, "pbft.tick.forge"):
            continue
        assert f"{taken}/{phase}/" in text, phase
    pops = "pbft.tick.pop/ops.ring.ring_pop/"
    assert text.count(f"{taken}/{pops}") == text.count(pops) > 0


def test_a_checkpoint_without_the_due_leaf_is_refused(tmp_path):
    """A checkpoint the parent wrote has four buffer leaves and no due bits:
    resuming it under the gate would skip its arrivals, so loading says
    what is wrong instead."""
    from blockchain_simulator_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint)

    cfg = EDGE
    state, bufs = pbft.init(cfg, jax.random.key(0))
    save_checkpoint(tmp_path / "now.npz", cfg, state, bufs, 7)
    assert load_checkpoint(tmp_path / "now.npz")[3] == 7
    old = (bufs.pp, bufs.prep_rt, bufs.commit, bufs.vc)
    save_checkpoint(tmp_path / "old.npz", cfg, state, old, 7)
    with pytest.raises(ValueError, match="buffer leaves"):
        load_checkpoint(tmp_path / "old.npz")
