"""Link classes (``SimConfig.link_classes`` / ``link_class_delay_ms``,
``ops/linkclass.py``), held from seven sides.

(a) **Against the plain reference** (``benchmark/reference/
    pbft_geo_engine.py``: one event a message, its arrival the class pair's
    propagation + its own jitter draw + a block's serialization; loaded by
    path, it imports nothing of the program): three classes of 6 / 5 / 3
    nodes over seeded runs, a symmetric and an asymmetric matrix, both quorum
    rules, a crashed node and a vote flipper.  A run's milestones are a
    maximum over few jitter draws, so the limits are set from readings,
    written beside them (``LIMIT_MS``).
(b) **An asymmetric matrix delivers by direction**: one message on one edge,
    the line read back at the pair's own offset and at no other.
(c) **One class whose matrix holds ``link_delay_ms``** is dict-equal to the
    program without classes on the same seeds, and its jitter draws are the
    dense arms' own, number for number.
(d) **Without classes nothing is there**: the same registry key, the same
    jaxpr, no leaf, no ``ops.linkclass`` in the lowered text.
(e) **Lists and tuples** give equal, hashable configurations; what is not a
    K x K matrix of whole ms over counts that sum to ``n`` is refused.
(f) **A swept row, a fault-swept row and a served row** equal the solo run
    of their seed.
(g) **Every arm that cannot run classes refuses them by name**; the counters;
    the compiled program's shapes do not grow with the delay span.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import engine, runner
from blockchain_simulator_tpu.models import pbft
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops import linkclass as lc
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.utils import aotcache, telemetry
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659  # one past 2**31, as the driver's are

COUNTS = (6, 5, 3)
SYM = ((3, 40, 90), (40, 4, 130), (90, 130, 5))
ASYM = ((3, 40, 90), (10, 4, 130), (20, 60, 5))
FIELDS = dict(protocol="pbft", n=14, sim_ms=1500, link_classes=COUNTS,
              link_class_delay_ms=SYM, quorum_rule="2f1")

# |program - reference| on the mean time to finality and on the last final
# block's commit time, ms.  Both engines draw U{3,4,5} a message from streams
# of their own; a milestone is the latest of 14 nodes' crossings, each an
# order statistic of a few draws.  Readings on this host over the seeds
# below, every case: at most 0.41 on the mean and 2.0 on the last commit.
# One matrix entry off by 9 ms (40 -> 31, both directions) reads 9.0 to 18.4
# on the mean.
LIMIT_MS = {"mean_time_to_finality_ms": 2.0, "last_commit_ms": 4.0}

CASES = {
    "sym-2f1": {},
    "sym-n2": {"quorum_rule": "n2"},
    "asym-2f1": {"link_class_delay_ms": ASYM},
    "asym-n2": {"link_class_delay_ms": ASYM, "quorum_rule": "n2"},
    "crashed": {"faults": {"n_crashed": 1}},
    "flipper": {"faults": {"n_byzantine": 1}},
}


@pytest.fixture(scope="module")
def geo_engine():
    path = os.path.join(ROOT, "benchmark", "reference", "pbft_geo_engine.py")
    spec = importlib.util.spec_from_file_location("ref_pbft_geo", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(fields: dict, **kw) -> SimConfig:
    fields = {**fields, **kw}
    faults = FaultConfig(**fields.pop("faults", {}))
    return SimConfig(**fields, faults=faults, stat_sampler="exact")


# ------------------------------------------------ (a) the plain reference ---


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "pbft_geo_engine.py")).read()
    assert not re.search(r"^\s*(from|import)\s+(blockchain_simulator_tpu|jax"
                         r"|numpy)", src, re.M)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("seed", [SEED, SEED + 7919])
def test_program_against_the_reference(case, seed, geo_engine, shared):
    fields = {**FIELDS, **CASES[case]}
    # view changes off on both sides: a view change stalls the pipeline by a
    # round or two, on a draw (benchmark/checks.against_reference compares
    # the calm rows alone)
    got = shared(f"zzlinkclass.ref.{case}.{seed}", lambda: (
        runner.run_simulation(_cfg(fields, pbft_view_change_num=0), seed=seed),
        geo_engine.run(fields, seed, pbft_view_change_num=0)))
    prog, ref = got
    assert prog["agreement_ok"] and ref["agreement_ok"]
    assert prog["blocks_final_all_nodes"] >= 15
    for key in ("rounds_sent", "blocks_final_all_nodes", "view_changes",
                "leader_rounds_max", "block_num_max"):
        assert prog[key] == ref[key], (key, prog, ref)
    for key, limit in LIMIT_MS.items():
        assert abs(prog[key] - ref[key]) <= limit, (key, prog, ref)


def test_one_entry_off_by_9_ms_is_told_apart(geo_engine, shared):
    """What the limits are for: the cell's control at this size."""
    off = tuple(tuple(31 if d == 40 else d for d in row) for row in SYM)
    prog = shared("zzlinkclass.ref.control", lambda: runner.run_simulation(
        _cfg(FIELDS, link_class_delay_ms=off, pbft_view_change_num=0),
        seed=SEED))
    ref = geo_engine.run(FIELDS, SEED, pbft_view_change_num=0)
    key = "mean_time_to_finality_ms"
    assert abs(prog[key] - ref[key]) > 2 * LIMIT_MS[key]


def test_view_changes_run_on_both_sides(geo_engine, shared):
    """With upstream's 1-in-100 coin on: rounds and agreement hold; the
    milestones are draws of unlike streams and are not compared."""
    fields = {**FIELDS, "pbft_view_change_num": 20}
    prog = shared("zzlinkclass.ref.vc", lambda: runner.run_simulation(
        _cfg(fields), seed=SEED))
    ref = geo_engine.run(fields, SEED)
    assert prog["view_changes"] > 0 and ref["view_changes"] > 0
    assert prog["agreement_ok"] and ref["agreement_ok"]
    assert prog["blocks_final_all_nodes"] > 0


# ------------------------------------------- (b) delivery by direction ------


def test_an_asymmetric_matrix_delivers_by_direction():
    """Node 0 (class 0) and node 3 (class 1) each send once at tick 5:
    class 1 reads node 0's value ``ASYM[0][1] - base`` ticks later, class 0
    reads node 3's ``ASYM[1][0] - base`` later, and at no other tick."""
    cfg = _cfg(FIELDS, n=5, link_classes=(3, 2),
               link_class_delay_ms=((3, 40), (10, 4)))
    plan = lc.one_way_plan(cfg)
    assert cfg.link_base_ms == 3 and plan.offsets == (0, 1, 7, 37)
    line = lc.line_init(plan, (5,), jnp.int32)
    seen = {}
    for t in range(60):
        line = lc.line_clear(line, t)
        value = jnp.zeros((5,), jnp.int32)
        if t == 5:
            value = value.at[0].set(11).at[3].set(22)
        line = lc.line_put(line, t, value)
        assert bool(lc.line_any(line, t, plan)) == (t - 5 in plan.offsets)
        got = np.asarray(lc.line_get(line, t, plan))  # [K reader, N sender]
        for k, i in zip(*np.nonzero(got)):
            seen[(int(i), int(k))] = (t - 5, int(got[k, i]))
    assert seen == {(0, 0): (0, 11), (0, 1): (37, 11),
                    (3, 0): (7, 22), (3, 1): (1, 22)}


def test_a_slot_of_a_skipped_tick_reads_nothing():
    """A quiet tick writes nothing: what its slot still holds from ``depth``
    ticks ago must not be read again."""
    cfg = _cfg(FIELDS)
    plan = lc.one_way_plan(cfg)
    line = lc.line_init(plan, (14,), jnp.int32)
    line = lc.line_put(lc.line_clear(line, 0), 0, jnp.full((14,), 7))
    for t in range(1, plan.depth + 1):  # every tick but the first skipped
        line = lc.line_clear(line, t)
    t = plan.depth  # slot 0 again: cleared, never rewritten
    assert not np.asarray(lc.line_get(line, t, plan)).any()
    assert not bool(lc.line_any(line, t, plan))


@pytest.mark.parametrize("w", [64, 40, 8, 70])
def test_a_row_of_flags_packs_into_words_and_back(w):
    """The PREPARE line keeps a bit a window, whatever the table's width."""
    flags = jnp.asarray(np.random.RandomState(w).rand(3, 5, w) < 0.3)
    words = lc.pack_bits(flags)
    assert words.shape == (3, 5, -(-w // 32)) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(lc.unpack_bits(words, w), flags)
    assert not np.asarray(lc.pack_bits(jnp.zeros((2, w), bool))).any()


def test_the_round_trip_sums_both_directions():
    cfg = _cfg(FIELDS, link_class_delay_ms=ASYM)
    rt = lc.roundtrip_plan(cfg)
    for a in range(3):
        for b in range(3):
            assert rt.offsets[rt.index[a][b]] == ASYM[a][b] + ASYM[b][a] - 6


# ---------------------------------------------- (c) one class of 3s ---------


@pytest.mark.parametrize("rule", ["n2", "2f1"])
def test_one_class_holding_link_delay_is_todays_program(rule, shared):
    base = SimConfig(protocol="pbft", n=12, sim_ms=900, quorum_rule=rule,
                     stat_sampler="exact", pbft_view_change_num=10)
    one = base.with_(link_classes=[12], link_class_delay_ms=[[3]])
    assert one.ring_depth == base.ring_depth
    rows = shared(f"zzlinkclass.one.{rule}", lambda: [
        (runner.run_simulation(base, seed=s), runner.run_simulation(one, seed=s))
        for s in (SEED, 11)])
    for want, got in rows:
        assert want == got
        assert want["blocks_final_all_nodes"] > 5


def test_one_class_arms_are_the_dense_arms_number_for_number():
    key = jax.random.key(5)
    n, w, bounds = 9, 4, ((0, 9),)
    r = np.random.RandomState(0)
    slots = jnp.asarray(r.randint(0, 3, (n, w)) * (r.rand(n, 1) < 0.5))
    value = jnp.asarray(r.randint(0, 9, (n,)) * (r.rand(n) < 0.4))
    send = jnp.asarray(r.rand(n) < 0.5)
    peers = jnp.asarray(r.rand(n) < 0.8)
    for drop in (0.0, 0.2):
        np.testing.assert_array_equal(
            dv.bcast_slots_dense(key, slots, 6, 9, drop),
            dv.bcast_slots_classed(key, slots[None], bounds, 6, 9, drop))
        np.testing.assert_array_equal(
            dv.bcast_window_value_max_dense(key, slots, 6, 9, drop),
            dv.bcast_window_value_max_classed(key, slots[None], bounds, 6, 9,
                                              drop))
        np.testing.assert_array_equal(
            dv.bcast_value_max_dense(key, value > 0, value, 6, 9, drop),
            dv.bcast_value_max_classed(key, value[None], bounds, 6, 9, drop))
        np.testing.assert_array_equal(
            dv.roundtrip_reply_counts_dense(key, send, 6, 9, drop,
                                            peer_mask=peers),
            dv.roundtrip_reply_counts_classed(key, send, bounds, 6, 9, drop,
                                              peer_mask=peers)[:, 0])


def test_classed_arms_split_the_dense_arms_by_class():
    """Three classes reading ONE sender tensor: the columns (and the peers'
    counts) of the dense arm, whatever the classes' sizes."""
    key = jax.random.key(6)
    n, w = 14, 4
    bounds = lc.one_way_plan(_cfg(FIELDS)).bounds
    r = np.random.RandomState(1)
    slots = jnp.asarray(r.randint(0, 3, (n, w)))
    send = jnp.asarray(r.rand(n) < 0.6)
    np.testing.assert_array_equal(
        dv.bcast_slots_dense(key, slots, 6, 9),
        dv.bcast_slots_classed(key, jnp.stack([slots] * 3), bounds, 6, 9))
    np.testing.assert_array_equal(
        dv.roundtrip_reply_counts_dense(key, send, 6, 9),
        dv.roundtrip_reply_counts_classed(key, send, bounds, 6, 9).sum(1))


# ---------------------------------------- (d) without classes: nothing ------


def test_without_classes_the_registry_key_and_the_jaxpr_are_the_parents():
    """A configuration that names no class equals one built before the
    fields existed (their defaults), is one registry entry with it, and its
    program holds no leaf and no operation of theirs."""
    plain = SimConfig(protocol="pbft", n=8, sim_ms=120, stat_sampler="exact")
    named = SimConfig(protocol="pbft", n=8, sim_ms=120, stat_sampler="exact",
                      link_classes=(), link_class_delay_ms=[])
    assert plain == named and hash(plain) == hash(named)
    assert runner.make_sim_fn(plain) is runner.make_sim_fn(named)
    state, bufs = jax.eval_shape(lambda: pbft.init(plain))
    assert bufs.lines is None
    assert len(jax.tree.leaves(bufs)) == 5  # four rings and the due bits
    text = runner.make_sim_fn(plain).lower(jax.random.key(0)).as_text(
        debug_info=True)
    # (scope paths, ``<scope>/``: a traceback frame's bare function name may
    # stand in the location table of any program lowered after a classed one)
    assert not any(f"{scope}/" in text for scope in lc.SCOPES + tuple(
        s for s in dv.SCOPES if s.endswith("_classed")))
    # and the jaxpr is the one the fields' defaults give: a configuration
    # that spells the defaults out traces to the same text (the parent's own
    # fingerprint of this program, ``sim.pbft_tick`` in GRAPH_BASELINE.json's
    # audit, is held by tests/test_zzgraph*.py and unchanged by this file)
    trace = lambda cfg: str(jax.make_jaxpr(
        runner.make_sim_fn.__wrapped__(cfg))(jax.random.key(0)))
    assert trace(plain) == trace(named)
    one = plain.with_(link_classes=(8,), link_class_delay_ms=((3,),))
    assert "dynamic_slice" in trace(plain)
    assert len(trace(one)) > len(trace(plain))


def test_the_classed_program_adds_no_scatter():
    def scatters(cfg):
        jaxpr = str(jax.make_jaxpr(runner.make_sim_fn.__wrapped__(cfg))(
            jax.random.key(0)))
        return len(re.findall(r"\bscatter[-_a-z]*\[", jaxpr))

    plain = SimConfig(protocol="pbft", n=8, sim_ms=120, stat_sampler="exact")
    geo = plain.with_(link_classes=(4, 3, 1), link_class_delay_ms=(
        (3, 12, 30), (10, 4, 25), (30, 20, 5)))
    assert scatters(geo) == scatters(plain) > 0


# ------------------------------------------------ (e) the two fields --------


def test_lists_and_tuples_give_equal_hashable_configs():
    a = _cfg(FIELDS, link_classes=list(COUNTS),
             link_class_delay_ms=[list(r) for r in SYM])
    b = _cfg(FIELDS)
    assert a == b and hash(a) == hash(b)
    assert isinstance(a.link_classes, tuple)
    assert all(isinstance(r, tuple) for r in a.link_class_delay_ms)
    assert runner.make_sim_fn(a) is runner.make_sim_fn(b)
    from blockchain_simulator_tpu.utils import checkpoint

    assert checkpoint.config_from_json(checkpoint.config_to_json(a)) == a


@pytest.mark.parametrize("kw,says", [
    (dict(link_classes=(6, 5, 2)), "sum to n=14"),
    (dict(link_classes=(6, 5, 3, 0)), "sum to n=14"),
    (dict(link_classes=(14,)), "square 1 x 1"),
    (dict(link_class_delay_ms=((3, 40, 90), (40, 4, 130))), "square 3 x 3"),
    (dict(link_class_delay_ms=((3, 40), (40, 4), (90, 130))), "square 3 x 3"),
    (dict(link_class_delay_ms=((3, 40, 90), (40, -4, 130), (90, 130, 5))),
     "whole ms >= 0"),
    (dict(link_class_delay_ms=((3, 40, 90), (40, 4.5, 130), (90, 130, 5))),
     "whole ms >= 0"),
    (dict(link_classes=()), "needs link_classes"),
])
def test_a_malformed_class_field_is_refused(kw, says):
    with pytest.raises(ValueError, match=says):
        SimConfig(**{**FIELDS, **kw})


def test_ranges_and_depths_follow_the_smallest_entry():
    cfg = _cfg(FIELDS)  # smallest entry 3: upstream's link delay
    plain = SimConfig(protocol="pbft", n=14)
    assert cfg.link_base_ms == 3
    assert cfg.one_way_range() == plain.one_way_range() == (6, 9)
    assert cfg.roundtrip_range() == plain.roundtrip_range()
    assert cfg.ring_depth == plain.ring_depth
    far = _cfg(FIELDS, link_class_delay_ms=tuple(
        tuple(d + 6 for d in row) for row in SYM))
    assert far.link_base_ms == 9 and far.one_way_range() == (12, 15)
    ow, rt = lc.one_way_plan(cfg), lc.roundtrip_plan(cfg)
    assert ow.bounds == ((0, 6), (6, 11), (11, 14))
    assert ow.offsets == (0, 1, 2, 37, 87, 127) and ow.depth == 128
    assert rt.offsets == (0, 2, 4, 74, 174, 254) and rt.depth == 255
    assert lc.one_way_plan(far) == ow  # the plan holds what a pair ADDS


# --------------------------------- (f) swept, fault-swept and served rows ---


@pytest.fixture(scope="module")
def rows(shared):
    cfg = _cfg(FIELDS, sim_ms=700)
    levels = [FaultConfig(n_crashed=1), FaultConfig(n_byzantine=1)]

    def build():
        from blockchain_simulator_tpu.serve import ScenarioServer

        seeds = [SEED, 12]
        fault = sweep.run_fault_sweep(cfg, levels, seeds)
        with ScenarioServer(max_batch=2, max_wait_ms=2000.0) as srv:
            tpl = {**FIELDS, "sim_ms": 700, "stat_sampler": "exact",
                   "link_classes": list(COUNTS),
                   "link_class_delay_ms": [list(r) for r in SYM]}
            pends = [srv.submit(dict(tpl, seed=seeds[0])),
                     srv.submit(dict(tpl, seed=seeds[1],
                                     faults={"n_byzantine": 1}))]
            served = [p.result(600) for p in pends]
        return {
            "seeds": seeds,
            "swept": sweep.run_seed_sweep(cfg, seeds),
            "fault": [fault[fc] for fc in levels],
            "served": served,
            "solo": [runner.run_simulation(cfg, seed=s) for s in seeds],
            "solo_fault": [[runner.run_simulation(cfg.with_(faults=fc), seed=s)
                            for s in seeds] for fc in levels],
        }

    return shared("zzlinkclass.rows", build)


def test_a_swept_row_equals_the_solo_run_of_its_seed(rows):
    assert rows["swept"] == rows["solo"]
    assert all(m["agreement_ok"] and m["blocks_final_all_nodes"] > 0
               for m in rows["swept"])


@pytest.mark.parametrize("level", [0, 1], ids=["crashed", "flipper"])
def test_a_fault_swept_row_equals_the_solo_run_of_its_point(rows, level):
    assert rows["fault"][level] == rows["solo_fault"][level]


def test_a_served_row_equals_the_solo_run_of_its_seed(rows):
    a, b = rows["served"]
    assert a["status"] == b["status"] == "ok"
    assert a["batch"]["mode"] == "batched" and a["batch"]["size"] == 2
    norm = lambda m: {k: str(v) for k, v in m.items()}
    assert norm(a["metrics"]) == norm(rows["solo"][0])
    assert norm(b["metrics"]) == norm(rows["solo_fault"][1][1])


def test_a_checkpointed_run_equals_the_uninterrupted_one(rows, tmp_path):
    """The lines are leaves of the buffers like the rings: a run cut into
    segments, and one resumed from its last checkpoint, read them back."""
    cfg = _cfg(FIELDS, sim_ms=700)
    cut, path = runner.run_checkpointed(cfg, 300, tmp_path, seed=rows["seeds"][0])
    assert cut == rows["solo"][0]
    assert runner.resume_simulation(path) == rows["solo"][0]


# ------------------------------------------------- (g) the other arms -------

REFUSED = {
    "delivery='stat'": dict(delivery="stat", schedule="tick"),
    "schedule='round'": dict(schedule="round"),
    "topology='gossip'": dict(topology="gossip", degree=4),
    "topology='kregular'": dict(topology="kregular", degree=4),
    "topology='committee'": dict(topology="committee", committees=2),
    "queued_links": dict(queued_links=True),
    "a mesh axis": dict(mesh_axis="nodes"),
    "protocol='raft'": dict(protocol="raft", quorum_rule="n2"),
    "protocol='paxos'": dict(protocol="paxos", quorum_rule="n2"),
    "protocol='mixed'": dict(protocol="mixed", quorum_rule="n2",
                             mixed_shards=2),
    "fidelity='reference'": dict(fidelity="reference", quorum_rule="n2"),
    "pbft_window=8": dict(pbft_window=8),
}


@pytest.mark.parametrize("arm", list(REFUSED))
def test_an_arm_without_classes_refuses_them_by_name(arm):
    cfg = _cfg(FIELDS, **REFUSED[arm])
    with pytest.raises(NotImplementedError) as e:
        runner.make_sim_fn(cfg)
    assert "link classes" in str(e.value) and arm in str(e.value)
    assert "ops/linkclass.check_arms" in str(e.value)


def test_the_other_doors_refuse_them_too():
    stat = _cfg(FIELDS, delivery="stat", schedule="tick")
    for door in (lambda: runner.make_dyn_sim_fn(stat),
                 lambda: runner.make_segment_fn(stat, 10),
                 lambda: pbft.init(stat),
                 lambda: sweep.run_seed_sweep(stat, [1, 2])):
        with pytest.raises(NotImplementedError, match="delivery='stat'"):
            door()
    with pytest.raises(NotImplementedError, match=r"the C\+\+ engine"):
        engine.run_cpp(_cfg(FIELDS))
    if len(jax.devices()) >= 2:  # the node-sharded program (shard_map)
        from blockchain_simulator_tpu.parallel import shard
        from blockchain_simulator_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match="a mesh axis"):
            shard.make_sharded_sim_fn(_cfg(FIELDS), mesh)
    from blockchain_simulator_tpu.serve import schema

    with pytest.raises(schema.InvalidRequestError, match="delivery='stat'"):
        schema.parse_request(
            {**FIELDS, "link_classes": list(COUNTS), "delivery": "stat",
             "schedule": "tick",
             "link_class_delay_ms": [list(r) for r in SYM]}, "x")


def test_the_counters_say_what_a_classed_program_holds():
    cfg = _cfg(FIELDS, sim_ms=64)
    name = lambda k: f"linkclass.{k}"
    assert set(telemetry.LINKCLASS_COUNTERS) == {name(k) for k in lc.traced}
    before = dict(lc.traced)
    state_bufs = jax.eval_shape(lambda: pbft.init(cfg))
    added = {k: lc.traced[k] - before[k] for k in before}
    assert added == {
        "programs": 1, "classes": 3, "offsets": 6,
        "ring_depth": cfg.ring_depth,
        "lane_state_bytes": sweep._logical_bytes(state_bufs)}
    assert sweep._lane_state_bytes.__wrapped__(cfg) == added["lane_state_bytes"]
    # a trace that closes moves them to the registry (utils/aotcache.py)
    got0 = telemetry.metrics.snapshot()["counters"]
    jax.jit(lambda x: x + 1).lower(jnp.zeros(3))
    got = telemetry.metrics.snapshot()["counters"]
    assert got.get(name("programs"), 0) - got0.get(name("programs"), 0) >= 0
    aotcache.registry._builds._count_lane_pinned()
    got = telemetry.metrics.snapshot()["counters"]
    assert got[name("programs")] == lc.traced["programs"]
    assert got[name("offsets")] == lc.traced["offsets"]


def test_no_shape_of_the_classed_program_grows_with_the_delay_span():
    """The same classes 10x further apart: the delay lines deepen (their
    leading axis, and the ``sent`` bits), and NOTHING else in the program
    changes shape: the bucket axis stays the jitter's three values, the rings
    their depth, the reads one slice a distinct offset."""
    near = _cfg(FIELDS, sim_ms=64)
    far = _cfg(FIELDS, sim_ms=64, link_class_delay_ms=tuple(
        tuple(3 + 10 * (d - 3) for d in row) for row in SYM))
    assert lc.one_way_plan(far).depth == 10 * 127 + 1
    assert near.ring_depth == far.ring_depth

    def shapes(cfg):
        jaxpr = jax.make_jaxpr(runner.make_sim_fn.__wrapped__(cfg))(
            jax.random.key(0))
        return re.findall(r"\[[0-9,]*\]", str(jaxpr))

    a, b = shapes(near), shapes(far)
    assert len(a) == len(b)  # the same operations, one for one
    # the one-way and round-trip lines, and the ``sent`` mask rolled to the
    # tick's slot (a roll is a slice of the mask laid twice end to end)
    depths = {(128, 1271), (255, 2541), (256, 2542), (510, 5082)}
    differing = {(x, y) for x, y in zip(a, b) if x != y}
    assert differing
    for x, y in differing:
        dx, dy = (int(s[1:-1].split(",")[0]) for s in (x, y))
        assert (dx, dy) in depths, (x, y)
        assert x[1:-1].split(",")[1:] == y[1:-1].split(",")[1:]
