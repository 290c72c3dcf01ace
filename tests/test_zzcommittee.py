"""The committee tier as tiles of lanes (``topo/committee.py``), held from
four sides.

(a) **Against the plain reference** (``benchmark/reference/
    committee_engine.py``: the per-message engine per committee and the
    combining rule in plain Python), at 8 x 64 and 4 x 128: every calm
    committee's counts equal the reference's, its times lie within the
    limits the deployment's configuration file writes, and
    ``outer_commit_ms`` / ``committees_decided`` / ``outer_quorum`` equal
    the reference's rule applied to the program's own milestones, through
    the very comparisons the benchmark cell runs
    (``benchmark/committee_checks.py``).
(b) **Against the flat program**: the tiled stack is bit-equal, leaf for
    leaf, to the flat dyn program run per committee with that committee's
    key and masks, at a C that T does not divide, with and without crashed
    nodes in the tail committees, for every tile width from 1 (the form
    where nothing can branch) to C, and under a lane batch around it (a
    sweep's), where the gates reduce over both axes and stay branches.
(c) **Metrics from one readback**: one ``jax.device_get`` a stack, the
    spans and counters by their names, the per-committee lists equal to the
    flat runs' own metrics, the ``MILESTONES`` tuple.
(d) **Names in a trace**: ``SCOPES`` on the lowered program's op_name
    paths, the engine's phases nested inside unrenamed.

C = 1 (the flat contract) and the sharded stack are pinned where they were:
``tests/test_zztopo.py``, ``tests/test_zzshardtopo.py``.
"""

import contextlib
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import base, pbft
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.topo import committee
from blockchain_simulator_tpu.utils import telemetry
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEEDS = (2_147_483_659, 7)  # one past 2**31, as the driver's are
CELL = "pbftcomm100k.solo"
SHAPES = {"8x64": (8, 64), "4x128": (4, 128)}


# ------------------------------------------- (a) against the plain reference


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (they import each other by bare name) and the
    cell's configuration."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("run", "program", "checks", "committee_checks")}
        spec = mods["run"].load_json(ROOT, "BENCHMARK.json")
        ctx = mods["run"].make_ctx(spec, CELL, SEEDS[0], False, on_chip=False)
        yield {**mods, "ctx": ctx}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module", params=sorted(SHAPES))
def held(request, bench, shared):
    """One shape's comparisons, by name: the program's runs over ``SEEDS``
    at upstream's defaults against the reference's undisturbed run; made
    once a run of the suite (tests/conftest.py ``shared``)."""
    def build():
        c, m = SHAPES[request.param]
        fields = {**bench["ctx"]["config"]["fields"], "n": c * m,
                  "committees": c, "sim_ms": 600}
        cfg = bench["program"].sim_config(fields)
        rows = [runner.run_simulation(cfg, seed=s) for s in SEEDS]
        cc, config = bench["committee_checks"], bench["ctx"]["config"]
        ref = cc.reference_milestones(config, fields, SEEDS[0])
        out = cc.guarantees(rows, fields) + cc.against_reference(
            rows, ref, config)
        return {"rows": rows, "ref": ref,
                "checks": {r["name"]: r for r in out}}

    return shared(f"zzcommittee.held.{request.param}", build)


@pytest.mark.parametrize("name", (
    "agreement_violations", "committees_undecided_max", "blocks_final_min",
    "reference_counts_agree", "hierarchy_gap_max", "outer_rule_violations",
    "rounds_sent_vs_reference_max", "blocks_final_all_nodes_vs_reference_max",
    "blocks_final_over_reference_max", "ttf_gap_ms_max",
    "commit_tail_gap_ms_max"))
def test_stack_holds_against_the_reference(name, held):
    assert held["checks"][name]["ok"], held["checks"][name]


def test_reference_finalizes_what_the_deployment_states(held):
    """11 rounds sent and 8 final at 600 ms in an undisturbed committee,
    whatever its size; every sampled committee agrees."""
    assert held["ref"]["counts"] == {"rounds_sent": 11,
                                     "blocks_final_all_nodes": 8}
    assert held["ref"]["counts_agree"] and held["ref"]["agreement_ok"]


def test_outer_rule_by_hand(held, bench):
    """The rule restated once more, here: the quorum-th smallest milestone
    plus 2 * (8 ms): upstream's U{3,4,5} over 3 ms links."""
    for m in held["rows"]:
        decided = sorted(t for t in m["inner_milestones_ms"] if t >= 0)
        q = m["committees"] // 2 + 1
        assert m["outer_quorum"] == q and m["outer_round_ms"] == 16.0
        assert m["committees_decided"] == len(decided) == m["committees"]
        assert m["outer_commit_ms"] == decided[q - 1] + 16.0
    # a run's unit of work: the mean over its committees of what each
    # finalized (these rows' committees all finalize 8)
    for m in held["rows"]:
        final = m["per_committee"]["blocks_final_all_nodes"]
        assert bench["committee_checks"].rounds(m) == sum(final) / len(final)
        assert min(final) >= 1
        assert max(final) == 8


def test_unit_of_work_counts_a_view_change_committee_by_its_share(bench):
    """A row in which one committee of the eight changes view and finalizes
    6: the run's unit is the mean, strictly between that minimum and the calm
    committees' 8, not the minimum."""
    fields = {**bench["ctx"]["config"]["fields"], "n": 8 * 64, "committees": 8,
              "sim_ms": 600}
    m = runner.run_simulation(bench["program"].sim_config(fields), seed=11)
    final = m["per_committee"]["blocks_final_all_nodes"]
    assert sum(m["per_committee"]["view_changes"]) >= 1
    assert min(final) >= 1 and max(final) == 8 and min(final) < 8
    unit = bench["committee_checks"].rounds(m)
    assert unit == sum(final) / len(final)
    assert min(final) < unit < 8


@pytest.mark.parametrize("control,fails", (
    ({"pbft_delay_hi": 5}, "ttf_gap_ms_max"),
    ({"committees": 4}, "hierarchy_gap_max")))
def test_a_broken_deployment_is_not_held(control, fails, bench):
    """The configuration's two controls at 8 x 64: the program with a delay
    bucket dropped, or with half as many committees of twice the size,
    against the reference of the deployment as stated."""
    fields = {**bench["ctx"]["config"]["fields"], "n": 512, "committees": 8}
    cc, config = bench["committee_checks"], bench["ctx"]["config"]
    cfg = bench["program"].sim_config({**fields, **control})
    rows = [runner.run_simulation(cfg, seed=SEEDS[0])]
    ref = cc.reference_milestones(config, fields, SEEDS[0])
    got = {r["name"]: r for r in cc.against_reference(rows, ref, config)}
    assert not got[fails]["ok"], got[fails]


# --------------------------------------------- (b) against the flat program

C, M = 5, 8
TILED = SimConfig(protocol="pbft", n=C * M, topology="committee", committees=C,
                  sim_ms=300)


def _stack_fn(cfg, monkeypatch, most):
    """A fresh jit of the dyn stack whose device holds ``most`` committees
    at once (None: it reports no memory, one committee after another)."""
    canon = canonical_fault_cfg(cfg)
    state = sweep._lane_state_bytes(committee.inner_cfg(canon))
    monkeypatch.setattr(sweep, "_device_bytes", lambda: None if most is None
                        else int(most * sweep._TEMP_FACTOR * state) + 1)
    return jax.jit(functools.partial(committee.run_stacked, canon))


def _flat_finals(cfg, key, nc, nb):
    """Every committee as the flat dyn program of its own key and counts."""
    icfg = committee.inner_cfg(canonical_fault_cfg(cfg))
    flat = jax.jit(runner.make_dyn_sim_fn(icfg))
    alive, honest = base.dyn_fault_masks(cfg.n, nc, nb)
    alive = np.asarray(alive).reshape(C, M)
    honest = np.asarray(honest).reshape(C, M)
    keys = committee._committee_keys(key, C)
    return [flat(keys[i], jnp.int32(M - alive[i].sum()),
                 jnp.int32(alive[i].sum() - honest[i].sum()))
            for i in range(C)]


def _assert_stack_equals(stacked, flats):
    for i, flat in enumerate(flats):
        got = jax.tree.map(lambda x: x[i], stacked)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(flat),
                        strict=True):
            assert a.dtype == b.dtype and np.array_equal(a, b), i


@pytest.mark.parametrize("most,lanes,tiles", (
    (None, 1, 5), (5, 5, 1), (3, 3, 2), (2, 2, 3), (1, 1, 5)))
@pytest.mark.parametrize("nc,nb", ((0, 0), (11, 2)),
                         ids=("no-faults", "tail-crashed"))
def test_tiled_stack_equals_flat_program_per_committee(
        most, lanes, tiles, nc, nb, monkeypatch):
    """Leaf for leaf, at C = 5 for every tile width: 3 and 2 do not divide
    it (the tail tile is padded and its padding cut off).  ``tail-crashed``:
    the last committee wholly and three of the one before crashed, two
    Byzantine before them.  What the stack ran as is written down where it
    is traced."""
    sim = _stack_fn(TILED, monkeypatch, most)
    icfg = committee.inner_cfg(canonical_fault_cfg(TILED))
    assert committee.tile_plan(icfg, C) == {"lanes": lanes, "tiles": tiles}
    key = jax.random.key(SEEDS[0])
    stacked = sim(key, jnp.int32(nc), jnp.int32(nb))
    assert committee.ran_as(TILED) == {"lanes": lanes, "tiles": tiles}
    _assert_stack_equals(stacked, _flat_finals(TILED, key, nc, nb))


def test_tile_rule_is_the_sweeps_rule(monkeypatch):
    icfg = committee.inner_cfg(canonical_fault_cfg(TILED))
    state = sweep._lane_state_bytes(icfg)
    # a committee stack counts as all its committees at once in a sweep
    assert sweep._lane_state_bytes(canonical_fault_cfg(TILED)) == C * state
    monkeypatch.setattr(sweep, "_device_bytes", lambda: int(8.5 * state))
    assert committee.tile_plan(icfg, 4) == {"lanes": 4, "tiles": 1}
    assert committee.tile_plan(icfg, 9) == {"lanes": 3, "tiles": 3}
    # lanes of a batch around the stack each hold a tile of their own
    assert committee.tile_plan(icfg, 9, outer=2) == {"lanes": 2, "tiles": 5}
    assert committee.tile_plan(icfg, 1, outer=64) == {"lanes": 1, "tiles": 1}
    # where nothing can branch the tile is the lone engine
    monkeypatch.setattr(base, "can_branch", lambda axis=None: False)
    assert committee.tile_plan(icfg, 9) == {"lanes": 1, "tiles": 9}
    # and where the device reports no memory to cut by (XLA:CPU): "no
    # memory reported" is not "the stack fits"
    monkeypatch.undo()
    monkeypatch.setattr(sweep, "_device_bytes", lambda: None)
    assert sweep._device_tile(icfg, 9) is None
    assert committee.tile_plan(icfg, 9) == {"lanes": 1, "tiles": 9}


@pytest.mark.parametrize("vmap,most", (
    (base.lane_vmap, 2), (base.lane_vmap, 10), (base.lane_vmap, None),
    (base.select_vmap, 2)),
    ids=("lanes-tiled", "lanes-one-tile", "lanes-no-memory", "select"))
def test_a_lane_batch_around_the_stack_equals_its_solo_runs(
        vmap, most, monkeypatch):
    """A sweep or a served bucket over a committee configuration: a lane
    batch around a body that is itself one.  Every lane's stack is bit-equal
    to the lone stack of its seed and fault level, tiles or not; under
    ``select_vmap``, and on a device that reports no memory, the stack keeps
    the lone engine a committee."""
    canon = canonical_fault_cfg(TILED)
    lone = _stack_fn(TILED, monkeypatch, None)
    keys = jax.vmap(jax.random.key)(jnp.asarray(SEEDS, jnp.uint32))
    ncs, nbs = jnp.asarray([0, 9], jnp.int32), jnp.asarray([0, 1], jnp.int32)
    want = [lone(keys[i], ncs[i], nbs[i]) for i in range(2)]
    _stack_fn(TILED, monkeypatch, most)  # the device the batch is traced on
    got = jax.jit(vmap(functools.partial(committee.run_stacked, canon)))(
        keys, ncs, nbs)
    _assert_stack_equals(got, want)
    # the plan of each form, written down where it was traced: two lanes
    # around the stack halve what a tile holds
    branch = vmap is base.lane_vmap
    lanes = {2: 1, 10: 5, None: 1}[most] if branch else 1
    assert committee._traced[canon, C, 2 if branch else 1, branch] == {
        "lanes": lanes, "tiles": -(-C // lanes)}


def _lowered(vmap, monkeypatch, most):
    canon = canonical_fault_cfg(TILED)
    _stack_fn(TILED, monkeypatch, most)
    keys = jax.vmap(jax.random.key)(jnp.asarray(SEEDS, jnp.uint32))
    zero = jnp.zeros(2, jnp.int32)
    fn = functools.partial(committee.run_stacked, canon)
    return jax.jit(vmap(fn)).lower(keys, zero, zero).as_text()


def test_gates_stay_branches_under_both_lane_axes(monkeypatch):
    """``gated``'s any-lane reduction sees both axes: the batched-and-tiled
    program holds as many ``while``s (the tick gate and the push gates: the
    edge engine's every gate is one) and as many ``select``s as the lone
    tiled one (no gate turned into a select of its carry per lane), and the
    program under ``select_vmap`` holds fewer loops: no tick gate at all."""
    lone = jax.jit(functools.partial(
        committee.run_stacked, canonical_fault_cfg(TILED)))
    _stack_fn(TILED, monkeypatch, 2)
    text = lone.lower(jax.random.key(0), jnp.int32(0), jnp.int32(0)).as_text()
    both = _lowered(base.lane_vmap, monkeypatch, 2)
    for op in ("stablehlo.while", "stablehlo.select"):
        assert text.count(op) > 0 and both.count(op) == text.count(op), op
    select = _lowered(base.select_vmap, monkeypatch, 2)
    assert select.count("stablehlo.while") < text.count("stablehlo.while")
    # no select has a ring among its operands, whatever the batch
    ring = "x".join(map(str, jax.eval_shape(lambda: pbft.init(
        committee.inner_cfg(TILED), jax.random.key(0)))[1].commit.shape))
    for t in (text, both):
        assert not [ln for ln in t.splitlines()
                    if "stablehlo.select" in ln and ring + "xi32" in ln]


# ------------------------------------------ (c) metrics from one readback


@contextlib.contextmanager
def _device_gets():
    """What ``jax.device_get`` is handed inside the block, call by call."""
    gets, real = [], jax.device_get
    jax.device_get = lambda x: gets.append(x) or real(x)
    try:
        yield gets
    finally:
        jax.device_get = real


@pytest.fixture(scope="module")
def one_stack():
    """A stack traced on a device that holds two of its four committees; its
    metrics are read after the stub is gone, when the rule asked again
    would answer otherwise (XLA:CPU reports no memory: T = 1)."""
    cfg = SimConfig(protocol="pbft", n=32, topology="committee", committees=4,
                    sim_ms=400, faults=FaultConfig(n_crashed=3))
    state = sweep._lane_state_bytes(
        committee.inner_cfg(canonical_fault_cfg(cfg)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sweep, "_device_bytes",
                   lambda: int(2 * sweep._TEMP_FACTOR * state) + 1)
        final = runner.final_state(cfg, seed=SEEDS[1])
    before = telemetry.metrics.snapshot()["counters"]
    with _device_gets() as gets, telemetry.capture() as spans:
        m = committee.metrics(cfg, final)
    after = telemetry.metrics.snapshot()["counters"]
    return {"cfg": cfg, "final": final, "metrics": m, "gets": gets,
            "spans": spans, "counters": {
                k: after.get(k, 0) - before.get(k, 0)
                for k in committee.COUNTERS}}


def test_one_device_get_a_stack(one_stack):
    assert len(one_stack["gets"]) == 1
    assert sorted(one_stack["gets"][0]) == sorted(pbft.METRIC_FIELDS)
    names = [s["name"] for s in one_stack["spans"]]
    assert names == list(committee.SPANS)
    rb = one_stack["spans"][0]["attrs"]
    assert rb["committees"] == 4 and rb["leaves"] == len(pbft.METRIC_FIELDS)
    # what the executable ran, not the rule asked again at the readback
    icfg = committee.inner_cfg(canonical_fault_cfg(one_stack["cfg"]))
    assert committee.tile_plan(icfg, 4) == {"lanes": 1, "tiles": 4}
    assert committee.ran_as(one_stack["cfg"]) == {"lanes": 2, "tiles": 2}
    assert (rb["tiles"], rb["tile_lanes"]) == (2, 2)
    assert one_stack["counters"] == {"committee.tiles": 2,
                                     "committee.tile_lanes": 4}


def test_host_rows_read_nothing_again(one_stack):
    """A sweep's row arrives as host arrays: sliced, not fetched, and not
    counted as a stack that ran here."""
    cfg = one_stack["cfg"]
    with sweep._readback(cfg, jax.tree.map(lambda x: x[None],
                                           one_stack["final"]), 1) as rows:
        with _device_gets() as gets, telemetry.capture() as spans:
            m = committee.metrics(cfg, rows[0])
    assert not gets and [s["name"] for s in spans] == ["topo.committee.outer"]
    assert m == one_stack["metrics"]


def test_per_committee_lists_are_the_flat_metrics(one_stack):
    cfg, m = one_stack["cfg"], one_stack["metrics"]
    assert set(committee.MILESTONES) <= set(m)
    icfg = committee.inner_cfg(cfg)
    for i in range(cfg.committees):
        flat = pbft.metrics(icfg, jax.tree.map(lambda x: x[i],
                                               one_stack["final"]))
        for k, v in m["per_committee"].items():
            assert v[i] == flat[k], (i, k)
        assert m["inner_milestones_ms"][i] == committee.milestone_ms(
            "pbft", flat)
    for k in ("blocks_final_all_nodes", "rounds_sent", "view_changes",
              "last_commit_ms", "mean_time_to_finality_ms", "agreement_ok"):
        assert len(m["per_committee"][k]) == cfg.committees
    assert "n" not in m["per_committee"]


# ------------------------------------------------- (d) names in a trace


@pytest.fixture(scope="module")
def op_names(shared):
    """The ``op_name`` paths of the compiled stack (what a profiler trace's
    event metadata carries), on a device that holds its three committees as
    one tile; compiled once a run of the suite (tests/conftest.py
    ``shared``)."""
    import re

    def build():
        cfg = SimConfig(protocol="pbft", n=24, topology="committee",
                        committees=3, sim_ms=100)
        state = sweep._lane_state_bytes(committee.inner_cfg(cfg))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(sweep, "_device_bytes",
                       lambda: int(3 * sweep._TEMP_FACTOR * state) + 1)
            text = jax.jit(runner.make_sim_fn(cfg)).lower(
                jax.random.key(0)).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    return shared("zzcommittee.op_names", build)


@pytest.mark.parametrize("scope", committee.SCOPES)
def test_compiled_stack_carries_the_scope(scope, op_names):
    assert scope in ("topo.committee.tile", "topo.committee.stack")
    assert any(f"/{scope}/" in n for n in op_names)


@pytest.mark.parametrize("inner", (
    "pbft.tick.pop", "pbft.tick.timers", "ops.ring.ring_pop",
    "ops.delay.sample_edge_delays", "ops.gate.any_lane",
    "gate.pbft.tick_taken"))
def test_engine_scopes_nest_inside_the_tile_unrenamed(inner, op_names):
    nested = "/topo.committee.stack/while/body/closed_call/topo.committee.tile/"
    assert any(nested in n and f"/{inner}/" in n for n in op_names), inner
