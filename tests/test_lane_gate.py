"""``models/base.gated`` and ``gated_push`` under a lane batch (``lane_vmap``):
the gate branches on "any lane active" and stays a branch, a ring is pushed
inside it and never selected, every lane's row is its solo run's, and no
program without the lane axis moves."""

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


def _plain_push_lone(monkeypatch):
    """``gated_push`` of a program that binds no batch axis: the helper
    itself with its eyes closed to every axis (the loop of at most one
    trip around fn and push, under the helper's scope)."""
    def push(*args, **kwargs):
        with monkeypatch.context() as m:
            m.setattr(base, "_under", lambda axis_name: False)
            return base.gated_push(*args, **kwargs)
    return push


def _plain_push_select(pred, fn, zeros, bufs, push, axis=None):
    """``gated_push`` under a batch that cannot branch, written out: the
    parent's form, a gated contribution and an unconditional push."""
    return push(bufs, _plain_gated(pred, fn, zeros, axis))


def _everywhere(monkeypatch, gate, push=None):
    """Every engine's ``gated`` (paxos has none left) and ``gated_push``."""
    for mod in (pbft, raft, paxos):
        if hasattr(mod, "gated"):
            monkeypatch.setattr(mod, "gated", gate)
        if push is not None:
            monkeypatch.setattr(mod, "gated_push", push)


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


@pytest.mark.parametrize("name", ["pbft-edge", "pbft-stat"])
def test_batched_pbft_program_keeps_a_conditional_per_gated_site(monkeypatch, name):
    """A ``gated`` site stays a conditional and a ``gated_push`` site a loop
    of at most one trip, in the lone program and under the lane batch."""
    gates, pushes = [], []

    def counting(pred, fn, zeros, axis=None):
        gates.append(1)
        return base.gated(pred, fn, zeros, axis)

    def counting_push(pred, fn, zeros, bufs, push, axis=None):
        pushes.append(1)
        return base.gated_push(pred, fn, zeros, bufs, push, axis)

    cfg = SWEPT[name].with_(sim_ms=350)  # traced nowhere else
    # the loops that are not gates (the scan, the samplers' own), counted in
    # the parent's form of the same program
    _everywhere(monkeypatch, base.gated, _plain_push_select)
    other_loops = runner.make_sim_fn.__wrapped__(cfg).lower(
        jax.random.key(0)).as_text().count("stablehlo.while")
    _everywhere(monkeypatch, counting, counting_push)
    # the lone program first, through the SAME cached solo factory: jit's
    # trace cache must not hand the lone jaxpr to the lane batch
    lone = runner.make_sim_fn(cfg).lower(jax.random.key(0)).as_text()
    n_gates, n_pushes = len(gates), len(pushes)
    lowered = sweep._batched_fn.__wrapped__(cfg, None).lower(_keys([1, 2]))
    text, scopes = lowered.as_text(), lowered.as_text(debug_info=True)
    assert (len(gates) - n_gates, len(pushes) - n_pushes) == (n_gates, n_pushes)
    # all four channels push through the helper (the stat arms' fused
    # chain-into-ring is the push itself, with no separate contribution)
    assert (n_gates, n_pushes) == (0, 4)
    for program in (text, lone):
        assert program.count("stablehlo.case") == n_gates
        assert program.count("stablehlo.while") == n_pushes + other_loops
    assert f"{base.GATE_SCOPE}/" in scopes
    assert f"{base.PUSH_SCOPE}/" in scopes
    dyn = sweep.dyn_batched_fn.__wrapped__(canonical_fault_cfg(cfg)).lower(
        _keys([1, 2]), jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32)).as_text()
    assert dyn.count("stablehlo.case") >= n_gates
    assert dyn.count("stablehlo.while") >= n_pushes + other_loops


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
        lowered = build()
        out[name] = (lowered.as_text(), len(calls) - before,
                     lowered.as_text(debug_info=True))
    return out


# which of the programs above batch under ``select_vmap`` (every cond a select)
SELECT_BATCHED = ("sweep-batched, mesh", "partition-dyn-sweep, nodes mesh")


def test_programs_without_a_lane_axis_are_the_plain_cond_programs(monkeypatch):
    """No lane axis, no lane rule: ``gated`` is the plain cond, and
    ``gated_push`` the plain loop of at most one trip around fn and push,
    or, under ``select_vmap``, the parent's gated contribution and
    unconditional push."""
    calls = []

    def counted(rule):
        def fn(*args, **kwargs):
            calls.append(1)
            return rule(*args, **kwargs)
        return fn

    _everywhere(monkeypatch, counted(base.gated), counted(base.gated_push))
    with_rule = _lone_and_mesh_texts(calls)
    _everywhere(monkeypatch, counted(_plain_gated),
                counted(_plain_push_lone(monkeypatch)))
    plain_lone = _lone_and_mesh_texts(calls)
    _everywhere(monkeypatch, counted(_plain_gated), counted(_plain_push_select))
    plain_select = _lone_and_mesh_texts(calls)
    for name, (text, n_gates, scopes) in with_rule.items():
        plain = plain_select if name in SELECT_BATCHED else plain_lone
        # both were traced anew (no trace cache answered for the other)
        assert n_gates == plain[name][1] >= 4, name
        assert text == plain[name][0], name
        assert base.GATE_SCOPE not in scopes
        assert (base.PUSH_SCOPE in scopes) == (name not in SELECT_BATCHED), name


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
    # later cells driven by ``sweep`` are appended to its list (PR 51:
    # ``pbftgeo209.mc``); nothing else of the entry moves
    assert len(entry) == 1 and entry[0].pop("workloads")[0] == "pbft1k.mc"
    assert entry == [{
        "name": "ops_gate_us.sweep", "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "ops", "moves": "points_per_s"}]
    mod = importlib.util.spec_from_file_location(
        "ops_gate_us_sweep",
        os.path.join(here, "layer_metrics", "ops_gate_us.sweep.py"))
    reader = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(reader)
    assert reader.read({"trace": None, "traffic": {"driver": "sweep"}}) is None
    assert base.GATE_SCOPE.startswith("ops.gate.")


# ------------------------------------------------- (f) the push inside the gate

RINGS = {"add": jnp.add, "max": jnp.maximum}


def _ring_case(op):
    """A toy channel: ``x`` [N] sends when it is positive; the contribution
    is two delay buckets; one ring, or a tuple of two, of depth 5.  Through
    the helper, written out, and as the call site whose push computes its
    own contribution."""
    combine = RINGS[op]

    def push_one(ring, t, contrib):
        for b in range(contrib.shape[0]):
            ring = ring.at[(t + b) % ring.shape[0]].set(
                combine(ring[(t + b) % ring.shape[0]], contrib[b]))
        return ring

    def contribution(x):
        return jnp.stack([x, 2 * x])

    def via_helper(x, rings, t, axis=None):
        return base.gated_push(
            (x > 0).any(), lambda: contribution(x), jnp.zeros((2,) + x.shape, x.dtype),
            rings, lambda rs, c: jax.tree.map(lambda r: push_one(r, t, c), rs),
            axis)

    def via_gated(x, rings, t, axis=None):
        c = base.gated((x > 0).any(), lambda: contribution(x),
                       jnp.zeros((2,) + x.shape, x.dtype), axis)
        return jax.tree.map(lambda r: push_one(r, t, c), rings)

    def via_fused(x, rings, t, axis=None):
        return base.gated_push(
            (x > 0).any(), tuple, (), rings,
            lambda rs, _: jax.tree.map(
                lambda r: push_one(r, t, contribution(x)), rs),
            axis)

    return via_helper, via_gated, via_fused


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(g, w)


X = jnp.asarray([[0, 0, 0], [1, 0, 2], [0, 0, 0], [0, 3, 0]], jnp.int32)


def _rings(tuple_of_rings, lanes=None):
    shape = (5, 3) if lanes is None else (lanes, 5, 3)
    ring = jnp.arange(np.prod(shape), dtype=jnp.int32).reshape(shape) % 7
    return (ring, ring + 1) if tuple_of_rings else ring


@pytest.mark.parametrize("op", list(RINGS))
@pytest.mark.parametrize("tuple_of_rings", [False, True])
@pytest.mark.parametrize("under", ["lone", "lanes", "select", "scan", "axis"])
def test_gated_push_equals_pushing_the_gated_contribution(op, tuple_of_rings, under):
    """Leaf for leaf, ``gated_push`` is ``push(bufs, gated(...))``: lone, under
    a lane batch in which one lane sends and the others do not, under a
    batch that cannot branch, inside a scan, and with a sharded axis."""
    via_helper, via_gated, _ = _ring_case(op)
    t = jnp.int32(4)
    if under == "lone":
        for x in X:
            rings = _rings(tuple_of_rings)
            _assert_trees_equal(jax.jit(via_helper)(x, rings, t),
                                via_gated(x, rings, t))
    elif under in ("lanes", "select"):
        vmap = base.lane_vmap if under == "lanes" else base.select_vmap
        rings = _rings(tuple_of_rings, lanes=4)
        got = jax.jit(vmap(lambda x, r: via_helper(x, r, t)))(X, rings)
        want = jax.vmap(lambda x, r: via_gated(x, r, t))(X, rings)
        _assert_trees_equal(got, want)
        # no lane sends: the rings come back as they went in
        quiet = jax.jit(vmap(lambda x, r: via_helper(x, r, t)))(0 * X, rings)
        _assert_trees_equal(quiet, rings)
    elif under == "scan":
        def run(step):
            def body(rings, xt):
                x, tt = xt
                return step(x, rings, tt), ()
            return jax.lax.scan(body, _rings(tuple_of_rings),
                                (X, jnp.arange(4, dtype=jnp.int32)))[0]
        _assert_trees_equal(jax.jit(lambda: run(via_helper))(), run(via_gated))
    else:
        x = jnp.asarray([0, 0, 5, 0], jnp.int32)  # only the second shard sends
        rings = _sharded_rings(tuple_of_rings)
        _assert_trees_equal(_sharded(via_helper, rings, t)(x, rings),
                            _sharded(via_gated, rings, t)(x, rings))
        # no shard sends: the rings come back as they went in
        _assert_trees_equal(_sharded(via_helper, rings, t)(0 * x, rings), rings)


def _sharded_rings(tuple_of_rings):
    ring = jnp.arange(20, dtype=jnp.int32).reshape(5, 4) % 7
    return (ring, ring + 1) if tuple_of_rings else ring


def _sharded(step, rings, t):
    """``step`` over two node shards of two rows each."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2,), ("nodes",))
    spec = jax.tree.map(lambda _: P(None, "nodes"), rings)
    # replication checking waived, as parallel/partition._shard_map does
    return jax.jit(jax.shard_map(
        lambda x, r: step(x, r, t, axis="nodes"), mesh=mesh,
        in_specs=(P("nodes"), spec), out_specs=spec, check_vma=False))


@pytest.mark.parametrize("op", list(RINGS))
@pytest.mark.parametrize("tuple_of_rings", [False, True])
def test_sharded_gated_push_loops_over_the_push_alone(op, tuple_of_rings):
    """Under a mesh axis a separate contribution comes out of ONE conditional
    and is pushed in ONE loop, on one reduction of the predicate; a fused
    push (``zeros == ()``) keeps the parent's form, the whole arm in the
    conditional with the ring as its ``zeros``, and gives the same rings."""
    via_helper, via_gated, via_fused = _ring_case(op)
    t = jnp.int32(4)
    rings = _sharded_rings(tuple_of_rings)
    x = jnp.asarray([0, 0, 5, 0], jnp.int32)
    for sends in (x, 0 * x):
        _assert_trees_equal(_sharded(via_fused, rings, t)(sends, rings),
                            _sharded(via_gated, rings, t)(sends, rings))
    split = _sharded(via_helper, rings, t).lower(x, rings).as_text()
    fused = _sharded(via_fused, rings, t).lower(x, rings).as_text()
    parent = _sharded(via_gated, rings, t).lower(x, rings).as_text()
    counts = {name: (text.count("stablehlo.while"), text.count("stablehlo.case"),
                     text.count("stablehlo.all_reduce"))
              for name, text in (("split", split), ("fused", fused),
                                 ("parent", parent))}
    assert counts == {"split": (1, 1, 1), "fused": (0, 1, 1),
                      "parent": (0, 1, 1)}
    for text in (split, fused):
        selects = [ln for ln in text.splitlines() if "stablehlo.select" in ln]
        assert not [ln for ln in selects if "tensor<5x2xi32>" in ln], selects


def test_lane_batch_under_a_mesh_axis_reduces_the_predicate_over_both():
    """No program in the repo binds a lane batch and a mesh axis at once;
    where one does, the loop runs when any lane of any shard sends, each
    lane keeps its own contribution, and a batch without a sender comes
    back as it went in."""
    from jax.sharding import PartitionSpec as P

    via_helper, via_gated, _ = _ring_case("add")
    t = jnp.int32(4)
    mesh = jax.make_mesh((2,), ("nodes",))
    xs = jnp.asarray([[0, 0, 0, 0], [0, 0, 5, 0], [0, 0, 0, 0]], jnp.int32)
    rings = jnp.arange(60, dtype=jnp.int32).reshape(3, 5, 4) % 7
    spec = P(None, None, "nodes")

    def sharded(step, vmap):
        return jax.jit(jax.shard_map(
            vmap(lambda x, r: step(x, r, t, axis="nodes")), mesh=mesh,
            in_specs=(P(None, "nodes"), spec), out_specs=spec, check_vma=False))

    lanes = sharded(via_helper, base.lane_vmap)
    _assert_trees_equal(lanes(xs, rings), sharded(via_gated, jax.vmap)(xs, rings))
    _assert_trees_equal(lanes(0 * xs, rings), rings)
    text = lanes.lower(xs, rings).as_text()
    assert text.count("stablehlo.while") == 1 and text.count("stablehlo.case") == 1


def test_gated_push_lowers_to_one_loop_and_never_selects_the_ring():
    """Lone and under the lane batch the helper is one ``while`` and no
    ``case``, and no select has a ring-shaped operand; under ``select_vmap``
    it is the parent's select on the contribution alone."""
    via_helper, _, _ = _ring_case("add")
    t = jnp.int32(1)
    rings = _rings(False, lanes=4)
    lone = jax.jit(via_helper).lower(X[0], rings[0], t).as_text()
    lanes = jax.jit(base.lane_vmap(lambda x, r: via_helper(x, r, t))).lower(
        X, rings).as_text()
    select = jax.jit(base.select_vmap(lambda x, r: via_helper(x, r, t))).lower(
        X, rings).as_text()
    for text in (lone, lanes):
        assert text.count("stablehlo.while") == 1
        assert "stablehlo.case" not in text
    assert "stablehlo.while" not in select and "stablehlo.case" not in select
    for text, ring_type in ((lone, "tensor<5x3xi32>"), (lanes, "tensor<4x5x3xi32>"),
                            (select, "tensor<4x5x3xi32>")):
        selects = [ln for ln in text.splitlines() if "stablehlo.select" in ln]
        assert not [ln for ln in selects if ring_type in ln], selects


# ------------------------------------- (g) the traced programs, structurally

STRUCTURAL = {
    "pbft-edge": SimConfig(protocol="pbft", n=8, sim_ms=100),
    "raft": SimConfig(protocol="raft", n=8, sim_ms=100),
    "paxos": SimConfig(protocol="paxos", n=8, sim_ms=100),
}


def _ring_leaves(cfg):
    """The ring leaves of a protocol's buffers (PBFT's bool ``due`` bits are
    bookkeeping about its rings, not one of them)."""
    proto = base.get_protocol(cfg.protocol)
    _, bufs = jax.eval_shape(lambda: proto.init(cfg, jax.random.key(0)))
    return [x for x in jax.tree.leaves(bufs) if x.dtype != jnp.bool_]


def _ring_shapes(cfg, n_loc=None):
    """The ring buffers' shapes (with ``n_loc`` rows where the node dim is
    sharded); a batched program holds them under one more leading dim."""
    shapes = {tuple(x.shape) for x in _ring_leaves(cfg)}
    if n_loc is not None:
        shapes = {(s[0], n_loc) + s[2:] for s in shapes}
    return shapes


def _walk(jaxpr, in_gate=False, in_scan=False):
    """(eqn, inside a gate's loop, inside the tick scan) for every equation,
    sub-jaxprs included.  The tick scan is the ``scan``; a ``while`` inside
    it is a gate (the engines have no other)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_gate, in_scan
        gate = in_gate or (in_scan and eqn.primitive.name == "while")
        scan = in_scan or eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, gate, scan)


def _ring_shaped(aval, rings):
    shape = tuple(getattr(aval, "shape", ()))
    return shape in rings or shape[1:] in rings


def _structure(closed, rings):
    selects, updates_outside, updates_inside = [], 0, 0
    for eqn, in_gate, in_scan in _walk(closed.jaxpr):
        if not in_scan:
            continue
        name = eqn.primitive.name
        if name == "select_n" and any(
                _ring_shaped(v.aval, rings) for v in eqn.invars):
            selects.append(str(eqn)[:200])
        if name in ("dynamic_update_slice", "scatter", "scatter-add") and any(
                _ring_shaped(v.aval, rings) for v in eqn.outvars):
            if in_gate:
                updates_inside += 1
            else:
                updates_outside += 1
    return selects, updates_outside, updates_inside


@pytest.mark.parametrize("name", list(STRUCTURAL))
def test_no_select_touches_a_ring_and_only_pops_update_outside_a_gate(name):
    cfg = STRUCTURAL[name]
    rings = _ring_shapes(cfg)
    n_rings = len(_ring_leaves(cfg))
    canon = canonical_fault_cfg(cfg)
    programs = {
        "make_sim_fn": jax.make_jaxpr(runner.make_sim_fn.__wrapped__(cfg))(
            jax.random.key(0)),
        "dyn_batched_fn": jax.make_jaxpr(sweep.dyn_batched_fn.__wrapped__(canon))(
            _keys([1, 2]), jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32)),
    }
    for tag, closed in programs.items():
        selects, outside, inside = _structure(closed, rings)
        assert selects == [], (tag, selects)
        # one pop a ring and no push; PBFT pops inside its tick gate
        # (models/pbft.step), so nothing of its rings is left outside
        assert outside == (0 if cfg.protocol == "pbft" else n_rings), (tag, outside)
        assert inside >= n_rings, (tag, inside)


def test_mesh_batched_program_never_selects_a_ring_either():
    """``_batched_fn(cfg, mesh)`` cannot branch (``select_vmap``): the
    contribution is selected as in the parent, the ring never."""
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=100)
    mesh = make_mesh(n_node_shards=2, n_sweep=2)
    shard.make_sharded_sim_fn.cache_clear()
    closed = jax.make_jaxpr(sweep._batched_fn.__wrapped__(cfg, mesh))(_keys([1, 2]))
    rings = _ring_shapes(cfg, n_loc=4) | _ring_shapes(cfg)
    selects, outside, inside = _structure(closed, rings)
    assert selects == []
    assert inside == 0 and outside > 4  # pops and unconditional pushes
    shard.make_sharded_sim_fn.cache_clear()


# --------------------------------- (h) the sharded programs, structurally

# a flood over a relay overlay (the benchmark's mesh cell in small), and the
# program that aborted on XLA:CPU with its arms inside a loop (KNOWN_ISSUES #0b')
SHARDED = {
    "paxos-gossip": SimConfig(protocol="paxos", n=16, sim_ms=100,
                              topology="gossip", degree=4, gossip_hops=4,
                              paxos_retry_timeout_ms=450),
    "raft-queued": SimConfig(protocol="raft", n=16, sim_ms=100,
                             queued_links=True),
}
COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
               "psum_scatter", "reduce_scatter", "pbroadcast")


@pytest.mark.parametrize("name", list(SHARDED))
def test_sharded_program_loops_over_pushes_and_keeps_collectives_outside(
        monkeypatch, name):
    """Sharded over a mesh axis, every ``gated_push`` site with a separate
    contribution is one ``while`` in the tick: the ring updates are inside
    it, no collective is (XLA:CPU would race it with the tick's own), the
    arm's collectives stay in a ``cond``, and no ring is selected or copied."""
    cfg = SHARDED[name]
    sites = []

    def counting_push(pred, fn, zeros, bufs, push, axis=None):
        sites.append((bool(jax.tree.leaves(zeros)), axis,
                      len(jax.tree.leaves(bufs))))
        return base.gated_push(pred, fn, zeros, bufs, push, axis)

    _everywhere(monkeypatch, base.gated, counting_push)
    mesh = make_mesh(n_node_shards=4)
    closed = jax.make_jaxpr(shard.make_sharded_sim_fn.__wrapped__(cfg, mesh))(
        jax.random.key(0))
    assert sites and all(separate and axis == "nodes"
                         for separate, axis, _ in sites), sites
    rings = _ring_shapes(cfg, n_loc=cfg.n // 4)
    inside, outside, ring_copies = [], [], []
    for eqn, in_gate, in_scan in _walk(closed.jaxpr):
        if not in_scan:
            continue
        prim = eqn.primitive.name
        (inside if in_gate else outside).append(prim)
        if prim.startswith("copy") and any(
                _ring_shaped(v.aval, rings) for v in eqn.outvars):
            ring_copies.append(str(eqn)[:200])
    assert outside.count("while") == len(sites)
    # each contribution out of its conditional, with the arm's collectives
    assert outside.count("cond") >= len(sites)
    assert [p for p in outside if p.startswith(COLLECTIVES)]
    assert not [p for p in inside if p.startswith(COLLECTIVES)]
    selects, updates_outside, updates_inside = _structure(closed, rings)
    assert selects == [] and ring_copies == []
    assert updates_inside >= sum(n for _, _, n in sites)
    if name == "paxos-gossip":  # one pop a ring, every push behind a gate
        assert updates_outside == sum(n for _, _, n in sites) == 6
