"""A batched dispatch reads its rows back in ONE transfer (ISSUE 29).

``parallel/sweep._readback`` fetches, once per dispatch, the leaves the
protocol's ``metrics`` reads (each module's ``METRIC_FIELDS``) and hands
``sim_metrics`` per-row HOST states whose other fields are None.  Pinned
here: the declarations cover what ``metrics`` reads; every sweep arm's rows
stay dict-equal to solo runs while exactly one ``jax.device_get`` happens,
inside the ``sweep.readback`` span, and no ``jax.Array`` reaches
``sim_metrics``; the span says what was fetched; a partial bucket compiles
nothing that a full one had not.
"""

import dataclasses

import jax
import numpy as np
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import base, mixed, paxos, pbft, raft
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils import telemetry
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

PBFT = SimConfig(protocol="pbft", n=8, sim_ms=300, stat_sampler="exact")
# one configuration per final-state type and metrics door: the three tick
# engines, the two blocked engines (their states carry fewer fields), and a
# committee stack, whose metrics slice per committee before the protocol's
SOLO_CFGS = {
    "pbft": PBFT,
    "raft": SimConfig(protocol="raft", n=8, sim_ms=1200,
                      stat_sampler="exact"),
    "paxos": SimConfig(protocol="paxos", n=8, sim_ms=1200,
                       stat_sampler="exact"),
    "pbft_round": PBFT.with_(delivery="stat", schedule="round",
                             model_serialization=False),
    "raft_hb": SimConfig(protocol="raft", n=8, sim_ms=1200, delivery="stat",
                         schedule="round", stat_sampler="exact"),
    "committee": PBFT.with_(n=16, topology="committee", committees=2,
                            fidelity="clean"),
}


def _fields(cfg):
    return base.get_protocol(cfg.protocol).METRIC_FIELDS


@pytest.mark.parametrize("name", sorted(SOLO_CFGS))
def test_metrics_reads_only_the_declared_fields(name):
    cfg = SOLO_CFGS[name]
    final = runner.final_state(cfg, seed=5)
    names = [f.name for f in dataclasses.fields(final)]
    assert set(_fields(cfg)) <= set(names)
    bare = type(final)(**{
        f: getattr(final, f) if f in _fields(cfg) else None for f in names
    })
    assert base.sim_metrics(cfg, bare) == base.sim_metrics(cfg, final)


def test_each_field_is_declared_once_and_mixed_declares_none():
    assert not hasattr(mixed, "METRIC_FIELDS")
    for mod in (pbft, raft, paxos):
        assert len(set(mod.METRIC_FIELDS)) == len(mod.METRIC_FIELDS)


def _faulty(f):
    return PBFT.with_(faults=FaultConfig(n_byzantine=f))


POINTS = [(_faulty(0), 3), (_faulty(1), 4), (_faulty(2), 5)]
CANON = canonical_fault_cfg(PBFT)

# each arm: (call, the (cfg, seed) of every row it must return, lanes)
ARMS = {
    "seed_sweep": (
        lambda: sweep.run_seed_sweep(PBFT, [3, 4, 5]),
        [(PBFT, 3), (PBFT, 4), (PBFT, 5)], 3),
    "dyn_points_vmapped": (
        lambda: sweep.run_dyn_points(CANON, POINTS, record=False),
        POINTS, 3),
    "dyn_points_multi_seed": (
        lambda: sweep.run_dyn_points(CANON, POINTS, record=False,
                                     multi_seed=True),
        POINTS, 3),
    # three points over a sweep axis of two: a fourth, padded lane rides
    # the dispatch and the fetch, unread
    "dyn_points_padded_mesh": (
        lambda: sweep.run_dyn_points(
            CANON, POINTS, record=False,
            mesh=make_mesh(n_node_shards=1, n_sweep=2)),
        POINTS, 4),
    # the server's bucket: duplicate lanes past n_out get no metrics
    "dyn_points_n_out": (
        lambda: sweep.run_dyn_points(CANON, POINTS + POINTS[-1:],
                                     record=False, n_out=3),
        POINTS, 4),
    "committee_seed_sweep": (
        lambda: sweep.run_seed_sweep(SOLO_CFGS["committee"], [3, 4]),
        [(SOLO_CFGS["committee"], 3), (SOLO_CFGS["committee"], 4)], 2),
}


@pytest.fixture(scope="module", params=sorted(ARMS))
def arm(request):
    """One batched call of an arm under watch: every ``jax.device_get``
    (what it was given, what it returned, the span it ran under), every
    state handed to ``sim_metrics``, every span.  The programs are warmed by
    a first, unwatched call."""
    call, points, lanes = ARMS[request.param]
    call()
    gets, states = [], []
    real_get, real_metrics = jax.device_get, sweep.sim_metrics

    def device_get(tree):
        host = real_get(tree)
        gets.append({"host": host, "under": telemetry.current()})
        return host

    def sim_metrics(cfg, state):
        states.append(state)
        return real_metrics(cfg, state)

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", device_get)
    mp.setattr(sweep, "sim_metrics", sim_metrics)
    try:
        with telemetry.capture() as spans:
            rows = call()
    finally:
        mp.undo()
    return {"rows": rows, "points": points, "lanes": lanes, "gets": gets,
            "states": states,
            "readback": [s for s in spans if s["name"] == "sweep.readback"]}


def test_rows_equal_solo_runs(arm):
    solo = [runner.run_simulation(cfg, seed=seed)
            for cfg, seed in arm["points"]]
    assert arm["rows"] == solo


def test_one_device_get_inside_the_readback_span(arm):
    assert len(arm["gets"]) == 1
    (span,) = arm["readback"]
    assert arm["gets"][0]["under"].span_id == span["id"]


def test_sim_metrics_sees_host_states_only(arm):
    cfg = arm["points"][0][0]
    assert len(arm["states"]) == len(arm["points"])
    for state in arm["states"]:
        got = {f.name for f in dataclasses.fields(state)
               if getattr(state, f.name) is not None}
        assert got == set(_fields(cfg))
        leaves = jax.tree.leaves(state)
        assert leaves and all(type(x) is np.ndarray for x in leaves)
        # rows are views of the one fetched batch, not copies
        assert all(x.base is not None for x in leaves)


def test_readback_span_says_what_was_fetched(arm):
    cfg = arm["points"][0][0]
    (span,) = arm["readback"]
    fetched = jax.tree.leaves(arm["gets"][0]["host"])
    assert span["attrs"] == {
        "rows": len(arm["points"]), "lanes": arm["lanes"],
        "leaves": len(_fields(cfg)),
        "bytes": sum(x.nbytes for x in fetched),
    }
    assert all(x.shape[0] == arm["lanes"] for x in fetched)


def test_a_module_without_the_declaration_fetches_every_leaf(monkeypatch):
    """``models/mixed`` declares no ``METRIC_FIELDS``: the same single fetch
    then carries the whole state.  Shown on pbft with its declaration
    hidden, so that the batched program is one the other cases compiled."""
    monkeypatch.delattr(pbft, "METRIC_FIELDS")
    with telemetry.capture() as spans:
        rows = sweep.run_seed_sweep(PBFT, [3, 4, 5])
    (span,) = [s for s in spans if s["name"] == "sweep.readback"]
    final = runner.final_state(PBFT, seed=3)
    assert span["attrs"]["leaves"] == len(jax.tree.leaves(final))
    assert span["attrs"]["bytes"] == 3 * sum(
        x.nbytes for x in jax.tree.leaves(final))
    assert rows == [runner.run_simulation(PBFT, seed=s) for s in (3, 4, 5)]


def test_a_partial_bucket_compiles_nothing_new():
    """The server warms each bucket full; the first bucket that comes
    partly filled (``n_out`` < lanes) must not compile inside a serving
    window.  On the chip a device-side ``[:rows]`` cost three compiles a
    bucket size and a 615 ms p90 (PERF.md section 6, PR 29)."""
    import jax.monitoring
    from jax._src import monitoring

    bucket = POINTS + POINTS[-1:]
    sweep.run_dyn_points(CANON, bucket, record=False)
    compiles = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        rows = sweep.run_dyn_points(CANON, bucket, record=False, n_out=3)
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert len(rows) == 3 and compiles == []
