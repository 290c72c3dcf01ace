"""What the cell ``paxos10k.mesh4`` (BASELINE config 3) rests on, on the CPU
at the configuration file's own rehearsal size (256 nodes, 8-out digraph) over
a 4-shard virtual mesh:

- the cell's rehearsal through the harness (``benchmark/run.drive``: set-up,
  window, the per-message reference after it) is ``correct``; each of the
  configuration's controls (the program with one guarantee broken) is not,
  by the check it names;
- the driver refuses, before it builds anything, a ``models.paxos`` that
  does not export the milestone tuple; a row that lacks a milestone is not
  ``correct``; a retry window shorter than the flood and reply horizon is
  refused by ``models/paxos.init`` itself, which is why no control runs it;
- the seam the driver calls: ``shard.readback`` fetches the leaves
  ``metrics`` reads and no other under the span ``shard.readback``,
  ``shard.run_sharded`` emits ``shard.execute`` then ``shard.readback`` with
  their attrs, and ``shard.collective_counts`` reads the compiled module's
  collectives (three flood all-reduces of the bytes the benchmark's count
  function gives, three packet all-gathers of the bytes the packets' shape
  gives);
- the sharded flood's exchange of senders (``ops/delivery._flood_exchange``)
  equals the dense scatter into the global row space entry for entry, at
  every size of its scatter and where it falls back on the dense arm.
"""

import dataclasses
import importlib
import json
import os
import random
import sys

import jax
import numpy as np
import pytest

from blockchain_simulator_tpu.models import paxos
from blockchain_simulator_tpu.models.base import sim_metrics
from blockchain_simulator_tpu.ops import delivery, topology
from blockchain_simulator_tpu.parallel import shard
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "paxos10k.mesh4"
SEED = 2_147_483_659  # one past 2**31, as the driver's are


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name) for name in (
            "run", "program", "checks", "paxos_checks", "mesh_trace")}
        spec = mods["run"].load_json(ROOT, "BENCHMARK.json")
        yield {**mods, "spec": spec, "counter": mods["run"].CompileCounter()}
    finally:
        sys.path.remove(BENCH)


def drive(bench, fields=None, seconds=2.0) -> dict:
    run = bench["run"]
    ctx = run.make_ctx(bench["spec"], CELL, SEED, False, on_chip=False,
                       program_fields=fields)
    record, comps = run.drive(ctx, seconds, bench["counter"])
    return {"run": record, "comps": {c["name"]: c for c in comps}}


@pytest.fixture(scope="module")
def sound(bench, shared):
    # one rehearsal a run of the suite (tests/conftest.py ``shared``)
    return shared("zzpaxos_cell.sound", lambda: drive(bench))


def test_rehearsal_is_correct(sound):
    comps = sound["comps"]
    assert all(c["ok"] for c in comps.values()), comps
    run = sound["run"]
    assert run["window"]["failed"] == 0 and run["window"]["attempted"] >= 1
    assert all(s["units"] == 1 for s in run["window"]["samples"])
    assert run["setup"]["shards"] == min(4, len(jax.devices()))
    assert run["setup"]["ticks"] == run["fields"]["sim_ms"]


def test_rehearsal_reads_the_programs_counter(bench, sound):
    counts = sound["run"]["setup"]["collectives"]
    assert counts["flood_allreduces"] == 3 and counts["collectives_per_tick"] >= 4
    assert counts["flood_allreduce_bytes"] == \
        bench["mesh_trace"].flood_allreduce_operand_bytes(sound["run"]["fields"])
    assert counts["flood_allgathers"] == 3
    assert counts["flood_allgather_bytes"] == packets_bytes(
        sound["run"]["fields"], sound["run"]["setup"]["shards"])
    reader = bench["run"].load_module("layer_metrics", "mesh_collectives_per_tick")
    assert reader.read(sound["run"]) == counts["collectives_per_tick"]


def packets_bytes(fields: dict, shards: int) -> int:
    """What one flood arm all-gathers: from every shard, the largest tier
    under its ``rows x proposers`` pairs and the row of its count, each row
    a code and a value per edge and the lane."""
    pairs = fields["n"] // shards * fields.get("paxos_n_proposers", 3)
    kmax = max(t for t in delivery.FLOOD_TIERS if t < pairs)
    return shards * (kmax + 1) * (2 * fields["degree"] + 1) * 4


def _controls():
    with open(os.path.join(BENCH, "configs", "paxos-gossip-10k.json")) as f:
        return [pytest.param(c, id=c["name"]) for c in json.load(f)["controls"]]


@pytest.mark.parametrize("control", _controls())
def test_control_is_not_correct(bench, control):
    got = drive(bench, control.get("rehearsal_fields", control["fields"]))
    assert not got["comps"][control["must_fail"]]["ok"], got["comps"]


@pytest.mark.parametrize("reader", (
    "paxos_tick_us.mesh", "paxos_flood_us.mesh", "ops_ring_us.mesh",
    "mesh_collective_us", "mesh_allreduce_ici_pct", "mesh_skew_pct",
    "shard_readback_ms", "device_idle_pct.mesh", "device_scoped_pct.mesh"))
def test_trace_readers_return_nothing_without_a_trace(bench, sound, reader):
    mod = bench["run"].load_module("layer_metrics", reader)
    assert mod.read(sound["run"]) is None
    other = {**sound["run"], "traffic": {"driver": "solo"},
             "trace": {"path": "/nonexistent", "window_s": 1.0, "busy_s": 0.5}}
    assert mod.read(other) is None


@pytest.mark.parametrize("export", (None, paxos.MILESTONES[:-1]),
                         ids=("no-tuple", "tuple-lacks-a-key"))
def test_driver_refuses_before_building(bench, monkeypatch, export):
    """A ``models.paxos`` that cannot say which milestones it reports (the
    parent of the PR that added the cell): ``setup()`` raises before the
    sharded program is ever asked for."""
    if export is None:
        monkeypatch.delattr(paxos, "MILESTONES")
    else:
        monkeypatch.setattr(paxos, "MILESTONES", export)
    built = []
    monkeypatch.setattr(shard, "make_sharded_sim_fn",
                        lambda *a: built.append(a) or (lambda key: None))
    ctx = bench["run"].make_ctx(bench["spec"], CELL, SEED, False, on_chip=False)
    ctx["rng"] = random.Random(1)
    driver = bench["run"].load_module("drivers", "mesh_solo").Driver(ctx)
    with pytest.raises(AttributeError, match="guarantee 'timing'.*lacks"):
        driver.setup()
    assert built == []


def test_a_row_without_a_milestone_is_not_correct(bench, sound):
    ctx = bench["run"].make_ctx(bench["spec"], CELL, SEED, False, on_chip=False)
    pc, fields = bench["paxos_checks"], ctx["reference_fields"]
    ref = pc.reference_milestones(ctx["config"], fields, SEED)
    rows = [dict(s["row"]) for s in sound["run"]["window"]["samples"]] * 2
    ok = pc.against_reference(rows, ref, ctx["config"], fields)
    assert all(c["ok"] for c in ok), ok
    del rows[-1][paxos.MILESTONES[0]]
    comps = {c["name"]: c for c in
             pc.against_reference(rows, ref, ctx["config"], fields)}
    assert [n for n, c in comps.items() if not c["ok"]] == ["rows_with_timing"]


def test_retry_window_under_the_horizon_is_refused_by_init(bench):
    """Why the configuration has no ``retry_early`` control: the program
    cannot be run with that guarantee broken."""
    ctx = bench["run"].make_ctx(bench["spec"], CELL, SEED, False, on_chip=False)
    cfg = bench["program"].sim_config(
        {**ctx["fields"], "paxos_retry_timeout_ms": 500})
    with pytest.raises(ValueError, match="max reply horizon"):
        paxos.init(cfg)


@pytest.fixture(scope="module")
def small():
    from blockchain_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig(protocol="paxos", n=64, sim_ms=400, topology="gossip",
                    degree=4, gossip_hops=6, delivery="stat",
                    model_serialization=False, paxos_retry_timeout_ms=450)
    shards = 2 if len(jax.devices()) >= 2 else 1
    return cfg, make_mesh(n_node_shards=shards, devices=jax.devices()[:shards])


def test_readback_fetches_the_metric_fields_and_no_other(small):
    cfg, mesh = small
    final = shard.make_sharded_sim_fn(cfg, mesh)(jax.random.key(3))
    with telemetry.capture() as spans:
        host = shard.readback(cfg, mesh, final)
    for f in dataclasses.fields(host):
        got = getattr(host, f.name)
        if f.name in paxos.METRIC_FIELDS:
            assert isinstance(got, np.ndarray), f.name
        else:
            assert got is None, f.name
    assert sim_metrics(cfg, host) == sim_metrics(cfg, final)
    [rec] = [s for s in spans if s["name"] == "shard.readback"]
    want = [getattr(final, f) for f in paxos.METRIC_FIELDS]
    assert rec["attrs"] == {
        "shards": mesh.shape["nodes"],
        "rows_per_shard": cfg.n // mesh.shape["nodes"],
        "leaves": len(want), "bytes": sum(x.nbytes for x in want)}


def test_run_sharded_emits_execute_then_readback(small):
    cfg, mesh = small
    with telemetry.capture() as spans:
        m = shard.run_sharded(cfg, mesh, seed=3)
    # the worker's first run of this program also writes its build.* records
    mine = [s for s in spans if s["name"].startswith("shard.")]
    assert [s["name"] for s in mine] == ["shard.execute", "shard.readback"]
    assert mine[0]["attrs"] == {"shards": mesh.shape["nodes"],
                                "rows_per_shard": cfg.n // mesh.shape["nodes"]}
    assert set(paxos.MILESTONES) <= set(m) and m["protocol"] == "paxos"


def test_collective_counts_of_the_compiled_module(bench, small):
    cfg, mesh = small
    if mesh.shape["nodes"] < 2:
        pytest.skip("a one-device mesh compiles its collectives away")
    counts = shard.collective_counts(cfg, mesh)
    assert counts["flood_allreduces"] == 3
    assert counts["flood_allreduce_bytes"] == \
        bench["mesh_trace"].flood_allreduce_operand_bytes(
            {"n": cfg.n, "paxos_delay_hi": 50, "paxos_delay_lo": 0})
    assert counts["flood_allgathers"] == 3
    assert counts["flood_allgather_bytes"] == packets_bytes(
        {"n": cfg.n, "degree": cfg.degree}, mesh.shape["nodes"])
    assert counts["collectives_per_tick"] == sum(
        v["count"] for v in counts["by_scope"].values())
    assert set(counts["by_scope"]) <= {"ops.mesh.pmax", "ops.mesh.psum",
                                       "ops.mesh.gather"}


@pytest.mark.parametrize("shards,share", ((1, 0.0), (2, 1.0), (4, 1.5), (8, 1.75)))
def test_ring_allreduce_bytes_per_chip(bench, shards, share):
    assert bench["mesh_trace"].ring_allreduce_bytes_per_chip(1000, shards) \
        == share * 1000


FLOOD_N, FLOOD_DEG, FLOOD_P = 256, 8, 3


@pytest.fixture(scope="module")
def flood():
    """``gossip_fwd`` under a 4-shard mesh with a given tuple of tiers."""
    from jax.sharding import PartitionSpec as P

    shards = 4 if len(jax.devices()) >= 4 else len(jax.devices())
    if shards < 2:
        pytest.skip("the exchange exists only across shards")
    mesh = make_mesh(n_node_shards=shards, devices=jax.devices()[:shards])
    nbrs = jax.numpy.asarray(
        topology.kregular_out_neighbors(FLOOD_N, FLOOD_DEG, 0))

    def run(tiers, fwd, drop):
        def fwd_fn(fwd, nbrs):
            return delivery.gossip_fwd(
                jax.random.key(3), fwd, nbrs, FLOOD_N, 3, 53, drop, "nodes",
                impl="rbg", tiers=tiers)

        return np.asarray(jax.jit(shard._partitioned(
            fwd_fn, mesh, in_specs=(P("nodes"), P("nodes")),
            out_specs=P(None, "nodes")))(fwd, nbrs))

    return run


@pytest.mark.parametrize("drop", (0.0, 0.3))
@pytest.mark.parametrize("live", (0, 1, 5, 30, 100, 400, 768))
def test_flood_exchange_equals_the_dense_scatter(flood, live, drop):
    rng = np.random.default_rng(live)
    fwd = np.zeros(FLOOD_N * FLOOD_P, np.int32)
    fwd[rng.choice(fwd.size, live, replace=False)] = rng.integers(1, 1000, live)
    fwd = jax.numpy.asarray(fwd.reshape(FLOOD_N, FLOOD_P))
    dense = flood((), fwd, drop)
    assert (dense > 0).sum() > 0 or live == 0
    # one tier every shard outgrows (the dense arm), two tiers, the
    # program's own, one tier just under a shard's pairs
    for tiers in ((4,), (8, 32), delivery.FLOOD_TIERS, (191,)):
        assert (flood(tiers, fwd, drop) == dense).all(), tiers
