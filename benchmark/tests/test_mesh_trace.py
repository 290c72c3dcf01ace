"""``mesh_trace.py`` on a small recorded four-plane trace (four TPU v5e chips,
the ``mesh_solo`` driver far below rehearsal size; ``record_mesh_fixture.py``
made it), and the mesh cell's readers on it, on a trace of a one-chip program
without its scopes (``solo_small``), and on no trace at all."""

import os

import pytest

import mesh_trace
import run as bench

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
MESH = os.path.join(FIXTURES, "mesh_small.xplane.pb.gz")
SOLO = os.path.join(FIXTURES, "solo_small.xplane.pb.gz")
# record_mesh_fixture.py's FIELDS over the rehearsal's: what a reader needs
FIELDS = {"n": 64, "paxos_delay_lo": 0, "paxos_delay_hi": 4,
          "paxos_n_proposers": 3}
TRACE_READERS = ("paxos_tick_us.mesh", "paxos_flood_us.mesh",
                 "ops_ring_us.mesh", "mesh_collective_us", "mesh_skew_pct",
                 "shard_readback_ms", "device_scoped_pct.mesh")


@pytest.fixture(scope="module")
def mesh():
    return mesh_trace.summarize(MESH, 4)


def fake_run(path: str, driver: str = "mesh_solo", planes: int = 4) -> dict:
    return {"traffic": {"driver": driver}, "fields": dict(FIELDS),
            "trace": {"path": path, "devices": list(range(planes)),
                      "window_s": 1.0, "busy_s": 0.9},
            "setup": {"shards": planes, "collectives": {
                "collectives_per_tick": 6}}, "window": {}}


def test_four_planes_are_read_and_agree_on_the_ticks(mesh):
    assert len(mesh["devices"]) == 4
    assert len(set(mesh["devices"])) == 4
    ticks = mesh["ticks_by_plane"]
    # one SPMD program: every plane steps the same scan; at this size (16
    # rows a shard, a 30 us tick) the planes drift by some ticks between
    # the flood's all-reduces, at the cell's they read 627 627 627 627
    assert min(ticks) > 100 and max(ticks) - min(ticks) <= 0.1 * max(ticks)
    assert mesh["ticks"] == pytest.approx(sum(ticks) / 4)


def test_classes_partition_the_busy_time(mesh):
    assert 0 < mesh["busy_s"] <= mesh["window_s"]
    assert sum(mesh["by_class_s"].values()) == pytest.approx(
        mesh["busy_s"], rel=1e-6)
    assert sum(mesh["by_inner_s"].values()) == pytest.approx(
        mesh["busy_s"], rel=1e-6)
    assert mesh["busy_s"] == pytest.approx(
        sum(mesh["busy_by_plane_s"]) / 4)
    assert mesh["skew_s"] == pytest.approx(
        max(mesh["busy_by_plane_s"]) - min(mesh["busy_by_plane_s"]))
    assert 0 < mesh["scoped_s"] <= mesh["busy_s"] * (1 + 1e-6)


def test_the_programs_scopes_and_collectives_are_in_the_trace(mesh):
    cls, inner = mesh["by_class_s"], mesh["by_inner_s"]
    # (``paxos.tick.pop`` is ring pops alone: class ``ring``)
    for phase in ("paxos.tick.acceptor", "paxos.tick.reply",
                  "paxos.tick.proposer", "paxos.tick.timers"):
        assert cls.get(phase, 0) > 0, phase
    assert cls["ring"] > 0 and cls["collective"] > 0
    assert any(k.startswith("ops.mesh.") for k in inner)
    assert any(k.startswith("ops.ring.") for k in inner)
    # the flood ran in the traced stretch: its scatter and its all-reduce
    assert cls["flood"] > 0 and mesh["flood_allreduces"] > 0
    assert 0 < mesh["flood_allreduce_s"] <= cls["collective"] * (1 + 1e-6)


def test_the_shard_readback_span_is_read_with_its_attrs(mesh):
    spans = mesh["spans"].get("shard.readback")
    assert spans
    stats = spans[0]["stats"]
    assert stats["shards"] == 4 and stats["rows_per_shard"] == 16
    assert stats["leaves"] == 10 and stats["bytes"] > 0


def test_classify():
    c = mesh_trace.classify
    assert c((), False) == mesh_trace.UNSCOPED
    assert c((), True) == "collective"  # no op_name: the HLO category says
    assert c(("paxos.tick.reply", "ops.mesh.psum"), False) == "collective"
    assert c(("paxos.tick.flood_fwd", "ops.delivery.gossip_fwd",
              "ops.mesh.pmax"), False) == "collective"
    assert c(("paxos.tick.flood_fwd", "ops.delivery.gossip_fwd"),
             False) == "flood"
    assert c(("paxos.tick.flood_fwd", "ops.delivery.gossip_fwd",
              "ops.delay.sample_edge_delays"), False) == "flood"
    assert c(("paxos.tick.flood_fwd", "ops.ring.ring_push_max"),
             False) == "ring"
    assert c(("paxos.tick.acceptor",), False) == "paxos.tick.acceptor"
    # inside the flood phase's conditional: the arm, whatever its op_name
    assert c(("paxos.tick.flood_fwd",), False, True) == "flood"
    assert c(("paxos.tick.flood_fwd", "ops.ring.ring_push_max"), False,
             True) == "ring"


def test_an_operation_without_an_op_name_takes_its_callers():
    ev = [("while.1", (), False, 0, 100),
          ("fusion.1", ("paxos.tick.pop", "ops.ring.ring_pop"), False, 1, 5),
          ("conditional.2", (), False, 10, 60),  # no op_name on the plane
          ("fusion.6", (), False, 11, 30),  # the compiler's scatter fusions
          ("fusion.7", ("paxos.tick.flood_fwd",), False, 31, 40),
          ("pmax.28", ("paxos.tick.flood_fwd", "ops.delivery.gossip_fwd",
                       "ops.mesh.pmax"), False, 41, 50),
          ("conditional.3", (), False, 61, 70),  # the reply arm
          ("fusion.9", ("paxos.tick.reply",), False, 62, 69),
          ("copy.3", (), False, 71, 75)]
    got = {k[0:1] + (a,): mesh_trace.classify(k[1], k[2], k[3])
           for k, a, _ in mesh_trace.with_callers(ev)}
    assert [got[k] for k in sorted(got, key=lambda k: k[1])] == [
        mesh_trace.UNSCOPED, "ring", mesh_trace.UNSCOPED, "flood", "flood",
        "collective", mesh_trace.UNSCOPED, "paxos.tick.reply",
        mesh_trace.UNSCOPED]


def test_prefixes_are_arguments():
    only_ops = mesh_trace.summarize(MESH, 4, scope_prefixes=("ops.",))
    assert not any(k.startswith("paxos.") for k in only_ops["by_class_s"])
    assert only_ops["by_class_s"]["ring"] > 0
    none = mesh_trace.summarize(MESH, 4, span_prefixes=("nothing.",))
    assert none["spans"] == {}


@pytest.mark.parametrize("name", TRACE_READERS)
def test_reader_reads_the_fixture(name):
    value = bench.load_module("layer_metrics", name).read(fake_run(MESH))
    assert value is not None and value >= 0


def test_per_tick_parts_add_up_to_the_tick(mesh):
    run = fake_run(MESH)
    tick = mesh_trace.per_tick_us(run, None)
    parts = sum(mesh_trace.per_tick_us(run, c) for c in mesh["by_class_s"])
    assert parts == pytest.approx(tick, rel=1e-6)
    assert tick == pytest.approx(mesh["busy_s"] / mesh["ticks"] * 1e6)


def test_allreduce_share_of_the_interconnect_peak(mesh):
    run = fake_run(MESH)
    moved = mesh_trace.ring_allreduce_bytes_per_chip(
        mesh_trace.flood_allreduce_operand_bytes(FIELDS), 4)
    assert moved == 1.5 * 4 * 64 * 3 * 4
    got = mesh_trace.allreduce_ici_pct(run, 1.6e12)
    assert got == pytest.approx(100.0 * moved * 8 * mesh["flood_allreduces"]
                                / mesh["flood_allreduce_s"] / 1.6e12)
    assert 0 < got < 100


def test_packet_allgathers_count_toward_the_share(monkeypatch, mesh):
    """The flood's cross-chip max in its other form: packets all-gathered
    (``ops.mesh.gather`` under the flood's scope), the bytes by the program's
    own counter; nothing where the program does not count them."""
    gathers = {**mesh, "flood_allgathers": 10.0, "flood_allgather_s": 1e-4}
    monkeypatch.setattr(mesh_trace, "of_run", lambda run: gathers)
    run = fake_run(MESH)
    assert mesh_trace.allreduce_ici_pct(run, 1.6e12) is None
    run["setup"]["collectives"]["flood_allgather_bytes"] = 4 * 1025 * 33 * 4
    assert mesh_trace.allgather_bytes_per_chip(4 * 1025 * 33 * 4, 4) \
        == 3 * 1025 * 33 * 4
    moved = (mesh["flood_allreduces"] * mesh_trace.ring_allreduce_bytes_per_chip(
        mesh_trace.flood_allreduce_operand_bytes(FIELDS), 4)
        + 10 * 3 * 1025 * 33 * 4)
    assert mesh_trace.allreduce_ici_pct(run, 1.6e12) == pytest.approx(
        100.0 * moved * 8 / (mesh["flood_allreduce_s"] + 1e-4) / 1.6e12)
    only = {**gathers, "flood_allreduces": 0.0, "flood_allreduce_s": 0.0}
    monkeypatch.setattr(mesh_trace, "of_run", lambda run: only)
    assert mesh_trace.allreduce_ici_pct(run, 1.6e12) == pytest.approx(
        100.0 * 10 * 3 * 1025 * 33 * 4 * 8 / 1e-4 / 1.6e12)


def test_flood_collectives_split_by_their_innermost_scope(mesh):
    # the fixture's program all-reduced every flood: no packet all-gather
    assert mesh["flood_allgathers"] == 0 and mesh["flood_allgather_s"] == 0


@pytest.mark.parametrize("name", TRACE_READERS + ("mesh_allreduce_ici_pct",
                                                  "device_idle_pct.mesh"))
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """The parent's program (no paxos scope, no shard span: a one-chip trace
    stands in), another driver's cell, an untraced run."""
    read = bench.load_module("layer_metrics", name).read
    if name != "device_idle_pct.mesh":  # idle needs no scope: it reads busy
        assert read(fake_run(SOLO, planes=1)) is None
    assert read(fake_run(MESH, driver="mixed_solo")) is None
    assert read({**fake_run(MESH), "trace": None}) is None


def test_counter_reader_reads_setup_and_nothing_without_it():
    read = bench.load_module("layer_metrics", "mesh_collectives_per_tick").read
    assert read(fake_run(MESH)) == 6
    run = fake_run(MESH)
    run["setup"].pop("collectives")
    assert read(run) is None
