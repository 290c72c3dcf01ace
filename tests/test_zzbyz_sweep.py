"""BASELINE config 4 (the Byzantine-fault sweep with forging) on the CPU at
small n: what the cell ``pbft100k.byzsweep`` rests on.

- the tick engine with ``byz_forge`` under ``sweep.dyn_batched_fn`` against
  the plain reference that forges (``benchmark/reference/pbft_byz_engine.py``:
  a per-message calendar of events that imports nothing from the program),
  at n = 63 over all eight fractions of the grid, f = floor(n * k / 21);
- the sweep layer's tiles: with the device's memory stubbed small, a list
  that outgrows it runs as equal tiles through ONE executable, rows in order
  and entry for entry those of one dispatch and of solo runs; a list that
  fits dispatches as it always did;
- the sweep layer's choice of program on one device (PR 49): lanes that are
  a large share of the device's memory run lane after lane through the
  ``lax.map`` executable, rows entry for entry those of the lane batch.

Both sides run at the SAME n here, so the onset is compared exactly to the
block tick; the cell compares n = 100,000 with n = 504 and says why that
holds (``benchmark/configs/pbft-byzsweep-100k.json``).
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models import pbft
from blockchain_simulator_tpu.models.base import canonical_fault_cfg, sim_metrics
from blockchain_simulator_tpu.parallel import sweep
from blockchain_simulator_tpu.utils import aotcache, telemetry
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, LEVELS, SEED = 63, 8, 2_147_483_659  # one past 2**31, as the driver's are
F_VALUES = [N * k // 21 for k in range(LEVELS)]
FIELDS = dict(protocol="pbft", n=N, sim_ms=600, delivery="stat",
              schedule="tick", model_serialization=False,
              stat_sampler="exact", pbft_view_change_num=0,
              faults=dict(byz_forge=True, byz_copies=3))
# Limits of the timing milestones, both sides at n = 63 and no view change.
# The forged slot's last replica: the crossing wave is fixed by the counting,
# the bucket inside it (three, 1 ms apart) is an order statistic of some
# twenty votes on each side: 2 ms is the whole wave.  Mean time to finality
# and commit tail: a block is final when its last replica has counted 32
# COMMITs, each the end of a chain of four delay draws; the last of 63
# replicas moves by a bucket between two streams: 1.5 ms.
FORGED_LIMIT_MS, TIME_LIMIT_MS = 2.0, 1.5


def cfg_of(**over) -> SimConfig:
    fields = {**FIELDS, **over}
    return SimConfig(**{**fields, "faults": FaultConfig(**fields["faults"])})


@pytest.fixture(scope="module")
def engine():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("checks")._engine("pbft_byz_engine")
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def program_rows(shared):
    """All eight levels as lanes of the one dynamic-operand executable (one
    dispatch a run of the suite: tests/conftest.py ``shared``)."""
    def build():
        cfg = cfg_of()
        canon = canonical_fault_cfg(cfg)
        keys = jax.vmap(jax.random.key)(
            jnp.full((LEVELS,), SEED % 2**32, jnp.uint32))
        finals = sweep.dyn_batched_fn(canon)(
            keys, jnp.zeros((LEVELS,), jnp.int32),
            jnp.asarray(F_VALUES, jnp.int32))
        rows = []
        for i, f in enumerate(F_VALUES):
            cfg_i = cfg.with_(
                faults=dataclasses.replace(cfg.faults, n_byzantine=f))
            rows.append(
                sim_metrics(cfg_i, jax.tree.map(lambda x: x[i], finals)))
        return rows

    return shared("zzbyz_sweep.program_rows", build)


@pytest.fixture(scope="module")
def reference_rows(engine, shared):
    return shared("zzbyz_sweep.reference_rows", lambda: [
        engine.run({**FIELDS, "faults": {**FIELDS["faults"],
                                         "n_byzantine": f}}, SEED + k)
        for k, f in enumerate(F_VALUES)])


@pytest.mark.parametrize("k", range(LEVELS))
def test_forging_level_equals_the_reference(k, program_rows, reference_rows):
    m, r = program_rows[k], reference_rows[k]
    for key in ("rounds_sent", "blocks_final_all_nodes", "forged_commits",
                "forged_commit_nodes", "agreement_ok"):
        assert m[key] == r[key], (key, m, r)
    assert m["rounds_sent"] == m["blocks_final_all_nodes"] == 11
    assert m["agreement_ok"] is (k == 0) and m["forged_commits"] == (k > 0)
    # the onset: the block tick whose wave finalized the forged slot on the
    # last replica (a forger hears one forger fewer: at n = 63 that moves
    # the k = 1 onset from the counting's tick 4 to 6, on both sides)
    assert m["forged_commit_ms"] // 50 == r["forged_commit_ms"] // 50, (m, r)
    assert abs(m["forged_commit_ms"] - r["forged_commit_ms"]) <= FORGED_LIMIT_MS
    assert abs(m["mean_time_to_finality_ms"]
               - r["mean_time_to_finality_ms"]) <= TIME_LIMIT_MS
    assert abs(m["last_commit_ms"] - r["last_commit_ms"]) <= TIME_LIMIT_MS


def test_onset_follows_the_counting(reference_rows):
    """3 * (f - 1) * j forged votes reach a forger by block tick j (its own
    are not sent to itself): the last replica crosses N/2 on the first j
    beyond it."""
    for f, r in zip(F_VALUES[1:], reference_rows[1:]):
        j = next(j for j in range(1, 12) if 3 * (f - 1) * j > N // 2)
        assert r["forged_commit_ms"] // 50 == j, (f, r)


def test_fewer_copies_move_the_onset(engine):
    """The cell's control: two copies a block tick instead of three."""
    fields = {**FIELDS, "faults": {"byz_forge": True, "n_byzantine": F_VALUES[2]}}
    three = engine.run({**fields, "faults": {**fields["faults"], "byz_copies": 3}}, 1)
    two = engine.run({**fields, "faults": {**fields["faults"], "byz_copies": 2}}, 1)
    assert two["forged_commit_ms"] - three["forged_commit_ms"] >= 50


@pytest.mark.parametrize("bad", (dict(quorum_rule="2f1"),
                                 dict(topology="gossip"),
                                 dict(pbft_max_rounds=64)))
def test_reference_refuses_what_it_does_not_model(engine, bad):
    with pytest.raises(ValueError):
        engine.run({**FIELDS, **bad}, 0)


def test_forge_rows_carry_the_attack_milestones_and_others_do_not():
    m = runner.run_simulation(cfg_of(n=8, sim_ms=200), seed=3)
    assert m["forged_commit_ms"] == -1.0 and m["forged_commit_nodes"] == 0
    plain = runner.run_simulation(
        cfg_of(n=8, sim_ms=200, faults=dict(n_byzantine=1)), seed=3)
    assert "forged_commit_ms" not in plain and "forged_commits" in plain
    assert "pbft.tick.forge" in pbft.SCOPES


# ---------------------------------------------------------------- tiles ---


def sweep_cfg() -> SimConfig:
    return cfg_of(n=42, sim_ms=300, pbft_view_change_num=1)


def grid(n: int) -> list[int]:
    return [n * k // 21 for k in range(LEVELS)]


def points_of(cfg: SimConfig, seed: int = 5) -> list:
    return [(cfg.with_(faults=dataclasses.replace(cfg.faults, n_byzantine=f)),
             seed) for f in grid(cfg.n)]


@pytest.fixture(scope="module")
def one_dispatch():
    # a worker's own: it also builds the program the tiled sweeps then run,
    # whose ``sweep.tile`` spans must hold no ``build.*`` span
    cfg = sweep_cfg()
    with telemetry.capture() as spans:
        rows = sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))
    return rows, spans


@pytest.fixture
def small_device(monkeypatch):
    """Stub the device's reported memory to hold ``most`` lanes as a lane
    batch.  The tile tests run tiny lanes on a tiny device, where every lane
    is a large share of it: they hold ``_MAP_LANE_SHARE`` at 1 (no lane is
    that large, so the list is a lane batch); ``share=None`` leaves the
    rule's own constant, under which such a device runs its lanes in turn."""
    cfg = sweep_cfg()
    canon = canonical_fault_cfg(cfg.with_(faults=dataclasses.replace(
        cfg.faults, n_byzantine=1)))
    state = sweep._lane_state_bytes(canon)

    def stub(most: int, share: float | None = 1.0):
        monkeypatch.setattr(sweep, "_device_bytes", lambda: int(
            most * sweep._TEMP_FACTOR * state) + 1)
        if share is not None:
            monkeypatch.setattr(sweep, "_MAP_LANE_SHARE", share)
        return canon, state

    return stub


@pytest.mark.parametrize("most,tiles,lanes,pad", ((4, 2, 4, 0), (3, 3, 3, 1),
                                                  (5, 2, 4, 0), (7, 2, 4, 0)))
def test_tiled_sweep_equals_one_dispatch(most, tiles, lanes, pad, one_dispatch,
                                         small_device):
    _, state = small_device(most)
    cfg = sweep_cfg()
    with telemetry.capture() as spans:
        rows = sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))
    assert rows == one_dispatch[0]
    got = [s for s in spans if s["name"] == "sweep.tile"]
    assert [s["attrs"]["tile"] for s in got] == list(range(tiles))
    assert all(s["attrs"]["lanes"] == lanes for s in got)
    assert all(s["attrs"]["points"] == lanes for s in got)  # a lane batch
    assert [s["attrs"]["pad"] for s in got] == [0] * (tiles - 1) + [pad]
    assert all(s["attrs"]["state_bytes"] == state for s in got)
    assert all(s["attrs"]["device_bytes"] == sweep._device_bytes() for s in got)
    # operands / execute / readback stand inside their tile, as under a chunk
    for tile in got:
        inside = [s["name"] for s in spans if s["parent"] == tile["id"]]
        assert inside == ["sweep.operands", "sweep.execute", "sweep.readback"]
        chunk = next(s for s in spans if s["id"] == tile["parent"])
        assert chunk["name"] == "sweep.chunk"


@pytest.mark.parametrize("k", (0, 3, 7))
def test_tiled_rows_equal_solo_runs(k, small_device):
    """Exact sampler: a tiled row is the solo run of its (f, seed), the
    padded tail's too."""
    small_device(3)
    cfg = sweep_cfg()
    f = grid(cfg.n)[k]
    row = sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))[k]
    solo = runner.run_simulation(cfg.with_(faults=dataclasses.replace(
        cfg.faults, n_byzantine=f)), seed=5)
    assert row == {"f": f, "seed": 5, **solo}


def test_all_tiles_run_one_executable(small_device):
    canon, _ = small_device(3)
    cfg = sweep_cfg()
    sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))
    fn = sweep.dyn_batched_fn(canon)
    before = fn._cache_size()
    misses = aotcache.registry.stats()["misses"]
    sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(6,))
    assert fn._cache_size() == before  # three tiles, the tail padded: one shape
    assert aotcache.registry.stats()["misses"] == misses


def test_meta_names_the_tile(small_device):
    canon, state = small_device(3)
    points = points_of(sweep_cfg())
    rows, meta = sweep.run_dyn_points(canon, points, with_index=True)
    assert meta["tile"] == {"program": "lane-batch", "lanes": 3, "points": 3,
                            "state_bytes": state,
                            "device_bytes": sweep._device_bytes()}
    assert (meta["dispatches"], meta["lanes"], meta["pad"]) == (3, 9, 1)
    assert [r["point"] for r in meta["rows"]] == list(range(LEVELS))
    assert len(rows) == LEVELS
    # a bucket-padded list (the server's) cut to the device: the first n_out
    # rows, whichever tile holds them; a tile of padding alone is not run
    got, meta = sweep.run_dyn_points(canon, points, n_out=5, with_index=True)
    assert got == rows[:5] and meta["dispatches"] == 2


def test_a_list_that_fits_dispatches_as_before(one_dispatch, small_device):
    rows, spans = one_dispatch
    assert [s["name"] for s in spans if s["name"].startswith("sweep.")] == [
        "sweep.operands", "sweep.execute", "sweep.readback"]
    canon, _ = small_device(LEVELS)  # a device that holds exactly the list
    cfg = sweep_cfg()
    points = points_of(cfg)
    with telemetry.capture() as again:
        got, meta = sweep.run_dyn_points(canon, points, with_index=True)
    assert not [s for s in again if s["name"] in ("sweep.tile", "sweep.chunk")]
    assert meta["tile"] is None and meta["dispatches"] == 1 and meta["pad"] == 0
    assert [{"f": f, "seed": 5, **m} for f, m in zip(grid(cfg.n), got)] == rows


def _bench_config(name: str) -> tuple[dict, SimConfig]:
    """A configuration of the benchmark and its canonical ``SimConfig``."""
    sys.path.insert(0, BENCH)
    try:
        program = importlib.import_module("program")
        run = importlib.import_module("run")
    finally:
        sys.path.remove(BENCH)
    config = run.load_json(BENCH, "configs", name + ".json")
    return config, canonical_fault_cfg(program.sim_config(config["fields"]))


def test_the_configurations_lane_is_sized_from_its_init():
    """``eval_shape`` of ``pbft.init`` at the cell's fields: three rings of
    460.8 MB, four int32 and two bool tables; as a lane batch on a 16 GB
    chip the eight points would run as two tiles of four."""
    config, canon = _bench_config("pbft-byzsweep-100k")
    state = sweep._lane_state_bytes(canon)
    rings = 3 * 18 * 100_000 * 64 * 4
    assert rings < state < rings + 140_000_000 and round(state / 1e6) == 1509
    assert config["grid"]["f_values"] == [100_000 * k // 21 for k in range(8)]
    assert config["grid"]["f_values"][-1] == (100_000 - 1) // 3


def test_device_tile_rule(monkeypatch):
    canon = canonical_fault_cfg(sweep_cfg())
    state = sweep._lane_state_bytes(canon)
    monkeypatch.setattr(sweep, "_device_bytes", lambda: None)
    assert sweep._device_tile(canon, 10_000) is None  # XLA:CPU reports none
    monkeypatch.setattr(sweep, "_device_bytes", lambda: int(10.5 * state))
    assert sweep._device_tile(canon, 5) is None       # 5 lanes x 2 fit 10.5
    assert sweep._device_tile(canon, 6)["lanes"] == 3
    assert sweep._device_tile(canon, 11)["lanes"] == 4  # 3 tiles: 4, 4, 3 + 1
    assert sweep._device_tile(canon, 1) is None
    monkeypatch.setattr(sweep, "_device_bytes", lambda: state)  # not even one
    assert sweep._device_tile(canon, 3)["lanes"] == 1


# ------------------------------- the choice of program on one device (PR 49)

V5E_BYTES = 16_909_336_064  # ``bytes_limit`` of a TPU v5 lite (my chip runs, PR 47)


def _bench_canon(name: str) -> SimConfig:
    return _bench_config(name)[1]


def _bare(rows: list) -> list:
    """``run_byzantine_sweep``'s rows as ``run_dyn_points`` returns them."""
    return [{k: v for k, v in m.items() if k not in ("f", "seed")}
            for m in rows]


@pytest.fixture
def placed(monkeypatch):
    """``run_dyn_points`` with the dispatch stubbed out: what it would run,
    as ``(program, mesh lanes or None, points)`` a dispatch."""
    calls = []

    def stub(canon, points, record=True, n_out=None, mesh=None,
             multi_seed=False, probe=None):
        calls.append(("lax.map" if multi_seed else "lane-batch",
                      mesh and dict(mesh.shape), len(list(points))))
        return [{} for _ in range(len(points) if n_out is None else n_out)]

    monkeypatch.setattr(sweep, "_dispatch_dyn_points", stub)
    return calls


MESH1, MESH4 = {"sweep": 1, "nodes": 1}, {"sweep": 4, "nodes": 1}
RULE = {  # (configuration, device bytes, points, mesh) -> what
    # ``meta["tile"]`` names (None: one lane batch) and the dispatches
    "fullmesh-1k-x32": ("pbft-fullmesh-1k", V5E_BYTES, 32, None,
                        None, [("lane-batch", None, 32)]),
    "fullmesh-1k-x100-tiles": ("pbft-fullmesh-1k", V5E_BYTES, 100, None,
                               "lane-batch", [("lane-batch", None, 50)] * 2),
    "byzsweep-100k-x8": ("pbft-byzsweep-100k", V5E_BYTES, 8, None,
                         "lax.map", [("lax.map", None, 8)]),
    "byzsweep-100k-x2-fits-as-a-batch": (
        "pbft-byzsweep-100k", V5E_BYTES, 2, None,
        "lax.map", [("lax.map", None, 2)]),
    "byzsweep-100k-x200-results-outgrow": (
        "pbft-byzsweep-100k", V5E_BYTES, 200, None,
        "lax.map", [("lax.map", None, 100)] * 2),
    "byzsweep-100k-one-point": ("pbft-byzsweep-100k", V5E_BYTES, 1, None,
                                None, [("lane-batch", None, 1)]),
    "byzsweep-100k-mesh-of-1": ("pbft-byzsweep-100k", V5E_BYTES, 8, 1,
                                "lax.map", [("lax.map", MESH1, 8)]),
    "byzsweep-100k-mesh-of-4": ("pbft-byzsweep-100k", V5E_BYTES, 8, 4,
                                None, [("lane-batch", MESH4, 8)]),
    "byzsweep-100k-no-memory-reported": (
        "pbft-byzsweep-100k", None, 8, None, None, [("lane-batch", None, 8)]),
    "byzsweep-100k-on-a-device-of-256GB": (
        "pbft-byzsweep-100k", 256 * 2**30, 8, None,
        None, [("lane-batch", None, 8)]),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_places_a_list(case, placed, monkeypatch):
    """Lane bytes by ``eval_shape`` at the cells' own sizes; nothing runs."""
    name, device, n_points, n_mesh, program, want = RULE[case]
    canon = _bench_canon(name)
    monkeypatch.setattr(sweep, "_device_bytes", lambda: device)
    mesh = None
    if n_mesh is not None:
        from blockchain_simulator_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_node_shards=1, n_sweep=n_mesh)
    rows, meta = sweep.run_dyn_points(canon, [(canon, s) for s in
                                              range(n_points)], mesh=mesh,
                                      record=False, with_index=True)
    assert placed == want and len(rows) == n_points
    if program is None:
        assert meta["tile"] is None
    else:
        each = want[0][2]
        assert (meta["tile"]["program"], meta["tile"]["points"],
                meta["tile"]["lanes"]) == (
            program, each, 1 if program == "lax.map" else each)
    assert meta["dispatches"] == len(want)
    assert meta["lanes"] == sum(w[2] for w in want)
    assert meta["pad"] == meta["lanes"] - n_points


def test_the_caller_can_still_force_the_map(placed, monkeypatch):
    """``multi_seed=True`` keeps its meaning whatever the lanes' size and
    whatever the device reports: one dispatch of the map, no tile."""
    canon = _bench_canon("pbft-fullmesh-1k")
    for device in (None, V5E_BYTES):
        monkeypatch.setattr(sweep, "_device_bytes", lambda: device)
        del placed[:]
        _, meta = sweep.run_dyn_points(canon, [(canon, s) for s in range(32)],
                                       record=False, multi_seed=True,
                                       with_index=True)
        assert placed == [("lax.map", None, 32)] and meta["tile"] is None


def test_the_constant_lies_between_its_readings():
    """PERF.md section 7 (h), on a v5e: the 121.4 MB per-edge lane (the lane
    batch x1.79) and the 279 MB one (``lax.map`` x1.16); nearer still, the
    per-edge PBFT lanes of 182.1 MB (the batch x1.34) and 273.1 MB
    (``lax.map`` x1.27) that PR 49 read."""
    lane = sweep._lane_state_bytes(_bench_canon("pbft-fullmesh-1k"))
    assert round(lane / 1e6, 1) == 121.4
    assert 182.1e6 < sweep._MAP_LANE_SHARE * V5E_BYTES < 273.1e6


def test_map_placed_rows_equal_the_lane_batch(one_dispatch, small_device):
    """Exact sampler: the rows of a rule-placed map dispatch are, entry for
    entry, those of the lane batch; one ``sweep.tile`` span says one lane at
    a time and all the points."""
    canon, state = small_device(3, share=None)
    cfg = sweep_cfg()
    with telemetry.capture() as spans:
        rows = sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))
    assert rows == one_dispatch[0]
    got = [s for s in spans if s["name"] == "sweep.tile"]
    assert len(got) == 1
    attrs = got[0]["attrs"]
    assert (attrs["tile"], attrs["lanes"], attrs["points"], attrs["pad"]) == (
        0, 1, LEVELS, 0)
    assert attrs["state_bytes"] == state
    assert attrs["device_bytes"] == sweep._device_bytes()
    inside = [s["name"] for s in spans if s["parent"] == got[0]["id"]]
    assert inside[-3:] == ["sweep.operands", "sweep.execute", "sweep.readback"]
    execute = next(s for s in spans if s["name"] == "sweep.execute")
    assert execute["attrs"]["lanes"] == LEVELS  # the points dispatched
    chunk = next(s for s in spans if s["id"] == got[0]["parent"])
    assert chunk["name"] == "sweep.chunk"


@pytest.mark.parametrize("k", (0, 4, 7))
def test_map_placed_rows_equal_solo_runs(k, small_device):
    small_device(3, share=None)
    cfg = sweep_cfg()
    f = grid(cfg.n)[k]
    row = sweep.run_byzantine_sweep(cfg, grid(cfg.n), seeds=(5,))[k]
    solo = runner.run_simulation(cfg.with_(faults=dataclasses.replace(
        cfg.faults, n_byzantine=f)), seed=5)
    assert row == {"f": f, "seed": 5, **solo}


@pytest.fixture
def results_fit(monkeypatch):
    """A device that holds one lane's state and temporaries and the stacked
    results of ``most`` lanes: every lane is a large share of it."""
    canon = canonical_fault_cfg(sweep_cfg())
    state = sweep._lane_state_bytes(canon)

    def stub(most: int):
        monkeypatch.setattr(sweep, "_device_bytes", lambda: int(
            sweep._TEMP_FACTOR * state
            + most * sweep._lane_result_bytes(canon)) + 1)
        return canon

    return stub


@pytest.mark.parametrize("most,points,dispatches,each,pad", (
    (8, 8, 1, 8, 0), (7, 8, 2, 4, 0), (4, 7, 2, 4, 1), (3, 8, 3, 3, 1),
    (1, 3, 3, 1, 0)))
def test_a_map_dispatch_is_sized_to_the_device(most, points, dispatches, each,
                                               pad, one_dispatch, results_fit):
    """Stacked results outgrow the device: as few equal map dispatches as
    fit, rows in order, the tail's padding run and not read."""
    canon = results_fit(most)
    pts = points_of(sweep_cfg())[:points]
    with telemetry.capture() as spans:
        rows, meta = sweep.run_dyn_points(canon, pts, record=False,
                                          with_index=True)
    assert rows == _bare(one_dispatch[0][:points])
    assert meta["tile"]["program"] == "lax.map"
    assert (meta["tile"]["lanes"], meta["tile"]["points"]) == (1, each)
    assert (meta["dispatches"], meta["lanes"], meta["pad"]) == (
        dispatches, dispatches * each, pad)
    assert [r["point"] for r in meta["rows"]] == list(range(points))
    got = [s["attrs"] for s in spans if s["name"] == "sweep.tile"]
    assert [a["tile"] for a in got] == list(range(dispatches))
    assert all(a["lanes"] == 1 and a["points"] == each for a in got)
    assert [a["pad"] for a in got] == [0] * (dispatches - 1) + [pad]


def test_a_bucket_padded_list_under_the_map(one_dispatch, results_fit):
    """The server's ``n_out``: the first rows, whichever dispatch holds them;
    a dispatch of padding alone is not run."""
    canon = results_fit(3)
    pts = points_of(sweep_cfg())
    got, meta = sweep.run_dyn_points(canon, pts, n_out=5, record=False,
                                     with_index=True)
    assert got == _bare(one_dispatch[0][:5]) and meta["dispatches"] == 2


def test_all_map_dispatches_run_one_executable(results_fit):
    canon = results_fit(3)
    pts = points_of(sweep_cfg())
    sweep.run_dyn_points(canon, pts, record=False)
    fn = sweep.multi_seed_fn(canon, 3)
    before = fn._cache_size()
    misses = aotcache.registry.stats()["misses"]
    sweep.run_dyn_points(canon, points_of(sweep_cfg(), seed=6), record=False)
    assert fn._cache_size() == before  # three dispatches, the tail padded
    assert aotcache.registry.stats()["misses"] == misses


def test_a_supervised_map_chunk_degrades_to_the_map(results_fit, monkeypatch):
    """The placement holds on the degrade arm: lanes sized to run one after
    another do not fit as a lane batch."""
    from blockchain_simulator_tpu.parallel import journal

    canon = results_fit(8)
    pts = points_of(sweep_cfg())
    seen = []
    real = sweep._dispatch_dyn_points

    def spy(canon, points, record=True, n_out=None, mesh=None,
            multi_seed=False, probe=None):
        seen.append(multi_seed)
        if len(seen) == 1:
            raise RuntimeError("the primary arm fails once")
        return real(canon, points, record, n_out, mesh, multi_seed, probe)

    monkeypatch.setattr(sweep, "_dispatch_dyn_points", spy)
    sup = journal.ChunkSupervisor(deadline_s=None, retries=0, backoff_s=0.0)
    rows = sweep.run_dyn_points(canon, pts, record=False, supervise=sup)
    assert seen == [True, True] and len(rows) == LEVELS


STACKS = {  # the stack cells' tiles on a v5e: topo/committee.tile_plan
    "pbft-committee-200x500": {"lanes": 100, "tiles": 2},
    "raft-groups-20kx5": {"lanes": 20_000, "tiles": 1},
    "raft-leadercrash-20kx5": {"lanes": 20_000, "tiles": 1},
}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_a_stacks_tiles_are_what_they_were(name, monkeypatch):
    from blockchain_simulator_tpu.topo import committee

    canon = _bench_canon(name)
    monkeypatch.setattr(sweep, "_device_bytes", lambda: V5E_BYTES)
    icfg = committee.inner_cfg(canon)
    assert committee.tile_plan(icfg, canon.committees) == STACKS[name]
    cut = sweep._device_tile(icfg, canon.committees)
    assert cut is None or sorted(cut) == ["device_bytes", "lanes",
                                          "state_bytes"]
