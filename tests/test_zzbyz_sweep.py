"""BASELINE config 4 (the Byzantine-fault sweep with forging) on the CPU at
small n: what the cell ``pbft100k.byzsweep`` rests on.

- the tick engine with ``byz_forge`` under ``sweep.dyn_batched_fn`` against
  the plain reference that forges (``benchmark/reference/pbft_byz_engine.py``:
  a per-message calendar of events that imports nothing from the program),
  at n = 63 over all eight fractions of the grid, f = floor(n * k / 21);
- the sweep layer's tiles: with the device's memory stubbed small, a list
  that outgrows it runs as equal tiles through ONE executable, rows in order
  and entry for entry those of one dispatch and of solo runs; a list that
  fits dispatches as it always did.

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
    """Stub the device's reported memory to hold ``most`` lanes."""
    cfg = sweep_cfg()
    canon = canonical_fault_cfg(cfg.with_(faults=dataclasses.replace(
        cfg.faults, n_byzantine=1)))
    state = sweep._lane_state_bytes(canon)

    def stub(most: int):
        monkeypatch.setattr(sweep, "_device_bytes", lambda: int(
            most * sweep._TEMP_FACTOR * state) + 1)
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
    assert meta["tile"] == {"lanes": 3, "state_bytes": state,
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


def test_the_configurations_lane_is_sized_from_its_init():
    """``eval_shape`` of ``pbft.init`` at the cell's fields: three rings of
    460.8 MB, four int32 and two bool tables; on a 16 GB chip the eight
    points run as two tiles of four."""
    sys.path.insert(0, BENCH)
    try:
        program = importlib.import_module("program")
        run = importlib.import_module("run")
    finally:
        sys.path.remove(BENCH)
    config = run.load_json(BENCH, "configs", "pbft-byzsweep-100k.json")
    canon = canonical_fault_cfg(program.sim_config(config["fields"]))
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
