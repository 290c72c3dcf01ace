"""The cell ``pbft100k.byzsweep`` beyond what ``test_correct.py`` holds for
every cell (a sound rehearsal is ``correct``, each control is not): the
timed path broken underneath a whole run, the driver's refusal of a program
that cannot run the cell, and the ``.byzsweep`` readers.
"""

import os

import pytest

import byz_checks
import byz_trace
import run as bench

SPEC = bench.load_json(bench.ROOT, "BENCHMARK.json")
CELL = "pbft100k.byzsweep"
READERS = [m["name"] for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]


@pytest.fixture(scope="module")
def counter():
    return bench.CompileCounter()


def drive(counter, seed=2_147_483_659):
    ctx = bench.make_ctx(SPEC, CELL, seed, False, on_chip=False)
    run, comps = bench.drive(ctx, 0.5, counter)
    return run, {c["name"]: c for c in comps}


def test_altered_row_is_rejected(monkeypatch, counter):
    from blockchain_simulator_tpu.models import base

    real = base.sim_metrics
    calls = {"n": 0}

    def altered(cfg, final):
        m = real(cfg, final)
        calls["n"] += 1
        if calls["n"] == 11:  # one row of the window's call (set-up made 8)
            m["forged_commit_ms"] += 50.0  # the onset, a block tick late
        return m

    monkeypatch.setattr(base, "sim_metrics", altered)
    # the sweep layer holds its own reference to the function
    from blockchain_simulator_tpu.parallel import sweep

    monkeypatch.setattr(sweep, "sim_metrics", altered)
    _, comps = drive(counter)
    assert not comps["forged_commit_gap_ms_max"]["ok"], comps


def test_compile_inside_the_window_is_rejected(monkeypatch, counter):
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.parallel import sweep

    real = sweep.sim_metrics
    shapes = iter(range(3, 10_000))

    def compiles(cfg, final):
        if os.environ.get("_BENCH_TEST_IN_WINDOW"):
            jax.jit(lambda x: x + 1)(jnp.zeros(next(shapes))).block_until_ready()
        return real(cfg, final)

    monkeypatch.setattr(sweep, "sim_metrics", compiles)
    driver_mod = bench.load_module("drivers", "byzsweep")
    real_window = driver_mod.Driver.window

    def window(self, t, s):
        os.environ["_BENCH_TEST_IN_WINDOW"] = "1"
        try:
            return real_window(self, t, s)
        finally:
            del os.environ["_BENCH_TEST_IN_WINDOW"]

    monkeypatch.setattr(bench, "load_module", lambda kind, name: driver_mod)
    monkeypatch.setattr(driver_mod.Driver, "window", window)
    _, comps = drive(counter)
    assert comps["compiles_in_window"]["value"] > 0
    assert not comps["compiles_in_window"]["ok"]


@pytest.mark.parametrize("lacking", ("scope", "span"))
def test_driver_refuses_a_program_without_the_scope_or_the_span(
        monkeypatch, counter, lacking):
    """How the parent of the PR that added the cell fails: at once, before
    anything is built."""
    from blockchain_simulator_tpu.models import pbft
    from blockchain_simulator_tpu.parallel import sweep

    if lacking == "scope":
        monkeypatch.setattr(pbft, "SCOPES", tuple(
            s for s in pbft.SCOPES if s != "pbft.tick.forge"))
    else:
        monkeypatch.delattr(sweep, "SPANS")
    built = []
    monkeypatch.setattr(sweep, "run_byzantine_sweep",
                        lambda *a, **k: built.append(a))
    with pytest.raises(AttributeError, match="refusing before building"):
        drive(counter)
    assert not built


def test_rehearsal_record_and_readers_without_a_trace(counter):
    run, comps = drive(counter)
    assert all(c["ok"] for c in comps.values()), comps
    w, setup = run["window"], run["setup"]
    assert w["unit"] == "points" and w["failed"] == 0
    assert w["attempted"] == 8 * len(w["samples"]) >= 8
    assert setup["points_per_call"] == 8 and setup["schedule"] == "tick"
    # XLA:CPU reports no memory: nothing is cut, one dispatch of eight lanes
    assert setup["tiles_per_call"] == 1 and setup["tile_lanes"] == 8
    assert [m["f"] for m in w["samples"][0]["rows"]] == byz_checks.f_values(
        bench.resolve(SPEC, CELL)["config"], run["fields"]["n"])
    assert len(READERS) == 9
    for name in READERS:
        assert bench.load_module("layer_metrics", name).read(run) is None, name
    # every other cell's traffic: the readers return nothing there either
    other = {**run, "traffic": {"driver": "sweep"}, "trace": {
        "spans": {"bench.dispatch": [{"busy_s": 1.0, "dur_s": 1.1}]},
        "busy_s": 1.0, "window_s": 2.0, "path": "/nonexistent"}}
    for name in READERS:
        assert bench.load_module("layer_metrics", name).read(other) is None


def test_readers_on_a_traced_call(monkeypatch):
    """The arithmetic of the readers on a made-up reduction of a traced
    call: two tiles of four lanes, 600 ticks each."""
    import program_trace

    run = {
        "traffic": {"driver": "byzsweep"},
        "fields": {"n": 100_000, "pbft_max_slots": 64},
        "window": {"steps_per_dispatch": 600, "tiles_per_call": 2},
        "trace": {"busy_s": 6.0, "window_s": 6.25, "path": "x", "spans": {
            "bench.dispatch": [{"busy_s": 6.0, "dur_s": 6.1}]}},
        "_program_trace": {
            "main_runs": 2, "busy_s": 6.0, "scoped_s": 4.5,
            "runs_by_inner_s": {"ops.ring.ring_pop": 1.8,
                                "ops.ring.ring_push_max": 0.6,
                                "ops.delay.bucket_count_chain": 0.24,
                                "ops.gate.any_lane": 0.012},
            "runs_by_outer_s": {},
            "spans": {"sweep.tile": [{"stats": {"lanes": 4}},
                                     {"stats": {"lanes": 4}}]}},
    }
    read = lambda name: bench.load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("tick_step_us.byzsweep") == pytest.approx(5000.0)
    assert read("ops_ring_us.byzsweep") == pytest.approx(2000.0)
    assert read("ops_sampler_us.byzsweep") == pytest.approx(200.0)
    assert read("ops_gate_us.byzsweep") == pytest.approx(10.0)
    assert read("sweep_tile_lanes") == 4
    assert read("sweep_host_ms.byzsweep") == pytest.approx(100.0)
    assert read("device_idle_pct.byzsweep") == pytest.approx(4.0)
    assert read("device_scoped_pct.byzsweep") == pytest.approx(75.0)
    # four lanes x three rings x (a slice read + one written) of 25.6 MB
    assert byz_trace.ring_pop_bytes_per_tick(run["fields"], 4) == 614_400_000
    # 614.4 MB in 1,500 us is 409.6 GB/s: half of a v5e's 819 GB/s
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    assert read("ring_pop_hbm_pct.byzsweep") == pytest.approx(
        100 * 409.6e9 / 819e9)
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "cpu"})()])
    assert read("ring_pop_hbm_pct.byzsweep") is None
    assert program_trace.of_run(run) is run["_program_trace"]
