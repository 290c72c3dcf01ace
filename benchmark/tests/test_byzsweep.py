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
            # the three pops on each of 2 x 600 tile-ticks, 500 us an event
            "runs_by_inner_instruction": {"ops.ring.ring_pop": {
                f"fusion.{k}": [1200, 0.6] for k in (6, 7, 8)}},
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
    # four lanes x (a slice read + one written) of 25.6 MB, a ring's pop
    assert byz_trace.ring_pop_bytes(run["fields"], 4) == 204_800_000
    # 3,600 pops of 204.8 MB in 1.8 s are 409.6 GB/s: half of a v5e's 819 GB/s
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    assert read("ring_pop_hbm_pct.byzsweep") == pytest.approx(
        100 * 409.6e9 / 819e9)
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "cpu"})()])
    assert read("ring_pop_hbm_pct.byzsweep") is None
    assert program_trace.of_run(run) is run["_program_trace"]


def crafted(pop_ticks: tuple, pop_us: float = 600.0):
    """A traced call as ``program_trace.load`` hands it over: two runs of the
    main program (two tiles of four lanes), 20 tile-ticks of 3 ms each; the
    three ring pops (``pop_us`` each, one instruction a ring), the
    view-change ring's (a twelfth of that) and a bitcast (3 us) on the ticks
    of every ten that ``pop_ticks`` names, a taken arm of 1 ms on every tick,
    under the scan's ``while``."""
    import xplane

    pop = ("pbft.tick.pop", "ops.ring.ring_pop")
    ops, names, modules, host = [], [], [], [
        (xplane.WINDOW, 0.0, 200e6, ("/host:CPU", 1), {})]

    def op(name, scopes, a, b):
        ops.append((scopes, a, b))
        names.append(name)

    for r in range(2):
        r0 = 1e6 + r * 70e6
        modules.append(("jit_batched", r0, r0 + 60e6))
        host.append(("sweep.tile", r0 - 1e5, r0 + 61e6, ("/host:CPU", 1),
                     {"lanes": 4}))
        op("while.1", (), r0, r0 + 60e6)
        for k in range(20):
            t = r0 + k * 3e6
            if k % 10 in pop_ticks:
                for i in range(3):
                    op(f"fusion.{6 + i}", pop, t + i * pop_us * 1e3,
                       t + (i + 1) * pop_us * 1e3)
                op("fusion.vc", pop, t + 1800e3,
                   t + 1800e3 + pop_us * 1e3 / 12)
                op("bitcast.5", pop, t + 1890e3, t + 1893e3)
            op("fusion.arm", ("pbft.tick.commit",), t + 1900e3, t + 2900e3)
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "instructions": names, "modules": modules}},
        "host": host}


def traced_run(monkeypatch, tmp_path, made: dict) -> dict:
    import jax
    import program_trace

    monkeypatch.setattr(program_trace, "load", lambda path: made)
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    return {"traffic": {"driver": "byzsweep"},
            "fields": {"n": 100_000, "pbft_max_slots": 64},
            "window": {"steps_per_dispatch": 20, "tiles_per_call": 2},
            "trace": {"path": str(path)}}


@pytest.mark.parametrize("pop_ticks,events", (
    (tuple(range(10)), 120), ((0, 3, 7), 36)))
def test_ring_pop_share_counts_the_pops_that_ran(monkeypatch, tmp_path,
                                                 pop_ticks, events):
    """The share follows the pops in the trace, not the ticks of the run:
    with the pops on every tick and on 30% of the ticks (inside a gate) it
    reads the same, under 100: 3 x 204.8 MB in 1,853 us."""
    run = traced_run(monkeypatch, tmp_path, crafted(pop_ticks))
    pops = byz_trace.ring_pops(run)
    assert sorted(pops) == ["bitcast.5", "fusion.6", "fusion.7", "fusion.8",
                            "fusion.vc"]
    whole = byz_trace.whole_ring_pops(pops)
    assert sorted(whole) == ["fusion.6", "fusion.7", "fusion.8"]
    assert sum(n for n, _ in whole.values()) == events
    assert pops["fusion.6"][1] == pytest.approx(events / 3 * 600e-6)
    share = bench.load_module(
        "layer_metrics", "ring_pop_hbm_pct.byzsweep").read(run)
    assert share == pytest.approx(100 * 3 * 204.8e6 / 1853e-6 / 819e9)
    assert 0 < share < 100
    # another driver's run reads nothing
    assert byz_trace.ring_pops({**run, "traffic": {"driver": "sweep"}}) is None


def test_ring_pop_share_is_not_held_under_the_roofline(monkeypatch, tmp_path):
    """Pops that run faster than the HBM could move ``ring_pop_bytes`` (a
    ring packed into a narrower type, say: 100 us where 204.8 MB need 250)
    are still picked, by their time beside the others', and the share reads
    over 100: the reading the driver refuses, not one the reader hides."""
    run = traced_run(monkeypatch, tmp_path, crafted((0, 3, 7), pop_us=100.0))
    whole = byz_trace.whole_ring_pops(byz_trace.ring_pops(run))
    assert sorted(whole) == ["fusion.6", "fusion.7", "fusion.8"]
    share = bench.load_module(
        "layer_metrics", "ring_pop_hbm_pct.byzsweep").read(run)
    under_us = 3 * 100 + 100 / 12 + 3
    assert share == pytest.approx(100 * 3 * 204.8e6 / under_us * 1e6 / 819e9)
    assert share > 200


def test_whole_ring_pops_by_time_beside_the_largest():
    pops = {"a": [10, 6.2e-3], "b": [10, 6.0e-3], "c": [4, 2.5e-3],
            "vc": [10, 0.58e-3], "over": [10, 1.6e-3], "under": [10, 1.5e-3],
            "idle": [0, 0.0]}  # the largest: c, 625 us an event
    assert sorted(byz_trace.whole_ring_pops(pops)) == ["a", "b", "c", "over"]
    assert byz_trace.whole_ring_pops({}) == {}


def test_instruction_table_is_the_inner_table_kept_apart():
    """On the recorded TPU trace (the served driver: three whole runs of 45
    ticks, the four pops on every tick): under every inner scope the
    instructions' self times sum to ``runs_by_inner_s``, and under
    ``ops.ring.ring_pop`` there are four instructions, each with one event a
    tick."""
    import program_trace

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fixtures", "served_small.xplane.pb.gz")
    whole = program_trace.summarize(path)
    table = whole["runs_by_inner_instruction"]
    assert sorted(table) == sorted(whole["runs_by_inner_s"])
    for scope, by in table.items():
        assert sum(s for _, s in by.values()) == pytest.approx(
            whole["runs_by_inner_s"][scope], rel=1e-9, abs=1e-15)
    pops = table[byz_trace.POP_SCOPE]
    assert len(pops) == 4
    assert {n for n, _ in pops.values()} == {whole["main_runs"] * 45}
