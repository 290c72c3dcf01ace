"""``program_trace.py`` on a small recorded trace (TPU v5e, the served driver
far below rehearsal size, 0.3 s of window; ``record_program_fixture.py`` made
it), and its readers on a trace of a program that has no scopes and no spans
(``solo_small``, recorded from the parent of the PR that added them)."""

import os

import pytest

import program_trace
import run as bench

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
SERVED = os.path.join(FIXTURES, "served_small.xplane.pb.gz")
SOLO = os.path.join(FIXTURES, "solo_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def served():
    return program_trace.summarize(SERVED)


def test_scope_path_reads_the_program_scopes_off_an_op_name():
    path = ("jit(sim)/vmap(jit(sim))/while/body/closed_call/pbft.tick.pop/"
            "ops.ring.ring_pop/jit(remainder)")
    assert program_trace.scope_path(path) == (
        "pbft.tick.pop", "ops.ring.ring_pop")
    assert program_trace.scope_path(
        "jit(f)/vmap(pbft.round.commit)/ops.delay.binom/mul") == (
        "pbft.round.commit", "ops.delay.binom")
    assert program_trace.scope_path("jit(sim_round)/while/body/mul") == ()
    assert program_trace.scope_path("") == ()


def test_scope_tables_sum_to_the_devices_busy_time(served):
    assert served["devices"] == ["/device:TPU:0"]
    assert 0 < served["busy_s"] < served["window_s"]
    for table in ("by_inner_s", "by_outer_s"):
        assert sum(served[table].values()) == pytest.approx(
            served["busy_s"], rel=1e-6)
    # the engine's phases are the outer scopes, the ops the inner ones
    outer = {k for k in served["by_outer_s"] if k != program_trace.UNSCOPED}
    assert outer and all(k.startswith("pbft.tick.") for k in outer)
    assert any(k.startswith("ops.ring.") for k in served["by_inner_s"])
    assert served["scoped_s"] == pytest.approx(
        served["busy_s"] - served["by_inner_s"].get(
            program_trace.UNSCOPED, 0.0), rel=1e-6)
    # at 16 nodes the control flow's own time (the gaps between tiny
    # operations, which carries no op_name) is half of the busy time
    assert served["scoped_s"] > 0.3 * served["busy_s"]
    # whole runs of the main program only: no more than the window holds
    assert served["main_runs"] >= 1
    assert sum(served["runs_by_outer_s"].values()) <= served["busy_s"] * (
        1 + 1e-6)


def test_the_programs_spans_hold_the_device_time(served):
    spans = served["spans"]
    execs = spans["serve.dispatch.execute"]
    assert len(execs) >= 2 and all(s["stats"]["span"] for s in execs)
    assert all(0 < s["busy_s"] <= s["dur_s"] for s in execs)
    # nearly all the device's time lies inside the execute spans
    inside = sum(s["busy_s"] for s in execs)
    assert 0.5 * served["busy_s"] < inside <= served["busy_s"] * (1 + 1e-6)
    flushes = spans["serve.batcher.flush"]
    assert all(s["stats"]["size"] == 1 and s["stats"]["mode"] == "solo"
               for s in flushes)
    for name in ("serve.dispatch.operands", "serve.dispatch.readback"):
        assert len(spans[name]) == len(execs)


def test_batcher_states_tile_the_thread_and_split_the_idle_time(served):
    b = served["batcher"]
    assert b["threads"] == 1
    assert all(b["n"][s] >= 1 for s in program_trace.BATCHER_STATES)
    assert sum(b["states_s"].values()) == pytest.approx(
        b["covered_s"], rel=0.02)
    assert sum(b["idle_by_state_s"].values()) == pytest.approx(
        b["idle_s"], rel=1e-9)
    assert abs(b["idle_by_state_s"]["(between states)"]) < 0.02 * b["idle_s"]
    # waiting for traffic is most of the idle time, not all of it
    waiting = b["idle_by_state_s"]["serve.batcher.idle"]
    assert 0.5 * b["idle_s"] < waiting < b["idle_s"]


def test_a_run_cut_by_the_start_of_tracing_is_left_out():
    # recorded from the moment tracing began: inside the window, but short
    cut = [("jit_sim", 5.0, 45.0)]
    whole = [("jit_sim", 50.0 + 100 * i, 150.0 + 100 * i) for i in range(4)]
    late = [("jit_sim", 950.0, 1050.0)]  # ends after the window
    main, runs = program_trace._main_runs(
        cut + whole + late + [("jit_slice", 0.0, 3.0)], 0.0, 1000.0)
    assert main == "jit_sim" and runs == [(a, b) for _, a, b in whole]
    assert program_trace._main_runs([], 0.0, 1.0) == (None, [])


def _run(path, driver, steps):
    return {"trace": {"path": path}, "traffic": {"driver": driver},
            "window": {"steps_per_dispatch": steps}}


def test_readers_report_in_their_cell_and_nowhere_else():
    run = _run(SERVED, "served", 45)
    read = lambda name: bench.load_module(  # noqa: E731
        "layer_metrics", name).read(run)
    tick = read("tick_step_us.served")
    flush, back = read("serve_flush_host_ms"), read("serve_readback_ms")
    assert tick > 0 and flush > back > 0
    assert 0 < read("device_idle_working_pct.served") < 100
    assert 30 < read("device_scoped_pct.served") <= 100
    # another cell's readers find nothing in this one
    for name in ("device_scoped_pct.solo", "device_scoped_pct.sweep",
                 "round_commit_us", "ops_ring_us.sweep", "sweep_readback_ms"):
        assert read(name) is None
    # the trace is reduced once for all the readers of a run
    assert run["_program_trace"] is not None


def _bare(trace, driver):
    """A run's record as ``run.drive`` makes it, with nothing in it."""
    return {"trace": trace, "traffic": {"driver": driver}, "fields": {},
            "setup": {}, "setup_s": 30.0, "t_window": 1000.0,
            "window": {"steps_per_dispatch": 20, "samples": [],
                       "unit": "rounds"}}


@pytest.mark.parametrize("driver", sorted(
    f[:-3] for f in os.listdir(os.path.join(bench.HERE, "drivers"))
    if f.endswith(".py")))
def test_readers_return_nothing_on_a_program_without_scopes_or_spans(driver):
    """Every reader ``BENCHMARK.json`` lists, under every driver: nothing on
    a run without a trace, and nothing on the trace of a program that has no
    scopes and no spans (the harness's own summary of it empty, so that only
    what the program wrote could be read)."""
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    empty = {"path": SOLO, "spans": {}, "busy_s": 0.0, "window_s": 0.0}
    for trace in (None, empty):
        run = _bare(trace, driver)
        for m in spec["per_layer"]:
            assert bench.load_module(
                "layer_metrics", m["name"]).read(run) is None, m["name"]
    # an untraced run, and a trace that cannot be read
    assert program_trace.of_run(_bare(None, "solo")) is None
    assert program_trace.of_run(_run("/nonexistent.xplane.pb", "solo", 1)) \
        is None
