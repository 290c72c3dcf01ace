"""The eight set-up readers (``build_log.py`` and its ``layer_metrics/``) on a
made-up build log, on a program that keeps none, and on every cell's
rehearsal."""

import pytest

import build_log
import run as bench

from blockchain_simulator_tpu.utils import aotcache, telemetry

SPEC = bench.load_json(bench.ROOT, "BENCHMARK.json")
EIGHT = ("setup_before_build_s", "build_factory_s", "build_trace_s",
         "build_lower_s", "build_compile_s", "setup_after_build_s",
         "build_programs", "build_cache_hit_pct")


def read(name, run):
    return bench.load_module("layer_metrics", name).read(run)


def a_run():
    return {"t_window": 1000.0, "setup_s": 30.0}  # the process began at 970


@pytest.fixture
def log(monkeypatch):
    """A build log to fill: ``add(name, t0, t1, **attrs)`` makes the record
    as the program does (``telemetry.emit``, monotonic stamps) and returns
    its id."""
    records = []
    monkeypatch.setattr(aotcache.registry, "builds", lambda: list(records))

    def add(name, t0, t1, parent=None, **attrs):
        return telemetry.emit(name, t0, t1, parent=parent,
                              sink=records.append, **attrs)
    return add


def test_the_readers_tile_setup_and_sum_roots_only(log):
    log("build.factory", 975.0, 975.5, factory="sim", key="sim:abc")
    outer = log("build.trace", 976.0, 978.0, fun="sim")
    log("build.trace", 976.5, 977.0, parent=outer, fun="product")
    lower = log("build.lower", 978.0, 979.0, fun="jit(sim)")
    log("build.trace", 978.2, 978.4, parent=lower, fun="_threefry_split")
    log("build.compile", 979.0, 981.0, fun="jit(sim)", cache="hit",
        retrieval_ms=1900.0)
    # a root under a span that is no build record (a request's dispatch)
    log("build.compile", 985.0, 986.0, parent="5e12fe00", fun="jit(metrics)",
        cache="miss")
    # inside the window: not set-up
    log("build.compile", 1001.0, 1002.0, fun="jit(late)", cache="miss")
    run = a_run()
    got = {name: read(name, run) for name in EIGHT}
    assert got == pytest.approx({
        "setup_before_build_s": 5.0, "build_factory_s": 0.5,
        "build_trace_s": 2.0, "build_lower_s": 1.0, "build_compile_s": 3.0,
        "setup_after_build_s": 14.0, "build_programs": 2,
        "build_cache_hit_pct": 50.0}, abs=1e-5)
    # before + (first build's start -> last build's end) + after = setup_s
    stamps = [s[1:] for s in build_log.before_window(run)]
    middle = max(t1 for _, t1 in stamps) - min(t0 for t0, _ in stamps)
    assert (got["setup_before_build_s"] + middle
            + got["setup_after_build_s"]) == pytest.approx(run["setup_s"])
    stages = sum(got[k] for k in ("build_factory_s", "build_trace_s",
                                  "build_lower_s", "build_compile_s"))
    assert stages <= middle
    assert build_log.overlaps(run) == []


def test_builds_on_two_threads_at_once_are_named(log):
    log("build.compile", 980.0, 984.0, fun="jit(a)", thread="MainThread")
    log("build.compile", 982.0, 985.0, fun="jit(b)", thread="batcher")
    run = a_run()
    assert read("build_compile_s", run) == pytest.approx(7.0, abs=1e-5)
    assert [(a["attrs"]["fun"], b["attrs"]["fun"])
            for a, b in build_log.overlaps(run)] == [("jit(a)", "jit(b)")]


def test_no_hit_share_where_the_cache_is_off(log):
    log("build.compile", 980.0, 981.0, fun="jit(a)", cache="off")
    run = a_run()
    assert read("build_cache_hit_pct", run) is None
    assert read("build_programs", run) == 1


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    # the parent of the PR that brought the log: the registry has no builds()
    monkeypatch.delattr(aotcache.ExecutableRegistry, "builds")
    run = a_run()
    assert [read(name, run) for name in EIGHT] == [None] * 8
    # and a log with nothing before the window
    monkeypatch.setattr(aotcache.ExecutableRegistry, "builds",
                        lambda self: [], raising=False)
    assert [read(name, a_run()) for name in EIGHT] == [None] * 8


def test_the_eight_are_listed_with_every_cell():
    """By name, wherever later entries were appended around them."""
    cells = [w["name"] for w in SPEC["workloads"]]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert set(EIGHT) <= set(listed)
    for name in EIGHT:
        m = listed[name]
        assert m["layer"] == "program builders" and m["moves"] == "setup_s"
        assert m["workloads"] == cells
        assert m["source"] == ("program_counter" if name in (
            "build_programs", "build_cache_hit_pct") else "program_span")


@pytest.fixture(scope="module")
def counter():
    aotcache.enable_xla_cache()  # as run.py's open_backend does
    return bench.CompileCounter()


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cells_rehearsal_lists_the_eight(workload, counter):
    ctx = bench.make_ctx(SPEC, workload, 2_147_483_659, False, on_chip=False)
    run, _ = bench.drive(ctx, 0.5, counter)
    listed = {m["name"]: read(m["name"], run)
              for m in bench.metrics_of(SPEC, workload, "per_layer")}
    assert all(listed[name] is not None for name in EIGHT), listed
    assert (listed["setup_before_build_s"] + listed["setup_after_build_s"]
            <= run["setup_s"])
