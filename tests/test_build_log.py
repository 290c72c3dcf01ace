"""The build log (utils/aotcache.py): every trace, lowering, backend compile
and registry miss is one ``build.*`` record of utils/telemetry.py.

One process-wide log (jax's monitoring listeners are process-wide), so each
test builds functions under names of its own and reads only their records.
"""

import ast
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from blockchain_simulator_tpu.utils import aotcache, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("build.trace", "build.lower", "build.compile")


def records_of(*names):
    """This process's stage records for functions of these names (a trace is
    ``f``, its lowering and compile ``jit(f)``)."""
    funs = set(names) | {f"jit({n})" for n in names}
    return [r for r in aotcache.registry.builds()
            if (r.get("attrs") or {}).get("fun") in funs]


@pytest.fixture(autouse=True)
def listening():
    aotcache.enable_xla_cache()  # what every entry point calls first


def test_fresh_function_leaves_one_record_per_stage_inside_its_call():
    def blog_fresh(x):
        for i in range(40):  # a trace of some milliseconds: over the floor
            x = jnp.where(x > i, x * 3 + 1, x - i)
        return x

    f = jax.jit(blog_fresh)
    x = jnp.ones(5)
    before = aotcache.registry.stats_snapshot()["builds"]
    t0 = time.monotonic()
    f(x).block_until_ready()
    t1 = time.monotonic()
    got = records_of("blog_fresh")
    assert [r["name"] for r in got] == list(STAGES)
    assert [r["attrs"]["fun"] for r in got] == [
        "blog_fresh", "jit(blog_fresh)", "jit(blog_fresh)"]
    ids = {r["id"] for r in aotcache.registry.builds()}
    assert all(r["parent"] not in ids for r in got)  # roots
    stamps = [telemetry.on_monotonic_clock(r) for r in got]
    assert t0 <= stamps[0][0] and stamps[-1][1] <= t1 + 1e-3
    for (a0, a1), (b0, _) in zip(stamps, stamps[1:]):
        assert a0 <= a1 <= b0 + 1e-3  # in that order, one after another
    assert got[2]["attrs"]["cache"] in ("hit", "miss")
    after = aotcache.registry.stats_snapshot()["builds"]
    assert after["n"] >= before["n"] + 3
    for key, r in zip(("trace_s", "lower_s", "compile_s"), got):
        assert after[key] - before[key] >= r["dur_ms"] / 1000.0 - 1e-5
    # a second call builds nothing
    f(x).block_until_ready()
    assert len(records_of("blog_fresh")) == 3


def test_inner_trace_has_its_parent_and_stays_out_of_the_sums():
    @jax.jit
    def blog_inner(x):
        time.sleep(0.02)  # tracing runs this body once: an inner record
        return x * 2      # of 20 ms, well over the floor

    def blog_outer(x):
        return blog_inner(x) + 1

    before = aotcache.registry.stats_snapshot()["builds"]["trace_s"]
    jax.jit(blog_outer)(jnp.ones(3)).block_until_ready()
    after = aotcache.registry.stats_snapshot()["builds"]["trace_s"]
    traces = {r["attrs"]["fun"]: r for r in records_of(
        "blog_inner", "blog_outer") if r["name"] == "build.trace"}
    inner, outer = traces["blog_inner"], traces["blog_outer"]
    assert inner["parent"] == outer["id"] and inner["trace"] == outer["trace"]
    assert inner["dur_ms"] >= 20.0 and outer["dur_ms"] >= inner["dur_ms"]
    # the outer's time once, not the inner's again
    assert after - before == pytest.approx(outer["dur_ms"] / 1000.0, abs=5e-3)
    # the jnp functions traced inside (multiply, add: tens of microseconds)
    # are under the floor and were not written
    assert not [r for r in aotcache.registry.builds()
                if r["name"] != "build.compile"
                and r["dur_ms"] < aotcache.FLOOR_S * 1000.0]


def test_a_call_with_tracers_whose_trace_jax_had_leaves_nothing():
    """jax reports a trace on every call of a jitted function with tracers,
    even when its own cache answers it: a served request's
    ``jax.random.key`` of a seed array.  No record per dispatch.  Counted
    on a function of this test's own name: the registry's sums are the
    process's, and another thread's build would move them."""
    @jax.jit
    def blog_keyed(seed):
        return jax.random.key(seed)

    keys = jax.vmap(blog_keyed)
    seeds = jnp.arange(4)
    keys(seeds)  # the first one traces, lowers and compiles
    keys(seeds + 1)
    n = len(records_of("blog_keyed"))
    told = []

    def listener(event, *a, fun_name=None, **kw):
        if fun_name == "blog_keyed":
            told.append(event)

    jax.monitoring.register_event_time_span_listener(listener)
    try:
        for _ in range(5):
            keys(seeds)
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    assert told.count("/jax/core/compile/jaxpr_trace_duration") >= 5
    assert len(records_of("blog_keyed")) == n


def test_a_root_hangs_under_the_threads_span_and_is_still_a_root():
    def blog_spanned(x):
        for i in range(40):
            x = jnp.where(x > i, x - 7, x + i)
        return x

    before = aotcache.registry.stats_snapshot()["builds"]["compile_s"]
    with telemetry.span("test.request") as ctx:
        jax.jit(blog_spanned)(jnp.ones(2)).block_until_ready()
    got = records_of("blog_spanned")
    assert [r["name"] for r in got] == list(STAGES)
    assert all(r["parent"] == ctx.span_id and r["trace"] == ctx.trace_id
               for r in got)
    after = aotcache.registry.stats_snapshot()["builds"]["compile_s"]
    assert after - before >= got[2]["dur_ms"] / 1000.0 - 1e-5


_CACHE_CHILD = """
import json, sys
from blockchain_simulator_tpu.utils import aotcache
assert aotcache.enable_xla_cache() == sys.argv[1]
import jax, jax.numpy as jnp

def blog_cached(x):
    return jnp.cumsum(x * 5) - 2

jax.jit(blog_cached)(jnp.ones(64)).block_until_ready()
rec = [r for r in aotcache.registry.builds()
       if r["name"] == "build.compile"
       and r["attrs"]["fun"] == "jit(blog_cached)"]
print(json.dumps({"recs": rec,
                  "builds": aotcache.registry.stats_snapshot()["builds"]}))
"""


def test_persistent_cache_miss_then_hit_across_processes(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           aotcache.XLA_CACHE_ENV: str(tmp_path)}
    seen = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD, str(tmp_path)], env=env,
            capture_output=True, text=True, timeout=300, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    (first,), (second,) = seen[0]["recs"], seen[1]["recs"]
    assert first["attrs"]["cache"] == "miss"
    assert "retrieval_ms" not in first["attrs"]
    assert second["attrs"]["cache"] == "hit"
    assert second["attrs"]["retrieval_ms"] >= 0.0
    assert seen[0]["builds"]["cache_hits"] == 0
    assert seen[0]["builds"]["cache_misses"] >= 1
    # every program of the second process was in the cache
    assert seen[1]["builds"]["cache_misses"] == 0
    assert seen[1]["builds"]["cache_hits"] >= 1
    assert seen[1]["builds"]["retrieval_s"] > 0.0


def test_registry_miss_records_a_factory_span_and_a_hit_nothing():
    reg = aotcache.ExecutableRegistry()
    built = []

    def inner_build(n):
        built.append(("inner", n))
        return n

    def outer_build(n):
        built.append(("outer", n))
        time.sleep(0.01)
        return reg.get("blog-inner", (n,), {}, inner_build) + 1

    assert reg.get("blog-outer", (4,), {}, outer_build) == 5
    inner, outer = reg.builds()  # the inner closes first
    assert (inner["name"], outer["name"]) == ("build.factory",) * 2
    assert outer["attrs"] == {"factory": "blog-outer", "key": "blog-outer"}
    assert inner["attrs"]["factory"] == "blog-inner"
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    snap = reg.stats_snapshot()["builds"]
    assert snap["n"] == 2 and snap["last"] == outer
    # the outer's time once, the inner's not again
    assert snap["factory_s"] == pytest.approx(outer["dur_ms"] / 1000.0)
    assert snap["factory_s"] >= 0.01
    # hits: the callable comes back, nothing is recorded, nothing is built
    assert reg.get("blog-outer", (4,), {}, outer_build) == 5
    assert reg.get("blog-inner", (4,), {}, inner_build) == 4
    assert len(reg.builds()) == 2 and len(built) == 2
    assert reg.stats()["hits"] == 2 and reg.stats()["misses"] == 2
    # a build that raises is a record too, and no entry
    with pytest.raises(ZeroDivisionError):
        reg.get("blog-bad", (), {}, lambda: 1 // 0)
    assert reg.builds()[-1]["status"] == "error" and len(reg) == 2


def test_builds_block_has_its_keys_and_the_log_its_bound():
    snap = aotcache.ExecutableRegistry().stats_snapshot()
    assert set(snap["builds"]) == {
        "n", "factory_s", "trace_s", "lower_s", "compile_s", "cache_hits",
        "cache_misses", "retrieval_s", "late", "last_late", "last"}
    assert snap["builds"]["n"] == 0 and snap["builds"]["last"] is None
    assert json.dumps(snap)  # /stats serves it
    assert aotcache.registry._builds._records.maxlen == (
        aotcache.BUILD_LOG_SIZE) == 1024
    log = aotcache.BuildLog(size=4)
    for i in range(10):
        telemetry.emit("build.trace", 1.0 + i, 1.5 + i, fun=f"f{i}",
                       sink=lambda rec: log._add(True, rec))
    assert len(log.records()) == 4 and log.snapshot()["n"] == 10
    assert [r["attrs"]["fun"] for r in log.records()] == [
        "f6", "f7", "f8", "f9"]
    assert log.snapshot()["trace_s"] == pytest.approx(5.0)
    # manifest() rides every runs.jsonl row: it stays as it was
    assert set(aotcache.registry.manifest()) == {"hits", "misses", "key",
                                                  "mesh"}


def test_a_compile_after_warming_is_late_and_one_inside_a_block_is_not():
    def blog_warmed(x):
        return x + 11

    def blog_late(x):
        return x + 12

    def blog_rewarmed(x):
        return x + 13

    x = jnp.ones(6)
    with aotcache.registry.warming():
        jax.jit(blog_warmed)(x).block_until_ready()
    late0 = aotcache.registry.stats_snapshot()["builds"]["late"]
    jax.jit(blog_late)(x).block_until_ready()
    with aotcache.registry.warming():
        jax.jit(blog_rewarmed)(x).block_until_ready()
    by_fun = {r["attrs"]["fun"]: r for r in records_of(
        "blog_warmed", "blog_late", "blog_rewarmed")
        if r["name"] == "build.compile"}
    assert "late" not in by_fun["jit(blog_warmed)"]["attrs"]
    assert by_fun["jit(blog_late)"]["attrs"]["late"] is True
    assert "late" not in by_fun["jit(blog_rewarmed)"]["attrs"]
    snap = aotcache.registry.stats_snapshot()["builds"]
    assert snap["late"] == late0 + 1
    assert snap["last_late"]["attrs"]["fun"] == "jit(blog_late)"
    notes = [r for r in telemetry.flight.snapshot()
             if r.get("event") == "build.late"
             and r.get("fun") == "jit(blog_late)"]
    assert len(notes) == 1 and notes[0]["cache"] in ("hit", "miss")
    # only a backend compile is late: its trace and lowering say nothing
    assert all("late" not in r["attrs"] for r in records_of("blog_late")
               if r["name"] != "build.compile")


def test_server_stats_name_the_program_compiled_after_prewarm():
    from blockchain_simulator_tpu.serve import ScenarioServer

    tpl = {"protocol": "pbft", "n": 8, "sim_ms": 200, "stat_sampler": "exact"}
    with ScenarioServer(max_batch=1, max_wait_ms=5.0) as srv:
        srv.prewarm(tpl)
        warmed = srv.stats()["cache"]["builds"]
        assert srv.request(dict(tpl, seed=3), 300)["status"] == "ok"
        assert srv.stats()["cache"]["builds"]["late"] == warmed["late"]
        # a shape the server did not prewarm: it compiles on the request
        odd = dict(tpl, n=12, sim_ms=150, seed=4)
        assert srv.request(odd, 300)["status"] == "ok"
        builds = srv.stats()["cache"]["builds"]
    assert builds["late"] >= warmed["late"] + 1
    assert builds["last_late"]["name"] == "build.compile"
    assert builds["last_late"]["attrs"]["late"] is True
    fun = builds["last_late"]["attrs"]["fun"]
    assert fun.startswith("jit(")
    assert any(r.get("event") == "build.late" and r.get("fun") == fun
               for r in telemetry.flight.snapshot())


def _module_scope_imports(path):
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    everywhere = [n for n in ast.walk(tree)
                  if isinstance(n, (ast.Import, ast.ImportFrom))]

    def names(nodes):
        return {a.name.split(".")[0] if isinstance(n, ast.Import)
                else (n.module or "").split(".")[0]
                for n in nodes for a in n.names}
    return names(top), names(everywhere)


def test_telemetry_imports_no_jax_and_aotcache_none_at_module_scope():
    utils = os.path.join(REPO, "blockchain_simulator_tpu", "utils")
    top, anywhere = _module_scope_imports(os.path.join(utils, "telemetry.py"))
    assert "jax" not in anywhere  # the twin finds jax through sys.modules
    top, anywhere = _module_scope_imports(os.path.join(utils, "aotcache.py"))
    assert "jax" not in top and "jax" in anywhere
    # telemetry.reset() had no caller and is gone; what the README
    # documents for placing records on a trace's clock stays
    assert not hasattr(telemetry, "reset")
    assert callable(telemetry.trace_clock_offset_ns)
    assert callable(telemetry.on_trace_clock)
