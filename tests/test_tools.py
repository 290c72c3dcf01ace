"""Observability tooling: run manifests (utils/obs.py), backend health
verdicts (utils/health.py), and the perf-trajectory tracker
(tools/bench_compare.py) — plus the one-JSON-line robustness contract on the
CLI, asserted rather than assumed.

Late-alphabet file on purpose: the subprocess tests (health CLI, committed-
artifact parsing) run outside the tier-1 window (ROADMAP.md)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from blockchain_simulator_tpu import SimConfig
from blockchain_simulator_tpu.utils import obs

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH_COMPARE = REPO / "tools" / "bench_compare.py"


def _run(args, env=None, timeout=120, cwd=REPO):
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=timeout, cwd=cwd, env=full_env,
    )


# ---------------------------------------------------------------- obs ------

def test_config_hash_is_stable_and_config_sensitive():
    assert obs.config_hash(SimConfig()) == obs.config_hash(SimConfig())
    assert obs.config_hash(SimConfig()) != obs.config_hash(SimConfig(n=16))
    assert len(obs.config_hash(SimConfig())) == 16


def test_finalize_manifest_and_runs_jsonl(tmp_path, monkeypatch):
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    cfg = SimConfig(protocol="pbft", n=8)
    rec = obs.finalize({"value": 1.0, "backend": "cpu"}, cfg,
                       compile_s=2.0, run_s=0.5, rounds=10)
    man = rec["manifest"]
    assert man["obs_schema"] == obs.OBS_SCHEMA
    assert man["config_hash"] == obs.config_hash(cfg)
    assert man["backend"] == "cpu"          # record value passes through
    assert man["jax"]                       # version from importlib.metadata
    assert man["compile_plus_first_run_s"] == 2.0
    assert man["rounds_per_s"] == 20.0      # THE uniform computation
    # idempotent: re-finalizing neither rebuilds the manifest nor re-appends
    assert obs.finalize(rec, cfg)["manifest"] is man
    lines = runs.read_text().strip().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["manifest"]["config_hash"] == man["config_hash"]


def test_record_run_keeps_caller_dict_pure(tmp_path, monkeypatch):
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    m = {"blocks": 5}
    obs.record_run(m, SimConfig())
    assert m == {"blocks": 5}  # sweep rows stay bit-comparable to singles
    assert "manifest" in json.loads(runs.read_text())
    # and with the env unset it is a no-op (no surprise files)
    monkeypatch.delenv(obs.RUNS_ENV)
    obs.record_run({"blocks": 5}, SimConfig(), runs_path=None)


def test_manifest_never_triggers_backend_init(monkeypatch):
    """Regression pin for the PR 2 guard (now also enforced statically by
    jaxlint's module-scope-backend-touch rule): with NO backend initialized
    (xla_bridge._backends empty — e.g. a parent that launches chip
    children and must not claim the chip itself), building a manifest must
    neither call backend introspection nor fail."""
    import jax
    from jax._src import xla_bridge

    def boom(*a, **kw):  # any introspection call = the bug
        raise AssertionError("manifest triggered a backend init")

    monkeypatch.setattr(xla_bridge, "_backends", {})
    monkeypatch.setattr(jax, "default_backend", boom)
    monkeypatch.setattr(jax, "devices", boom)
    rec = obs.manifest(SimConfig(protocol="pbft", n=8))
    assert rec["obs_schema"] == obs.OBS_SCHEMA
    assert rec["config_hash"]
    assert "backend" not in rec and "device_count" not in rec
    # explicit caller-provided values still pass through untouched
    rec = obs.manifest(None, backend="tpu", device_count=4)
    assert rec["backend"] == "tpu" and rec["device_count"] == 4


# ------------------------------------------------------- bench_compare -----

def _bench_artifact(tmp_path, n, value, metric="m_rounds_per_sec"):
    path = tmp_path / f"BENCH_r{n:02d}.json"
    parsed = None if value is None else {
        "metric": metric, "value": value, "unit": "rounds/s",
        "backend": "cpu", "rounds": 100,
    }
    path.write_text(json.dumps(
        {"n": n, "cmd": "python bench.py", "rc": 0 if parsed else 1,
         "tail": "", "parsed": parsed}))
    return str(path)


def test_bench_compare_parses_every_artifact_it_is_given(tmp_path):
    paths = [_bench_artifact(tmp_path, 1, 100.0),
             _bench_artifact(tmp_path, 2, None),   # a round with no line
             _bench_artifact(tmp_path, 3, 110.0)]
    proc = _run([str(BENCH_COMPARE), *paths])
    assert proc.returncode == 0, proc.stderr + proc.stdout
    for p in paths:
        assert os.path.basename(p) in proc.stdout  # every one in the table
    assert "no regression" in proc.stdout


def test_bench_compare_empty_history_is_not_an_error():
    # no BENCH_r*.json record is committed until a chip run produces one:
    # with no positional files the default glob finds no round artifact
    assert not sorted(REPO.glob("BENCH_r*.json"))
    proc = _run([str(BENCH_COMPARE)])
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_bench_compare_regression_gate(tmp_path):
    ok = [_bench_artifact(tmp_path, 1, 100.0),
          _bench_artifact(tmp_path, 2, 95.0)]
    proc = _run([str(BENCH_COMPARE)] + ok)
    assert proc.returncode == 0, proc.stdout
    regressed = ok + [_bench_artifact(tmp_path, 3, 10.0)]
    proc = _run([str(BENCH_COMPARE)] + regressed)
    assert proc.returncode == 1
    assert "REGRESSION" in proc.stdout
    # a failed round (parsed null) is charted but never compared
    with_null = ok + [_bench_artifact(tmp_path, 4, None)]
    proc = _run([str(BENCH_COMPARE)] + with_null)
    assert proc.returncode == 0, proc.stdout


def test_bench_compare_reads_runs_jsonl(tmp_path):
    runs = tmp_path / "runs.jsonl"
    rows = [
        {"metric": "x_rounds_per_sec", "value": 50.0, "backend": "cpu",
         "manifest": {"obs_schema": 1}},
        {"metric": "x_rounds_per_sec", "value": 51.0, "backend": "cpu",
         "manifest": {"obs_schema": 1}},
    ]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "x_rounds_per_sec" in proc.stdout


def test_bench_compare_never_gates_findings_counters(tmp_path):
    """jaxlint_new_findings is lower-is-better: a drop (findings FIXED) must
    chart but never trip the throughput regression gate."""
    runs = tmp_path / "runs.jsonl"
    rows = [
        {"metric": "jaxlint_new_findings", "value": 1,
         "manifest": {"obs_schema": 1}},
        {"metric": "jaxlint_new_findings", "value": 0,
         "manifest": {"obs_schema": 1}},
    ]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "jaxlint_new_findings" in proc.stdout  # charted, not gated


def test_bench_compare_never_gates_graph_cost_trajectories(tmp_path):
    """The jaxgraph per-program cost series (graph_* prefix, lint/graph) are
    lower-is-better: shrinking a program must chart but never trip the
    throughput rule — growth is gated by the lint.graph budget gate against
    GRAPH_BASELINE.json, not here.  Keyed on the prefix, not the unit
    suffix: an unrelated future "*_bytes" bench metric stays gated."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric in ("graph_sim_pbft_tick_gflops", "graph_sim_pbft_tick_bytes"):
        rows += [
            {"metric": metric, "value": 100.0, "manifest": {"obs_schema": 1}},
            {"metric": metric, "value": 5.0, "manifest": {"obs_schema": 1}},
        ]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "graph_sim_pbft_tick_gflops" in proc.stdout


def test_bench_compare_never_gates_chaos_counters(tmp_path):
    """The chaos drill's counters (chaos_ prefix, tools/chaos_drill.py)
    are lower-is-better with their own exit-code gate: a DROP (faults
    fixed) must chart without tripping the throughput rule, and a rise is
    the drill's failure to report, not bench_compare's."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric in ("chaos_invariant_violations", "chaos_replay_divergence"):
        rows += [
            {"metric": metric, "value": 3, "manifest": {"obs_schema": 1}},
            {"metric": metric, "value": 0, "manifest": {"obs_schema": 1}},
        ]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "chaos_invariant_violations" in proc.stdout


def test_bench_compare_never_gates_fleet_counters(tmp_path):
    """The fleet drill/bench series (fleet_ prefix, tools/fleet_bench.py)
    is charted only: fleet_invariant_violations is lower-is-better with
    the drill's own exit gate, and fleet_rps mixes replica counts and
    machine states across runs — neither may trip the throughput rule."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric, vals in (("fleet_invariant_violations", (2, 0)),
                         ("fleet_rps", (40.0, 5.0))):
        rows += [{"metric": metric, "value": v,
                  "manifest": {"obs_schema": 1}} for v in vals]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "fleet_rps" in proc.stdout


def test_bench_compare_never_gates_journal_resume_series(tmp_path):
    """The durable-sweep series (journal_ from mesh_sweep_bench --journal,
    resume_ from tools/sweep_resume_drill.py) are charted only: overhead
    pct and recompute counts are lower-is-better with their own
    drill/bench exit codes — a drop (a fix, or a fuller journal) must
    never trip the throughput rule."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric, vals in (("journal_overhead_pct", (2.8, 0.4)),
                         ("resume_recomputed_chunks", (1, 0)),
                         ("resume_points_per_s", (5000.0, 100.0))):
        rows += [{"metric": metric, "value": v,
                  "manifest": {"obs_schema": 1}} for v in vals]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "journal_overhead_pct" in proc.stdout
    assert "resume_recomputed_chunks" in proc.stdout


def test_bench_compare_never_gates_telemetry_series(tmp_path):
    """The telemetry report series (telemetry_ prefix, tools/
    telemetry_report.py) is charted only: span-miss counts and coverage/
    overhead percentages are gated by the report's own exit code — a
    coverage drop must never trip the generic throughput rule."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric, vals in (("telemetry_span_miss", (3, 0)),
                         ("telemetry_coverage_pct", (99.9, 10.0)),
                         ("telemetry_overhead_pct", (4.0, 0.5))):
        rows += [{"metric": metric, "value": v,
                  "manifest": {"obs_schema": 1}} for v in vals]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "telemetry_coverage_pct" in proc.stdout


def test_bench_compare_never_gates_query_series(tmp_path):
    """The adaptive-query drill series (query_ prefix, tools/
    query_drill.py) is charted only: violations are lower-is-better and
    the savings multiplier mixes domain widths across runs — both are
    gated by the drill's own exit code, never the throughput rule."""
    runs = tmp_path / "runs.jsonl"
    rows = []
    for metric, vals in (("query_invariant_violations", (2, 0)),
                         ("query_dispatch_savings_x", (21.3, 1.3))):
        rows += [{"metric": metric, "value": v,
                  "manifest": {"obs_schema": 1}} for v in vals]
    runs.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "query_dispatch_savings_x" in proc.stdout


def test_bench_compare_gates_p99_latency_inverted(tmp_path):
    """serve_p99_ms is lower-is-better AND gated: an increase beyond the
    threshold is the regression; a decrease (faster serving) never trips."""
    runs = tmp_path / "runs.jsonl"

    def write(vals):
        runs.write_text("".join(
            json.dumps({"metric": "serve_p99_ms", "value": v,
                        "manifest": {"obs_schema": 1}}) + "\n"
            for v in vals))

    write([100.0, 350.0])  # 3.5x slower: beyond the 50% threshold
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 1
    assert "REGRESSION: serve_p99_ms" in proc.stdout
    write([350.0, 100.0])  # got faster: charted, never gated
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout


def test_bench_compare_gates_sweep_points_per_s(tmp_path):
    """The mesh-sweep smoke's throughput metric rides the default
    higher-is-better gate: a drop beyond the threshold fails, a rise never
    does (tools/mesh_sweep_bench.py --quick emits it)."""
    runs = tmp_path / "runs.jsonl"

    def write(vals):
        runs.write_text("".join(
            json.dumps({"metric": "sweep_points_per_s", "value": v,
                        "manifest": {"obs_schema": 1}}) + "\n"
            for v in vals))

    write([10.0, 2.0])  # 5x slower: beyond the 50% threshold
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 1
    assert "REGRESSION: sweep_points_per_s" in proc.stdout
    write([2.0, 10.0])  # faster sweeps never trip
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout


def test_bench_compare_gates_tick_rounds_per_s(tmp_path):
    """The tick-bench smoke's throughput metric rides the default
    higher-is-better gate (tools/tick_bench.py --quick emits it); the full
    run's tick_bench_rounds_per_s series is a separate name so quick/full
    scales never mix (the mesh_sweep_bench precedent)."""
    runs = tmp_path / "runs.jsonl"

    def write(metric, vals):
        runs.write_text("".join(
            json.dumps({"metric": metric, "value": v,
                        "manifest": {"obs_schema": 1}}) + "\n"
            for v in vals))

    write("tick_rounds_per_s", [100.0, 20.0])  # 5x slower: gated
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 1
    assert "REGRESSION: tick_rounds_per_s" in proc.stdout
    write("tick_rounds_per_s", [20.0, 100.0])  # faster ticks never trip
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout


def test_bench_compare_never_gates_p50_latency(tmp_path):
    """The median moves with the max_wait batching knob by design: charted
    only (UNGATED_SUFFIXES), in either direction."""
    runs = tmp_path / "runs.jsonl"
    runs.write_text("".join(
        json.dumps({"metric": "serve_p50_ms", "value": v,
                    "manifest": {"obs_schema": 1}}) + "\n"
        for v in (10.0, 500.0)))
    proc = _run([str(BENCH_COMPARE), _bench_artifact(tmp_path, 1, 100.0),
                 "--runs", str(runs)])
    assert proc.returncode == 0, proc.stdout
    assert "serve_p50_ms" in proc.stdout


def test_bench_compare_unparseable_artifact_exits_2(tmp_path):
    bad = tmp_path / "BENCH_r09.json"
    bad.write_text("{not json")
    proc = _run([str(BENCH_COMPARE), str(bad)])
    assert proc.returncode == 2
    assert "cannot parse" in proc.stderr


# ------------------------------------------------------------- lint gate ---

def test_lint_sh_chains_both_gates(tmp_path):
    """tools/lint.sh = jaxlint (vs the committed baseline) + bench_compare;
    the lint run leaves a runs.jsonl line when $BLOCKSIM_RUNS_JSONL is set."""
    runs = tmp_path / "runs.jsonl"
    proc = subprocess.run(
        ["bash", str(REPO / "tools" / "lint.sh")],
        capture_output=True, text=True, timeout=240, cwd=REPO,
        # WARM_BENCH=0: the cold/warm bench pair costs ~1 min even scaled
        # down — the chain itself is covered by test_warm_bench_script_*
        # (tests/test_zsweep_cache.py); this smoke pins the lint+compare
        # gates.  GRAPH=0: the IR audit traces every factory (~1.5 min) —
        # its gate is covered end-to-end by tests/test_zzgraph.py.
        # COMMS=0: shardlint compiles every mesh program under SPMD
        # (~2.5 min) — covered by tests/test_zzcomms.py (rule units +
        # the slow-marked full-audit exit-0 test).
        # SERVE=0: the serving smoke compiles a daemon's worth of
        # executables — covered by tests/test_zserve.py's self-test.
        # CHAOS=0: the chaos drill runs every scenario twice — covered by
        # tests/test_zchaos.py (scenario-level + slow CLI test).
        # MESH_SWEEP=0: the mesh-sweep smoke compiles two sweep
        # executables — covered by tests/test_zzpartition.py.
        # FLEET=0: the fleet drill runs every fleet scenario twice —
        # covered by tests/test_zfleet.py (scenario-level + slow CLI).
        # RESUME=0: the sweep resume drill SIGKILLs a real subprocess
        # pair — covered by tests/test_zjournal.py (in-process resume
        # pin) and the slow CLI test.
        # TICK=0: the tick-bench smoke compiles three dispatch arms —
        # covered by tests/test_ztick.py (bit-equality + executable pins).
        # TELEM=0: the telemetry report drives a warm in-process fleet —
        # covered by tests/test_zztelemetry.py (gates + slow CLI test).
        # TOPO=0 / SHARD_TOPO=0: the topology smokes compile sparse and
        # mesh-sharded overlay programs (~1 min each) — covered by
        # tests/test_zztopo.py and tests/test_zzshardtopo.py.
        # CONSOBS=0: the consensus-obs report compiles armed/disarmed
        # twins (~2 min) — covered by tests/test_zzobsim.py.
        # GATHER=0 / QUERY=0: the gather-locality smoke compiles the
        # overlay program under both layouts on 8 devices and the query
        # drill SIGKILLs a real subprocess — covered by
        # tests/test_zzexchange.py and tests/test_zzquery.py.  Together
        # those stages outgrew this smoke's 240 s budget; the chain
        # itself is pinned by the script-contract asserts below.
        env={**os.environ, "BLOCKSIM_RUNS_JSONL": str(runs),
             "WARM_BENCH": "0", "GRAPH": "0", "COMMS": "0", "SERVE": "0",
             "CHAOS": "0", "MESH_SWEEP": "0", "FLEET": "0", "RESUME": "0",
             "TICK": "0", "TELEM": "0", "TOPO": "0", "SHARD_TOPO": "0",
             "CONSOBS": "0", "GATHER": "0", "QUERY": "0"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "jaxlint" in proc.stdout and "no regression" in proc.stdout
    # the jaxgraph, serve and chaos stages are chained (and skippable) —
    # pin the script contract
    script = (REPO / "tools" / "lint.sh").read_text()
    assert "blockchain_simulator_tpu.lint.graph" in script
    assert '"${GRAPH:-1}"' in script
    assert "blockchain_simulator_tpu.lint.comms" in script
    assert '"${COMMS:-1}"' in script
    assert "blockchain_simulator_tpu.serve --self-test" in script
    assert '"${SERVE:-1}"' in script
    assert "tools/chaos_drill.py --quick" in script
    assert '"${CHAOS:-1}"' in script
    assert "tools/mesh_sweep_bench.py --quick" in script
    assert '"${MESH_SWEEP:-1}"' in script
    assert "tools/fleet_bench.py --quick" in script
    assert '"${FLEET:-1}"' in script
    assert "tools/sweep_resume_drill.py --quick" in script
    assert '"${RESUME:-1}"' in script
    assert "tools/tick_bench.py --quick" in script
    assert '"${TICK:-1}"' in script
    assert "tools/telemetry_report.py --quick" in script
    assert '"${TELEM:-1}"' in script
    assert "tools/gather_locality_bench.py --quick" in script
    assert '"${GATHER:-1}"' in script
    assert "tools/query_drill.py --quick" in script
    assert '"${QUERY:-1}"' in script
    recs = [json.loads(ln) for ln in runs.read_text().strip().splitlines()]
    lint_recs = [r for r in recs if r.get("metric") == "jaxlint_new_findings"]
    assert lint_recs and lint_recs[-1]["value"] == 0
    assert lint_recs[-1]["manifest"]["obs_schema"] == obs.OBS_SCHEMA


# --------------------------------------------------------------- health ----

CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def test_health_cli_prints_one_structured_verdict_line(tmp_path):
    log = tmp_path / "HEALTH.jsonl"
    proc = _run(["-m", "blockchain_simulator_tpu.utils.health",
                 "--patience", "240", "--log", str(log)],
                env=CPU_ENV, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1  # exactly one JSON verdict line
    rec = json.loads(lines[0])
    assert rec["verdict"] == "healthy"
    assert rec["backend"] == "cpu"
    assert rec["probe_s"] > 0
    assert rec["supervised"] is True
    # the rolling log got the same verdict
    logged = json.loads(log.read_text().strip().splitlines()[-1])
    assert logged["verdict"] == "healthy"


def test_health_probe_sick_on_bogus_platform():
    proc = _run(["-m", "blockchain_simulator_tpu.utils.health",
                 "--in-process", "--log", ""],
                env={"JAX_PLATFORMS": "definitely_not_a_backend"},
                timeout=240)
    assert proc.returncode == 1
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["verdict"] == "sick"
    assert "error" in rec


# ------------------------------------------- CLI one-JSON-line contract ----

@pytest.mark.parametrize("argv", [
    ["--protocol", "pbft", "--n", "8", "--sim-ms", "600", "--timing"],
    ["--protocol", "pbft", "--n", "8", "--sim-ms", "600",
     "--seeds", "0", "1"],
    ["--protocol", "pbft", "--n", "8", "--sim-ms", "400",
     "--pbft-rounds", "4", "--pbft-max-slots", "8", "--byz-sweep"],
])
def test_cli_every_line_is_json_with_manifest(argv, capsys):
    from blockchain_simulator_tpu.cli import main

    assert main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines
    for line in lines:
        rec = json.loads(line)  # the robustness contract, asserted
        assert rec["manifest"]["obs_schema"] == obs.OBS_SCHEMA
        assert rec["manifest"]["config_hash"]


def test_cli_timing_reports_compile_split(capsys):
    from blockchain_simulator_tpu.cli import main

    assert main(["--protocol", "pbft", "--n", "8", "--sim-ms", "500",
                 "--timing"]) == 0
    m = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert m["wallclock_s"] > 0
    assert m["compile_plus_first_run_s"] > 0  # the staged warm run
    # the manifest mirrors the split and computes rounds/s uniformly
    assert m["manifest"]["run_s"] == round(m["wallclock_s"], 3)
    assert m["manifest"].get("rounds_per_s") == obs.rounds_per_s(
        m["blocks_final_all_nodes"], m["wallclock_s"]
    )
