"""jaxgraph (lint/graph) tests: per-rule firing + clean fixtures over
synthetic programs, budget-gate mechanics, baseline mechanics, catalog
completeness (pure AST, cheap), a small real-program audit with a
determinism pin, and the slow whole-repo sweep (the acceptance gate).

Named test_zz* so the heavy traces land at the very end of the tier-1
alphabetical order (the test_zsweep_cache convention); everything except
the slow-marked sweep traces at most three tiny n=8 programs.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.lint.graph import audit, ir
from blockchain_simulator_tpu.lint.graph import programs as prog_mod
from blockchain_simulator_tpu.lint.graph.programs import ProgramSpec

REPO = Path(__file__).resolve().parents[1]


def spec_of(fn_args_builder, program="fixture", factory="fixture", **kw):
    return ProgramSpec(program, factory, fn_args_builder, **kw)


def audit_one(build, **kw):
    """Run the full audit machinery over one synthetic spec."""
    return audit.run_audit([spec_of(build, **kw)], factories={})


def rules_fired(result):
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------- ir helpers

def test_ir_counts_nested_scan_primitives():
    def f(x):
        def body(c, _):
            return c * 2.0, ()

        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    closed, lowered = ir.trace_program(f, (jnp.float32(1.0),))
    counts = ir.primitive_counts(closed)
    assert counts.get("scan") == 1
    assert counts.get("mul", 0) >= 1  # the body's eqn, reached recursively
    assert ir.cost_summary(lowered) is not None


def test_ir_fingerprint_stable_and_distinguishes():
    f1 = lambda x: x + 1  # noqa: E731
    f2 = lambda x: x * 3  # noqa: E731
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    a1, _ = ir.trace_program(f1, args)
    a2, _ = ir.trace_program(f1, args)
    b, _ = ir.trace_program(f2, args)
    assert ir.fingerprint(a1) == ir.fingerprint(a2)
    assert ir.fingerprint(a1) != ir.fingerprint(b)


# ------------------------------------------------------------- rule fixtures

def test_host_callback_fires_and_clean():
    def with_cb():
        def f(x):
            y = jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x
            )
            return y + 1.0

        return f, (jnp.float32(1.0),)

    res = audit_one(with_cb)
    assert "host-callback-in-program" in rules_fired(res), res.findings

    res = audit_one(lambda: ((lambda x: x + 1.0), (jnp.float32(1.0),)))
    assert "host-callback-in-program" not in rules_fired(res)


def test_f64_fires_under_x64_and_clean_in_default_mode():
    def build():
        return (lambda x: x * 2.0), (
            jax.ShapeDtypeStruct((4,), jnp.dtype("float64")),
        )

    with jax.enable_x64(True):
        res = audit_one(build)
    assert "f64-in-program" in rules_fired(res), res.findings

    res = audit_one(
        lambda: ((lambda x: x * 2.0),
                 (jax.ShapeDtypeStruct((4,), jnp.float32),))
    )
    assert "f64-in-program" not in rules_fired(res)


def test_weak_type_boundary_fires_on_python_scalar_and_clean_on_avals():
    # a bare Python scalar example arg traces to a weak-typed input aval —
    # the re-specialization hazard the rule polices
    res = audit_one(lambda: ((lambda x: x + jnp.float32(1.0)), (1.0,)))
    assert "weak-type-boundary" in rules_fired(res), res.findings

    res = audit_one(
        lambda: ((lambda x: x + jnp.float32(1.0)),
                 (jax.ShapeDtypeStruct((), jnp.float32),))
    )
    assert "weak-type-boundary" not in rules_fired(res)


def test_large_constant_fires_and_small_stays_clean():
    big = np.zeros((300, 300), np.float32)  # 360 KB >= LARGE_CONST_BYTES

    res = audit_one(lambda: ((lambda x: x + big), (big,)))
    # the example arg is concrete but the CLOSURE constant is what bakes in
    assert "large-jaxpr-constant" in rules_fired(res), res.findings

    small = np.zeros((4,), np.float32)
    res = audit_one(lambda: ((lambda x: x + small), (small,)))
    assert "large-jaxpr-constant" not in rules_fired(res)


def test_slow_lowering_fires_on_scatter_add():
    idx = jnp.array([0, 2])

    def build():
        return (lambda x: x.at[idx].add(1.0)), (
            jax.ShapeDtypeStruct((8,), jnp.float32),
        )

    res = audit_one(build)
    fired = [f for f in res.findings if f.rule == "slow-lowering-confirmed"]
    assert fired and fired[0].detail == "scatter-add", res.findings
    assert fired[0].count >= 1


def test_registry_key_divergence_fires_on_distinct_twins_only():
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    diverging = [
        spec_of(lambda: ((lambda x: x + 1.0), args), program="a",
                divergence_group="g"),
        spec_of(lambda: ((lambda x: x * 3.0), args), program="b",
                divergence_group="g", budget=False),
    ]
    res = audit.run_audit(diverging, factories={})
    assert "registry-key-divergence" in rules_fired(res), res.findings

    agreeing = [
        spec_of(lambda: ((lambda x: x + 1.0), args), program="a",
                divergence_group="g"),
        spec_of(lambda: ((lambda x: x + 1.0), args), program="b",
                divergence_group="g", budget=False),
    ]
    res = audit.run_audit(agreeing, factories={})
    assert "registry-key-divergence" not in rules_fired(res)


def test_unaudited_factory_fires_from_discovery():
    res = audit.run_audit([], factories={"ghost": ["somewhere.py"]})
    fired = [f for f in res.findings if f.rule == "unaudited-factory"]
    assert fired and fired[0].program == "ghost"
    assert res.uncovered == ["ghost"]


def test_untraceable_program_is_an_error_not_a_crash():
    def broken():
        raise RuntimeError("factory exploded")

    res = audit_one(broken)
    assert res.errors and "factory exploded" in res.errors[0]
    assert res.reports == {}


# -------------------------------------------------------- discovery/catalog

def test_discover_factories_finds_decorated_registrations(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "from blockchain_simulator_tpu.utils import aotcache\n\n"
        "@aotcache.cached_factory(\"tmp-factory\")\n"
        "def make(cfg):\n    return cfg\n"
    )
    found = prog_mod.discover_factories([str(tmp_path)])
    assert list(found) == ["tmp-factory"]


def test_catalog_covers_every_registered_factory():
    """The completeness contract, pure-AST (no tracing): every
    cached_factory name in the tree has at least one audit spec, and the
    audit-scale configs are valid for the engine arms they claim."""
    found = prog_mod.discover_factories()
    specs = prog_mod.build_catalog()
    covered = {s.factory for s in specs}
    assert set(found) <= covered, f"unaudited: {set(found) - covered}"
    # spec names are unique (baseline keys on them)
    names = [s.program for s in specs]
    assert len(names) == len(set(names))


def test_audit_configs_hit_their_engine_arms():
    from blockchain_simulator_tpu.models import mixed, pbft_round, raft_hb
    from blockchain_simulator_tpu.runner import use_round_schedule

    cfgs = prog_mod.audit_configs()
    assert pbft_round.eligible(cfgs["pbft_round"])
    assert raft_hb.eligible(cfgs["raft_hb"])
    assert mixed.fast_eligible(cfgs["mixed_fast"])
    for arm in ("pbft_tick", "raft_tick", "paxos_tick", "mixed_tick"):
        assert not use_round_schedule(cfgs[arm]), arm


# ------------------------------------------------------------ budget gate

def _report(name="p", flops=1000.0, nbytes=5000.0, budget=True, memory=None):
    return audit.ProgramReport(
        program=name, factory="f", fingerprint="x" * 24,
        cost={"flops": flops, "bytes": nbytes}, memory=memory, prims={},
        n_eqns=1, const_bytes=0, divergence_group=None, budget=budget,
    )


def _result(reports):
    return audit.AuditResult(
        reports=reports, findings=[], errors=[], factories={},
        uncovered=[], stale_budgets=[],
    )


def test_budget_missing_and_regression_and_stale():
    res = _result({"p": _report()})
    audit.apply_budgets(res, {}, tolerance=0.25)
    assert [f.rule for f in res.findings] == ["budget-missing"]

    # deliberately fattened program: measured flops 2x over the pin
    res = _result({"p": _report(flops=2000.0)})
    audit.apply_budgets(res, {"p": {"flops": 1000.0, "bytes": 5000.0}}, 0.25)
    assert [f.rule for f in res.findings] == ["budget-regression"]
    assert res.findings[0].detail == "flops"

    # within tolerance: clean both ways
    res = _result({"p": _report(flops=1100.0)})
    audit.apply_budgets(res, {"p": {"flops": 1000.0, "bytes": 5000.0}}, 0.25)
    assert res.findings == [] and res.stale_budgets == []

    # big improvement: stale note, never a finding
    res = _result({"p": _report(flops=100.0)})
    audit.apply_budgets(res, {"p": {"flops": 1000.0, "bytes": 5000.0}}, 0.25)
    assert res.findings == []
    assert res.stale_budgets == [("p", "flops", 100.0, 1000.0)]

    # budget=False specs (divergence twins) are never budget-gated
    res = _result({"p": _report(budget=False)})
    audit.apply_budgets(res, {}, 0.25)
    assert res.findings == []


def test_memory_budget_axes_gate_and_pin():
    """The memory satellite: compiled memory_analysis axes (peak temp +
    argument bytes) gate alongside flops/bytes and land in written
    budgets."""
    mem = {"temp_bytes": 4096.0, "argument_bytes": 2048.0}
    pin = {"flops": 1000.0, "bytes": 5000.0,
           "temp_bytes": 1024.0, "argument_bytes": 2048.0}

    # temp allocation 4x over its pin: regression on the memory axis
    res = _result({"p": _report(memory=dict(mem))})
    audit.apply_budgets(res, {"p": pin}, tolerance=0.25)
    assert [(f.rule, f.detail) for f in res.findings] == [
        ("budget-regression", "temp_bytes")
    ]

    # at-pin memory is clean
    res = _result({"p": _report(memory={"temp_bytes": 1024.0,
                                        "argument_bytes": 2048.0})})
    audit.apply_budgets(res, {"p": pin}, tolerance=0.25)
    assert res.findings == [] and res.stale_budgets == []

    # pinned memory axis with NO measurement is exit-2 material, not a
    # silent pass (the backend stopped reporting memory_analysis)
    res = _result({"p": _report(memory=None)})
    audit.apply_budgets(res, {"p": pin}, tolerance=0.25)
    assert res.findings == []
    assert any("temp_bytes" in e for e in res.errors)


def test_write_baseline_pins_memory_axes(tmp_path):
    path = str(tmp_path / "GRAPH_BASELINE.json")
    mem = {"temp_bytes": 4096.0, "argument_bytes": 2048.0}
    audit.write_baseline(path, _result({"p": _report(memory=dict(mem))}))
    doc = audit.load_baseline(path)
    assert doc["budgets"]["p"] == {
        "flops": 1000.0, "bytes": 5000.0,
        "temp_bytes": 4096.0, "argument_bytes": 2048.0,
    }


def test_memory_summary_on_real_lowering(small_audit):
    """ir.memory_summary returns both axes, positive, on a real compiled
    budget program (the fixture audit compiles sim.pbft_tick)."""
    res, _ = small_audit
    rep = res.reports["sim.pbft_tick"]
    assert rep.memory is not None
    assert rep.memory["argument_bytes"] > 0
    assert rep.memory["temp_bytes"] >= 0


def test_budget_gate_fires_on_fattened_real_program(small_audit):
    """The satellite contract end-to-end on a REAL traced program: pin the
    committed-style budget at half the measured cost (equivalently: the
    program doubled) and the gate fires."""
    res, _ = small_audit
    rep = res.reports["sim.pbft_tick"]
    pins = {"sim.pbft_tick": {"flops": rep.cost["flops"] / 2.0,
                              "bytes": rep.cost["bytes"]}}
    fat = _result({"sim.pbft_tick": rep})
    audit.apply_budgets(fat, pins, tolerance=0.25)
    assert [f.rule for f in fat.findings] == ["budget-regression"]


# ----------------------------------------------------------- baseline file

def test_split_by_baseline_count_semantics():
    f = audit.GraphFinding(rule="slow-lowering-confirmed", program="p",
                           detail="scatter-add", message="m", count=3)
    entries = {f.key(): {"count": 3, "justification": "j"}}
    new, n_base, stale = audit.split_by_baseline([f], entries)
    assert new == [] and n_base == 1 and stale == []

    # the program GAINED scatters past its grandfathered count: stays new
    grown = audit.GraphFinding(rule="slow-lowering-confirmed", program="p",
                               detail="scatter-add", message="m", count=5)
    new, n_base, _ = audit.split_by_baseline([grown], entries)
    assert len(new) == 1 and n_base == 0

    # unused entry is stale
    new, _, stale = audit.split_by_baseline([], entries)
    assert stale == [f.key()]


def test_write_baseline_roundtrip_preserves_justifications(tmp_path):
    path = str(tmp_path / "GRAPH_BASELINE.json")
    rep = _report(name="p")
    res = _result({"p": rep})
    res.findings = [audit.GraphFinding(
        rule="slow-lowering-confirmed", program="p", detail="scatter-add",
        message="m", count=2,
    )]
    audit.write_baseline(path, res)
    doc = audit.load_baseline(path)
    assert doc["budgets"] == {"p": {"flops": 1000.0, "bytes": 5000.0}}
    key = ("slow-lowering-confirmed", "p", "scatter-add")
    assert doc["entries"][key]["count"] == 2

    # hand-edit the justification; a rewrite must keep it
    with open(path) as fh:
        raw = json.load(fh)
    raw["entries"][0]["justification"] = "measured OK in PR N"
    with open(path, "w") as fh:
        json.dump(raw, fh)
    audit.write_baseline(path, res, old=audit.load_baseline(path))
    doc = audit.load_baseline(path)
    assert doc["entries"][key]["justification"] == "measured OK in PR N"


def test_prune_baseline_drops_retired_budgets_and_fixed_entries(tmp_path):
    """--prune-baseline hygiene: budgets for programs no longer in the
    catalog drop, finding entries shrink to what the audit still produces
    (fixed entries drop), live budget values and justifications survive
    UNTOUCHED — pruning never re-pins."""
    path = str(tmp_path / "GRAPH_BASELINE.json")
    live_key = ("slow-lowering-confirmed", "live", "scatter-add")
    old = {
        "budgets": {
            "live": {"flops": 123.0, "bytes": 456.0},     # kept verbatim
            "retired": {"flops": 1.0, "bytes": 2.0},      # program gone
        },
        "entries": {
            live_key: {"count": 3, "justification": "measured OK"},
            ("slow-lowering-confirmed", "retired", "scatter"):
                {"count": 2, "justification": "stale"},
        },
        "tolerance": 0.25,
    }
    res = _result({"live": _report(name="live", flops=999.0)})
    # the audit still produces only ONE of the entry's three findings
    res.findings = [audit.GraphFinding(
        rule="slow-lowering-confirmed", program="live", detail="scatter-add",
        message="m", count=1,
    )]
    info = audit.prune_baseline(path, res, old)
    assert info["dropped_budgets"] == ["retired"]
    assert info["dropped_entries"] == [
        ("slow-lowering-confirmed", "retired", "scatter")
    ]
    assert info["shrunk_entries"] == [live_key]
    doc = audit.load_baseline(path)
    # live budget kept at its OLD pin, not the measured 999
    assert doc["budgets"] == {"live": {"flops": 123.0, "bytes": 456.0}}
    assert doc["entries"] == {
        live_key: {"count": 1, "justification": "measured OK"}
    }


def test_prune_baseline_cli_requires_full_run_and_baseline(tmp_path):
    """The CLI guards: --prune-baseline refuses subset runs and a missing
    baseline file (exit 2) rather than silently rewriting the wrong
    thing."""
    proc = subprocess.run(
        [sys.executable, "-m", "blockchain_simulator_tpu.lint.graph",
         "--prune-baseline", "--only", "sim.pbft_tick"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 2
    assert "full catalog run" in proc.stderr
    proc = subprocess.run(
        [sys.executable, "-m", "blockchain_simulator_tpu.lint.graph",
         "--prune-baseline", "--baseline", str(tmp_path / "missing.json")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 2
    assert "existing baseline" in proc.stderr


def test_committed_baseline_pins_every_budgeted_program():
    doc = audit.load_baseline(audit.default_baseline_path())
    budgeted = {s.program for s in prog_mod.build_catalog() if s.budget}
    assert budgeted == set(doc["budgets"])
    for name, pin in doc["budgets"].items():
        assert pin["flops"] > 0 and pin["bytes"] > 0, name
        # the memory satellite: the MEMORY_PINNED representatives carry
        # compiled memory axes (temp may legitimately be 0 for tiny
        # programs); the rest stay trace-only (compiles cost minutes)
        if name in prog_mod.MEMORY_PINNED:
            assert pin["argument_bytes"] > 0, name
            assert pin["temp_bytes"] >= 0, name
    for entry in doc["entries"].values():
        assert entry["justification"] and \
            not entry["justification"].startswith("TODO")


# ------------------------------------------------- real programs (tier-1)

@pytest.fixture(scope="module")
def small_audit():
    """One audit of three tiny real programs (sim.pbft_tick + the pbft
    dynamic-fault divergence twins), shared module-wide: the cheap tier-1
    stand-in for the slow whole-repo sweep."""
    keep = {"sim.pbft_tick", "sweep_dynf.pbft", "sweep_dynf.pbft_b2"}
    specs = [s for s in prog_mod.build_catalog() if s.program in keep]
    assert len(specs) == 3
    res = audit.run_audit(specs, factories={})
    return res, specs


def test_small_audit_traces_clean_vs_committed_baseline(small_audit):
    res, _ = small_audit
    assert res.errors == []
    assert set(res.reports) == {
        "sim.pbft_tick", "sweep_dynf.pbft", "sweep_dynf.pbft_b2"
    }
    doc = audit.load_baseline(audit.default_baseline_path())
    audit.apply_budgets(res, doc["budgets"], doc["tolerance"])
    new, _, _ = audit.split_by_baseline(res.findings, doc["entries"])
    assert new == [], [f.message for f in new]


def test_dynf_twins_share_one_jaxpr(small_audit):
    """The registry-key contract on the real sweep substrate: fault configs
    differing only in counts canonicalize onto ONE traced program."""
    res, _ = small_audit
    assert (res.reports["sweep_dynf.pbft"].fingerprint
            == res.reports["sweep_dynf.pbft_b2"].fingerprint)


def test_audit_is_deterministic_across_runs(small_audit):
    """Budget bit-stability: re-tracing yields identical fingerprints and
    identical (not merely close) cost records."""
    res, specs = small_audit
    res2 = audit.run_audit(
        [s for s in specs if s.program == "sim.pbft_tick"], factories={}
    )
    a = res.reports["sim.pbft_tick"]
    b = res2.reports["sim.pbft_tick"]
    assert a.fingerprint == b.fingerprint
    assert a.cost == b.cost


# ------------------------------------------------------ whole-repo (slow)

@pytest.mark.slow
def test_whole_repo_sweep_every_factory_auditable():
    """The acceptance gate: every registered factory traces, zero
    non-baselined findings, budgets verified — exactly what
    `python -m blockchain_simulator_tpu.lint.graph` gates in CI."""
    proc = subprocess.run(
        [sys.executable, "-m", "blockchain_simulator_tpu.lint.graph",
         "--format", "json"],
        capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["errors"] == []
    assert doc["new_findings"] == []
    # every discovered factory has at least one traced program
    traced_factories = {r["factory"] for r in doc["programs"].values()}
    assert set(doc["factories"]) <= traced_factories


def test_write_baseline_aggregates_duplicate_finding_keys(tmp_path):
    """Two findings with one (rule, program, detail) key must collapse into
    ONE summed entry — a written baseline has to pass its own next run."""
    path = str(tmp_path / "GRAPH_BASELINE.json")
    res = _result({"p": _report(name="p")})
    dup = lambda: audit.GraphFinding(  # noqa: E731
        rule="large-jaxpr-constant", program="p",
        detail="(300, 300):float32", message="m", count=1,
    )
    res.findings = [dup(), dup()]
    audit.write_baseline(path, res)
    doc = audit.load_baseline(path)
    key = ("large-jaxpr-constant", "p", "(300, 300):float32")
    assert doc["entries"][key]["count"] == 2
    new, _, _ = audit.split_by_baseline([dup(), dup()], doc["entries"])
    assert new == []


def test_write_baseline_subset_preserves_out_of_scope_pins(tmp_path):
    """A --only subset rewrite must not wipe the other programs' budgets or
    entries (the jaxlint write_baseline(linted_paths=...) contract)."""
    path = str(tmp_path / "GRAPH_BASELINE.json")
    full = _result({"p": _report(name="p"), "q": _report(name="q")})
    full.findings = [audit.GraphFinding(
        rule="slow-lowering-confirmed", program="q", detail="scatter-add",
        message="m",
    )]
    audit.write_baseline(path, full)
    old = audit.load_baseline(path)

    # re-measure ONLY p (cost changed); q's pin + entry must survive
    subset = _result({"p": _report(name="p", flops=1234.0)})
    audit.write_baseline(path, subset, old=old, full=False)
    doc = audit.load_baseline(path)
    assert doc["budgets"]["p"]["flops"] == 1234.0
    assert doc["budgets"]["q"] == {"flops": 1000.0, "bytes": 5000.0}
    assert ("slow-lowering-confirmed", "q", "scatter-add") in doc["entries"]

    # a FULL rewrite with q truly gone does drop it
    audit.write_baseline(path, subset, old=audit.load_baseline(path))
    doc = audit.load_baseline(path)
    assert set(doc["budgets"]) == {"p"} and doc["entries"] == {}
