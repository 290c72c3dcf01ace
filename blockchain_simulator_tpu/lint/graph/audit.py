"""jaxgraph audit engine: trace the catalog, run IR rules, gate budgets.

Mechanics deliberately mirror ``lint/engine.py``: findings are grandfathered
in a committed baseline (``GRAPH_BASELINE.json``) keyed on stable identities
with per-entry justifications; ``--write-baseline`` regenerates the file
preserving them; the CLI exits 1 on any non-baselined finding and 2 on
infrastructure errors (a factory that stopped tracing IS an infrastructure
error — the acceptance contract is that every registered executable stays
auditable).

The baseline file carries a second section jaxlint has no analog for:
``budgets`` pins each program's analytical FLOP/byte cost
(``Lowered.cost_analysis()``, bit-stable run to run).  The gate fires when a
program's measured cost grows beyond ``tolerance`` over its pin — a static
perf regression caught in CI without running a bench.  Shrinking beyond
tolerance is reported as a stale budget (refresh with ``--write-baseline``),
never gated: getting faster is the goal, same as the bench_compare
``_compile_s`` carve-out.
"""

from __future__ import annotations

import dataclasses
import json
import os

from blockchain_simulator_tpu.lint import baseline as baseline_mod
from blockchain_simulator_tpu.lint.graph import ir
from blockchain_simulator_tpu.lint.graph import programs as prog_mod

BASELINE_NAME = "GRAPH_BASELINE.json"
REPO_ROOT = prog_mod.REPO_ROOT

# Constants below this many bytes are normal trace residue (fault masks,
# iota seeds); at or above it they bloat the executable (and its compile-
# cache entry) and — when derived from per-point values a sweep varies —
# defeat the one-executable-per-fault-structure contract.
LARGE_CONST_BYTES = 1 << 16  # 64 KiB

# Budget growth beyond this fraction of the pinned value fails the gate.
DEFAULT_TOLERANCE = 0.25


@dataclasses.dataclass
class GraphFinding:
    """One IR-contract violation for one program (or factory/group)."""

    rule: str
    program: str   # program name, factory name, or divergence group
    detail: str    # stable identity within (rule, program)
    message: str
    count: int = 1

    def key(self) -> tuple[str, str, str]:
        return (self.rule, self.program, self.detail)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


RULE_SUMMARIES = {
    "host-callback-in-program": (
        "pure_callback/io_callback/debug/infeed primitives traced into a "
        "sim program (breaks serialized executables + vmap composition)"
    ),
    "f64-in-program": (
        "64-bit dtype aval in the trace (x64 leak: doubles memory traffic, "
        "breaks 32-bit engine-boundary contracts)"
    ),
    "weak-type-boundary": (
        "weak-typed program input/output (re-specializes per caller "
        "context: one registry key, many executables)"
    ),
    "large-jaxpr-constant": (
        f"constant >= {LARGE_CONST_BYTES} bytes baked into the jaxpr "
        "(bloats the executable; should be an operand)"
    ),
    "slow-lowering-confirmed": (
        "scatter/sort/cum* primitive confirmed in the traced IR (the "
        "ground-truth replacement for the AST slow-cpu-lowering allowlist)"
    ),
    "registry-key-divergence": (
        "one registry key traced to multiple distinct jaxprs across sweep "
        "points (silent recompile leak)"
    ),
    "unaudited-factory": (
        "cached_factory registration with no covering audit program "
        "(grow lint/graph/programs.py with the factory)"
    ),
    "budget-missing": (
        "program has no pinned FLOP/byte/memory budget in "
        "GRAPH_BASELINE.json (pin with --write-baseline)"
    ),
    "budget-regression": (
        "program's analytical FLOP/byte cost or compiled memory footprint "
        "(peak temp + argument bytes) grew beyond tolerance over its "
        "pinned budget (static perf regression)"
    ),
}

# The pinned budget axes: flops/bytes come from the analytical cost model
# (Lowered.cost_analysis), temp_bytes/argument_bytes from the compiled
# executable's memory_analysis() — peak XLA temp allocation and total
# argument bytes per device.  Memory axes turn the RSS stories (7.4 GB @1M
# nodes, 12.4 GB @4M — ROADMAP item 3) into pinned numbers instead of lore.
BUDGET_AXES = ("flops", "bytes", "temp_bytes", "argument_bytes")


@dataclasses.dataclass
class ProgramReport:
    """Everything measured about one traced program."""

    program: str
    factory: str
    fingerprint: str
    cost: dict | None            # {"flops", "bytes"} or None
    memory: dict | None          # {"temp_bytes", "argument_bytes"} or None
    prims: dict                  # {primitive: count} (flagged subset)
    n_eqns: int
    const_bytes: int
    divergence_group: str | None
    budget: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AuditResult:
    reports: dict                 # {program: ProgramReport}
    findings: list                # [GraphFinding], pre-baseline
    errors: list                  # ["spec: message"] — exit-2 material
    factories: dict               # discovered {factory: [files]}
    uncovered: list               # factory names with no spec
    stale_budgets: list           # [(program, axis, measured, pinned)]


def _check_program(rep: ProgramReport, closed) -> list[GraphFinding]:
    """The per-program IR rules (everything not needing cross-program or
    baseline context)."""
    findings: list[GraphFinding] = []
    counts = ir.primitive_counts(closed)

    for prim in sorted(ir.HOST_CALLBACK_PRIMS & counts.keys()):
        findings.append(GraphFinding(
            rule="host-callback-in-program", program=rep.program, detail=prim,
            count=counts[prim],
            message=(
                f"host-callback primitive `{prim}` x{counts[prim]} traced "
                f"into `{rep.program}`: the program is no longer a "
                "self-contained executable (serialization and "
                "vmap/shard_map sweeps both regress)"
            ),
        ))

    for dtype, n in sorted(ir.wide_dtypes(closed).items()):
        findings.append(GraphFinding(
            rule="f64-in-program", program=rep.program, detail=dtype, count=n,
            message=(
                f"{n} aval(s) of 64-bit dtype `{dtype}` in `{rep.program}`: "
                "an x64 leak (numpy float64 constant or flipped flag) — the "
                "repo's engines are 32-bit end to end"
            ),
        ))

    for desc in ir.boundary_weak_types(closed):
        findings.append(GraphFinding(
            rule="weak-type-boundary", program=rep.program, detail=desc,
            message=(
                f"weak-typed boundary aval {desc} on `{rep.program}`: weak "
                "types re-specialize on caller literal context, so one "
                "registry key can silently compile multiple executables"
            ),
        ))

    for shape, dtype, nbytes in ir.const_leaves(closed):
        if nbytes >= LARGE_CONST_BYTES:
            findings.append(GraphFinding(
                rule="large-jaxpr-constant", program=rep.program,
                detail=f"{shape}:{dtype}",
                message=(
                    f"constant {shape}:{dtype} ({nbytes} bytes) baked into "
                    f"`{rep.program}`'s jaxpr: serialized cache entries "
                    "carry it verbatim and sweep points that vary it split "
                    "the executable; pass it as an operand"
                ),
            ))

    for prim in sorted(ir.SLOW_PRIMS & counts.keys()):
        findings.append(GraphFinding(
            rule="slow-lowering-confirmed", program=rep.program, detail=prim,
            count=counts[prim],
            message=(
                f"confirmed-slow lowering `{prim}` x{counts[prim]} in "
                f"`{rep.program}` (XLA:CPU serializes scatter/sort/cum* — "
                "KNOWN_ISSUES #0b); measured-acceptable sites belong in "
                "GRAPH_BASELINE.json with their measurement"
            ),
        ))
    return findings


def run_audit(specs=None, factories=None) -> AuditResult:
    """Trace every spec and run every rule that needs no baseline.

    Budget findings are attached separately (:func:`apply_budgets`) because
    they compare against the baseline file, which callers may be rewriting.
    """
    if specs is None:
        specs = prog_mod.build_catalog()
    if factories is None:
        factories = prog_mod.discover_factories()

    reports: dict[str, ProgramReport] = {}
    findings: list[GraphFinding] = []
    errors: list[str] = []
    closed_by_program: dict[str, object] = {}

    for spec in specs:
        try:
            fn, example_args = spec.build()
            closed, lowered = ir.trace_program(fn, example_args)
        except Exception as e:  # exit-2 material: factories must stay traceable
            errors.append(f"{spec.program}: {type(e).__name__}: {e}")
            continue
        counts = ir.primitive_counts(closed)
        flagged = {
            p: c for p, c in counts.items()
            if p in ir.SLOW_PRIMS or p in ir.HOST_CALLBACK_PRIMS
        }
        rep = ProgramReport(
            program=spec.program,
            factory=spec.factory,
            fingerprint=ir.fingerprint(closed),
            cost=ir.cost_summary(lowered),
            # compiling is the expensive step — only the MEMORY_PINNED
            # subset pays it (programs.py: the RSS-story representatives)
            memory=ir.memory_summary(lowered)
            if (spec.budget and getattr(spec, "memory", False)) else None,
            prims=flagged,
            n_eqns=sum(counts.values()),
            const_bytes=sum(b for _, _, b in ir.const_leaves(closed)),
            divergence_group=spec.divergence_group,
            budget=spec.budget,
        )
        reports[spec.program] = rep
        closed_by_program[spec.program] = closed
        findings.extend(_check_program(rep, closed))

    # registry-key divergence: specs sharing a group must share a jaxpr
    groups: dict[str, list[ProgramReport]] = {}
    for rep in reports.values():
        if rep.divergence_group:
            groups.setdefault(rep.divergence_group, []).append(rep)
    for group, reps in sorted(groups.items()):
        prints = sorted({r.fingerprint for r in reps})
        if len(prints) > 1:
            members = ", ".join(
                f"{r.program}={r.fingerprint[:8]}" for r in reps
            )
            findings.append(GraphFinding(
                rule="registry-key-divergence", program=group,
                detail="+".join(p[:8] for p in prints),
                message=(
                    f"registry key group `{group}` traced to "
                    f"{len(prints)} distinct jaxprs ({members}): sweep "
                    "points that should share one executable will silently "
                    "recompile per point (canonical_fault_cfg regression)"
                ),
            ))

    # completeness: every discovered factory registration is covered
    covered = {s.factory for s in specs}
    uncovered = sorted(set(factories) - covered)
    for name in uncovered:
        findings.append(GraphFinding(
            rule="unaudited-factory", program=name,
            detail=(factories[name] or ["?"])[0],
            message=(
                f"cached_factory(\"{name}\") registered in "
                f"{', '.join(factories[name])} has no audit program — add a "
                "ProgramSpec in lint/graph/programs.py so its IR stays "
                "under contract"
            ),
        ))

    return AuditResult(
        reports=reports, findings=findings, errors=errors,
        factories=factories, uncovered=uncovered, stale_budgets=[],
    )


# ---------------------------------------------------------------- baseline

def load_baseline(path: str) -> dict:
    """GRAPH_BASELINE.json -> {"budgets": {...}, "entries": {key: entry},
    "tolerance": float}."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return {
        "budgets": doc.get("budgets", {}),
        "entries": baseline_mod.load_entries(doc),
        "tolerance": float(doc.get("tolerance", DEFAULT_TOLERANCE)),
    }


def _measured_budget(rep: ProgramReport) -> dict:
    """The measurable budget axes of one report, merged (cost axes +
    compiled memory axes; absent surfaces simply omit their keys)."""
    merged = dict(rep.cost or {})
    if rep.memory:
        merged.update(rep.memory)
    return merged


def apply_budgets(result: AuditResult, budgets: dict, tolerance: float) -> None:
    """Attach budget-missing / budget-regression findings (and stale-budget
    notes) to ``result`` by comparing measured costs against the pins."""
    for name in sorted(result.reports):
        rep = result.reports[name]
        if not rep.budget:
            continue
        if rep.cost is None:
            result.errors.append(
                f"{name}: backend returned no cost analysis "
                "(budget gate needs Lowered.cost_analysis())"
            )
            continue
        measured_all = _measured_budget(rep)
        pin = budgets.get(name)
        if pin is None:
            result.findings.append(GraphFinding(
                rule="budget-missing", program=name, detail="budget",
                message=(
                    f"`{name}` has no pinned FLOP/byte/memory budget "
                    f"(measured flops={rep.cost['flops']:.0f} "
                    f"bytes={rep.cost['bytes']:.0f}); pin with "
                    "--write-baseline"
                ),
            ))
            continue
        for axis in BUDGET_AXES:
            measured, pinned = measured_all.get(axis), float(
                pin.get(axis, 0.0)
            )
            if pinned <= 0:
                continue
            if measured is None:
                result.errors.append(
                    f"{name}: budget axis {axis} is pinned but the backend "
                    "measured nothing for it (compiled memory_analysis "
                    "unavailable?)"
                )
                continue
            if measured > pinned * (1.0 + tolerance):
                result.findings.append(GraphFinding(
                    rule="budget-regression", program=name, detail=axis,
                    message=(
                        f"`{name}` {axis} grew {measured / pinned:.2f}x over "
                        f"its pin ({measured:.0f} vs {pinned:.0f}, tolerance "
                        f"+{tolerance:.0%}): a static perf regression — "
                        "shrink the program or re-pin with --write-baseline "
                        "and a justification in the PR"
                    ),
                ))
            elif measured < pinned * (1.0 - tolerance):
                result.stale_budgets.append((name, axis, measured, pinned))


def split_by_baseline(
    findings: list[GraphFinding], entries: dict
) -> tuple[list[GraphFinding], int, list[tuple]]:
    """(new findings, n_baselined, stale entry keys) — the shared count
    semantics (lint/baseline.py): an entry absorbs findings up to its
    count; a finding whose count GREW past the entry's stays new (a
    program gaining scatters is a change, not grandfather)."""
    return baseline_mod.split_by_baseline(findings, entries)


def write_baseline(
    path: str, result: AuditResult, old: dict | None = None,
    tolerance: float | None = None, full: bool = True,
) -> dict:
    """Write measured budgets + current findings as the new baseline,
    preserving old justifications (the lint/engine.py contract).  Budget
    findings are represented by the refreshed budgets, not entries.

    ``full=False`` (a ``--only`` subset run): old budgets and entries for
    programs OUTSIDE this run's reports are preserved wholesale, so
    re-baselining one program never silently drops the pins (and
    hand-written justifications) of the rest — the same subset contract as
    jaxlint's ``write_baseline(linted_paths=...)``."""
    old = old or {"budgets": {}, "entries": {}, "tolerance": DEFAULT_TOLERANCE}
    budgets = {
        name: _measured_budget(rep)
        for name, rep in sorted(result.reports.items())
        if rep.budget and rep.cost is not None
    }
    counts = baseline_mod.collapse_counts(
        result.findings, skip_rules=("budget-missing", "budget-regression")
    )
    if not full:
        audited = set(result.reports)
        for name, pin in old["budgets"].items():
            if name not in audited:
                budgets[name] = pin
        for key, entry in old["entries"].items():
            if key[1] not in audited and key not in counts:
                counts[key] = entry["count"]
        budgets = dict(sorted(budgets.items()))
    doc = {
        "jaxgraph_baseline": 1,
        "comment": (
            "IR-level grandfathered findings + per-program budgets: "
            "analytical FLOP/byte cost (Lowered.cost_analysis) and "
            "compiled memory footprint (memory_analysis peak temp + "
            "argument bytes), all bit-stable.  Regenerate with `python -m "
            "blockchain_simulator_tpu.lint.graph --write-baseline` "
            "(justifications preserved); new programs must come in clean "
            "and budgeted."
        ),
        "tolerance": tolerance if tolerance is not None
        else old.get("tolerance", DEFAULT_TOLERANCE),
        "budgets": budgets,
        "entries": baseline_mod.merge_entries(counts, old["entries"]),
    }
    baseline_mod.dump_doc(path, doc)
    return doc


def prune_baseline(path: str, result: AuditResult, old: dict) -> dict:
    """Baseline hygiene (``--prune-baseline``, the jaxlint analog): rewrite
    the baseline keeping only what the current catalog still justifies —
    finding entries shrink to the count actually consumed by ``result``'s
    findings (fixed entries drop entirely) and budgets whose program is no
    longer in the catalog drop (retired programs must not linger as stale
    pins).  Live budget VALUES and all justifications are preserved
    untouched: pruning never re-pins — that is ``--write-baseline``'s job.

    Returns ``{"dropped_entries": [...], "shrunk_entries": [...],
    "dropped_budgets": [...]}``.  ``result`` must come from a FULL audit
    run (a subset run cannot distinguish retired from out-of-scope)."""
    consumed = baseline_mod.collapse_counts(
        result.findings, skip_rules=("budget-missing", "budget-regression")
    )
    audited = set(result.reports)
    dropped_budgets = sorted(set(old["budgets"]) - audited)
    budgets = {name: pin for name, pin in sorted(old["budgets"].items())
               if name in audited}
    entries, dropped_entries, shrunk_entries = baseline_mod.prune_entries(
        old["entries"], consumed
    )
    doc = {
        "jaxgraph_baseline": 1,
        "comment": (
            "IR-level grandfathered findings + per-program budgets: "
            "analytical FLOP/byte cost (Lowered.cost_analysis) and "
            "compiled memory footprint (memory_analysis peak temp + "
            "argument bytes), all bit-stable.  Regenerate with `python -m "
            "blockchain_simulator_tpu.lint.graph --write-baseline` "
            "(justifications preserved); new programs must come in clean "
            "and budgeted."
        ),
        "tolerance": old.get("tolerance", DEFAULT_TOLERANCE),
        "budgets": budgets,
        "entries": entries,
    }
    baseline_mod.dump_doc(path, doc)
    return {
        "dropped_entries": dropped_entries,
        "shrunk_entries": shrunk_entries,
        "dropped_budgets": dropped_budgets,
    }


def default_baseline_path() -> str:
    return os.path.join(REPO_ROOT, BASELINE_NAME)
