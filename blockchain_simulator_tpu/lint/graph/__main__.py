"""CLI: ``python -m blockchain_simulator_tpu.lint.graph``.

Flags mirror jaxlint's where the concept is shared (``--format``,
``--baseline``, ``--no-baseline``, ``--write-baseline``,
``--prune-baseline``, ``--list-rules``) plus graph-only ones
(``--list-programs``, ``--only``, ``--tolerance``).
Exit codes: 0 = clean vs baseline, 1 = new findings, 2 = a program failed
to trace / bad baseline / usage error.

The audit runs on the CPU backend by default: a lint gate must never claim
a chip, and the IR contracts it checks are backend-independent.  Override
with ``$BLOCKSIM_GRAPH_PLATFORM``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _force_platform() -> None:
    """Pin the audit backend BEFORE any backend init: env for the
    host-device-count flag, config because the caller's JAX_PLATFORMS (or
    an earlier jax import) must not decide where a lint gate runs."""
    platform = os.environ.get("BLOCKSIM_GRAPH_PLATFORM", "cpu")
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", platform)
    # the host-device-count flag is read at backend INIT, not jax import
    # (tests call main() in-process, jax long imported), so gate on backend
    # state rather than sys.modules
    backend_up = False
    if "jax" in sys.modules:
        try:
            from jax._src import xla_bridge

            backend_up = bool(getattr(xla_bridge, "_backends", None))
        except Exception:
            pass
    flags = os.environ.get("XLA_FLAGS", "")
    if not backend_up and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", platform)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="blockchain_simulator_tpu.lint.graph",
        description="jaxgraph: IR-level audit of every registered "
                    "executable factory (jaxpr rules + FLOP/byte budget "
                    "gate)",
    )
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=None,
                   help="baseline file (default: GRAPH_BASELINE.json at the "
                        "repo root when present)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding and skip the budget gate")
    p.add_argument("--write-baseline", action="store_true",
                   help="write current findings + measured budgets as the "
                        "new baseline (preserves justifications) and exit 0")
    p.add_argument("--prune-baseline", action="store_true",
                   help="baseline hygiene: drop finding entries the audit "
                        "no longer produces and budgets for programs no "
                        "longer in the catalog (retired factories); never "
                        "re-pins live budgets or touches justifications")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--list-programs", action="store_true")
    p.add_argument("--only", nargs="*", default=None, metavar="PROGRAM",
                   help="audit only these programs (disables the "
                        "completeness rule and runs.jsonl recording)")
    p.add_argument("--tolerance", type=float, default=None,
                   help="budget growth fraction that fails the gate "
                        "(default: the baseline file's, else 0.25)")
    args = p.parse_args(argv)

    from blockchain_simulator_tpu.lint.graph import audit as audit_mod
    from blockchain_simulator_tpu.lint.graph import programs as prog_mod

    if args.list_rules:
        for rid, summary in sorted(audit_mod.RULE_SUMMARIES.items()):
            print(f"{rid:<28} {summary}")
        return 0

    specs = prog_mod.build_catalog()
    if args.list_programs:
        for s in specs:
            extra = f"  [group {s.divergence_group}]" if s.divergence_group \
                else ""
            print(f"{s.program:<28} factory={s.factory}{extra}")
        return 0

    subset = args.only is not None
    if subset:
        known = {s.program for s in specs}
        unknown = [x for x in args.only if x not in known]
        if unknown:
            print(f"jaxgraph: unknown program(s): {', '.join(unknown)}",
                  file=sys.stderr)
            return 2
        specs = [s for s in specs if s.program in args.only]

    if args.prune_baseline:
        # guard BEFORE the (minutes-long) audit: a subset run cannot
        # distinguish retired from out-of-scope, and pruning needs a file
        if subset:
            print("jaxgraph: --prune-baseline needs a full catalog run "
                  "(drop --only)", file=sys.stderr)
            return 2
        prune_path = args.baseline or audit_mod.default_baseline_path()
        if args.no_baseline or not os.path.exists(prune_path):
            print(f"jaxgraph: --prune-baseline needs an existing baseline "
                  f"({prune_path})", file=sys.stderr)
            return 2

    _force_platform()

    factories = prog_mod.discover_factories()
    if subset:
        # a subset run cannot claim completeness — silence the rule by
        # scoping discovery to the covered factories
        factories = {k: v for k, v in factories.items()
                     if k in {s.factory for s in specs}}
    result = audit_mod.run_audit(specs, factories)

    baseline_path = args.baseline or audit_mod.default_baseline_path()
    baseline = {"budgets": {}, "entries": {},
                "tolerance": audit_mod.DEFAULT_TOLERANCE}
    if not args.no_baseline and os.path.exists(baseline_path):
        try:
            baseline = audit_mod.load_baseline(baseline_path)
        except (json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
            print(f"jaxgraph: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
    tolerance = args.tolerance if args.tolerance is not None \
        else baseline["tolerance"]

    if args.write_baseline:
        # budgets must exist to be written; missing cost is an error either way
        audit_mod.apply_budgets(result, {}, tolerance)
        result.findings = [
            f for f in result.findings if f.rule != "budget-missing"
        ]
        if result.errors:
            for e in result.errors:
                print(f"jaxgraph: {e}", file=sys.stderr)
            return 2
        # load old from disk regardless of --no-baseline: a rewrite must
        # never lose hand-written justifications (jaxlint's write path)
        old = None
        if os.path.exists(baseline_path):
            try:
                old = audit_mod.load_baseline(baseline_path)
            except (json.JSONDecodeError, KeyError, TypeError, ValueError):
                old = None  # corrupt: regenerate from scratch
        doc = audit_mod.write_baseline(baseline_path, result, old,
                                       tolerance=args.tolerance,
                                       full=not subset)
        print(f"jaxgraph: wrote {len(doc['budgets'])} budget(s) and "
              f"{len(doc['entries'])} finding entr(ies) to "
              f"{baseline_path}")
        return 0

    if args.prune_baseline:
        if result.errors:
            for e in result.errors:
                print(f"jaxgraph: {e}", file=sys.stderr)
            return 2
        info = audit_mod.prune_baseline(baseline_path, result, baseline)
        for r, pr, d in info["dropped_entries"]:
            print(f"jaxgraph: pruned fixed entry {r} @ {pr}: {d!r}")
        for r, pr, d in info["shrunk_entries"]:
            print(f"jaxgraph: shrank overcounted entry {r} @ {pr}: {d!r}")
        for pr in info["dropped_budgets"]:
            print(f"jaxgraph: dropped retired budget {pr}")
        print(f"jaxgraph: pruned {len(info['dropped_entries'])} entr(ies), "
              f"shrank {len(info['shrunk_entries'])}, dropped "
              f"{len(info['dropped_budgets'])} retired budget(s) in "
              f"{baseline_path}")
        return 0

    if not args.no_baseline:
        audit_mod.apply_budgets(result, baseline["budgets"], tolerance)
    new, n_baselined, stale = audit_mod.split_by_baseline(
        result.findings, {} if args.no_baseline else baseline["entries"]
    )
    # entries for programs a subset run did not trace are not stale
    if subset:
        stale = [k for k in stale if k[1] in result.reports]

    if args.format == "json":
        print(json.dumps({
            "jaxgraph_schema": 1,
            "programs": {k: r.to_dict() for k, r in
                         sorted(result.reports.items())},
            "new_findings": [f.to_dict() for f in new],
            "baselined": n_baselined,
            "stale_baseline": [
                {"rule": r, "program": pr, "detail": d} for r, pr, d in stale
            ],
            "stale_budgets": [
                {"program": pr, "axis": ax, "measured": m, "pinned": pin}
                for pr, ax, m, pin in result.stale_budgets
            ],
            "errors": result.errors,
            "factories": result.factories,
            "rules": sorted(audit_mod.RULE_SUMMARIES),
        }, indent=1))
    else:
        for name in sorted(result.reports):
            r = result.reports[name]
            cost = (f"gflops={r.cost['flops'] / 1e9:.6f} "
                    f"mbytes={r.cost['bytes'] / 1e6:.3f}"
                    if r.cost else "cost=n/a")
            if r.memory:
                cost += (f" temp_mb={r.memory['temp_bytes'] / 1e6:.3f} "
                         f"arg_mb={r.memory['argument_bytes'] / 1e6:.3f}")
            prims = (" " + ",".join(f"{k}x{v}" for k, v in
                                    sorted(r.prims.items()))
                     if r.prims else "")
            print(f"{name:<28} [{r.factory}] {r.fingerprint[:12]} "
                  f"eqns={r.n_eqns} {cost}{prims}")
        for f in new:
            print(f"{f.program}: {f.rule}: {f.message}")
        for r, pr, d in stale:
            print(f"jaxgraph: stale baseline entry {r} @ {pr}: {d!r} "
                  "(fixed? regenerate with --write-baseline)",
                  file=sys.stderr)
        for pr, ax, m, pin in result.stale_budgets:
            print(f"jaxgraph: stale budget {pr}.{ax}: measured {m:.0f} well "
                  f"under pin {pin:.0f} (improvement — re-pin with "
                  "--write-baseline)", file=sys.stderr)
        for e in result.errors:
            print(f"jaxgraph: ERROR {e}", file=sys.stderr)
        print(f"jaxgraph: {len(result.reports)} programs, "
              f"{len(result.factories)} factories, {len(new)} new "
              f"finding(s), {n_baselined} baselined, "
              f"{len(result.errors)} error(s)")

    # gate-equivalent runs leave the trail in runs.jsonl next to jaxlint's
    # (no-op unless $BLOCKSIM_RUNS_JSONL is set; obs never inits a backend)
    gate_equivalent = (
        not subset and not args.no_baseline and args.baseline is None
    )
    if gate_equivalent:
        from blockchain_simulator_tpu.utils import obs

        obs.record_run({
            "metric": "jaxgraph_new_findings",
            "value": len(new),
            "unit": "findings",
            "programs": len(result.reports),
            "baselined": n_baselined,
            "errors": len(result.errors),
        })
        for name in sorted(result.reports):
            r = result.reports[name]
            if not (r.budget and r.cost):
                continue
            safe = name.replace(".", "_").replace("-", "_")
            obs.record_run({
                "metric": f"graph_{safe}_gflops",
                "value": round(r.cost["flops"] / 1e9, 9),
                "unit": "gflops",
            })
            obs.record_run({
                "metric": f"graph_{safe}_bytes",
                "value": r.cost["bytes"],
                "unit": "bytes",
            })

    if result.errors:
        return 2
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
