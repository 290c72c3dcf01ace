"""CLI: ``python -m blockchain_simulator_tpu.lint.comms``.

Flags mirror the jaxgraph CLI exactly (``--format``, ``--baseline``,
``--no-baseline``, ``--write-baseline``, ``--prune-baseline``,
``--list-rules``, ``--list-programs``, ``--only``, ``--tolerance``).
Exit codes: 0 = clean vs baseline, 1 = new findings, 2 = a mesh program
failed to compile / bad baseline / usage error.

The audit compiles on the CPU backend with 8 forced host devices: the
committed contract is the CPU-lowered SPMD HLO (deterministic,
CI-runnable, claims no chip), not measured interconnect time.  Override with ``$BLOCKSIM_GRAPH_PLATFORM`` (shared with the graph
audit — same backend, one stage later).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from blockchain_simulator_tpu.lint.graph.__main__ import _force_platform


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="blockchain_simulator_tpu.lint.comms",
        description="shardlint: post-SPMD communication audit of every "
                    "mesh-capable factory (collective extraction + "
                    "per-mesh comms budget gate)",
    )
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=None,
                   help="baseline file (default: COMMS_BASELINE.json at the "
                        "repo root when present)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding and skip the budget gate")
    p.add_argument("--write-baseline", action="store_true",
                   help="write current findings + measured comms budgets as "
                        "the new baseline (preserves justifications) and "
                        "exit 0")
    p.add_argument("--prune-baseline", action="store_true",
                   help="baseline hygiene: drop finding entries the audit "
                        "no longer produces and budgets for programs no "
                        "longer in the catalog; never re-pins live budgets "
                        "or touches justifications")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--list-programs", action="store_true")
    p.add_argument("--only", nargs="*", default=None, metavar="PROGRAM",
                   help="audit only these programs (disables the "
                        "completeness rule and runs.jsonl recording)")
    p.add_argument("--tolerance", type=float, default=None,
                   help="budget growth fraction that fails the gate "
                        "(default: the baseline file's, else 0.25); growth "
                        "from a zero pin always fails")
    args = p.parse_args(argv)

    from blockchain_simulator_tpu.lint.comms import audit as audit_mod
    from blockchain_simulator_tpu.lint.comms import programs as prog_mod

    if args.list_rules:
        for rid, summary in sorted(audit_mod.RULE_SUMMARIES.items()):
            print(f"{rid:<28} {summary}")
        return 0

    specs = prog_mod.build_catalog()
    if args.list_programs:
        for s in specs:
            print(f"{s.program:<36} factory={s.factory}")
        return 0

    subset = args.only is not None
    if subset:
        known = {s.program for s in specs}
        unknown = [x for x in args.only if x not in known]
        if unknown:
            print(f"shardlint: unknown program(s): {', '.join(unknown)}",
                  file=sys.stderr)
            return 2
        specs = [s for s in specs if s.program in args.only]

    if args.prune_baseline:
        # guard BEFORE the (minutes-long) audit — same as jaxgraph
        if subset:
            print("shardlint: --prune-baseline needs a full catalog run "
                  "(drop --only)", file=sys.stderr)
            return 2
        prune_path = args.baseline or audit_mod.default_baseline_path()
        if args.no_baseline or not os.path.exists(prune_path):
            print(f"shardlint: --prune-baseline needs an existing baseline "
                  f"({prune_path})", file=sys.stderr)
            return 2

    _force_platform()

    from blockchain_simulator_tpu.lint.graph.programs import (
        discover_mesh_factories,
    )

    factories = discover_mesh_factories()
    if subset:
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
            print(f"shardlint: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
    tolerance = args.tolerance if args.tolerance is not None \
        else baseline["tolerance"]

    if args.write_baseline:
        if result.errors:
            for e in result.errors:
                print(f"shardlint: {e}", file=sys.stderr)
            return 2
        old = None
        if os.path.exists(baseline_path):
            try:
                old = audit_mod.load_baseline(baseline_path)
            except (json.JSONDecodeError, KeyError, TypeError, ValueError):
                old = None  # corrupt: regenerate from scratch
        doc = audit_mod.write_baseline(baseline_path, result, old,
                                       tolerance=args.tolerance,
                                       full=not subset)
        print(f"shardlint: wrote {len(doc['budgets'])} budget(s) and "
              f"{len(doc['entries'])} finding entr(ies) to "
              f"{baseline_path}")
        return 0

    if args.prune_baseline:
        if result.errors:
            for e in result.errors:
                print(f"shardlint: {e}", file=sys.stderr)
            return 2
        info = audit_mod.prune_baseline(baseline_path, result, baseline)
        for r, pr, d in info["dropped_entries"]:
            print(f"shardlint: pruned fixed entry {r} @ {pr}: {d!r}")
        for r, pr, d in info["shrunk_entries"]:
            print(f"shardlint: shrank overcounted entry {r} @ {pr}: {d!r}")
        for pr in info["dropped_budgets"]:
            print(f"shardlint: dropped retired budget {pr}")
        print(f"shardlint: pruned {len(info['dropped_entries'])} entr(ies), "
              f"shrank {len(info['shrunk_entries'])}, dropped "
              f"{len(info['dropped_budgets'])} retired budget(s) in "
              f"{baseline_path}")
        return 0

    if not args.no_baseline:
        audit_mod.apply_budgets(result, baseline["budgets"], tolerance)
    new, n_baselined, stale = audit_mod.split_by_baseline(
        result.findings, {} if args.no_baseline else baseline["entries"]
    )
    if subset:
        stale = [k for k in stale if k[1] in result.reports]

    if args.format == "json":
        print(json.dumps({
            "shardlint_schema": 1,
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
            mesh = "x".join(f"{k}={v}" for k, v in sorted(r.mesh.items()))
            t = r.totals
            print(f"{name:<36} [{r.factory}/{r.arm or '?'} {mesh}] "
                  f"colls={t['collectives']} "
                  f"({t['loop_collectives']} in loop) "
                  f"kb={t['bytes'] / 1e3:.3f} "
                  f"loop_kb={t['loop_bytes'] / 1e3:.3f}")
        for f in new:
            print(f"{f.program}: {f.rule}: {f.message}")
        for r, pr, d in stale:
            print(f"shardlint: stale baseline entry {r} @ {pr}: {d!r} "
                  "(fixed? regenerate with --write-baseline)",
                  file=sys.stderr)
        for pr, ax, m, pin in result.stale_budgets:
            print(f"shardlint: stale budget {pr}.{ax}: measured {m:.0f} "
                  f"well under pin {pin:.0f} (improvement — re-pin with "
                  "--write-baseline)", file=sys.stderr)
        for e in result.errors:
            print(f"shardlint: ERROR {e}", file=sys.stderr)
        print(f"shardlint: {len(result.reports)} programs, "
              f"{len(result.factories)} mesh factories, {len(new)} new "
              f"finding(s), {n_baselined} baselined, "
              f"{len(result.errors)} error(s)")

    # gate-equivalent runs leave the trail in runs.jsonl next to jaxgraph's
    gate_equivalent = (
        not subset and not args.no_baseline and args.baseline is None
    )
    if gate_equivalent:
        from blockchain_simulator_tpu.utils import obs

        obs.record_run({
            "metric": "comms_new_findings",
            "value": len(new),
            "unit": "findings",
            "programs": len(result.reports),
            "baselined": n_baselined,
            "errors": len(result.errors),
        })
        for name in sorted(result.reports):
            r = result.reports[name]
            safe = (name.replace(".", "_").replace("-", "_")
                    .replace("@", "_"))
            obs.record_run({
                "metric": f"comms_{safe}_bytes",
                "value": r.totals["bytes"],
                "unit": "bytes",
                "loop_bytes": r.totals["loop_bytes"],
                "collectives": r.totals["collectives"],
            })

    if result.errors:
        return 2
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
