"""jaxlint engine: AST walker, suppressions, baseline, CLI.

``python -m blockchain_simulator_tpu.lint [paths...]`` parses every ``.py``
file under the given paths (never importing them — rules police import-time
behavior, so the linter must not trigger it), runs every registered rule
(rules/__init__.py), and reports findings that are neither

- **suppressed** — an inline ``# jaxlint: disable=<rule>[,<rule>...]``
  comment on any line the offending node spans (use for sites whose
  justification belongs next to the code, e.g. obs.py's guarded backend
  read), nor
- **baselined** — grandfathered in ``LINT_BASELINE.json`` at the repo root:
  entries keyed by (rule, path, stripped source line) with a count and a
  one-line justification.  Keying on line TEXT instead of line numbers keeps
  the baseline stable across unrelated edits.  ``--write-baseline``
  regenerates the file, preserving existing justifications.

Exit codes: 0 = clean vs the baseline, 1 = new findings, 2 = a file failed
to parse (or usage error).  When ``$BLOCKSIM_RUNS_JSONL`` is set the run is
recorded through utils/obs.py like every other entrypoint, so the findings
trajectory charts in ``tools/bench_compare.py`` next to the perf history.
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import os
import re
import sys
import tokenize
from collections import Counter

from blockchain_simulator_tpu.lint import common
from blockchain_simulator_tpu.lint.rules import ALL_RULES, RULES_BY_ID

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE_NAME = "LINT_BASELINE.json"

SUPPRESS_RE = re.compile(r"#\s*jaxlint:\s*disable=([A-Za-z0-9_, \-]+)")


def rel_path(path: str, root: str = REPO_ROOT) -> str:
    """Repo-relative posix path (the identity used in findings, baseline
    entries and suppressions); absolute if outside the repo."""
    ap = os.path.abspath(path)
    try:
        rp = os.path.relpath(ap, root)
    except ValueError:
        return ap.replace(os.sep, "/")
    if rp.startswith(".."):
        return ap.replace(os.sep, "/")
    return rp.replace(os.sep, "/")


def parse_suppressions(src: str) -> dict[int, set[str]]:
    """Per-line suppression directives, read from COMMENT tokens only — a
    ``# jaxlint: disable=`` sequence inside a string literal is content,
    not a directive."""
    sup: dict[int, set[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(src).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = SUPPRESS_RE.search(tok.string)
            if m:
                sup.setdefault(tok.start[0], set()).update(
                    r.strip() for r in m.group(1).split(",") if r.strip()
                )
    except tokenize.TokenError:  # ast.parse succeeded; be permissive
        for i, line in enumerate(src.splitlines(), start=1):
            m = SUPPRESS_RE.search(line)
            if m:
                sup[i] = {r.strip() for r in m.group(1).split(",")
                          if r.strip()}
    return sup


def lint_source(
    src: str, path: str = "<memory>", rules=None, stale_sup_out=None
) -> tuple[list[common.Finding], int]:
    """Lint one source blob; returns (findings, n_suppressed).

    Raises ``SyntaxError`` for unparseable source — callers decide whether
    that is exit-2 (CLI) or a test failure (fixtures).

    ``stale_sup_out`` (a list) collects ``(path, line, rule)`` for inline
    ``# jaxlint: disable=`` directives that suppressed nothing — dead
    suppressions that would silently swallow a future real finding.  Only
    populated on full-rule runs (``rules=None``): a subset run cannot decide
    that a directive for an un-run rule is dead.
    """
    tree = ast.parse(src)
    common.annotate_parents(tree)
    src_lines = src.splitlines()
    ctx = common.RuleContext(
        path=path,
        tree=tree,
        src_lines=src_lines,
        aliases=common.import_aliases(tree),
        functions=common.FunctionIndex(tree),
    )
    findings: list[common.Finding] = []
    for rule in (rules if rules is not None else ALL_RULES):
        findings.extend(rule.check(ctx))

    sup = parse_suppressions(src)
    kept: list[common.Finding] = []
    n_suppressed = 0
    used: set[tuple[int, str]] = set()
    for f in findings:
        span = range(f.line, (f.end_line or f.line) + 1)
        directives: set[str] = set()
        for ln in span:
            directives |= sup.get(ln, set())
        if f.rule in directives or "all" in directives:
            n_suppressed += 1
            match = f.rule if f.rule in directives else "all"
            for ln in span:
                if match in sup.get(ln, set()):
                    used.add((ln, match))
        else:
            kept.append(f)
    if stale_sup_out is not None and rules is None:
        for ln in sorted(sup):
            for rule_id in sorted(sup[ln]):
                if (ln, rule_id) not in used:
                    stale_sup_out.append((path, ln, rule_id))
    kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return kept, n_suppressed


def resolve_path_args(raw: list[str]) -> list[str]:
    """CLI path args are repo-root-relative by contract (SKILL.md/README):
    a relative arg resolves against REPO_ROOT first and falls back to the
    cwd only when the rooted path does not exist.  Root-FIRST, not
    cwd-presence-dependent — a foreign cwd that happens to contain its own
    ``tools/`` must not hijack the documented invocation."""
    out = []
    for p in raw:
        if not os.path.isabs(p):
            rooted = os.path.join(REPO_ROOT, p)
            if os.path.exists(rooted):
                out.append(rooted)
                continue
        out.append(p)
    return out


def iter_py_files(paths: list[str]):
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            else:
                # an explicit non-.py file arg is a misconfiguration: a CI
                # gate that typo'd its target must fail loudly, not lint
                # nothing and exit 0
                raise FileNotFoundError(f"not a Python file: {p}")
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d != "__pycache__" and not d.startswith(".")
                )
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)
        else:
            raise FileNotFoundError(f"no such path: {p}")


def lint_paths(
    paths: list[str], rules=None, stale_sup_out=None
) -> tuple[list[common.Finding], dict[str, list[str]], int, list[str]]:
    """Lint every file under ``paths``; returns
    (findings, {linted_rel_path: src_lines}, n_suppressed, parse_errors).
    The returned sources are THE text the findings were computed against —
    baseline keying reuses them instead of re-reading from disk.
    ``stale_sup_out`` aggregates dead inline suppressions per
    :func:`lint_source`."""
    findings: list[common.Finding] = []
    files: dict[str, list[str]] = {}
    n_suppressed = 0
    errors: list[str] = []
    for fp in iter_py_files(paths):
        rp = rel_path(fp)
        if rp in files:
            continue  # overlapping path args must not double-count findings
        try:
            with open(fp, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            errors.append(f"{fp}: {e}")
            continue
        files[rp] = src.splitlines()
        try:
            fs, ns = lint_source(src, path=rp, rules=rules,
                                 stale_sup_out=stale_sup_out)
        except SyntaxError as e:
            errors.append(f"{fp}: syntax error: {e}")
            continue
        findings.extend(fs)
        n_suppressed += ns
    return findings, files, n_suppressed, errors


# ---------------------------------------------------------------- baseline

def load_baseline(path: str) -> dict[tuple[str, str, str], dict]:
    """Baseline file -> {(rule, path, line_text): entry}."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    out = {}
    for e in doc.get("entries", []):
        out[(e["rule"], e["path"], e["text"])] = {
            "count": int(e.get("count", 1)),
            "justification": e.get("justification", ""),
        }
    return out


def split_by_baseline(
    findings: list[common.Finding],
    baseline: dict[tuple[str, str, str], dict],
    line_text_of,
    used_out: Counter | None = None,
) -> tuple[list[common.Finding], int, list[tuple[str, str, str]]]:
    """(new findings, n_baselined, stale baseline keys).  ``used_out``
    receives the per-key consumed counts (``--prune-baseline`` rewrites
    entries down to exactly these)."""
    used: Counter = used_out if used_out is not None else Counter()
    new: list[common.Finding] = []
    for f in findings:
        key = f.key(line_text_of(f))
        allowed = baseline.get(key, {}).get("count", 0)
        if used[key] < allowed:
            used[key] += 1
        else:
            new.append(f)
    stale = [k for k, e in baseline.items() if used[k] < e["count"]]
    return new, sum(used.values()), stale


def write_baseline(
    path: str,
    findings: list[common.Finding],
    line_text_of,
    old: dict[tuple[str, str, str], dict] | None = None,
    linted_paths: list[str] | None = None,
) -> None:
    """Write findings as the new baseline.  Old entries keep their
    justifications; old entries for paths OUTSIDE ``linted_paths`` are
    preserved wholesale, so re-baselining one file never silently drops the
    grandfathered findings (and hand-written justifications) of the rest of
    the tree."""
    counts: Counter = Counter()
    for f in findings:
        counts[f.key(line_text_of(f))] += 1
    if old and linted_paths is not None:
        in_scope = set(linted_paths)
        for (rule, fpath, text), entry in old.items():
            if fpath in in_scope or (rule, fpath, text) in counts:
                continue
            # entries for files that no longer exist are droppable here —
            # otherwise a deleted/renamed file's entry would survive every
            # --write-baseline and warn as stale forever
            fp = fpath if os.path.isabs(fpath) \
                else os.path.join(REPO_ROOT, fpath)
            if os.path.exists(fp):
                counts[(rule, fpath, text)] = entry["count"]
    entries = []
    for (rule, fpath, text), count in sorted(counts.items()):
        just = (old or {}).get((rule, fpath, text), {}).get(
            "justification", "TODO: justify or fix"
        )
        entries.append({
            "rule": rule, "path": fpath, "text": text, "count": count,
            "justification": just,
        })
    doc = {
        "jaxlint_baseline": 1,
        "comment": (
            "Grandfathered findings: (rule, path, stripped source line) -> "
            "count + one-line justification.  Regenerate with `python -m "
            "blockchain_simulator_tpu.lint --write-baseline` (existing "
            "justifications are preserved); new code must come in clean."
        ),
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def prune_baseline(
    path: str,
    findings: list[common.Finding],
    line_text_of,
    old: dict[tuple[str, str, str], dict],
    linted_paths,
) -> tuple[list[tuple[str, str, str]], int]:
    """Baseline hygiene (``--prune-baseline``): rewrite the baseline with
    each in-scope entry's count reduced to what actually still fires —
    justifications preserved, fully-fixed entries dropped.  Entries for
    files outside ``linted_paths`` are preserved wholesale (the
    ``write_baseline`` subset contract).  Returns (dropped keys,
    n_reduced)."""
    used: Counter = Counter()
    split_by_baseline(findings, old, line_text_of, used_out=used)
    in_scope = set(linted_paths)
    counts: Counter = Counter()
    dropped: list[tuple[str, str, str]] = []
    n_reduced = 0
    for key, entry in old.items():
        if key[1] not in in_scope:
            # entries for files that no longer exist ARE decidable — a
            # deleted/renamed file's entry is exactly the staleness this
            # command exists to clean (the write_baseline contract)
            fp = key[1] if os.path.isabs(key[1]) \
                else os.path.join(REPO_ROOT, key[1])
            if os.path.exists(fp):
                counts[key] = entry["count"]  # not linted: not decidable
            else:
                dropped.append(key)
            continue
        still = used[key]
        if still == 0:
            dropped.append(key)
        else:
            if still < entry["count"]:
                n_reduced += 1
            counts[key] = still
    entries = []
    for (rule, fpath, text), count in sorted(counts.items()):
        entries.append({
            "rule": rule, "path": fpath, "text": text, "count": count,
            "justification": old[(rule, fpath, text)]["justification"],
        })
    doc = {
        "jaxlint_baseline": 1,
        "comment": (
            "Grandfathered findings: (rule, path, stripped source line) -> "
            "count + one-line justification.  Regenerate with `python -m "
            "blockchain_simulator_tpu.lint --write-baseline` (existing "
            "justifications are preserved); new code must come in clean."
        ),
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return dropped, n_reduced


# --------------------------------------------------------------------- CLI

def _default_paths() -> list[str]:
    out = [os.path.join(REPO_ROOT, "blockchain_simulator_tpu")]
    for extra in ("tools", "bench.py", "chip_smoke.py"):
        p = os.path.join(REPO_ROOT, extra)
        if os.path.exists(p):
            out.append(p)
    return out


def _line_text_reader(sources: dict[str, list[str]] | None = None):
    """Baseline keying: finding -> stripped source-line text.  ``sources``
    (lint_paths' output) is the text the findings were computed against;
    disk reads are only a fallback for findings from other runs."""
    cache: dict[str, list[str]] = dict(sources or {})

    def line_text_of(f: common.Finding) -> str:
        lines = cache.get(f.path)
        if lines is None:
            fp = f.path if os.path.isabs(f.path) \
                else os.path.join(REPO_ROOT, f.path)
            try:
                with open(fp, encoding="utf-8") as fh:
                    lines = fh.read().splitlines()
            except OSError:
                lines = []
            cache[f.path] = lines
        if 1 <= f.line <= len(lines):
            return lines[f.line - 1].strip()
        return ""

    return line_text_of


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="blockchain_simulator_tpu.lint",
        description="jaxlint: repo-specific traced-purity / PRNG / "
                    "backend-safety static analysis",
    )
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the package + tools "
                        "+ bench.py + chip_smoke.py)")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: {BASELINE_NAME} at the "
                        "repo root when present)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, grandfathered or not")
    p.add_argument("--write-baseline", action="store_true",
                   help="write the current findings as the new baseline "
                        "(preserves existing justifications) and exit 0")
    p.add_argument("--prune-baseline", action="store_true",
                   help="baseline hygiene: drop/shrink baseline entries "
                        "that no longer fire (justifications preserved), "
                        "report dead inline suppressions, and exit 0")
    p.add_argument("--list-rules", action="store_true")
    args = p.parse_args(argv)

    if args.list_rules:
        for rid, mod in sorted(RULES_BY_ID.items()):
            print(f"{rid:<32} {mod.SUMMARY}")
        return 0

    paths = resolve_path_args(args.paths) if args.paths \
        else _default_paths()
    stale_sups: list[tuple[str, int, str]] = []
    try:
        findings, files, n_suppressed, errors = lint_paths(
            paths, stale_sup_out=stale_sups
        )
    except FileNotFoundError as e:
        print(f"jaxlint: {e}", file=sys.stderr)
        return 2
    if errors:
        for e in errors:
            print(f"jaxlint: {e}", file=sys.stderr)
        return 2

    line_text_of = _line_text_reader(files)
    baseline_path = args.baseline or os.path.join(REPO_ROOT, BASELINE_NAME)

    if args.write_baseline:
        old = load_baseline(baseline_path) \
            if os.path.exists(baseline_path) else {}
        write_baseline(baseline_path, findings, line_text_of, old,
                       linted_paths=files)
        print(f"jaxlint: wrote {len(findings)} finding(s) to "
              f"{baseline_path}")
        return 0

    if args.prune_baseline:
        if not os.path.exists(baseline_path):
            print(f"jaxlint: no baseline at {baseline_path}",
                  file=sys.stderr)
            return 2
        try:
            old = load_baseline(baseline_path)
        except (json.JSONDecodeError, KeyError, TypeError) as e:
            print(f"jaxlint: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
        dropped, n_reduced = prune_baseline(
            baseline_path, findings, line_text_of, old, linted_paths=files
        )
        for rule, fpath, text in dropped:
            print(f"jaxlint: pruned fixed entry {rule} @ {fpath}: {text!r}")
        for fpath, ln, rule in stale_sups:
            print(f"jaxlint: stale suppression {fpath}:{ln}: "
                  f"`# jaxlint: disable={rule}` no longer fires — remove it")
        print(f"jaxlint: pruned {len(dropped)} entr(ies), reduced "
              f"{n_reduced}, {len(stale_sups)} stale suppression(s) in "
              f"{baseline_path}")
        return 0

    baseline: dict = {}
    if not args.no_baseline and os.path.exists(baseline_path):
        try:
            baseline = load_baseline(baseline_path)
        except (json.JSONDecodeError, KeyError, TypeError) as e:
            print(f"jaxlint: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
    new, n_baselined, stale = split_by_baseline(
        findings, baseline, line_text_of
    )
    # staleness is only decidable for files this run actually linted: a
    # subset invocation must not claim entries for un-linted files are fixed
    stale = [k for k in stale if k[1] in files]

    if args.format == "json":
        print(json.dumps({
            "jaxlint_schema": 1,
            "files": len(files),
            "new_findings": [f.to_dict() for f in new],
            "baselined": n_baselined,
            "suppressed": n_suppressed,
            "stale_baseline": [
                {"rule": r, "path": pp, "text": t} for r, pp, t in stale
            ],
            "stale_suppressions": [
                {"path": pp, "line": ln, "rule": r}
                for pp, ln, r in stale_sups
            ],
            "rules": sorted(RULES_BY_ID),
        }, indent=1))
    else:
        for f in new:
            fn = f" [{f.function}]" if f.function else ""
            print(f"{f.path}:{f.line}:{f.col + 1}: {f.rule}{fn}: "
                  f"{f.message}")
        for r, pp, t in stale:
            print(f"jaxlint: stale baseline entry {r} @ {pp}: {t!r} "
                  "(fixed? regenerate with --write-baseline)",
                  file=sys.stderr)
        for pp, ln, r in stale_sups:
            print(f"jaxlint: stale suppression {pp}:{ln}: "
                  f"`# jaxlint: disable={r}` no longer fires "
                  "(remove it, or --prune-baseline for a report)",
                  file=sys.stderr)
        print(f"jaxlint: {len(files)} files, {len(new)} new finding(s), "
              f"{n_baselined} baselined, {n_suppressed} suppressed")

    # leave the lint trail in runs.jsonl like every other entrypoint (no-op
    # unless $BLOCKSIM_RUNS_JSONL is set; obs never imports jax) — but ONLY
    # for gate-equivalent runs: a --no-baseline or partial-path invocation
    # counts a different population, and charting it into the same
    # jaxlint_new_findings series would make the trajectory reflect
    # invocation scope instead of code health
    gate_equivalent = (
        not args.no_baseline
        and args.baseline is None  # a custom baseline counts differently
        and sorted(os.path.abspath(p) for p in paths)
        == sorted(os.path.abspath(p) for p in _default_paths())
    )
    if gate_equivalent:
        from blockchain_simulator_tpu.utils import obs

        obs.record_run({
            "metric": "jaxlint_new_findings",
            "value": len(new),
            "unit": "findings",
            "files": len(files),
            "baselined": n_baselined,
            "suppressed": n_suppressed,
        })
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
