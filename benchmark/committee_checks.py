"""The comparisons that decide ``correct`` in the cells of the committee tier
(N replicas as C committees of m, PBFT inside each, one combining step over
them): records in ``checks.py``'s shape, made with its ``exact`` /
``at_most`` / ``at_least``.

The plain reference is ``reference/committee_engine.py``: the per-message
engine called per committee at n = m for a seeded sample of committees,
undisturbed (no view change), and the combining rule in plain Python.  What
is held, on every run of the window:

- the guarantees: agreement in every committee; every committee decides (C
  of C); at least one block final on all honest nodes of every committee;
- the combining rule, exactly: the reference's ``outer_rule`` applied to the
  run's own milestones gives the run's ``outer_commit_ms``,
  ``committees_decided``, ``outer_quorum`` and ``outer_round_ms``, and a
  committee's milestone is its own last commit;
- the hierarchy: as many committees of the same size as the reference's;
- counts, on every committee without a view change (a view change stalls
  the pipeline by a round or two and the reference runs with none, as
  ``checks.against_reference`` holds a flat row): ``rounds_sent`` and
  ``blocks_final_all_nodes`` equal the reference's; no committee, calm or
  not, finalizes more;
- times, on every committee without a view change: the commit tail and the
  mean time to finality within the configuration file's limits of the
  reference sample's median (an undisturbed committee's milestones do not
  depend on its stream beyond a tick);
- determinism: a seeded sample of committees equals the flat run of the
  committee's own key (``rows_differing_from_flat``; the driver runs them).

Every run has to carry ``per_committee`` and the milestone keys that
``topo.committee.MILESTONES`` names in the program.  A program whose
``metrics`` drops the inner counts for C > 1 fails with a ``KeyError`` where
its first row is read (:func:`rounds`, in the driver's set-up).
"""

from __future__ import annotations

import statistics

import checks

# what ``per_committee`` must hold, a list of C each
INNER_KEYS = ("blocks_final_all_nodes", "rounds_sent", "view_changes",
              "last_commit_ms", "mean_time_to_finality_ms", "agreement_ok")


def reference_milestones(config: dict, fields: dict, seed: int) -> dict:
    """The reference's undisturbed run of this deployment's fields: a seeded
    sample of its committees at their own size."""
    ref = config["reference"]
    return checks._engine(ref["engine"]).run(
        fields, seed, sample=int(ref.get("sample", 3)),
        pbft_view_change_num=0)


def committees_of(row: dict) -> list[dict]:
    """A run's ``per_committee`` lists as one dict a committee."""
    pc = row["per_committee"]
    return [{k: pc[k][i] for k in INNER_KEYS}
            for i in range(len(pc[INNER_KEYS[0]]))]


def calm(row: dict) -> list[dict]:
    return [c for c in committees_of(row) if c["view_changes"] == 0]


def rounds(row: dict) -> float:
    """The unit of work of a run: blocks final on all honest nodes of a
    committee, as the mean over the C committees of
    ``blocks_final_all_nodes`` (8 in a calm committee, 4-7 in the one in ten
    whose leader changed view), as ``raftgroups_checks.rounds`` counts a run
    of groups.  The mean and not the minimum: a view change costs the run
    what it cost its committee, averaged over C draws, where the minimum is
    set by the run's unluckiest committee alone and a window's sum of minima
    spreads past the metric's bound with the seeds (PERF.md section 6).  The
    minimum stays held as the guarantee ``blocks_final_min``."""
    return statistics.fmean(row["per_committee"]["blocks_final_all_nodes"])


def guarantees(rows: list[dict], fields: dict) -> list[dict]:
    want = fields["committees"]
    return [
        checks.exact("agreement_violations",
                     sum(1 for m in rows if not m.get("agreement_ok")), 0),
        checks.exact("committees_undecided_max",
                     max(want - m["committees_decided"] for m in rows), 0),
        checks.at_least("blocks_final_min", min(
            c["blocks_final_all_nodes"]
            for m in rows for c in committees_of(m)), 1),
    ]


def outer_rule(rows: list[dict], engine, hi: int) -> dict:
    """The combining rule, exact, on the program's own milestones."""
    wrong = 0
    for m in rows:
        own = [engine.milestone(c) for c in committees_of(m)]
        want = engine.outer_rule(m["inner_milestones_ms"], m["committees"], hi)
        wrong += int(own != [float(t) for t in m["inner_milestones_ms"]]
                     or any(m[k] != v for k, v in want.items()))
    return checks.exact("outer_rule_violations", wrong, 0)


def against_reference(rows: list[dict], ref: dict, config: dict) -> list[dict]:
    lim = config["reference"]
    iv = ref["_interval"]
    engine = checks._engine(lim["engine"])
    out = [
        checks.exact("reference_agreement_ok", bool(ref["agreement_ok"]), True),
        checks.exact("reference_counts_agree", bool(ref["counts_agree"]), True),
        checks.exact("hierarchy_gap_max", max(
            abs(m["committees"] - ref["committees"])
            + abs(m["committee_size"] - ref["committee_size"])
            for m in rows), 0),
        outer_rule(rows, engine, ref["one_way_hi"]),
    ]
    every = [c for m in rows for c in committees_of(m)]
    quiet = [c for m in rows for c in calm(m)]
    out.append(checks.at_least(
        "committees_without_view_change_min",
        min(len(calm(m)) for m in rows), 1))
    for key in engine.COUNT_KEYS:
        out.append(checks.exact(f"{key}_vs_reference_max", max(
            (abs(c[key] - ref["counts"][key]) for c in quiet), default=0), 0))
    out.append(checks.at_most("blocks_final_over_reference_max", max(
        c["blocks_final_all_nodes"] - ref["counts"]["blocks_final_all_nodes"]
        for c in every), 0))
    sample = list(ref["rows"].values())
    ref_ttf = statistics.median(r["mean_time_to_finality_ms"] for r in sample)
    ref_tail = statistics.median(checks.commit_tail(r, iv) for r in sample)
    out.append(checks.at_most("ttf_gap_ms_max", max(
        (abs(c["mean_time_to_finality_ms"] - ref_ttf) for c in quiet),
        default=0.0), lim["ttf_limit_ms"]))
    out.append(checks.at_most("commit_tail_gap_ms_max", max(
        (abs(checks.commit_tail(c, iv) - ref_tail) for c in quiet),
        default=0.0), lim["tail_limit_ms"]))
    return out


def rows_equal_flat(pairs: list) -> dict:
    """``[(committee's dict out of a stack, the flat run's metrics dict)]``:
    a committee differs when any key the two share differs."""
    return checks.exact("rows_differing_from_flat", sum(
        1 for c, flat in pairs
        if any(c[k] != flat[k] for k in INNER_KEYS)), 0)
