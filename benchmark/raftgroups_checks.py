"""The comparisons that decide ``correct`` in the cells of multi-Raft (N nodes
as C independent Raft groups of m, Raft with terms inside each): records in
``checks.py``'s shape, made with its ``exact`` / ``at_most``.

The plain reference is ``reference/raft_terms_engine.py``: a per-message
event heap, one group at a time, a term on every message, Figure 2's rules.
A group's run is a draw (which timer fires first, whether a vote splits), so
a group is not compared with a group: what is exact is held in EVERY group
of every run, and what is a time is compared as a distribution over groups
with the reference's over its own sample.

- the guarantees, exact, every group of every run: ``term_conflicts`` 0 (the
  program's oracle: no node became leader of a term another node led); at
  most one leader of the group's highest term, and at most one leader at
  all, at the end of a run (on a full mesh without faults a deposed leader
  hears of the new term before its candidate can win); ``agreement_ok``;
  the group committed the reference's count of blocks (50, its stop rule),
  all of them under its first leader;
- the distributions, over all groups of all runs against the reference's
  sample: the first successful election (mean and 90th percentile), the
  share of groups whose first leader has term 1 / 2 / 3 or more, the commit
  tail (last commit minus first election, mean), the failover (the first
  leader's last heartbeat to the next election won, mean and 90th
  percentile), each within the configuration file's limit, which lies
  between the largest reading of the sound program and the reading of the
  control that breaks it (``limits_from`` there).  The groups that have no
  leader, or no failover, by the end of a run are counted in the result's
  notes and decide nothing: no control moves them (a whole run's group
  without a leader fails the block count);
- determinism: a seeded sample of groups equals the flat run of the group's
  own key (``rows_differing_from_flat``; the driver runs them).

A run whose ``per_committee`` lacks the keys of terms (``raft_terms`` off:
the first control) is held by what it has (``groups_with_two_leaders``), and
``terms_reported`` says that the rest could not be read.
"""

from __future__ import annotations

import math
import statistics

import checks

# what ``per_committee`` holds with terms on, a list of C each
TERM_KEYS = ("term_final", "leader_term", "n_leaders_term_final",
             "term_conflicts", "step_downs", "first_leader_ms",
             "first_leader_term", "first_leader_blocks", "failover_ms")
INNER_KEYS = ("n_leaders", "blocks", "rounds", "elections", "last_block_ms",
              "agreement_ok") + TERM_KEYS


def reference_groups(config: dict, fields: dict, seed: int,
                     sim_ms: int | None = None) -> dict:
    """The reference's sample: ``reference.groups`` groups of this
    deployment's size under its fields, on streams drawn from ``seed``."""
    ref = config["reference"]
    return checks._engine(ref["engine"]).run(
        fields, seed, groups=int(ref["groups"]), sim_ms=sim_ms)


def groups_of(row: dict) -> dict:
    """A run's per-group lists (``topo.committee.metrics``'
    ``per_committee``)."""
    return row["per_committee"]


def rounds(row: dict) -> float:
    """The unit of work of a run: blocks committed by a majority in every
    group, as the mean over the groups of ``blocks`` (50 a sound run)."""
    return statistics.fmean(groups_of(row)["blocks"])


def pooled(rows: list[dict], key: str) -> list:
    return [v for m in rows for v in groups_of(m)[key]]


def has_terms(rows: list[dict]) -> bool:
    return all(k in groups_of(m) for m in rows for k in TERM_KEYS)


def p90(values: list) -> float:
    v = sorted(values)
    return float(v[math.ceil(0.9 * len(v)) - 1])


def election_safety(rows: list[dict]) -> list[dict]:
    """Exact, in every group: what holds at any instant of a run."""
    out = [checks.exact("groups_with_two_leaders", sum(
        1 for n in pooled(rows, "n_leaders") if n > 1), 0)]
    out.append(checks.exact("terms_reported", has_terms(rows), True))
    if has_terms(rows):
        out.append(checks.exact("term_conflicts_total",
                                sum(pooled(rows, "term_conflicts")), 0))
        out.append(checks.exact("groups_with_two_leaders_of_a_term", sum(
            1 for n in pooled(rows, "n_leaders_term_final") if n > 1), 0))
    return out


def guarantees(rows: list[dict], ref: dict) -> list[dict]:
    """Exact, in every group of every whole run."""
    want = set(ref["per_group"]["blocks"])
    out = election_safety(rows)
    out.append(checks.exact("reference_counts_agree", len(want), 1))
    blocks = max(want)
    out.append(checks.exact("agreement_violations", sum(
        1 for ok in pooled(rows, "agreement_ok") if not ok), 0))
    out.append(checks.exact("blocks_vs_reference_gap_max", max(
        abs(b - blocks) for b in pooled(rows, "blocks")), 0))
    if has_terms(rows):
        out.append(checks.exact("blocks_not_by_first_leader_max", max(
            b - f for b, f in zip(pooled(rows, "blocks"),
                                  pooled(rows, "first_leader_blocks"))), 0))
    return out


def term_shares(first_terms: list) -> tuple:
    n = len(first_terms)
    return (sum(1 for t in first_terms if t == 1) / n,
            sum(1 for t in first_terms if t == 2) / n,
            sum(1 for t in first_terms if t >= 3) / n)


def elections_against_reference(rows: list[dict], ref: dict,
                                lim: dict) -> list[dict]:
    """The first successful election, over the groups that had one."""
    mine = [t for t in pooled(rows, "first_leader_ms") if t >= 0]
    theirs = [t for t in ref["per_group"]["first_leader_ms"] if t >= 0]
    my_terms = [t for t in pooled(rows, "first_leader_term") if t > 0]
    their_terms = [t for t in ref["per_group"]["first_leader_term"] if t > 0]
    return [
        checks.at_most("first_election_mean_gap_ms", abs(
            statistics.fmean(mine) - statistics.fmean(theirs)),
            lim["first_election_mean_limit_ms"]),
        checks.at_most("first_election_p90_gap_ms", abs(
            p90(mine) - p90(theirs)), lim["first_election_p90_limit_ms"]),
        checks.at_most("first_term_share_gap_max", max(
            abs(a - b) for a, b in zip(term_shares(my_terms),
                                       term_shares(their_terms))),
            lim["first_term_share_limit"]),
    ]


def against_reference(rows: list[dict], ref: dict, config: dict) -> list[dict]:
    """Whole runs against the reference's sample: the hierarchy, then the
    distributions of the three timings."""
    lim = config["reference"]
    sizes = {(m["committees"], m["committee_size"]) for m in rows}
    out = [checks.exact("reference_term_conflicts",
                        sum(ref["per_group"]["term_conflicts"]), 0),
           checks.exact("reference_leaders_of_one_term_max",
                        max(ref["per_group"]["leaders_of_one_term_max"]), 1),
           checks.exact("group_size_gap_max", max(
               abs(m - ref["group_size"]) for _, m in sizes), 0)]
    if not has_terms(rows):
        return out
    out += elections_against_reference(rows, ref, lim)
    tail = lambda g: [b - f for b, f in zip(  # noqa: E731
        g["last_block_ms"], g["first_leader_ms"]) if b >= 0 and f >= 0]
    mine = [x for m in rows for x in tail(groups_of(m))]
    out.append(checks.at_most("commit_tail_mean_gap_ms", abs(
        statistics.fmean(mine) - statistics.fmean(tail(ref["per_group"]))),
        lim["tail_mean_limit_ms"]))
    mine = [t for t in pooled(rows, "failover_ms") if t >= 0]
    theirs = [t for t in ref["per_group"]["failover_ms"] if t >= 0]
    out.append(checks.at_most("failover_mean_gap_ms", abs(
        statistics.fmean(mine) - statistics.fmean(theirs)),
        lim["failover_mean_limit_ms"]))
    out.append(checks.at_most("failover_p90_gap_ms", abs(
        p90(mine) - p90(theirs)), lim["failover_p90_limit_ms"]))
    return out


def rows_equal_flat(pairs: list) -> dict:
    """``[(a group's dict out of a stack, the flat run's metrics dict)]``:
    a group differs when any key the two share differs."""
    return checks.exact("rows_differing_from_flat", sum(
        1 for c, flat in pairs
        if any(c[k] != flat[k] for k in c if k in flat)), 0)


def group(row: dict, i: int) -> dict:
    """Group ``i`` of a run as one dict."""
    return {k: v[i] for k, v in groups_of(row).items()}
