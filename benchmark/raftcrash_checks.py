"""The comparisons that decide ``correct`` in the cells of multi-Raft under a
crash schedule (N nodes as C independent Raft groups of m, Raft with terms
inside each, every group's leader killed K times a run): records in
``checks.py``'s shape, made with its ``exact`` / ``at_most``.

The plain reference is ``reference/raft_crash_engine.py``: a per-message event
heap, one group at a time, a term on every message, the crash and the restart
as events of their own.  A group's run is a draw (its phase, which timer fires
first, whether two candidates stand), so a group is not compared with a
group: what is exact is held in EVERY group of every run, and what is a time
is compared as a distribution over crashes with the reference's over its own
sample.

- the guarantees, exact, every group of every run: election safety
  (``raftgroups_checks.election_safety``: ``term_conflicts`` 0, at most one
  alive leader of a group's highest term and at most one at all at the end
  of a run); crash integrity (the program's two oracles: ``dead_acts``, a
  node that fired a timer, won, took a term or a heartbeat or voted while
  it was down, and ``double_votes``, a node that voted twice in one term
  across a restart); every scheduled crash fell (``crashes`` is the
  schedule's count, the reference's own);
- every crash hit a leader and was replaced before the next: the shares of
  crashes that found no leader and of crashes not replaced, of all crashes,
  at most the configuration's limit (the reference's own share is about 0;
  both are in the result's notes beside the reference's);
- the distribution over all crashes of all groups of all runs of the
  failover (crash to the next election won, ms): mean, median, 90th
  percentile, and the share of failovers before which more than one
  election timer fired, each within the configuration file's limit of the
  reference's sample (``limits_from`` there has the two readings a limit
  lies between);
- determinism: a seeded sample of groups equals the flat run of the group's
  own key, schedule included (``rows_differing_from_flat``; the driver runs
  them).

A program whose ``per_committee`` lacks the schedule's keys cannot be read:
``schedule_reported`` says so and fails.
"""

from __future__ import annotations

import statistics

import checks
import raftgroups_checks

groups_of = raftgroups_checks.groups_of
pooled = raftgroups_checks.pooled
rows_equal_flat = raftgroups_checks.rows_equal_flat
group = raftgroups_checks.group

# what ``per_committee`` holds under a schedule beside the keys of terms
CRASH_KEYS = ("crashes", "crashes_found_no_leader", "crashes_unreplaced",
              "failovers", "failovers_multi_election", "restarts",
              "dead_acts", "double_votes")


def reference_groups(config: dict, fields: dict, seed: int) -> dict:
    """The reference's sample: ``reference.groups`` groups of this
    deployment's size under its fields and its schedule, on streams drawn
    from ``seed``."""
    ref = config["reference"]
    return checks._engine(ref["engine"]).run(
        fields, seed, groups=int(ref["groups"]))


def rounds(row: dict) -> float:
    """The unit of work of a run: a crashed leader replaced by a leader of a
    higher term, as the mean over the groups of ``failovers`` (the
    schedule's ``crashes`` a sound run)."""
    return statistics.fmean(groups_of(row)["failovers"])


def has_schedule(rows: list[dict]) -> bool:
    return all(k in groups_of(m) for m in rows for k in CRASH_KEYS)


def failovers_ms(per_group: dict) -> list:
    """Every failover of every group, ms: the per-crash columns pooled."""
    out, k = [], 0
    while f"crash{k}_failover_ms" in per_group:
        out += [t for t in per_group[f"crash{k}_failover_ms"] if t >= 0]
        k += 1
    return out


def shape(per_group: dict) -> dict:
    """The failover's distribution over all crashes of ``per_group``; where
    no crash was replaced, numbers that fail every limit."""
    ms = failovers_ms(per_group) or [1e9]  # finite: the result line is JSON
    crashes = max(sum(per_group["crashes"]), 1)
    return {
        "mean": statistics.fmean(ms), "median": statistics.median(ms),
        "p90": raftgroups_checks.p90(ms),
        "multi_share": sum(per_group["failovers_multi_election"]) / len(ms),
        "no_leader_share": sum(per_group["crashes_found_no_leader"]) / crashes,
        "unreplaced_share": sum(per_group["crashes_unreplaced"]) / crashes,
    }


def merged(rows: list[dict]) -> dict:
    """The runs' per-group lists end to end, key by key."""
    return {k: pooled(rows, k) for k in groups_of(rows[0])}


def guarantees(rows: list[dict], ref: dict, fields: dict) -> list[dict]:
    """Exact, in every group of every whole run."""
    out = raftgroups_checks.election_safety(rows)
    out.append(checks.exact("schedule_reported", has_schedule(rows), True))
    if not has_schedule(rows):
        return out
    want = int(fields["faults"]["crashes"])
    out += [
        checks.exact("dead_acts_total", sum(pooled(rows, "dead_acts")), 0),
        checks.exact("double_votes_total",
                     sum(pooled(rows, "double_votes")), 0),
        checks.exact("agreement_violations", sum(
            1 for ok in pooled(rows, "agreement_ok") if not ok), 0),
        checks.exact("crashes_vs_schedule_gap_max", max(
            abs(c - want) for c in pooled(rows, "crashes")), 0),
        checks.exact("reference_crashes_vs_schedule_gap_max", max(
            abs(c - want) for c in ref["per_group"]["crashes"]), 0),
    ]
    return out


def against_reference(rows: list[dict], ref: dict, config: dict) -> list[dict]:
    """Whole runs against the reference's sample: the hierarchy, the
    reference's own oracles, then the failover's distribution."""
    lim = config["reference"]
    theirs_pg = ref["per_group"]
    sizes = {(m["committees"], m["committee_size"]) for m in rows}
    out = [checks.exact("reference_term_conflicts",
                        sum(theirs_pg["term_conflicts"]), 0),
           checks.exact("reference_leaders_of_one_term_max",
                        max(theirs_pg["leaders_of_one_term_max"]), 1),
           checks.exact("reference_dead_acts",
                        sum(theirs_pg["dead_acts"])
                        + sum(theirs_pg["double_votes"]), 0),
           checks.exact("group_size_gap_max", max(
               abs(m - ref["group_size"]) for _, m in sizes), 0)]
    if not has_schedule(rows):
        return out
    mine, theirs = shape(merged(rows)), shape(theirs_pg)
    out += [
        checks.at_most("crashes_found_no_leader_share",
                       mine["no_leader_share"], lim["no_leader_share_limit"]),
        checks.at_most("crashes_unreplaced_share",
                       mine["unreplaced_share"], lim["unreplaced_share_limit"]),
        checks.at_most("failover_mean_gap_ms",
                       abs(mine["mean"] - theirs["mean"]),
                       lim["failover_mean_limit_ms"]),
        checks.at_most("failover_median_gap_ms",
                       abs(mine["median"] - theirs["median"]),
                       lim["failover_median_limit_ms"]),
        checks.at_most("failover_p90_gap_ms",
                       abs(mine["p90"] - theirs["p90"]),
                       lim["failover_p90_limit_ms"]),
        checks.at_most("multi_election_share_gap",
                       abs(mine["multi_share"] - theirs["multi_share"]),
                       lim["multi_election_share_limit"]),
    ]
    return out
