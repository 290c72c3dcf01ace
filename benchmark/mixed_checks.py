"""The comparisons that decide ``correct`` in the cells of the mixed
deployment (S Raft groups under PBFT over their representatives): records in
``checks.py``'s shape, made with its ``exact`` / ``at_most`` / ``at_least``.

The plain reference is ``reference/mixed_engine.py``, run after the window on
the deployment's own fields at the cell's own size with view changes off.
Counts are exact.  The timing milestones are compared within the limits of
the configuration file's ``reference`` block (set from chip readings that
``PERF.md`` records); those of the finality layer on the rows without a view
change, as ``checks.against_reference`` does for PBFT alone.

Every row has to carry every timing key (``models.mixed.MILESTONES`` names
them in the program): ``rows_with_timing`` must equal the number of rows, so a
row that lacks one is not ``correct``, whatever the rest of it says.  (The
``mixed_solo`` driver refuses a program without the tuple before it builds.)
"""

from __future__ import annotations

import checks

TIMING_KEYS = ("leader_elected_ms_max", "raft_commit_tail_ms_max",
               "global_first_propose_ms", "global_last_commit_ms",
               "global_view_changes")


def reference_milestones(config: dict, fields: dict, seed: int) -> dict:
    """The reference's run of this deployment's fields at the cell's own
    size, undisturbed (no view change)."""
    return checks._engine(config["reference"]["engine"]).run(
        fields, seed, pbft_view_change_num=0)


def global_commit_tail(m: dict, interval: int) -> float:
    """The last final global block's commit past its own block tick, counted
    from the first proposal: free of when the first shard elected."""
    return (m["global_last_commit_ms"] - m["global_first_propose_ms"]
            - (m["global_blocks_final"] - 1) * interval)


def guarantees(rows: list[dict], fields: dict, want_blocks: int,
               want_rounds: int) -> list[dict]:
    """What every run of the window must satisfy whatever its seed: every
    shard has a leader and committed ``want_blocks`` Raft blocks, every one
    of ``want_rounds`` global rounds is final on every representative, and
    agreement holds."""
    s = fields["mixed_shards"]
    return [
        checks.exact("agreement_violations",
                     sum(1 for m in rows if not m.get("agreement_ok")), 0),
        checks.exact("shards_without_leader_max",
                     max(s - m["shards_with_leader"] for m in rows), 0),
        checks.exact("raft_blocks_shortfall_max",
                     max(want_blocks - m["raft_blocks_min"] for m in rows), 0),
        checks.exact("raft_blocks_total_gap_max", max(
            abs(s * want_blocks - m["raft_blocks_total"]) for m in rows), 0),
        checks.exact("finality_shortfall_max", max(
            want_rounds - m["global_blocks_final"] for m in rows), 0),
        checks.exact("rounds_sent_gap_max", max(
            abs(want_rounds - m["global_rounds_sent"]) for m in rows), 0),
    ]


def against_reference(rows: list[dict], ref: dict, config: dict,
                      interval: int) -> list[dict]:
    lim = config["reference"]
    out = [checks.exact("reference_agreement_ok", bool(ref["agreement_ok"]),
                        True)]
    for key in ("shards_with_leader", "raft_blocks_min", "raft_blocks_total",
                "global_rounds_sent", "global_blocks_final"):
        out.append(checks.exact(f"{key}_vs_reference_max", max(
            abs(m[key] - ref[key]) for m in rows), 0))
    timed = [m for m in rows if all(k in m for k in TIMING_KEYS)]
    calm = [m for m in timed if m["global_view_changes"] == 0]
    out.append(checks.exact("rows_with_timing", len(timed), len(rows)))
    if not timed:
        return out
    out.append(checks.at_least("rows_without_view_change", len(calm), 1))

    def gap(sel, of):
        return max((abs(of(m) - of(ref)) for m in sel), default=0.0)

    out.append(checks.at_most("election_gap_ms_max", gap(
        timed, lambda m: m["leader_elected_ms_max"]),
        lim["election_limit_ms"]))
    out.append(checks.at_most("raft_tail_gap_ms_max", gap(
        timed, lambda m: m["raft_commit_tail_ms_max"]),
        lim["raft_tail_limit_ms"]))
    out.append(checks.at_most("ttf_gap_ms_max", gap(
        calm, lambda m: m["global_mean_ttf_ms"]), lim["ttf_limit_ms"]))
    out.append(checks.at_most("commit_tail_gap_ms_max", gap(
        calm, lambda m: global_commit_tail(m, interval)),
        lim["tail_limit_ms"]))
    return out
