"""The comparisons that decide ``correct`` in the cells of the Byzantine-fault
sweep: records in ``checks.py``'s shape, made with its ``exact`` /
``at_most`` / ``at_least``.

A row is one point of ``parallel/sweep.run_byzantine_sweep``: the metrics
dict of one (fault level, seed) with ``f`` and ``seed`` beside it.  The
plain reference is ``reference/pbft_byz_engine.py``, run after the window
once per LEVEL on the deployment's own fields, at the node count the
configuration file gives it and the same Byzantine fraction (the grid is
``floor(n * k / denominator)`` on both sides), with view changes off.  The
two sides cannot share a draw, so:

- counts are exact, level by level: rounds sent and blocks final (on the
  rows that had no view change, which stalls the pipeline by a round or
  two; no row may finalize more), forged slots, agreement, and whether the
  forged slot became final on every replica;
- times are compared within the limits of the configuration file's
  ``reference`` block (set from chip readings that ``PERF.md`` records): the
  tick at which the last replica finalized the forged slot on every row, the
  mean time to finality and the commit tail on the rows without a view
  change.

Every row has to carry the attack's milestones (``rows_with_attack_keys``
must equal the number of rows); the ``byzsweep`` driver refuses a program
without this deployment's scope and span before it builds.
"""

from __future__ import annotations

import checks

ATTACK_KEYS = ("forged_commits", "forged_commit_ms", "forged_commit_nodes")
# what a batched row and the solo run of its (f, seed) share exactly, and
# what they share within a tick: the normal sampler's stream is not
# batch-invariant (parallel/sweep.py's caveat), the counts do not depend on it
COUNT_KEYS = ("n", "rounds_sent", "blocks_final_all_nodes", "forged_commits",
              "forged_commit_nodes", "unattributed_commits", "view_changes",
              "leader_rounds_max", "block_num_max", "agreement_ok")
TIME_KEYS = ("last_commit_ms", "mean_time_to_finality_ms", "forged_commit_ms")


def f_values(config: dict, n: int) -> list[int]:
    """The grid at ``n`` nodes: ``floor(n * k / denominator)`` per level."""
    g = config["grid"]
    return [n * k // g["denominator"] for k in g["levels_k"]]


def reference_levels(config: dict, fields: dict, seed: int) -> list[dict]:
    """The reference's run of every level of the grid, on this deployment's
    fields at ``reference.n`` nodes, view changes off."""
    ref = config["reference"]
    engine = checks._engine(ref["engine"])
    n = int(ref.get("n", fields["n"]))
    out = []
    for i, f in enumerate(f_values(config, n)):
        faults = {**fields.get("faults", {}), "n_byzantine": f}
        out.append(engine.run({**fields, "faults": faults}, seed + i, n=n,
                              pbft_view_change_num=0))
    return out


def has_attack_keys(m: dict) -> bool:
    return all(k in m for k in ATTACK_KEYS)


def sound(m: dict) -> bool:
    """What a row must show by itself to count as a point completed: the
    honest pipeline finalized something and the row carries the attack's
    milestones."""
    return (m["blocks_final_all_nodes"] > 0 and m["rounds_sent"] > 0
            and has_attack_keys(m))


def against_reference(rows: list[dict], refs: list[dict], config: dict,
                      fields: dict) -> list[dict]:
    """Rows (each with its ``f``) against the reference's run of their
    level."""
    lim = config["reference"]
    iv = fields.get("pbft_block_interval_ms", 50)
    n = fields["n"]
    level_of = {f: i for i, f in enumerate(f_values(config, n))}
    out = [
        checks.exact("rows_off_the_grid",
                     sum(1 for m in rows if m["f"] not in level_of), 0),
        checks.exact("rows_with_attack_keys",
                     sum(1 for m in rows if has_attack_keys(m)), len(rows)),
        checks.exact("reference_finality_shortfall_max", max(
            r["rounds_sent"] - r["blocks_final_all_nodes"] for r in refs), 0),
        checks.exact("reference_agreement_only_without_forgers", sum(
            1 for r in refs if r["agreement_ok"] != (r["n_byzantine"] == 0)), 0),
    ]
    pairs = [(m, refs[level_of[m["f"]]]) for m in rows
             if m["f"] in level_of and has_attack_keys(m)]
    if not pairs:
        return out
    calm = [(m, r) for m, r in pairs if m["view_changes"] == 0]

    def gap(key, some):
        return max((abs(m[key] - r[key]) for m, r in some), default=0.0)

    out += [
        checks.at_least("rows_without_view_change", len(calm), 1),
        checks.exact("rounds_sent_vs_reference_max",
                     gap("rounds_sent", calm), 0),
        checks.exact("blocks_final_vs_reference_max",
                     gap("blocks_final_all_nodes", calm), 0),
        checks.at_most("blocks_final_over_reference_max", max(
            m["blocks_final_all_nodes"] - r["blocks_final_all_nodes"]
            for m, r in pairs), 0),
        checks.exact("forged_commits_vs_reference_max",
                     gap("forged_commits", pairs), 0),
        checks.exact("agreement_vs_reference", sum(
            1 for m, r in pairs if m["agreement_ok"] != r["agreement_ok"]), 0),
        checks.exact("forged_everywhere_vs_reference", sum(
            1 for m, r in pairs if (m["forged_commit_nodes"] == n)
            != (r["forged_commit_nodes"] == r["n"])), 0),
        checks.at_most("forged_commit_gap_ms_max",
                       gap("forged_commit_ms", pairs), lim["forged_limit_ms"]),
        checks.at_most("ttf_gap_ms_max",
                       gap("mean_time_to_finality_ms", calm),
                       lim["ttf_limit_ms"]),
        checks.at_most("commit_tail_gap_ms_max", max(
            (abs(checks.commit_tail(m, iv) - checks.commit_tail(r, iv))
             for m, r in calm), default=0.0), lim["tail_limit_ms"]),
    ]
    return out


def off_solo(row: dict, solo: dict, tick_limit_ms: float) -> bool:
    """Does a batched row differ from the solo run of its (f, seed) by more
    than the sampler grants: any count, or a time by more than the limit."""
    return (any(row.get(k) != solo.get(k) for k in COUNT_KEYS)
            or any(abs(row[k] - solo[k]) > tick_limit_ms for k in TIME_KEYS))


def rows_near_solo(rows: list[dict], solo: list[dict],
                   tick_limit_ms: float) -> dict:
    return checks.exact("rows_off_solo", sum(
        1 for a, b in zip(rows, solo) if off_solo(a, b, tick_limit_ms)), 0)
