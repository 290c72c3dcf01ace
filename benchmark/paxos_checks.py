"""The comparisons that decide ``correct`` in the cells of the Paxos-over-a-
relay deployment: records in ``checks.py``'s shape, made with its ``exact`` /
``at_most`` / ``at_least``.

The plain reference is ``reference/paxos_gossip_engine.py``, run after the
window on the deployment's own fields at the cell's own size, over the
overlay the program's builder makes from the configuration's seed (the
digraph is data of the deployment, handed to the reference as rows of ids).
The two sides cannot share a draw, so:

- counts are exact: every acceptor executes, one command is decided and it
  is a proposer's own, nobody gives up, on both sides;
- timing is compared on milestones that do not depend on which window won
  (``models/paxos.MILESTONES``): the window of a proposer that met no
  competing ticket, the winner's commit flood to its last acceptor, the first
  execute's lag behind the winner's commit request.  Each is the MEDIAN over
  the window's rows against the reference's run, within the limits of the
  configuration file's ``reference`` block (set from chip readings that
  ``PERF.md`` records).  The winner's own window is held to a wider limit: a
  window that wins at t = 0 against two competing tickets waits for more
  replies than one that wins after a retry, and either side's run may be of
  either kind.

A reference run is *calm* when its winner's commit flood executed every
acceptor within the flood's horizon and it has a window without competition;
one that is not (a later proposer's ticket overtook the winner's between its
propose and its commit: a matter of the seed) is run again on the next seed,
at most ``reference.tries`` times.

Every row has to carry every milestone (``rows_with_timing`` must equal the
number of rows); the ``mesh_solo`` driver refuses a program without the
tuple before it builds.
"""

from __future__ import annotations

import statistics

import checks

TIMING_KEYS = ("winner_window_ms", "commit_flood_ms", "first_execute_lag_ms",
               "solo_window_ms")


def overlay_of(fields: dict) -> list:
    """The deployment's digraph as rows of out-neighbour ids: what the
    program's builder makes from the configuration's seed."""
    from blockchain_simulator_tpu.ops import topology

    return topology.kregular_out_neighbors(
        fields["n"], fields["degree"], fields.get("seed", 0)).tolist()


def flood_horizon_ms(fields: dict) -> int:
    """The longest a flood can take to reach a node: ``gossip_hops + 1``
    legs of at most ``link + delay_hi - 1`` ms."""
    return (fields["gossip_hops"] + 1) * (
        fields.get("link_delay_ms", 3) + fields.get("paxos_delay_hi", 50) - 1)


def calm(m: dict, fields: dict) -> bool:
    return (m["n_committed_proposers"] >= 1 and m["solo_window_ms"] >= 0
            and m["first_execute_lag_ms"] == 0
            and 0 <= m["commit_flood_ms"] <= flood_horizon_ms(fields))


def reference_milestones(config: dict, fields: dict, seed: int) -> dict:
    """The reference's run of this deployment's fields at the cell's own
    size: the first calm one of ``reference.tries`` seeds (the last tried if
    none is), with ``tries`` beside it."""
    engine = checks._engine(config["reference"]["engine"])
    nbrs = overlay_of(fields)
    tries = int(config["reference"].get("tries", 1))
    for i in range(tries):
        m = engine.run(fields, seed + i, nbrs)
        m["tries"] = i + 1
        if calm(m, fields):
            break
    return m


def guarantees(rows: list[dict], fields: dict) -> list[dict]:
    """What every run of the window must satisfy whatever its seed."""
    n, p = fields["n"], fields.get("paxos_n_proposers", 3)
    return [
        checks.exact("agreement_violations",
                     sum(1 for m in rows if not m.get("agreement_ok")), 0),
        checks.exact("acceptor_executes_shortfall_max",
                     max(n - m["acceptor_executes"] for m in rows), 0),
        checks.exact("decided_out_of_range",
                     sum(1 for m in rows
                         if not 0 <= m["decided_command"] < p), 0),
        checks.exact("gave_up_max", max(m["gave_up"] for m in rows), 0),
        checks.at_least("committed_proposers_min",
                        min(m["n_committed_proposers"] for m in rows), 1),
    ]


def _median_gap(rows: list[dict], ref: dict, key: str) -> float:
    got = [m[key] for m in rows if m.get(key, -1.0) >= 0]
    if not got or ref.get(key, -1.0) < 0:
        return float("inf")
    return abs(statistics.median(got) - ref[key])


def against_reference(rows: list[dict], ref: dict, config: dict,
                      fields: dict) -> list[dict]:
    lim = config["reference"]
    n, p = fields["n"], fields.get("paxos_n_proposers", 3)
    out = [
        checks.exact("reference_agreement_ok", bool(ref["agreement_ok"]), True),
        checks.exact("reference_acceptor_executes", ref["acceptor_executes"], n),
        checks.exact("reference_gave_up", ref["gave_up"], 0),
        checks.exact("reference_decided_in_range",
                     0 <= ref["decided_command"] < p, True),
        checks.exact("reference_calm", calm(ref, fields), True),
        checks.exact("acceptor_executes_vs_reference_max", max(
            abs(m["acceptor_executes"] - ref["acceptor_executes"])
            for m in rows), 0),
    ]
    timed = [m for m in rows if all(k in m for k in TIMING_KEYS)]
    out.append(checks.exact("rows_with_timing", len(timed), len(rows)))
    if not timed:
        return out
    out.append(checks.at_least("rows_with_solo_window", sum(
        1 for m in timed if m["solo_window_ms"] >= 0), 1))
    out.append(checks.at_most("solo_window_gap_ms", _median_gap(
        timed, ref, "solo_window_ms"), lim["window_limit_ms"]))
    out.append(checks.at_most("commit_flood_gap_ms", _median_gap(
        timed, ref, "commit_flood_ms"), lim["flood_limit_ms"]))
    out.append(checks.at_most("first_execute_lag_gap_ms", _median_gap(
        timed, ref, "first_execute_lag_ms"), lim["lag_limit_ms"]))
    out.append(checks.at_most("winner_window_gap_ms", _median_gap(
        timed, ref, "winner_window_ms"), lim["winner_limit_ms"]))
    return out
