"""The plain reference of the committee tier: N replicas as C committees of
m = N / C, PBFT to quorum inside every committee, and one combining step
over the committees.

Inside a committee nothing but that committee's replicas take part, so a
committee is one full-mesh PBFT cluster of m replicas: the per-message
event-heap engine of ``pbft_engine.py`` (``engine.cpp``: every PREPARE and
COMMIT one heap event, its own ``std::mt19937_64`` stream) is called per
committee at n = m, at the committee's own size, with a seed of its own.
The combining step is stated here in plain Python, as the deployment's
configuration file states it: every committee's representative reports the
time its committee's last final block committed (its *milestone*; a
committee with no block final on all its replicas reports none); the
hierarchy commits when a majority of committees, C // 2 + 1 of them, have
reported, one worst-case representative round trip later:

    outer_commit = (C // 2 + 1)-th smallest milestone + 2 * (one_way_hi - 1)

with ``one_way_hi - 1`` the largest one-way delay a message can draw: the
largest send delay of U{lo..hi-1} plus the link's propagation.

Nothing here imports the program under test.  A deployment arrives as the
plain field dict of a ``benchmark/configs/*.json`` file.

What the engine can afford: some 6 million heap events a committee at
m = 500 over 600 simulated ms (a few seconds each), so a caller asks for a
seeded sample of committees and holds every committee's *counts* to the
sample's: an undisturbed run's counts (rounds sent, blocks final on all
replicas) do not depend on the random stream, and the sample says so itself
(``counts_agree``).
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib
import random

_DIR = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=1)
def _pbft_engine():
    spec = importlib.util.spec_from_file_location(
        "bench_ref_pbft_engine_for_committees", _DIR / "pbft_engine.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


COUNT_KEYS = ("rounds_sent", "blocks_final_all_nodes")


def shape(fields: dict) -> tuple[int, int]:
    """(C, m) of a deployment's fields; refuses what is no committee tier."""
    if fields.get("topology") != "committee":
        raise ValueError("the committee reference needs topology='committee'")
    c, n = int(fields["committees"]), int(fields["n"])
    if c < 1 or n % c or n // c < 2:
        raise ValueError(f"{n} replicas do not split into {c} committees")
    return c, n // c


def inner_fields(fields: dict) -> dict:
    """One committee as a deployment of its own: m replicas on a full mesh,
    every protocol constant inherited."""
    _, m = shape(fields)
    out = {k: v for k, v in fields.items() if k != "committees"}
    out.update(n=m, topology="full")
    return out


def one_way_hi(fields: dict) -> int:
    """One past the largest one-way delay in ms: the send delay is drawn from
    U{lo..hi-1} and the link adds its propagation."""
    eng = _pbft_engine()
    f = {**eng.UPSTREAM, **fields}
    return f["pbft_delay_hi"] + f["link_delay_ms"]


def outer_quorum(committees: int) -> int:
    return committees // 2 + 1


def outer_rule(milestones: list, committees: int, hi: int) -> dict:
    """The combining step over the committees' milestones (-1: none)."""
    decided = sorted(t for t in milestones if t >= 0)
    quorum = outer_quorum(committees)
    trip = 0.0 if committees == 1 else float(2 * (hi - 1))
    return {
        "outer_quorum": quorum,
        "committees_decided": len(decided),
        "outer_round_ms": trip,
        "outer_commit_ms": float(decided[quorum - 1] + trip)
        if len(decided) >= quorum else -1.0,
    }


def milestone(row: dict) -> float:
    """A committee's report: the commit time of its last final block."""
    return float(row["last_commit_ms"]) \
        if row["blocks_final_all_nodes"] > 0 else -1.0


def sample_of(committees: int, k: int, seed: int) -> list[int]:
    """Which committees the engine runs: ``k`` of them, drawn from ``seed``,
    the first always among them (a committee's index changes nothing but its
    stream)."""
    k = max(min(k, committees), 1)
    rest = random.Random(seed).sample(range(1, committees), k - 1) \
        if committees > 1 else []
    return [0] + sorted(rest)


def run(fields: dict, seed: int, sample: int = 3, **override) -> dict:
    """The committee tier of ``fields`` with view changes as the caller gives
    them (``pbft_view_change_num=0`` for an undisturbed run): the sampled
    committees' milestone dicts by committee index, the counts every
    committee of an undisturbed run must show (and whether the sample agrees
    on them), and what :func:`outer_rule` needs of the deployment."""
    eng = _pbft_engine()
    f = {**fields, **override}
    c, m = shape(f)
    inner = inner_fields(f)
    rows = {}
    for i in sample_of(c, sample, seed):
        # a stream a committee: the engine's seed is 62 bits wide
        rows[i] = eng.run(inner, (int(seed) * 1_000_003 + i) & (2**62 - 1))
    first = next(iter(rows.values()))
    return {
        "committees": c,
        "committee_size": m,
        "rows": rows,
        "counts": {k: first[k] for k in COUNT_KEYS},
        "counts_agree": all(r[k] == first[k] for r in rows.values()
                            for k in COUNT_KEYS),
        "agreement_ok": all(bool(r["agreement_ok"]) for r in rows.values()),
        "one_way_hi": one_way_hi(f),
        "_interval": {**eng.UPSTREAM, **f}["pbft_block_interval_ms"],
    }
