"""The plain reference that forges: a per-message PBFT simulator with
Byzantine nodes, in pure Python.

The protocol is the one ``engine.cpp`` beside this file runs for
``pbft_engine`` (upstream pbft-node.cc: PRE_PREPARE -> PREPARE ->
PREPARE_RES -> COMMIT, thresholds N/2 at :231 and :248, one vote counted per
message with no per-sender dedup, a 50 ms block timer, a 1/100 view change),
restated here because that engine has no attack.  Every message is one event
with its own delay draw from ``random.Random(seed)``; events are kept in a
calendar of one FIFO per millisecond, which delivers them in the order a
(time, sequence) heap would.  Nothing here imports the program under test: a
deployment arrives as the plain field dict of a ``benchmark/configs/*.json``
file, and upstream's constants are restated below.

The attack (``faults.byz_forge``, upstream has no fault model at all; the
definition is ``utils/config.FaultConfig``'s, restated): the last
``n_byzantine`` nodes are Byzantine.  They receive and count like any node,
but cast no honest vote (they answer no PREPARE and broadcast no COMMIT of
their own), and on every block tick each of them broadcasts ``byz_copies``
COMMIT votes for slot ``pbft_max_slots - 1``, which no leader ever proposes.
With one vote counted per message, ``3 * f * j`` forged votes have reached a
node after block tick ``j``, so the forged slot becomes final once that
passes N/2.

The cost is O(N^2) events a round, so the engine runs a deployment's fields
at a node count the host can afford (``reference.n`` in the configuration
file), with the Byzantine count at the same fraction of N.  What it yields
does not depend on its random stream beyond a millisecond: rounds sent,
blocks final on all nodes, whether and at which tick the forged slot became
final on the last node, the commit tail and the mean time to finality.
"""

from __future__ import annotations

import random

# upstream's constants (SimConfig's defaults restate the same sources)
UPSTREAM = {
    "link_delay_ms": 3,            # blockchain-simulator.cc:24
    "link_rate_mbps": 3.0,         # blockchain-simulator.cc:23
    "model_serialization": True,
    "pbft_block_interval_ms": 50,  # pbft-node.cc:106
    "pbft_max_rounds": 40,         # pbft-node.cc:407
    "pbft_tx_size": 1000,          # pbft-node.cc:104
    "pbft_tx_speed": 1000,         # pbft-node.cc:105
    "pbft_delay_lo": 3,            # pbft-node.cc:66-69, U{3,4,5}
    "pbft_delay_hi": 6,
    "pbft_view_change_num": 1,     # pbft-node.cc:401
    "pbft_view_change_den": 100,
    "pbft_max_slots": 64,          # pbft-node.h:50
    "fidelity": "clean",
    "quorum_rule": "n2",
}

PRE_PREPARE, PREPARE, PREPARE_RES, COMMIT, VIEW_CHANGE = range(5)


def run(fields: dict, seed: int, **override) -> dict:
    """One full-mesh PBFT run of a deployment's fields with
    ``faults.n_byzantine`` forgers; returns the milestone dict."""
    f = {**UPSTREAM, **fields, **override}
    if f.get("protocol", "pbft") != "pbft" or f.get("topology", "full") != "full":
        raise ValueError("the reference engine here covers full-mesh PBFT")
    if f["quorum_rule"] != "n2":
        raise ValueError("the reference counts as upstream does: N/2, no dedup")
    faults = f.get("faults") or {}
    n, sim_ms = int(f["n"]), int(f["sim_ms"])
    n_byz = int(faults.get("n_byzantine", 0))
    forge = bool(faults.get("byz_forge", False))
    copies = int(faults.get("byz_copies", 3))
    slots, interval = int(f["pbft_max_slots"]), int(f["pbft_block_interval_ms"])
    max_rounds = min(int(f["pbft_max_rounds"]), slots)
    if forge and max_rounds >= slots:
        raise ValueError("the forged slot must be one no leader proposes")
    ser = 0
    if f["model_serialization"]:
        block_bytes = (f["pbft_tx_speed"] * interval // 1000) * f["pbft_tx_size"]
        ser = int(block_bytes * 8 / (f["link_rate_mbps"] * 1e6) * 1000 + 0.999)
    lo = f["pbft_delay_lo"] + f["link_delay_ms"]
    span = f["pbft_delay_hi"] - f["pbft_delay_lo"]
    vc_num, vc_den = f["pbft_view_change_num"], f["pbft_view_change_den"]
    clean = f["fidelity"] == "clean"
    quorum = n // 2
    honest = [i < n - n_byz for i in range(n)]
    rng = random.Random(int(seed))
    rand = rng.random

    # the window is the ticks 0 .. sim_ms - 1: what would land later is lost
    calendar: list[list] = [[] for _ in range(sim_ms)]

    def send(now: int, to: int, msg: tuple, extra: int = 0) -> None:
        t = now + lo + int(rand() * span) + extra
        if t < sim_ms:
            calendar[t].append((to, msg))

    def bcast(now: int, frm: int, msg: tuple, extra: int = 0) -> None:
        for to in range(n):
            if to != frm:
                send(now, to, msg, extra)

    v, leader, next_n = [1] * n, [0] * n, [0] * n
    prepare_vote = [[0] * slots for _ in range(n)]
    commit_vote = [[0] * slots for _ in range(n)]
    prep_sent = [[False] * slots for _ in range(n)]
    committed = [[False] * slots for _ in range(n)]
    commit_tick = [[-1] * slots for _ in range(n)]
    propose_tick = [-1] * slots
    view_changes = 0
    events = 0

    for now in range(1, sim_ms):
        if now % interval == 0:
            # every node's block timer (pbft-node.cc:372-411), then the
            # forgers' wave
            for i in range(n):
                if i == leader[i] and next_n[i] < max_rounds:
                    s = next_n[i]
                    bcast(now, i, (PRE_PREPARE, i, v[i], s), ser)
                    if propose_tick[s] < 0:
                        propose_tick[s] = now
                    next_n[i] += 1
                    if int(rand() * vc_den) < vc_num:
                        v[i] += 1
                        leader[i] = (leader[i] + 1) % n
                        view_changes += 1
                        bcast(now, i, (VIEW_CHANGE, i, v[i], leader[i]))
            if forge:
                for i in range(n):
                    if not honest[i]:
                        for _ in range(copies):
                            bcast(now, i, (COMMIT, i, v[i], slots - 1))
        for to, (kind, frm, a, s) in calendar[now]:
            events += 1
            if kind == PREPARE:
                # an honest peer answers SUCCESS whatever its state; a
                # Byzantine one answers FAILED, which is never counted
                if honest[to]:
                    send(now, frm, (PREPARE_RES, to, a, s))
            elif kind == PREPARE_RES:
                pv = prepare_vote[to]
                pv[s] += 1
                if pv[s] >= quorum and not (clean and prep_sent[to][s]):
                    prep_sent[to][s] = True
                    pv[s] = 0
                    if honest[to]:
                        bcast(now, to, (COMMIT, to, a, s))
            elif kind == COMMIT:
                cv = commit_vote[to]
                cv[s] += 1
                if cv[s] > quorum and not (clean and committed[to][s]):
                    cv[s] = 0
                    if commit_tick[to][s] < 0:
                        commit_tick[to][s] = now
                    committed[to][s] = True
            elif kind == PRE_PREPARE:
                if s < slots:
                    next_n[to] = max(next_n[to], s + 1)
                    bcast(now, to, (PREPARE, to, a, s))
            else:  # VIEW_CHANGE: adopt (v, leader), pbft-node.cc:271-280
                v[to], leader[to] = a, s
        calendar[now] = []

    rounds = max(next_n)
    final, ttf, last = 0, [], -1
    for s in range(min(rounds, slots)):
        if propose_tick[s] >= 0 and all(committed[i][s] for i in range(n)):
            done = max(commit_tick[i][s] for i in range(n))
            final += 1
            ttf.append(done - propose_tick[s])
            last = max(last, done)
    forged = [s for s in range(slots) if propose_tick[s] < 0
              and any(committed[i][s] for i in range(n))]
    forged_ticks = [commit_tick[i][s] for s in forged for i in range(n)
                    if committed[i][s]]
    return {
        "protocol": "pbft", "n": n, "n_byzantine": n_byz,
        "rounds_sent": rounds, "blocks_final_all_nodes": final,
        "view_changes": view_changes, "last_commit_ms": float(last),
        "mean_time_to_finality_ms": sum(ttf) / len(ttf) if ttf else -1.0,
        "forged_commits": len(forged),
        "forged_commit_ms": float(max(forged_ticks)) if forged else -1.0,
        "forged_commit_nodes": max(
            (sum(1 for i in range(n) if committed[i][s]) for s in forged),
            default=0),
        "agreement_ok": not forged,
        "delivered_msgs": events,
    }
