"""The plain reference under link classes: a per-message PBFT simulator whose
links differ by the classes of their two ends.

Every PRE_PREPARE, PREPARE, PREPARE_RES, COMMIT and VIEW_CHANGE is one event
of its own.  A message sent at ``t`` from ``src`` to ``dst`` arrives at

    t + matrix[class(src)][class(dst)] + U{delay_lo..delay_hi-1} (+ ser)

with one jitter draw a message from this file's own ``random.Random`` stream
and ``ser`` the serialization of a block (PRE_PREPARE only), and is handled
there by upstream's ``HandleRead`` logic (pbft-node.cc, restated below as
``benchmark/reference/engine.cpp`` restates it, clean fidelity).  Nothing is
aggregated, short-circuited or batched: a PREPARE really arrives at its peer,
and the peer's PREPARE_RES is a message of its own.

The event queue is a priority queue over whole ticks: a bucket a tick, first
in first out inside one, which pops in the order a ``(time, seq)`` heap would
(times are whole ms and every delay is at least one).  At n = 209 a 40-round
run is 7 million events and about 20 s of plain Python.

Nothing here imports the program under test: a deployment arrives as the
plain field dict of a ``benchmark/configs/*.json`` file (``link_classes``,
``link_class_delay_ms``), and upstream's constants are restated below.
"""

from __future__ import annotations

import random

# upstream's constants (pbft-node.cc, blockchain-simulator.cc)
UPSTREAM = {
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
    "pbft_max_slots": 64,
    "fidelity": "clean",
    "quorum_rule": "n2",
}

PRE_PREPARE, PREPARE, PREPARE_RES, COMMIT, VIEW_CHANGE = range(5)


def thresholds(n: int, rule: str) -> tuple[int, int]:
    """(prepare, commit) votes needed, ``>=``: upstream's N/2 and N/2 + 1
    (pbft-node.cc:231,248), or PBFT's own 2f + 1 with f = (n - 1) // 3."""
    if rule == "2f1":
        need = 2 * ((n - 1) // 3) + 1
        return need, need
    return n // 2, n // 2 + 1


def run(fields: dict, seed: int, **override) -> dict:
    """One full-mesh PBFT run of a deployment's fields under its link
    classes; returns the milestone dict of ``pbft_engine.run``."""
    f = {**UPSTREAM, **fields, **override}
    if f.get("protocol", "pbft") != "pbft" or f.get("topology", "full") != "full":
        raise ValueError("the reference engine here covers full-mesh PBFT")
    if f["fidelity"] != "clean":
        raise ValueError("the reference engine here is clean fidelity")
    faults = f.get("faults") or {}
    if faults.get("drop_prob") or faults.get("byz_forge"):
        raise ValueError("the reference engine here has no drops and no forgers")
    n, sim_ms = int(f["n"]), int(f["sim_ms"])
    counts = list(f.get("link_classes") or [n])
    matrix = [list(r) for r in f.get("link_class_delay_ms")
              or [[f.get("link_delay_ms", 3)]]]
    if sum(counts) != n or len(matrix) != len(counts):
        raise ValueError("link_classes must sum to n, one matrix row a class")
    cls = [k for k, c in enumerate(counts) for _ in range(c)]
    prop = [[matrix[cls[i]][cls[j]] for j in range(n)] for i in range(n)]

    ser = 0
    if f["model_serialization"]:
        block_bytes = (f["pbft_tx_speed"] * f["pbft_block_interval_ms"]
                       // 1000) * f["pbft_tx_size"]
        ser = int(block_bytes * 8 / (f["link_rate_mbps"] * 1e6) * 1000 + 0.999)
    lo, span = f["pbft_delay_lo"], f["pbft_delay_hi"] - f["pbft_delay_lo"]
    interval, slots = f["pbft_block_interval_ms"], f["pbft_max_slots"]
    max_rounds = min(f["pbft_max_rounds"], slots)
    vc_num, vc_den = f["pbft_view_change_num"], f["pbft_view_change_den"]
    need_p, need_c = thresholds(n, f["quorum_rule"])
    n_alive = n - max(int(faults.get("n_crashed", 0)), 0)
    n_honest = n_alive - int(faults.get("n_byzantine", 0))
    alive = [i < n_alive for i in range(n)]
    honest = [i < n_honest for i in range(n)]

    rng = random.Random(int(seed))
    rand = rng.random
    buckets: list = [[] for _ in range(sim_ms)]  # events at t >= sim_ms never run

    def send(now, kind, src, dst, a, b, extra=0):
        t = now + prop[src][dst] + lo + int(rand() * span) + extra
        if t < sim_ms:
            buckets[t].append((kind, dst, src, a, b))

    def bcast(now, kind, src, a, b, extra=0):
        row = prop[src]
        base = now + lo + extra
        for dst in range(n):
            if dst != src:
                t = base + row[dst] + int(rand() * span)
                if t < sim_ms:
                    buckets[t].append((kind, dst, src, a, b))

    view = [1] * n
    leader = [0] * n
    next_n = [0] * n
    rounds_sent = [0] * n
    block_num = [0] * n
    view_changes = [0] * n
    tx_val = [[-1] * slots for _ in range(n)]
    prepare_vote = [[0] * slots for _ in range(n)]
    commit_vote = [[0] * slots for _ in range(n)]
    commit_tick = [[-1] * slots for _ in range(n)]
    prep_sent = [[False] * slots for _ in range(n)]
    committed = [[False] * slots for _ in range(n)]
    propose_tick = [-1] * slots
    delivered = 0

    for now in range(sim_ms):
        # messages first, timers after: a tick's arrivals are handled before
        # the block it sends (a VIEW_CHANGE that lands on a block tick
        # decides who sends)
        for kind, me, src, a, b in buckets[now]:
            delivered += 1
            if not alive[me]:
                continue
            if kind == PREPARE:  # unconditional SUCCESS reply (pbft-node.cc:212-221)
                if honest[me]:
                    send(now, PREPARE_RES, me, src, a, b)
            elif kind == PREPARE_RES:  # count -> COMMIT broadcast (:223-240)
                votes = prepare_vote[me]
                votes[b] += 1
                if votes[b] >= need_p and not prep_sent[me][b]:
                    prep_sent[me][b] = True
                    votes[b] = 0
                    if honest[me]:
                        bcast(now, COMMIT, me, a, b)
            elif kind == COMMIT:  # count -> finality (:241-265)
                votes = commit_vote[me]
                votes[b] += 1
                if votes[b] >= need_c and not committed[me][b]:
                    votes[b] = 0
                    commit_tick[me][b] = now
                    committed[me][b] = True
                    block_num[me] += 1
            elif kind == PRE_PREPARE:  # store, broadcast PREPARE (:193-211)
                if b < slots:
                    tx_val[me][b] = b
                    next_n[me] = max(next_n[me], b + 1)
                    bcast(now, PREPARE, me, a, b)
            else:  # VIEW_CHANGE: adopt (v, leader) (:271-280)
                view[me], leader[me] = a, b
        buckets[now] = None
        if now == 0 or now % interval:
            continue
        for me in range(n):  # SendBlock (pbft-node.cc:372-411)
            if not alive[me] or leader[me] != me or next_n[me] >= max_rounds:
                continue
            slot = next_n[me]
            bcast(now, PRE_PREPARE, me, view[me], slot, ser)
            if propose_tick[slot] < 0:
                propose_tick[slot] = now
            rounds_sent[me] += 1
            next_n[me] += 1
            if int(rand() * vc_den) < vc_num:  # pbft-node.cc:401-403
                view[me] += 1
                leader[me] = (leader[me] + 1) % n
                view_changes[me] += 1
                bcast(now, VIEW_CHANGE, me, view[me], leader[me])

    rounds = max(next_n)
    final, ttf_sum, last = 0, 0.0, -1
    agree = True
    for s in range(min(rounds, slots)):
        nodes = [i for i in range(n) if alive[i]]
        vals = {tx_val[i][s] for i in nodes
                if committed[i][s] and tx_val[i][s] >= 0}
        agree = agree and len(vals) <= 1
        if propose_tick[s] >= 0 and all(committed[i][s] for i in nodes):
            at = max(commit_tick[i][s] for i in nodes)
            final += 1
            ttf_sum += at - propose_tick[s]
            last = max(last, at)
    return {
        "protocol": "pbft", "n": n, "rounds_sent": rounds,
        "leader_rounds_max": max(rounds_sent),
        "blocks_final_all_nodes": final, "block_num_max": max(block_num),
        "view_changes": sum(view_changes), "last_commit_ms": float(last),
        "mean_time_to_finality_ms": ttf_sum / final if final else -1.0,
        "delivered_msgs": delivered, "agreement_ok": agree,
    }
