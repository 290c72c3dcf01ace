"""The plain reference for single-decree Paxos over a gossip relay: a
per-message event heap, nothing tensorized, nothing imported from the program
under test (``models/``, ``ops/``).  A deployment arrives as the plain field
dict of a ``benchmark/configs/*.json`` file and the overlay as data (the
``[N, deg]`` out-neighbour table the program's builder made from the
configuration's seed: the digraph is part of the deployment, not of either
implementation).

Every request travels edge by edge: a node that holds a copy with hops left
sends one copy to each of its out-neighbours, each with its own
``link + U{lo..hi-1}`` ms draw.  Replies go point to point to the proposer,
each with its own draw.  One event is one message (or one proposer's window
timer); times are whole milliseconds, as upstream's ``rand() % 50`` gives.

Protocol, from upstream ``paxos/paxos-node.cc`` (constants restated in
``UPSTREAM``): nodes ``0..P-1`` call ``requireTicket`` at t = 0 (:136-138);
an acceptor promises a ticket above its ``t_max`` (:177-197), accepts a
proposal whose ticket equals ``t_max`` (:199-221) and executes a commit whose
ticket and command equal what it stored (:222-247); per-message send delay
``rand() % 50`` ms (:397-400) over 3 ms links.

Departures from ``paxos-node.cc``, each the deployment's stated semantics
(``fidelity: clean`` on a relay; the program's module docstring gives the
same list):

1. **A relay.**  Upstream broadcasts to every peer; here a request floods
   over the k-out digraph with a hop budget (TTL): the origin's copies carry
   ``gossip_hops``, a receiver forwards with one less while any are left.  A
   node processes a request the first time it sees that (proposer, value),
   and forwards again any strictly fresher copy (same value, more hops left),
   so an early, nearly spent copy cannot truncate the flood.
2. **Quorum N/2 + 1 including the proposer**, per phase, in place of
   upstream's shared counter that closes at exactly N - 2 replies (which
   cannot close over a relay and mixes phases).  The proposer casts its own
   vote as an acceptor when it sends (upstream gets this through its echo
   loop only).
3. **Retry on a jittered window timeout alone** (``retry_timeout +
   U{0..timeout/2-1}`` ms from the opening of each phase's window), never
   early on failures: upstream has no timeout and wedges on a lost reply.
4. **Adoption of the value with the highest store ticket** among the
   promises seen in the window; upstream adopts whatever byte rides the
   reply that closed its window.
5. No echo-back (upstream reflects every packet to its sender, for ever).

Same-millisecond order is the heap's: requests before replies before
timers, then by insertion.  (The program serializes a tick's requests by
proposer and by kind; the two differ only on ties.)

What ``run`` returns are the counts of a run (who committed, who executed,
what was decided, retries) and the timing milestones the program names in
``models/paxos.MILESTONES``, computed here from this engine's own event
times.
"""

from __future__ import annotations

import heapq
import random

UPSTREAM = {
    "link_delay_ms": 3,            # blockchain-simulator.cc:24
    "paxos_n_proposers": 3,        # paxos-node.cc:136-138
    "paxos_delay_lo": 0,           # paxos-node.cc:397-400, rand() % 50
    "paxos_delay_hi": 50,
    "paxos_max_ticket": 120,       # the program's retry budget (no upstream
    # counterpart: its one-character ticket codec breaks past 9)
    "paxos_retry_timeout_ms": 600,
    "gossip_hops": 8,
    "degree": 16,
}

TICKET, PROPOSE, COMMIT, DONE = 0, 1, 2, 3
_REQ, _REPLY, _TIMER = 0, 1, 2  # same-millisecond order


def check_overlay(nbrs, n: int, degree: int) -> dict:
    """The overlay as the reference takes it: ``n`` rows of ``degree``
    out-neighbours, ids in range.  A row may name itself (a fixed point of
    one of the builder's permutation columns) or one neighbour twice: such
    an edge carries its message like any other and the receiver's dedup
    drops it, so they are counted and reported, not refused."""
    if len(nbrs) != n:
        raise ValueError(f"overlay has {len(nbrs)} rows, the deployment {n}")
    loops = dups = 0
    for i, row in enumerate(nbrs):
        if len(row) != degree:
            raise ValueError(f"row {i} has {len(row)} out-edges, not {degree}")
        if min(row) < 0 or max(row) >= n:
            raise ValueError(f"row {i} names a node outside 0..{n - 1}")
        loops += sum(1 for v in row if v == i)
        dups += degree - len(set(row))
    return {"self_loops": loops, "repeated_edges": dups}


def run(fields: dict, seed: int, nbrs, **override) -> dict:
    """One run of the deployment ``fields`` (a configuration file's field
    dict) over the overlay ``nbrs`` (a sequence of ``n`` rows of out-
    neighbour ids).  ``override`` lays single fields over it."""
    f = {**UPSTREAM, **fields, **override}
    n, p = int(f["n"]), int(f["paxos_n_proposers"])
    if f.get("topology") != "gossip" or f.get("fidelity", "clean") != "clean":
        raise ValueError("paxos_gossip_engine runs the clean protocol over a "
                         "gossip relay only")
    nbrs = [[int(v) for v in row] for row in nbrs]
    overlay = check_overlay(nbrs, n, int(f["degree"]))
    lo = int(f["link_delay_ms"]) + int(f["paxos_delay_lo"])
    hi = int(f["link_delay_ms"]) + int(f["paxos_delay_hi"])  # exclusive
    hops0, sim_ms = int(f["gossip_hops"]), int(f["sim_ms"])
    timeout, max_ticket = int(f["paxos_retry_timeout_ms"]), int(f["paxos_max_ticket"])
    majority = n // 2 + 1
    rng = random.Random(seed)
    rnd, span = rng.random, hi - lo
    delay = lambda: lo + int(rnd() * span)  # noqa: E731  U{lo..hi-1}

    # acceptors
    t_max = [0] * n
    command = [-1] * n
    t_store = [0] * n
    exec_ms = [-1] * n
    # dedup per (node, kind, proposer), flat: the value and the hops of the
    # best copy seen (a ticket request's value is its ticket, the others'
    # (ticket, command))
    seen_val = ([0] * p + [(0, -1)] * (2 * p)) * n
    seen_hops = [-1] * (3 * p * n)
    # proposers
    ticket = [0] * p
    phase = [TICKET] * p
    vs, vf = [0] * p, [0] * p
    proposal = list(range(p))
    adopt = [(0, -1)] * p  # (store ticket, command) of the best promise
    commit_ms = [-1] * p
    gave_up = [False] * p
    window = [0] * p       # id of the open window: a timer of a closed one is stale
    tk_send, cm_send = [-1] * p, [-1] * p

    heap: list = []
    seq = 0
    events = 0

    heappush, heappop = heapq.heappush, heapq.heappop

    def flood_from(node, t, kind, q, val, hops):
        nonlocal seq
        base = t + lo
        for v in nbrs[node]:
            seq += 1
            heappush(heap, (base + int(rnd() * span), _REQ, seq, v, kind, q,
                           val, hops))

    def execute(node, t):
        if exec_ms[node] < 0:
            exec_ms[node] = t

    def open_window(q, t, kind, val):
        """Proposer ``q`` sends a request: its own vote, the flood's origin,
        a fresh timer."""
        nonlocal seq
        me = q
        if kind == TICKET:
            ok = val > t_max[me]
            adopt[q] = (t_store[me], command[me]) if ok and command[me] >= 0 \
                else (0, -1)
            if ok:
                t_max[me] = val
            tk_send[q] = t
        elif kind == PROPOSE:
            tkt, cmd = val
            ok = tkt == t_max[me]
            if ok:
                command[me], t_store[me] = cmd, tkt
        else:
            tkt, cmd = val
            ok = tkt == t_store[me] and cmd == command[me]
            if ok:
                execute(me, t)
            cm_send[q] = t
        vs[q], vf[q] = (1, 0) if ok else (0, 1)
        # the origin holds the full-TTL copy: no loopback copy is fresher
        at = (me * 3 + kind) * p + q
        seen_val[at], seen_hops[at] = val, hops0
        flood_from(me, t, kind, q, val, hops0)
        window[q] += 1
        seq += 1
        heappush(heap, (t + timeout + rng.randrange(max(timeout // 2, 1)),
                        _TIMER, seq, q, window[q]))

    for q in range(p):  # paxos-node.cc:136-138
        ticket[q] = 1
        open_window(q, 0, TICKET, 1)

    while heap and heap[0][0] < sim_ms:
        ev = heappop(heap)
        t, kind = ev[0], ev[1]
        events += 1
        if kind == _REQ:
            _, _, _, node, rk, q, val, hops = ev
            at = (node * 3 + rk) * p + q
            sv = seen_val[at]
            fresh = val > sv
            if not (fresh or (val == sv and hops > seen_hops[at])):
                continue
            seen_val[at], seen_hops[at] = val, hops
            if hops > 0:
                flood_from(node, t, rk, q, val, hops - 1)
            if not fresh:
                continue
            # first sighting of this (proposer, value): act as an acceptor
            payload = None
            if rk == TICKET:
                ok = val > t_max[node]
                if ok:
                    if command[node] >= 0:
                        payload = (t_store[node], command[node])
                    t_max[node] = val
            elif rk == PROPOSE:
                tkt, cmd = val
                ok = tkt == t_max[node]
                if ok:
                    command[node], t_store[node] = cmd, tkt
            else:
                tkt, cmd = val
                ok = tkt == t_store[node] and cmd == command[node]
                if ok:
                    execute(node, t)
            seq += 1
            heappush(heap, (t + delay(), _REPLY, seq, q, rk, ok, payload))
        elif kind == _REPLY:
            _, _, _, q, rk, ok, payload = ev
            if payload is not None and payload > adopt[q]:
                adopt[q] = payload
            if gave_up[q] or phase[q] != rk:
                continue  # a reply of another phase's type does not count
            if ok:
                vs[q] += 1
            else:
                vf[q] += 1
            if vs[q] < majority or not ok:
                continue
            if rk == TICKET:
                if adopt[q][1] >= 0:
                    proposal[q] = adopt[q][1]
                phase[q] = PROPOSE
                open_window(q, t, PROPOSE, (ticket[q], proposal[q]))
            elif rk == PROPOSE:
                phase[q] = COMMIT
                open_window(q, t, COMMIT, (ticket[q], proposal[q]))
            else:
                phase[q] = DONE
                commit_ms[q] = t  # CLIENT COMMIT SUCCESS, paxos-node.cc:339
                window[q] += 1
        else:
            _, _, _, q, wid = ev
            if wid != window[q] or phase[q] == DONE or gave_up[q]:
                continue
            if ticket[q] >= max_ticket:
                gave_up[q] = True
                continue
            ticket[q] += 1  # requireTicket, paxos-node.cc:511-518
            phase[q] = TICKET
            open_window(q, t, TICKET, ticket[q])

    winners = [q for q in range(p) if commit_ms[q] >= 0]
    winner = min(winners, key=lambda q: commit_ms[q]) if winners else -1
    executed = [i for i in range(n) if exec_ms[i] >= 0]
    cmds = sorted({command[i] for i in executed})
    agreement = len(cmds) <= 1 and all(proposal[w] == cmds[0] for w in winners) \
        if (cmds or not winners) else False
    out = {
        "n": n, "events": events, **overlay,
        "n_committed_proposers": len(winners), "winner": winner,
        "winner_commit_ms": float(commit_ms[winner]) if winners else -1.0,
        "last_commit_ms": float(max(commit_ms)) if winners else -1.0,
        "retries": sum(max(tk - 1, 0) for tk in ticket),
        "acceptor_executes": len(executed),
        "first_execute_ms": float(min(exec_ms[i] for i in executed))
        if executed else -1.0,
        "decided_command": cmds[0] if cmds else -1,
        "gave_up": sum(gave_up), "agreement_ok": bool(agreement),
        "winner_window_ms": -1.0, "commit_flood_ms": -1.0,
        "first_execute_lag_ms": -1.0, "solo_window_ms": -1.0,
        "window_ms": [float(commit_ms[q] - tk_send[q]) if commit_ms[q] >= 0
                      else -1.0 for q in range(p)],
    }
    if winners:
        out["winner_window_ms"] = float(commit_ms[winner] - tk_send[winner])
        if executed:
            out["commit_flood_ms"] = float(
                max(exec_ms[i] for i in executed) - cm_send[winner])
            out["first_execute_lag_ms"] = float(
                out["first_execute_ms"] - cm_send[winner])
        for w in winners:
            rest = [q for q in range(p) if q != w]
            if all(0 <= commit_ms[q] < tk_send[w] for q in rest):
                out["solo_window_ms"] = float(commit_ms[w] - tk_send[w])
    return out
