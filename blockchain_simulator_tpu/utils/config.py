"""Typed simulation configuration.

The reference hard-codes every operating constant (see SURVEY.md §5 "Config"):
N=8 (blockchain-simulator.cc:67), 3 Mbps / 3 ms links (blockchain-simulator.cc:22-24),
port 7071, PBFT tx_size/tx_speed/timeout (pbft-node.cc:102-107), Raft election
window / heartbeat (raft-node.cc:69-72,80), Paxos proposer set {0,1,2}
(paxos-node.cc:136), per-protocol random send delays, stop thresholds 40/50
blocks.  Every one of those numbers is a field here, with the reference value
as the default.

Time is discretized into 1 ms ticks (fine enough to resolve the 0-6 ms /
0-50 ms delay distributions and the 50 ms timers of the reference).
All delay fields are expressed in ticks (= ms).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Fault-injection configuration (a capability the reference lacks entirely;
    its only fault-like mechanisms are PBFT's random view change, pbft-node.cc:401-403,
    and Raft's election timeout, raft-node.cc:114).

    All masks are derived deterministically from the seed at init time.
    """

    # Fraction of nodes that are crashed from t=0 (never send, never process).
    crash_frac: float = 0.0
    # Number of crashed nodes (overrides crash_frac when >= 0). Crashed nodes
    # are chosen as the *last* ids so proposers/leader-0 stay alive by default.
    n_crashed: int = -1
    # Per-message drop probability on every edge.
    drop_prob: float = 0.0
    # Number of Byzantine nodes (vote-flippers): their SUCCESS votes are
    # delivered as FAILED and vice versa. Chosen as the last ids.
    n_byzantine: int = 0
    # Active Byzantine attack (PBFT): forgers broadcast COMMIT votes for a
    # slot no honest leader ever proposed (the last slot index).  With the
    # reference's counting — no per-sender vote dedup, SURVEY.md quirk #2 —
    # each forger's vote counts ``byz_copies`` times, so f forgers muster
    # f*byz_copies forged votes; a ``quorum_rule="2f1"`` node deduplicates by
    # sender id, capping each forger at one counted vote.
    byz_forge: bool = False
    byz_copies: int = 3
    # A crash schedule: faults that happen DURING the run (Raft with terms;
    # models/raft.py "Crash schedule").  Structure static and hashable, the
    # times a draw: crash k of a group (k = 0..crashes-1) falls at tick
    # ``first_ms + k * period_ms + phase``, ``phase`` one draw a group from
    # U{0..period_ms-1} off the group's init key, and hits whichever node of
    # the group is an alive leader on that tick (none: the crash is recorded
    # as having found no leader and kills nobody); the node is back
    # ``downtime_ms`` later as a follower.  ``crashes = 0``: no schedule,
    # and a program built without one carries no leaf and no operation for
    # it.  Every arm that cannot run one refuses it by name
    # (models/raft.check_schedule).
    crashes: int = 0
    first_ms: int = 0
    period_ms: int = 0
    downtime_ms: int = 0

    def __post_init__(self):
        if self.crashes < 0:
            raise ValueError(f"faults.crashes={self.crashes} must be >= 0")
        if self.crashes and not (
                self.first_ms >= 0 and 0 < self.downtime_ms < self.period_ms):
            raise ValueError(
                f"a crash schedule needs first_ms >= 0 and 0 < downtime_ms < "
                f"period_ms (a killed node is back before the next kill), got "
                f"first_ms={self.first_ms} period_ms={self.period_ms} "
                f"downtime_ms={self.downtime_ms}"
            )

    def resolved_n_crashed(self, n: int) -> int:
        if self.n_crashed >= 0:
            return min(self.n_crashed, n)
        return int(self.crash_frac * n)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Full, hashable (static under jit) simulation configuration."""

    # --- core ---------------------------------------------------------------
    protocol: str = "pbft"  # runtime-selectable (the reference's compile-time
    # switch at network-helper.cc:17 becomes a flag; SURVEY.md §1)
    n: int = 8  # cluster size (blockchain-simulator.cc:67)
    sim_ms: int = 10_000  # app window 0-10 s (blockchain-simulator.cc:54-55)
    seed: int = 0

    # --- network model ------------------------------------------------------
    link_delay_ms: int = 3  # p2p channel Delay (blockchain-simulator.cc:24)
    link_rate_mbps: float = 3.0  # p2p channel DataRate (blockchain-simulator.cc:23)
    # If True (default — faithful to the reference's timing), add
    # ceil(bytes*8/rate) serialization time to block-carrying messages: the
    # reference's 50 KB PBFT blocks take ~136 ms on its 3 Mbps links
    # (blockchain-simulator.cc:22-24, pbft-node.cc:377-380) and its 20 KB
    # Raft proposals ~54 ms (raft-node.cc:409) — the dominant timing term of
    # the system being reproduced.  Simplification (documented divergence):
    # links are NOT queued — serialization is a constant per-message latency,
    # whereas ns-3 queues back-to-back packets per link.  At the reference
    # PBFT defaults this is a REAL divergence: a 50 KB block serializes
    # ~136 ms but blocks depart every 50 ms, so the upstream's per-link
    # queues grow ~86 ms per round and its time-to-finality drifts linearly
    # (quantified in tests/test_fidelity.py via queued_links below).  Set
    # False to model propagation + the explicit random scheduling delay only
    # (the round-blocked PBFT fast path requires this).
    model_serialization: bool = True
    # ns-3-exact queued transport: each directed link is a serial 3 Mbps
    # pipe — a packet transmits when the link is free, occupies it for its
    # serialization time, then propagates; small votes queue behind blocks
    # on the same link.  Modeled per-edge by the C++ engine
    # (engine.cpp:198-215, all protocols) and by the tensorized engines via
    # per-destination busy registers for the leader's block channel: pbft
    # routes queued blocks through per-destination FIFOs (models/pbft.py —
    # its backlog is unbounded), raft keeps them on rings widened by the
    # bounded (ser - hb) * rounds backlog and queues plain heartbeats behind
    # in-flight proposals (models/raft.py).  4-byte vote/control unicast
    # traffic keeps constant latency — a documented divergence: a sender's
    # own votes never queue behind its in-flight blocks, which moves no
    # milestone since thresholds never hinge on the one leader vote;
    # tests/test_fidelity.py pins both engines against each other.  Paxos
    # messages are all 3-4 bytes, so queued == constant-latency there
    # (accepted as a bit-exact no-op).  The mixed shard sim refuses the flag.
    queued_links: bool = False
    # Link classes (ROADMAP R4; ops/linkclass.py): where the replicas are.
    # ``link_classes`` gives the node count of each class (a rack, a region,
    # a continent), ids contiguous in that order and summing to ``n``;
    # ``link_class_delay_ms`` is the K x K matrix of one-way propagation in
    # ms, row = the sender's class, column = the receiver's, and takes
    # ``link_delay_ms``'s place between and inside classes: a one-way delay
    # is ``matrix[class(i)][class(j)]`` + the protocol's jitter draw (+ the
    # block's serialization, as ever).  Lists freeze to tuples, so a config
    # built from JSON hashes and equals one built from tuples.  Empty (the
    # default): one scalar link latency, and a program built without classes
    # carries no leaf and no operation for them.  Per-edge PBFT on the full
    # mesh runs them, flat and under the lane batch; every other arm refuses
    # them by name (ops/linkclass.check_arms).
    link_classes: tuple = ()
    link_class_delay_ms: tuple = ()

    # --- topology -----------------------------------------------------------
    # The runtime topology axis (topo/): how the N nodes are wired.
    # "full"      — the reference's full mesh (blockchain-simulator.cc:34-51);
    #               "dense" is an accepted alias, normalized to "full" so the
    #               registry key / config hash is one spelling.
    # "gossip"    — random k-out digraph over which block/control messages
    #               FLOOD with a hop TTL (BASELINE config 3; the pre-topo/
    #               spelling "kregular" meant this relay mode).
    # "kregular"  — seeded circulant k-regular overlay with DIRECT
    #               neighbor-index delivery: per-tick messages are gathered
    #               through [N, k+1] in/out tables (topo/spec.py,
    #               ops/gatherdeliv.py) instead of dense N x N edge tensors —
    #               O(N*k) memory, and at degree k = N-1 bit-equal to the
    #               full mesh (the sorted full-overlay table is the identity
    #               permutation, so the same threefry draws are consumed).
    # "committee" — two-level hierarchy: the protocol runs INSIDE each of
    #               ``committees`` equal committees (lax.map over the stacked
    #               committee axis, O(N * n/committees) memory), then an
    #               outer aggregate step over committee representatives
    #               (topo/committee.py).
    topology: str = "full"
    degree: int = 16  # out-degree: gossip flood fan-out / kregular overlay k
    gossip_hops: int = 8  # flood TTL; must cover the graph diameter (~log_deg N)
    committees: int = 4  # committee count when topology == "committee"
    topo_seed: int = 0  # kregular overlay-builder seed — deliberately separate
    # from the run seed, so fault/seed sweeps over one overlay share ONE
    # compiled program (the overlay is topology *structure*, not randomness)

    # --- execution backend --------------------------------------------------
    # "edge": exact per-edge delay sampling (O(N^2) work per active tick).
    # "stat": statistically-exact aggregated delivery — per-receiver bucket
    #         counts drawn from binomial/multinomial chains (O(N·B)); valid for
    #         full-mesh count-consumed channels; the 100k-node path.
    delivery: str = "edge"
    # Binomial sampler for "stat" delivery bucket counts (ops/delay.py):
    # "exact"  — BTRS rejection sampling (jax.random.binomial).
    # "normal" — Gaussian approximation: ~6x fewer elementwise passes; counts
    #            still sum exactly (every message delivered exactly once),
    #            only the spread across delay buckets is approximate with
    #            relative error O(1/sqrt(count)).
    # "auto"   — "normal" when n >= 4096 (where the error is negligible and
    #            the tick loop is sampler-bound), else "exact".
    stat_sampler: str = "auto"
    # Per-edge integer delay sampler for the *edge* paths (ops/delay.py
    # sample_edge_delays — dense delivery, gossip forwarding):
    # "threefry" — jax.random.randint on the caller's threefry key: the
    #              historical stream every bit-pinned edge-path test rides.
    # "rbg"      — the same exact-uniform integer map fed by XLA's
    #              RngBitGenerator (the ops/delay._fast_normal trick): far
    #              cheaper bit generation on XLA:CPU, pure integer ops —
    #              bit-stable across unbatched compilations (jit, lax.map
    #              lanes, mesh bodies), though NOT under vmap batching
    #              (RngBitGenerator is not batch-invariant; same caveat
    #              class as the "normal" stat mode — see ops/delay.py).
    #              Power-of-two spans bit-slice each word into two
    #              exactly-uniform 16-bit draws.  A DIFFERENT stream than
    #              "threefry" (same distribution), so flipping the toggle
    #              moves seed-pinned trajectories.
    # "auto"     — "rbg" when n >= 4096 (edge tensors are O(N^2): the
    #              sampler dominates the tick), else "threefry".
    edge_sampler: str = "threefry"
    # Stepping granularity of the simulation loop:
    # "tick"  — the general engine: one scan step per 1 ms tick (always valid).
    # "round" — PBFT fast path: one scan step per block interval
    #           (models/pbft_round.py); requires full-mesh stat delivery, no
    #           byz_forge/queued links, drops only with view changes off (and
    #           the exact vote table), and the message wave — including the
    #           constant serialization offset when modeled — closing inside
    #           one block interval (pbft_round.eligible).
    # "auto"  — "round" when eligible and n >= 4096 (where the tick engine's
    #           per-tick ring traffic dominates), else "tick".
    schedule: str = "auto"
    # "reference": replicate the reference's observable quirks (N/2 thresholds,
    #              reset-on-threshold vote counters, never-re-armed Raft
    #              election timer, N-2 Paxos reply counting).
    # "clean":     documented fixes (latched commits, re-armed timers, N-1
    #              counting, highest-command adoption).
    fidelity: str = "clean"
    # Quorum rule for PBFT/Raft vote thresholds (SURVEY.md quirk #2; BASELINE
    # config 4 sweeps f up to n/3, where the reference's simple-majority rule
    # is not Byzantine-safe):
    # "n2":  the reference's thresholds — PBFT prepare >= N/2, commit > N/2
    #        (pbft-node.cc:231,248), Raft votes+self > N/2 (raft-node.cc:209)
    #        — and no per-sender vote deduplication.
    # "2f1": Byzantine-safe 2f+1 quorum with f = (n-1)//3, votes deduplicated
    #        per sender: any two quorums intersect in >= f+1 nodes, hence in
    #        an honest node, so no two honest nodes finalize different blocks
    #        and forged vote floods cannot reach quorum.
    quorum_rule: str = "n2"

    # --- PBFT (pbft-node.cc) -------------------------------------------------
    pbft_block_interval_ms: int = 50  # timeout=0.05 (pbft-node.cc:106)
    pbft_max_rounds: int = 40  # stop at n_round==40 (pbft-node.cc:407)
    pbft_tx_size: int = 1000  # 1 KB per tx (pbft-node.cc:104)
    pbft_tx_speed: int = 1000  # 1000 tx/s (pbft-node.cc:105)
    pbft_delay_lo: int = 3  # random send delay U{3,4,5} ms
    pbft_delay_hi: int = 6  # (pbft-node.cc:66-69), exclusive
    pbft_view_change_num: int = 1  # P(view change) = num/den per leader round
    pbft_view_change_den: int = 100  # (rand()%100==5, pbft-node.cc:401)
    pbft_max_slots: int = 64  # vote-table slots (tx[1000], pbft-node.h:50; 40
    # rounds only ever touch slots 0..39)
    pbft_window: int = 0  # live vote-state window W: per-node vote counters
    # live in [N, W] keyed by slot % W and are evicted on re-tenancy, capping
    # per-tick memory traffic at O(N·W) instead of O(N·S) (the 100k-node
    # scaling lever).  0 (default) = W = pbft_max_slots = exact full-table
    # mode.  A window is safe when W * block_interval far exceeds the message
    # horizon (validated in pbft.init); per-slot metrics are exact in both
    # modes (they fold into [S] accumulators either way).

    # --- Raft (raft-node.cc) -------------------------------------------------
    raft_heartbeat_ms: int = 50  # heartbeat_timeout=0.05 (raft-node.cc:80)
    raft_election_lo_ms: int = 150  # election timeout U[150,300) ms
    raft_election_hi_ms: int = 300  # (raft-node.cc:69-72)
    raft_delay_lo: int = 0  # random send delay U{0,1,2} ms
    raft_delay_hi: int = 3  # (raft-node.cc:63-66), exclusive
    raft_proposal_delay_ms: int = 1000  # proposals start 1 s after election
    # (raft-node.cc:216)
    raft_max_blocks: int = 50  # stop at blockNum>=50 (raft-node.cc:248)
    raft_max_rounds: int = 50  # stop proposals at round==50 (raft-node.cc:361)
    raft_tx_size: int = 200  # 200 B per tx (raft-node.cc:23)
    raft_tx_speed: int = 2000  # 2000 tx/s (raft-node.cc:24)
    # Raft WITH terms (Ongaro & Ousterhout, Figure 2, on upstream's message
    # set): a term a node carried by every message, one vote a TERM (not one
    # a run, quirk #6), a candidate or leader that sees a higher term steps
    # down, a grant re-arms the election timer (models/raft.py "Terms").
    # A protocol choice a deployment states, as ``quorum_rule`` is.  Needs
    # fidelity="clean"; implemented for delivery="edge" on the full mesh
    # (flat, and inside topology="committee"); every other arm refuses it
    # by name (models/raft.check_terms).
    raft_terms: bool = False

    # --- Paxos (paxos-node.cc) -----------------------------------------------
    paxos_delay_lo: int = 0  # random send delay U[0,50) ms
    paxos_delay_hi: int = 50  # (paxos-node.cc:397-400), exclusive
    paxos_n_proposers: int = 3  # nodes 0,1,2 propose at t=0 (paxos-node.cc:136)
    paxos_max_ticket: int = 120  # ticket values are single bytes in the
    # reference codec ('0'+t, paxos-node.cc:49-51); cap retries
    paxos_retry_timeout_ms: int = 250  # clean-fidelity failure detection: a
    # reply window unresolved after this long is abandoned and retried with a
    # higher ticket (~2x the 106 ms max round trip).  The reference has no
    # timeout — a lost reply wedges its proposer forever; reference fidelity
    # reproduces that stall.
    # CLIENT_PROPOSE external-client hook (paxos-node.cc:357-361): proposer
    # lane `paxos_client_node` (must be < paxos_n_proposers; -1 = none) does
    # not fire requireTicket at t=0 — a simulated client triggers it at
    # `paxos_client_ms` instead (mid-run injection; both engines).
    paxos_client_node: int = -1
    paxos_client_ms: int = 0

    # --- echo-back fidelity (quirk #1) ---------------------------------------
    # Reflect every received packet to its sender once (never re-reflect):
    # the bounded variant of the reference's unconditional echo
    # (pbft-node.cc:175, raft-node.cc:136, paxos-node.cc:158), which would
    # ping-pong forever.  Modeled by the C++ engine only — the tensorized
    # backends design echo away (models/pbft.py docstring) and refuse it.
    echo_back: bool = False

    # --- mixed-protocol shard sim (BASELINE config 5) ------------------------
    mixed_shards: int = 16  # number of raft shards; shard size = n // shards;
    # cross-shard PBFT runs over the shard representatives

    # --- faults --------------------------------------------------------------
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)

    # --- sharding ------------------------------------------------------------
    # Name of the mesh axis over which node state is sharded (None = unsharded).
    mesh_axis: Optional[str] = None

    # ------------------------------------------------------------------------
    def __post_init__(self):
        if self.topology == "dense":  # alias: one spelling in the registry key
            object.__setattr__(self, "topology", "full")
        self._freeze_link_classes()
        if self.protocol not in ("pbft", "raft", "paxos", "mixed"):
            raise ValueError(f"unknown protocol {self.protocol!r}")
        if self.delivery not in ("edge", "stat"):
            raise ValueError(f"unknown delivery mode {self.delivery!r}")
        if self.fidelity not in ("reference", "clean"):
            raise ValueError(f"unknown fidelity {self.fidelity!r}")
        if self.stat_sampler not in ("exact", "normal", "auto"):
            raise ValueError(f"unknown stat_sampler {self.stat_sampler!r}")
        if self.edge_sampler not in ("threefry", "rbg", "auto"):
            raise ValueError(f"unknown edge_sampler {self.edge_sampler!r}")
        if self.schedule not in ("tick", "round", "auto"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.quorum_rule not in ("n2", "2f1"):
            raise ValueError(f"unknown quorum_rule {self.quorum_rule!r}")
        if self.quorum_rule == "2f1" and self.fidelity != "clean":
            raise ValueError(
                "quorum_rule='2f1' requires fidelity='clean': vote dedup "
                "relies on the clean latches (each node votes once per slot); "
                "the reference's reset-on-threshold counters re-count"
            )
        if self.raft_terms:
            if self.protocol != "raft":
                raise ValueError(
                    "raft_terms is standalone Raft's (protocol='raft'); "
                    f"protocol {self.protocol!r} does not implement terms"
                    + (" (the mixed shard sim runs the stat arm of "
                       "models/raft.py, which has none)"
                       if self.protocol == "mixed" else "")
                )
            if self.fidelity != "clean":
                raise ValueError(
                    "raft_terms requires fidelity='clean': reference "
                    "fidelity is upstream's Raft, which has no terms "
                    "(quirk #6) and never re-arms an election timer"
                )
        if self.faults.byz_forge:
            if self.protocol != "pbft":
                raise ValueError(
                    "byz_forge (forged COMMIT-vote flooding) is a PBFT attack; "
                    f"protocol {self.protocol!r} does not implement it"
                )
            if self.pbft_max_rounds >= self.pbft_max_slots:
                raise ValueError(
                    "byz_forge targets the last vote-table slot; "
                    "pbft_max_rounds must be < pbft_max_slots so no honest "
                    "leader ever proposes it"
                )
        if self.topology not in ("full", "gossip", "kregular", "committee"):
            raise ValueError(
                f"unknown topology {self.topology!r} (valid: full/dense, "
                "gossip, kregular, committee)"
            )
        if self.protocol == "paxos" and not 1 <= self.paxos_n_proposers <= self.n:
            raise ValueError(
                f"paxos_n_proposers={self.paxos_n_proposers} must be in [1, n={self.n}]"
            )
        if self.paxos_client_node >= 0:
            if self.protocol != "paxos":
                raise ValueError("paxos_client_node requires protocol='paxos'")
            if self.paxos_client_node >= self.paxos_n_proposers:
                raise ValueError(
                    f"paxos_client_node={self.paxos_client_node} must be a "
                    f"proposer lane (< paxos_n_proposers="
                    f"{self.paxos_n_proposers}): lanes are the static "
                    "proposer channel layout in both engines"
                )
            if not 0 <= self.paxos_client_ms < self.sim_ms:
                raise ValueError(
                    f"paxos_client_ms={self.paxos_client_ms} outside the "
                    f"simulation window [0, {self.sim_ms})"
                )
        if self.topology == "gossip":
            if self.protocol not in ("paxos", "pbft", "raft"):
                raise NotImplementedError(
                    "gossip topology is implemented for paxos (BASELINE "
                    "config 3: request floods), pbft (block-dissemination "
                    "floods) and raft (vote/heartbeat floods with direct "
                    "unicast replies); the mixed shard sim keeps full-mesh "
                    "raft inside its (small) shards by design"
                )
            if self.fidelity != "clean":
                raise ValueError(
                    "reference fidelity is defined on the full mesh only "
                    "(the reference has no gossip relay)"
                )
            if self.protocol == "raft":
                if self.delivery != "stat":
                    raise ValueError(
                        "raft gossip rides the stat-mode value channels; "
                        "use delivery='stat' with topology='gossip'"
                    )
                # flood values encode (tick+1)*(n+1) + id, TTL-scaled by
                # gossip_hops+1 — must fit int32
                if (self.sim_ms + 1) * (self.n + 1) * (self.gossip_hops + 1) >= 2**31:
                    raise ValueError(
                        "raft gossip encoding (sim_ms+1)*(n+1)*(gossip_hops+1) "
                        "overflows int32 at this size; reduce sim_ms, n, or "
                        "gossip_hops"
                    )
        if self.topology == "kregular":
            if self.protocol not in ("paxos", "pbft", "raft"):
                raise NotImplementedError(
                    "the kregular gather overlay is implemented for pbft, "
                    "raft and paxos; the mixed shard sim keeps full-mesh "
                    "raft inside its (small) shards by design"
                )
            if self.fidelity != "clean":
                raise ValueError(
                    "reference fidelity is defined on the full mesh only; "
                    "the kregular overlay requires fidelity='clean' (e.g. "
                    "the reference's N-2 paxos reply window never closes "
                    "when a proposer reaches only k neighbors)"
                )
            if not 1 <= self.degree <= self.n - 1:
                raise ValueError(
                    f"kregular degree={self.degree} must be in [1, n-1="
                    f"{self.n - 1}] (degree n-1 IS the full mesh)"
                )
        if self.topology == "committee":
            if self.protocol not in ("paxos", "pbft", "raft"):
                raise NotImplementedError(
                    "committee topology runs the flat protocol per "
                    "committee; the mixed shard sim is already a two-level "
                    "hierarchy of its own"
                )
            if self.committees < 1:
                raise ValueError(f"committees={self.committees} must be >= 1")
            if self.n % self.committees != 0:
                raise ValueError(
                    f"n={self.n} must divide evenly into "
                    f"committees={self.committees} equal committees"
                )
            m = self.n // self.committees
            if m < 2:
                raise ValueError(
                    f"committee size n/committees = {m} must be >= 2 "
                    "(a 1-node committee has no quorum to run)"
                )
            if self.protocol == "paxos" and self.paxos_n_proposers > m:
                raise ValueError(
                    f"paxos_n_proposers={self.paxos_n_proposers} exceeds "
                    f"the committee size {m}: proposers are per-committee "
                    "lanes (nodes 0..P-1 of each committee)"
                )
            if self.mesh_axis is not None:
                raise ValueError(
                    "committee topology is unsharded in this version: the "
                    "committee axis is a lax.map, not a mesh axis "
                    "(shard the SWEEP axis instead, parallel/partition.py)"
                )

    def _freeze_link_classes(self):
        """Lists (JSON) to tuples, then the shape of the two class fields:
        K counts >= 1 summing to ``n``, a K x K matrix of whole ms >= 0."""
        counts = tuple(self.link_classes)
        matrix = tuple(tuple(row) for row in self.link_class_delay_ms)
        object.__setattr__(self, "link_classes", counts)
        object.__setattr__(self, "link_class_delay_ms", matrix)
        if not counts and not matrix:
            return
        k = len(counts)
        if not k:
            raise ValueError(
                "link_class_delay_ms needs link_classes: the node count of "
                "each class whose pairs the matrix gives"
            )
        if any(int(c) != c or c < 1 for c in counts) or sum(counts) != self.n:
            raise ValueError(
                f"link_classes={counts} must be whole node counts >= 1 that "
                f"sum to n={self.n} (ids contiguous, class after class)"
            )
        if len(matrix) != k or any(len(row) != k for row in matrix):
            raise ValueError(
                f"link_class_delay_ms must be a square {k} x {k} matrix, one "
                f"row a class of link_classes={counts}; got rows of "
                f"{[len(row) for row in matrix]}"
            )
        if any(int(d) != d or d < 0 for row in matrix for d in row):
            raise ValueError(
                "link_class_delay_ms holds one-way propagation in whole ms "
                f">= 0 (1 tick = 1 ms); got {matrix}"
            )

    # --- derived quantities (plain python; all static under jit) ------------
    @property
    def eff_stat_sampler(self) -> str:
        """Resolved stat_sampler ('auto' -> by cluster size)."""
        if self.stat_sampler == "auto":
            return "normal" if self.n >= 4096 else "exact"
        return self.stat_sampler

    @property
    def eff_edge_sampler(self) -> str:
        """Resolved edge_sampler ('auto' -> by cluster size)."""
        if self.edge_sampler == "auto":
            return "rbg" if self.n >= 4096 else "threefry"
        return self.edge_sampler

    @property
    def ticks(self) -> int:
        """Total simulation ticks (1 tick = 1 ms)."""
        return self.sim_ms

    def one_way_range(self) -> tuple[int, int]:
        """[lo, hi) one-way message delay in ticks: link propagation + the
        protocol's explicit random scheduling delay (SURVEY.md §3.5 notes the
        double delay: Simulator::Schedule(getRandomDelay) + channel Delay)."""
        if self.protocol == "pbft":
            lo, hi = self.pbft_delay_lo, self.pbft_delay_hi
        elif self.protocol == "raft":
            lo, hi = self.raft_delay_lo, self.raft_delay_hi
        else:
            lo, hi = self.paxos_delay_lo, self.paxos_delay_hi
        d = self.link_base_ms
        lo, hi = lo + d, hi + d
        if lo < 1:  # a message can never arrive in the tick it was sent
            lo, hi = 1, max(hi, 2)
        if hi <= lo:  # degenerate range (e.g. delay_lo == delay_hi): one bucket
            hi = lo + 1
        return lo, hi

    @property
    def link_base_ms(self) -> int:
        """The propagation every message pays: ``link_delay_ms``, or under
        link classes the matrix's smallest entry.  What a class pair adds to
        it is the sender-side delay lines' to hold (ops/linkclass.py), so
        the ranges above and below, the jitter's bucket axis and the rings'
        depth stay those of one scalar latency."""
        if not self.link_classes:
            return self.link_delay_ms
        return min(min(row) for row in self.link_class_delay_ms)

    def roundtrip_range(self) -> tuple[int, int]:
        """[lo, hi) request+reply delay (reply is processed instantly at the
        peer and travels back with an independent random delay)."""
        lo, hi = self.one_way_range()
        return 2 * lo, 2 * hi - 1

    @property
    def ring_depth(self) -> int:
        """Ring-buffer depth: must exceed the maximum scheduling horizon.
        With serialization modeled, the worst case is a round trip whose
        request leg carries a block-sized message (Raft proposal acks land at
        rt_hi - 1 + ser; 20 KB at 3 Mbps ≈ 54 ticks)."""
        _, rt_hi = self.roundtrip_range()
        if self.protocol == "pbft":
            # queued-link mode routes blocks through per-destination serial-
            # pipe FIFOs (models/pbft.py PbftState registers) — their delivery
            # offsets are unbounded and never touch the ring, which then only
            # carries 4-byte vote/control traffic
            biggest = 0 if self.queued_links else self.pbft_block_bytes
        elif self.protocol == "raft":
            biggest = self.raft_block_bytes
            if self.queued_links:
                # queued raft deliveries stay on the rings: the serial-pipe
                # backlog is bounded — a proposal serializes ser ticks but
                # departs every heartbeat, so after R proposal rounds the
                # per-link queue holds at most (ser - hb) * R extra ticks
                # (models/raft.py link_busy; the backlog resets with the
                # leader's links on a leadership change)
                ser = self.serialization_ticks(biggest)
                extra = max(0, ser - self.raft_heartbeat_ms) * self.raft_max_rounds
                return rt_hi + ser + 1 + extra
        else:
            biggest = 4
        return rt_hi + self.serialization_ticks(biggest) + 1

    @property
    def quorum(self) -> int:
        """The reference's majority threshold N/2 (pbft-node.cc:231,248;
        raft-node.cc:209; paxos-node.cc:259) — integer division, *not* 2f+1."""
        return self.n // 2

    @property
    def byz_f(self) -> int:
        """Max tolerable Byzantine count under the 2f+1 rule: f = (n-1)//3."""
        return (self.n - 1) // 3

    @property
    def pbft_prepare_need(self) -> int:
        """Votes needed to cross the prepare phase (>= semantics).
        n2: prepare_vote >= N/2 (pbft-node.cc:231)."""
        if self.quorum_rule == "2f1":
            return 2 * self.byz_f + 1
        return self.quorum

    @property
    def pbft_commit_need(self) -> int:
        """Votes needed to finalize (>= semantics).
        n2: commit_vote > N/2 (pbft-node.cc:248) ⇔ >= N/2 + 1."""
        if self.quorum_rule == "2f1":
            return 2 * self.byz_f + 1
        return self.quorum + 1

    @property
    def majority_need(self) -> int:
        """Raft votes (including self) needed to win / commit (>= semantics).
        n2: votes + self > N/2 (raft-node.cc:209)."""
        if self.quorum_rule == "2f1":
            return 2 * self.byz_f + 1
        return self.quorum + 1

    @property
    def raft_lose_need(self) -> int:
        """FAILED votes at which a candidate abandons the election
        (>= semantics).  n2: vote_failed >= N/2 (raft-node.cc:225); 2f1: the
        election is unwinnable once n - vote_failed < majority_need."""
        if self.quorum_rule == "2f1":
            return self.n - self.majority_need + 1
        return self.quorum

    @property
    def pbft_block_txs(self) -> int:
        # num = tx_speed / (1000/(timeout*1000))  (pbft-node.cc:377)
        return self.pbft_tx_speed * self.pbft_block_interval_ms // 1000

    @property
    def pbft_block_bytes(self) -> int:
        return self.pbft_block_txs * self.pbft_tx_size  # 50 KB

    @property
    def raft_block_txs(self) -> int:
        # num = tx_speed / (1000/(heartbeat_timeout*1000)) (raft-node.cc:409)
        return self.raft_tx_speed * self.raft_heartbeat_ms // 1000

    @property
    def raft_block_bytes(self) -> int:
        return self.raft_block_txs * self.raft_tx_size  # 20 KB

    def serialization_ticks(self, nbytes: int) -> int:
        if not self.model_serialization:
            return 0
        return int(nbytes * 8 / (self.link_rate_mbps * 1e6) * 1000 + 0.999)

    def with_(self, **kw) -> "SimConfig":
        return dataclasses.replace(self, **kw)
