"""Mixed-protocol shard simulation (BASELINE config 5).

``S`` Raft shards of ``m`` nodes each (``n = S·m``) run leader election +
heartbeat replication *internally*, while a cross-shard PBFT instance over the
``S`` shard representatives finalizes global blocks.  This is the hierarchical
composition named in BASELINE.json ("256 Raft shards × 1k nodes with
cross-shard PBFT finality") — a capability with no upstream counterpart
(upstream runs exactly one protocol per compiled binary, SURVEY.md §1); its
plain reference is the per-message ``benchmark/reference/mixed_engine.py``
(tests/test_zzmixed_reference.py, tests/test_zzmixed_cell.py).

Composition is pure function reuse, the payoff of the protocol-backend API
(models/base.py): the Raft backend's ``step`` is vmapped over the shard
axis (every leaf ``[m, ...]`` → ``[S, m, ...]``, per-shard PRNG streams via
``fold_in(shard)``) as a lane batch (``base.lane_vmap``: its ``gated``
deliveries branch on "any shard active" instead of lowering to selects),
and the PBFT backend runs unchanged over ``S``
virtual nodes whose ``alive`` mask is recomputed *every tick* as "shard has an
elected leader" — a shard only participates in cross-shard consensus while
its Raft layer is healthy.  Faults (crash/Byzantine/drop) apply within each
shard; a shard whose leader crashes drops out of the PBFT quorum until
re-election (clean fidelity re-arms election timers, so representation
recovers).

Scale-out: the shard axis is embarrassingly parallel; ``parallel.shard``
row-shards the raft leaves over the mesh's ``nodes`` axis (the S-node PBFT
layer is replicated per device — it is O(S) tiny), which is how BASELINE
config 5's 256 shards x 1k nodes = 256k simulated nodes run on one mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from blockchain_simulator_tpu.models import pbft, raft
from blockchain_simulator_tpu.models.base import lane_vmap
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils.config import FaultConfig


# where a run's device time goes, as ``jax.named_scope`` names (HLO metadata
# only — see models/pbft.SCOPES).  ``mixed.tick.*`` are the parts of one
# :func:`step` and sit under ``mixed.prefix`` (the election-phase scan) or
# ``mixed.fallback`` (the per-tick continuation when a shard failed the
# handoff: zero device time on a sound run, which is how a trace shows which
# arm of ``scan_fast``'s cond ran); ``raft.tick.*`` / ``pbft.tick.*`` / ``ops.*``
# nest inside under their own names.
SCOPES = (
    "mixed.prefix",
    "mixed.tick.raft_shards",
    "mixed.tick.membership",
    "mixed.tick.finality",
    "mixed.handoff",
    "mixed.steady.raft_hb",
    "mixed.steady.finality",
    "mixed.fallback",
)

# what :func:`metrics` reports of WHEN things happened, in ticks (= ms): the
# last shard's election, the last raft commit past its leader's election,
# the first global proposal, the last global commit, and the global view
# changes (a run with one is not held to an undisturbed reference's global
# times).  The plain reference (benchmark/reference/mixed_engine.py) yields
# the same keys; a caller that holds a run to it asks for this tuple first.
MILESTONES = (
    "leader_elected_ms_max",
    "raft_commit_tail_ms_max",
    "global_first_propose_ms",
    "global_last_commit_ms",
    "global_view_changes",
)


@struct.dataclass
class MixedState:
    raft: raft.RaftState  # leaves [S, m, ...]
    pbft: pbft.PbftState  # leaves [S, ...]


@struct.dataclass
class MixedBufs:
    raft: raft.RaftBufs  # leaves [S, D_raft, m, ...]
    pbft: pbft.PbftBufs  # leaves [D_pbft, S, ...]


def sub_configs(cfg):
    """(raft_cfg for one m-node shard, pbft_cfg over S representatives).

    The RAFT sub-config resolves ``stat_sampler="auto"`` at the PARENT scale
    (cfg.n = S·m), not the shard size: under the shard batch a taken
    ``gated()`` arm runs for every shard (``step`` branches on "any shard
    active" and selects per shard inside the arm), and the auto
    heuristic's n >= 4096 cutoff is about total per-tick sampler work.
    At config-5 scale (256k rows) this swaps the ~40-pass
    BTRS exact binomial for the ~6-pass normal approximation in all 256
    shards (the approximation error is O(1/sqrt(count)) per bucket —
    negligible at 1k-node shards), a severalfold cut in the per-tick cost
    that dominated the r4 artifact's 2348 s run (ARTIFACT_config5.json;
    VERDICT r4 weak-#3).  The S-representative PBFT layer keeps its own
    "auto" resolution: it steps ONCE, un-vmapped, so the override would
    trade accuracy (S is small — per-bucket counts ~S/3) for nothing."""
    s = cfg.mixed_shards
    m = cfg.n // s
    rcfg = cfg.with_(
        protocol="raft", n=m, mesh_axis=None, stat_sampler=cfg.eff_stat_sampler
    )
    # faults live at the raft level; representatives fail by losing their
    # leader, not by an independent fault mask
    pcfg = cfg.with_(
        protocol="pbft", n=s, mesh_axis=None, faults=FaultConfig()
    )
    return rcfg, pcfg


def init(cfg, key=None):
    s = cfg.mixed_shards
    if cfg.n % s != 0:
        raise ValueError(f"n={cfg.n} not divisible into {s} shards")
    if cfg.n // s < 3:
        raise ValueError("shard size must be >= 3 for a meaningful raft quorum")
    rcfg, pcfg = sub_configs(cfg)
    k = jax.random.key(cfg.seed) if key is None else key
    shard_keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(s))
    r_state, r_bufs = jax.vmap(lambda kk: raft.init(rcfg, kk))(shard_keys)
    p_state, p_bufs = pbft.init(pcfg, jax.random.fold_in(k, 0x5AFE))
    # no representative is alive until its shard elects a leader
    p_state = p_state.replace(alive=jnp.zeros((s,), bool))
    return MixedState(raft=r_state, pbft=p_state), MixedBufs(raft=r_bufs, pbft=p_bufs)


def step(cfg, state: MixedState, bufs: MixedBufs, t, tkey):
    """One tick.  Sharded (cfg.mesh_axis set): raft shards are row-sharded
    over the mesh axis (embarrassingly parallel — per-shard PRNG streams key
    on the GLOBAL shard id), while the S-representative PBFT instance is
    replicated on every device: its inputs (the [S] has-leader mask) are
    all-gathered, so each device steps an identical copy with identical keys
    and the replicated state never diverges."""
    axis = cfg.mesh_axis
    rcfg, pcfg = sub_configs(cfg)
    s_loc = state.raft.block_num.shape[0]  # local shard rows
    base = 0 if axis is None else jax.lax.axis_index(axis) * s_loc
    with jax.named_scope("mixed.tick.raft_shards"):
        shard_keys = jax.vmap(
            lambda i: jax.random.fold_in(tkey, 0x0C0C + base + i)
        )(jnp.arange(s_loc))
        # the shard batch is a lane batch: raft.step's gated deliveries
        # branch on "any shard active" (models/base.gated) instead of
        # lowering to selects that every shard pays on every tick
        r_state, r_bufs = lane_vmap(
            functools.partial(raft.step, rcfg, t=t)
        )(state.raft, bufs.raft, tkey=shard_keys)
    with jax.named_scope("mixed.tick.membership"):
        # cross-shard membership: a representative is alive iff its shard
        # currently has an elected, alive leader
        has_leader = (r_state.is_leader & r_state.alive).any(axis=1)
        if axis is not None:
            has_leader = jax.lax.all_gather(has_leader, axis, tiled=True)
        p_state = state.pbft.replace(alive=has_leader)
    with jax.named_scope("mixed.tick.finality"):
        p_state, p_bufs = pbft.step(
            pcfg, p_state, bufs.pbft, t, jax.random.fold_in(tkey, 0x9B9B)
        )
    return MixedState(raft=r_state, pbft=p_state), MixedBufs(raft=r_bufs, pbft=p_bufs)


def fast_eligible(cfg) -> bool:
    """Can the raft shards ride the heartbeat-blocked steady scan
    (models/raft_hb.py)?  The shard sub-config must satisfy the same
    eligibility as a standalone round-schedule raft — the shards ARE
    standalone raft instances under the vmap."""
    if cfg.protocol != "mixed":
        return False
    if cfg.n % cfg.mixed_shards != 0 or cfg.n // cfg.mixed_shards < 3:
        return False  # init rejects these with a better message
    from blockchain_simulator_tpu.models import raft_hb

    rcfg, _ = sub_configs(cfg)
    return raft_hb.eligible(rcfg)


def prefix_handoff(cfg, state, bufs, key):
    """Per-tick mixed prefix through the raft election phase, then the
    checked handoff (models/raft_hb.handoff) in EVERY shard.  Returns
    ``(carry, ok_all, h_s)`` — shared by ``scan_fast`` (which conds on
    ``ok_all`` inside the trace) and utils/trace.run_traced (which branches
    on the host to record the phase that actually ran)."""
    from blockchain_simulator_tpu.models import raft_hb

    axis = cfg.mesh_axis
    rcfg, _ = sub_configs(cfg)
    t_e = raft_hb.prefix_ticks(rcfg)

    def tick_body(carry, t):
        st, bf = carry
        st, bf = step(cfg, st, bf, t, prng.tick_key(key, t))
        return (st, bf), ()

    with jax.named_scope("mixed.prefix"):
        carry, _ = jax.lax.scan(tick_body, (state, bufs), jnp.arange(t_e))
    with jax.named_scope("mixed.handoff"):
        ok_s, h_s = jax.vmap(
            lambda st: raft_hb.handoff(rcfg, st)
        )(carry[0].raft)
        bad = (~ok_s).sum()
        if axis is not None:
            bad = jax.lax.psum(bad, axis)
    return carry, bad == 0, h_s


def fast_finish(cfg, carry, h_s, key, with_probe: bool = False):
    """The heartbeat-scheduled steady phase from a quiet handoff: vmapped
    O(1)-per-heartbeat raft scans + the per-tick S-representative PBFT layer
    with its ``alive`` mask pinned all-true.  Returns the final MixedState;
    with ``with_probe`` (utils/trace.run_traced) also per-shard heartbeat
    series and per-tick global-layer series:
    ``(state, (raft_ys [S?, K] leaves, pbft_ys [ticks - t_e] leaves))``."""
    from blockchain_simulator_tpu.models import raft_hb

    axis = cfg.mesh_axis
    rcfg, pcfg = sub_configs(cfg)
    t_e = raft_hb.prefix_ticks(rcfg)
    s = cfg.mixed_shards
    st, bf = carry
    s_loc = st.raft.block_num.shape[0]
    base = 0 if axis is None else jax.lax.axis_index(axis) * s_loc
    # per-shard steady-scan streams key on the GLOBAL shard id, so the
    # sharded run is bit-identical to the single-device run (the same
    # convention as step's per-tick shard keys)
    with jax.named_scope("mixed.steady.raft_hb"):
        hb_keys = jax.vmap(
            lambda i: jax.random.fold_in(key, 0x4BB7 + base + i)
        )(jnp.arange(s_loc))
        if with_probe:
            res, raft_ys = jax.vmap(
                lambda k, hh: raft_hb.steady_scan(rcfg, k, hh, with_probe=True)
            )(hb_keys, h_s)
        else:
            res = jax.vmap(
                lambda k, hh: raft_hb.steady_scan(rcfg, k, hh)
            )(hb_keys, h_s)
            raft_ys = None
        raft_final = jax.vmap(
            lambda rst, hh, r: raft_hb.materialize(rcfg, rst, hh, r)
        )(st.raft, h_s, res)
    ones = jnp.ones((s,), bool)

    def p_body(pcarry, t):
        ps, pb = pcarry
        ps = ps.replace(alive=ones)
        ps, pb = pbft.step(
            pcfg, ps, pb, t,
            jax.random.fold_in(prng.tick_key(key, t), 0x9B9B),
        )
        ys = (
            {"global_blocks": ps.block_num.max(),
             "global_commit_events": ps.slot_commits.sum()}
            if with_probe
            else ()
        )
        return (ps, pb), ys

    with jax.named_scope("mixed.steady.finality"):
        (p_state, _), pbft_ys = jax.lax.scan(
            p_body, (st.pbft, bf.pbft),
            t_e + jnp.arange(max(cfg.ticks - t_e, 0)),
        )
    final = MixedState(raft=raft_final, pbft=p_state)
    return (final, (raft_ys, pbft_ys)) if with_probe else final


def scan_fast(cfg, state: MixedState, bufs: MixedBufs, key):
    """Heartbeat-scheduled mixed simulation (BASELINE config 5's wall-clock
    lever): run the full per-tick mixed engine for the raft election prefix,
    evaluate the checked handoff (models/raft_hb.handoff) in EVERY shard,
    then ``lax.cond`` on all-shards-quiet:

    - fast branch (``fast_finish``): the S raft shards collapse to vmapped
      O(1)-per-heartbeat steady scans (256 shards x 1k nodes stop paying
      256k rows of per-tick sampler work), while the S-representative PBFT
      layer — the only part with genuine per-tick cross-shard dynamics —
      keeps stepping every tick with its ``alive`` mask pinned all-true
      (every shard has a live, undeposable leader post-handoff, which is
      exactly what the per-tick engine would recompute).  PBFT keys/
      evolution are bit-identical to the per-tick engine; raft milestones
      follow the raft_hb count contract.
    - slow branch: any shard failed the handoff (split election, crashed
      majority) — CONTINUE the per-tick mixed scan from the prefix carry,
      bit-identical to an uninterrupted tick run.

    Works unsharded, under vmap, and inside shard_map (cfg.mesh_axis row-
    shards the shard axis; the handoff verdict is psum-agreed)."""
    from blockchain_simulator_tpu.models import raft_hb

    rcfg, _ = sub_configs(cfg)
    t_e = raft_hb.prefix_ticks(rcfg)

    def tick_body(carry, t):
        st, bf = carry
        st, bf = step(cfg, st, bf, t, prng.tick_key(key, t))
        return (st, bf), ()

    carry, ok_all, h_s = prefix_handoff(cfg, state, bufs, key)

    def fast_branch(carry):
        return fast_finish(cfg, carry, h_s, key)

    @jax.named_scope("mixed.fallback")
    def tick_branch(carry):
        (st, _), _ = jax.lax.scan(
            tick_body, carry, t_e + jnp.arange(max(cfg.ticks - t_e, 0))
        )
        return st

    return jax.lax.cond(ok_all, fast_branch, tick_branch, carry)


def metrics(cfg, state: MixedState) -> dict:
    s = cfg.mixed_shards
    rcfg, pcfg = sub_configs(cfg)
    is_leader = np.asarray(state.raft.is_leader) & np.asarray(state.raft.alive)
    has_leader = is_leader.any(axis=1)
    block_num = np.asarray(state.raft.block_num)
    leader_tick = np.asarray(state.raft.leader_tick)
    # per-shard raft blocks: the earliest-elected current leader's count
    # (raft.metrics' convention — a deposed ex-leader keeps a stale count)
    lt = np.where(is_leader, leader_tick, np.iinfo(np.int32).max)
    lead_idx = lt.argmin(axis=1)
    shard_blocks = np.where(
        has_leader, block_num[np.arange(s), lead_idx], 0
    )
    # MILESTONES: times that do not depend on the random stream beyond a
    # tick or two.  Per shard the last block's commit past its leader's
    # election is the proposal delay + the heartbeat schedule + one ack
    # round trip; -1 where no shard has one
    lead_tick = leader_tick[np.arange(s), lead_idx]
    block_tick = np.asarray(state.raft.block_tick)[np.arange(s), lead_idx]
    done = has_leader & (shard_blocks > 0)
    tails = block_tick.max(axis=1)[done] - lead_tick[done]
    pm = pbft.metrics(pcfg, state.pbft)
    proposed = np.asarray(state.pbft.slot_propose_tick)
    proposed = proposed[proposed < pbft._NEVER]
    milestones = dict(zip(MILESTONES, (
        float(lead_tick[has_leader].max()) if has_leader.any() else -1.0,
        float(tails.max()) if tails.size else -1.0,
        float(proposed.min()) if proposed.size else -1.0,
        pm["last_commit_ms"],
        pm["view_changes"],
    ), strict=True))
    return {
        "protocol": "mixed",
        "n": cfg.n,
        "shards": s,
        "shard_size": cfg.n // s,
        "shards_with_leader": int(has_leader.sum()),
        "raft_blocks_total": int(shard_blocks.sum()),
        "raft_blocks_min": int(shard_blocks[has_leader].min()) if has_leader.any() else 0,
        "global_blocks_final": pm["blocks_final_all_nodes"],
        "global_rounds_sent": pm["rounds_sent"],
        "global_mean_ttf_ms": pm["mean_time_to_finality_ms"],
        "agreement_ok": pm["agreement_ok"],
        **milestones,
    }
