"""Traced probe taps: per-sample counters, windowed reductions, and
final-state invariant monitors.

Everything in this module runs INSIDE a jit trace.  Two hard rules, both
pinned by tests/test_zzobsim.py:

- **No host calls**: this module never imports ``utils/telemetry`` (the
  host-side-only rule, KNOWN_ISSUES #0m) — the graph audit's
  ``host-callback-in-program`` rule proves no callback reaches the HLO.
- **Zero PRNG**: taps only READ state; they never consume a key.  Armed
  programs therefore step through bit-identical state trajectories, which
  is what makes the armed-vs-disarmed primary-metrics bit-equality pins
  (exact sampler) possible at all.

Reductions are scatter-free by construction: sums/maxes of state fields
per sample, and a static-index gather (KNOWN_ISSUES #0n) to pick the
window boundaries — safe under ``vmap``/``lax.map``/``shard_map``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu.obsim import schema

_I32_NEVER = np.iinfo(np.int32).max  # models/pbft._NEVER sentinel


def _i32(x):
    return jnp.asarray(x, jnp.int32)


# ------------------------------------------------------------- samples ---


def sample(cfg, state) -> dict:
    """One probe sample: the protocol's schema.SERIES_FIELDS counters read
    off ``state`` (device-side, a handful of sums/maxes).  ``cfg`` must be
    the config the state belongs to (the INNER config on the committee
    path, so ``cfg.n`` is the committee size)."""
    p = cfg.protocol
    if p == "pbft":
        q = (2 * cfg.n) // 3 + 1
        return {
            "msgs_rounds": _i32(state.rounds_sent.sum()),
            "commits": _i32(state.slot_commits.sum()),
            "blocks": _i32(state.block_num.max()),
            "views": _i32(state.v.max()),
            "view_changes": _i32(state.view_changes.sum()),
            "slots_any": _i32((state.slot_commits > 0).sum()),
            "slots_quorum": _i32((state.slot_commits >= q).sum()),
        }
    if p == "raft":
        return {
            "msgs_rounds": _i32(state.round.sum()),
            "blocks": _i32(state.block_num.max()),
            "elections": _i32(state.elections.sum()),
            "leaders": _i32((state.is_leader & state.alive).sum()),
        }
    if p == "paxos":
        from blockchain_simulator_tpu.models import paxos as paxos_model

        ph = state.phase
        return {
            "msgs_tickets": _i32(state.ticket.sum()),
            "executes": _i32(state.is_commit.sum()),
            "committed": _i32((state.commit_tick >= 0).sum()),
            "phase_ticket": _i32((ph == paxos_model.PH_TICKET).sum()),
            "phase_propose": _i32((ph == paxos_model.PH_PROPOSE).sum()),
            "phase_commit": _i32((ph == paxos_model.PH_COMMIT).sum()),
        }
    raise NotImplementedError(p)


def raft_steady_sample(ys: dict, h_state) -> dict:
    """Map the raft heartbeat fast path's per-heartbeat scan ys
    (models/raft_hb.steady_scan ``with_probe=True``: blocks/rounds/...)
    into the raft probe schema.  Elections and leadership are frozen by
    the handoff's steady-state precondition, so those fields broadcast
    the handoff state's values across the heartbeat axis."""
    blocks = _i32(ys["blocks"])
    return {
        "msgs_rounds": _i32(ys["rounds"]),
        "blocks": blocks,
        "elections": jnp.full_like(blocks, _i32(h_state.elections.sum())),
        "leaders": jnp.full_like(
            blocks, _i32((h_state.is_leader & h_state.alive).sum())
        ),
    }


# ------------------------------------------------- windowed reductions ---


def window(series: dict, n_samples: int, windows: int) -> dict:
    """Reduce per-sample series ``{field: [..., m]}`` to window-boundary
    series ``{field: [..., W]}`` via a static-index gather on the last
    axis (schema.window_bounds; scatter-free, KNOWN_ISSUES #0n)."""
    idx = schema.window_bounds(n_samples, windows)
    return {k: v[..., idx] for k, v in series.items()}


def liveness_lag(progress) -> jax.Array:
    """Samples since the cumulative progress counter last advanced
    (``m`` = never advanced).  ``progress`` is the protocol's
    schema.PROGRESS_FIELD per-sample series ``[m]``; a max-reduce over a
    comparison against the shifted series — no scatter, no PRNG."""
    prog = _i32(progress)
    m = prog.shape[-1]
    prev = jnp.concatenate([jnp.zeros_like(prog[..., :1]), prog[..., :-1]],
                           axis=-1)
    inc = prog > prev
    idx = jnp.arange(m, dtype=jnp.int32)
    last = jnp.max(jnp.where(inc, idx, -1), axis=-1)
    return _i32(jnp.where(last < 0, m, m - 1 - last))


# ------------------------------------------------------------ monitors ---


def monitors(cfg, state) -> dict:
    """On-device invariant monitors over the FINAL state: traced twins of
    each protocol's host-side ``metrics()`` agreement logic (so a monitor
    firing and ``agreement_ok=False`` are the same event), plus a
    quorum-certificate consistency check.  Returns int32 violation
    counters; zero = clean.  A byzantine node tripping these is SIGNAL,
    not a bug (KNOWN_ISSUES #0o).  ``liveness_lag`` is attached by the
    callers that hold the per-sample progress series."""
    p = cfg.protocol
    if p == "pbft":
        commits = state.slot_commits
        proposed = state.slot_propose_tick < _I32_NEVER
        # forged (quorum without any proposal) + misattributed commits —
        # models/pbft.metrics forged_commits/unattributed_commits, traced
        viol_agree = _i32(((commits > 0) & ~proposed).sum()
                          + state.unattributed.sum())
        # a finalization stamped BEFORE its slot's first proposal is an
        # inconsistent quorum certificate (commit_tick is a last-event
        # pmax, propose_tick a first-event pmin — clean runs order them)
        viol_quorum = _i32(
            ((commits > 0) & proposed
             & (state.slot_commit_tick >= 0)
             & (state.slot_commit_tick < state.slot_propose_tick)).sum()
        )
        return {"viol_agreement": viol_agree, "viol_quorum": viol_quorum}
    if p == "raft" and state.term is not None:
        # with terms (cfg.raft_terms) the leader at the end is as a rule
        # not the node whose id the followers stored, and an old leader may
        # stand beside a newer term's for the ticks a message takes; what
        # must hold is election safety: the program's own oracle, and no
        # two alive honest leaders of one term
        lead = state.is_leader & state.alive & state.honest
        same = ((state.term[:, None] == state.term[None, :])
                & lead[:, None] & lead[None, :]).sum() - lead.sum()
        viol_agree = _i32(state.term_conflicts.sum())
        if state.dead_acts is not None:
            # under a crash schedule (cfg.faults.crashes) ``alive`` is the
            # mask at the end of the run, and the schedule's own oracles
            # join the program's: a node that acted while it was down, a
            # node that voted twice in one term across a restart
            viol_agree = viol_agree + _i32(
                state.dead_acts.sum() + state.double_votes.sum())
        return {"viol_agreement": viol_agree, "viol_quorum": _i32(same // 2)}
    if p == "raft":
        cand = state.is_leader & state.alive
        lt = jnp.where(cand, state.leader_tick, _I32_NEVER)
        lead = _i32(jnp.argmin(lt))  # earliest-elected alive leader
        stored = state.alive & (state.m_value >= 0)
        # raft.metrics agreement: every alive stored value names the leader
        viol_agree = _i32(jnp.where(
            cand.any(), (stored & (state.m_value != lead)).sum(), 0
        ))
        # split brain among CORRECT nodes (byzantine double-voting can
        # split honestly-elected leaders; >1 honest alive leader = signal)
        viol_quorum = _i32(jnp.maximum(
            (state.is_leader & state.alive & state.honest).sum() - 1, 0
        ))
        return {"viol_agreement": viol_agree, "viol_quorum": viol_quorum}
    if p == "paxos":
        np_prop = cfg.paxos_n_proposers
        executed = state.is_commit & state.alive
        n_exec = executed.sum()
        cmd_min = jnp.min(jnp.where(executed, state.command, _I32_NEVER))
        cmd_max = jnp.max(jnp.where(executed, state.command, -1))
        distinct = (n_exec > 0) & (cmd_min != cmd_max)
        winners = state.commit_tick[:np_prop] >= 0
        # paxos.metrics agreement: one executed command, and every
        # committed proposer proposed exactly it
        wrong = winners & (state.proposal[:np_prop] != cmd_min)
        viol_agree = _i32(distinct) + _i32(
            jnp.where(n_exec > 0, wrong.sum(), 0)
        )
        # a committed proposer whose quorum left zero executed acceptors
        # claimed executions nobody holds (paxos.metrics, same branch)
        viol_quorum = _i32((winners.sum() > 0) & (n_exec == 0))
        return {"viol_agreement": viol_agree, "viol_quorum": viol_quorum}
    raise NotImplementedError(p)


# ------------------------------------------------------------ assembly ---


def finalize(cfg, pcfg, final_state, series, n_samples: int) -> dict:
    """Assemble the probe pytree from a run's per-sample series dict
    ``{field: [m]}`` and its final state: windowed series always, the
    monitor block when ``pcfg.monitors`` (schema docstring).  Pure traced
    data — callers return it as a second jit output."""
    out = {"series": window(series, n_samples, pcfg.windows)}
    if pcfg.monitors:
        mon = monitors(cfg, final_state)
        mon["liveness_lag"] = liveness_lag(
            series[schema.PROGRESS_FIELD[cfg.protocol]]
        )
        out["monitors"] = mon
    return out
