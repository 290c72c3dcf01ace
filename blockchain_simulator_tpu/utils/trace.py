"""Tracing / observability.

The reference's only observability is timestamped ``NS_LOG_INFO`` lines
(SURVEY.md §5) read by eye.  Here observability is data, at three levels:

- **End-of-run metrics**: each backend's ``metrics()`` (already structured).
- **Probe time series** (this module): ``run_traced`` runs the SAME simulator
  ``run_simulation`` would — it dispatches through
  ``runner.use_round_schedule``, validating an ineligible explicit
  ``schedule='round'`` with the same ``ValueError`` — with a per-step probe
  emitted as scan ``ys``:

  - general tick engine: one sample per 1 ms tick (the tensorized equivalent
    of grepping the reference's log for commit/election/finality lines);
  - round-blocked PBFT (models/pbft_round): one sample per BLOCK ROUND;
  - heartbeat raft (models/raft_hb): one sample per HEARTBEAT after the
    election prefix (per-tick samples when the checked handoff fell back to
    the tick engine);
  - heartbeat-scheduled mixed (models/mixed.scan_fast): per-heartbeat shard
    aggregates + the global PBFT layer sampled at the same ticks.

  Fast-path series carry a ``"t"`` array mapping sample index -> virtual
  tick; pass ``cfg.with_(schedule="tick")`` for bit-exact per-tick series on
  the general engine (the documented override).
- **Event export**: ``events_from_series`` reconstructs per-event ticks from
  monotone counters; ``to_chrome_trace`` converts a whole series dict into a
  Chrome-trace/Perfetto JSON timeline (counter tracks + instant events).
- **Profiler capture**: ``profile_run`` wraps a run in ``jax.profiler.trace``
  for TensorBoard/perfetto (compile + device timeline).
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu.models.base import get_protocol
from blockchain_simulator_tpu.utils import aotcache
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils.config import SimConfig


def probe(cfg: SimConfig, state) -> dict:
    """Per-step scalar probes for a protocol state (device-side, cheap).

    Reads only the field names shared between each protocol's tick state and
    its fast-path state (e.g. PbftState and PbftRoundState), so the same
    probe serves both engines."""
    p = cfg.protocol
    if p == "pbft":
        return {
            "blocks_committed_max": state.block_num.max(),
            "commit_events_total": state.slot_commits.sum(),
            "view_max": state.v.max(),
            "rounds_sent": state.next_n.max(),
        }
    if p == "raft":
        return {
            "n_leaders": (state.is_leader & state.alive).sum(),
            "blocks": state.block_num.max(),
            "elections": state.elections.sum(),
        }
    if p == "paxos":
        return {
            "executes": state.is_commit.sum(),
            "max_ticket": state.ticket.max(),
            "committed_proposers": (state.commit_tick >= 0).sum(),
        }
    if p == "mixed":
        return {
            "shards_with_leader": (state.raft.is_leader & state.raft.alive)
            .any(axis=1)
            .sum(),
            "raft_blocks_total": state.raft.block_num.max(axis=1).sum(),
            "global_blocks": state.pbft.block_num.max(),
        }
    raise NotImplementedError(p)


def _np_series(ys) -> dict:
    return {k: np.asarray(v) for k, v in ys.items()}


# The jitted programs are cached per config in the unified executable
# registry (utils/aotcache.py; SimConfig is frozen/hashable, the same
# convention as runner.make_sim_fn) so a multi-seed --trace sweep compiles
# once and reruns with fresh keys — and the hit/miss trail lands on the run
# manifest's `cache` block.  Every program below — including the
# multi-program raft_hb/mixed factories' prefix/steady/cont pieces — is
# traced and budget-pinned by the graph audit (lint/graph/programs.py
# `trace.*` specs).

@aotcache.cached_factory("trace-tick")
def _tick_traced_fn(cfg: SimConfig):
    proto = get_protocol(cfg.protocol)

    @jax.jit
    def sim(key):
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))

        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t))
            return (st, bf), probe(cfg, st)

        (state, _), ys = jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))
        return state, ys

    return sim


def _traced_tick(cfg: SimConfig, seed):
    """General per-tick engine with the probe as scan ``ys`` (the seed
    behavior of run_traced, now the schedule='tick' arm)."""
    proto = get_protocol(cfg.protocol)
    key = jax.random.key(cfg.seed if seed is None else seed)
    state, ys = jax.block_until_ready(_tick_traced_fn(cfg)(key))
    return proto.metrics(cfg, state), _np_series(ys)


@aotcache.cached_factory("trace-pbft-round")
def _pbft_round_traced_fn(cfg: SimConfig):
    from blockchain_simulator_tpu.models import pbft_round

    @jax.jit
    def sim(key):
        state, _ = pbft_round.init(cfg, jax.random.fold_in(key, 0x1217))
        return pbft_round.scan_rounds(cfg, state, key, with_probe=True)

    return sim


def _traced_pbft_round(cfg: SimConfig, seed):
    """Round-blocked PBFT fast path with one probe sample per round.

    The scan is exactly runner.make_sim_fn's (same init, same keys, probes
    only read), so the returned metrics are bit-identical to
    ``run_simulation``'s on this config."""
    from blockchain_simulator_tpu.models import pbft_round

    key = jax.random.key(cfg.seed if seed is None else seed)
    state, ys = jax.block_until_ready(_pbft_round_traced_fn(cfg)(key))
    series = _np_series(ys)
    bt = cfg.pbft_block_interval_ms
    # sample i is the state after round r = i + 1 (block tick r * interval)
    series["t"] = (1 + np.arange(len(next(iter(series.values()))))) * bt
    return pbft_round.metrics(cfg, state), series


@aotcache.cached_factory("trace-raft-hb")
def _raft_hb_traced_fns(cfg: SimConfig):
    """(prefix, steady, cont) jitted programs for the traced raft fast path;
    the key is a runtime argument so seeds share one compile."""
    from blockchain_simulator_tpu.models import raft as raft_tick
    from blockchain_simulator_tpu.models import raft_hb

    t_e = raft_hb.prefix_ticks(cfg)

    def body(key, carry, t):
        st, bf = carry
        st, bf = raft_tick.step(cfg, st, bf, t, prng.tick_key(key, t))
        return (st, bf), probe(cfg, st)

    @jax.jit
    def prefix(key):
        state, bufs = raft_tick.init(cfg, jax.random.fold_in(key, 0x1217))
        carry, ys = jax.lax.scan(
            functools.partial(body, key), (state, bufs), jnp.arange(t_e)
        )
        ok, h = raft_hb.handoff(cfg, carry[0])
        return carry, ys, ok, h

    @jax.jit
    def steady(state, h, key):
        out, ys = raft_hb.steady_scan(cfg, key, h, with_probe=True)
        return raft_hb.materialize(cfg, state, h, out), ys

    @jax.jit
    def cont(carry, key):
        (st, _), ys = jax.lax.scan(
            functools.partial(body, key), carry,
            t_e + jnp.arange(max(cfg.ticks - t_e, 0)),
        )
        return st, ys

    return prefix, steady, cont


def _traced_raft_hb(cfg: SimConfig, seed):
    """Heartbeat-blocked raft fast path, probed.

    The phase split runs on the host (run_traced is a single-seed host
    driver; the CLI forbids --trace under vmap/shard_map): the tick-engine
    election prefix runs first, the checked handoff verdict is read back,
    and EITHER the per-heartbeat steady scan (per-heartbeat series) OR the
    tick-engine continuation from the prefix carry (per-tick series over the
    full window) runs — the same two branches as raft_hb.scan_from_init's
    traced ``lax.cond``, with the same keys, so milestones match
    ``run_simulation``."""
    from blockchain_simulator_tpu.models import raft_hb

    prefix, steady, cont = _raft_hb_traced_fns(cfg)
    key = jax.random.key(cfg.seed if seed is None else seed)
    carry, pre_ys, ok, h = jax.block_until_ready(prefix(key))

    if bool(ok):
        state, ys = jax.block_until_ready(steady(carry[0], h, key))
        series = _np_series(ys)
        hb = cfg.raft_heartbeat_ms
        series["t"] = int(h.hb0) + np.arange(raft_hb.n_hb_steps(cfg)) * hb
        return raft_hb.metrics(cfg, state), series

    state, post_ys = jax.block_until_ready(cont(carry, key))
    series = {
        k: np.concatenate([np.asarray(pre_ys[k]), np.asarray(post_ys[k])])
        for k in pre_ys
    }
    return raft_hb.metrics(cfg, state), series


@aotcache.cached_factory("trace-mixed")
def _mixed_traced_fns(cfg: SimConfig):
    """(prefix, finish, prefix_probed, cont) jitted programs for the traced
    mixed fast path; the key is a runtime argument so seeds share one
    compile."""
    from blockchain_simulator_tpu.models import mixed, raft_hb

    rcfg, _ = mixed.sub_configs(cfg)
    t_e = raft_hb.prefix_ticks(rcfg)

    @jax.jit
    def prefix(key):
        state, bufs = mixed.init(cfg, jax.random.fold_in(key, 0x1217))
        return mixed.prefix_handoff(cfg, state, bufs, key)

    @jax.jit
    def finish(carry, h_s, key):
        return mixed.fast_finish(cfg, carry, h_s, key, with_probe=True)

    def body(key, c, t):
        st, bf = c
        st, bf = mixed.step(cfg, st, bf, t, prng.tick_key(key, t))
        return (st, bf), probe(cfg, st)

    # fallback arm only: re-probe the prefix per tick for a contiguous
    # series (prefix() records no ys; the rerun is one extra compile of the
    # same engine, paid only when a shard's handoff failed)
    @jax.jit
    def prefix_probed(key):
        state, bufs = mixed.init(cfg, jax.random.fold_in(key, 0x1217))
        return jax.lax.scan(
            functools.partial(body, key), (state, bufs), jnp.arange(t_e)
        )

    @jax.jit
    def cont(carry, key):
        (st, _), ys = jax.lax.scan(
            functools.partial(body, key), carry,
            t_e + jnp.arange(max(cfg.ticks - t_e, 0)),
        )
        return st, ys

    return prefix, finish, prefix_probed, cont


def _traced_mixed_fast(cfg: SimConfig, seed):
    """Heartbeat-scheduled mixed sim, probed: per-heartbeat SHARD AGGREGATES
    (total/min raft blocks over shards, shards stopped) plus the global PBFT
    layer sampled at the same ticks; per-tick mixed series over the full
    window when any shard's handoff fell back to the tick engine."""
    from blockchain_simulator_tpu.models import mixed, raft_hb

    rcfg, _ = mixed.sub_configs(cfg)
    t_e = raft_hb.prefix_ticks(rcfg)
    prefix, finish, prefix_probed, cont = _mixed_traced_fns(cfg)
    key = jax.random.key(cfg.seed if seed is None else seed)
    carry, ok_all, h_s = jax.block_until_ready(prefix(key))

    if bool(ok_all):
        state, (raft_ys, pbft_ys) = jax.block_until_ready(
            finish(carry, h_s, key)
        )
        hb = rcfg.raft_heartbeat_ms
        k_steps = raft_hb.n_hb_steps(rcfg)
        # shards' heartbeat clocks differ by their election offsets; the
        # aggregate series is indexed by STEP, timestamped at the latest
        # shard's k-th heartbeat (documented approximation)
        t_hb = int(np.asarray(h_s.hb0).max()) + np.arange(k_steps) * hb
        blocks = np.asarray(raft_ys["blocks"])          # [S, K]
        stopped = np.asarray(raft_ys["stopped"])        # [S, K]
        g_blocks = np.asarray(pbft_ys["global_blocks"])         # [ticks - t_e]
        g_commits = np.asarray(pbft_ys["global_commit_events"])
        # sample the per-tick global layer at the heartbeat ticks
        gi = np.clip(t_hb - t_e, 0, max(len(g_blocks) - 1, 0))
        series = {
            "t": t_hb,
            "raft_blocks_total": blocks.sum(axis=0),
            "raft_blocks_min": blocks.min(axis=0),
            "shards_stopped": stopped.sum(axis=0),
            "global_blocks": g_blocks[gi] if len(g_blocks) else np.zeros(
                (k_steps,), np.int32),
            "global_commit_events": g_commits[gi] if len(g_commits)
            else np.zeros((k_steps,), np.int32),
        }
        return mixed.metrics(cfg, state), series

    carry2, pre_ys = jax.block_until_ready(prefix_probed(key))
    state, post_ys = jax.block_until_ready(cont(carry2, key))
    series = {
        k: np.concatenate([np.asarray(pre_ys[k]), np.asarray(post_ys[k])])
        for k in pre_ys
    }
    return mixed.metrics(cfg, state), series


@aotcache.cached_factory("trace-committee")
def _committee_traced_fn(cfg: SimConfig):
    """Jitted ``sim(key) -> (stacked_finals, series)`` for the committee
    hierarchy: the static-arm run_stacked body (runner.make_sim_fn
    committee arm — config's own fault counts on the dyn operand slots)
    with the standard probe sampled per tick INSIDE each committee's
    body (topo/committee.stacked_body probe hook; a lane of a tile), so
    the series leaves stack to ``[C, ticks]``."""
    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.topo import committee

    canon = base_model.canonical_fault_cfg(cfg)
    nc = cfg.faults.resolved_n_crashed(cfg.n)
    nb = cfg.faults.n_byzantine

    def finalize_fn(icfg, final, ys):
        del icfg, final  # full per-tick series — no reduction on this path
        return ys

    @jax.jit
    def sim(key):
        return committee.run_stacked(
            canon, key, jnp.int32(nc), jnp.int32(nb),
            probe=(probe, finalize_fn),
        )

    return sim


def _traced_committee(cfg: SimConfig, seed):
    """Committee hierarchy with stacked per-committee probe series.

    ``series`` leaves are ``[C, ticks]`` (lane 0 of the leading axis is
    committee 0); ``series["t"]`` is the inner tick axis.  Metrics are
    the committee outer aggregate (topo/committee.metrics), bit-identical
    to ``run_simulation``'s on this config (probes only read)."""
    from blockchain_simulator_tpu.topo import committee

    key = jax.random.key(cfg.seed if seed is None else seed)
    finals, ys = jax.block_until_ready(_committee_traced_fn(cfg)(key))
    series = _np_series(ys)
    series["t"] = np.arange(committee.inner_cfg(cfg).ticks)
    return committee.metrics(cfg, finals), series


def _reject_stacked(cfg: SimConfig) -> None:
    # profile_run only: the profiler capture wraps the flat static
    # program; probe tracing handles committee via _traced_committee
    if cfg.topology == "committee":
        raise NotImplementedError(
            "profile_run wraps the flat (state, bufs) engine; profile the "
            "inner committee config instead (probe tracing — run_traced — "
            "does support committee, with stacked [C, ticks] series)"
        )


def run_traced(cfg: SimConfig, seed: int | None = None):
    """Run one simulation recording a probe series.

    Returns ``(metrics, series)`` where ``series`` maps probe names to
    ``np.ndarray``.  Dispatches through ``runner.use_round_schedule``
    exactly like ``run_simulation`` — an ineligible explicit
    ``schedule='round'`` raises the same ``ValueError``, and cpp-only
    fidelity flags are rejected the same way (``runner._reject_cpp_only``)
    — so the traced simulator is ALWAYS the one the untraced run would use:

    - tick engine: per-tick samples, length ``cfg.ticks`` (no ``"t"`` key;
      the sample index IS the tick).  ``cfg.with_(schedule="tick")`` forces
      this arm for bit-exact tick series on any config.  The kregular
      overlay rides this arm too (its tables are trace constants).
    - fast paths: per-round / per-heartbeat samples with a ``"t"`` array of
      virtual ticks (see the module docstring for each protocol's keys).
    - committee hierarchy: stacked ``[C, ticks]`` series, one lane per
      committee, plus the inner ``"t"`` tick axis (per-committee counter
      tracks and instant events in the chrome-trace export).
    """
    from blockchain_simulator_tpu.runner import (
        _reject_cpp_only,
        use_round_schedule,
    )

    _reject_cpp_only(cfg)
    if cfg.topology == "committee":
        use_round_schedule(cfg)  # validates schedule='round' (always tick)
        return _traced_committee(cfg, seed)
    if use_round_schedule(cfg):  # raises on ineligible explicit 'round'
        if cfg.protocol == "pbft":
            return _traced_pbft_round(cfg, seed)
        if cfg.protocol == "raft":
            return _traced_raft_hb(cfg, seed)
        return _traced_mixed_fast(cfg, seed)
    return _traced_tick(cfg, seed)


def events_from_series(series: dict, name: str) -> np.ndarray:
    """Sample indices at which a monotone counter series increments — the
    reconstruction of the reference's per-event log timestamps (e.g.
    pbft-node.cc:259 commit lines) from the recorded time series.  For
    per-tick series the index is the tick; fast-path series map indices to
    ticks via ``series["t"]``."""
    s = np.asarray(series[name])
    prev = np.concatenate([[0], s[:-1]])
    return np.flatnonzero(s > prev)


# to_chrome_trace caps each counter track's sample count so multi-hour
# windows stay loadable in the Perfetto UI; instant events are never dropped.
MAX_COUNTER_SAMPLES = 2000


def chrome_events(series: dict, name: str = "sim", pid: int = 0,
                  ) -> list[dict]:
    """The Chrome-trace event list of one probe series dict — the body of
    :func:`to_chrome_trace`.  ``pid`` namespaces the process row."""
    ts_map = np.asarray(series["t"]) if "t" in series else None
    events: list[dict] = [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": name}},
    ]
    tid = 0

    def emit(label: str, v: np.ndarray) -> None:
        nonlocal tid
        t_axis = (
            ts_map
            if ts_map is not None and len(ts_map) == len(v)
            else np.arange(len(v))
        )
        tid += 1
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": label},
        })
        stride = max(1, len(v) // MAX_COUNTER_SAMPLES)
        for i in range(0, len(v), stride):
            events.append({
                "name": label, "ph": "C", "pid": pid, "tid": 0,
                "ts": int(t_axis[i]) * 1000,
                "args": {label: float(v[i])},
            })
        d = np.diff(v.astype(np.int64), prepend=0)
        if np.all(d >= 0):  # monotone counter: increments are events
            for i in np.flatnonzero(d > 0):
                events.append({
                    "name": label, "ph": "i", "s": "t", "pid": pid,
                    "tid": tid, "ts": int(t_axis[i]) * 1000,
                    "args": {"value": int(v[i]), "delta": int(d[i])},
                })

    for k in sorted(series):
        if k == "t":
            continue
        v = np.asarray(series[k])
        if v.size == 0 or v.ndim not in (1, 2):
            continue
        if v.ndim == 1:
            emit(k, v)
        else:
            # stacked committee series [C, m] (run_traced committee arm):
            # one counter track + per-committee instant events per lane
            for ci in range(v.shape[0]):
                emit(f"{k}/c{ci}", v[ci])
    return events


def to_chrome_trace(series: dict, path, name: str = "sim") -> dict:
    """Convert a probe series dict to a Chrome-trace JSON timeline.

    Written for ui.perfetto.dev / chrome://tracing: one process named
    ``name``; every 1-D series becomes a counter track ("ph": "C",
    downsampled to <= MAX_COUNTER_SAMPLES points), and every monotone
    non-decreasing series additionally emits one INSTANT event ("ph": "i")
    per increment — commits, elections, view changes as discrete marks on
    their own named tracks.  Virtual time maps 1 tick (= 1 simulated ms) to
    1000 trace-µs, so the UI's ms ruler reads in simulated milliseconds.

    ``series["t"]`` (fast-path series) supplies sample->tick mapping for
    every same-length series; series without a matching ``t`` use their
    sample index as the tick.  Returns ``{"events", "instants", "path"}``
    (counts, for callers that report them).
    """
    events = chrome_events(series, name=name, pid=0)
    n_instant = sum(1 for e in events if e.get("ph") == "i")
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    return {"events": len(events), "instants": n_instant, "path": str(path)}


def profile_run(cfg: SimConfig, logdir: str, seed: int | None = None) -> dict:
    """Capture a profiler trace of one (pre-compiled) run into ``logdir``.

    Returns the run metrics augmented with wallclock timings.  View with
    TensorBoard's profile plugin or ui.perfetto.dev.
    """
    from blockchain_simulator_tpu.runner import make_sim_fn

    _reject_stacked(cfg)
    proto = get_protocol(cfg.protocol)
    sim = make_sim_fn(cfg)
    key = jax.random.key(cfg.seed if seed is None else seed)
    t0 = time.perf_counter()
    jax.block_until_ready(sim(key))  # compile + warm outside the capture
    compile_s = time.perf_counter() - t0
    with jax.profiler.trace(logdir):
        t0 = time.perf_counter()
        final = jax.block_until_ready(sim(key))
        run_s = time.perf_counter() - t0
    m = proto.metrics(cfg, final)
    m["compile_plus_first_run_s"] = compile_s
    m["profiled_run_s"] = run_s
    return m
