"""Probed twins of the dynamic-fault program factories.

``make_probed_dyn_sim_fn(cfg, pcfg)`` mirrors ``runner.make_dyn_sim_fn``
arm for arm — committee stack (tiles of lanes), round-schedule raft heartbeat
fast path (taps thread through the ``lax.cond`` phase split), round-
blocked PBFT, general tick engine — returning ``sim(key, n_crashed,
n_byzantine) -> (final_state, probes)`` with the probe pytree described
in :mod:`obsim.schema`.

Registry discipline (utils/aotcache.py): the probed programs live under
their OWN ``consobs-*`` factory names keyed ``(cfg, pcfg, …)`` — one
executable per (fault structure, probe config) — and the disarmed
factories are not touched at all, so today's programs stay byte-identical
(fingerprint pin in tests/test_zzobsim.py).  The batched/mesh twins
mirror parallel/sweep.py's ``dyn_batched_fn`` / ``multi_seed_fn`` /
``mesh_dyn_batched_fn`` shapes: ``vmap`` for the sweep batch, the
scatter-free ``lax.map`` body (partition.seq_map, KNOWN_ISSUES #0i) for
the multi-seed arm, and shard_map/pjit over the mesh's sweep axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.models import base as base_model
from blockchain_simulator_tpu.models.base import get_protocol
from blockchain_simulator_tpu.obsim import schema
from blockchain_simulator_tpu.obsim import taps
from blockchain_simulator_tpu.runner import (
    _reject_cpp_only,
    check_batchable,
    use_round_schedule,
)
from blockchain_simulator_tpu.utils import aotcache
from blockchain_simulator_tpu.utils import prng


def make_probed_dyn_sim_fn(cfg, pcfg: schema.ProbeConfig):
    """``sim(key, n_crashed, n_byzantine) -> (final_state, probes)`` —
    runner.make_dyn_sim_fn with the taps armed.  UNJITTED, like its twin:
    the factories below own the jit/vmap/mesh wrappers.  The state
    trajectory is bit-identical to the disarmed program (taps read state,
    consume zero PRNG), so primary metrics are bit-equal under the exact
    sampler — the tests' contract."""
    cfg = base_model.canonical_fault_cfg(cfg)
    check_batchable(cfg)
    _reject_cpp_only(cfg)
    schema.series_fields(cfg.protocol)  # typed refusal before tracing
    n = cfg.n

    if cfg.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        use_round_schedule(cfg)  # validates schedule='round' (always tick)

        def finalize_fn(icfg, final, ys):
            return taps.finalize(icfg, pcfg, final, ys, icfg.ticks)

        def sim_comm(key, n_crashed, n_byzantine):
            return committee.run_stacked(
                cfg, key, n_crashed, n_byzantine,
                probe=(taps.sample, finalize_fn),
            )

        return sim_comm

    if use_round_schedule(cfg):
        if cfg.protocol == "raft":
            from blockchain_simulator_tpu.models import raft as raft_tick
            from blockchain_simulator_tpu.models import raft_hb

            # both lax.cond branches must reduce to one aval: clamp the
            # window count to the SHORTER branch's sample count (prefix
            # ticks + heartbeats vs full ticks)
            m_fast = raft_hb.prefix_ticks(cfg) + raft_hb.n_hb_steps(cfg)
            w_eff = max(1, min(pcfg.windows, m_fast, cfg.ticks))

            def reduce_fn(series):
                m = jax.tree.leaves(series)[0].shape[0]
                red = {"series": taps.window(series, m, w_eff)}
                if pcfg.monitors:
                    red["liveness_lag"] = taps.liveness_lag(
                        series[schema.PROGRESS_FIELD["raft"]]
                    )
                return red

            probe = (
                functools.partial(taps.sample, cfg),
                taps.raft_steady_sample,
                reduce_fn,
            )

            def sim_hb(key, n_crashed, n_byzantine):
                state, bufs = raft_tick.init(
                    cfg, jax.random.fold_in(key, 0x1217)
                )
                state = base_model.apply_fault_masks(
                    cfg, state,
                    *base_model.dyn_fault_masks(n, n_crashed, n_byzantine),
                )
                final, red = raft_hb.scan_from_init(
                    cfg, state, bufs, key, probe=probe
                )
                probes = {"series": red["series"]}
                if pcfg.monitors:
                    mon = taps.monitors(cfg, final)
                    mon["liveness_lag"] = red["liveness_lag"]
                    probes["monitors"] = mon
                return final, probes

            return sim_hb

        from blockchain_simulator_tpu.models import pbft_round

        bt = cfg.pbft_block_interval_ms
        r_last = (cfg.ticks - 1) // bt
        if r_last < 1:
            raise ValueError(
                "cannot arm probes on a round-schedule run with zero "
                f"block rounds (ticks={cfg.ticks} <= interval={bt})"
            )

        def sim_round(key, n_crashed, n_byzantine):
            state, _ = pbft_round.init(cfg, jax.random.fold_in(key, 0x1217))
            state = base_model.apply_fault_masks(
                cfg, state,
                *base_model.dyn_fault_masks(n, n_crashed, n_byzantine),
            )
            final, ys = pbft_round.scan_rounds(
                cfg, state, key,
                with_probe=functools.partial(taps.sample, cfg),
            )
            return final, taps.finalize(cfg, pcfg, final, ys, r_last)

        return sim_round

    proto = get_protocol(cfg.protocol)

    def sim(key, n_crashed, n_byzantine):
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))
        state = base_model.apply_fault_masks(
            cfg, state,
            *base_model.dyn_fault_masks(n, n_crashed, n_byzantine),
        )

        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t))
            return (st, bf), taps.sample(cfg, st)

        (state, bufs), ys = jax.lax.scan(
            body, (state, bufs), jnp.arange(cfg.ticks)
        )
        return state, taps.finalize(cfg, pcfg, state, ys, cfg.ticks)

    return sim


# --------------------------------------------------- cached executables ---


@aotcache.cached_factory("consobs-solo")
def probed_solo_fn(cfg, pcfg: schema.ProbeConfig):
    """One probed solo executable per (fault structure, probe config) —
    the armed twin of serve/dispatch._solo_fn / a jitted
    runner.make_dyn_sim_fn."""
    return jax.jit(make_probed_dyn_sim_fn(cfg, pcfg))


@aotcache.cached_factory("consobs-batched")
def probed_batched_fn(cfg, pcfg: schema.ProbeConfig, multi_seed: bool = False):
    """The armed twin of sweep.dyn_batched_fn (``jit(vmap(...))``) and —
    with ``multi_seed=True``, which only disambiguates the registry key
    the way sweep.multi_seed_fn's ``n_seeds`` does — of the sequential
    ``lax.map`` multi-seed arm (partition.seq_map, scatter-free batch
    body, KNOWN_ISSUES #0i).  Probe leaves gain the leading batch axis."""
    from blockchain_simulator_tpu.parallel import partition

    fn = make_probed_dyn_sim_fn(cfg, pcfg)
    if multi_seed:
        return jax.jit(partition.seq_map(fn))
    return jax.jit(base_model.lane_vmap(fn))


@aotcache.cached_factory("consobs-mesh")
def probed_mesh_fn(cfg, pcfg: schema.ProbeConfig, mesh):
    """The armed twin of sweep.mesh_dyn_batched_fn, arm for arm: size-1
    mesh degenerates to :func:`probed_batched_fn`; a >1 nodes axis takes
    the explicit-sharding pjit arm (partition.batched_out_shardings is
    pytree-generic, so the probe leaves ride it — ``[B, C, …]`` committee
    probes shard their committee dim like the finals, flat ``[B, W]``
    series shard the batch axis); a sweep-only mesh shard_maps the
    scatter-free ``lax.map`` body with every out leaf — finals and probes
    alike carry the leading batch axis — on the sweep axis."""
    from jax.sharding import PartitionSpec as P

    from blockchain_simulator_tpu.parallel import partition
    from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS, SWEEP_AXIS

    fn = make_probed_dyn_sim_fn(cfg, pcfg)
    if partition.mesh_size(mesh) == 1:
        return probed_batched_fn(cfg, pcfg)
    if int(dict(mesh.shape).get(NODES_AXIS, 1)) > 1:
        batched = base_model.select_vmap(fn)
        b = max(partition.sweep_axis_size(mesh), 1)
        keys_sds = jax.eval_shape(
            lambda: jax.vmap(jax.random.key)(jnp.arange(b, dtype=jnp.uint32))
        )
        cnt_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
        outs = jax.eval_shape(batched, keys_sds, cnt_sds, cnt_sds)
        lane = P(SWEEP_AXIS) if partition.sweep_axis_size(mesh) > 1 else P()
        return partition.partition(
            batched, mesh,
            in_shardings=(lane, lane, lane),
            out_shardings=partition.batched_out_shardings(cfg, mesh, outs),
        )
    lane = P(SWEEP_AXIS)
    return partition.partition(
        partition.seq_map(fn), mesh,
        in_specs=(lane, lane, lane), out_specs=lane,
    )
