"""Micro-batched scenario dispatch: N queued requests, one vmapped program.

The execution substrate of the scenario server (serve/server.py).  A batch
is a list of admitted :class:`~blockchain_simulator_tpu.serve.schema.
ScenarioRequest` sharing one canonical fault structure (their batch group);
dispatch runs them as ONE vmapped dynamic-fault-operand executable — the
same ``parallel/sweep.dyn_batched_fn`` registry entry the fault sweeps
compile, so a warm sweep cache serves traffic with zero compiles.

Batch-size buckets: a vmapped executable is shape-specialized on its batch
axis, so serving raw queue depths would compile one program per observed
batch size.  Batches are instead padded up to the next power-of-two bucket
(capped at the server's ``max_batch``) by repeating the last lane — at most
``log2(max_batch) + 1`` executables per group ever exist, and a padded lane
costs one discarded vmap lane of compute.  The occupancy histogram on the
stats endpoint makes the padding observable (KNOWN_ISSUES: the
batching/latency trade-off entry).

Robustness: a failed batched dispatch degrades to per-request solo
dispatch (``serve-solo`` executable, also registry-cached) so one poisoned
request fails alone — its peers still get answers — and every lane failure
surfaces as a typed :class:`~blockchain_simulator_tpu.serve.schema.
ServeError` response, never a crashed daemon.

Bit-equality: under ``stat_sampler="exact"`` a request's metrics are
bit-equal whether served solo, batched, or padded (integer draws from the
same per-lane key); the ``"normal"`` CLT sampler keeps the ±1-tick float
caveat documented in parallel/sweep.py.  tests/test_zserve.py pins the
exact-sampler equalities; tools/serve_bench.py re-checks them on the
artifact workload.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.chaos import inject
from blockchain_simulator_tpu.models.base import sim_metrics
from blockchain_simulator_tpu.runner import make_dyn_sim_fn
from blockchain_simulator_tpu.serve import schema
from blockchain_simulator_tpu.utils import aotcache, obs, telemetry


@aotcache.cached_factory("serve-solo")
def _solo_fn(canon):
    """Jitted ``sim(key, n_crashed, n_byzantine) -> final`` for one
    canonical fault structure: the un-vmapped degrade/solo path of the
    scenario server.  One registry entry per structure serves every
    (seed, fault count) request solo — the serving analog of the sweep
    contract, audited as ``serve_solo.*`` in lint/graph/programs.py."""
    return jax.jit(make_dyn_sim_fn(canon))


def bucket_size(n: int, max_batch: int) -> int:
    """The padded batch size actually dispatched for ``n`` queued requests:
    next power of two >= n, capped at ``max_batch`` (n is never above it —
    the batcher flushes at max_batch)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


def _operands(reqs):
    """(keys[B], n_crashed[B], n_byzantine[B]) for a padded request list."""
    keys = jax.vmap(jax.random.key)(
        jnp.asarray([r.seed for r in reqs], jnp.uint32)
    )
    nc = jnp.asarray(
        [r.cfg.faults.resolved_n_crashed(r.cfg.n) for r in reqs], jnp.int32
    )
    nb = jnp.asarray([r.cfg.faults.n_byzantine for r in reqs], jnp.int32)
    return keys, nc, nb


def _solo_metrics(req):
    """Run one request through the solo executable; returns its metrics.
    The ``serve.solo_dispatch`` chaos point fires first with the request
    id, so a drill can poison exactly one request (chaos/inject.py).
    Dispatch stamps (span synthesis at answer time, serve/server._answer)
    bracket the whole attempt; inside it three spans name the host's
    states — ``serve.dispatch.operands`` / ``.execute`` / ``.readback``,
    children of the request's ``serve.dispatch`` span, whose id is
    pre-minted here because the server only emits that span at answer
    time.  All host-side, per the telemetry rule."""
    # stamp BEFORE the chaos point (and fire the point INSIDE the
    # try/finally): a poisoned request that raises at the injection
    # still records a near-zero dispatch-attempt span instead of
    # leaving a stale batched-dispatch stamp — or no stamp at all —
    # behind for the span synthesizer
    req.t_dispatch0 = time.monotonic()
    req.trace_id = req.trace_id or telemetry.new_trace_id()
    req.dispatch_span = telemetry.new_span_id()
    ctx = telemetry.TraceContext(req.trace_id, req.dispatch_span)
    try:
        inject.chaos_point("serve.solo_dispatch", req_id=req.req_id)
        with telemetry.span("serve.dispatch.operands", ctx=ctx,
                            id=req.req_id):
            keys, nc, nb = _operands([req])
            args = (keys[0], nc[0], nb[0])
            if req.probe is not None:
                # the armed solo twin (consobs-solo registry entry) —
                # same operands, final state bit-equal under the exact
                # sampler; the probe summary rides the metrics row
                from blockchain_simulator_tpu.obsim import build as obsb

                sim = obsb.probed_solo_fn(req.canon, req.probe)
            else:
                sim = _solo_fn(req.canon)
        with telemetry.span("serve.dispatch.execute", ctx=ctx,
                            id=req.req_id):
            out = jax.block_until_ready(sim(*args))
        with telemetry.span("serve.dispatch.readback", ctx=ctx,
                            id=req.req_id):
            if req.probe is None:
                return sim_metrics(req.cfg, out)
            from blockchain_simulator_tpu.obsim import host as obsh
            from blockchain_simulator_tpu.obsim import schema as obs_schema

            final, probes = out
            m = sim_metrics(req.cfg, final)
            m["probe"] = obs_schema.summarize(req.canon, req.probe, probes)
            obsh.note_violations(m["probe"], req.cfg, req.seed)
            return m
    finally:
        req.t_dispatch1 = time.monotonic()


def run_batch(reqs, max_batch: int, force_solo: bool = False,
              solo_reason: str | None = None, mesh=None,
              journal=None) -> list[tuple]:
    """Dispatch one same-group batch; returns ``[(req, response)]`` in
    order, one entry per request, every response either 200 or a typed
    error body.

    ``mesh`` routes the batched dispatch onto the mesh-partitioned sweep
    executable (``sweep.run_dyn_points(mesh=...)`` →
    ``mesh_dyn_batched_fn`` — the batch axis shards over the mesh's sweep
    axis; ROADMAP item 1b).  Solo/degrade dispatches stay single-device
    regardless: a one-request program has no batch axis to shard.

    One request dispatches solo; two or more dispatch as one vmapped
    executable over the bucket-padded lane set.  Any batched failure
    degrades to per-request solo dispatch (the failure count lands in the
    server's ``degraded_batches`` stat via the ``degraded`` flag) and any
    SOLO failure answers as the typed ``dispatch-failed`` error — the
    signal the server's quarantine keys on.

    ``force_solo=True`` skips the batched attempt entirely (the server's
    circuit breaker, when a group's vmapped path is known-bad);
    ``solo_reason`` labels the batch ``mode`` of such intentional solo
    dispatches (``breaker-solo``, ``quarantined-solo``) so the access log
    distinguishes policy from degradation.

    ``journal`` (a parallel/journal.SweepJournal — ``ScenarioServer(
    journal_path=)``, daemon ``--journal``): batched flushes ride the
    durable-sweep journal as single-chunk dispatches keyed on their
    content (canonical structure + the padded point list), so a long
    sweep-shaped request batch survives a daemon death — the WAL replays
    the *admissions*, and the journal answers any batch whose rows were
    already computed without recompiling or re-running it.  Solo and
    degrade dispatches stay un-journaled (their recompute is one
    request)."""
    t0 = time.monotonic()
    canon = reqs[0].canon
    group = obs.config_hash(canon)
    if len(reqs) == 1:
        req = reqs[0]
        batch = {"size": 1, "padded": 1, "mode": solo_reason or "solo",
                 "group": group}
        try:
            m = _solo_metrics(req)
        except Exception as e:  # typed, never a crashed worker
            err = schema.DispatchFailedError(f"solo dispatch failed: "
                                             f"{type(e).__name__}: {e}")
            return [(req, err.to_response(req.req_id))]
        latency = time.monotonic() - (req.submitted or t0)
        return [(req, schema.ok_response(req, m, batch, latency))]

    if force_solo:
        # the breaker's solo-only mode: each request alone through the
        # solo executable, by policy (not degradation — no degraded flag)
        out = []
        solo = {"size": len(reqs), "padded": 1,
                "mode": solo_reason or "forced-solo", "group": group}
        for req in reqs:
            try:
                m = _solo_metrics(req)
            except Exception as e:
                err = schema.DispatchFailedError(
                    f"solo dispatch failed: {type(e).__name__}: {e}"
                )
                out.append((req, err.to_response(req.req_id)))
                continue
            latency = time.monotonic() - (req.submitted or t0)
            out.append((req, schema.ok_response(req, m, solo, latency)))
        return out

    padded = bucket_size(len(reqs), max_batch)
    lanes = list(reqs) + [reqs[-1]] * (padded - len(reqs))
    batch = {"size": len(reqs), "padded": padded, "mode": "batched",
             "group": group}
    if mesh is not None:
        from blockchain_simulator_tpu.parallel import partition

        batch["mesh"] = partition.mesh_shape_dict(mesh)
    try:
        from blockchain_simulator_tpu.parallel import sweep

        # the sweeps' group-dispatch primitive, fed the queue instead of a
        # cross product; record=False — the server writes its own per-
        # request access-log records; n_out skips pad-lane metrics
        d0 = time.monotonic()
        try:
            # the batcher groups on (canon, probe), so one flush is
            # probe-homogeneous: reqs[0].probe speaks for every lane
            rows = sweep.run_dyn_points(
                canon, [(r.cfg, r.seed) for r in lanes], record=False,
                n_out=len(reqs), mesh=mesh, journal=journal,
                probe=reqs[0].probe,
            )
        finally:
            d1 = time.monotonic()
            for req in reqs:
                req.t_dispatch0, req.t_dispatch1 = d0, d1
        out = []
        for req, m in zip(reqs, rows):
            latency = time.monotonic() - (req.submitted or t0)
            out.append((req, schema.ok_response(req, m, batch, latency)))
        return out
    except Exception:
        # a batch peer failed: serve every lane solo so one poisoned
        # request cannot take its neighbors' answers down with it.  The
        # failed flush's stamps are cleared first — each solo retry
        # below re-stamps its own attempt, and a request whose solo
        # never starts must not carry the dead batched dispatch's
        # timing as if it ran
        for req in reqs:
            req.t_dispatch0 = req.t_dispatch1 = 0.0
        out = []
        solo = {"size": len(reqs), "padded": 1, "mode": "degraded-solo",
                "group": group, "degraded": True}
        for req in reqs:
            try:
                m = _solo_metrics(req)
            except Exception as e:
                err = schema.DispatchFailedError(
                    f"dispatch failed (batched, then solo): "
                    f"{type(e).__name__}: {e}"
                )
                out.append((req, err.to_response(req.req_id)))
                continue
            latency = time.monotonic() - (req.submitted or t0)
            out.append((req, schema.ok_response(req, m, solo, latency)))
        return out
