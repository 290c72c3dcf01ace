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

The lone flush (one queued request: the solo path, and every breaker,
quarantine and degrade retry) has three host states between admission and
answer, each a span (:func:`_solo_metrics`): ``serve.dispatch.operands``
builds the operands on the host and runs no device program (the key wrapped
around host key data, the fault counts numpy scalars that ride the call;
attr ``device_programs``); ``serve.dispatch.execute`` is the ONE call of the
solo executable and the wait for it (while the device runs, the host picks
the leaves the readback will fetch); ``serve.dispatch.readback`` is ONE
``jax.device_get`` of the leaves the protocol's ``metrics`` reads
(``models/base.metric_leaves`` / ``host_final``; a committee stack's is
``topo/committee.metrics``' own, under its span inside this one) and
``metrics`` on numpy (attrs ``leaves``, ``bytes``, ``fetches``).  Built on
the device and read leaf by leaf, the same three scalars and nine small
leaves were some twenty host round trips: about 5 ms of operands and 5.3 ms
of readback in a 36 ms flush at n=1024 on the chip (PERF.md section 6,
PR 52).

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
import numpy as np

from blockchain_simulator_tpu.chaos import inject
from blockchain_simulator_tpu.models import base as base_model
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


def _key(seed: int):
    """``jax.random.key(seed)`` for one request, with no device program:
    the typed key wrapped around host key data (the two words ``[0, seed]``,
    what threefry's seeding program makes of a 32-bit seed), one 8-byte
    upload that rides the call.  Seeding on the device is a program launched
    (and, under ``vmap``, traced) per request.  A seed outside uint32 raises,
    as it did when the seed was uploaded as one.  Under another default PRNG
    implementation the words would be another key than the static run's:
    refused, which the caller answers as a typed dispatch failure."""
    impl = jax.config.jax_default_prng_impl
    if impl != "threefry2x32":
        raise NotImplementedError(
            f"jax_default_prng_impl={impl}: the served key is built on the "
            "host from threefry2x32's seeding")
    return jax.random.wrap_key_data(np.array([0, seed], np.uint32),
                                    impl="threefry2x32")


def _solo_metrics(req):
    """Run one request through the solo executable; returns its metrics.
    The ``serve.solo_dispatch`` chaos point fires first with the request
    id, so a drill can poison exactly one request (chaos/inject.py).
    Dispatch stamps (span synthesis at answer time, serve/server._answer)
    bracket the whole attempt; inside it three spans name the host's
    states — ``serve.dispatch.operands`` / ``.execute`` / ``.readback``
    (module docstring: what each holds, and its attrs), children of the
    request's ``serve.dispatch`` span, whose id is pre-minted here because
    the server only emits that span at answer time.  All host-side, per
    the telemetry rule."""
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
        cfg = req.cfg
        with telemetry.span("serve.dispatch.operands", ctx=ctx,
                            id=req.req_id, device_programs=0):
            args = (_key(req.seed),
                    np.int32(cfg.faults.resolved_n_crashed(cfg.n)),
                    np.int32(cfg.faults.n_byzantine))
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
            out = sim(*args)
            # while the device runs: which leaves the readback will fetch,
            # for its span's attrs (shapes alone, no value is touched), so
            # that nothing stands between the two spans
            final, probes = out if req.probe is not None else (out, None)
            picked = base_model.metric_leaves(cfg, final)
            leaves = jax.tree.leaves(picked)
            fetched = {"leaves": len(leaves), "fetches": 1,
                       "bytes": sum(x.nbytes for x in leaves)}
            jax.block_until_ready(out)
        with telemetry.span("serve.dispatch.readback", ctx=ctx,
                            id=req.req_id, **fetched):
            m = base_model.sim_metrics(
                cfg, base_model.host_final(cfg, final, picked))
            if req.probe is None:
                return m
            from blockchain_simulator_tpu.obsim import host as obsh
            from blockchain_simulator_tpu.obsim import schema as obs_schema

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
