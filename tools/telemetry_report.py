"""ARTIFACT_telemetry.json generator: the telemetry layer's own gate.

Assembles spans + metrics + the access log from a REAL in-process fleet
drill (FleetRouter over two LocalReplica daemons — the serving path
router→replica→batcher→dispatch) and gates two contracts of
utils/telemetry.py (ISSUE 14):

- **span completeness** — every request the router admitted has a closed
  span tree: a ``router.request`` root, at least one ``router.send``
  child, and a ``serve.request`` on the same trace parented to a send
  span (ok answers must also carry a ``serve.dispatch`` segment).  A
  request with spans missing is a miss; the gate is zero misses.
- **wall-time coverage** — for served requests, the named leaf segments
  (serve.admit / queue_wait / batch_wait / dispatch / answer, measured —
  no residuals) must account for >= 95% of at least one request's whole
  client-observed wall (the ``router.request`` duration): the "where does
  the p50 live" question answered by data.

The full run (no ``--quick``) adds the **overhead leg**: tools/
serve_bench.py runs twice in subprocesses — telemetry disarmed, then
armed (``BLOCKSIM_SPANS_JSONL`` + ``BLOCKSIM_FLIGHT_DIR`` set) — and the
armed sustained req/s must be within 5% of the disarmed run measured in
the same artifact (the within-one-artifact ratio rule, ROADMAP floors
note); the PR 6 floor comparison is recorded alongside.  The armed run
is second, so the committed ARTIFACT_serve_bench.json always shows
telemetry-armed serving.

Usage:
    JAX_PLATFORMS=cpu python tools/telemetry_report.py [--quick]

``--quick`` = fleet drill + gates only (~30 s warm; tools/lint.sh chains
it, ``TELEM=0`` skips).  Lands ``telemetry_span_miss`` /
``telemetry_coverage_pct`` / ``telemetry_overhead_pct`` rows in
runs.jsonl when ``$BLOCKSIM_RUNS_JSONL`` is set (charted, never gated by
bench_compare — this report's exit code is the gate).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys as _sys
import tempfile
import time

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "ARTIFACT_telemetry.json")
SERVE_ARTIFACT = os.path.join(REPO, "ARTIFACT_serve_bench.json")

# the committed PR 6 serving floor (2-core box; ROADMAP "Measured
# floors") — recorded next to the in-artifact overhead ratio, which is
# the gated number (this box has 1 core, so cross-PR walls are context)
PR6_FLOOR_RPS = 19.6


def _force_platform(platform: str | None) -> None:
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


# --------------------------------------------------------- fleet drill ---


def fleet_drill(workdir: str, n_requests: int = 8) -> dict:
    """Drive a router→replica→batcher→dispatch request set with spans
    captured; returns spans + responses + the router/replica stats."""
    from blockchain_simulator_tpu.chaos.fleet_scenarios import LocalReplica
    from blockchain_simulator_tpu.serve.router import FleetRouter
    from blockchain_simulator_tpu.utils import telemetry

    tpl = {"protocol": "pbft", "n": 8, "sim_ms": 200,
           "stat_sampler": "exact"}
    replicas = [
        LocalReplica("replica-0", max_batch=4, max_wait_ms=60.0),
        LocalReplica("replica-1", max_batch=4, max_wait_ms=60.0),
    ]
    responses: list[dict] = []
    with telemetry.capture() as spans:
        router = FleetRouter(replicas, probe=False)
        try:
            pendings = []
            for i in range(n_requests):
                obj = dict(tpl, seed=100 + i, id=f"tr-{i}",
                           faults={"n_byzantine": i % 2})
                pendings.append(router.submit(obj))
            responses = [pd.result(300) for pd in pendings]
            # one deliberate edge rejection: completeness must hold for
            # rejected admissions too (root span, no serve children)
            bad = router.request({"protocol": "nope", "id": "tr-bad"})
            responses.append(bad)
            router_stats = router.stats()
        finally:
            router.close()
            for rep in replicas:
                rep.close()
    # the replica-side /metrics surface, over real HTTP -- checked while
    # the replicas were alive would race close(); re-exposed from the
    # process-global registry instead (same body the daemon serves)
    exposition = telemetry.metrics.exposition()
    return {
        "spans": spans,
        "responses": responses,
        "router_stats": router_stats,
        "exposition": exposition,
    }


def _by_trace(spans) -> dict:
    out: dict = {}
    for rec in spans:
        if rec.get("kind") == "span":
            out.setdefault(str(rec.get("trace")), []).append(rec)
    return out


def completeness(spans, responses) -> dict:
    """The span-completeness gate: every admitted id has a closed tree."""
    traces = _by_trace(spans)
    misses: list[str] = []
    checked = 0
    for resp in responses:
        rid = resp.get("id")
        ok = resp.get("status") == "ok"
        # find this id's router.request root
        root = None
        for recs in traces.values():
            for rec in recs:
                if rec.get("name") == "router.request" \
                        and (rec.get("attrs") or {}).get("id") == rid:
                    root = rec
                    break
            if root:
                break
        if root is None:
            misses.append(f"{rid}: no router.request root span")
            continue
        checked += 1
        recs = traces.get(str(root.get("trace")), [])
        names = {r.get("name") for r in recs}
        send_ids = {r.get("id") for r in recs
                    if r.get("name") == "router.send"}
        if ok and not send_ids:
            misses.append(f"{rid}: no router.send span")
        serve_roots = [r for r in recs if r.get("name") == "serve.request"]
        if ok:
            if not serve_roots:
                misses.append(f"{rid}: no serve.request span on the trace")
            elif not any(r.get("parent") in send_ids for r in serve_roots):
                misses.append(
                    f"{rid}: serve.request not parented to a router.send")
            if "serve.dispatch" not in names:
                misses.append(f"{rid}: served without a serve.dispatch span")
    return {"checked": checked, "misses": misses}


LEAF_SEGMENTS = ("serve.admit", "serve.queue_wait", "serve.batch_wait",
                 "serve.dispatch", "serve.answer")


def coverage(spans, responses) -> dict:
    """Per served request: named-leaf-segment wall over the client-observed
    ``router.request`` wall; the gate takes the best-covered request (the
    acceptance asks for >= 95% of ONE request's wall)."""
    traces = _by_trace(spans)
    per_request: dict[str, float] = {}
    for trace_id, recs in traces.items():
        root = next((r for r in recs if r.get("name") == "router.request"),
                    None)
        if root is None or root.get("status") != "ok":
            continue
        wall = float(root.get("dur_ms", 0.0))
        if wall <= 0:
            continue
        leaf = sum(float(r.get("dur_ms", 0.0)) for r in recs
                   if r.get("name") in LEAF_SEGMENTS)
        rid = (root.get("attrs") or {}).get("id", trace_id)
        per_request[str(rid)] = round(100.0 * min(leaf, wall) / wall, 2)
    vals = sorted(per_request.values())
    return {
        "per_request_pct": per_request,
        "best_pct": vals[-1] if vals else 0.0,
        "median_pct": vals[len(vals) // 2] if vals else 0.0,
    }


# -------------------------------------------------------- overhead leg ---


def serve_bench_leg(armed: bool, workdir: str) -> dict:
    """One tools/serve_bench.py subprocess; ``armed=True`` sets the span
    log + flight dir so every request pays the full telemetry path."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               p for p in (REPO, os.environ.get("PYTHONPATH")) if p)}
    env.pop("BLOCKSIM_SPANS_JSONL", None)
    env.pop("BLOCKSIM_FLIGHT_DIR", None)
    if armed:
        env["BLOCKSIM_SPANS_JSONL"] = os.path.join(
            workdir, "bench_spans.jsonl")
        env["BLOCKSIM_FLIGHT_DIR"] = workdir
    t0 = time.monotonic()
    proc = subprocess.run(
        [_sys.executable, os.path.join(REPO, "tools", "serve_bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=3600,
    )
    rec: dict = {"armed": armed, "rc": proc.returncode,
                 "wall_s": round(time.monotonic() - t0, 1)}
    try:
        with open(SERVE_ARTIFACT) as f:
            bench = json.load(f)
        rec["rps"] = bench.get("warm", {}).get("rps")
        rec["p50_ms"] = bench.get("warm", {}).get("p50_ms")
        rec["p99_ms"] = bench.get("warm", {}).get("p99_ms")
    except (OSError, json.JSONDecodeError) as e:
        rec["error"] = f"artifact unreadable: {e}"
    if armed:
        spans_path = env["BLOCKSIM_SPANS_JSONL"]
        try:
            rec["spans_logged"] = sum(1 for _ in open(spans_path))
        except OSError:
            rec["spans_logged"] = 0
    if proc.returncode != 0:
        rec["tail"] = proc.stdout[-500:] + proc.stderr[-300:]
    return rec


# ---------------------------------------------------------------- main ---


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="telemetry_report")
    p.add_argument("--quick", action="store_true",
                   help="fleet drill + gates only, no serve_bench "
                        "overhead leg (tools/lint.sh chains this)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--out", default=None,
                   help="artifact path (default ARTIFACT_telemetry.json "
                        "on full runs, none on --quick)")
    p.add_argument("--platform", default="cpu")
    args = p.parse_args(argv)

    _force_platform(args.platform)
    from blockchain_simulator_tpu.utils import obs

    workdir = tempfile.mkdtemp(prefix="telemetry_report_")
    t_start = time.monotonic()
    drill = fleet_drill(workdir, n_requests=args.requests)
    comp = completeness(drill["spans"], drill["responses"])
    cov = coverage(drill["spans"], drill["responses"])
    ok_responses = sum(1 for r in drill["responses"]
                       if r.get("status") == "ok")
    expo = drill["exposition"]
    expo_ok = ("blocksim_serve_request_ms_bucket" in expo
               and "blocksim_fleet_received_total" in expo)

    overhead = None
    legs = None
    if not args.quick:
        disarmed = serve_bench_leg(False, workdir)
        armed = serve_bench_leg(True, workdir)
        legs = {"disarmed": disarmed, "armed": armed}
        if isinstance(disarmed.get("rps"), (int, float)) \
                and isinstance(armed.get("rps"), (int, float)) \
                and disarmed["rps"]:
            overhead = round(
                100.0 * (disarmed["rps"] - armed["rps"]) / disarmed["rps"],
                2)

    gates = {
        "span_completeness": len(comp["misses"]) == 0 and comp["checked"] > 0,
        "coverage_95": cov["best_pct"] >= 95.0,
        "exposition": expo_ok,
        "drill_served": ok_responses == args.requests,
    }
    if legs is not None:
        gates["bench_rc"] = (legs["disarmed"]["rc"] == 0
                             and legs["armed"]["rc"] == 0)
        # the gated ratio is within-THIS-artifact (1-core box vs the
        # 2-core PR 6 floor is context, not a gate); a negative overhead
        # is measurement noise in the armed run's favor
        gates["overhead_5pct"] = overhead is not None and overhead <= 5.0

    artifact = {
        "metric": "telemetry_report",
        "ok": all(gates.values()),
        "gates": gates,
        "drill": {
            "requests": args.requests,
            "served": ok_responses,
            "spans_captured": len(drill["spans"]),
            "router_received": drill["router_stats"].get("received"),
            "router_latency_ms": drill["router_stats"].get("latency_ms"),
        },
        "completeness": comp,
        "coverage": cov,
        "overhead_pct": overhead,
        "serve_bench_legs": legs,
        "pr6_floor_rps": PR6_FLOOR_RPS,
        "armed_within_5pct_of_pr6_floor": (
            None if legs is None or not isinstance(
                legs["armed"].get("rps"), (int, float))
            else legs["armed"]["rps"] >= 0.95 * PR6_FLOOR_RPS),
        "exposition_sample": "\n".join(expo.splitlines()[:12]),
        "wall_s": round(time.monotonic() - t_start, 1),
    }
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # charted-never-gated trajectory rows (bench_compare telemetry_ rule)
    obs.finalize({"metric": "telemetry_span_miss",
                  "value": len(comp["misses"]), "unit": "requests"})
    obs.finalize({"metric": "telemetry_coverage_pct",
                  "value": cov["best_pct"], "unit": "%"})
    if overhead is not None:
        obs.finalize({"metric": "telemetry_overhead_pct",
                      "value": overhead, "unit": "%"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    if not artifact["ok"]:
        print(f"telemetry_report: GATES NOT MET ({gates})", flush=True)
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    _sys.exit(main())
