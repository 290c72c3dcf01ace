"""ARTIFACT_consobs.json generator: the consensus-observability gate.

Exercises the obsim/ probe layer (ISSUE 17) end to end and gates its
four contracts:

- **Coverage + bit-equality** — every {pbft, raft, paxos} x {dense,
  kregular, committee} combo (plus the pbft_round / raft_hb fast paths)
  runs back-to-back disarmed (the plain runner program) and armed (the
  ``consobs-solo`` registry twin): the armed run must return the probe
  schema's full field set for its protocol AND primary metrics that are
  dict-equal to the disarmed run under the exact sampler (taps consume
  zero PRNG, so equality is bitwise, not approximate).
- **Monitors** — every fault-free combo must be monitor-clean
  (``chaos/invariants.check_consensus_probes`` returns []), and the
  synthetic byzantine-forge leg — a quorum granted to a slot that was
  never proposed, injected into a real final state — must trip the
  agreement monitor (>= 1 violation) and, armed with a flight dir, dump
  a ``consensus-violation`` post-mortem (obsim/host.note_violations).
- **Forensics** — two armed runs of the same (cfg, seed) are identical;
  perturbing ONE (sample, field) of one series must make
  ``obsim/diverge.first_divergence`` locate exactly that (sample, field)
  — the "bit-equality pin failed, WHERE?" answer as data.
- **Overhead** — armed wall within 5% of disarmed, measured warm,
  min-of-N, back-to-back in THIS artifact (the within-one-artifact
  ratio rule): the 10k tick path (fewer reps on ``--quick``) and the
  serve capacity phase (a batched ``dispatch.run_batch`` flush, armed
  vs disarmed; measured on ``--quick`` but gated only at full scale —
  the short quick flush is noise-dominated).

Usage:
    JAX_PLATFORMS=cpu python tools/consensus_obs_report.py [--quick]
    JAX_PLATFORMS=cpu python tools/consensus_obs_report.py --forensics \
        --seeds 3 4 [--protocol pbft] [--topology full]

``--quick`` = small overhead workload, no artifact (tools/lint.sh
chains it; ``CONSOBS=0`` skips).  Lands ``consobs_overhead_pct`` /
``consobs_invariant_violations`` rows in runs.jsonl when
``$BLOCKSIM_RUNS_JSONL`` is set (charted, never gated by bench_compare
— this report's exit code is the gate).  ``--forensics`` is the
interactive mode: probe two seeds of one config and render their first
divergence (exit 0 either way; it is a lens, not a gate).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys as _sys
import tempfile
import time

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "ARTIFACT_consobs.json")


def _force_platform(platform: str | None) -> None:
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def _combo_cfgs() -> dict:
    """The 9 protocol x topology combos plus the two round-schedule fast
    paths, at the audit scale (lint/graph/programs.audit_configs sizes —
    degree 3 keeps the kregular gathers real, committees=2 stacks)."""
    from blockchain_simulator_tpu.utils.config import SimConfig

    out = {}
    for p in ("pbft", "raft", "paxos"):
        out[f"{p}_dense"] = SimConfig(protocol=p, n=8, sim_ms=200,
                                      stat_sampler="exact")
        out[f"{p}_kreg"] = SimConfig(protocol=p, n=8, sim_ms=200,
                                     fidelity="clean", topology="kregular",
                                     degree=3, stat_sampler="exact")
        out[f"{p}_comm"] = SimConfig(protocol=p, n=8, sim_ms=200,
                                     topology="committee", committees=2,
                                     stat_sampler="exact")
    out["pbft_round"] = SimConfig(protocol="pbft", n=8, sim_ms=200,
                                  delivery="stat", schedule="round",
                                  model_serialization=False,
                                  stat_sampler="exact")
    out["raft_hb"] = SimConfig(protocol="raft", n=8, sim_ms=400,
                               delivery="stat", schedule="round",
                               stat_sampler="exact")
    return out


@functools.lru_cache(maxsize=None)
def _disarmed_solo(canon):
    import jax

    from blockchain_simulator_tpu.runner import make_dyn_sim_fn

    return jax.jit(make_dyn_sim_fn(canon))


def _ops(cfg):
    fc = cfg.faults
    return int(fc.resolved_n_crashed(cfg.n)), int(fc.n_byzantine)


# ---------------------------------------------- coverage + bit-equality ---


def combo_leg(cfg, seed: int = 0) -> dict:
    """One combo's disarmed-vs-armed pair: primary-metrics dict equality
    (bitwise under the exact sampler) + probe schema coverage + clean
    monitors."""
    import jax

    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.models.base import sim_metrics
    from blockchain_simulator_tpu.obsim import build, schema

    canon = base_model.canonical_fault_cfg(cfg)
    nc, nb = _ops(cfg)
    key = jax.random.PRNGKey(seed)
    final_d = jax.block_until_ready(_disarmed_solo(canon)(key, nc, nb))
    m_d = sim_metrics(cfg, final_d)
    pcfg = schema.ProbeConfig()
    final_a, probes = jax.block_until_ready(
        build.probed_solo_fn(canon, pcfg)(key, nc, nb)
    )
    m_a = sim_metrics(cfg, final_a)
    summary = schema.summarize(canon, pcfg, probes)
    return {
        "bit_equal": m_d == m_a,
        "fields_ok": (summary["fields"]
                      == sorted(schema.SERIES_FIELDS[canon.protocol])),
        "violations": summary.get("violations", 0),
        "summary": summary,
    }


# ------------------------------------------------ synthetic forge leg ---


def synthetic_leg(workdir: str) -> dict:
    """Byzantine forge: grant a full quorum to a slot no leader ever
    proposed, injected into a REAL final state — the agreement monitor
    (the traced twin of pbft.metrics forged_commits) must count it, the
    invariant check must flag it, and the armed flight recorder must
    leave a ``consensus-violation`` post-mortem."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from blockchain_simulator_tpu.chaos import invariants
    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.obsim import host, taps
    from blockchain_simulator_tpu.utils import telemetry
    from blockchain_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig(protocol="pbft", n=8, sim_ms=200, stat_sampler="exact")
    canon = base_model.canonical_fault_cfg(cfg)
    final = jax.block_until_ready(
        _disarmed_solo(canon)(jax.random.PRNGKey(7), 0, 0)
    )
    propose = np.asarray(final.slot_propose_tick)
    never = propose == np.iinfo(np.int32).max
    commits = np.asarray(final.slot_commits).copy()
    slot = int(np.flatnonzero(never)[-1])  # an unproposed slot exists:
    commits[slot] = cfg.n                  # 200 ms leaves the tail empty
    forged = final.replace(slot_commits=jnp.asarray(commits))
    mon = {k: int(v) for k, v in taps.monitors(cfg, forged).items()}
    mon["liveness_lag"] = 0
    summary = {"protocol": cfg.protocol, "topology": cfg.topology,
               "monitors": mon,
               "violations": mon["viol_agreement"] + mon["viol_quorum"]}
    flagged = invariants.check_consensus_probes([summary])
    old = os.environ.get(telemetry.FLIGHT_ENV)
    os.environ[telemetry.FLIGHT_ENV] = workdir
    try:
        dump = host.note_violations(summary, cfg, seed=7)
    finally:
        if old is None:
            os.environ.pop(telemetry.FLIGHT_ENV, None)
        else:
            os.environ[telemetry.FLIGHT_ENV] = old
    return {
        "forged_slot": slot,
        "monitors": mon,
        "violations": summary["violations"],
        "invariant_flagged": bool(flagged),
        "invariant_detail": flagged,
        "flight_dumped": bool(dump and os.path.exists(dump)),
    }


# ---------------------------------------------------- forensics legs ---


def forensics_leg() -> dict:
    """Identity + localization: same (cfg, seed) armed twice is
    divergence-free; perturbing exactly one (sample, field) must be
    located exactly (obsim/diverge.first_divergence)."""
    import jax

    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.obsim import build, diverge, schema
    from blockchain_simulator_tpu.utils.config import SimConfig

    cfg = base_model.canonical_fault_cfg(
        SimConfig(protocol="pbft", n=8, sim_ms=200, stat_sampler="exact")
    )
    pcfg = schema.ProbeConfig(windows=8)
    sim = build.probed_solo_fn(cfg, pcfg)
    key = jax.random.PRNGKey(11)
    _, probes_a = jax.block_until_ready(sim(key, 0, 0))
    _, probes_b = jax.block_until_ready(sim(key, 0, 0))
    same = diverge.first_divergence(probes_a, probes_b)

    import numpy as np

    series_b = {k: np.asarray(v).copy()
                for k, v in probes_b["series"].items()}
    series_b["msgs_rounds"][..., 5] += 1  # the planted perturbation
    div = diverge.first_divergence(probes_a, {"series": series_b})
    bounds = schema.window_bounds(cfg.ticks, pcfg.windows)
    return {
        "identical_runs_clean": same is None,
        "located": (div is not None and div["sample"] == 5
                    and div["fields"] == ["msgs_rounds"]),
        "divergence": div,
        "rendered": diverge.render(div, t_axis=bounds, unit="window"),
    }


def forensics_mode(args) -> int:
    """``--forensics``: probe two seeds of one config and render where
    their histories first part ways — the interactive lens the README
    recipe documents."""
    import jax

    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.obsim import build, diverge, schema
    from blockchain_simulator_tpu.utils.config import SimConfig

    kw = {"protocol": args.protocol, "n": args.n, "sim_ms": args.sim_ms,
          "stat_sampler": "exact"}
    if args.topology != "full":
        kw["topology"] = args.topology
        if args.topology == "kregular":
            kw.update(degree=3, fidelity="clean")
        if args.topology == "committee":
            kw["committees"] = 2
    cfg = base_model.canonical_fault_cfg(SimConfig(**kw))
    pcfg = schema.ProbeConfig(windows=args.windows)
    sim = build.probed_solo_fn(cfg, pcfg)
    sa, sb = args.seeds
    _, pa = jax.block_until_ready(sim(jax.random.PRNGKey(sa), 0, 0))
    _, pb = jax.block_until_ready(sim(jax.random.PRNGKey(sb), 0, 0))
    div = diverge.first_divergence(pa, pb)
    unit, n_samples = schema.sample_axis(cfg)
    bounds = schema.window_bounds(n_samples, pcfg.windows) \
        if n_samples > 0 else None
    print(f"# {cfg.protocol}/{cfg.topology} seeds {sa} vs {sb} "
          f"({pcfg.windows} windows over {n_samples} {unit}s)")
    print(diverge.render(div, t_axis=bounds, unit="window"))
    if div is not None:
        print(json.dumps(div, default=str))
    return 0


# ------------------------------------------------------ overhead legs ---


def _timed_pair(fn_d, fn_a, reps: int, sync=None) -> tuple:
    """Warm both arms, then ``reps`` INTERLEAVED (disarmed, armed)
    timings; returns (min_d, min_a).  Interleaving is the load-bearing
    part: this box's wall for the SAME program drifts ~10% over minutes,
    so sequential all-d-then-all-a legs book the drift onto one arm and
    flip the sign of a 5% gate — adjacent pairs see the same box state."""
    def run(fn):
        r = fn()
        if sync is not None:
            sync(r)
        return r

    run(fn_d), run(fn_a)
    best_d = best_a = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run(fn_d)
        best_d = min(best_d, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run(fn_a)
        best_a = min(best_a, time.perf_counter() - t0)
    return best_d, best_a


def tick_overhead_leg(quick: bool) -> dict:
    """Armed-vs-disarmed wall on the long tick path, back to back: the
    probe tax is a handful of per-tick sums + one windowed gather, so
    the gate is a flat 5% of the disarmed wall."""
    import jax

    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.obsim import build, schema
    from blockchain_simulator_tpu.utils.config import SimConfig

    # the 10k tick path even on --quick: at shorter runs the FIXED tap
    # cost (windowed gather + monitors, amortized over ticks) inflates
    # the ratio.  n=64, not 16: the n=16 10k program is dispatch-bound
    # on this box and its wall swings +/-15% run to run (sign flips on
    # a 5% gate); n=64 is execution-bound and repeats within ~1%.
    cfg = base_model.canonical_fault_cfg(SimConfig(
        protocol="pbft", n=64, sim_ms=10_000, stat_sampler="exact",
    ))
    reps = 2 if quick else 4
    key = jax.random.PRNGKey(0)
    disarmed = _disarmed_solo(cfg)
    armed = build.probed_solo_fn(cfg, schema.ProbeConfig())
    wall_d, wall_a = _timed_pair(
        lambda: disarmed(key, 0, 0), lambda: armed(key, 0, 0),
        reps, sync=jax.block_until_ready,
    )
    return {
        "ticks": cfg.ticks, "n": cfg.n, "reps": reps,
        "disarmed_s": round(wall_d, 4), "armed_s": round(wall_a, 4),
        "overhead_pct": round(100.0 * (wall_a - wall_d) / wall_d, 2),
    }


def serve_overhead_leg(quick: bool) -> dict:
    """The serve capacity phase: one bucket-padded batched flush
    (dispatch.run_batch over 8 same-group requests), armed vs disarmed,
    min-of-N — the probe tax on the serving path includes the host-side
    summaries, not just the traced taps."""
    from blockchain_simulator_tpu.serve import dispatch, schema

    def reqs(armed: bool):
        out = []
        for i in range(8):
            obj = {"protocol": "pbft", "n": 8,
                   "sim_ms": 400 if quick else 1000,
                   "stat_sampler": "exact", "seed": 50 + i}
            if armed:
                obj["probe"] = True
            out.append(schema.parse_request(obj, f"ov-{armed}-{i}"))
        return out

    reps = 3 if quick else 5

    # admission (parse_request) is outside the timed region: the
    # capacity phase measures the FLUSH — batcher group to answered
    # batch — which is where the armed executable and the per-lane
    # host summaries live.  Reps interleave arms (_timed_pair).
    rs_d, rs_a = reqs(False), reqs(True)
    for rs in (rs_d, rs_a):
        for rq, resp in dispatch.run_batch(rs, max_batch=8):  # warm
            assert resp["code"] == 200, resp
    wall_d, wall_a = _timed_pair(
        lambda: dispatch.run_batch(rs_d, max_batch=8),
        lambda: dispatch.run_batch(rs_a, max_batch=8),
        reps,
    )
    return {
        "batch": 8, "reps": reps,
        "disarmed_s": round(wall_d, 4), "armed_s": round(wall_a, 4),
        "overhead_pct": round(100.0 * (wall_a - wall_d) / wall_d, 2),
    }


# ---------------------------------------------------------------- main ---


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="consensus_obs_report")
    p.add_argument("--quick", action="store_true",
                   help="small overhead workloads, no artifact "
                        "(tools/lint.sh chains this)")
    p.add_argument("--out", default=None,
                   help="artifact path (default ARTIFACT_consobs.json on "
                        "full runs, none on --quick)")
    p.add_argument("--platform", default="cpu")
    p.add_argument("--forensics", action="store_true",
                   help="compare two seeds' probe series and render their "
                        "first divergence (no gates)")
    p.add_argument("--seeds", type=int, nargs=2, default=(0, 1),
                   help="--forensics: the two seeds to compare")
    p.add_argument("--protocol", default="pbft",
                   choices=("pbft", "raft", "paxos"))
    p.add_argument("--topology", default="full",
                   choices=("full", "kregular", "committee"))
    p.add_argument("--n", type=int, default=8)
    p.add_argument("--sim-ms", type=int, default=200)
    p.add_argument("--windows", type=int, default=16)
    args = p.parse_args(argv)

    _force_platform(args.platform)
    if args.forensics:
        return forensics_mode(args)

    from blockchain_simulator_tpu.chaos import invariants
    from blockchain_simulator_tpu.utils import obs

    t_start = time.monotonic()
    workdir = tempfile.mkdtemp(prefix="consobs_report_")

    combos = {}
    clean_summaries = []
    for name, cfg in _combo_cfgs().items():
        combos[name] = combo_leg(cfg)
        clean_summaries.append(combos[name]["summary"])
    clean_violations = invariants.check_consensus_probes(clean_summaries)

    synth = synthetic_leg(workdir)
    forensics = forensics_leg()
    tick_oh = tick_overhead_leg(args.quick)
    serve_oh = serve_overhead_leg(args.quick)
    # the quick serve flush is a few hundred ms of dispatch against
    # fixed per-row host summaries plus box noise — measured and
    # charted on --quick, GATED only at full scale (sim_ms=1000, the
    # committed-artifact run) where dispatch dominates
    overhead = (tick_oh["overhead_pct"] if args.quick
                else max(tick_oh["overhead_pct"],
                         serve_oh["overhead_pct"]))

    gates = {
        "bit_equal_all": all(c["bit_equal"] for c in combos.values()),
        "schema_coverage": all(c["fields_ok"] for c in combos.values()),
        "monitors_clean": not clean_violations,
        "synthetic_fires": (synth["violations"] >= 1
                            and synth["invariant_flagged"]
                            and synth["flight_dumped"]),
        "forensics_exact": (forensics["identical_runs_clean"]
                            and forensics["located"]),
        "overhead_5pct": overhead <= 5.0,
    }

    artifact = {
        "metric": "consobs_report",
        "ok": all(gates.values()),
        "gates": gates,
        "quick": bool(args.quick),
        "combos": combos,
        "clean_invariant_violations": clean_violations,
        "synthetic": synth,
        "forensics": forensics,
        "overhead": {"tick_path": tick_oh, "serve_phase": serve_oh,
                     "gated_pct": overhead},
        "wall_s": round(time.monotonic() - t_start, 1),
    }
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # charted-never-gated trajectory rows (bench_compare consobs_ rule)
    obs.finalize({"metric": "consobs_overhead_pct", "value": overhead,
                  "unit": "%"})
    obs.finalize({"metric": "consobs_invariant_violations",
                  "value": len(clean_violations), "unit": "violations"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    if not artifact["ok"]:
        print(f"consensus_obs_report: GATES NOT MET ({gates})", flush=True)
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    _sys.exit(main())
