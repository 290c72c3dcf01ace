"""Roofline analysis of the headline path (models/pbft_round.py).

What fraction of the chip's HBM bandwidth / vector FLOP peak does the
round-blocked fast path achieve, and how much headroom is left?

Method: XLA's own cost analysis of the compiled whole-run executable
(``jit(sim).lower(key).compile().cost_analysis()`` -> flops, bytes accessed),
divided by the number of simulated rounds, against the measured wall clock
per round (bench._measure's timing).  Cost analysis is of the executable
actually compiled for the device this runs on, and the peaks are keyed by
that device's ``device_kind`` (bench.HBM_BYTES_S): a device without a
published peak — the CPU included — is an error, not a default.

v5e single-chip peaks (Google Cloud "TPU v5e"): 819 GB/s HBM BW,
197 TFLOP/s bf16 MXU.  The round step is [N]-vector int32/f32 elementwise + PRNG work — no matmuls
— so the relevant ceilings are HBM bytes and VPU flops; we report HBM
utilization (the binding one for streaming vector code) plus the raw flop
rate for context.

``ROOFLINE_SCHEDULE=tick`` points the same analysis at the general
per-tick engine instead of the round fast path (ISSUE 13: the tick path is
what every windowed-drop / view-change / Byzantine-fallback config runs,
and its wall is sampling/delivery compute — KNOWN_ISSUES #5).  The tick
numbers pair with ARTIFACT_tick_bench.json's dispatch-arm ratios: this
tool prices ONE program against the hardware ceilings, tick_bench prices
the dispatch arms against each other.

Prints one JSON object.
"""

from __future__ import annotations

import json
import os
import sys
import time

N = int(os.environ.get("ROOFLINE_N", "100000"))
ROUNDS = int(os.environ.get("ROOFLINE_ROUNDS", "2000"))
SCHEDULE = os.environ.get("ROOFLINE_SCHEDULE", "round")
BF16_FLOPS = {"TPU v5 lite": 197e12}  # Google Cloud "TPU v5e"


def main() -> int:
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    os.environ["BENCH_N"] = str(N)  # bench reads its N at import time
    from bench import _cfg, _measure, hbm_bytes_s

    dev = jax.devices()[0]
    hbm_peak = hbm_bytes_s(dev.device_kind)
    flop_peak = BF16_FLOPS[dev.device_kind]

    cfg = _cfg(ROUNDS)
    from blockchain_simulator_tpu.runner import make_sim_fn, use_round_schedule

    if SCHEDULE == "tick":
        # the tick-engine roofline: same workload pinned onto the general
        # engine (the bench _cfg already carries the windowed vote table
        # it would fall back to)
        cfg = cfg.with_(schedule="tick")
        assert not use_round_schedule(cfg)
    elif SCHEDULE != "round":
        raise SystemExit(f"unknown ROOFLINE_SCHEDULE {SCHEDULE!r} "
                         "(expected 'round' or 'tick')")
    else:
        assert use_round_schedule(cfg), \
            "headline config must resolve to the round path"
    sim = make_sim_fn(cfg)
    key = jax.random.key(0)

    t0 = time.monotonic()
    compiled = jax.jit(sim).lower(key).compile()
    lower_s = time.monotonic() - t0
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))

    value, rounds_done, wall, compile_s, _ = _measure(cfg, batch=1)
    per_round_s = wall / max(rounds_done, 1)
    bytes_per_round = bytes_acc / ROUNDS
    flops_per_round = flops / ROUNDS
    hbm_util = (bytes_per_round / per_round_s) / hbm_peak
    out = {
        "n": N,
        "rounds": ROUNDS,
        "schedule": SCHEDULE,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "rounds_per_sec": round(value, 2),
        "per_round_us": round(per_round_s * 1e6, 1),
        "xla_bytes_accessed_per_round": round(bytes_per_round),
        "xla_flops_per_round": round(flops_per_round),
        "achieved_GBps": round(bytes_per_round / per_round_s / 1e9, 2),
        "achieved_GFLOPs": round(flops_per_round / per_round_s / 1e9, 2),
        "hbm_peak_GBps": hbm_peak / 1e9,
        "hbm_utilization": round(hbm_util, 4),
        "flop_utilization_vs_mxu_peak": round(
            (flops_per_round / per_round_s) / flop_peak, 6
        ),
        "lower_compile_s": round(lower_s, 1),
        "measure_compile_s": round(compile_s, 1),
        "note": (
            "elementwise [N]-vector program (no matmuls): the binding "
            "ceilings are HBM bytes and VPU throughput; hbm_utilization "
            "<< 1 means the path is dispatch/latency-bound per scan step, "
            "i.e. throughput rises with N at ~constant wall per round"
        ),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
