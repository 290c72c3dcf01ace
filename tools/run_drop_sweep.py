"""Drop-probability sweep on the round-blocked fast path (r5 capability).

Per-message drop faults became eligible on the round schedule in round 5
(view changes off, exact vote table — models/pbft_round.eligible); this
sweep maps finality vs drop rate at scale and writes
ARTIFACT_drop_sweep.json at the repo root.  The N/2(+1) thresholds predict
a sharp cliff: commits survive while expected votes ~N(1-p)^2 (prepare) /
~N(1-p) (commit) clear the quorum, and starve entirely past it —
the sweep pins where.

Usage: [JAX_PLATFORMS=cpu] python tools/run_drop_sweep.py
Env: DROP_N (default 10000), DROP_PS (comma floats), DROP_ROUNDS (default 40).
"""

from __future__ import annotations

import json
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

N = int(_os.environ.get("DROP_N", "10000"))
PS = [float(x) for x in _os.environ.get(
    "DROP_PS", "0,0.02,0.05,0.1,0.2,0.3,0.4,0.5").split(",")]
ROUNDS = int(_os.environ.get("DROP_ROUNDS", "40"))


def main() -> int:
    import jax

    from blockchain_simulator_tpu.runner import run_simulation, use_round_schedule
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    points = []
    for p in PS:
        cfg = SimConfig(
            protocol="pbft",
            n=N,
            sim_ms=ROUNDS * 50 + 100,
            pbft_max_rounds=ROUNDS,
            pbft_max_slots=ROUNDS + 8,
            pbft_view_change_num=0,
            delivery="stat",
            model_serialization=False,
            schedule="round",
            faults=FaultConfig(drop_prob=p),
        )
        assert use_round_schedule(cfg)
        m = run_simulation(cfg)
        pt = {
            "drop_prob": p,
            "blocks_final_all_nodes": m["blocks_final_all_nodes"],
            "block_num_max": m["block_num_max"],
            "mean_time_to_finality_ms": m["mean_time_to_finality_ms"],
            "agreement_ok": m["agreement_ok"],
        }
        points.append(pt)
        print(json.dumps(pt), flush=True)

    out = {
        "config": f"PBFT n={N}, round fast path, {ROUNDS} rounds, VCs off",
        "backend": jax.default_backend(),
        "quorum_note": (
            f"binding side is the PREPARE quorum N/2 = {N // 2}: expected "
            "replies ~(N-1)(1-p)^2 cross it iff (1-p)^2 >= ~1/2, i.e. "
            "p <= 1 - sqrt(1/2) ~ 0.293 — hence survival at 0.2 and "
            "starvation at 0.3.  The commit leg (~(N-1)(1-p) one-way "
            "arrivals vs N/2+1) alone would allow p up to ~0.5."
        ),
        "points": points,
    }
    path = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "ARTIFACT_drop_sweep.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"written": path}))
    return 0


if __name__ == "__main__":
    _sys.exit(main())
