"""Round-fast-path throughput vs cluster size N — scaling-curve artifact.

Runs the headline configuration (bench._cfg: stat delivery, windowed state,
round-blocked schedule) across a ladder of N on the current backend and
writes ARTIFACT_scaling_<backend>.json at the repo root.  Each N runs in
THIS process.

The curve answers "where does per-round cost leave the dispatch-bound
plateau and go memory-bound?" — on the TPU the headline claim is that a
whole consensus round is a handful of O(N) vector ops, so rounds/s should
hold roughly flat until [N]-vector traffic saturates HBM; on CPU the knee
arrives early (caches).  Usage:

    JAX_PLATFORMS=cpu python tools/scaling_curve.py
    # or on the TPU: python tools/scaling_curve.py

Env: SCALE_NS (comma list, default "4096,10000,20000,50000,100000"),
SCALE_ROUNDS (default 500).
"""

from __future__ import annotations

import json
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

NS = [int(x) for x in _os.environ.get(
    "SCALE_NS", "4096,10000,20000,50000,100000").split(",")]
ROUNDS = int(_os.environ.get("SCALE_ROUNDS", "500"))


def main() -> int:
    import jax

    import bench

    backend = jax.default_backend()
    path = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), f"ARTIFACT_scaling_{backend}.json")

    # merge with a prior partial run and REWRITE AFTER EVERY POINT, so a
    # failure at a later N never discards completed measurements
    points: list[dict] = []
    try:
        with open(path) as f:
            points = json.load(f).get("points", [])
    except (OSError, json.JSONDecodeError):
        pass

    def write():
        points.sort(key=lambda p: p["n"])
        out = {
            "artifact": "round-fast-path scaling curve",
            "backend": backend,
            "schedule": "round (models/pbft_round.py), stat delivery, ser off",
            "rounds_per_point": ROUNDS,
            "points": points,
            "note": (
                "rounds/s vs N for the headline path; flat = dispatch-bound "
                "per scan step, falling = [N]-vector memory traffic bound"
            ),
        }
        with open(path, "w") as f:
            json.dump(out, f, indent=1)

    for n in NS:
        bench.N_NODES = n  # bench._cfg reads the module global
        value, rounds_done, wall, compile_s, _ = bench._measure(
            bench._cfg(ROUNDS), batch=1)
        pt = {
            "n": n,
            "rounds_per_sec": round(value, 2),
            "per_round_us": round(wall / max(rounds_done, 1) * 1e6, 1),
            "rounds": rounds_done,
            "wall_s": round(wall, 3),
            "compile_s": round(compile_s, 1),
        }
        points = [p for p in points if p["n"] != n] + [pt]
        write()
        print(json.dumps(pt), flush=True)

    print(json.dumps({"written": path}))
    return 0


if __name__ == "__main__":
    _sys.exit(main())
