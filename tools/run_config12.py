"""BASELINE configs 1 and 2 artifacts.

Config 1 — "Raft, 16 nodes, full-mesh topology (ns-3 CPU reference run)":
runs on the framework's own C++ CPU reference engine (the ns-3 replacement,
engine/engine.cpp) AND on the JAX backend, cross-checking milestones.

Config 2 — "PBFT, 1k nodes, vmapped prepare/commit on a single TPU chip":
the general tick engine at n=1000 on whatever single device the backend
exposes (the artifact records the backend).

Writes ARTIFACT_config12.json at the repo root.

Usage: python tools/run_config12.py
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import json
import time

import jax

from blockchain_simulator_tpu.engine import run_cpp
from blockchain_simulator_tpu.models.base import get_protocol
from blockchain_simulator_tpu.runner import make_sim_fn
from blockchain_simulator_tpu.utils import obs
from blockchain_simulator_tpu.utils.config import SimConfig


def _timed_jax(cfg):
    """Compile-vs-execution split through the shared obs.timed_run staging."""
    proto = get_protocol(cfg.protocol)
    final, first, wall = obs.timed_run(make_sim_fn(cfg), jax.random.key(cfg.seed))
    return proto.metrics(cfg, final), wall, first


def main() -> None:
    # --- config 1: raft n=16 full mesh ---------------------------------------
    cfg1 = SimConfig(protocol="raft", n=16, sim_ms=10_000)
    t0 = time.perf_counter()
    m_cpp = run_cpp(cfg1)
    cpp_wall = time.perf_counter() - t0
    m_jax, jax_wall, _ = _timed_jax(cfg1)
    config1 = {
        "cfg": "raft n=16 full mesh, 10 s window, reference defaults",
        "cpp_engine": {"wall_s": round(cpp_wall, 3), **m_cpp},
        "jax_engine": {"wall_s": round(jax_wall, 3), **m_jax},
        "milestones_agree": all(
            m_cpp[k] == m_jax[k] for k in ("n_leaders", "blocks", "agreement_ok")
        ),
    }

    # --- config 2: pbft n=1000, single chip, tick engine ---------------------
    cfg2 = SimConfig(
        protocol="pbft", n=1000, sim_ms=2500, delivery="stat",
        schedule="tick", pbft_window=8, pbft_max_slots=48,
    )
    m2, wall2, first2 = _timed_jax(cfg2)
    config2 = {
        "cfg": "pbft n=1000, stat delivery, tick engine, single device",
        "backend": jax.default_backend(),
        "config_hash": obs.config_hash(cfg2),
        "wall_s": round(wall2, 3),
        "compile_plus_first_run_s": round(first2, 3),
        "rounds_per_s": obs.rounds_per_s(m2["blocks_final_all_nodes"], wall2),
        **m2,
    }
    config1["config_hash"] = obs.config_hash(cfg1)

    out = obs.finalize(
        {"config1": config1, "config2": config2,
         "backend": jax.default_backend()},
        cfg2, compile_s=first2, run_s=wall2,
        rounds=m2["blocks_final_all_nodes"],
    )
    path = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "ARTIFACT_config12.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
