"""Repro: does executable serialization round-trip across processes here?

KNOWN_ISSUES.md #0e records the verdict this script produced on XLA:CPU:
``jax.experimental.serialize_executable`` round-trips a compiled simulation
executable across PROCESSES, bit-equal.  The persistent layer of
``utils/aotcache.py`` is gated on exactly this capability — if a jax upgrade
breaks it, this script is the 60-second check (aotcache degrades to
in-process-only caching either way; it never raises).  Whether it holds for
TPU executables is not known (ROADMAP S4): send this script through the
chip tool to find out.

Usage:
    JAX_PLATFORMS=cpu python tools/repro_exe_serialize.py

The parent is stdlib-only and never touches jax — a chip belongs to one
process at a time — so both halves are children, one after the other: one
compiles + serializes + measures, the next deserializes + runs.  Prints one
JSON verdict line: {"serialize_ok", "bit_equal", "platform", "compile_s",
"deserialize_s", "payload_bytes"}.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import tempfile
import time

CFG_KW = dict(protocol="pbft", n=64, sim_ms=2000, delivery="stat")
SEED = 7


def _metrics(final):
    from blockchain_simulator_tpu.models.base import get_protocol
    from blockchain_simulator_tpu.utils.config import SimConfig

    return get_protocol("pbft").metrics(SimConfig(**CFG_KW), final)


def load_child(path: str) -> None:
    import jax

    # treedef unpickling resolves flax-struct state types by import
    from blockchain_simulator_tpu.models import pbft  # noqa: F401
    from jax.experimental.serialize_executable import deserialize_and_load

    with open(path, "rb") as f:
        payload, in_tree, out_tree = pickle.load(f)
    t0 = time.perf_counter()
    compiled = deserialize_and_load(payload, in_tree, out_tree)
    dt = time.perf_counter() - t0
    final = jax.block_until_ready(compiled(jax.random.key(SEED)))
    print(json.dumps({"deserialize_s": round(dt, 3), "metrics": _metrics(final)},
                     default=str))


def save_child(path: str) -> None:
    import jax

    from blockchain_simulator_tpu.runner import make_sim_fn
    from blockchain_simulator_tpu.utils.config import SimConfig
    from jax.experimental.serialize_executable import serialize

    sim = make_sim_fn(SimConfig(**CFG_KW))
    key = jax.random.key(SEED)
    t0 = time.perf_counter()
    compiled = sim.lower(key).compile()
    compile_s = time.perf_counter() - t0
    ref = _metrics(jax.block_until_ready(compiled(key)))
    payload, in_tree, out_tree = serialize(compiled)
    with open(path, "wb") as f:
        pickle.dump((payload, in_tree, out_tree), f)
    print(json.dumps({"compile_s": round(compile_s, 3),
                      "payload_bytes": len(payload),
                      "platform": jax.devices()[0].platform, "metrics": ref},
                     default=str))


def _run_child(mode: str, path: str) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode, path],
        capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"{mode}: {proc.stderr[-1000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    verdict = {"serialize_ok": False, "bit_equal": None, "platform": None,
               "compile_s": None, "deserialize_s": None,
               "payload_bytes": None}
    fd, path = tempfile.mkstemp(suffix=".jaxexe")
    os.close(fd)
    try:
        saved = _run_child("--save", path)
        verdict.update({k: saved[k] for k in
                        ("platform", "compile_s", "payload_bytes")})
        loaded = _run_child("--load", path)
        verdict["serialize_ok"] = True
        verdict["deserialize_s"] = loaded["deserialize_s"]
        verdict["bit_equal"] = loaded["metrics"] == saved["metrics"]
    except Exception as e:  # the verdict line IS the point — never traceback
        verdict["error"] = f"{type(e).__name__}: {e}"
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass
    print(json.dumps(verdict))
    return 0 if verdict["serialize_ok"] and verdict["bit_equal"] else 1


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if "--save" in sys.argv:
        save_child(sys.argv[sys.argv.index("--save") + 1])
    elif "--load" in sys.argv:
        load_child(sys.argv[sys.argv.index("--load") + 1])
    else:
        sys.exit(main())
