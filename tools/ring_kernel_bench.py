"""Measure the pallas fused ring push vs the DUS chain on the real chip.

Times the full PBFT tick engine (the production consumer) with
BLOCKSIM_RING_KERNEL=dus and =pallas, plus a push-only micro scan isolating
the op.  Writes chiprun_out/ring_kernel.json (the directory the chip tool
brings back).

Each measurement runs in a FRESH child process, one after the other, from a
parent that never touches jax: the lowering is chosen at trace time per
process (ops/ring.py), and a chip belongs to one process at a time.

N defaults to 102400, not 100000: an explicit pallas request raises on a
ring whose flattened row is not a multiple of 128, and the PBFT ``vc`` ring
is ``[D, N]``.

Usage: python tools/ring_kernel_bench.py
       python tools/ring_kernel_bench.py --child micro|full  (internal)
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import json
import subprocess
import time

N = int(_os.environ.get("RINGK_N", "102400"))
TICKS = int(_os.environ.get("RINGK_TICKS", "2100"))


def _tick_cfg():
    from blockchain_simulator_tpu.utils.config import SimConfig

    return SimConfig(
        protocol="pbft", n=N, sim_ms=TICKS, pbft_max_rounds=40,
        pbft_max_slots=48, pbft_window=8, delivery="stat", schedule="tick",
        model_serialization=False,
    )


def child(which: str) -> None:
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu import runner

    if which == "full":
        sim = runner.make_sim_fn(_tick_cfg())
        jax.block_until_ready(sim(jax.random.key(1)))
        t0 = time.perf_counter()
        jax.block_until_ready(sim(jax.random.key(2)))
        wall = time.perf_counter() - t0
    else:  # push-only micro: the 3 PBFT add/max channel shapes at this N
        from blockchain_simulator_tpu.ops import ring

        d, w = 18, 8
        bufs = (
            jnp.zeros((d, N, w), jnp.int32),
            jnp.zeros((d, N, w), jnp.int32),
            jnp.zeros((d, N, w), jnp.int32),
        )
        c5 = jnp.ones((5, N, w), jnp.int32)
        c3 = jnp.ones((3, N, w), jnp.int32)

        @jax.jit
        def run(bufs):
            def body(bs, t):
                a, b, c = bs
                a = ring.ring_push_add(a, t, 12, c5)
                b = ring.ring_push_add(b, t, 6, c3)
                c = ring.ring_push_max(c, t, 6, c3)
                return (a, b, c), ()

            return jax.lax.scan(body, bufs, jnp.arange(TICKS))[0]

        jax.block_until_ready(run(bufs))
        t0 = time.perf_counter()
        jax.block_until_ready(run(bufs))
        wall = time.perf_counter() - t0
    print(json.dumps({
        "wall_s": round(wall, 3),
        "us_per_tick": round(wall / TICKS * 1e6, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }), flush=True)


def _run_child(which: str, mode: str) -> dict | None:
    env = dict(_os.environ)
    env["BLOCKSIM_RING_KERNEL"] = mode
    env["RINGK_N"] = str(N)
    env["RINGK_TICKS"] = str(TICKS)
    proc = subprocess.run(
        [_sys.executable, _os.path.abspath(__file__), "--child", which],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if proc.returncode != 0:
        _sys.stderr.write(f"[{mode}/{which}] failed:\n" + proc.stderr[-800:] + "\n")
        return None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def main() -> None:
    out = {"n": N, "ticks": TICKS, "window": 8}
    for mode in ("dus", "pallas"):
        for which in ("micro", "full"):
            r = _run_child(which, mode)
            out[f"{mode}_{which}"] = r
            print(json.dumps({f"{mode}_{which}": r}), flush=True)
    try:
        out["push_speedup"] = round(
            out["dus_micro"]["wall_s"] / out["pallas_micro"]["wall_s"], 2)
        out["tick_engine_speedup"] = round(
            out["dus_full"]["wall_s"] / out["pallas_full"]["wall_s"], 2)
    except (TypeError, KeyError, ZeroDivisionError):
        pass
    out_dir = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "chiprun_out")
    _os.makedirs(out_dir, exist_ok=True)
    with open(_os.path.join(out_dir, "ring_kernel.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    if "--child" in _sys.argv:
        child(_sys.argv[_sys.argv.index("--child") + 1])
    else:
        main()
