"""Chip readings of ``ops/delivery.reply_count_by_target`` alone: the device
time of one count in each form (compare-and-sum, scatter-add), lone and under
a 256-lane batch, at n = 256, 1,024, 4,096, 16,384.  What
``REPLY_COUNT_DENSE_MAX_N`` is set from (PERF.md section 3).

    chiprun -- python tools/reply_count_readings.py [out.json]

Device time is the duration of the jitted program's events on the device
plane's ``XLA Modules`` line of one profiler session (five warm calls each,
the median); the host's wall clock around ``block_until_ready`` stands beside
it.  Off the chip it refuses: a CPU timing says nothing about the bound.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu.models import base
from blockchain_simulator_tpu.ops import delivery as dv

SIZES = (256, 1024, 4096, 16384)
LANES = (1, 256)
REPS = 5


CANDIDATES = 4  # a shard sees a handful of distinct targets a tick


def _inputs(n: int, lanes: int, seed: int):
    """Repliers as an election tick has them: every row answers one of a few
    candidates (or none: -1), about half of the wires set."""
    rng = np.random.default_rng(seed)
    shape = (lanes, n)
    cands = rng.integers(0, n, (lanes, CANDIDATES))
    target = np.take_along_axis(
        cands, rng.integers(0, CANDIDATES, shape), axis=-1)
    target = np.where(rng.random(shape) < 0.1, -1, target).astype(np.int32)
    wire = rng.random(shape) < 0.5
    if lanes == 1:
        wire, target = wire[0], target[0]
    return jnp.asarray(wire), jnp.asarray(target)


FORMS = {"dense": dv._reply_count_dense, "scatter": dv._reply_count_scatter}


def _program(form: str, n: int, lanes: int):
    def count(wire, target):
        return FORMS[form](wire, target, n)

    batched = base.lane_vmap(count) if lanes > 1 else count
    batched.__name__ = f"count_{form}_n{n}_l{lanes}"  # the module's name
    # one program a (form, n, lanes), built once and kept by the caller
    return jax.jit(batched)  # jaxlint: disable=static-arg-recompile-hazard


def _module_times(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    name = e.name.split("(")[0]
                    out.setdefault(name, []).append(e.duration_ns / 1e3)
    return out


def main(out_path: str | None, sizes=SIZES, lanes_of=LANES,
         rehearse: bool = False) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"refusing: {dev.platform} is not the chip", file=sys.stderr)
        return 2
    rows = []
    for n in sizes:
        for lanes in lanes_of:
            args = _inputs(n, lanes, seed=n + lanes)
            got = {}
            for form in FORMS:
                fn = _program(form, n, lanes)
                t0 = time.perf_counter()
                got[form] = jax.block_until_ready(fn(*args))  # compiles
                rows.append({"form": form, "n": n, "lanes": lanes,
                             "compile_s": time.perf_counter() - t0,
                             "call": (fn, args)})
            assert (np.asarray(got["dense"]) == np.asarray(got["scatter"])).all()
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for row in rows:
                fn, args = row.pop("call")
                walls = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    walls.append((time.perf_counter() - t0) * 1e6)
                row["wall_us_min"] = min(walls)
        device = _module_times(trace_dir)
    for row in rows:
        dts = device.get("jit_count_{form}_n{n}_l{lanes}".format(**row), [])
        row["device_us"] = statistics.median(dts) if dts else None
        row["device_us_all"] = dts
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "jax": jax.__version__, "reps": REPS, "rows": rows}
    for r in rows:
        print(json.dumps({k: r[k] for k in
                          ("form", "n", "lanes", "device_us", "wall_us_min")}))
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
