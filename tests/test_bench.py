"""Contract of bench.py: it measures on the device jax finds, or it refuses.

One process, no fallback.  On a tpu it prints one JSON line with a value
and exits 0 (only a chip run can show that — chip_smoke.py's territory).
What a CPU-only box can pin is everything else: a backend that cannot start
exits non-zero with no line; no tpu and no explicit ``JAX_PLATFORMS``
refuses before compiling anything; an explicit ``JAX_PLATFORMS=cpu`` is a
rehearsal of the same path whose line names the device and carries no
metric name and no value, with an exit code that is never 0."""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO))
import bench  # noqa: E402


def _run_bench(env_overrides, drop=(), timeout=300):
    env = dict(os.environ)
    env.update({
        "BENCH_N": "4096",          # >= 4096: the round fast path
        "BENCH_ROUNDS": "50",
        "BENCH_ROUNDS_SER": "0",    # no companion (keep the test fast)
        **env_overrides,
    })
    for k in ("XLA_FLAGS", *drop):
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=REPO,
    )


def test_bench_unusable_backend_exits_nonzero_without_a_line():
    # a backend that cannot initialize: no fallback, no value, rc != 0
    proc = _run_bench({"JAX_PLATFORMS": "definitely_not_a_backend"})
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no usable backend" in proc.stderr


def test_bench_refuses_without_chip_or_explicit_platform():
    # no chip here and nobody asked for another platform: refuse before
    # compiling anything, say why, print nothing
    proc = _run_bench({}, drop=("JAX_PLATFORMS",))
    assert proc.returncode == bench.EXIT_REFUSED, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "refusing to measure" in proc.stderr


def test_bench_cpu_rehearsal_names_device_and_prints_no_value():
    proc = _run_bench({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == bench.EXIT_REHEARSAL, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["device"]["platform"] == "cpu"
    assert rec["manifest"]["platform"] == "cpu"
    # the path ran end to end...
    assert rec["rounds"] == rec["rounds_cfg"] == 50
    assert "timing_model" in rec
    # ...and nothing is printed under a device metric's name
    assert not {"metric", "value", "unit", "vs_baseline",
                "hbm_utilization"} & set(rec)
    assert "rounds_per_s" not in rec["manifest"]


def test_bench_hbm_peak_is_keyed_by_device_kind():
    import pytest

    assert bench.hbm_bytes_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="no published HBM peak"):
        bench.hbm_bytes_s("TPU v99")
