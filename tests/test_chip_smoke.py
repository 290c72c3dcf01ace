"""chip_smoke.py off the chip: it refuses, it stays off jax, and its leg
table holds at tiny sizes with CPU children.

Whether the system starts on the accelerator is shown only by sending
``python chip_smoke.py`` through the chip tool.  What a CPU-only box pins:
the command cannot pass here, the parent never touches jax (a parent that
holds the chip starves its own children), and every leg's commands, parsing
and checks still work — the same table, sizes cut, children on the CPU."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

# the leg table's shapes at sizes a CPU child compiles in seconds; n stays
# >= 4096 because that is where schedule 'auto' resolves to the round path
TINY = {
    "n": 4096, "rounds": 20, "tick_ms": 300, "sweep_rounds": 10,
    "exact_n": 8, "exact_ms": 300, "kreg_n": 64, "kreg_ms": 100,
    "serve_n": 8, "serve_ms": 300,
}


def test_cpu_smoke_refuses_in_seconds_and_runs_no_leg(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""  # no result line without a chip
    assert "not 'tpu': refusing to run any leg" in proc.stderr
    report = json.loads(proc.stderr.split("chip_smoke: FAILED ", 1)[1])
    assert not report["ok"]
    assert [leg["name"] for leg in report["legs"]] == ["device"]


def test_smoke_alone_in_a_directory_fails_without_a_line(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    proc = subprocess.run(
        [sys.executable, str(lone)], capture_output=True, text=True,
        timeout=60, cwd=tmp_path,
    )
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not beside this script" in proc.stderr


def test_importing_chip_smoke_pulls_in_neither_jax_nor_the_package():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib', 'blockchain_simulator_tpu'))]; "
            "print(bad)" % str(REPO))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"


def _run_table(tmp_path, legs, xla_flags):
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": xla_flags}
    sm = chip_smoke.Smoke(TINY, platform="cpu", env=env,
                          log_dir=str(tmp_path / "logs"))
    ok = sm.run([(n, f) for n, f in chip_smoke.LEGS
                 if n == "device" or n in legs])
    return ok, {leg["name"]: leg for leg in sm.legs}


def test_leg_table_passes_at_tiny_sizes_with_cpu_children(tmp_path):
    # one CPU device per child, as on a one-chip machine: mesh4 must report
    # itself skipped, every other leg must hold.  (parity's sizes are
    # upstream's and cannot be cut; it rides the slow test below, and
    # tests/test_differential.py pins the same equalities in-process.)
    names = [n for n, _ in chip_smoke.LEGS if n != "parity"]
    ok, legs = _run_table(tmp_path, names, "")
    assert ok, legs
    assert list(legs) == names
    assert legs["mesh4"]["skipped"] == "1 device"
    assert all(legs[n]["ok"] for n in names if n != "mesh4"), legs
    assert legs["device"]["checked"]["platform"] == "cpu"
    assert legs["serve"]["checked"]["degraded_batches"] == 0
    # --timing children report their compile + first run
    assert legs["pbft100k_tick"]["compile_s"] > 0


def test_failed_leg_fails_the_run_and_is_not_caught_into_a_pass(tmp_path):
    sm = chip_smoke.Smoke(TINY, platform="cpu",
                          env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
                          log_dir=str(tmp_path / "logs"))

    def leg_exits_3(s):
        s.child("exit3", [sys.executable, "-c", "import sys; sys.exit(3)"])
        return {}

    def leg_overruns(s):
        s.child("sleeper", [sys.executable, "-c",
                            "import time; time.sleep(60)"], timeout_s=0.5)
        return {}

    ok = sm.run([("device", chip_smoke.leg_device), ("bad", leg_exits_3),
                 ("slow", leg_overruns)])
    assert not ok
    bad, slow = sm.legs[1], sm.legs[2]
    assert not bad["ok"] and "exit code 3" in bad["error"]
    assert not slow["ok"] and "killed" in slow["error"]
    assert slow["wall_s"] < 30  # the overrunning child was killed, not awaited


def test_last_stdout_line_is_the_verdict_with_exactly_its_keys(tmp_path,
                                                                capsys):
    # the driver parses the LAST stdout line: {"ok", "device": {"platform",
    # "kind", "count"}} and nothing else; the report rides the line before
    sm = chip_smoke.Smoke(TINY, platform="cpu",
                          env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
                          log_dir=str(tmp_path / "logs"))
    assert sm.run([("device", chip_smoke.leg_device)])
    capsys.readouterr()
    assert chip_smoke.emit(sm, True, ["parity"], 1.0) == 0
    report, last = map(json.loads, capsys.readouterr().out.splitlines())
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": sm.device["device_kind"], "count": 1}}
    assert isinstance(last["device"]["kind"], str)
    assert report["partial"] == ["parity"] and report["versions"]["jax"]
    assert [leg["name"] for leg in report["legs"]] == ["device"]
    assert json.loads((tmp_path / "logs" / "report.json").read_text()) == report
    # a leg that failed on the right platform: exit 1, verdict says so
    assert chip_smoke.emit(sm, False, [], 1.0) == 1
    out = capsys.readouterr()
    assert [json.loads(ln) for ln in out.out.splitlines()] == [
        dict(last, ok=False)]
    assert "chip_smoke: FAILED " in out.err
    # the wrong platform: nothing on stdout at all
    sm.platform = "tpu"
    assert chip_smoke.emit(sm, False, [], 1.0) == 1
    assert capsys.readouterr().out == ""


@pytest.mark.slow
def test_parity_and_mesh4_legs_on_eight_virtual_devices(tmp_path):
    ok, legs = _run_table(tmp_path, ["parity", "mesh4"],
                          "--xla_force_host_platform_device_count=8")
    assert ok, legs
    assert legs["parity"]["checked"]["pbft"]["blocks"] == 40
    assert legs["mesh4"]["checked"]["kregular"]["node_leaf_devices"] == 4
