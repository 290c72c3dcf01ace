"""``correct`` comes out false when it should.

- the controls: each configuration's ``controls`` run the PROGRAM with one
  stated guarantee broken (a delay bucket dropped; the last round cut; the
  batch-variant rbg delay stream) against the reference of the configuration
  as stated.  Each must fail the number it names.  (On the chip, at the cells'
  own sizes: ``chip_readings.py``; ``PERF.md`` has the readings.)
- the timed path broken underneath a whole run (the harness's look for a chip
  skipped): an answer altered where it is produced, and a step that returns
  its state unchanged.
"""

import json
import os

import pytest

import run as bench

SPEC = bench.load_json(bench.ROOT, "BENCHMARK.json")


def controls():
    for cell in SPEC["workloads"]:
        cfg = bench.resolve(SPEC, cell["name"])["config"]
        for c in cfg.get("controls", []):
            if cell["name"] not in c.get("workloads", [cell["name"]]):
                continue
            yield pytest.param(cell["name"], c, id=f"{cell['name']}-{c['name']}")


@pytest.fixture(scope="module")
def counter():
    return bench.CompileCounter()


def drive(workload, counter, fields=None, seed=2_147_483_659):
    ctx = bench.make_ctx(SPEC, workload, seed, False, on_chip=False,
                         program_fields=fields)
    _, comps = bench.drive(ctx, 0.5, counter)
    return {c["name"]: c for c in comps}


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_sound_run_is_correct(workload, counter):
    comps = drive(workload, counter)
    assert all(c["ok"] for c in comps.values()), comps


@pytest.mark.parametrize("workload,control", list(controls()))
def test_control_is_rejected(workload, control, counter):
    fields = control.get("rehearsal_fields", control["fields"])
    comps = drive(workload, counter, fields)
    assert not comps[control["must_fail"]]["ok"], comps


def test_altered_answer_is_rejected(monkeypatch, counter):
    from blockchain_simulator_tpu.models import base

    real = base.sim_metrics
    calls = {"n": 0}

    def altered(cfg, final):
        m = real(cfg, final)
        calls["n"] += 1
        if calls["n"] == 4:  # one row of the window, where it is produced
            m["last_commit_ms"] += 5.0
        return m

    monkeypatch.setattr(base, "sim_metrics", altered)
    comps = drive("pbft100k.solo", counter)
    assert not comps["commit_tail_gap_ms_max"]["ok"]


def test_step_that_returns_its_state_unchanged_is_rejected(monkeypatch, counter):
    import jax

    from blockchain_simulator_tpu import runner
    from blockchain_simulator_tpu.models import pbft_round

    def stuck(cfg):
        def sim(key):
            state, _ = pbft_round.init(cfg, key)
            return state  # the scan never ran

        return sim

    monkeypatch.setattr(runner, "make_sim_fn", stuck)
    comps = drive("pbft100k.solo", counter)
    assert not comps["finality_shortfall_max"]["ok"]


def test_compile_inside_the_window_is_rejected(monkeypatch, counter):
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models import base

    real = base.sim_metrics
    shapes = iter(range(3, 10_000))

    def compiles(cfg, final):
        if os.environ.get("_BENCH_TEST_IN_WINDOW"):
            jax.jit(lambda x: x + 1)(jnp.zeros(next(shapes))).block_until_ready()
        return real(cfg, final)

    monkeypatch.setattr(base, "sim_metrics", compiles)
    driver_mod = bench.load_module("drivers", "solo")
    real_window = driver_mod.Driver.window

    def window(self, t, s):
        os.environ["_BENCH_TEST_IN_WINDOW"] = "1"
        try:
            return real_window(self, t, s)
        finally:
            del os.environ["_BENCH_TEST_IN_WINDOW"]

    monkeypatch.setattr(bench, "load_module", lambda kind, name: driver_mod)
    monkeypatch.setattr(driver_mod.Driver, "window", window)
    comps = drive("pbft100k.solo", counter)
    assert comps["compiles_in_window"]["value"] > 0
    assert not comps["compiles_in_window"]["ok"]


def test_peaks_table_knows_only_what_it_lists():
    peaks = bench.load_json(bench.HERE, "peaks.json")
    assert peaks["by_device_kind"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "cpu" not in peaks["by_device_kind"] and peaks["source"]
