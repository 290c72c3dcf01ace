"""Driver ``byzsweep``: one caller, one Byzantine-fault sweep after another.

The timed path is ``parallel/sweep.run_byzantine_sweep(cfg, f_values, seeds=
(s,), forge=True)``: the configuration's grid of fault levels under one fresh
seed per call, as lanes of the one vmapped dynamic-operand tick program, in
as many dispatches as the sweep layer's own tile makes of them (it sizes a
dispatch from the program's state bytes and the memory the device reports),
the readback included (one fetch a dispatch, then a metrics dict a row on
the host).  A point counts when its dict
is back and sound (``byz_checks.sound``).  After the window every row is
held to the configuration's guarantees against the plain reference
``reference/pbft_byz_engine.py``, level by level, and a seeded sample of
rows, all of one seeded level (one solo compile), is run again solo
(``runner.run_simulation`` at that level's static fault config).

**A program that lacks this deployment's scope and span is refused in
``setup()``, before anything is built** (``mixed_solo``'s rule): without the
span ``sweep.tile`` the sweep layer dispatches the whole grid as one lane
batch, eight times 1.5 GB of state into a 16 GB chip, and without the scope
``pbft.tick.forge`` and the attack's milestones the rows cannot be judged.
The process ends at once with an ``AttributeError`` that says what is
lacking, a non-zero exit and no result line.  That is how the parent of the
PR that added the cell fails cleanly on it.

Set-up makes the first call under ``utils/telemetry.capture`` and keeps what
its ``sweep.tile`` spans say (tiles a call, lanes a tile, padding, state and
device bytes): the per-tick readers divide a call's device time by ticks
times tiles.  The tracer is polled between calls only, so a traced window
holds one whole call.
"""

from __future__ import annotations

import dataclasses
import time

import byz_checks
import program


def refuse_without_tiles(pbft, sweep) -> None:
    """Raise unless the program has the scope and the span this deployment
    is measured and sized by."""
    lacking = []
    if "pbft.tick.forge" not in getattr(pbft, "SCOPES", ()):
        lacking.append("the scope pbft.tick.forge (models.pbft.SCOPES)")
    if "sweep.tile" not in getattr(sweep, "SPANS", ()):
        lacking.append("the span sweep.tile (parallel.sweep.SPANS)")
    if lacking:
        raise AttributeError(
            "byzsweep: refusing before building. This program's sweep layer "
            "does not size a dispatch to the device (the whole grid would be "
            "one lane batch) and its rows cannot be held to the attack's "
            "guarantees: it lacks " + " and ".join(lacking))


class Driver:
    def __init__(self, ctx: dict):
        from blockchain_simulator_tpu import runner
        from blockchain_simulator_tpu.parallel import sweep
        from blockchain_simulator_tpu.utils import telemetry

        self.ctx = ctx
        self.cfg = program.sim_config(ctx["fields"])
        self.runner, self.sweep, self.telemetry = runner, sweep, telemetry
        self.rng = ctx["rng"]
        self.f_values = byz_checks.f_values(ctx["config"], self.cfg.n)
        stated = ctx["config"]["grid"]["f_values"]
        if self.cfg.n == ctx["config"]["fields"]["n"] and self.f_values != stated:
            raise ValueError(f"the grid's rule gives {self.f_values}, the "
                             f"configuration states {stated}")

    def _seed(self) -> int:
        return self.rng.randrange(2**31 - 1)

    def _one(self, seed: int) -> dict:
        t0 = time.monotonic()
        with self.ctx["tracer"].span("dispatch"):
            rows = self.sweep.run_byzantine_sweep(
                self.cfg, self.f_values, seeds=(seed,), forge=True)
        t1 = time.monotonic()
        return {"seed": seed, "t0": t0, "t1": t1, "rows": rows,
                "units": sum(1 for m in rows if byz_checks.sound(m))}

    def _solo(self, f: int, seed: int) -> dict:
        faults = dataclasses.replace(self.cfg.faults, n_byzantine=f)
        return self.runner.run_simulation(self.cfg.with_(faults=faults),
                                          seed=seed)

    def setup(self) -> dict:
        from blockchain_simulator_tpu.models import pbft

        refuse_without_tiles(pbft, self.sweep)
        with self.telemetry.capture() as spans:
            first = self._one(self._seed())
        tiles = [s["attrs"] for s in spans if s["name"] == "sweep.tile"]
        lanes = tiles[0]["lanes"] if tiles else len(self.f_values)
        self.tiles_per_call = max(len(tiles), 1)
        # the level whose rows are run again solo after the window, and its
        # program: built (and in the persistent cache) before the window
        self.solo_level = self.rng.randrange(len(self.f_values))
        t0 = time.monotonic()
        self._solo(self.f_values[self.solo_level], self._seed())
        return {
            "first_call_s": first["t1"] - first["t0"],
            "solo_warm_s": time.monotonic() - t0,
            "schedule": program.schedule_of(self.cfg),
            "ticks": self.cfg.ticks, "points_per_call": len(self.f_values),
            "tiles_per_call": self.tiles_per_call, "tile_lanes": lanes,
            "tile_pad": sum(t["pad"] for t in tiles),
            "state_bytes": tiles[0]["state_bytes"] if tiles else None,
            "device_bytes": tiles[0]["device_bytes"] if tiles else None,
            "solo_level": self.solo_level,
        }

    def window(self, t_window: float, seconds: float) -> dict:
        tracer = self.ctx["tracer"]
        samples = []
        while time.monotonic() - t_window < seconds:
            tracer.poll()
            samples.append(self._one(self._seed()))
            tracer.poll()
        attempted = len(samples) * len(self.f_values)
        calls = [s["t1"] - s["t0"] for s in samples]
        notes = {"calls": len(samples), "tiles_per_call": self.tiles_per_call,
                 "call_s_min": round(min(calls), 4),
                 "call_s_max": round(max(calls), 4),
                 "rows_with_view_change": sum(
                     1 for s in samples for m in s["rows"]
                     if m["view_changes"])}
        return {"samples": samples, "attempted": attempted,
                "failed": attempted - sum(s["units"] for s in samples),
                "unit": "points", "steps_per_dispatch": self.cfg.ticks,
                "tiles_per_call": self.tiles_per_call, "notes": notes}

    def verify(self, window: dict) -> list[dict]:
        config, fields = self.ctx["config"], self.ctx["reference_fields"]
        with self.ctx["tracer"].span("check"):
            rows = [m for s in window["samples"] for m in s["rows"]]
            t0 = time.monotonic()
            refs = byz_checks.reference_levels(config, fields, self.ctx["seed"])
            window["notes"]["reference_s"] = round(time.monotonic() - t0, 1)
            out = byz_checks.against_reference(rows, refs, config, fields)
            f = self.f_values[self.solo_level]
            mine = [m for m in rows if m["f"] == f]
            k = min(int(self.ctx["traffic"].get("verify_rows", 2)), len(mine))
            sample = self.rng.sample(mine, k)
            solo = [self._solo(f, m["seed"]) for m in sample]
            out.append(byz_checks.rows_near_solo(
                sample, solo, config["reference"]["solo_tick_limit_ms"]))
        return out

    def close(self) -> None:
        pass
