"""Driver ``sweep``: one caller, one Monte Carlo dispatch after another.

The timed path is ``parallel/sweep.run_seed_sweep(cfg, seeds)``: ``lanes``
fresh seeds per call as one vmapped program, the readback included (one
fetch of the leaves the metrics read, for every lane; then a metrics dict a
row on the host).  A row counts when its dict is back and checked.  After the
window a seeded sample of the window's own rows is re-run solo
(``runner.run_simulation``) and must be dict-equal.
"""

from __future__ import annotations

import time

import checks
import program


class Driver:
    def __init__(self, ctx: dict):
        from blockchain_simulator_tpu import runner
        from blockchain_simulator_tpu.parallel import sweep

        self.ctx = ctx
        self.cfg = program.sim_config(ctx["fields"])
        self.runner, self.sweep = runner, sweep
        self.rng = ctx["rng"]
        self.lanes = int(ctx["traffic"]["lanes"])

    def _seeds(self) -> list[int]:
        return [self.rng.randrange(2**31 - 1) for _ in range(self.lanes)]

    def _one(self, seeds: list[int]) -> dict:
        t0 = time.monotonic()
        with self.ctx["tracer"].span("dispatch"):
            rows = self.sweep.run_seed_sweep(self.cfg, seeds)
        t1 = time.monotonic()
        ok = sum(1 for m in rows
                 if m["agreement_ok"] and m["blocks_final_all_nodes"] > 0)
        return {"seeds": seeds, "t0": t0, "t1": t1, "units": ok, "rows": rows}

    def setup(self) -> dict:
        first = self._one(self._seeds())
        # the solo program of the after-window check, so that it is built
        # (and in the persistent cache) before the window
        t0 = time.monotonic()
        self.runner.run_simulation(self.cfg, seed=self._seeds()[0])
        return {
            "first_call_s": first["t1"] - first["t0"],
            "solo_warm_s": time.monotonic() - t0,
            "schedule": program.schedule_of(self.cfg),
        }

    def window(self, t_window: float, seconds: float) -> dict:
        tracer = self.ctx["tracer"]
        samples = []
        while time.monotonic() - t_window < seconds:
            tracer.poll()
            samples.append(self._one(self._seeds()))
            tracer.poll()
        attempted = len(samples) * self.lanes
        return {"samples": samples, "attempted": attempted,
                "failed": attempted - sum(s["units"] for s in samples),
                "unit": "points", "steps_per_dispatch": self.cfg.ticks}

    def verify(self, window: dict) -> list[dict]:
        with self.ctx["tracer"].span("check"):
            pairs = [(seed, row) for s in window["samples"]
                     for seed, row in zip(s["seeds"], s["rows"])]
            rows = [r for _, r in pairs]
            out = checks.guarantees(rows, None)
            k = min(int(self.ctx["traffic"].get("verify_rows", 4)), len(pairs))
            sample = self.rng.sample(pairs, k)
            solo = [self.runner.run_simulation(self.cfg, seed=s)
                    for s, _ in sample]
            out.append(checks.rows_equal_solo([r for _, r in sample], solo))
            ref = checks.reference_milestones(
                self.ctx["config"], self.ctx["reference_fields"], self.ctx["seed"])
            out += checks.against_reference(rows, ref, self.ctx["config"],
                                            scaled=False)
        return out

    def close(self) -> None:
        pass
