"""Driver ``solo``: one caller, one simulation after another (closed loop),
with the traffic file's ``in_flight`` runs queued on the device.

The timed path is ``runner.run_simulation``'s own, split at its one seam:
``runner.make_sim_fn(cfg)(key)`` (the registry's compiled program, dispatched
without waiting) and ``models.base.sim_metrics(cfg, final)`` (the host
readback into the metrics dict, which waits for that run).  The caller reads
the oldest run back and at once queues another, so the device always holds
``in_flight`` runs (``1`` is ``run_simulation`` back to back).  A run counts
when its dict is in hand; the window stops queueing at ``--seconds`` and ends
when the last queued run is read back.

Why a queue: the machine's host pauses now and then for up to a few seconds
(PERF.md section 6).  The device works through what is queued meanwhile, so
a pause shorter than the queue costs the rate nothing.
"""

from __future__ import annotations

import collections
import time

import checks
import program


class Driver:
    def __init__(self, ctx: dict):
        from blockchain_simulator_tpu import runner
        from blockchain_simulator_tpu.models.base import sim_metrics

        self.ctx = ctx
        self.cfg = program.sim_config(ctx["fields"])
        self.runner, self.sim_metrics = runner, sim_metrics
        self.rng = ctx["rng"]
        self.rounds = self.cfg.pbft_max_rounds
        self.in_flight = int(ctx["traffic"].get("in_flight", 1))

    def _seed(self) -> int:
        return self.rng.randrange(2**31 - 1)

    def _dispatch(self, seed: int) -> tuple:
        import jax

        t0 = time.monotonic()
        with self.ctx["tracer"].span("dispatch"):
            final = self.sim(jax.random.key(seed))
        return seed, t0, final

    def _collect(self, pending: tuple) -> dict:
        seed, t0, final = pending
        with self.ctx["tracer"].span("readback"):
            m = self.sim_metrics(self.cfg, final)
        return {"seed": seed, "t0": t0, "t1": time.monotonic(),
                "units": m["blocks_final_all_nodes"], "row": m}

    def setup(self) -> dict:
        # the first call traces, lowers and compiles (or loads from the
        # persistent cache) and runs the program once: every shape the
        # window uses.  What it costs beyond a lone warm call is warm_build_s.
        t0 = time.monotonic()
        self.sim = self.runner.make_sim_fn(self.cfg)
        first = self._collect(self._dispatch(self._seed()))
        second = self._collect(self._dispatch(self._seed()))
        lone = second["t1"] - second["t0"]
        return {"build_s": max(first["t1"] - t0 - lone, 0.0),
                "schedule": program.schedule_of(self.cfg)}

    def window(self, t_window: float, seconds: float) -> dict:
        tracer = self.ctx["tracer"]
        samples, queue = [], collections.deque()
        while True:
            tracer.poll()
            while (len(queue) < self.in_flight
                   and time.monotonic() - t_window < seconds):
                queue.append(self._dispatch(self._seed()))
            if not queue:
                break
            samples.append(self._collect(queue.popleft()))
        done = [t_window] + [s["t1"] for s in samples]
        # a pause of the host shows as one long gap between two completions
        gaps = [b - a for a, b in zip(done, done[1:])]
        notes = {"in_flight": self.in_flight,
                 "longest_completion_gap_s": round(max(gaps, default=0.0), 4)}
        return {"samples": samples, "attempted": len(samples), "failed": 0,
                "unit": "rounds", "steps_per_dispatch": self.rounds,
                "notes": notes}

    def verify(self, window: dict) -> list[dict]:
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            out = checks.guarantees(rows, self.rounds)
            ref = checks.reference_milestones(
                self.ctx["config"], self.ctx["reference_fields"], self.ctx["seed"])
            scaled = ref["rounds_sent"] != self.rounds
            out += checks.against_reference(rows, ref, self.ctx["config"],
                                            scaled)
        return out

    def close(self) -> None:
        pass
