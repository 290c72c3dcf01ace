"""Driver ``mixed_solo``: ``solo``'s closed loop, queue and window, for the
mixed deployment (S Raft shards under PBFT over their representatives).

The timed path is the same seam of ``runner.run_simulation``
(``make_sim_fn(cfg)(key)``, then ``models.base.sim_metrics``).  What differs
is what a run yields and what it is held to: a unit of work is a *global*
round, a block final on every shard representative
(``global_blocks_final``), and the checks are ``mixed_checks``' — the
configuration's guarantees on every run, and the milestones of the plain
reference ``reference/mixed_engine.py`` at the cell's own size.

**A program that cannot be held to the deployment's guarantees is refused
in ``setup()``, before anything is built.**  The guarantees include timing
milestones equal to the reference's; ``models/mixed`` names the ones its
``metrics`` reports in its ``MILESTONES`` tuple.  Where the module has no
such tuple, or the tuple lacks one that ``mixed_checks`` compares, the
process ends at once with an ``AttributeError`` that says which guarantee
cannot be judged, a non-zero exit and no result line (PERF.md section 6,
PR 28: a program without the milestones also batches the shards so that a
run takes 35 s, and a process that rehearses it outlasts the driver's limit).

The queue is sized in work, not in runs: ``in_flight`` runs or ``queue_s``
seconds of them, whichever is more, by the lone warm run that ``setup()``
times.  At 0.53 s a run two queued runs ride out a pause of the host of one
second, and the machine pauses for two now and then (one window in six read
2% low, PERF.md section 6).  A traced run polls the tracer while it waits for
the device, so that the trace is ``trace_seconds`` long and not a run longer
(``solo`` polls between completions); a traced second of this engine is a
million device events.

A run in which one shard failed the handoff falls back to the per-tick engine
for the whole window and takes many times as long: it shows as the note
``longest_completion_gap_s`` (and in a trace as ``mixed_fallback_pct``).
"""

from __future__ import annotations

import importlib.util
import math
import os
import time

import mixed_checks
import program

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_solo",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "solo.py"))
solo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solo)


def refuse_without_milestones(mixed) -> None:
    """Raise unless ``models.mixed.MILESTONES`` holds every key the checks
    compare."""
    names = getattr(mixed, "MILESTONES", None)
    lacking = (mixed_checks.TIMING_KEYS if names is None else
               tuple(k for k in mixed_checks.TIMING_KEYS if k not in names))
    if lacking:
        raise AttributeError(
            "mixed_solo: refusing before building. The configuration's "
            "guarantee 'timing' (the milestones equal the per-message "
            "reference's on every row) cannot be judged on this program: "
            f"models.mixed.MILESTONES lacks {', '.join(lacking)}")


class Driver(solo.Driver):
    def _collect(self, pending: tuple) -> dict:
        seed, t0, final = pending
        tracer = self.ctx["tracer"]
        if tracer.on and tracer.t_open is not None:  # inside the window
            import jax

            leaves = jax.tree_util.tree_leaves(final)
            while not all(x.is_ready() for x in leaves):
                tracer.poll()
                time.sleep(0.01)
        with tracer.span("readback"):
            m = self.sim_metrics(self.cfg, final)
        return {"seed": seed, "t0": t0, "t1": time.monotonic(),
                "units": m["global_blocks_final"], "row": m}

    def setup(self) -> dict:
        from blockchain_simulator_tpu.models import mixed, raft_hb

        refuse_without_milestones(mixed)
        # as ``solo``: the first call traces, lowers, compiles (or loads
        # from the persistent cache) and runs the program once, every shape
        # the window uses; the second is a lone warm run
        t0 = time.monotonic()
        self.sim = self.runner.make_sim_fn(self.cfg)
        first = self._collect(self._dispatch(self._seed()))
        second = self._collect(self._dispatch(self._seed()))
        lone_s = second["t1"] - second["t0"]
        self.in_flight = max(self.in_flight, math.ceil(
            float(self.ctx["traffic"].get("queue_s", 0.0)) / lone_s))
        out = {"build_s": max(first["t1"] - t0 - lone_s, 0.0),
               "schedule": program.schedule_of(self.cfg)}
        # what the per-tick and per-heartbeat readers divide by
        rcfg, _ = mixed.sub_configs(self.cfg)
        prefix = raft_hb.prefix_ticks(rcfg)
        out.update(prefix_ticks=prefix,
                   steady_ticks=max(self.cfg.ticks - prefix, 0),
                   hb_steps=raft_hb.n_hb_steps(rcfg))
        return out

    def verify(self, window: dict) -> list[dict]:
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            fields = self.ctx["reference_fields"]
            out = mixed_checks.guarantees(
                rows, fields, self.cfg.raft_max_blocks, self.rounds)
            ref = mixed_checks.reference_milestones(
                self.ctx["config"], fields, self.ctx["seed"])
            out += mixed_checks.against_reference(
                rows, ref, self.ctx["config"],
                self.cfg.pbft_block_interval_ms)
        return out
