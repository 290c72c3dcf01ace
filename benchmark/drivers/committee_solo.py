"""Driver ``committee_solo``: ``solo``'s closed loop, queue and window, for
the committee tier (N replicas as C committees of m, PBFT inside each, one
combining step over them).

The timed path is the same seam of ``runner.run_simulation``
(``make_sim_fn(cfg)(key)``, then ``models.base.sim_metrics``, which for a
committee configuration is ``topo.committee.metrics``).  What differs is
what a run yields and what it is held to: a unit of work is a round, a block
final on all honest nodes of a committee, the mean over the committees of
``blocks_final_all_nodes`` (``committee_checks.rounds``), and the checks are
``committee_checks``' —
the configuration's guarantees on every run, the combining rule exactly, and
the counts and times of the plain reference
``reference/committee_engine.py`` at the committees' own size.

A program whose ``topo.committee.metrics`` reports the outer aggregate alone
for C > 1 (no ``per_committee``) cannot have its rounds counted: it fails
with a ``KeyError`` where set-up reads its first row, a non-zero exit and no
result line.

The queue is sized in work, as ``mixed_solo``'s: ``in_flight`` runs or
``queue_s`` seconds of them, whichever is more, by the lone warm run that
``setup()`` times.  A traced run polls the tracer while it waits for the
device, so that the trace is ``trace_seconds`` long and not a run longer.

After the window ``verify_rows`` committees of one seeded run are run again
as the FLAT program of the committee's own key (committee i of a stack keyed
k runs on ``fold_in(k, i)``; no faults in this traffic): the determinism
guarantee, ``rows_differing_from_flat``.  The flat program is built in
``setup()``.
"""

from __future__ import annotations

import importlib.util
import math
import os
import time

import committee_checks
import program
import readers

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_solo",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "solo.py"))
solo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solo)


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
                "units": committee_checks.rounds(m), "row": m}

    def _flat(self, seed: int, i: int) -> dict:
        """Committee ``i`` of the stack keyed ``seed``, as the flat program
        of its own key."""
        import jax

        key = jax.random.key(seed)
        if self.cfg.committees > 1:
            key = jax.random.fold_in(key, i)
        return self.sim_metrics(self.inner, self.flat(key))

    def setup(self) -> dict:
        from blockchain_simulator_tpu.topo import committee

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
               "schedule": program.schedule_of(self.cfg),
               "lone_run_s": lone_s}
        # the after-window check's flat program, built before the window
        self.inner = committee.inner_cfg(self.cfg)
        self.flat = self.runner.make_sim_fn(self.inner)
        t0 = time.monotonic()
        self._flat(self._seed(), 0)
        out["flat_warm_s"] = time.monotonic() - t0
        # what the stack ran as, as the program wrote it down where it
        # traced the stack (not the rule asked again)
        plan = committee.ran_as(self.cfg) or {}
        out.update(ticks=self.inner.ticks, committees=self.cfg.committees,
                   tiles=plan.get("tiles"), tile_lanes=plan.get("lanes"))
        self.counters0 = self._counters()
        return out

    def _counters(self) -> dict:
        from blockchain_simulator_tpu.topo import committee
        from blockchain_simulator_tpu.utils import telemetry

        got = telemetry.metrics.snapshot()["counters"]
        return {k: got.get(k, 0.0) for k in getattr(committee, "COUNTERS", ())}

    def window(self, t_window: float, seconds: float) -> dict:
        out = super().window(t_window, seconds)
        out["steps_per_dispatch"] = self.inner.ticks
        now = self._counters()
        out["counters"] = {k: now[k] - self.counters0.get(k, 0.0) for k in now}
        rows = [s["row"] for s in out["samples"]]
        mins = [min(m["per_committee"]["blocks_final_all_nodes"])
                for m in rows]
        out["notes"].update(
            # a run's unit is the mean over its committees; its minimum over
            # them, the hierarchy's floor, moves with the view changes the
            # run's seed drew and is reported beside it
            units_histogram=readers.histogram(
                [round(s["units"], 3) for s in out["samples"]]),
            run_min_histogram=readers.histogram(mins),
            committees_with_view_change=sum(
                1 for m in rows for c in committee_checks.committees_of(m)
                if c["view_changes"]),
            blocks_final_min_any_committee=min(mins, default=0))
        return out

    def verify(self, window: dict) -> list[dict]:
        config, fields = self.ctx["config"], self.ctx["reference_fields"]
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            out = committee_checks.guarantees(rows, fields)
            t0 = time.monotonic()
            ref = committee_checks.reference_milestones(
                config, fields, self.ctx["seed"])
            window["notes"]["reference_s"] = round(time.monotonic() - t0, 1)
            out += committee_checks.against_reference(rows, ref, config)
            one = self.rng.choice(window["samples"])
            k = min(int(self.ctx["traffic"].get("verify_rows", 2)),
                    self.cfg.committees)
            picked = self.rng.sample(range(self.cfg.committees), k)
            mine = committee_checks.committees_of(one["row"])
            out.append(committee_checks.rows_equal_flat(
                [(mine[i], self._flat(one["seed"], i)) for i in picked]))
        return out
