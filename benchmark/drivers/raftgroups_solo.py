"""Driver ``raftgroups_solo``: ``solo``'s closed loop, queue and window, for
multi-Raft (N nodes as C independent Raft groups of m, Raft with terms inside
each, run as one committee stack).

The timed path is the same seam of ``runner.run_simulation``
(``make_sim_fn(cfg)(key)``, then ``models.base.sim_metrics``, which for a
committee configuration is ``topo.committee.metrics``).  A unit of work is a
round, one block committed by a majority in every group, counted as the mean
over the groups of ``blocks`` (``raftgroups_checks.rounds``), and the checks
are ``raftgroups_checks``': election safety and the block count exactly in
every group of every run, the distributions of the first election, the
commit tail and the failover against the plain reference
``reference/raft_terms_engine.py``'s sample of groups.

A program whose ``SimConfig`` has no ``raft_terms`` (the parent of the PR
that brought terms) fails where the driver builds its configuration: a
``TypeError``, a non-zero exit and no result line, before anything is built.

The queue is sized in work, as ``committee_solo``'s: ``in_flight`` runs or
``queue_s`` seconds of them, whichever is more, by the lone warm run that
``setup()`` times.  A traced run polls the tracer while it waits for the
device; a run is longer than a trace may be, so ``setup()`` moves the start
of the traced window to ``trace_lead_s`` before the device is due to finish
the window's first run (by the same lone warm run: dispatch to the finals
ready, the host's metrics pass not counted), and the trace holds that run's
end, its one readback and the start of the next.

After the window ``verify_rows`` groups of one seeded run are run again as
the FLAT program of the group's own key (group i of a stack keyed k runs on
``fold_in(k, i)``; no faults in this traffic): the determinism guarantee,
``rows_differing_from_flat``.  The flat program is built in ``setup()``.
"""

from __future__ import annotations

import importlib.util
import os
import time

import raftgroups_checks
import readers


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


committee_solo = _sibling("committee_solo")


class Driver(committee_solo.Driver):
    """``committee_solo``'s set-up (the stack and the flat program of a
    group's key built and warmed, the queue sized, the traced plan and the
    counters read) and ``solo``'s window; the unit, the notes and the checks
    are multi-Raft's."""

    def _collect(self, pending: tuple) -> dict:
        import jax

        seed, t0, final = pending
        tracer = self.ctx["tracer"]
        if tracer.on and tracer.t_open is not None:  # inside the window
            leaves = jax.tree_util.tree_leaves(final)
            while not all(x.is_ready() for x in leaves):
                tracer.poll()
                time.sleep(0.01)
        else:
            jax.block_until_ready(final)
        # the device is done; the host's part follows
        self.ready_s = time.monotonic() - t0
        with tracer.span("readback"):
            m = self.sim_metrics(self.cfg, final)
        return {"seed": seed, "t0": t0, "t1": time.monotonic(),
                "units": raftgroups_checks.rounds(m), "row": m}

    def setup(self) -> dict:
        out = super().setup()
        # a run is longer than a trace may be: open the traced window shortly
        # before the device finishes the window's first run (by the lone warm
        # run: its dispatch to its finals ready), so that it holds that run's
        # readback
        out["lone_ready_s"] = self.ready_s
        tracer = self.ctx["tracer"]
        tracer.delay_s = max(tracer.delay_s, self.ready_s - float(
            self.ctx["traffic"].get("trace_lead_s", 0.0)))
        return out

    def _counters(self) -> dict:
        from blockchain_simulator_tpu.utils import telemetry

        got = telemetry.metrics.snapshot()["counters"]
        return {**super()._counters(),
                **{k: got.get(k, 0.0)
                   for k in getattr(telemetry, "RAFT_COUNTERS", ())}}

    def window(self, t_window: float, seconds: float) -> dict:
        out = committee_solo.solo.Driver.window(self, t_window, seconds)
        out["steps_per_dispatch"] = self.inner.ticks
        now = self._counters()
        out["counters"] = {k: now[k] - self.counters0.get(k, 0.0) for k in now}
        rows = [s["row"] for s in out["samples"]]
        pooled, histogram = raftgroups_checks.pooled, readers.histogram
        out["notes"].update(
            units_histogram=histogram(
                [round(s["units"], 3) for s in out["samples"]]),
            groups=len(pooled(rows, "blocks")))
        if raftgroups_checks.has_terms(rows):
            out["notes"].update(
                term_final_histogram=histogram(pooled(rows, "term_final")),
                first_leader_term_histogram=histogram(
                    pooled(rows, "first_leader_term")),
                groups_without_leader=sum(
                    1 for t in pooled(rows, "first_leader_ms") if t < 0),
                groups_without_failover=sum(
                    1 for t in pooled(rows, "failover_ms") if t < 0),
                last_block_ms_max=max(pooled(rows, "last_block_ms")))
        return out

    def verify(self, window: dict) -> list[dict]:
        config, fields = self.ctx["config"], self.ctx["reference_fields"]
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            t0 = time.monotonic()
            ref = raftgroups_checks.reference_groups(
                config, fields, self.ctx["seed"])
            window["notes"]["reference_s"] = round(time.monotonic() - t0, 1)
            window["notes"]["reference_groups"] = ref["groups"]
            out = raftgroups_checks.guarantees(rows, ref)
            out += raftgroups_checks.against_reference(rows, ref, config)
            one = self.rng.choice(window["samples"])
            k = min(int(self.ctx["traffic"].get("verify_rows", 2)),
                    self.cfg.committees)
            picked = self.rng.sample(range(self.cfg.committees), k)
            out.append(raftgroups_checks.rows_equal_flat(
                [(raftgroups_checks.group(one["row"], i),
                  self._flat(one["seed"], i)) for i in picked]))
        return out
