"""Driver ``raftcrash_solo``: ``raftgroups_solo``'s set-up, closed loop, queue,
window and traced-window placement, for multi-Raft under a crash schedule (N
nodes as C independent Raft groups of m, Raft with terms inside each, every
group's leader killed ``faults.crashes`` times a run and back ``downtime_ms``
later, run as one committee stack).

The timed path is the same seam of ``runner.run_simulation``
(``make_sim_fn(cfg)(key)``, then ``models.base.sim_metrics``, which for a
committee configuration is ``topo.committee.metrics``).  A unit of work is a
round: one crashed leader replaced by a leader of a higher term, counted as
the mean over the groups of ``failovers`` (``raftcrash_checks.rounds``), and
the checks are ``raftcrash_checks``': election safety and crash integrity
exactly in every group of every run, the failover's distribution over all
crashes against the plain reference ``reference/raft_crash_engine.py``'s
sample of groups.

A program whose ``FaultConfig`` has no schedule (the parent of the PR that
brought it) fails where the driver builds its configuration: a ``TypeError``,
a non-zero exit and no result line, before anything is built.

After the window ``verify_rows`` groups of one seeded run are run again as
the FLAT program of the group's own key (group i of a stack keyed k runs on
``fold_in(k, i)``, its schedule's phase a draw from that key): the
determinism guarantee, ``rows_differing_from_flat``.
"""

from __future__ import annotations

import importlib.util
import os
import time

import raftcrash_checks
import readers

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_raftgroups_solo", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "raftgroups_solo.py"))
raftgroups_solo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(raftgroups_solo)


class Driver(raftgroups_solo.Driver):
    """``raftgroups_solo``'s driver; the unit, the notes and the checks are
    the crash schedule's."""

    def _collect(self, pending: tuple) -> dict:
        out = super()._collect(pending)
        out["units"] = raftcrash_checks.rounds(out["row"])
        return out

    def window(self, t_window: float, seconds: float) -> dict:
        out = super().window(t_window, seconds)
        rows = [s["row"] for s in out["samples"]]
        if rows and raftcrash_checks.has_schedule(rows):
            pooled = raftcrash_checks.pooled
            out["notes"].update(
                failovers_histogram=readers.histogram(
                    pooled(rows, "failovers")),
                crashes_found_no_leader=sum(
                    pooled(rows, "crashes_found_no_leader")),
                crashes_unreplaced=sum(pooled(rows, "crashes_unreplaced")),
                crashes=sum(pooled(rows, "crashes")),
                restarts=sum(pooled(rows, "restarts")),
                failover_ms_max=max(pooled(rows, "failover_max_ms")))
        return out

    def verify(self, window: dict) -> list[dict]:
        config, fields = self.ctx["config"], self.ctx["reference_fields"]
        notes = window["notes"]
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            t0 = time.monotonic()
            ref = raftcrash_checks.reference_groups(
                config, fields, self.ctx["seed"])
            notes["reference_s"] = round(time.monotonic() - t0, 1)
            notes["reference_groups"] = ref["groups"]
            shapes = {"reference": raftcrash_checks.shape(ref["per_group"])}
            if raftcrash_checks.has_schedule(rows):
                shapes["program"] = raftcrash_checks.shape(
                    raftcrash_checks.merged(rows))
            notes.update({f"{who}_failover_{k}": round(v, 5)
                          for who, shape in shapes.items()
                          for k, v in shape.items()})
            out = raftcrash_checks.guarantees(rows, ref, fields)
            out += raftcrash_checks.against_reference(rows, ref, config)
            one = self.rng.choice(window["samples"])
            k = min(int(self.ctx["traffic"].get("verify_rows", 2)),
                    self.cfg.committees)
            picked = self.rng.sample(range(self.cfg.committees), k)
            out.append(raftcrash_checks.rows_equal_flat(
                [(raftcrash_checks.group(one["row"], i),
                  self._flat(one["seed"], i)) for i in picked]))
        return out
