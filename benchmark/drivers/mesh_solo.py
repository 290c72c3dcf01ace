"""Driver ``mesh_solo``: ``solo``'s closed loop, queue and window, for a
deployment whose node state is sharded over a device mesh.

The timed path is what ``python -m blockchain_simulator_tpu --shards S``
runs: ``parallel/shard.make_sharded_sim_fn(cfg, make_mesh(n_node_shards=S))
(key)``, one SPMD program over the mesh axis ``nodes`` dispatched without
waiting, then ``shard.readback`` (one fetch of the leaves ``metrics`` reads)
and ``models.base.sim_metrics`` on the oldest.  ``S`` is the traffic file's
``node_shards`` on the chip; a rehearsal takes as many shards as it finds
devices, at most that many (the largest count that divides ``n``).

A unit of work, a *round*, is one decree executed by every acceptor: one a
run, none for a run in which an acceptor did not execute (which also fails
the run's guarantees).  The checks are ``paxos_checks``': the configuration's
guarantees on every run, and the milestones of the plain reference
``reference/paxos_gossip_engine.py`` at the cell's own size.

**A program that cannot be held to the deployment's guarantees is refused
in ``setup()``, before anything is built** (``mixed_solo``'s rule): where
``models/paxos`` has no ``MILESTONES`` tuple, or the tuple lacks a key that
``paxos_checks`` compares, the process ends at once with an
``AttributeError`` that says which guarantee cannot be judged, a non-zero
exit and no result line.  That is how the parent of the PR that added the
cell fails cleanly on it.

The queue is sized in work as ``mixed_solo``'s: ``in_flight`` runs or
``queue_s`` seconds of them, whichever is more, by the lone warm run of
set-up.  Set-up also reads the program's own counter of its communication
(``shard.collective_counts``: collectives per tick, bytes a taken flood arm
all-gathers, or all-reduces where its senders outgrow the exchange) once
from the compiled module; with jax's compile cache on that is a load of the
executable the runs use.

A traced run cannot hold a whole run (a sharded tick is hundreds of device
events on each of four planes and stopping the tracer costs tens of seconds
per million), so the driver opens the trace when a run has completed, just
before it reads that run back: the trace then holds one ``shard.readback``
and the first ticks of the run the mesh turned to next, the same stretch of
a run in every traced process, and the per-tick readers divide by the ticks
they count in it.
"""

from __future__ import annotations

import collections
import importlib.util
import math
import os
import time

import paxos_checks
import program

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_solo",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "solo.py"))
solo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solo)


def refuse_without_milestones(paxos) -> None:
    """Raise unless ``models.paxos.MILESTONES`` holds every key the checks
    compare."""
    names = getattr(paxos, "MILESTONES", None)
    lacking = (paxos_checks.TIMING_KEYS if names is None else
               tuple(k for k in paxos_checks.TIMING_KEYS if k not in names))
    if lacking:
        raise AttributeError(
            "mesh_solo: refusing before building. The configuration's "
            "guarantee 'timing' (the milestones equal the per-message "
            "reference's) cannot be judged on this program: "
            f"models.paxos.MILESTONES lacks {', '.join(lacking)}")


def shards_for(n: int, want: int, found: int) -> int:
    """As many node shards as there are devices, at most ``want``, that
    divide ``n``."""
    return max(s for s in range(1, min(want, found) + 1) if n % s == 0)


class Driver(solo.Driver):
    def _wait(self, final) -> None:
        """Until the run is done; a traced window polls the tracer
        meanwhile, so that the trace is ``trace_seconds`` long and not a run
        longer."""
        import jax

        tracer = self.ctx["tracer"]
        leaves = jax.tree_util.tree_leaves(final)
        if tracer.on and tracer.t_start is not None and tracer.t_stop is None:
            while not all(x.is_ready() for x in leaves):
                tracer.poll()
                time.sleep(0.005)
        with tracer.span("wait"):
            jax.block_until_ready(leaves)

    def _collect(self, pending: tuple) -> dict:
        seed, t0, final = pending
        self._wait(final)
        tracer = self.ctx["tracer"]
        if tracer.on and tracer.t_open is not None:  # inside the window
            tracer.poll()  # opens the trace here: the readback is in it
        with tracer.span("readback"):
            host = self.shard.readback(self.cfg, self.mesh, final)
            m = self.sim_metrics(self.cfg, host)
        return {"seed": seed, "t0": t0, "t1": time.monotonic(),
                "units": int(m["acceptor_executes"] == self.cfg.n), "row": m}

    def setup(self) -> dict:
        import jax

        from blockchain_simulator_tpu.models import paxos
        from blockchain_simulator_tpu.parallel import shard
        from blockchain_simulator_tpu.parallel.mesh import make_mesh

        refuse_without_milestones(paxos)
        devs = jax.devices()
        want = int(self.ctx["traffic"].get("node_shards", 1))
        shards = want if self.ctx["on_chip"] else shards_for(
            self.cfg.n, want, len(devs))
        self.shard = shard
        self.mesh = make_mesh(n_node_shards=shards, devices=devs[:shards])
        # as ``solo``: the first call traces, lowers, compiles (or loads from
        # the persistent cache) and runs the program once, every shape the
        # window uses; the second is a lone warm run
        t0 = time.monotonic()
        self.sim = shard.make_sharded_sim_fn(self.cfg, self.mesh)
        first = self._collect(self._dispatch(self._seed()))
        second = self._collect(self._dispatch(self._seed()))
        lone_s = second["t1"] - second["t0"]
        self.in_flight = max(self.in_flight, math.ceil(
            float(self.ctx["traffic"].get("queue_s", 0.0)) / lone_s))
        t1 = time.monotonic()
        counts = shard.collective_counts(self.cfg, self.mesh)
        return {"build_s": max(first["t1"] - t0 - lone_s, 0.0),
                "schedule": "tick", "ticks": self.cfg.ticks,
                "shards": shards, "rows_per_shard": self.cfg.n // shards,
                "lone_run_s": lone_s, "collectives": counts,
                "counter_s": time.monotonic() - t1}

    def window(self, t_window: float, seconds: float) -> dict:
        samples, queue = [], collections.deque()
        while True:
            # the tracer is polled in ``_collect``, as a run completes
            while (len(queue) < self.in_flight
                   and time.monotonic() - t_window < seconds):
                queue.append(self._dispatch(self._seed()))
            if not queue:
                break
            samples.append(self._collect(queue.popleft()))
        done = [t_window] + [s["t1"] for s in samples]
        gaps = [b - a for a, b in zip(done, done[1:])]
        rows = [s["row"] for s in samples]
        notes = {
            "in_flight": self.in_flight, "shards": self.mesh.shape["nodes"],
            "longest_completion_gap_s": round(max(gaps, default=0.0), 4),
            "committed_proposers": sorted(
                collections.Counter(
                    m["n_committed_proposers"] for m in rows).items()),
            "retries": sorted(collections.Counter(
                m["retries"] for m in rows).items()),
        }
        if self.ctx["traffic"].get("seed_classes", {}).get("by"):
            notes["seed_class_misses"] = solo.seed_class_misses(
                self.ctx["traffic"], samples)
        return {"samples": samples, "attempted": len(samples),
                "failed": sum(1 for s in samples if not s["units"]),
                "unit": "rounds", "steps_per_dispatch": self.cfg.ticks,
                "notes": notes}

    def verify(self, window: dict) -> list[dict]:
        with self.ctx["tracer"].span("check"):
            rows = [s["row"] for s in window["samples"]]
            fields = self.ctx["reference_fields"]
            out = paxos_checks.guarantees(rows, fields)
            t0 = time.monotonic()
            ref = paxos_checks.reference_milestones(
                self.ctx["config"], fields, self.ctx["seed"])
            window["notes"]["reference_s"] = round(time.monotonic() - t0, 1)
            window["notes"]["reference_tries"] = ref["tries"]
            out += paxos_checks.against_reference(
                rows, ref, self.ctx["config"], fields)
        return out
