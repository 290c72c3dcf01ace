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

A run's seed is drawn fresh from ``--seed``, unless the traffic file gives
``seed_classes`` (``{"classes": {<name>: [run seeds]}, "by": <the key of a
run's metrics whose value names its class>}``; ``by`` may be left out, as
where there is one class): run seeds sorted beforehand by how much WORK they
make (a Paxos run whose proposers retry six times takes 7.6% longer than one
with three).  The runs then take the classes in turn at the file's own
ratio, so that every prefix of a window, whatever its length, holds each
class's share to within one run (``class_order``); inside a class the seeds
come in an order that ``--seed`` shuffles, round and round.  A window's rate
then does not move with the share of long runs its seeds happened to draw,
at any speed of the program and any ``--seconds``.  ``seed_class_misses``
counts the runs that came out of another class than the file has them in,
for a window's notes (``mesh_solo``, the one driver whose cell has classes).
"""

from __future__ import annotations

import collections
import itertools
import time

import checks
import program


def class_order(classes: dict, rng):
    """The run seeds of ``classes`` (``{name: [seeds]}``) without end: each
    class round and round in an order ``rng`` shuffles, the classes taken in
    turn so that after any k seeds each class has given its share of k
    (its size over the sizes' sum) to within one: the next seed is always of
    the class furthest behind its share (the first by name of those that tie).
    After as many seeds as there are, every one has come once."""
    names = sorted(classes)
    pools = {k: list(classes[k]) for k in names}
    for k in names:
        rng.shuffle(pools[k])
    total = sum(len(v) for v in pools.values())
    given = dict.fromkeys(names, 0)
    for k in itertools.count(1):
        behind = max(names,
                     key=lambda c: len(pools[c]) * k - given[c] * total)
        yield pools[behind][given[behind] % len(pools[behind])]
        given[behind] += 1


def seed_class_misses(traffic: dict, samples: list) -> int:
    """Runs of a window whose metrics put them in another class than the
    traffic file's ``seed_classes`` has their seed in."""
    spec = traffic["seed_classes"]
    of = {s: name for name, seeds in spec["classes"].items() for s in seeds}
    return sum(1 for s in samples
               if str(s["row"].get(spec["by"])) != of.get(s["seed"]))


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
        classes = ctx["traffic"].get("seed_classes")
        self.seeds = class_order(classes["classes"], self.rng) \
            if classes else None

    def _seed(self) -> int:
        if self.seeds is not None:
            return next(self.seeds)
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
