"""Driver ``served``: open-loop scenario requests against the in-process
``serve.ScenarioServer`` (its queue, micro-batcher and dispatch), at the
fixed rate the traffic file gives.

A generator thread submits request ``i`` at ``t_window + i / rate`` and never
waits for an answer; a collector thread takes the answers in submission
order (one batcher answers in that order).  A request's latency is the time
its answer was in hand minus the time it was DUE, so a stall of the generator
or the server is charged to the requests it delayed.  A refused, failed or
timed-out request counts as ``failed`` and keeps the latency it had when the
failure was known.  No daemon, no HTTP, no child process: one process holds
the chip, generates the load and takes the trace.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time

import checks
import program


class Driver:
    def __init__(self, ctx: dict):
        from blockchain_simulator_tpu import runner
        from blockchain_simulator_tpu.serve import ScenarioServer, ServeError
        from blockchain_simulator_tpu.utils import telemetry

        self.ctx, self.runner, self.telemetry = ctx, runner, telemetry
        self.ServeError = ServeError
        tr = ctx["traffic"]
        self.template = dict(ctx["fields"])
        self.template.pop("faults", None)
        self.cfg0 = program.sim_config(self.template)  # the fault-free run
        self.f_levels = list(tr["f_levels"])
        self.rate = float(tr["rate_per_s"])
        self.arrivals = tr.get("arrivals", "fixed")
        self.timeout_s = float(tr.get("timeout_s", 30.0))
        self.server = ScenarioServer(
            max_batch=int(tr["max_batch"]), max_wait_ms=float(tr["max_wait_ms"]),
            max_queue=int(tr["max_queue"]), default_timeout_s=self.timeout_s)
        self.rng = ctx["rng"]
        self._i = 0

    def _request(self) -> dict:
        i, self._i = self._i, self._i + 1
        return dict(self.template, seed=self.rng.randrange(2**31 - 1),
                    faults={"n_byzantine": self.f_levels[i % len(self.f_levels)]})

    def _offsets(self, n: int) -> list[float]:
        """When each request is due after the window opens.  ``fixed``: every
        ``1 / rate``.  ``exponential``: independent users — the gaps are the
        n quantiles of the exponential distribution with that mean (the same
        set in every run), in an order drawn from the seed."""
        if self.arrivals == "fixed":
            return [i / self.rate for i in range(n)]
        if self.arrivals != "exponential":
            raise ValueError(f"arrivals {self.arrivals!r}")
        gaps = [-math.log(1.0 - (i + 0.5) / n) / self.rate for i in range(n)]
        self.rng.shuffle(gaps)
        out, t = [], 0.0
        for g in gaps:
            out.append(t)
            t += g
        return out

    def setup(self) -> dict:
        # every program a flush can reach: the solo program and each
        # power-of-two bucket.  The second pass is the programs alone.
        t0 = time.monotonic()
        self.server.prewarm(self.template)
        t1 = time.monotonic()
        walls = self.server.prewarm(self.template)
        t2 = time.monotonic()
        # the static solo program of the after-window check
        self.runner.run_simulation(self.cfg0, seed=1)
        t3 = time.monotonic()
        # once through the whole path: a full batch and a lone request
        futs = [self.server.submit(self._request())
                for _ in range(self.server.max_batch)]
        bad = [r for r in (f.result(600) for f in futs)
               if r.get("status") != "ok"]
        lone = self.server.request(self._request(), 600)
        if bad or lone.get("status") != "ok":
            raise RuntimeError(f"warm-up requests failed: {bad or lone}")
        return {"prewarm_first_s": t1 - t0, "prewarm_second_s": t2 - t1,
                "build_s": max((t1 - t0) - (t2 - t1), 0.0),
                "bucket_run_s": walls, "solo_warm_s": t3 - t2,
                "schedule": program.schedule_of(self.cfg0)}

    def window(self, t_window: float, seconds: float) -> dict:
        tracer = self.ctx["tracer"]
        n = max(int(self.rate * seconds), 1)
        objs = [self._request() for _ in range(n)]
        offsets = self._offsets(n)
        slots: list = [None] * n
        ready = [threading.Event() for _ in range(n)]
        stats0 = self.server.stats()

        def generate():
            for i, obj in enumerate(objs):
                due = t_window + offsets[i]
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                t_sub = time.monotonic()
                try:
                    with tracer.span("generator"):
                        fut, err = self.server.submit(obj), None
                except self.ServeError as e:
                    fut, err = None, e.kind
                slots[i] = {"i": i, "due": due, "t_submit": t_sub,
                            "fut": fut, "error": err, "request": obj}
                ready[i].set()

        def collect():
            for i in range(n):
                ready[i].wait()
                s = slots[i]
                if s["fut"] is not None:
                    with tracer.span("collect_wait"):
                        s["response"] = s.pop("fut").result(
                            self.timeout_s + 30.0)
                else:
                    s["response"] = {"status": "refused", "kind": s["error"]}
                    s.pop("fut")
                s["t_done"] = time.monotonic()

        capture = (self.telemetry.capture() if tracer.on
                   else contextlib.nullcontext([]))
        with capture as spans:
            threads = [threading.Thread(target=generate, name="bench-generator"),
                       threading.Thread(target=collect, name="bench-collector")]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                tracer.poll()
                time.sleep(0.02)
            for t in threads:
                t.join()
        tracer.close()
        stats1 = self.server.stats()
        ok = [s for s in slots if s["response"].get("status") == "ok"]
        late = sorted((s["t_submit"] - s["due"]) * 1e3 for s in slots)
        return {
            "samples": slots, "attempted": n, "failed": n - len(ok),
            "unit": "requests", "steps_per_dispatch": self.cfg0.ticks,
            "latencies_ms": [(s["t_done"] - s["due"]) * 1e3 for s in slots],
            "t_last_done": max(s["t_done"] for s in slots),
            "stats": {k: stats1[k] - stats0[k] for k in
                      ("served", "batches", "degraded_batches", "errors",
                       "timeouts")},
            "occupancy": stats1["occupancy"],
            "server_spans": list(spans),
            "notes": {"generator_late_ms_median": late[len(late) // 2],
                      "generator_late_ms_max": late[-1],
                      "drain_s_after_window": max(
                          s["t_done"] for s in slots) - (t_window + seconds)},
        }

    def verify(self, window: dict) -> list[dict]:
        with self.ctx["tracer"].span("check"):
            done = [s for s in window["samples"]
                    if s["response"].get("status") == "ok"]
            # a refused or timed-out request is ``failed`` on the result
            # line, not a wrong answer
            out = [checks.exact("degraded_batches",
                                window["stats"]["degraded_batches"], 0),
                   checks.exact("degraded_answers", sum(
                       1 for s in done
                       if s["response"]["batch"].get("degraded")), 0)]
            if not done:
                return out
            rows = [s["response"]["metrics"] for s in done]
            out += checks.guarantees(rows, None)
            # a seeded sample, half of it fault-free: each answer again as a
            # lone request (the un-vmapped program), and the fault-free ones
            # against runner.run_simulation at the static configuration
            k = min(int(self.ctx["traffic"].get("verify_rows", 4)), len(done))
            clean = [s for s in done
                     if s["request"]["faults"]["n_byzantine"] == 0]
            sample = self.rng.sample(clean, min(k // 2, len(clean)))
            rest = [s for s in done if s not in sample]
            sample += self.rng.sample(rest, min(k - len(sample), len(rest)))
            again = [self.server.request(dict(s["request"]), 600).get("metrics")
                     for s in sample]
            out.append(checks.rows_equal_solo(
                [s["response"]["metrics"] for s in sample], again))
            static = [s for s in sample
                      if s["request"]["faults"]["n_byzantine"] == 0]
            solo = [self.runner.run_simulation(self.cfg0,
                                               seed=s["request"]["seed"])
                    for s in static]
            c = checks.rows_equal_solo(
                [s["response"]["metrics"] for s in static], solo)
            c["name"] = "rows_differing_from_static_solo"
            out.append(c)
            ref = checks.reference_milestones(
                self.ctx["config"], self.ctx["reference_fields"],
                self.ctx["seed"])
            out += checks.against_reference(
                [s["response"]["metrics"] for s in clean], ref,
                self.ctx["config"], scaled=False)
        return out

    def close(self) -> None:
        self.server.close()
