"""The benchmark's command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python benchmark/run.py --selftest

One process: it reaches the chip, builds and warms the cell's programs
(set-up), drives the cell's traffic for ``--seconds`` (the window), checks
what the window produced, and prints one JSON line last on stdout.  With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy time and a breakdown.
Every number compared stands beside its limit in the line's last key,
``checks`` (a list of ``[name, value, rule, limit]``), and as the last lines
of stderr: the two places of which the driver's record of a run that is not
correct keeps the end.

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``drivers/<driver>.py``, ``e2e_metrics/<metric>.py`` and
``layer_metrics/<metric>.py``.  ``benchmark/README.md`` says how to add one.

Off a ``tpu`` it refuses: exit 2 and no line.  When the caller set
``JAX_PLATFORMS`` (a rehearsal) the same path runs at the tiny sizes the
configuration and traffic files give under ``rehearsal*``, NOTHING is printed
on stdout, the result line goes to stderr without any metric value (before
the compared numbers), and the exit code is 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse
import contextlib
import importlib.util
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_REFUSED, EXIT_REHEARSAL, EXIT_FAILED = 2, 3, 1


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module; FileNotFoundError if absent."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> dict:
    """The cell's entry with its configuration and traffic files loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": load_json(ROOT, cfg_entry["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
    }


def metrics_of(spec: dict, workload: str, section: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports: an
    entry without ``workloads`` is every cell's."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


class CompileCounter:
    """Counts XLA compilations (a load from the persistent cache counts too)
    through jax's own monitoring event, so a shape that was not warmed shows
    as a compile inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.compiles += 1


class Tracer:
    """The profiler around a few seconds of steady window, driven from the
    thread that runs the window: ``poll()`` between calls starts it
    ``delay_s`` after the window opened and stops it ``seconds`` later."""

    def __init__(self, on: bool, seconds: float, out_dir: str,
                 delay_s: float = 1.0):
        self.on, self.seconds, self.dir, self.delay_s = on, seconds, out_dir, delay_s
        self.t_open = self.t_start = self.t_stop = None
        self._ann = None

    def open(self, t_window: float):
        self.t_open = t_window

    def poll(self):
        if not self.on or self.t_stop is not None:
            return
        import jax

        now = time.monotonic()
        if self.t_start is None:
            if now - self.t_open >= self.delay_s:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self._ann = jax.profiler.TraceAnnotation("bench.trace_window")
                self._ann.__enter__()
                self.t_start = time.monotonic()
        elif now - self.t_start >= self.seconds:
            self.close()

    def close(self):
        if self.on and self.t_start is not None and self.t_stop is None:
            import jax

            self._ann.__exit__(None, None, None)
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()

    def span(self, name: str):
        """A host span on the profiler's clock (and nothing when off)."""
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def device_record(devs, tracer_summary=None) -> dict:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:  # a backend without memory stats reports 0
            pass
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if tracer_summary:
        rec["busy_s"] = tracer_summary["busy_s"]
        rec["window_s"] = tracer_summary["window_s"]
    return rec


def open_backend(chips: int):
    """Import the program and jax, place the compile cache, find the devices.
    Returns ``(devices, on_chip)`` or an exit code: off a tpu only
    a caller that set ``JAX_PLATFORMS`` (a rehearsal) goes on."""
    explicit = bool(os.environ.get("JAX_PLATFORMS"))
    sys.path.insert(0, ROOT)
    try:
        import jax

        from blockchain_simulator_tpu.utils import aotcache
    except ImportError as e:
        print(f"run.py: the program is not beside the benchmark: {e}",
              file=sys.stderr)
        return EXIT_REFUSED
    aotcache.enable_xla_cache()  # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"run.py: no usable backend: {e}", file=sys.stderr)
        return EXIT_REFUSED
    on_chip = devs[0].platform == "tpu"
    if not on_chip and not explicit:
        print(f"run.py: jax found {devs[0].platform!r}, not a tpu: refusing "
              "(set JAX_PLATFORMS to rehearse; a rehearsal prints no value)",
              file=sys.stderr)
        return EXIT_REFUSED
    if on_chip and len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, jax found {len(devs)}",
              file=sys.stderr)
        return EXIT_REFUSED
    devs = devs[:chips] if on_chip else devs[:1]
    if on_chip:
        peaks = load_json(HERE, "peaks.json")["by_device_kind"]
        if devs[0].device_kind not in peaks:
            print(f"run.py: no published peaks for device kind "
                  f"{devs[0].device_kind!r}; add it to benchmark/peaks.json "
                  "with its source", file=sys.stderr)
            return EXIT_FAILED
    return devs, on_chip


def make_ctx(spec: dict, workload: str, seed: int, trace_on: bool,
             on_chip: bool, program_fields: dict | None = None) -> dict:
    """What a driver gets.  Off the chip the configuration's and the traffic
    file's ``rehearsal*`` sizes apply.  ``program_fields`` (the controls of
    ``tests/``) are laid over the fields the PROGRAM runs; the plain
    reference keeps the configuration as stated."""
    got = resolve(spec, workload)
    config, traffic = got["config"], dict(got["traffic"])
    fields = dict(config["fields"])
    if not on_chip:
        fields.update(config.get("rehearsal_fields", {}))
        traffic.update(traffic.get("rehearsal", {}))
        config = {**config, "reference": {
            **config.get("reference", {}),
            **config.get("rehearsal_reference", {})}}
    trace_dir = os.path.join(ROOT, "chiprun_out", "bench_trace", workload)
    if trace_on:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "workload": workload, "seed": seed, "config": config,
        "traffic": traffic, "on_chip": on_chip, "trace_dir": trace_dir,
        "fields": {**fields, **(program_fields or {})},
        "reference_fields": fields,
        "tracer": Tracer(trace_on, float(traffic.get("trace_seconds", 3)),
                         trace_dir, float(traffic.get("trace_delay_s", 1.0))),
        "rng": random.Random(seed),
    }


def drive(ctx: dict, seconds: float, counter: CompileCounter,
          n_devices: int = 1) -> tuple[dict, list[dict]]:
    """Set-up, the window, the checks: returns the run's record (what the
    metric readers read) and the comparisons that decide ``correct``."""
    import checks

    tracer = ctx["tracer"]
    driver = load_module("drivers", ctx["traffic"]["driver"]).Driver(ctx)
    try:
        setup = driver.setup()
        before = counter.compiles
        t_window = time.monotonic()
        tracer.open(t_window)
        window = driver.window(t_window, float(seconds))
        tracer.close()
        compiled = counter.compiles - before
        comparisons = driver.verify(window)
        comparisons.append(checks.exact("compiles_in_window", compiled, 0))
    finally:
        driver.close()
    run = {
        "workload": ctx["workload"], "fields": ctx["fields"],
        "traffic": ctx["traffic"], "seconds": float(seconds),
        "setup_s": t_window - T_PROCESS, "setup": setup,
        "t_window": t_window, "window": window, "trace": None,
    }
    if tracer.on:
        import xplane

        run["trace"] = xplane.summarize(ctx["trace_dir"], n_devices=n_devices)
    return run, comparisons


def run_cell(args) -> int:
    spec = load_json(args.spec)
    cell = resolve(spec, args.workload)["cell"]
    backend = open_backend(cell["chips"])
    if isinstance(backend, int):
        return backend
    devs, on_chip = backend
    trace_on = bool(args.trace)
    out = sys.stdout if on_chip else sys.stderr
    ctx = make_ctx(spec, args.workload, args.seed, trace_on, on_chip)
    run, comparisons = drive(ctx, args.seconds, CompileCounter(), len(devs))
    correct = all(c["ok"] for c in comparisons)
    window = run["window"]
    for k, v in window.get("notes", {}).items():
        print(f"note {k}={v}", file=out)

    section = "per_layer" if trace_on else "end_to_end"
    reader_dir = "layer_metrics" if trace_on else "e2e_metrics"
    metrics = {}
    for m in metrics_of(spec, args.workload, section):
        value = load_module(reader_dir, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {
        "correct": bool(correct), "attempted": window["attempted"],
        "failed": window["failed"], "metrics": metrics,
        "device": device_record(devs, run["trace"]),
    }
    if run["trace"]:
        line["breakdown"] = run["trace"]["breakdown"]
    if not on_chip:
        # a rehearsal: the path ran, nothing was measured, stdout stays empty
        line["metrics"] = {k: {"unit": v["unit"]} for k, v in metrics.items()}
        line["rehearsal"] = True
    # every number compared beside its limit, last in the line and last on
    # stderr: what a record of a run that is not correct keeps
    line["checks"] = [[c["name"], c["value"], c["rule"], c["limit"]]
                      for c in comparisons]
    print(json.dumps(line), flush=True, file=out)
    for c in comparisons:
        print(f"check {c['name']} {c['value']} {c['rule']} {c['limit']}"
              + ("" if c["ok"] else "  FAILED"), file=sys.stderr)
    return 0 if on_chip else EXIT_REHEARSAL


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--selftest", action="store_true")
    p.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help="another BENCHMARK.json (the selftest's dummy cell)")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    if args.selftest:
        import selftest

        return selftest.main(ROOT)
    if not args.workload:
        p.error("--workload is required")
    if args.seconds is None:
        args.seconds = load_json(args.spec)["run_seconds"]
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
