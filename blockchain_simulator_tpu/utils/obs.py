"""Run manifests: one schema-versioned provenance record per JSON output line.

Every entrypoint that prints a result line (cli, bench.py, parallel/sweep.py,
tools/run_config*.py) routes it through :func:`finalize`, which attaches a
``manifest`` sub-record — config hash, jax/jaxlib versions, backend + device
count, the compile-vs-execution wall split, and rounds/s computed uniformly —
and appends the finalized record to an optional ``runs.jsonl``
(``BLOCKSIM_RUNS_JSONL``).  ``tools/bench_compare.py`` reads that file (plus
the committed ``BENCH_*.json``) into a machine-readable perf trajectory.

Design constraints this module must respect:

- **Never initialize a backend.**  A chip belongs to one process at a
  time: a parent that launches chip children (chip_smoke.py, the fleet
  launcher) must stay off the backend, and the cli's C++-engine path never
  needs one.  Device fields are therefore filled only when a backend is
  *already initialized* in this process or when passed explicitly; package
  versions come from ``importlib.metadata``, which imports nothing.
- **Never mutate a caller's metrics dict into inequality.**  Library code
  (sweeps, runner) returns metrics dicts that tests compare bit-for-bit
  against other runs; only the *printing* layer attaches manifests.
  :func:`record_run` exists for libraries: it appends a finalized COPY to
  ``runs.jsonl`` (when enabled) and leaves the caller's dict untouched.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
import time

OBS_SCHEMA = 1

# Environment switch: when set, every finalized record is appended (one JSON
# line each) to this path.  Unset = no file I/O (the default for tests).
RUNS_ENV = "BLOCKSIM_RUNS_JSONL"

# Size cap for every rolling JSONL this writer appends to (runs.jsonl,
# HEALTH.jsonl via utils/health.py, telemetry span logs): when the file
# exceeds the cap it rotates to ``<path>.1`` (one generation kept) before
# the append, so multi-drill processes never grow a log without bound.
# The default is far above any single drill's output; set the env to a
# small value to exercise rotation (tests do).  0 disables rotation.
LOG_MAX_ENV = "BLOCKSIM_LOG_MAX_BYTES"
LOG_MAX_BYTES_DEFAULT = 64 * 1024 * 1024


def _dist_version(name: str) -> str | None:
    """Installed package version without importing the package."""
    try:
        import importlib.metadata

        return importlib.metadata.version(name)
    except Exception:
        return None


def config_hash(cfg) -> str:
    """Stable 16-hex-digit digest of a SimConfig (or any dataclass): the
    join key between a result line, a trace file, and a runs.jsonl record."""
    if dataclasses.is_dataclass(cfg):
        d = dataclasses.asdict(cfg)
    else:
        d = dict(cfg)
    blob = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def device_info() -> dict | None:
    """``{platform, device_kind, device_count}`` of the backend this process
    ALREADY holds, or None — never triggers a backend init of its own
    (merely importing the package pulls jax in, e.g. on the cli's
    C++-engine path, and a process that has not touched the chip must not
    claim it just to stamp a record)."""
    if "jax" not in sys.modules:
        return None
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return None
        # guarded: a backend exists, so this cannot init one
        devs = sys.modules["jax"].devices()
        return {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "device_count": len(devs)}
    except Exception:  # backend broken: provenance, never a failure mode
        return None


def manifest(cfg=None, backend=None, device_count=None) -> dict:
    """The schema-versioned provenance record.

    Every record names its device: ``platform``, ``device_kind`` and
    ``device_count`` as jax reports them (:func:`device_info` — only when
    this process already holds a backend), with ``backend`` kept as the
    historical alias of ``platform``.  ``backend``/``device_count``
    arguments override for records relayed from another process.
    """
    rec: dict = {
        "obs_schema": OBS_SCHEMA,
        "ts": round(time.time(), 3),
        "jax": _dist_version("jax"),
        "jaxlib": _dist_version("jaxlib"),
    }
    if cfg is not None:
        rec["config_hash"] = config_hash(cfg)
        rec["protocol"] = getattr(cfg, "protocol", None)
        rec["n"] = getattr(cfg, "n", None)
    dev = device_info() or {}
    backend = backend if backend is not None else dev.get("platform")
    if backend is not None:
        rec["backend"] = rec["platform"] = backend
    if dev.get("platform") == backend and "device_kind" in dev:
        rec["device_kind"] = dev["device_kind"]
    if device_count is None:
        device_count = dev.get("device_count")
    if device_count is not None:
        rec["device_count"] = device_count
    try:
        # executable-registry provenance (utils/aotcache.py): hit/miss
        # counters, the last registry key touched, and the persistent cache
        # dir (null when disabled).  aotcache never imports jax at module
        # scope and .manifest() only reads counters, so this is safe from
        # a jax-free parent too.
        from blockchain_simulator_tpu.utils import aotcache

        rec["cache"] = aotcache.registry.manifest()
    except Exception:  # provenance, never a failure mode
        pass
    try:
        # telemetry provenance (utils/telemetry.py): compact counter
        # totals + spans recorded, attached only once the process has
        # actually counted something — a bare sim run's manifest stays
        # the size it always was.  telemetry is pure-stdlib host code
        # (no jax), so this is safe from a jax-free parent.
        from blockchain_simulator_tpu.utils import telemetry

        tel = telemetry.metrics.manifest()
        if tel.get("counters"):
            rec["telemetry"] = tel
    except Exception:  # provenance, never a failure mode
        pass
    return rec


def canonical_json(rec) -> str:
    """THE canonical JSON encoding shared by every content-addressed
    surface (sweep-journal chunk keys and row checksums,
    parallel/journal.py): sorted keys, compact separators, no default
    coercion — a value json can't encode should fail loudly here, not
    checksum differently on the read side after a round trip."""
    return json.dumps(rec, sort_keys=True, separators=(",", ":"))


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile on a sorted copy — THE percentile every
    latency surface shares (serve self-test, tools/serve_bench.py), so the
    gated ``*_p99_ms`` trajectories are computed one way.  No numpy: the
    callers include daemon control paths that must not touch a backend."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx]


def rounds_per_s(rounds, run_s) -> float | None:
    """THE uniform throughput computation: completed consensus rounds over
    the measured execution-only wall (never the compile-inclusive first
    run)."""
    if rounds is None or not run_s or run_s <= 0:
        return None
    return round(rounds / run_s, 2)


def timed_run(sim, key, measure_key=None):
    """Compile-vs-execution wall split.

    Runs ``sim`` twice to ``jax.block_until_ready`` (checked on a TPU v5e to
    scale with the work, KNOWN_ISSUES.md #1): ``sim(key)`` pays compile +
    warmup, then ``sim(measure_key or key)`` measures execution only (the
    artifact scripts warm on one seed and report another).  Returns
    ``(final, compile_plus_first_run_s, run_s)``.
    """
    import jax

    # the caller hands over a sim that runs on ITS backend: waiting for it
    # is this function's job, not a backend init of the manifest's own
    t0 = time.perf_counter()
    jax.block_until_ready(sim(key))  # jaxlint: disable=module-scope-backend-touch
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    final = jax.block_until_ready(  # jaxlint: disable=module-scope-backend-touch
        sim(key if measure_key is None else measure_key))
    run_s = time.perf_counter() - t0
    return final, compile_s, run_s


def _read_jsonl_one(path: str) -> list[dict]:
    out: list[dict] = []
    try:
        f = open(path)
    except OSError:
        return out
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def read_jsonl(path: str) -> list[dict]:
    """Every parseable dict record of a rolling JSONL log, in order — the
    one tolerant reader the rolling logs share (runs.jsonl access-log
    checks in chaos/invariants.py, health verdicts, bench_compare's
    trajectory load).  Torn lines (a crash or a concurrent append
    mid-write) and non-dict records are skipped; a missing file reads as
    empty — log readers never raise.

    The retained rotation generation (``<path>.1``, the writer's
    :func:`rotate_if_over`) is read FIRST so a log that rotated mid-drill
    still reads as one continuous history — without this, a rotation
    would silently sever bench_compare's regression baselines and the
    invariant checkers' access-log coverage."""
    return _read_jsonl_one(path + ".1") + _read_jsonl_one(path)


# rotate_if_over's per-path stat is amortized: the size check runs on the
# first append to a path and then every _ROTATE_EVERY appends — at 64 MiB
# default cap, a between-checks overshoot of a few records is noise, and
# the serving hot path (several span lines per answered request) stops
# paying a stat syscall per line.
_ROTATE_EVERY = 16
_rotate_counts: dict[str, int] = {}


def rotate_if_over(path: str, max_bytes: int | None = None) -> bool:
    """Rotate ``path`` to ``path + ".1"`` when it exceeds the size cap
    (``$BLOCKSIM_LOG_MAX_BYTES``, default 64 MiB; 0 disables).  One
    rotated generation is kept — these are rolling observability logs,
    and every reader (:func:`read_jsonl`, health.latest_verdict, the
    invariant checkers) is already tolerant of a log that begins
    mid-history.  Returns True when a rotation happened; failures are
    swallowed like every other write in this module."""
    if max_bytes is None:
        try:
            max_bytes = int(os.environ.get(LOG_MAX_ENV,
                                           LOG_MAX_BYTES_DEFAULT))
        except ValueError:
            max_bytes = LOG_MAX_BYTES_DEFAULT
    if max_bytes <= 0:
        return False
    try:
        if os.path.getsize(path) <= max_bytes:
            return False
        os.replace(path, path + ".1")
        return True
    except OSError:
        return False


def append_jsonl(record: dict, path: str | None = None) -> None:
    """Append one JSON line; path defaults to $BLOCKSIM_RUNS_JSONL (no-op
    when neither is set).  The shared rolling-log writer — runs.jsonl,
    HEALTH.jsonl and the telemetry span log all come through here — so
    the size-capped rotation (:func:`rotate_if_over`) bounds all of them
    in one place.  Append failures are swallowed: observability must
    never take down the run it observes."""
    path = path or os.environ.get(RUNS_ENV)
    if not path:
        return
    n = _rotate_counts.get(path, 0)
    if n % _ROTATE_EVERY == 0:
        rotate_if_over(path)
    _rotate_counts[path] = n + 1
    try:
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")
    except OSError:
        pass


def finalize(
    record: dict,
    cfg=None,
    compile_s=None,
    run_s=None,
    rounds=None,
    runs_path: str | None = None,
    append: bool = True,
) -> dict:
    """Attach the manifest to ``record`` and (``append=True``) append it to
    the optional runs.jsonl.  Idempotent: a record that already carries a
    manifest is returned untouched and NOT re-appended.  Pass
    ``append=False`` when a library layer (sweep's ``record_run``) already
    logged the run — the printed line still gets its manifest without the
    rolling log double-counting it.  Returns ``record`` so call sites stay
    one-line: ``print(json.dumps(obs.finalize(m, cfg)))``."""
    if "manifest" in record:
        return record
    record["manifest"] = manifest(
        cfg,
        backend=record.get("backend"),
        device_count=record.get("devices"),
    )
    if compile_s is not None:
        record["manifest"]["compile_plus_first_run_s"] = round(compile_s, 3)
    if run_s is not None:
        record["manifest"]["run_s"] = round(run_s, 3)
        rps = rounds_per_s(rounds, run_s)
        if rps is not None:
            record["manifest"]["rounds_per_s"] = rps
    if append:
        append_jsonl(record, runs_path)
    return record


def record_run(metrics: dict, cfg=None, **kw) -> None:
    """Library-side hook: append a finalized COPY of ``metrics`` to the
    optional runs.jsonl without touching the caller's dict (sweep rows are
    compared bit-for-bit against single runs in tests)."""
    if not (kw.get("runs_path") or os.environ.get(RUNS_ENV)):
        return
    finalize(dict(metrics), cfg, **kw)
