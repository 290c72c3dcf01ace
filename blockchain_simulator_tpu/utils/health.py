"""Backend health verdicts: is the device this process would use alive?

- :func:`probe_backend` — the in-process probe: platform, device kind and
  count, tiny-matmul compile+run latency, scalar readback.  Returns a
  structured verdict dict (``healthy`` or ``sick``); never raises.
- :func:`probe_backend_supervised` — the classifier that can also say
  ``wedged`` (no verdict within ``patience_s``).  A chip belongs to one
  process at a time, so WHERE the probe runs depends on who holds it: a
  process that already holds a non-CPU backend probes in-process (a child
  could never get the chip, and its verdict would always be ``sick``);
  any other caller runs the probe in a child process that is KILLED AND
  REAPED when it overruns — a child left running would keep holding (or
  waiting for) the chip the next process needs.  A would-be ``wedged``
  verdict is retried with jittered exponential backoff (one slow probe
  must not flip the serve admission gate); the record carries the attempt
  count.
- ``python -m blockchain_simulator_tpu.utils.health`` — prints exactly one
  JSON verdict line and appends it to a rolling ``HEALTH.jsonl`` (``--log
  ''`` disables the file).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

VERDICTS = ("healthy", "sick", "wedged")

HEALTH_ENV = "BLOCKSIM_HEALTH_JSONL"


class BackendWedgedError(RuntimeError):
    """The rolling health log's latest verdict says the backend is wedged:
    dispatching would hang on it, so the caller fails fast instead.  Typed
    so the sweep tier
    (parallel/sweep.py ``journal=`` paths and the sweep entrypoints) and
    drills classify the refusal without string-matching.  Carries the
    offending verdict record as ``.verdict``."""

    def __init__(self, verdict: dict):
        self.verdict = dict(verdict)
        super().__init__(
            f"backend wedged per health log (probe_s="
            f"{verdict.get('probe_s')}, ts={verdict.get('ts')}): refusing "
            "to dispatch onto a backend that does not answer; re-probe "
            "with `python -m blockchain_simulator_tpu.utils.health`"
        )


def require_not_wedged(path: str | None = None, max_age_s: float = 3600.0,
                       replica: str | None = None) -> dict | None:
    """Fail fast on a fresh ``wedged`` verdict — the sweep tier's
    admission gate: consulted before dispatch so a multi-hour grid never
    hangs on a backend a probe already classified.

    Reads :func:`latest_verdict` (explicit path, else
    ``$BLOCKSIM_HEALTH_JSONL``; no log = no gate) and raises the typed
    :class:`BackendWedgedError` only when the latest verdict is
    ``wedged`` AND younger than ``max_age_s`` (a stale verdict from hours
    ago says nothing about the backend now — sweeps fail open).  Returns
    the verdict record consulted (or None), so callers can journal the
    provenance."""
    rec = latest_verdict(path, replica=replica)
    if rec is None:
        return None
    if rec.get("verdict") == "wedged":
        ts = rec.get("ts")
        fresh = not (isinstance(ts, (int, float))
                     and time.time() - ts > max_age_s)
        if fresh:
            raise BackendWedgedError(rec)
    return rec


def probe_backend(replica: str | None = None) -> dict:
    """Probe whatever backend jax resolves, in-process: backend init, then
    a jitted 128x128 bf16 matmul whose scalar result is read back.

    Never raises: any failure returns a ``sick`` verdict with the error
    string.  A *hang* cannot be classified from inside the hanging call —
    callers that need the ``wedged`` verdict use
    :func:`probe_backend_supervised`.
    """
    t0 = time.monotonic()
    rec: dict = {"verdict": "sick", "probe_s": None, "backend": None}
    if replica:
        # fleet identity: verdicts are per-PROCESS, so N replicas sharing
        # one rolling HEALTH.jsonl must label their lines or they gate
        # each other's admission (latest_verdict filters on this)
        rec["replica"] = str(replica)
    try:
        import jax
        import jax.numpy as jnp

        # the probe's JOB is the backend init; callers that must not hold
        # the device run it in a supervised child
        devs = jax.devices()  # jaxlint: disable=module-scope-backend-touch
        rec["backend"] = rec["platform"] = devs[0].platform
        rec["device_kind"] = devs[0].device_kind
        rec["device_count"] = len(devs)
        rec["init_s"] = round(time.monotonic() - t0, 2)
        t1 = time.monotonic()
        val = float(
            jax.jit(lambda a: (a @ a).sum())(jnp.ones((128, 128), jnp.bfloat16))  # jaxlint: disable=module-scope-backend-touch
        )
        rec["compile_run_s"] = round(time.monotonic() - t1, 2)
        rec["probe_value"] = val
        rec["verdict"] = "healthy"
    except Exception as e:  # a broken backend is the datum, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
    rec["probe_s"] = round(time.monotonic() - t0, 2)
    return rec


def _holds_accelerator() -> bool:
    """Does THIS process already hold a non-CPU backend?  Then a probe
    child could never get the chip; the probe must run here."""
    from blockchain_simulator_tpu.utils import obs

    dev = obs.device_info()
    return dev is not None and dev["platform"] != "cpu"


def probe_backend_supervised(
    patience_s: float = 120.0,
    env=None,
    attempts: int = 2,
    backoff_s: float = 2.0,
    rng=None,
    replica: str | None = None,
) -> dict:
    """Probe under a deadline; classify a silent probe as ``wedged`` — but
    only after ``attempts`` probes, separated by a jittered exponential
    backoff.

    One slow probe (a cold compile under load, a paging blip) must not
    flip the serving admission gate to paused: a would-be ``wedged``
    verdict is retried ``attempts - 1`` times, sleeping
    ``backoff_s * 2**k * uniform(0.5, 1.5)`` between probes, and only the
    final miss is declared.  ``healthy``/``sick`` verdicts return
    immediately.  The returned record carries ``attempts`` (probes
    actually run) so HEALTH.jsonl shows how hard the verdict was earned.
    ``rng`` (a ``random.random``-like callable) makes the jitter
    injectable for deterministic drills.
    """
    rng = rng if rng is not None else random.random
    in_process = _holds_accelerator()
    rec: dict = {}
    for attempt in range(1, max(1, int(attempts)) + 1):
        rec = (_probe_attempt_inprocess(patience_s) if in_process
               else _probe_attempt_child(patience_s, env))
        rec["attempts"] = attempt
        if rec["verdict"] != "wedged" or attempt >= attempts:
            break
        time.sleep(backoff_s * (2.0 ** (attempt - 1)) * (0.5 + rng()))
    rec["supervised"] = True
    if replica:
        rec["replica"] = str(replica)
    return rec


def _wedged(t0: float, patience_s: float, what: str) -> dict:
    return {
        "verdict": "wedged",
        "probe_s": round(time.monotonic() - t0, 2),
        "backend": None,
        "error": f"no probe verdict within {patience_s:.0f}s; {what}",
    }


def _probe_attempt_inprocess(patience_s: float) -> dict:
    """ONE probe of the backend this process holds, on a worker thread so
    a device that never answers still yields ``wedged`` after
    ``patience_s`` (a thread cannot be killed; it is left blocked)."""
    box: list = []
    t0 = time.monotonic()
    t = threading.Thread(target=lambda: box.append(probe_backend()),
                         daemon=True, name="health-probe")
    t.start()
    t.join(patience_s)
    if not box:
        return _wedged(t0, patience_s, "in-process probe still blocked")
    return box[0]


def _probe_attempt_child(patience_s: float, env=None) -> dict:
    """ONE supervised probe attempt in a child process:
    ``python -m blockchain_simulator_tpu.utils.health --child`` prints one
    JSON line.  A child that overruns ``patience_s`` is killed by process
    group and reaped before the verdict returns — no probe child ever
    outlives its verdict."""
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    # the child must resolve this package even when the caller runs elsewhere
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    child_env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_root, child_env.get("PYTHONPATH")) if p
    )
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "-m", "blockchain_simulator_tpu.utils.health",
         "--child"],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=child_env,
        start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=patience_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        proc.communicate()
        return _wedged(t0, patience_s, "probe child killed and reaped")
    for line in out.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "verdict" in rec:
            return rec
    return {
        "verdict": "sick",
        "probe_s": round(time.monotonic() - t0, 2),
        "backend": None,
        "error": f"probe child exited rc={proc.returncode} "
                 "with no verdict line",
    }


def latest_verdict(path: str | None = None,
                   replica: str | None = None) -> dict | None:
    """Most recent verdict record from a rolling health log (explicit path,
    else ``$BLOCKSIM_HEALTH_JSONL``), or None when no log / no parseable
    verdict line exists.  Read-only and never raises: the scenario server
    (serve/) consults this at startup to decide whether admission opens
    paused — a stale or missing log must default to serving, not crash.

    ``replica`` (a fleet replica id) restricts the read to that replica's
    own lines plus UNLABELED lines (a global probe gates everyone): N
    replicas sharing one HEALTH.jsonl no longer clobber each other's
    admission gating.  Without it, every verdict line counts — the
    single-daemon behavior, unchanged."""
    from blockchain_simulator_tpu.utils import obs

    path = path or os.environ.get(HEALTH_ENV)
    if not path:
        return None
    last = None
    for rec in obs.read_jsonl(path):
        if rec.get("verdict") not in VERDICTS:
            continue
        if replica is not None and rec.get("replica") is not None \
                and str(rec.get("replica")) != str(replica):
            continue
        last = rec
    return last


def append_health(rec: dict, path: str | None = None) -> None:
    """Append one verdict line to the rolling health log.  Path precedence:
    explicit arg, $BLOCKSIM_HEALTH_JSONL, nothing (no-op — resolved here so
    obs.append_jsonl's own $BLOCKSIM_RUNS_JSONL fallback never captures
    health verdicts).  Failures are swallowed — telemetry never takes down
    the caller."""
    from blockchain_simulator_tpu.utils import obs

    path = path or os.environ.get(HEALTH_ENV)
    if path:
        obs.append_jsonl(rec, path)


def main(argv=None) -> int:
    """CLI: print exactly ONE JSON verdict line; exit 0 healthy, 1 sick,
    2 wedged.  Default mode is supervised (the only mode that can report
    ``wedged`` instead of hanging with the backend).  The platform is
    jax's choice; ask for a CPU probe with ``JAX_PLATFORMS=cpu``."""
    p = argparse.ArgumentParser(prog="blockchain_simulator_tpu.utils.health")
    p.add_argument("--child", action="store_true",
                   help="internal: run the in-process probe and print it")
    p.add_argument("--in-process", action="store_true",
                   help="probe this process's backend directly (hangs if "
                        "the backend does; default is a supervised child "
                        "with --patience)")
    p.add_argument("--patience", type=float, default=120.0,
                   help="supervised mode: seconds to wait for the child's "
                        "verdict before it is killed and the backend "
                        "declared wedged")
    p.add_argument("--attempts", type=int, default=2,
                   help="supervised mode: probes (jittered exponential "
                        "backoff between them) before a silent backend is "
                        "declared wedged — one slow probe must not flip "
                        "the serve admission gate")
    p.add_argument("--replica", default=None,
                   help="fleet replica id to label the verdict with — "
                        "replicas sharing one HEALTH.jsonl gate admission "
                        "on their own lines only (serve/fleet.py)")
    p.add_argument("--log", default="HEALTH.jsonl",
                   help="rolling verdict log to append to ('' disables)")
    args = p.parse_args(argv)

    if args.child:
        rec = probe_backend(replica=args.replica)
        print(json.dumps(rec), flush=True)
        return 0 if rec["verdict"] == "healthy" else 1

    if args.in_process:
        rec = probe_backend(replica=args.replica)
    else:
        rec = probe_backend_supervised(patience_s=args.patience,
                                       attempts=args.attempts,
                                       replica=args.replica)
    rec["ts"] = round(time.time(), 3)
    print(json.dumps(rec), flush=True)
    append_health(rec, args.log or None)
    return {"healthy": 0, "sick": 1}.get(rec["verdict"], 2)


if __name__ == "__main__":
    sys.exit(main())
