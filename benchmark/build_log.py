"""Set-up from the inside: the program's own build log, cut at the window.

``blockchain_simulator_tpu.utils.aotcache.registry.builds()`` is the run's
own process's log of ``build.*`` span records: ``build.factory`` (a registry
miss: host-side construction) and jax's stages ``build.trace`` / ``.lower`` /
``.compile``, each with the program's name, ``build.compile`` with what the
persistent cache did.  The records are on ``time.monotonic()``'s clock
(``telemetry.on_monotonic_clock``), the clock ``run["t_window"]`` is on, so
what lies before the window is set-up and tiles ``setup_s``:

    process start -> first build t0         setup_before_build_s
    first build t0 -> last build t1         holds the four stage sums, and
                                            the first runs between builds
    last build t1 -> window start           setup_after_build_s

Process start is ``t_window - setup_s``.  A stage's sum is over its roots:
a record whose ``parent`` is another build record was opened inside that one
on the same thread (a product traced inside a trace, a lowering rule that
traces) and its time is its parent's.  A program without the log (the
parent of the PR that brought it) gives ``None`` everywhere.
"""

from __future__ import annotations

STAGES = ("build.factory", "build.trace", "build.lower", "build.compile")


def before_window(run: dict):
    """``[(record, t0, t1)]`` of the build records that ended before the
    window opened, oldest first; ``None`` where the program keeps no log."""
    if "_build_log" not in run:
        run["_build_log"] = _read(run["t_window"])
    return run["_build_log"]


def _read(t_window: float):
    try:
        from blockchain_simulator_tpu.utils import aotcache, telemetry

        records = aotcache.registry.builds()
        clock = telemetry.on_monotonic_clock
    except (ImportError, AttributeError):
        return None
    stamped = [(r, *clock(r)) for r in records]
    return sorted((s for s in stamped if s[2] <= t_window),
                  key=lambda s: s[1])


def roots(log, name: str):
    ids = {r["id"] for r, _, _ in log}
    return [s for s in log if s[0]["name"] == name
            and s[0].get("parent") not in ids]


def stage_s(run: dict, name: str):
    """Seconds under the root records of one stage before the window."""
    log = before_window(run)
    if not log:
        return None
    return sum(t1 - t0 for _, t0, t1 in roots(log, name))


def before_s(run: dict):
    log = before_window(run)
    if not log:
        return None
    return log[0][1] - (run["t_window"] - run["setup_s"])


def after_s(run: dict):
    log = before_window(run)
    if not log:
        return None
    return run["t_window"] - max(t1 for _, _, t1 in log)


def compiles(run: dict):
    """The ``build.compile`` records before the window: one per program
    jax compiled or loaded, inner ones included."""
    log = before_window(run)
    if log is None:
        return None
    return [r for r, _, _ in log if r["name"] == "build.compile"]


def overlaps(run: dict):
    """Pairs of root records that ran at the same time: on one thread
    roots never overlap, so each pair is two threads building at once (the
    stage sums may then exceed the time between the first and last
    build)."""
    log = before_window(run) or []
    ids = {r["id"] for r, _, _ in log}
    rts = [s for s in log if s[0].get("parent") not in ids]
    return [(a[0], b[0]) for i, a in enumerate(rts) for b in rts[i + 1:]
            if b[1] < a[2] and a[1] < b[2]]
