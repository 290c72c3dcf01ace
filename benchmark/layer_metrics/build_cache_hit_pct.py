"""Program builders: share of the programs built before the window that the
persistent compile cache had (``cache == hit`` over hit + miss on the
``build.compile`` records; program counter).  Nothing where the cache is
off."""

import build_log


def read(run: dict):
    got = build_log.compiles(run) or []
    n = {c: sum(1 for r in got if r["attrs"].get("cache") == c)
         for c in ("hit", "miss")}
    asked = n["hit"] + n["miss"]
    return 100.0 * n["hit"] / asked if asked else None
