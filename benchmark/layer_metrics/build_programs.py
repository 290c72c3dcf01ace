"""Program builders: programs jax compiled or loaded before the window
(``build.compile`` records of the program's build log; program counter)."""

import build_log


def read(run: dict):
    got = build_log.compiles(run)
    return len(got) if got else None
