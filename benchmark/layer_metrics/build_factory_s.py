"""Program builders: seconds under root ``build.factory`` records before the
window (registry misses: host-side construction before jax sees anything);
the program's build log (program span)."""

import build_log


def read(run: dict):
    return build_log.stage_s(run, "build.factory")
