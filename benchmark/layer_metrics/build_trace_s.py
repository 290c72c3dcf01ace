"""Program builders: seconds under root ``build.trace`` records before the
window (jax tracing the program to a jaxpr); the program's build log
(program span)."""

import build_log


def read(run: dict):
    return build_log.stage_s(run, "build.trace")
