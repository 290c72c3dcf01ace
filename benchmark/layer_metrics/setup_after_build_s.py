"""Program builders: the end of the last build record before the window to
the window's start (warm-up runs, a second prewarm pass); the program's build
log (program span), ``None`` where the program keeps none."""

import build_log


def read(run: dict):
    return build_log.after_s(run)
