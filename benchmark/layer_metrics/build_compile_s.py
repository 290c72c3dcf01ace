"""Program builders: seconds under root ``build.compile`` records before the
window (XLA's backend compile; on a warm persistent cache, retrieval and
deserialisation); the program's build log (program span)."""

import build_log


def read(run: dict):
    return build_log.stage_s(run, "build.compile")
