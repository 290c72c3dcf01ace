"""Program builders: seconds under root ``build.lower`` records before the
window (jaxpr to MLIR module, the traces of lowering rules included); the
program's build log (program span)."""

import build_log


def read(run: dict):
    return build_log.stage_s(run, "build.lower")
