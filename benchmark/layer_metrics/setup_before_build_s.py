"""Program builders: process start to the first build record (imports,
reaching the chip, the harness's preparation); the program's build log
(program span), ``None`` where the program keeps none."""

import build_log


def read(run: dict):
    return build_log.before_s(run)
