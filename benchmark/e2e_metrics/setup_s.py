"""Process start to the first instant of the window: reach the chip, build,
compile or load from the cache, warm every shape (host clock)."""


def read(run: dict):
    return run["setup_s"]
