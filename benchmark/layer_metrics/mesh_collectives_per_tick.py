"""Mesh: the collective instructions inside the tick loop of the compiled
sharded program, as on a tick that takes every gate: the program's own
counter (``parallel/shard.collective_counts``, read once in set-up from the
optimized module).  Nothing where the program has no such counter."""


def read(run: dict):
    counts = run["setup"].get("collectives")
    if run["traffic"].get("driver") != "mesh_solo" or not counts:
        return None
    return counts["collectives_per_tick"]
