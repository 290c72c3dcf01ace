"""Mesh: the share of one chip's published interconnect peak
(``peaks.json``'s ``ici_bits_per_s``) that the flood's cross-chip max reaches:
the bits one chip moves for it in the trace over the device time of its
collectives (device trace).  The sharded flood reduces across chips in one of
two forms, and the reader counts whichever ran: an all-gather of the shards'
packets of senders, each chip then taking the max into its own rows
((shards - 1) / shards of the gathered bytes, which the program counts from
its compiled module: ``setup.collectives.flood_allgather_bytes``), or, on a
tick with more senders than a packet holds, a ring all-reduce of the scatter
target in the global row space (2 x (shards - 1) / shards x operand bytes by
``mesh_trace``'s count functions).  The time includes the wait for the
slowest chip to arrive, so the share reads low where the chips are skewed,
and a packet exchange of half a megabyte is bound by latency, not bandwidth;
it cannot pass 100."""

import json
import os

import mesh_trace


def read(run: dict):
    t = mesh_trace.of_run(run)
    if not t:
        return None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    return mesh_trace.allreduce_ici_pct(run, peaks[kind]["ici_bits_per_s"])
