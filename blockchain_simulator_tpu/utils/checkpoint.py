"""Checkpoint / resume.

The reference has no checkpointing of any kind — simulation state dies with
the process (SURVEY.md §5).  Here the entire simulation is one pytree
(protocol state + future-inbox ring buffers) plus the tick counter, so a
checkpoint is a flat ``np.savez`` archive of the leaves with the config
embedded as JSON.  Because every random draw is a pure function of
``(seed, tick, channel)`` (utils/prng.py), resuming from a checkpoint
reproduces the uninterrupted run *bit-exactly* — tested in
tests/test_checkpoint.py.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib

import jax
import numpy as np

from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig


def config_to_json(cfg: SimConfig) -> str:
    return json.dumps(dataclasses.asdict(cfg))


def config_from_json(s: str) -> SimConfig:
    d = json.loads(s)
    d["faults"] = FaultConfig(**d["faults"])
    return SimConfig(**d)


def save_checkpoint(path, cfg: SimConfig, state, bufs, tick: int,
                    dyn_counts=None) -> None:
    """Write one checkpoint: config + tick + all state/buffer leaves.

    ``dyn_counts`` — the traced ``(n_crashed, n_byzantine)`` fault
    operands of a dynamic-fault-operand run (runner.run_dyn_checkpointed):
    stored alongside state/bufs so a resumed run re-derives the exact
    masks (models/base.dyn_fault_masks) the crashed run was tracing.
    ``None`` (the static path) writes no ``__dyn__`` entry — archives
    stay readable both ways."""
    arrays = {}
    for prefix, tree in (("s", state), ("b", bufs)):
        for i, leaf in enumerate(jax.tree.leaves(tree)):
            arrays[f"{prefix}{i}"] = np.asarray(leaf)
    if dyn_counts is not None:
        nc, nb = dyn_counts
        arrays["__dyn__"] = np.asarray([int(nc), int(nb)], dtype=np.int32)
    # content-first atomicity (the WAL/journal rule): write the archive to
    # a sibling tmp, fsync, then os.replace — a kill mid-save can never
    # leave a torn ckpt_*.npz for the resume glob to trip over (the tmp
    # name does not match the glob).  This is load-bearing for the sweep
    # supervisor's re-kill story (runner.run_dyn_checkpointed resume=True
    # trusts the newest archive).
    path = str(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(
                f,
                __cfg__=np.frombuffer(config_to_json(cfg).encode(),
                                      dtype=np.uint8),
                __tick__=np.int64(tick),
                **arrays,
            )
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_checkpoint(path):
    """Read a checkpoint back: ``(cfg, state, bufs, tick)``.

    The pytree structure is rebuilt from the protocol's ``init`` (via
    ``eval_shape`` — no device work), then filled with the stored leaves.
    """
    from blockchain_simulator_tpu.models.base import get_protocol

    path = pathlib.Path(path)
    z = np.load(path)
    cfg = config_from_json(bytes(z["__cfg__"]).decode())
    tick = int(z["__tick__"])
    proto = get_protocol(cfg.protocol)
    s0, b0 = jax.eval_shape(
        lambda: proto.init(cfg, jax.random.key(0))
    )
    for prefix, tree, what in (("s", s0, "state"), ("b", b0, "buffer")):
        stored = sum(k[:1] == prefix and k[1:].isdigit() for k in z.files)
        if stored != len(jax.tree.leaves(tree)):
            # e.g. an archive from before PbftBufs held its due bits: its
            # rings cannot be resumed by a program that skips quiet ticks
            raise ValueError(
                f"{path}: {stored} {what} leaves stored, this program's "
                f"{cfg.protocol} init has {len(jax.tree.leaves(tree))}: the "
                "checkpoint was written by another version of the engine")
    state = jax.tree.unflatten(
        jax.tree.structure(s0),
        [jax.numpy.asarray(z[f"s{i}"]) for i in range(len(jax.tree.leaves(s0)))],
    )
    bufs = jax.tree.unflatten(
        jax.tree.structure(b0),
        [jax.numpy.asarray(z[f"b{i}"]) for i in range(len(jax.tree.leaves(b0)))],
    )
    return cfg, state, bufs, tick


def load_dyn_counts(path):
    """The stored ``(n_crashed, n_byzantine)`` dynamic-fault operands of a
    checkpoint, or ``None`` for a static-path archive (pre-dyn
    checkpoints have no ``__dyn__`` entry — tolerated, not an error)."""
    z = np.load(pathlib.Path(path))
    if "__dyn__" not in z:
        return None
    d = np.asarray(z["__dyn__"])
    return int(d[0]), int(d[1])
