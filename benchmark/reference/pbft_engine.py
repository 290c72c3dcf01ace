"""The plain reference: a per-message event-heap PBFT simulator in C++.

``engine.cpp`` beside this file is a copy of the repo's serial discrete-event
engine (every PREPARE and COMMIT is one heap event; no tensors, no batching,
its own ``std::mt19937_64`` stream).  It is compiled here with ``g++`` into
the git-ignored ``benchmark/_build/`` and called through ctypes.  Nothing in
this file imports the program under test: a deployment arrives as the plain
field dict of a ``benchmark/configs/*.json`` file, and upstream's constants
(pbft-node.cc, blockchain-simulator.cc) are restated below.

The engine's cost is O(N^2) heap events per consensus round, so it runs a
deployment's *fields* at a node count the host can afford (``reference.n`` in
the configuration file).  What it yields are milestones that do not depend on
its random stream: rounds sent, blocks final on all nodes, the commit time of
the last block, the mean time to finality.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import pathlib
import subprocess
import tempfile

_DIR = pathlib.Path(__file__).resolve().parent
_SRC = _DIR / "engine.cpp"
_BUILD = _DIR.parent / "_build"

# upstream's constants (SimConfig's defaults restate the same sources)
UPSTREAM = {
    "link_delay_ms": 3,            # blockchain-simulator.cc:24
    "link_rate_mbps": 3.0,         # blockchain-simulator.cc:23
    "model_serialization": True,
    "pbft_block_interval_ms": 50,  # pbft-node.cc:106
    "pbft_max_rounds": 40,         # pbft-node.cc:407
    "pbft_tx_size": 1000,          # pbft-node.cc:104
    "pbft_tx_speed": 1000,         # pbft-node.cc:105
    "pbft_delay_lo": 3,            # pbft-node.cc:66-69, U{3,4,5}
    "pbft_delay_hi": 6,
    "pbft_view_change_num": 1,     # pbft-node.cc:401
    "pbft_view_change_den": 100,
    "pbft_max_slots": 64,
    "fidelity": "clean",
}


class _Cfg(ctypes.Structure):
    # field order is struct SimCfg's in engine.cpp
    _fields_ = [(k, ctypes.c_int32) for k in ("protocol", "n", "sim_ms")] + [
        ("seed", ctypes.c_int64)] + [(k, ctypes.c_int32) for k in (
            "fidelity", "delay_lo", "delay_hi", "pbft_interval",
            "pbft_max_rounds", "pbft_slots", "pbft_vc_num", "pbft_vc_den",
            "raft_hb", "raft_elo", "raft_ehi", "raft_prop_delay",
            "raft_max_blocks", "raft_max_rounds", "paxos_p",
            "paxos_max_ticket", "paxos_timeout", "n_crashed",
            "n_byzantine")] + [("drop_prob", ctypes.c_double)] + [
        (k, ctypes.c_int32) for k in (
            "ser_pbft", "ser_raft", "queued_links", "link_prop", "echo",
            "paxos_client_node", "paxos_client_ms")]


def build() -> pathlib.Path:
    """Compile the engine unless the library of exactly this source exists."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    lib = _BUILD / f"libpbftref-{digest}.so"
    if not lib.exists():
        _BUILD.mkdir(exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD)
        os.close(fd)
        try:
            proc = subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
                 str(_SRC)], capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(f"g++ failed ({proc.returncode}):\n"
                                   f"{proc.stderr}")
            os.chmod(tmp, 0o755)
            os.replace(tmp, lib)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return lib


_handle = None


def _lib():
    global _handle
    if _handle is None:
        _handle = ctypes.CDLL(str(build()))
        _handle.run_sim.argtypes = [ctypes.POINTER(_Cfg), ctypes.c_char_p,
                                    ctypes.c_int]
        _handle.run_sim.restype = ctypes.c_int
    return _handle


def run(fields: dict, seed: int, **override) -> dict:
    """One full-mesh PBFT run of a deployment's fields; returns the
    engine's milestone dict."""
    f = {**UPSTREAM, **fields, **override}
    if f.get("protocol", "pbft") != "pbft" or f.get("topology", "full") != "full":
        raise ValueError("the reference engine here covers full-mesh PBFT")
    ser = 0
    if f["model_serialization"]:
        block_bytes = (f["pbft_tx_speed"] * f["pbft_block_interval_ms"]
                       // 1000) * f["pbft_tx_size"]
        ser = int(block_bytes * 8 / (f["link_rate_mbps"] * 1e6) * 1000 + 0.999)
    faults = f.get("faults") or {}
    c = _Cfg(
        protocol=0, n=f["n"], sim_ms=f["sim_ms"], seed=int(seed) & (2**62 - 1),
        fidelity=1 if f["fidelity"] == "clean" else 0,
        delay_lo=f["pbft_delay_lo"] + f["link_delay_ms"],
        delay_hi=f["pbft_delay_hi"] + f["link_delay_ms"],
        pbft_interval=f["pbft_block_interval_ms"],
        pbft_max_rounds=f["pbft_max_rounds"], pbft_slots=f["pbft_max_slots"],
        pbft_vc_num=f["pbft_view_change_num"],
        pbft_vc_den=f["pbft_view_change_den"],
        n_crashed=max(int(faults.get("n_crashed", 0)), 0),
        n_byzantine=int(faults.get("n_byzantine", 0)), drop_prob=0.0,
        ser_pbft=ser, link_prop=f["link_delay_ms"])
    buf = ctypes.create_string_buffer(4096)
    rc = _lib().run_sim(ctypes.byref(c), buf, len(buf))
    if rc != 0:
        raise RuntimeError(f"reference engine failed with code {rc}")
    return json.loads(buf.value.decode())
