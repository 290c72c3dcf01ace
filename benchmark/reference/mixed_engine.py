"""The plain reference for the mixed deployment: ``mixed_engine.cpp`` beside
this file, a per-message event-heap simulation of S Raft groups of m nodes
under PBFT over their representatives (its header says what it does and
where it departs from a literal upstream Raft).  Compiled here with ``g++``
into the git-ignored ``benchmark/_build/`` and called through ctypes.

Nothing in this file imports the program under test: a deployment arrives as
the plain field dict of a ``benchmark/configs/*.json`` file, and upstream's
constants (raft-node.cc, pbft-node.cc, blockchain-simulator.cc) are restated
below.  At 256 x 1,024 a run is 83 million heap events, about 9 s.

What it yields are milestones that do not depend on its random stream beyond
a millisecond or two: groups with a leader, when the last group elected,
blocks committed per group, the last block's commit past its leader's
election, global rounds sent and final, the mean time to global finality and
the last global commit.
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
_SRC = _DIR / "mixed_engine.cpp"
_BUILD = _DIR.parent / "_build"

# upstream's constants (SimConfig's defaults restate the same sources)
UPSTREAM = {
    "link_delay_ms": 3,             # blockchain-simulator.cc:24
    "link_rate_mbps": 3.0,          # blockchain-simulator.cc:23
    "model_serialization": True,
    "raft_heartbeat_ms": 50,        # raft-node.cc:80
    "raft_election_lo_ms": 150,     # raft-node.cc:69-72, U[150,300)
    "raft_election_hi_ms": 300,
    "raft_delay_lo": 0,             # raft-node.cc:63-66, U{0,1,2}
    "raft_delay_hi": 3,
    "raft_proposal_delay_ms": 1000,  # raft-node.cc:216
    "raft_max_blocks": 50,          # raft-node.cc:248
    "raft_max_rounds": 50,          # raft-node.cc:361
    "raft_tx_size": 200,            # raft-node.cc:23
    "raft_tx_speed": 2000,          # raft-node.cc:24
    "pbft_block_interval_ms": 50,   # pbft-node.cc:106
    "pbft_max_rounds": 40,          # pbft-node.cc:407
    "pbft_tx_size": 1000,           # pbft-node.cc:104
    "pbft_tx_speed": 1000,          # pbft-node.cc:105
    "pbft_delay_lo": 3,             # pbft-node.cc:66-69, U{3,4,5}
    "pbft_delay_hi": 6,
    "pbft_view_change_num": 1,      # pbft-node.cc:401
    "pbft_view_change_den": 100,
    "pbft_max_slots": 64,
    "mixed_shards": 16,
    "fidelity": "clean",
    "delivery": "edge",
}


class _Cfg(ctypes.Structure):
    # field order is struct Cfg's in mixed_engine.cpp
    _fields_ = [(k, ctypes.c_int32) for k in ("shards", "m", "sim_ms")] + [
        ("seed", ctypes.c_int64)] + [(k, ctypes.c_int32) for k in (
            "raft_lo", "raft_hi", "raft_hb", "raft_elo", "raft_ehi",
            "raft_prop_delay", "raft_max_blocks", "raft_max_rounds",
            "raft_ser", "n_crashed", "n_byzantine", "pbft_lo", "pbft_hi",
            "pbft_interval", "pbft_max_rounds", "pbft_slots", "pbft_vc_num",
            "pbft_vc_den", "pbft_ser", "edge_ties")]


def build() -> pathlib.Path:
    """Compile the engine unless the library of exactly this source exists."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    lib = _BUILD / f"libmixedref-{digest}.so"
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
        _handle.run_mixed.argtypes = [ctypes.POINTER(_Cfg), ctypes.c_char_p,
                                      ctypes.c_int]
        _handle.run_mixed.restype = ctypes.c_int
    return _handle


def _ser_ms(f: dict, nbytes: int) -> int:
    if not f["model_serialization"]:
        return 0
    return int(nbytes * 8 / (f["link_rate_mbps"] * 1e6) * 1000 + 0.999)


def _one_way(lo: int, hi: int, link: int) -> tuple[int, int]:
    """[lo, hi) of a one-way delay in whole ms: the send delay plus the link;
    a message never arrives in the millisecond it was sent."""
    lo, hi = lo + link, hi + link
    if lo < 1:
        lo, hi = 1, max(hi, 2)
    return lo, max(hi, lo + 1)


def run(fields: dict, seed: int, **override) -> dict:
    """One run of a mixed deployment's fields; returns the milestone dict."""
    f = {**UPSTREAM, **fields, **override}
    if (f.get("protocol") != "mixed" or f.get("topology", "full") != "full"
            or f["fidelity"] != "clean"
            or f["delivery"] not in ("stat", "edge")
            or f.get("quorum_rule", "n2") != "n2"):
        raise ValueError("the reference engine here covers the full-mesh "
                         "mixed deployment at clean fidelity")
    faults = f.get("faults") or {}
    if faults.get("drop_prob", 0.0) or faults.get("crash_frac", 0.0):
        raise ValueError("the reference engine takes fault counts only")
    shards = f["mixed_shards"]
    if f["n"] % shards:
        raise ValueError(f"n={f['n']} not divisible into {shards} groups")
    raft_bytes = (f["raft_tx_speed"] * f["raft_heartbeat_ms"] // 1000
                  ) * f["raft_tx_size"]
    pbft_bytes = (f["pbft_tx_speed"] * f["pbft_block_interval_ms"] // 1000
                  ) * f["pbft_tx_size"]
    r_lo, r_hi = _one_way(f["raft_delay_lo"], f["raft_delay_hi"],
                          f["link_delay_ms"])
    p_lo, p_hi = _one_way(f["pbft_delay_lo"], f["pbft_delay_hi"],
                          f["link_delay_ms"])
    c = _Cfg(
        shards=shards, m=f["n"] // shards, sim_ms=f["sim_ms"],
        seed=int(seed) & (2**62 - 1), raft_lo=r_lo, raft_hi=r_hi,
        raft_hb=f["raft_heartbeat_ms"], raft_elo=f["raft_election_lo_ms"],
        raft_ehi=f["raft_election_hi_ms"],
        raft_prop_delay=f["raft_proposal_delay_ms"],
        raft_max_blocks=f["raft_max_blocks"],
        raft_max_rounds=f["raft_max_rounds"], raft_ser=_ser_ms(f, raft_bytes),
        n_crashed=max(int(faults.get("n_crashed", 0)), 0),
        n_byzantine=int(faults.get("n_byzantine", 0)), pbft_lo=p_lo,
        pbft_hi=p_hi, pbft_interval=f["pbft_block_interval_ms"],
        pbft_max_rounds=f["pbft_max_rounds"], pbft_slots=f["pbft_max_slots"],
        pbft_vc_num=f["pbft_view_change_num"],
        pbft_vc_den=f["pbft_view_change_den"], pbft_ser=_ser_ms(f, pbft_bytes),
        edge_ties=int(f["delivery"] == "edge"))
    buf = ctypes.create_string_buffer(4096)
    rc = _lib().run_mixed(ctypes.byref(c), buf, len(buf))
    if rc != 0:
        raise RuntimeError(f"reference engine failed with code {rc}")
    return json.loads(buf.value.decode())
