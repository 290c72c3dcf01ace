"""C++ CPU reference engine bindings.

The engine (``engine.cpp``) is the framework's native, serial, per-message
discrete-event simulator — the self-contained replacement for the ns-3
dependency the upstream reference schedules into (SURVEY.md §7 L6).  It is
compiled on demand with ``g++ -O2 -shared -fPIC`` (cached next to the source
under a name that carries a hash of the source's CONTENTS — the library is
git-ignored but travels with a copied working tree, and mtimes do not
survive a copy) and called through ctypes with a flat config
struct; results come back as a JSON metrics string with the same keys as the
JAX backends' ``metrics()`` dicts, so differential tests compare them
directly.
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

_PROTOCOLS = {"pbft": 0, "raft": 1, "paxos": 2}


class _CppCfg(ctypes.Structure):
    # field order must match struct SimCfg in engine.cpp
    _fields_ = [
        ("protocol", ctypes.c_int32),
        ("n", ctypes.c_int32),
        ("sim_ms", ctypes.c_int32),
        ("seed", ctypes.c_int64),
        ("fidelity", ctypes.c_int32),
        ("delay_lo", ctypes.c_int32),
        ("delay_hi", ctypes.c_int32),
        ("pbft_interval", ctypes.c_int32),
        ("pbft_max_rounds", ctypes.c_int32),
        ("pbft_slots", ctypes.c_int32),
        ("pbft_vc_num", ctypes.c_int32),
        ("pbft_vc_den", ctypes.c_int32),
        ("raft_hb", ctypes.c_int32),
        ("raft_elo", ctypes.c_int32),
        ("raft_ehi", ctypes.c_int32),
        ("raft_prop_delay", ctypes.c_int32),
        ("raft_max_blocks", ctypes.c_int32),
        ("raft_max_rounds", ctypes.c_int32),
        ("paxos_p", ctypes.c_int32),
        ("paxos_max_ticket", ctypes.c_int32),
        ("paxos_timeout", ctypes.c_int32),
        ("n_crashed", ctypes.c_int32),
        ("n_byzantine", ctypes.c_int32),
        ("drop_prob", ctypes.c_double),
        ("ser_pbft", ctypes.c_int32),
        ("ser_raft", ctypes.c_int32),
        ("queued_links", ctypes.c_int32),
        ("link_prop", ctypes.c_int32),
        ("echo", ctypes.c_int32),
        ("paxos_client_node", ctypes.c_int32),
        ("paxos_client_ms", ctypes.c_int32),
    ]


def build(force: bool = False) -> pathlib.Path:
    """Compile the engine unless the library built from exactly this
    source is already there; returns the .so path."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    lib = _DIR / f"_libengine-{digest}.so"
    if force or not lib.exists():
        # compile to a temp file and os.replace() so concurrent builders
        # (parallel pytest workers, two CLI invocations) never load a
        # partially written .so — replace is atomic within one directory
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
        os.close(fd)
        try:
            proc = subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                 "-o", tmp, str(_SRC)],
                capture_output=True,
                text=True,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"engine compilation failed (g++ exit {proc.returncode}):\n"
                    f"{proc.stderr}"
                )
            os.chmod(tmp, 0o755)  # mkstemp creates 0600; keep the .so loadable
            os.replace(tmp, lib)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        for old in _DIR.glob("_libengine*.so"):  # libraries of other sources
            if old != lib:
                old.unlink(missing_ok=True)
    return lib


_lib_handle = None


def _lib():
    global _lib_handle
    if _lib_handle is None:
        handle = ctypes.CDLL(str(build()))
        handle.run_sim.argtypes = [
            ctypes.POINTER(_CppCfg), ctypes.c_char_p, ctypes.c_int
        ]
        handle.run_sim.restype = ctypes.c_int
        _lib_handle = handle
    return _lib_handle


def cpp_config(cfg, seed: int | None = None) -> _CppCfg:
    """Map a ``SimConfig`` onto the engine's flat config struct."""
    if cfg.protocol not in _PROTOCOLS:
        raise ValueError(
            f"the C++ engine implements {sorted(_PROTOCOLS)}; "
            f"protocol {cfg.protocol!r} is jax-engine only"
        )
    if cfg.topology != "full":
        raise ValueError(
            "the C++ engine simulates the full mesh only; "
            f"topology {cfg.topology!r} is jax-engine only"
        )
    if cfg.quorum_rule != "n2":
        raise ValueError(
            "the C++ engine implements the reference's n2 majority counting "
            f"only; quorum_rule {cfg.quorum_rule!r} is jax-engine only"
        )
    if cfg.faults.byz_forge:
        raise ValueError(
            "the C++ engine does not implement the byz_forge attack; "
            "it is jax-engine only"
        )
    lo, hi = cfg.one_way_range()
    if cfg.protocol == "paxos" and cfg.fidelity == "clean":
        # mirror paxos.init's clean-fidelity invariant (models/paxos.py:144-157):
        # the engine's temporal-separation safety argument requires stale
        # same-type replies to drain before a retry window opens
        _, rt_hi = cfg.roundtrip_range()
        if cfg.paxos_retry_timeout_ms < rt_hi:
            raise ValueError(
                f"paxos_retry_timeout_ms={cfg.paxos_retry_timeout_ms} must be "
                f">= the max reply horizon ({rt_hi} ms): clean-fidelity "
                "correctness relies on abandoned windows draining before retry"
            )
    return _CppCfg(
        protocol=_PROTOCOLS[cfg.protocol],
        n=cfg.n,
        sim_ms=cfg.sim_ms,
        seed=cfg.seed if seed is None else seed,
        fidelity=1 if cfg.fidelity == "clean" else 0,
        delay_lo=lo,
        delay_hi=hi,
        pbft_interval=cfg.pbft_block_interval_ms,
        pbft_max_rounds=cfg.pbft_max_rounds,
        pbft_slots=cfg.pbft_max_slots,
        pbft_vc_num=cfg.pbft_view_change_num,
        pbft_vc_den=cfg.pbft_view_change_den,
        raft_hb=cfg.raft_heartbeat_ms,
        raft_elo=cfg.raft_election_lo_ms,
        raft_ehi=cfg.raft_election_hi_ms,
        raft_prop_delay=cfg.raft_proposal_delay_ms,
        raft_max_blocks=cfg.raft_max_blocks,
        raft_max_rounds=cfg.raft_max_rounds,
        paxos_p=cfg.paxos_n_proposers,
        paxos_max_ticket=cfg.paxos_max_ticket,
        paxos_timeout=cfg.paxos_retry_timeout_ms,
        n_crashed=cfg.faults.resolved_n_crashed(cfg.n),
        n_byzantine=cfg.faults.n_byzantine,
        drop_prob=cfg.faults.drop_prob,
        ser_pbft=cfg.serialization_ticks(cfg.pbft_block_bytes),
        ser_raft=cfg.serialization_ticks(cfg.raft_block_bytes),
        echo=1 if cfg.echo_back else 0,
        paxos_client_node=cfg.paxos_client_node,
        paxos_client_ms=cfg.paxos_client_ms,
        queued_links=1 if cfg.queued_links else 0,
        link_prop=cfg.link_delay_ms,
    )


def run_cpp(cfg, seed: int | None = None) -> dict:
    """Run one simulation on the C++ engine; returns the metrics dict
    (same keys as the matching JAX backend's ``metrics()``)."""
    if cfg.faults.crashes:  # refused in the one place that lists the arms
        from blockchain_simulator_tpu.models import raft

        raft.check_schedule(cfg, engine="cpp")
    if cfg.link_classes:  # likewise (ops/linkclass.check_arms)
        from blockchain_simulator_tpu.ops import linkclass

        linkclass.check_arms(cfg, engine="cpp")
    if cfg.raft_terms:
        raise NotImplementedError(
            "raft_terms is not implemented by the C++ engine (engine.cpp is "
            "upstream's Raft, which has no terms); the per-message reference "
            "with terms is benchmark/reference/raft_terms_engine.py"
        )
    c = cpp_config(cfg, seed)
    buf = ctypes.create_string_buffer(4096)
    rc = _lib().run_sim(ctypes.byref(c), buf, len(buf))
    if rc != 0:
        raise RuntimeError(f"engine run_sim failed with code {rc}")
    return json.loads(buf.value.decode())
