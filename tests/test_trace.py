"""utils/trace.py: probe series + event reconstruction + CLI wiring.

The trace series must agree with the end-of-run metrics — the reconstruction
of the reference's per-event NS_LOG timestamps (e.g. the pbft-node.cc:259
commit lines) from device-side data.  run_traced dispatches through
runner.use_round_schedule exactly like run_simulation, so the fast paths
(per-round PBFT, per-heartbeat raft, heartbeat-scheduled mixed) are traced
too — those series carry a "t" virtual-tick axis.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

from blockchain_simulator_tpu import SimConfig, run_simulation
from blockchain_simulator_tpu.utils.trace import (
    events_from_series,
    run_traced,
    to_chrome_trace,
)

CFG = SimConfig(protocol="pbft", n=16, sim_ms=2500)

# round-eligible at small n via the explicit schedule override (stat
# delivery, serialization off so the wave closes inside the 50 ms interval)
CFG_ROUND = SimConfig(protocol="pbft", n=16, sim_ms=2500, delivery="stat",
                      schedule="round", model_serialization=False)


def test_traced_metrics_match_plain_run():
    m_t, series = run_traced(CFG)
    m = run_simulation(CFG)
    assert m_t == m
    assert set(series) == {
        "blocks_committed_max", "commit_events_total", "view_max", "rounds_sent",
    }
    assert all(len(v) == CFG.ticks for v in series.values())


def test_commit_event_ticks_match_slot_commit_ticks():
    from blockchain_simulator_tpu.runner import final_state

    _, series = run_traced(CFG)
    # commit_events_total increments exactly when some node first-finalizes a
    # slot; the per-slot LAST finalization ticks recorded in the state must
    # all appear among those event ticks
    ev = set(events_from_series(series, "commit_events_total").tolist())
    st = final_state(CFG)
    slot_ticks = np.asarray(st.slot_commit_tick)
    for tick in slot_ticks[slot_ticks >= 0]:
        assert int(tick) in ev


def test_rounds_series_is_block_cadence():
    _, series = run_traced(CFG)
    ev = events_from_series(series, "rounds_sent")
    # a block broadcast happens exactly at 50 ms ticks (pbft-node.cc:406)
    assert len(ev) == 40
    assert all(int(t) % CFG.pbft_block_interval_ms == 0 for t in ev)


def test_raft_probe():
    cfg = SimConfig(protocol="raft", n=8, sim_ms=2000)
    m, series = run_traced(cfg)
    assert m["n_leaders"] == int(series["n_leaders"][-1]) == 1
    # leader election visible in the series at the recorded time
    t_elect = int(np.flatnonzero(series["n_leaders"] > 0)[0])
    assert t_elect == int(m["leader_elected_ms"])


def test_paxos_probe():
    cfg = SimConfig(protocol="paxos", n=12, sim_ms=1500)
    m, series = run_traced(cfg)
    assert set(series) == {"executes", "max_ticket", "committed_proposers"}
    assert all(len(v) == cfg.ticks for v in series.values())
    # series endpoint == metrics surface (no faults: every node is alive)
    assert int(series["committed_proposers"][-1]) == m["n_committed_proposers"]
    assert int(series["executes"][-1]) == m["acceptor_executes"]
    # event reconstruction: the first execute lands at the recorded tick
    ev = events_from_series(series, "executes")
    assert int(ev[0]) == int(m["first_execute_ms"])


def test_mixed_probe():
    # edge delivery keeps the mixed sim on the general tick engine (the
    # fast path requires stat delivery), covering the per-tick mixed probe
    cfg = SimConfig(protocol="mixed", n=12, mixed_shards=4, sim_ms=1200)
    m, series = run_traced(cfg)
    assert set(series) == {
        "shards_with_leader", "raft_blocks_total", "global_blocks",
    }
    assert all(len(v) == cfg.ticks for v in series.values())
    assert int(series["shards_with_leader"][-1]) == m["shards_with_leader"]
    # election ramp is visible: shards gain leaders over time, never at t=0
    assert int(series["shards_with_leader"][0]) == 0
    m_plain = run_simulation(cfg)
    assert m == m_plain


def test_round_fast_path_series():
    """run_traced on a round-schedule PBFT config: per-ROUND series whose
    milestones match run_simulation bit-for-bit (same scan, probes only
    read) and the tick engine's distributionally (drop-free counts are
    bit-equal per models/pbft_round.py's contract)."""
    m_r, series = run_traced(CFG_ROUND)
    assert m_r == run_simulation(CFG_ROUND)
    # one sample per round, timestamped at the 50 ms block cadence
    r_last = (CFG_ROUND.ticks - 1) // CFG_ROUND.pbft_block_interval_ms
    assert len(series["t"]) == r_last
    assert all(int(t) % 50 == 0 for t in series["t"])
    # count milestones match the tick engine exactly (drop-free contract)
    m_tick = run_simulation(CFG_ROUND.with_(schedule="tick"))
    assert m_r["blocks_final_all_nodes"] == m_tick["blocks_final_all_nodes"]
    assert m_r["rounds_sent"] == m_tick["rounds_sent"]
    # commit events reconstruct: one increment sample per committed round
    ev = events_from_series(series, "blocks_committed_max")
    assert len(ev) >= m_r["blocks_final_all_nodes"] - 1


def test_round_ineligible_schedule_raises_like_run_simulation():
    # edge delivery is round-ineligible: run_traced must raise the SAME
    # ValueError run_simulation does, not silently run the tick engine
    bad = CFG_ROUND.with_(delivery="edge")
    with pytest.raises(ValueError, match="schedule='round'"):
        run_traced(bad)
    with pytest.raises(ValueError, match="schedule='round'"):
        run_simulation(bad)


def test_raft_hb_traced_series():
    cfg = SimConfig(protocol="raft", n=8, sim_ms=2000, delivery="stat",
                    schedule="round")
    m_t, series = run_traced(cfg)
    assert m_t == run_simulation(cfg)
    # per-heartbeat samples on the 50 ms cadence, monotone block counter
    # ending at the metrics surface
    assert set(series) == {"blocks", "rounds", "acks_in_window", "stopped",
                           "t"}
    assert int(series["blocks"][-1]) == m_t["blocks"]
    assert np.all(np.diff(series["blocks"]) >= 0)
    assert np.all(np.diff(series["t"]) == cfg.raft_heartbeat_ms)


def test_to_chrome_trace(tmp_path):
    _, series = run_traced(CFG_ROUND)
    path = tmp_path / "trace.json"
    out = to_chrome_trace(series, path, name="pbft-round")
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == out["events"]
    # >= 1 instant event per committed block on the commit counter track
    commits = [e for e in doc["traceEvents"]
               if e.get("ph") == "i" and e["name"] == "blocks_committed_max"]
    m_r = run_simulation(CFG_ROUND)
    assert len(commits) >= m_r["blocks_final_all_nodes"] - 1
    assert out["instants"] >= len(commits)
    # instant timestamps ride the virtual-tick axis (1 tick = 1000 us)
    assert all(e["ts"] % 1000 == 0 for e in commits)


def test_cli_trace(tmp_path):
    out = tmp_path / "series.npz"
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "blockchain_simulator_tpu", "--protocol", "pbft",
         "--n", "8", "--sim-ms", "1200", "--trace", str(out)],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    m = json.loads(proc.stdout.strip().splitlines()[-1])
    assert m["trace_file"] == str(out)
    data = np.load(out)
    assert len(data["rounds_sent"]) == 1200


def test_cli_trace_multi_seed_writes_per_seed_files(tmp_path, capsys):
    # --trace with --seeds: one FILE.<seed>.npz + one JSON line per seed
    from blockchain_simulator_tpu.cli import main

    out = tmp_path / "series.npz"
    rc = main(["--protocol", "pbft", "--n", "8", "--sim-ms", "600",
               "--trace", str(out), "--seeds", "3", "4"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    for seed, line in zip([3, 4], lines):
        m = json.loads(line)
        assert m["seed"] == seed
        path = tmp_path / f"series.{seed}.npz"
        assert m["trace_file"] == str(path)
        assert len(np.load(path)["rounds_sent"]) == 600
        # every CLI line carries the obs manifest (utils/obs.py)
        assert m["manifest"]["obs_schema"] == 1
        assert m["manifest"]["config_hash"]


def test_cli_trace_validation_exit_codes(capsys):
    from blockchain_simulator_tpu.cli import main

    # cpp-only fidelity flag on the --trace branch: clean message + exit 2
    assert main(["--protocol", "pbft", "--echo-back", "--trace", "x.npz"]) == 2
    # ineligible explicit schedule='round' fails BEFORE compiling, exit 2
    assert main(["--protocol", "pbft", "--schedule", "round",
                 "--trace", "x.npz"]) == 2
    err = capsys.readouterr().err
    assert "schedule='round'" in err
    # --profile stays single-seed
    assert main(["--protocol", "pbft", "--profile", "logs",
                 "--seeds", "0", "1"]) == 2


def test_profile_run(tmp_path):
    from blockchain_simulator_tpu.utils.trace import profile_run

    m = profile_run(CFG.with_(sim_ms=600), str(tmp_path))
    assert m["profiled_run_s"] > 0
    assert any(tmp_path.iterdir())  # a capture landed


def test_kregular_trace_regression():
    """The kregular overlay rides the tick arm (tables are trace
    constants): per-tick series, metrics identical to the untraced run."""
    cfg = SimConfig(protocol="pbft", n=12, sim_ms=400, topology="kregular",
                    degree=10, fidelity="clean")
    m_t, series = run_traced(cfg)
    assert m_t == run_simulation(cfg)
    assert "t" not in series  # tick arm: the sample index IS the tick
    assert all(v.shape == (cfg.ticks,) for v in series.values())


def test_committee_trace_stacked_series(tmp_path):
    """ISSUE 17 satellite: --trace no longer refuses committee — stacked
    [C, ticks] series, one lane per committee, metrics bit-identical to
    the untraced outer aggregate, per-committee chrome-trace tracks."""
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=400, topology="committee",
                    committees=2)
    m_t, series = run_traced(cfg)
    assert m_t == run_simulation(cfg)
    inner_ticks = series["t"].shape[0]
    for k, v in series.items():
        if k == "t":
            continue
        assert v.shape == (2, inner_ticks), k
    # chrome export: one counter track per (field, committee) lane
    out = to_chrome_trace(series, tmp_path / "comm.json", name="pbft-comm")
    doc = json.loads((tmp_path / "comm.json").read_text())
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "thread_name"}
    assert any(name.endswith("/c0") for name in lanes)
    assert any(name.endswith("/c1") for name in lanes)
    # per-committee commit instants exist (committee 0 finalizes blocks)
    assert out["instants"] > 0


def test_cli_trace_committee(tmp_path):
    """The CLI --trace path on a committee config writes the stacked npz
    (the round-18 refusal is gone)."""
    from blockchain_simulator_tpu.cli import main

    out = tmp_path / "comm.npz"
    rc = main(["--protocol", "pbft", "--n", "8", "--sim-ms", "300",
               "--topology", "committee", "--committees", "2",
               "--trace", str(out)])
    assert rc == 0
    data = np.load(out)
    stacked = [k for k in data.files if k != "t" and data[k].ndim == 2]
    assert stacked and all(data[k].shape[0] == 2 for k in stacked)
