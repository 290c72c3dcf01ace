"""Chip smoke: drive the main path once on the accelerator and check it.

    python chip_smoke.py            # every leg; the driver's command
    python chip_smoke.py sweep serve  # a subset (the device leg always runs)

The quickest proof that the system still starts on the chip.  Each leg is a
child process through a normal entry point (``python -m
blockchain_simulator_tpu``, ``python -m blockchain_simulator_tpu.serve``; a
``python -c`` child on the public API only where the CLI does not reach the
function), run strictly one after another, each with a deadline, killed by
process group AND reaped before the next starts — a chip belongs to one
process at a time.  This parent is stdlib-only and never imports jax or the
package, so it never holds the chip its children need.

Sizes are the ones users of a 100k-node simulator call real: at n = 100k the
tick engine holds three ``[D=18, N, W=8]`` int32 rings (~57 MB each) on the
device.  Results are checked by the repo's own means: the C++ event-heap
engine (``engine/engine.cpp``) as the independent reference at upstream's
operating point, and the bit-equality pins between differently compiled
programs (round vs tick, vmapped vs solo, kregular vs dense, served vs solo).

Exit code 0 only when every leg passed on platform ``tpu``.  The LAST stdout
line is then the verdict, one JSON object with exactly these keys and the
device as jax reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the stdout line before it is the report (jax/jaxlib/libtpu versions, the
cache directory and its entry counts, per leg ``name``, ``ok``, ``wall_s``,
``compile_s`` and the checked values), also written to
``chiprun_out/chip_smoke/report.json``.  A leg failing or timing out on the
chip exits 1 with ``"ok": false`` on the verdict line and the report on
stderr.  Where jax finds no ``tpu`` — or the package is not beside this
script — it exits non-zero and prints NO stdout line; no flag or environment
variable makes it pass without a chip.
"""

from __future__ import annotations

import json
import os
import select
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
PY = sys.executable
CLI = [PY, "-m", "blockchain_simulator_tpu"]
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUDGET_S = 1150.0  # the whole smoke, compilation included, must end by here

# The sizes users call real.  tests/test_chip_smoke.py drives the same leg
# table at tiny sizes with CPU children.
REAL = {
    "n": 100_000,        # the 100k-node cluster of bench.py's _cfg
    "rounds": 2000,      # round path: 2000 rounds = 100 simulated seconds
    "tick_ms": 2100,     # tick vs round: 40 rounds on the per-tick engine
    "sweep_rounds": 200,
    "exact_n": 256, "exact_ms": 600,
    "kreg_n": 100_000, "kreg_ms": 200,
    "serve_n": 1024, "serve_ms": 600,
}

DEVICE_SRC = """
import importlib.metadata as md, json, jax
from blockchain_simulator_tpu.utils import aotcache
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "device_kind": d[0].device_kind,
                  "count": len(d), "cache_dir": aotcache.enable_xla_cache(),
                  "versions": {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}}))
"""

# solo reference runs: the CLI's own flags -> the CLI's own config -> one
# run_simulation per seed in ONE process (the CLI's --seeds is the batched
# program; looping solo runs is what it does not reach)
SOLO_SRC = """
import json, sys
from blockchain_simulator_tpu import cli, runner
from blockchain_simulator_tpu.utils import aotcache
aotcache.enable_xla_cache()
args = cli.build_parser().parse_args(sys.argv[1:])
cfg = cli.config_from_args(args)
print(json.dumps([runner.run_simulation(cfg, seed=s) for s in args.seeds]))
"""

# four chips from one process: sharded vs single-device metrics, and WHERE
# the node-axis leaves of the sharded final state live
MESH_SRC = """
import json, sys, jax, jax.numpy as jnp
from blockchain_simulator_tpu import cli, runner
from blockchain_simulator_tpu.models.base import canonical_fault_cfg, sim_metrics
from blockchain_simulator_tpu.parallel import shard, sweep
from blockchain_simulator_tpu.parallel.mesh import make_mesh
from blockchain_simulator_tpu.utils import aotcache
aotcache.enable_xla_cache()
cfg = cli.config_from_args(cli.build_parser().parse_args(sys.argv[2:]))
mesh = make_mesh(n_node_shards=int(sys.argv[1]))
key = jax.random.key(cfg.seed)
if cfg.topology == "kregular":
    sim = sweep.sharded_topo_sim_fn(canonical_fault_cfg(cfg), mesh)
    final = sim(key, jnp.int32(cfg.faults.resolved_n_crashed(cfg.n)),
                jnp.int32(cfg.faults.n_byzantine))
else:
    final = shard.make_sharded_sim_fn(cfg, mesh)(key)
final = jax.block_until_ready(final)
spread = sorted({len(x.sharding.device_set) for x in jax.tree.leaves(final)
                 if x.ndim and x.shape[0] == cfg.n})
print(json.dumps({"sharded": sim_metrics(cfg, final), "node_leaf_devices": spread,
                  "single": runner.run_simulation(cfg)}))
"""


class LegFailed(Exception):
    """A leg's check did not hold (or its child failed / overran)."""


class Smoke:
    """One smoke run: the child runner, its deadline, and the leg results."""

    def __init__(self, sizes: dict, platform: str = "tpu", env=None,
                 budget_s: float = BUDGET_S, log_dir: str = LOG_DIR):
        self.sizes = dict(sizes)
        self.platform = platform
        self.env = {**os.environ, **(env or {})}
        # children resolve the package from this checkout wherever we run
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, self.env.get("PYTHONPATH")) if p)
        self.deadline = time.monotonic() + budget_s
        self.log_dir = log_dir
        self.device: dict = {}
        self.legs: list[dict] = []
        self._compile_s: list[float] = []
        self._n_children = 0
        os.makedirs(log_dir, exist_ok=True)

    # ------------------------------------------------------------ children
    def _log(self, tag: str):
        self._n_children += 1
        return open(os.path.join(
            self.log_dir, f"{self._n_children:02d}_{tag}.err"), "wb")

    def _timeout(self, want_s: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 1:
            raise LegFailed("the smoke's overall time budget is spent")
        return min(want_s, left)

    @staticmethod
    def _reap(proc: subprocess.Popen) -> None:
        """Kill the child's whole process group and reap it: nothing a leg
        started may still hold (or wait for) the chip when the next starts."""
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()

    def child(self, tag: str, cmd: list[str], timeout_s: float = 600.0) -> str:
        """Run one child to completion; returns its stdout.  Non-zero exit
        or a missed deadline fails the leg."""
        with self._log(tag) as err:
            proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=err,
                                    cwd=ROOT, env=self.env,
                                    start_new_session=True)
            try:
                out, _ = proc.communicate(timeout=self._timeout(timeout_s))
            except subprocess.TimeoutExpired:
                raise LegFailed(f"{tag}: no exit within its deadline; killed")
            finally:
                self._reap(proc)
        if proc.returncode != 0:
            raise LegFailed(f"{tag}: exit code {proc.returncode} "
                            f"(stderr: {err.name})")
        return out.decode()

    def cli(self, tag: str, flags: list[str], timeout_s: float = 600.0):
        """One CLI run -> its JSON result lines, manifests split off (the
        ``compile_plus_first_run_s`` of a --timing run is booked to the
        leg's compile_s)."""
        rows = [json.loads(ln) for ln in
                self.child(tag, CLI + flags, timeout_s).splitlines()]
        if not rows:
            raise LegFailed(f"{tag}: printed no result line")
        for r in rows:
            man = r.pop("manifest")
            if man.get("platform", self.platform) != self.platform:
                raise LegFailed(f"{tag}: ran on {man.get('platform')!r}, "
                                f"not {self.platform!r}")
            if "compile_plus_first_run_s" in r:  # a --timing run
                self._compile_s.append(r.pop("compile_plus_first_run_s"))
                del r["wallclock_s"]
        return rows

    def solo(self, tag: str, flags: list[str], timeout_s: float = 600.0):
        return json.loads(self.child(tag, [PY, "-c", SOLO_SRC] + flags,
                                     timeout_s))

    # ---------------------------------------------------------------- legs
    def run(self, legs) -> bool:
        """Run ``legs`` (name, fn) in order; every result lands in
        ``self.legs``.  The first leg must be ``device``: nothing else runs
        unless it finds the required platform."""
        for name, fn in legs:
            t0 = time.monotonic()
            self._compile_s = []
            rec: dict = {"name": name, "ok": False}
            try:
                rec["checked"] = fn(self)
                rec["ok"] = "skipped" not in rec["checked"]
                if not rec["ok"]:
                    rec["skipped"] = rec.pop("checked")["skipped"]
            except (LegFailed, KeyError, ValueError, OSError) as e:
                # a child's output that cannot be parsed or lacks a checked
                # key is a failed leg like any other — never a pass
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["wall_s"] = round(time.monotonic() - t0, 1)
            rec["compile_s"] = (round(sum(self._compile_s), 1)
                                if self._compile_s else None)
            self.legs.append(rec)
            print(f"chip_smoke: {json.dumps(rec)}", file=sys.stderr,
                  flush=True)
            if name == "device" and not rec["ok"]:
                return False
        return all(r["ok"] or "skipped" in r for r in self.legs)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


def _r100k(s: dict, rounds: int) -> list[str]:
    """bench.py's ``_cfg(rounds)`` as CLI flags."""
    return ["--protocol", "pbft", "--n", str(s["n"]), "--delivery", "stat",
            "--serialization", "off", "--pbft-rounds", str(rounds),
            "--pbft-max-slots", str(rounds + 8), "--pbft-window", "8"]


def _kreg(s: dict) -> list[str]:
    """The sparse-overlay cluster.  Windowed vote state and serialization
    off as on the full mesh: with the defaults (exact 64-slot table,
    D = 152 rings) the program needs 26 GB of HBM at n = 100k."""
    return ["--protocol", "pbft", "--topology", "kregular", "--n",
            str(s["kreg_n"]), "--degree", "8", "--fidelity", "clean",
            "--serialization", "off", "--pbft-window", "8",
            "--sim-ms", str(s["kreg_ms"])]


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def leg_device(sm: Smoke) -> dict:
    dev = json.loads(sm.child("device", [PY, "-c", DEVICE_SRC], 120))
    # this child compiled nothing: the cache is still as the smoke found it
    dev["cache_entries_before"] = _cache_entries(dev["cache_dir"])
    sm.device = dev
    check(dev["platform"] == sm.platform,
          f"jax found platform {dev['platform']!r}, not {sm.platform!r}: "
          "refusing to run any leg")
    return dev


def leg_parity(sm: Smoke) -> dict:
    """Upstream's operating point (BASELINE.md), chip vs the C++ engine:
    the milestone equalities of tests/test_differential.py."""
    out = {}
    for proto, n, ms in (("pbft", 8, 2500), ("raft", 16, 10_000),
                         ("paxos", 8, 10_000)):
        flags = ["--protocol", proto, "--n", str(n), "--sim-ms", str(ms)]
        mj, = sm.cli(f"parity_{proto}_jax", flags + ["--timing"])
        mc, = sm.cli(f"parity_{proto}_cpp", flags + ["--engine", "cpp"])
        check(mj["agreement_ok"] and mc["agreement_ok"], f"{proto}: agreement")
        if proto == "pbft":
            check(mj["rounds_sent"] == mc["rounds_sent"] == 40
                  and mj["blocks_final_all_nodes"]
                  == mc["blocks_final_all_nodes"] == 40,
                  f"pbft milestones differ: {mj} vs {mc}")
            check(abs(mj["mean_time_to_finality_ms"]
                      - mc["mean_time_to_finality_ms"]) < 6, "pbft ttf")
            out[proto] = {"blocks": 40, "ttf_ms": [
                m["mean_time_to_finality_ms"] for m in (mj, mc)]}
        elif proto == "raft":
            check(mj["n_leaders"] == mc["n_leaders"] == 1
                  and mj["blocks"] == mc["blocks"] > 0
                  and mj["leader_elected_ms"] < 1000
                  and mc["leader_elected_ms"] < 1000
                  and abs(mj["mean_block_interval_ms"]
                          - mc["mean_block_interval_ms"]) < 5,
                  f"raft milestones differ: {mj} vs {mc}")
            out[proto] = {"blocks": mj["blocks"]}
        else:
            check(mj["n_committed_proposers"] >= 1
                  and mc["n_committed_proposers"] >= 1
                  and mj["decided_command"] in (0, 1, 2)
                  and mc["decided_command"] in (0, 1, 2),
                  f"paxos milestones differ: {mj} vs {mc}")
            out[proto] = {"committed": mj["n_committed_proposers"]}
    return out


def leg_round(sm: Smoke) -> dict:
    r = sm.sizes["rounds"]
    m, = sm.cli("pbft_round", _r100k(sm.sizes, r)
                + ["--sim-ms", str(r * 50 + 100), "--timing"])
    check(m["schedule"] == "round", f"schedule resolved to {m['schedule']!r}")
    check(m["blocks_final_all_nodes"] == r and m["agreement_ok"],
          f"round path: {m}")
    return {"n": sm.sizes["n"], "blocks_final_all_nodes": r,
            "agreement_ok": True}


def leg_tick(sm: Smoke) -> dict:
    """The same cluster on the per-tick engine and on the round path:
    drop-free counts are bit-equal (models/pbft_round.py)."""
    base = _r100k(sm.sizes, sm.sizes["rounds"]) + [
        "--sim-ms", str(sm.sizes["tick_ms"]), "--timing"]
    mt, = sm.cli("pbft_tick", base + ["--schedule", "tick"])
    mr, = sm.cli("pbft_tick_round", base + ["--schedule", "round"])
    keys = ("rounds_sent", "blocks_final_all_nodes", "view_changes")
    check(mt["schedule"] == "tick" and mr["schedule"] == "round", "schedule")
    check(all(mt[k] == mr[k] for k in keys) and mt["rounds_sent"] > 0
          and mt["agreement_ok"] and mr["agreement_ok"],
          f"tick {mt} vs round {mr}")
    return {k: mt[k] for k in keys}


def leg_sweep(sm: Smoke) -> dict:
    """``parallel/sweep.run_seed_sweep``: vmapped batch 4 at full n, and
    the batched rows dict-equal to solo runs under the exact sampler."""
    s, r = sm.sizes, sm.sizes["sweep_rounds"]
    seeds = ["--seeds", "0", "1", "2", "3"]
    rows = sm.cli("sweep_batch4", _r100k(s, r)
                  + ["--sim-ms", str(r * 50 + 100)] + seeds)
    check(len(rows) == 4 and all(
        m["blocks_final_all_nodes"] == r and m["agreement_ok"] for m in rows),
        f"batch-4 rows: {rows}")
    exact = ["--protocol", "pbft", "--n", str(s["exact_n"]), "--delivery",
             "stat", "--serialization", "off", "--stat-sampler", "exact",
             "--sim-ms", str(s["exact_ms"])] + seeds
    batched = sm.cli("sweep_exact", exact)
    for m in batched:
        m.pop("schedule")
    check(batched == sm.solo("sweep_exact_solo", exact),
          "exact-sampler sweep rows differ from solo runs")
    check(batched[0]["blocks_final_all_nodes"] > 0, f"idle rows: {batched}")
    return {"batch": 4, "n": s["n"], "rounds": r,
            "exact_rows_equal_solo": True}


def leg_kregular(sm: Smoke) -> dict:
    s = sm.sizes
    m, = sm.cli("kregular", _kreg(s) + ["--timing"], 900)
    # 0 final blocks is by design at degree << quorum (KNOWN_ISSUES #0n)
    check(m["agreement_ok"] and m["rounds_sent"] > 0, f"kregular: {m}")
    pair = ["--protocol", "pbft", "--n", "64", "--sim-ms", "600",
            "--stat-sampler", "exact", "--fidelity", "clean"]
    mk, = sm.cli("kregular64", pair + ["--topology", "kregular",
                                       "--degree", "63"])
    md, = sm.cli("dense64", pair)
    check(mk == md and mk["blocks_final_all_nodes"] > 0,
          f"kregular degree n-1 {mk} differs from dense {md}")
    return {"n": s["kreg_n"], "rounds_sent": m["rounds_sent"],
            "degree63_equals_dense": True}


def leg_serve(sm: Smoke) -> dict:
    """The daemon on the default platform: 8 concurrent requests in two
    bursts, answers equal to solo runs, a real batch formed, no degrade."""
    s = sm.sizes
    req = {"protocol": "pbft", "n": s["serve_n"], "sim_ms": s["serve_ms"],
           "stat_sampler": "exact"}
    answers: list = [None] * 8
    with sm._log("serve") as err:
        proc = subprocess.Popen(
            [PY, "-m", "blockchain_simulator_tpu.serve", "--port", "0",
             "--max-wait-ms", "200", "--timeout-s", "900"],
            stdout=subprocess.PIPE, stderr=err, cwd=ROOT, env=sm.env,
            start_new_session=True)
        try:
            end = time.monotonic() + sm._timeout(300)
            check(bool(select.select([proc.stdout], [], [],
                                     end - time.monotonic())[0]),
                  "daemon printed nothing before its deadline")
            line = proc.stdout.readline().decode()
            check(line.startswith("READY "), f"daemon said {line!r}")
            ready = json.loads(line[len("READY "):])
            check(ready.get("platform") == sm.platform,
                  f"daemon serves on {ready.get('platform')!r}")
            base = f"http://127.0.0.1:{ready['port']}"

            def call(path, obj=None):
                data = None if obj is None else json.dumps(obj).encode()
                req = urllib.request.Request(base + path, data=data)
                with urllib.request.urlopen(
                        req, timeout=sm._timeout(900)) as r:
                    return json.loads(r.read())

            def post(i):
                try:
                    answers[i] = call("/scenario", dict(req, seed=i))
                except Exception as e:  # surfaced by the 8 x 200 check
                    answers[i] = {"error": repr(e)}

            burst_s = []
            for burst in (range(0, 4), range(4, 8)):
                t0 = time.monotonic()
                threads = [threading.Thread(target=post, args=(i,))
                           for i in burst]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                burst_s.append(round(time.monotonic() - t0, 2))
            stats = call("/stats")
            call("/shutdown", {})
            rc = proc.wait(timeout=sm._timeout(120))
        except subprocess.TimeoutExpired:
            raise LegFailed("daemon did not exit after /shutdown")
        finally:
            sm._reap(proc)
    check(all(a.get("code") == 200 for a in answers), f"answers: {answers}")
    check(rc == 0, f"daemon exit code {rc}")
    check(max(a["batch"]["size"] for a in answers) >= 2, "no batch formed")
    check(stats["degraded_batches"] == 0 and stats["errors"] == 0
          and stats["platform"] == sm.platform, f"stats: {stats}")
    solo = sm.solo("serve_solo", [
        "--protocol", "pbft", "--n", str(s["serve_n"]), "--sim-ms",
        str(s["serve_ms"]), "--stat-sampler", "exact",
        "--seeds"] + [str(i) for i in range(8)])
    check([a["metrics"] for a in answers] == solo,
          "served metrics differ from solo runs")
    return {"served": stats["served"], "occupancy": stats["occupancy"],
            "degraded_batches": 0, "errors": 0, "burst_s": burst_s,
            "ready_device": {k: ready[k] for k in
                             ("platform", "device_kind", "device_count")}}


def leg_mesh4(sm: Smoke) -> dict:
    """Node-sharded programs on four real chips, from one process."""
    if sm.device["count"] < 4:
        return {"skipped": f"{sm.device['count']} device"}
    s, out = sm.sizes, {}
    r = s["sweep_rounds"]
    for tag, flags in (
        ("round", _r100k(s, r) + ["--sim-ms", str(r * 50 + 100)]),
        ("tick", _r100k(s, r) + ["--sim-ms", str(s["tick_ms"]),
                                 "--schedule", "tick"]),
        ("kregular", _kreg(s) + ["--stat-sampler", "exact"]),
    ):
        rec = json.loads(sm.child(f"mesh4_{tag}",
                                  [PY, "-c", MESH_SRC, "4"] + flags, 900))
        m4, m1 = rec["sharded"], rec["single"]
        if tag != "kregular":
            # the full-mesh sharded programs fold the shard index into
            # their draws (tests/test_parallel.py): the view-change
            # sequence differs, the VC-invariant milestones must not; the
            # overlay program is bit-equal at any mesh size
            check(abs(m4["mean_time_to_finality_ms"]
                      - m1["mean_time_to_finality_ms"]) < 5, f"{tag}: ttf")
            keys = ("rounds_sent", "blocks_final_all_nodes",
                    "block_num_max", "agreement_ok")
            m4, m1 = ({k: m[k] for k in keys} for m in (m4, m1))
        check(m4 == m1 and m4["agreement_ok"] and m4["rounds_sent"] > 0,
              f"{tag}: 4-shard {m4} vs 1 {m1}")
        check(rec["node_leaf_devices"] == [4],
              f"{tag}: node-axis leaves on {rec['node_leaf_devices']} devices")
        out[tag] = {"equal_to_single": True, "node_leaf_devices": 4}
    return out


LEGS = [
    ("device", leg_device), ("parity", leg_parity),
    ("pbft100k_round", leg_round), ("pbft100k_tick", leg_tick),
    ("sweep", leg_sweep), ("kregular100k", leg_kregular),
    ("serve", leg_serve), ("mesh4", leg_mesh4),
]


def verdict(ok: bool, dev: dict) -> dict:
    """The result line: exactly these keys, the device as jax reports it
    (``jax.devices()[0].platform``, ``.device_kind``, ``len(jax.devices())``)."""
    return {"ok": bool(ok),
            "device": {"platform": dev["platform"],
                       "kind": dev["device_kind"], "count": dev["count"]}}


def emit(sm: Smoke, ok: bool, skipped_legs: list[str], wall_s: float) -> int:
    """Print the outcome of ``sm``'s run; returns the exit code.  stdout gets
    a result only when the device leg found the required platform: the
    report line (on success), then the verdict as the last line."""
    dev = sm.device
    report = {
        "ok": ok,
        "device": {k: dev.get(k) for k in ("platform", "device_kind", "count")},
        "versions": dev.get("versions"),
        "cache_dir": dev.get("cache_dir"),
        "cache_entries": {"before": dev.get("cache_entries_before"),
                          "after": _cache_entries(dev.get("cache_dir", ""))},
        "wall_s": round(wall_s, 1),
        "legs": sm.legs,
    }
    if skipped_legs:
        report["partial"] = skipped_legs
    with open(os.path.join(sm.log_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    if not ok:
        print("chip_smoke: FAILED " + json.dumps(report), file=sys.stderr)
    if dev.get("platform") != sm.platform:
        return 1  # no accelerator: no result line
    if ok:
        print(json.dumps(report))
    print(json.dumps(verdict(ok, dev)), flush=True)
    return 0 if ok else 1


def main(argv: list[str]) -> int:
    if not os.path.isdir(os.path.join(ROOT, "blockchain_simulator_tpu")):
        print("chip_smoke: the blockchain_simulator_tpu package is not "
              "beside this script; nothing to smoke", file=sys.stderr)
        return 2
    names = [n for n, _ in LEGS]
    unknown = sorted(set(argv) - set(names))
    if unknown:
        print(f"chip_smoke: unknown leg(s) {unknown}; legs: {names}",
              file=sys.stderr)
        return 2
    legs = [(n, f) for n, f in LEGS if n == "device" or not argv or n in argv]
    t0 = time.monotonic()
    sm = Smoke(REAL)
    ok = sm.run(legs)
    return emit(sm, ok, [n for n in names if n not in dict(legs)],
                time.monotonic() - t0)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
