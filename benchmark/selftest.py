"""``python benchmark/run.py --selftest`` (under ``JAX_PLATFORMS=cpu``).

1. ``BENCHMARK.json`` keeps to the contract's shapes: key sets, names,
   units, bounds, lengths, the share of four-chip cells.
2. Every cell resolves to files that exist: its configuration, its traffic
   mix, the mix's driver, and a reader for every metric it reports.
3. Each cell's rehearsal (tiny sizes, CPU) prints nothing on stdout, ends
   stderr with a result line that has the contract's keys and NO metric
   value, and does not exit 0.
4. A dummy cell made of two new data files and two new entries (no edit to
   any file that is there) rehearses the same way, and is removed again.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def line_ok(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def validate(spec: dict, root: str, here: str) -> list[str]:
    bad: list[str] = []

    def need(cond, what):
        if not cond:
            bad.append(what)

    need(set(spec) == KEYS["top"], f"top-level keys {sorted(spec)}")
    need(1 <= spec["run_seconds"] <= 51, "run_seconds out of 1..51")
    need(len(spec["command"]) <= 32 and all(line_ok(w) for w in spec["command"]),
         "command")
    for p in spec["paths"]:
        need(PATH.match(p) and not p.startswith("/") and ".." not in p,
             f"path {p!r}")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[section]]
        need(len(names) == len(set(names)), f"duplicate name in {section}")
        for e in spec[section]:
            extra = set(e) - KEYS[section] - {"workloads"}
            if section in ("configs", "workloads"):
                extra = set(e) - KEYS[section]
            need(not extra and KEYS[section] <= set(e),
                 f"{section} {e.get('name')}: keys {sorted(e)}")
            need(NAME.match(e["name"]), f"{section} name {e['name']!r}")
    cells = {w["name"]: w for w in spec["workloads"]}
    cfgs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for c in spec["configs"]:
        need(line_ok(c["source"]) and line_ok(c["why"]), f"config {c['name']} text")
        need(len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"]),
             f"config {c['name']} reduced")
        need(any(c["file"].startswith(p + "/") for p in spec["paths"])
             and os.path.isfile(os.path.join(root, c["file"])),
             f"config file {c['file']}")
        need(any(w["config"] == c["name"] for w in spec["workloads"]),
             f"config {c['name']} is used by no cell")
    pairs = set()
    for w in spec["workloads"]:
        need(w["config"] in cfgs, f"cell {w['name']}: config {w['config']!r}")
        need(NAME.match(w["traffic"]), f"cell {w['name']}: traffic name")
        need(w["chips"] in (1, 4) and line_ok(w["why"]), f"cell {w['name']}")
        need((w["config"], w["traffic"]) not in pairs, f"pair of {w['name']} repeats")
        pairs.add((w["config"], w["traffic"]))
        tpath = os.path.join(here, "traffic", w["traffic"] + ".json")
        need(os.path.isfile(tpath), f"traffic file of {w['name']}")
        if os.path.isfile(tpath):
            with open(tpath) as f:
                drv = json.load(f).get("driver", "")
            need(os.path.isfile(os.path.join(here, "drivers", drv + ".py")),
                 f"driver {drv!r} of {w['name']}")
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    need(four <= max(len(spec["workloads"]) // 2, 1), "too many four-chip cells")
    need("setup_s" in e2e and "workloads" not in e2e["setup_s"],
         "setup_s must be every cell's")
    for section, rdir in (("end_to_end", "e2e_metrics"),
                          ("per_layer", "layer_metrics")):
        for m in spec[section]:
            need(UNIT.match(m["unit"]), f"unit {m['unit']!r} of {m['name']}")
            need(m["better"] in ("lower", "higher"), f"better of {m['name']}")
            need(m["source"] in SOURCES, f"source of {m['name']}")
            need(os.path.isfile(os.path.join(here, rdir, m["name"] + ".py")),
                 f"no reader {rdir}/{m['name']}.py")
            for w in m.get("workloads", []):
                need(w in cells, f"{m['name']} lists unknown cell {w!r}")
            if section == "end_to_end":
                need(m["source"] in ("host_clock", "device_trace"),
                     f"{m['name']}: an end-to-end source is the harness's own")
                need(0 < m["bound"] <= 0.25, f"bound of {m['name']}")
            else:
                need(line_ok(m["layer"]), f"layer of {m['name']}")
                need(m["moves"] in e2e, f"{m['name']} moves {m['moves']!r}")
                moved = e2e.get(m["moves"], {})
                for w in m.get("workloads", []):
                    need("workloads" not in moved or w in moved["workloads"],
                         f"{m['name']}: cell {w} does not report {m['moves']}")
    for w in cells:
        mine = lambda sec: [m for m in spec[sec]  # noqa: E731
                            if "workloads" not in m or w in m["workloads"]]
        need(len(mine("end_to_end")) >= 2, f"cell {w}: needs setup_s and one more")
        need(len(mine("per_layer")) >= 1, f"cell {w}: no per-layer metric")
    return bad


def rehearse(root: str, workload: str, spec_path: str | None = None) -> list[str]:
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483659", "--seconds", "1",
           "--trace", "0"]
    if spec_path:
        cmd += ["--spec", spec_path]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=root, timeout=900)
    bad = []
    if proc.returncode == 0:
        bad.append(f"{workload}: a rehearsal exited 0")
    if proc.stdout.strip():
        bad.append(f"{workload}: a rehearsal wrote to stdout")
    last = [ln for ln in proc.stderr.splitlines() if ln.startswith("{")][-1:]
    try:
        line = json.loads(last[0])
    except (IndexError, ValueError):
        return bad + [f"{workload}: no result line on stderr (exit "
                      f"{proc.returncode}): {proc.stderr[-400:]}"]
    if not {"correct", "attempted", "failed", "metrics", "device"} <= set(line):
        bad.append(f"{workload}: result line keys {sorted(line)}")
    if any("value" in v for v in line.get("metrics", {}).values()):
        bad.append(f"{workload}: a rehearsal printed a metric value")
    if line.get("correct") is not True:
        bad.append(f"{workload}: rehearsal not correct")
    return bad


def dummy_cell(root: str, here: str, spec: dict) -> list[str]:
    """Add a cell as a later PR would — new files and new entries only —
    rehearse it, and take it away again."""
    cfg_file = os.path.join(here, "configs", "_selftest-dummy.json")
    mix_file = os.path.join(here, "traffic", "_selftest-mix.json")
    spec_file = os.path.join(here, "_build", "selftest.BENCHMARK.json")
    os.makedirs(os.path.dirname(spec_file), exist_ok=True)
    with open(os.path.join(here, "configs", "pbft-fullmesh-1k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "_selftest-dummy"
    cfg["rehearsal_fields"] = {"n": 16, "sim_ms": 300}
    cfg["rehearsal_reference"] = {"n": 16}
    new = json.loads(json.dumps(spec))
    new["configs"].append({"name": "_selftest-dummy", "source": "selftest",
                           "file": "benchmark/configs/_selftest-dummy.json",
                           "reduced": ["sim_ms"], "why": "selftest"})
    new["workloads"].append({"name": "_selftest.cell", "config": "_selftest-dummy",
                             "traffic": "_selftest-mix", "chips": 1,
                             "why": "selftest"})
    for m in new["end_to_end"] + new["per_layer"]:
        if "pbft1k.mc" in m.get("workloads", []):
            m["workloads"].append("_selftest.cell")
    try:
        with open(cfg_file, "w") as f:
            json.dump(cfg, f)
        with open(mix_file, "w") as f:
            json.dump({"driver": "sweep", "lanes": 2, "unit": "points",
                       "verify_rows": 1}, f)
        with open(spec_file, "w") as f:
            json.dump(new, f)
        return validate(new, root, here) + rehearse(root, "_selftest.cell",
                                                    spec_file)
    finally:
        for p in (cfg_file, mix_file, spec_file):
            if os.path.exists(p):
                os.unlink(p)


def main(root: str) -> int:
    here = os.path.join(root, "benchmark")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    bad = validate(spec, root, here)
    if os.path.getsize(path) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    print(f"selftest: shapes and files: {len(bad)} fault(s)", file=sys.stderr)
    if not bad:
        for w in spec["workloads"]:
            got = rehearse(root, w["name"])
            print(f"selftest: rehearsal {w['name']}: "
                  f"{'ok' if not got else got}", file=sys.stderr)
            bad += got
        got = dummy_cell(root, here, spec)
        print(f"selftest: dummy cell added, rehearsed, removed: "
              f"{'ok' if not got else got}", file=sys.stderr)
        bad += got
    for b in bad:
        print("selftest: FAULT " + b, file=sys.stderr)
    print(json.dumps({"selftest_ok": not bad, "faults": len(bad)}))
    return 0 if not bad else 1


if __name__ == "__main__":
    sys.exit(main(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
