"""The comparisons that decide ``correct``.

Every comparison is one record ``{"name", "value", "limit", "rule", "ok"}``
that ``run.py`` prints beside its limit in every run.  Counts are exact
(limit 0).  The timing milestones are compared with the plain reference
(``reference/pbft_engine.py``: a per-message event-heap engine, run after the
window on the deployment's own fields); their limits are in the
configuration file, set from chip readings that ``PERF.md`` records.
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def exact(name: str, value, want) -> dict:
    return {"name": name, "value": value, "limit": want, "rule": "==",
            "ok": value == want}


def at_most(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": value, "limit": limit, "rule": "<=",
            "ok": bool(value <= limit)}


def at_least(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": value, "limit": limit, "rule": ">=",
            "ok": bool(value >= limit)}


def _engine(name: str):
    path = os.path.join(HERE, "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_milestones(config: dict, fields: dict, seed: int) -> dict:
    """Run the plain reference on this deployment's fields (at the node and
    round counts the configuration file gives it) with view changes off: the
    milestones of an undisturbed run, which do not depend on its random
    stream."""
    ref = config["reference"]
    over = {"n": ref.get("n", fields["n"]), "pbft_view_change_num": 0}
    if "rounds" in ref:
        interval = fields.get("pbft_block_interval_ms", 50)
        tail = fields["sim_ms"] - fields["pbft_max_rounds"] * interval
        over.update(pbft_max_rounds=ref["rounds"],
                    pbft_max_slots=ref["rounds"] + 8,
                    sim_ms=ref["rounds"] * interval + tail)
    m = _engine(ref["engine"]).run(fields, seed, **over)
    m["_interval"] = fields.get("pbft_block_interval_ms", 50)
    return m


def commit_tail(m: dict, interval: int) -> float:
    """The last final block's commit time past its own block tick: free of
    how many rounds the run had."""
    return m["last_commit_ms"] - (m["blocks_final_all_nodes"] - 1) * interval


def guarantees(rows: list[dict], want_final: int | None) -> list[dict]:
    """What every row of the window must satisfy whatever its seed:
    agreement, and the configuration's finality guarantee — all
    ``want_final`` rounds final on all honest nodes, or (``None``) at least
    one."""
    out = [exact("agreement_violations",
                 sum(1 for m in rows if not m.get("agreement_ok")), 0)]
    if want_final is None:
        out.append(at_least("blocks_final_min",
                            min(m["blocks_final_all_nodes"] for m in rows), 1))
    else:
        out.append(exact("finality_shortfall_max", max(
            want_final - m["blocks_final_all_nodes"] for m in rows), 0))
        out.append(exact("rounds_sent_gap_max", max(
            abs(want_final - m["rounds_sent"]) for m in rows), 0))
    return out


def against_reference(rows: list[dict], ref: dict, config: dict,
                      scaled: bool) -> list[dict]:
    """Rows against the reference's milestones.  ``scaled``: the reference
    ran fewer rounds than the rows (its cost is N^2 per round), so counts are
    compared as the share of rounds that became final and times as the
    commit tail; otherwise counts are compared as they are, on the rows that
    had no view change (a view change stalls the pipeline by a round or
    two, and the reference runs with none)."""
    lim = config["reference"]
    iv = ref["_interval"]
    out = [exact("reference_agreement_ok", bool(ref["agreement_ok"]), True)]
    if scaled:
        calm = rows
        out.append(exact("reference_final_share",
                         ref["blocks_final_all_nodes"] / ref["rounds_sent"], 1.0))
    else:
        calm = [m for m in rows if m["view_changes"] == 0]
        out.append(at_least("rows_without_view_change", len(calm), 1))
        out.append(exact("rounds_sent_vs_reference_max", max(
            (abs(m["rounds_sent"] - ref["rounds_sent"]) for m in calm),
            default=0), 0))
        out.append(exact("blocks_final_vs_reference_max", max(
            (abs(m["blocks_final_all_nodes"] - ref["blocks_final_all_nodes"])
             for m in calm), default=0), 0))
        out.append(at_most("blocks_final_over_reference_max", max(
            m["blocks_final_all_nodes"] - ref["blocks_final_all_nodes"]
            for m in rows), 0))
    out.append(at_most("ttf_gap_ms_max", max(
        (abs(m["mean_time_to_finality_ms"] - ref["mean_time_to_finality_ms"])
         for m in calm), default=0.0), lim["ttf_limit_ms"]))
    out.append(at_most("commit_tail_gap_ms_max", max(
        (abs(commit_tail(m, iv) - commit_tail(ref, iv)) for m in calm),
        default=0.0), lim["tail_limit_ms"]))
    return out


def rows_equal_solo(rows: list[dict], solo: list[dict]) -> dict:
    """Batched or served rows against the solo runs of their seeds."""
    return exact("rows_differing_from_solo",
                 sum(1 for a, b in zip(rows, solo) if a != b), 0)
