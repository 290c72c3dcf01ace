"""Serving: requests served per flush in the window (``served / batches``
from ``ScenarioServer.stats()``)."""


def read(run: dict):
    st = run["window"].get("stats")
    if not st or not st.get("batches"):
        return None
    return st["served"] / st["batches"]
