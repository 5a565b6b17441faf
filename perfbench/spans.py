"""The program's spans in each observation's ``trace.json``: the CLI's
Chrome trace, ``B``/``E`` pairs nested per thread, with a span's
attributes and its parent's ``parent_id`` in the ``args`` of its
``B`` event (a root span has none)."""

from __future__ import annotations

import json
import os


def closed(outdir: str) -> list[dict] | None:
    """The closed spans of the observation written to ``outdir``, each
    ``{"name", "s", "args"}`` with ``s`` its seconds; None without a
    ``trace.json``."""
    path = os.path.join(outdir, "trace.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    open_, out = {}, []
    for e in events:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") == "B":
            open_.setdefault(key, []).append(e)
        elif e.get("ph") == "E" and open_.get(key):
            b = open_[key].pop()
            out.append({"name": b["name"], "s": (e["ts"] - b["ts"]) / 1e6,
                        "args": b.get("args", {})})
    return out


def per_obs(run, value):
    """Mean over the run's observations of ``value(spans, obs)``, over
    those with a trace for which it is not None; None if none is."""
    vals = []
    for o in run.obs:
        ss = closed(o["outdir"])
        v = None if ss is None else value(ss, o)
        if v is not None:
            vals.append(v)
    return sum(vals) / len(vals) if vals else None
