"""Seconds per observation of candidate provenance: the ``Lineage``
spans of the CLI's ``trace.json`` (the ids, tolerance margins and
marks of the lineage ledger, block by block), summed per observation.
Nothing where the program opens no such span."""

from perfbench import spans


def _seconds(ss, _obs):
    lin = [s["s"] for s in ss if s["name"] == "Lineage"]
    return sum(lin) if lin else None


def read(run):
    return spans.per_obs(run, _seconds)
