"""Lineage marks per observation: the ``marks`` attribute (the marks a
block wrote) summed over the ``Lineage`` spans of the CLI's
``trace.json``.  Nothing where the program opens no such span."""

from perfbench import spans


def _marks(ss, _obs):
    lin = [s["args"].get("marks", 0) for s in ss if s["name"] == "Lineage"]
    return float(sum(lin)) if lin else None


def read(run):
    return spans.per_obs(run, _marks)
