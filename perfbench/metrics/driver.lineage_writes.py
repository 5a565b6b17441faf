"""Lineage file writes per observation: the ``writes`` attribute (the
writes of ``lineage.jsonl`` a block took) summed over the ``Lineage``
spans of the CLI's ``trace.json``.  Nothing where no such span carries
the attribute."""

from perfbench import spans


def _writes(ss, _obs):
    lin = [s["args"]["writes"] for s in ss
           if s["name"] == "Lineage" and "writes" in s["args"]]
    return float(sum(lin)) if lin else None


def read(run):
    return spans.per_obs(run, _writes)
