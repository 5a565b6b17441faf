"""Seconds per observation the CLI spends writing its output files
(the ``Output`` span of its ``trace.json``: the run report,
``candidates.peasoup`` and ``overview.xml``).  Nothing where the
program opens no such span."""

from perfbench import spans


def _seconds(ss, _obs):
    out = [s["s"] for s in ss if s["name"] == "Output"]
    return sum(out) if out else None


def read(run):
    return spans.per_obs(run, _seconds)
