"""Seconds per observation the chunked driver spends putting its static
state on the devices (the ``Prep-Upload`` spans of the CLI's
``trace.json``: the replicated flat filterbank parts, the resample
tables and the zap lists).  Nothing where the program opens no such
span."""

from perfbench import spans


def _seconds(ss, _obs):
    out = [s["s"] for s in ss if s["name"] == "Prep-Upload"]
    return sum(out) if out else None


def read(run):
    return spans.per_obs(run, _seconds)
