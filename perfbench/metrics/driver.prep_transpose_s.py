"""Seconds per observation the chunked driver spends laying the
filterbank out channel-major on the host (the ``Prep-Transpose`` spans
of the CLI's ``trace.json``: the threaded transpose, killmask and tail
pad).  Nothing where the program opens no such span."""

from perfbench import spans


def _seconds(ss, _obs):
    out = [s["s"] for s in ss if s["name"] == "Prep-Transpose"]
    return sum(out) if out else None


def read(run):
    return spans.per_obs(run, _seconds)
