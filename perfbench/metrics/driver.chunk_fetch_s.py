"""Seconds per observation the chunked driver waits for each chunk's
packed peaks to come home from every shard (the ``Chunk-Fetch`` spans
of the CLI's ``trace.json``, summed: the gather wait, device time
still running included).  Nothing where the program opens no such
span."""

from perfbench import spans


def _seconds(ss, _obs):
    out = [s["s"] for s in ss if s["name"] == "Chunk-Fetch"]
    return sum(out) if out else None


def read(run):
    return spans.per_obs(run, _seconds)
