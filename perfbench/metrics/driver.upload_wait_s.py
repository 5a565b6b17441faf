"""Seconds per observation the chunked driver waits for its static
state to land on the devices before the first chunk's results can be
read (the ``Upload-Wait`` spans of the CLI's ``trace.json``: the
residue of the filterbank's host-to-device copy after the first
dispatch).  Nothing where the program opens no such span."""

from perfbench import spans


def _seconds(ss, _obs):
    out = [s["s"] for s in ss if s["name"] == "Upload-Wait"]
    return sum(out) if out else None


def read(run):
    return spans.per_obs(run, _seconds)
