"""Seconds per observation that no program span explains: the CLI
call's wall time less the summed time of the root spans (those with
no parent) of its ``trace.json``."""

from perfbench import spans


def _unspanned(ss, obs):
    return obs["wall_s"] - sum(s["s"] for s in ss
                               if "parent_id" not in s["args"])


def read(run):
    return spans.per_obs(run, _unspanned)
