"""Milliseconds from the call of the train step to its return, before
``block_until_ready``: what the host spends enqueueing one step.  Median
over the window's steps."""
from chipbench import stats


def read(obs):
    return stats.median([ret - call for call, ret, _ in obs["steps"]]) * 1e3
