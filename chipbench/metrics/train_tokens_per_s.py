"""Tokens of the whole steps completed in the window, over the time to the
end of the last of them.  Each step ends in ``block_until_ready``."""
from chipbench import stats


def read(obs):
    return stats.train_tokens_per_s(obs)
