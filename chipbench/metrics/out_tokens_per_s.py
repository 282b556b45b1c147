"""Output tokens that reached a client inside the window, per second of the
window."""
from chipbench import stats


def read(obs):
    return len(stats.window_tokens(obs)) / obs["seconds"]
