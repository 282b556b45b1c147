"""Gap between consecutive streamed tokens of one request, 99th percentile
of the gaps pooled over all requests (those whose later token fell inside
the window)."""
from chipbench import stats


def read(obs):
    gaps = [t - rec["token_times"][k - 1]
            for rec, k, t in stats.window_tokens(obs) if k > 0]
    return stats.percentile(gaps, 99) * 1e3
