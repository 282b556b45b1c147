"""Time from when a request was DUE to its first streamed token
(``stats.ttft_waits``), 90th percentile over every request due in the
window.  Per layer, with no bound: a window holds some 40 requests, three of
them beyond the 90th percentile, and no statistic of their waits repeats to
better than 8 per cent (PERF.md).  The driver prints every wait on its
``ttft_ms`` line."""
from chipbench import stats


def read(obs):
    waits = stats.ttft_waits(obs["requests"], obs["drain_end"])
    return stats.percentile(waits, 90) * 1e3
