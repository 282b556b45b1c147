"""How late the load generator sent each request against its due time,
99th percentile: a starved generator must not read as a fast server."""
from chipbench import stats


def read(obs):
    late = [rec["sent"] - rec["due"] for rec in obs["requests"]
            if rec["sent"] is not None]
    return stats.percentile(late, 99) * 1e3
