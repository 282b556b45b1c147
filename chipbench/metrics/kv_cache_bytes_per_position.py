"""Bytes one position of one row holds in the engine's resident caches,
over all layers: the program's gauge
``alpa_serving_kv_cache_bytes{kind="latent"}`` (set when the resident
caches are made, from the arrays' own sizes) over the engine's rows and
the served context.  A latent layer holds its normed latent and one
shared rotary key a position; a cache that went back to per-head keys and
values would read 71 times as much.  Nothing where the program has no
such series."""

SERIES = 'alpa_serving_kv_cache_bytes{kind="latent"}'


def read(obs):
    after = (obs.get("counters") or ({}, {}))[1]
    if not after.get(SERIES) or not obs.get("served_context"):
        return None
    return after[SERIES] / (obs["engine_rows"] * obs["served_context"])
