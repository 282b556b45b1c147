"""Bytes one row holds in the conv layers' states, over all of them: the
program's gauge ``alpa_serving_kv_cache_bytes{kind="conv"}`` (set when the
resident caches are made, from the arrays' own sizes) over the engine's
rows.  Two positions of the hidden width a layer, whatever the row's
length: 81,920 over ten layers of 2,048 in bfloat16; a state that grew with
the context would show here.  Nothing where the program has no such
series or no such layer."""

SERIES = 'alpa_serving_kv_cache_bytes{kind="conv"}'


def read(obs):
    after = (obs.get("counters") or ({}, {}))[1]
    if not after.get(SERIES) or not obs.get("engine_rows"):
        return None
    return after[SERIES] / obs["engine_rows"]
