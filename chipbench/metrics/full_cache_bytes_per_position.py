"""Bytes one position of one row holds in the full-attention layers of the
engine's resident caches, over those layers: the program's gauge
``alpa_serving_kv_cache_bytes{kind="full"}`` (set when the resident caches
are made, from the arrays' sizes as the device lays them out) over the
engine's rows and the served context.  Keys of 192 channels and values of
128 over 4 heads in 2 layers are 5,120 in bfloat16; a cache whose 192
channels a head were padded to 256 lanes would read 6,144.  Nothing where
the program has no such series or the run does not say its served
context."""

SERIES = 'alpa_serving_kv_cache_bytes{kind="full"}'


def read(obs):
    after = (obs.get("counters") or ({}, {}))[1]
    if not after.get(SERIES) or not obs.get("served_context"):
        return None
    return after[SERIES] / (obs["engine_rows"] * obs["served_context"])
