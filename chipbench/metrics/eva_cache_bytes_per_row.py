"""Bytes one row of the engine holds in the caches of the layers whose keys
are of two kinds, over all of them: the program's gauge
``alpa_serving_kv_cache_bytes`` of the kinds ``window`` (the rows of one
aligned window) and ``summary`` (a pooled key and value for every chunk of
the served context), set when the resident caches are made from the
arrays' own sizes as the device lays them out, over the engine's rows.
536,870,912 at eight layers with a summary slot for every 16 of 32,768
positions (8 x (2,048 + 2,048) x 16,384 B); a padded layout, or a cache of
the context's positions beside, would show here.  Nothing where the
program has no ``summary`` series."""

SERIES = 'alpa_serving_kv_cache_bytes{kind="%s"}'


def read(obs):
    after = (obs.get("counters") or ({}, {}))[1]
    if not after.get(SERIES % "summary") or not obs.get("engine_rows"):
        return None
    return (after[SERIES % "summary"] + after.get(SERIES % "window", 0)) / \
        obs["engine_rows"]
