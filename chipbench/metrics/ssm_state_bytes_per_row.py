"""Bytes one row holds in the Mamba-2 layers' states, over all of them: the
program's gauge ``alpa_serving_kv_cache_bytes{kind="ssm"}`` (set when the
resident caches are made, from the arrays' own sizes: every such layer's
ssm state and conv state) over the engine's rows.  49,082,368 at full
depth (23 x (64 x 64 x 128 float32 + 3 x 6,144 bfloat16)), whatever the
row's length; a state that grew with the context, or a second copy kept,
would show here.  Nothing where the program has no such series or no such
layer."""

SERIES = 'alpa_serving_kv_cache_bytes{kind="ssm"}'


def read(obs):
    after = (obs.get("counters") or ({}, {}))[1]
    if not after.get(SERIES) or not obs.get("engine_rows"):
        return None
    return after[SERIES] / obs["engine_rows"]
