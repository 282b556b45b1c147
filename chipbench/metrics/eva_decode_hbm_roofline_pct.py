"""The attention of a decode tick over keys of two kinds against its
MEMORY roofline: the least bytes the traced ticks' attention had to read (a
layer an active row, one row of keys and values,
``arithmetic_evabyte.row_bytes``, for every exact key ``t % window + 1``
and every summary ``window / chunk x (t // window)`` its query saw: the
program's counters ``alpa_serving_eva_keys_total{kind}`` a tick, which the
engine feeds from the rows' positions) over the HBM peak, over the device
seconds of the part ``attention`` (the summaries' pooling and both writes
inside it) in the runs of ``jit_decode`` (``Capture.device_time()``).  A
true lower bound: no write, no query, no summary pooled again and no
skipped or padded slot is counted, so the share cannot pass 100, and it
does not depend on what implements the attention.  Nothing where the
program has no such counter or no such part."""
from chipbench import arithmetic_evabyte, counters, device_parts

PROGRAM = "jit_decode"
SERIES = 'alpa_serving_eva_keys_total{kind="%s"}'


def read(obs):
    entry = device_parts.program(PROGRAM)
    config = obs.get("config") or {}
    traced = {"counters": obs.get("traced_counters")}
    steps = counters.delta(traced, "alpa_serving_decode_steps_total")
    keys = [counters.delta(traced, SERIES % kind)
            for kind in ("exact", "summary")]
    if entry is None or obs.get("peaks") is None or not steps or \
            None in keys or "window_size" not in config:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    attention_s = part_seconds(entry, "attention")
    if not attention_s:
        return None
    least = config["num_hidden_layers"] * sum(keys) / steps * \
        arithmetic_evabyte.row_bytes(config, obs["cache_itemsize"])
    least_s = entry["runs"] * least / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / attention_s
