"""The attention of a prefill chunk over keys of two kinds against its
COMPUTE roofline: the operations the traced chunks' attention needs
(``arithmetic_evabyte.attention_flops_a_pair`` for every pair of a query
and a key it sees, exact and causal inside the window or a summary of a
window before, a layer: the program's counters
``alpa_serving_eva_chunk_pairs_total{kind}``, which the engine feeds from
the chunks' offsets, over the chunks it ran, ``padded positions /
prefill_chunk``: the mean over the WINDOW's chunks, since a counter rises
by an admission's whole chunks before they run and the traced seconds
hold a few admissions) times the traced runs of ``jit_chunk_prefill``,
over the
bf16 peak, over the device seconds of the part ``attention`` in those runs
(``Capture.device_time()``).  The pooling of a chunk's own 64 summaries,
the writes and the softmax's elementwise work are left out of the count
and in the time, so the share errs low and cannot pass 100.  Nothing where
the program has no such counter or no such part."""
from chipbench import arithmetic_evabyte, counters, device_parts

PROGRAM = "jit_chunk_prefill"
SERIES = 'alpa_serving_eva_chunk_pairs_total{kind="%s"}'


def read(obs):
    entry = device_parts.program(PROGRAM)
    config = obs.get("config") or {}
    window = {"counters": obs.get("counters")}
    padded = counters.delta(window,
                            "alpa_serving_prefill_padded_tokens_total")
    pairs = [counters.delta(window, SERIES % kind)
             for kind in ("exact", "summary")]
    if entry is None or obs.get("peaks") is None or not padded or \
            None in pairs or "window_size" not in config:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    attention_s = part_seconds(entry, "attention")
    if not attention_s:
        return None
    chunks = padded / config["serve"]["prefill_chunk"]
    flops = config["num_hidden_layers"] * sum(pairs) / chunks * \
        arithmetic_evabyte.attention_flops_a_pair(config)
    least_s = entry["runs"] * flops / obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / attention_s
