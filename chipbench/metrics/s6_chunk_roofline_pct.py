"""The Mamba-1 mixers of the chunk step against their COMPUTE roofline, as
``ssm_chunk_roofline_pct`` holds Mamba-2's: the operations the mixers of
one chunk need (``arithmetic_jamba.mamba_chunk_flops`` a layer over the
chunk's positions, real or padding, which the program computes alike: the
four projections and the recurrence in its LINEAR form, 6 operations a
state value a position) times the traced runs of ``jit_chunk_prefill``,
over the bf16 peak, over the device time of the part ``ssm_mixer`` (the
recurrence inside it included) in those runs (``Capture.device_time()``).
The recurrence runs on the vector units, which have a twentieth of that
peak: the share says what the mixers cost beside a model of products
alone, errs low and cannot pass 100.  Nothing where the program has no
such part."""
from chipbench import arithmetic_jamba, device_parts

PROGRAM = "jit_chunk_prefill"


def read(obs):
    entry = device_parts.program(PROGRAM)
    config = obs.get("config") or {}
    if entry is None or obs.get("peaks") is None or \
            "mamba_dt_rank" not in config:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    ssm_s = part_seconds(entry, "ssm_mixer")
    if not ssm_s:
        return None
    flops = arithmetic_jamba.mamba_layers(config) * \
        arithmetic_jamba.mamba_chunk_flops(
            config, config["serve"]["prefill_chunk"])
    least_s = entry["runs"] * flops / obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / ssm_s
