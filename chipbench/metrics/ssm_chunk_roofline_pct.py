"""The Mamba-2 mixers of the chunk step against their COMPUTE roofline: the
operations the mixers of one chunk need
(``arithmetic_nemotron.mamba_chunk_flops`` a layer over the chunk's
positions, real or padding, which the program computes alike: the two
projections and the recurrence in its LINEAR form, 6 operations a state
value a position, what any form of the scan does at least) times the
traced runs of ``jit_chunk_prefill``, over the bf16 peak, over the device
time of the part ``ssm_mixer`` in those runs (``Capture.device_time()``).
At 1,024 positions the projections do 1,024 operations a byte of weight,
past the chip's 240: compute bounds them.  The convolution, the gate, the
norm and whatever the chunked form multiplies beyond the linear one only
lengthen the part's time: the share errs low and cannot pass 100.  Nothing
where the program has no such part."""
from chipbench import arithmetic_nemotron, device_parts

PROGRAM = "jit_chunk_prefill"


def read(obs):
    entry = device_parts.program(PROGRAM)
    config = obs.get("config") or {}
    if entry is None or obs.get("peaks") is None or \
            "hybrid_override_pattern" not in config:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    ssm_s = part_seconds(entry, "ssm_mixer")
    if not ssm_s:
        return None
    flops = arithmetic_nemotron.layers_of(config, "M") * \
        arithmetic_nemotron.mamba_chunk_flops(
            config, config["serve"]["prefill_chunk"])
    least_s = entry["runs"] * flops / obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / ssm_s
