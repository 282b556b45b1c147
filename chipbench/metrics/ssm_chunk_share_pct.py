"""Share of the chunk program's device time that falls inside the Mamba-2
mixers: the device time of the part ``ssm_mixer`` (both projections, the
convolution, the scan in sub-chunks from the row's state, the gated norm)
over all device time of the runs of ``jit_chunk_prefill`` in the traced
seconds, by the program's own table (``Capture.device_time()``).  Nothing
where the program has no such part."""
from chipbench import device_parts

PROGRAM = "jit_chunk_prefill"


def read(obs):
    entry = device_parts.program(PROGRAM)
    if entry is None:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    ssm_s = part_seconds(entry, "ssm_mixer")
    if not ssm_s:
        return None
    return 100.0 * ssm_s / sum(entry["parts"].values())
