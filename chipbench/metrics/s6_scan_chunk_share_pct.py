"""Share of the chunk program's device time that falls inside the Mamba-1
mixers' RECURRENCE alone: the device time of the part ``ssm_mixer.scan``
(the program's ``jax.named_scope("selective_scan")`` inside its
``ssm_mixer``: the walk over the chunk's positions, whatever implements
it, with what spreads its operands and folds its state) over all device
time of the runs of ``jit_chunk_prefill`` in the traced seconds, by the
program's own table (``Capture.device_time()``).  Nothing where the
program has no such part."""
from chipbench import device_parts

PROGRAM = "jit_chunk_prefill"
PART = "ssm_mixer.scan"


def read(obs):
    entry = device_parts.program(PROGRAM)
    if entry is None:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    scan_s = part_seconds(entry, PART)
    if not scan_s:
        return None
    return 100.0 * scan_s / sum(entry["parts"].values())
