"""Share of the decode program's device time that falls inside the Mamba-2
mixers: the device time of the part ``ssm_mixer`` (the program's
``jax.named_scope("ssm_mixer")``, the whole mixer: both projections, the
convolution, the recurrence over every row's state, the gated norm) over
all device time of the runs of ``jit_decode`` in the traced seconds, by
the program's own table (``Capture.device_time()``: a fusion counts for
the part of its largest product).  By the bytes some 59 % of a tick of 64
rows: every row's states are read and written whole whatever its length.
Nothing where the program has no such part."""
from chipbench import device_parts

PROGRAM = "jit_decode"


def read(obs):
    entry = device_parts.program(PROGRAM)
    if entry is None:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    ssm_s = part_seconds(entry, "ssm_mixer")
    if not ssm_s:
        return None
    return 100.0 * ssm_s / sum(entry["parts"].values())
