"""Share of the decode program's device time that falls inside the gated
short convolutions: the device time of the part ``short_conv`` (the
program's ``jax.named_scope("short_conv")``, the whole mixer: both
products, the gates, the taps, the state's update) over all device time of
the runs of ``jit_decode`` in the traced seconds, by the program's own
table (``Capture.device_time()``: a fusion counts for the part of its
largest product).  A few per cent by the model's design; it grows if the
state grows or the mixer falls off its roofline.  Nothing where the
program has no such part."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_decode")
    if entry is None:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    conv_s = part_seconds(entry, "short_conv")
    if not conv_s:
        return None
    return 100.0 * conv_s / sum(entry["parts"].values())
