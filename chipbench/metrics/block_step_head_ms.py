"""What one block step spends on the vocabulary: the device time of the
parts ``head`` (the logits' product over rows x block positions),
``embed`` and ``unmask`` (the draw, the confidences over the vocabulary
and the rule) in one run of ``jit_block_step``, mean over the traced runs
(``Capture.device_time()``).  Nothing where the program ran no such
program or its captures reduce no device time."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_block_step")
    if entry is None:
        return None
    return device_parts.part_ms_a_run(entry, "head", "embed", "unmask")
