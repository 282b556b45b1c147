"""What one decode tick spends on the vocabulary: the device time of the
part ``head`` (the logits' product) and of ``embed`` in one run of
``jit_decode``, mean over the traced runs (``Capture.device_time()``).
``embed`` belongs to it because a tied table's cast to the model's
precision is traced under ``wte`` (flax casts in ``Embed.__call__``, and
the compiler keeps one cast for the lookup and for ``wte.attend``): in a
decode the lookup itself is a few rows."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_decode")
    if entry is None:
        return None
    return device_parts.part_ms_a_run(entry, "head", "embed")
