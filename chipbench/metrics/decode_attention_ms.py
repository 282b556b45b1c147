"""What one decode tick spends in the attention core: the device time of
the part ``attention`` (scores, softmax, values) with the
``attention.cache_write`` inside it (the tick's keys and values written
into the cache) in one run of ``jit_decode``, mean over the traced runs
(``Capture.device_time()``).  It is what reading every cache to its full
length costs, whatever the rows' positions."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_decode")
    if entry is None:
        return None
    return device_parts.part_ms_a_run(entry, "attention")
