"""A dense admission's prefill on the device: the median time one run of
``jit_prefill`` takes (the profiler's ``XLA Modules`` line, traced
seconds), from the program's own reduction of its capture
(``Capture.device_time()``).  Every row waits while it runs, so it is the
device's share of the longest gap between a request's tokens.  Nothing
where no admission fell in the traced seconds, or where the engine admits
in chunks (``jit_chunk_prefill``: ``prefill_chunk_ms``)."""
from chipbench import device_parts, stats


def read(obs):
    entry = device_parts.program("jit_prefill")
    return None if entry is None else 1e3 * stats.median(entry["run_s"])
