"""One chunk of a chunked admission's prefill on the device: the median
time one run of the chunk program takes (the profiler's ``XLA Modules``
line, traced seconds).  A cell whose engine admits in chunks spends its
prefill in whole runs of this program with every row waiting, so its share
of the window is this times the chunks admitted.  Nothing where the trace
holds no such run: a dense prefill (``jit_prefill``) is another program."""
from chipbench import stats

# the generator's chunk step is ``jax.jit(chunk_prefill)``
# (serve/generation.py)
CHUNK_PROGRAM = "jit_chunk_prefill"


def read(obs):
    trace = obs.get("device_trace")
    if trace is None:
        return None
    runs = trace["program_runs"].get(CHUNK_PROGRAM)
    if not runs:
        return None
    return 1e3 * stats.median(runs)
