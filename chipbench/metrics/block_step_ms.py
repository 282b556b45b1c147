"""One forward of a block for all rows on the device: the median time one
run of the block step's program takes (the profiler's ``XLA Modules``
line, traced seconds).  Nothing where the trace holds no such run."""
from chipbench import stats

# the generator's block step is jitted under its own name
# (serve/generation.py ``block_step``)
BLOCK_PROGRAM = "jit_block_step"


def read(obs):
    trace = obs.get("device_trace")
    if trace is None:
        return None
    runs = trace["program_runs"].get(BLOCK_PROGRAM)
    if not runs:
        return None
    return 1e3 * stats.median(runs)
