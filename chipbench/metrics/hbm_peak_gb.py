"""Largest ``memory_stats()["peak_bytes_in_use"]`` over the cell's chips,
in GB.  The allocator's view: on this backend it has been seen to leave out
a program's temporaries (PR 22), so the compiler's ``memory_analysis()`` of
the step program is printed on an earlier line where there is one."""
from chipbench import stats


def read(obs):
    return stats.peak_memory_gb(obs)
