"""Milliseconds of cross-mesh transfer work in a pipeshard step, wherever it
ran (the pool's threads, overlapped or not): the rise of
``alpa_overlap_transfer_busy_seconds_total`` over the rise of
``alpa_overlap_steps_total`` in the window.  Against
``reshard_wait_ms_per_step`` it says how much of the transfers is hidden."""
from chipbench import counters


def read(obs):
    s = counters.per_step(obs, "alpa_overlap_transfer_busy_seconds_total",
                          "alpa_overlap_steps_total")
    return None if s is None else s * 1e3
