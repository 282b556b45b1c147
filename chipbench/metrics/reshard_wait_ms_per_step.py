"""Milliseconds of a pipeshard step that the driver thread spent blocked on
a cross-mesh transfer: the exposed part of resharding.  The rise of
``alpa_overlap_wait_blocked_seconds_total`` over the rise of
``alpa_overlap_steps_total`` in the window (``runtime_emitter``
``record_overlap_step``); both tick only in dispatch mode ``overlap``."""
from chipbench import counters


def read(obs):
    s = counters.per_step(obs, "alpa_overlap_wait_blocked_seconds_total",
                          "alpa_overlap_steps_total")
    return None if s is None else s * 1e3
