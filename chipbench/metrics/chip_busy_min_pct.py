"""The least busy chip's busy share of the traced steps: the smallest
``busy_s`` of a chip over the capture's window (``Capture.device_time()``).
``device.busy_s`` is the mean over chips; in a pipeline the first and the
last stage's chips wait longest, and the step is as fast as the schedule
keeps the idlest of them fed."""
from chipbench import device_parts


def read(obs):
    found = device_parts.table()
    if not found or not found["busy_s"]:
        return None
    lo, hi = found["window_us"]
    return 100.0 * min(found["busy_s"].values()) / ((hi - lo) / 1e6)
