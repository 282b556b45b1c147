"""Milliseconds the program's own ``launch_on_driver`` takes to enqueue one
step, mean over the window: the rise of the sum over the rise of the count
of ``alpa_pipeshard_dispatch_seconds`` (a pipeshard step) or, where that did
not tick, ``alpa_mesh_dispatch_seconds`` (one mesh).  The inside twin of
``host_dispatch_ms.train``: the difference is the ``parallelize`` wrapper's
(flattening the arguments, the executable cache, placing the batch)."""
from chipbench import counters


def read(obs):
    for name in ("alpa_pipeshard_dispatch_seconds",
                 "alpa_mesh_dispatch_seconds"):
        mean = counters.mean_observed(obs, name)
        if mean is not None:
            return mean * 1e3
    return None
