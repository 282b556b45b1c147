"""Share of the traced steps in which a mesh waits for the step to begin:
the cause ``boundary`` of ``Capture.pipeline_time()`` (``boundary_s`` over
``envelope_s``), mean over meshes.  From the end of a step's last program
to the return of the next step's ``pipeshard.place-inputs``: what the host
does between two steps, the inputs' ``device_put``, the zeroed
accumulators."""
from chipbench import pipeline_parts


def read(obs):
    return pipeline_parts.share_pct("boundary_s")
