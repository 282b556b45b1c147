"""Share of the traced steps in which a mesh waits for the other: the
cause ``upstream`` of ``Capture.pipeline_time()`` (``upstream_s`` over
``envelope_s``), mean over meshes.  A stage program that a RUN op on
another mesh feeds (through the RESHARD that carries the value) had not
finished on the device: the pipeline's fill, its drain and every
steady-state bubble.  More micro-batches or another schedule move it."""
from chipbench import pipeline_parts


def read(obs):
    return pipeline_parts.share_pct("upstream_s")
