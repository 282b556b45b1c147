"""Share of the traced steps in which a mesh waits for the driver: the
cause ``dispatch`` of ``Capture.pipeline_time()`` (``dispatch_s`` over
``envelope_s``), mean over meshes.  What the next stage program needs had
finished on the device and the enqueue of its RUN op had not returned: the
one driver thread was elsewhere (``dispatch_by_span`` in the table names
the span it was in, ``pipeshard.run-ahead`` where another mesh's queue
held it)."""
from chipbench import pipeline_parts


def read(obs):
    return pipeline_parts.share_pct("dispatch_s")
