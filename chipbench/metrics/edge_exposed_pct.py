"""Share of the traced steps in which a mesh waits for a value to arrive
and its program to start: the cause ``edge`` of
``Capture.pipeline_time()`` (``edge_s`` over ``envelope_s``), mean over
meshes.  The producers had finished, the RUN op was enqueued, the program
had not begun: the cross-mesh move and the launch."""
from chipbench import pipeline_parts


def read(obs):
    return pipeline_parts.share_pct("edge_s")
