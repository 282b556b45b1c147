"""Share of the traced steps a chip's op line spends in a collective, so
that the core does nothing else (a synchronous ``all-gather``, a ``-done``
that waits): ``collective_exposed_s`` of ``Capture.pipeline_time()`` over
``envelope_s``, mean over chips (a mesh's value is the mean over its
chips).  ``collective_hidden_s`` beside it in the table is the time
between an asynchronous collective's ``-start`` and its ``-done``."""
from chipbench import pipeline_parts


def read(obs):
    found = pipeline_parts.table()
    if not found:
        return None
    chips = sum(row["chips"] for row in found.values())
    return 100.0 * sum(row["chips"] * row["collective_exposed_s"] /
                       row["envelope_s"] for row in found.values()) / chips
