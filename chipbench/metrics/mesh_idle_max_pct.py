"""The idle share of the idlest mesh in the traced steps: by mesh what
``chip_busy_min_pct`` is by chip.  Of ``Capture.pipeline_time()`` the four
causes of idleness (``boundary_s``, ``upstream_s``, ``dispatch_s``,
``edge_s``) over ``envelope_s``, the largest over meshes.  A mesh is busy
while one of its stage programs runs on its chips (the RUN ops of the
steps' ``pipeshard.step`` spans, each laid over its program's run)."""
from chipbench import pipeline_parts


def read(obs):
    found = pipeline_parts.table()
    if not found:
        return None
    return 100.0 * max(
        sum(row[f"{cause}_s"] for cause in pipeline_parts.CAUSES) /
        row["envelope_s"] for row in found.values())
