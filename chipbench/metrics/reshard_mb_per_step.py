"""Megabytes that cross between meshes in one pipeshard step: the ``bytes``
of the program's ``reshard.edge`` / ``reshard.edge-group`` spans that began
inside one ``pipeshard.step`` span, median over the traced steps.  A count;
it repeats exactly."""
from chipbench import stats


def read(obs):
    steps = stats.program_spans(obs, "pipeshard.step")
    edges = [s for s in obs["program_spans"]
             if s["name"].startswith("reshard.edge") and s["args"]]
    if not steps or not edges:
        return None
    sums = [sum(e["args"].get("bytes", 0) for e in edges
                if step["ts_us"] <= e["ts_us"] <=
                step["ts_us"] + step["dur_us"]) for step in steps]
    return stats.median(sums) / 1e6
