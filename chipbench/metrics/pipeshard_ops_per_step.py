"""Operations of the register program replayed in one pipeshard step: the
program's per-op spans (categories ``instruction`` and ``transfer``) inside
one ``pipeshard.step`` span.  A count; it repeats exactly."""
from chipbench import stats


def read(obs):
    steps = stats.program_spans(obs, "pipeshard.step")
    ops = [s for s in obs["program_spans"]
           if s["category"] in ("instruction", "transfer")]
    if not steps or not ops:
        return None
    counts = [sum(step["ts_us"] <= op["ts_us"] <=
                  step["ts_us"] + step["dur_us"] for op in ops)
              for step in steps]
    return stats.median(counts)
