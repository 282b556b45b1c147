"""Rows of the busiest expert over the mean, the largest over the expert
layers: the program's gauge ``alpa_moe_expert_load_max_over_mean``, set
from the routing of the warm-up step and so in the window's closing
snapshot.  1 is a perfectly even routing; a dropless layer's grouped
matmul takes the same time whatever this reads, an expert-parallel one
waits for the busiest chip.  Nothing where the program has no such gauge."""


def read(obs):
    pair = obs.get("counters")
    if not pair:
        return None
    return pair[1].get("alpa_moe_expert_load_max_over_mean")
