"""Distinct experts a routed layer's decode touched, mean over the window's
ticks and the expert layers: the rise of the program's counter
``alpa_moe_experts_touched_total`` (each tick's decode returns the number
for every routed layer; the engine reads it back with the next tick's
tokens) over the rise of ``alpa_serving_decode_steps_total`` and the
number of expert layers.  It sets the bytes a tick reads of the routed
experts.  Nothing where the program has no such counter."""
from chipbench import counters


def read(obs):
    layers = obs.get("expert_layers")
    per_tick = counters.per_step(obs, "alpa_moe_experts_touched_total",
                                 "alpa_serving_decode_steps_total")
    if per_tick is None or not layers:
        return None
    return per_tick / layers
