"""Share of the window's routed token-expert rows that landed on an expert
this chip holds: the rise of the program's counter
``alpa_moe_local_rows_total`` over the rise of
``alpa_moe_routed_rows_total`` (the engine feeds both from what each
tick's decode says of its routed layers, read back with the next tick's
tokens).  One share of eight reads 12.5 when the router is balanced; it
sets the expert path's work here.  Nothing where the program has no such
counter."""
from chipbench import counters


def read(obs):
    ratio = counters.per_step(obs, "alpa_moe_local_rows_total",
                              "alpa_moe_routed_rows_total")
    return None if ratio is None else 100.0 * ratio
