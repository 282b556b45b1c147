"""Share of the window's routed picks that fell on an identity expert,
which has no matrix and adds its weight times the layer's input: the rise
of the program's counter ``alpa_moe_zero_picks_total`` over the rise of
``alpa_moe_routed_rows_total`` (which counts every pick, 12 a token a
routed layer; the engine feeds both from what each tick's decode says of
its routed layers, read back with the next tick's tokens).  256 of 768
router outputs read 33.3 when the router is balanced; it sets the rows the
experts multiply, which the data so decides.  Nothing where the program
has no such counter."""
from chipbench import counters


def read(obs):
    ratio = counters.per_step(obs, "alpa_moe_zero_picks_total",
                              "alpa_moe_routed_rows_total")
    return None if ratio is None else 100.0 * ratio
