"""Of the cache positions the window's decode ticks held on the layers that
select their positions, the share their attention cores fetched and
attended over: the rise of the program's counter
``alpa_serving_select_positions_total{what="selected"}`` over the rise of
``{what="held"}`` (the engine feeds both a tick, from the rows' lengths:
``index_topk`` a row a layer, or all the row holds while that is less).
100: every row is still shorter than the selection, and the layers read
as plain latent attention does.  Nothing where the program has no such
counter."""
from chipbench import counters

SERIES = 'alpa_serving_select_positions_total{what="%s"}'


def read(obs):
    ratio = counters.per_step(obs, SERIES % "selected", SERIES % "held")
    return None if ratio is None else 100.0 * ratio
