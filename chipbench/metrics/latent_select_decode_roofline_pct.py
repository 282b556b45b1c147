"""The decode program's selecting layers against their MEMORY roofline: the
least the traced ticks had to read of those layers' caches
(``arithmetic_dsa.latent_select_decode_bytes``: the index key of every
position the rows held, which the indexer scores, and the row, latent and
shared rotary key, of every position selected; both from the program's
counter ``alpa_serving_select_positions_total`` over the traced seconds)
over the HBM peak, over the time the device events under the program's
scopes ``indexer`` and ``latent_select`` took inside the runs of
``jit_decode`` in those seconds.  The count leaves out the projections'
weights, the scores, the choice and the gathered copy's write, so it is a
true lower bound and the share cannot pass 100 %; a core that read every
position held would have to read ``held x 1,152`` bytes more than the
count allows in the same time.  Nothing where the driver found no such
event (``obs["decode_trace"]``) or the program has no such counter."""
from chipbench import arithmetic_dsa, counters

SERIES = 'alpa_serving_select_positions_total{what="%s"}'


def read(obs):
    found = obs.get("decode_trace") or {}
    traced = {"counters": obs.get("traced_counters")}
    held = counters.delta(traced, SERIES % "held")
    selected = counters.delta(traced, SERIES % "selected")
    if obs["peaks"] is None or not held or not selected or \
            not found.get("indexer_events") or \
            not found.get("latent_select_events"):
        return None
    least_s = arithmetic_dsa.latent_select_decode_bytes(
        obs["config"], held, selected, obs["cache_itemsize"]) / \
        obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (found["indexer_s"] + found["latent_select_s"])
