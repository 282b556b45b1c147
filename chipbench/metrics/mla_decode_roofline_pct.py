"""The absorbed attention cores of the decode program against their
roofline: the least the traced ticks' cores have to do
(``arithmetic_mla.absorbed_core_work`` of the cache positions the ticks'
rows attended over, the program's counter
``alpa_serving_decode_positions_total`` over the traced seconds: the larger
of their operations over the chip's bf16 peak and the bytes of those
positions' latent cache, read once, over the HBM peak; at 128 heads the
two are about equal on a v5e) over the time the device events under the
program's scope ``attention`` took inside the runs of ``jit_decode`` in
those seconds.  The scope also holds the cache's write and the
absorption's two small products, and the kernel reads whole key blocks
where the count is of positions, so the share is of the core as the
program runs it.  Nothing where the driver found no such event
(``obs["decode_trace"]``) or the program has no such counter."""
from chipbench import arithmetic_mla, counters


def read(obs):
    found = obs.get("decode_trace") or {}
    positions = counters.delta({"counters": obs.get("traced_counters")},
                               "alpa_serving_decode_positions_total")
    if obs["peaks"] is None or not positions or \
            not found.get("attention_events"):
        return None
    work = arithmetic_mla.absorbed_core_work(obs["config"], positions,
                                             obs["cache_itemsize"])
    least_s = max(work["flops"] / obs["peaks"]["bf16_flops_per_s"],
                  work["bytes"] / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / found["attention_s"]
