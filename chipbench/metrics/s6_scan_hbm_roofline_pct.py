"""The Mamba-1 mixers' recurrence over a chunk against its MEMORY roofline:
the least bytes ANY form of the scan moves (``arithmetic_jamba.
scan_chunk_bytes`` a layer: ``x`` in and ``y`` out in the model's dtype,
the low-rank ``dt``, ``B`` and ``C`` in, the state once in and once out)
times the Mamba layers times the traced runs of ``jit_chunk_prefill``,
over the HBM peak, over the device time of the part ``ssm_mixer.scan`` in
those runs (``Capture.device_time()``).  The vector and transcendental
units bound this walk and ``chipbench/peaks.py`` publishes no peak of
theirs, so memory is the one roofline it can be held to: the share says
how far from free the walk is, does not depend on what implements it, and
cannot pass 100.  Nothing where the program has no such part."""
from chipbench import arithmetic_jamba, device_parts

PROGRAM = "jit_chunk_prefill"
PART = "ssm_mixer.scan"


def read(obs):
    entry = device_parts.program(PROGRAM)
    config = obs.get("config") or {}
    if entry is None or obs.get("peaks") is None or \
            "mamba_dt_rank" not in config:
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    scan_s = part_seconds(entry, PART)
    if not scan_s:
        return None
    least = arithmetic_jamba.mamba_layers(config) * \
        arithmetic_jamba.scan_chunk_bytes(
            config, config["serve"]["prefill_chunk"], obs["cache_itemsize"])
    least_s = entry["runs"] * least / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / scan_s
