"""Share of the chips' busy time in the traced steps that the attention
core takes: the part ``attention`` (scores, softmax, values: forward,
backward and the recomputation of a rematerialised block) of every program
that ran, over the chip's busy seconds, mean over chips
(``Capture.device_time()``).  It is what a fused attention kernel can
move.  Nothing where more than a tenth of a chip's device time is under no
``op_name``."""
from chipbench import device_parts


def read(obs):
    found = device_parts.table()
    if not found:
        return None
    # only a program that reduced a capture has this module: the import
    # stays behind the table, so that an older program gives nothing
    from alpa_tpu.telemetry.device_time import part_seconds
    shares = []
    for chip, programs in found["programs"].items():
        entries = device_parts.scoped(list(programs.values()))
        if entries is None or not found["busy_s"][chip]:
            return None
        shares.append(sum(part_seconds(e, "attention") for e in entries) /
                      found["busy_s"][chip])
    return 100.0 * sum(shares) / len(shares) if shares else None
