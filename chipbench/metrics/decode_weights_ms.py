"""What one decode tick spends multiplying by the layers' weights: the
device time of the parts ``projection`` (q, k, v, output, gate) and ``mlp``
in one run of ``jit_decode``, mean over the traced runs
(``Capture.device_time()``: an event's part is read off the compiled
decode's ``op_name``; a matmul fused with the next norm's statistics counts
whole, the table's ``mixed_s`` says how much rests on that).  At a few
rows a tick it is the time to read the weights."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_decode")
    if entry is None:
        return None
    return device_parts.part_ms_a_run(entry, "projection", "mlp")
