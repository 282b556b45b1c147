"""The gated short convolutions of the chunk step against their COMPUTE
roofline: the operations the conv mixers of one chunk need
(``arithmetic_lfm2.conv_mixer_flops`` a layer over the chunk's positions,
real or padding, which the program computes alike: both products, the two
gates, the taps) times the traced runs of ``jit_chunk_prefill``, over the
bf16 peak, over the device time of the part ``short_conv`` in those runs
(``Capture.device_time()``).  At 1,024 positions the products do 1,024
operations a byte of weight, past the chip's 240: compute bounds them.
The chunk step compiled for the described v5e (13 layers, PR 38): all 20
products of the ten mixers run in fusions counted as ``short_conv``, and no
event counted as another part holds an instruction of a mixer
(``tests/serve/test_decode_in_place.py::
test_lfm2_chunk_step_runs_the_mixers_products_in_their_part`` holds that
at no chip time).  What the compiler streams ahead here too (60
``copy-start`` and 80 ``slice-start`` pairs that the part inherits) moves
bytes, not operations, and what the part's fusions took in besides (the
norm before a mixer, the residual sum after it: 30 of its 80 fusions are
mixed) only lengthens its time: the share errs low and cannot pass 100.

Why not the decode's share of its MEMORY roofline, which ISSUE 38 named
(``conv_decode_hbm_roofline_pct``): on the chip it read 150 % (PERF.md
section 6, PR 38).  The compiler streams ``W_in`` and ``W_out`` ahead into
fast memory under the expert kernels of the layer before, so a third of
the mixers' bytes move in time that belongs to other parts: the time
leaves out part of the work, and no reading of the table mends that.
Nothing where the program has no such part or the driver gives no
operations."""
from chipbench import device_parts


def read(obs):
    entry = device_parts.program("jit_chunk_prefill")
    if entry is None or obs["peaks"] is None or \
            not obs.get("conv_flops_per_chunk"):
        return None
    from alpa_tpu.telemetry.device_time import part_seconds
    conv_s = part_seconds(entry, "short_conv")
    if not conv_s:
        return None
    least_s = entry["runs"] * obs["conv_flops_per_chunk"] / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / conv_s
