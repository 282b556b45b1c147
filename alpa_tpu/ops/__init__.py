"""TPU kernels (pallas) and kernel-backed ops.

New capability vs the reference (SURVEY.md §2.7: sequence parallelism is
ABSENT in Alpa): fused attention (``flash_attention``: forward and backward
kernels that keep the scores in fast memory) plus two sequence-parallel
designs — ring attention (k/v rotation) and
Ulysses (all-to-all head redistribution) — make long-context training a
first-class citizen of this framework.
"""
from alpa_tpu.ops.ring_attention import (make_ring_attention_fn,
                                         ring_attention)
from alpa_tpu.ops.ulysses_attention import (make_ulysses_attention_fn,
                                            ulysses_attention)
