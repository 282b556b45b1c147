"""Tokens a forward of a block decides, a row: the rise of the program's
counter ``alpa_serving_block_tokens_unmasked_total`` over the rise of
``alpa_serving_block_forwards_total`` of both phases (row-forwards of the
active rows: every tick is one forward a row, denoising or committing),
over the window.  Under the static rule with two denoising forwards and
one commit a block of four it is 4/3; a program that carried a commit and
the next block's first denoising forward in one forward would read 2.
Nothing where the program has no such counters."""
from chipbench import counters

FORWARDS = 'alpa_serving_block_forwards_total{phase="%s"}'


def read(obs):
    unmasked = counters.delta(obs,
                              "alpa_serving_block_tokens_unmasked_total")
    forwards = [counters.delta(obs, FORWARDS % phase)
                for phase in ("denoise", "commit")]
    if unmasked is None or not any(forwards):
        return None
    return unmasked / sum(f or 0.0 for f in forwards)
