"""Operations and bytes of a decoder with routed experts, computed from
shapes (the companion of ``arithmetic.py`` for ``drivers/train_lm.py``).
Kept with the benchmark so that no later PR can move a utilisation by
changing how the work is counted."""


def moe_decoder_train_flops_per_token(hidden: int, layers: int, seq_len: int,
                                      vocab: int, expert_width: int,
                                      num_experts: int,
                                      experts_per_tok: int) -> float:
    """Model FLOPs of one token's forward and backward pass (three times
    the forward's multiply-adds, twice over) through a decoder whose every
    layer has full multi-head attention and top-k routed gated experts, and
    an untied vocabulary head; ACTIVE parameters only, recomputed
    operations not counted.  Per layer: 24 h^2 for the four attention
    projections, 12 s h for scores and values over the whole context (the
    convention of ``arithmetic.decoder_train_flops_per_token``: no causal
    discount), 6 h E for the router, 18 k h w for the k experts' three
    matrices of h x w; 6 h V for the head."""
    per_layer = (24 * hidden**2 + 12 * seq_len * hidden +
                 6 * hidden * num_experts +
                 18 * experts_per_tok * hidden * expert_width)
    return layers * per_layer + 6 * hidden * vocab


def expert_grouped_matmul_work(tokens: int, hidden: int, expert_width: int,
                               num_experts: int, experts_per_tok: int,
                               itemsize: int) -> tuple:
    """(operations, bytes) the grouped matmuls of ONE expert layer need in
    one step, forward and backward.  There are two products over the
    ``tokens * experts_per_tok`` rows: rows x (h, 2w) for gate and up at
    once, rows x (w, h) for down.  Each runs three times (forward, the
    gradient of the rows, the gradient of the weights), each time 2 m k n
    operations, and each time reads or writes the rows (m, k), the weights
    (E, k, n) and the result (m, n) once, at ``itemsize`` bytes."""
    m = tokens * experts_per_tok
    flops = bytes_ = 0
    for k, n in ((hidden, 2 * expert_width), (expert_width, hidden)):
        flops += 3 * 2 * m * k * n
        bytes_ += 3 * (m * k + num_experts * k * n + m * n) * itemsize
    return flops, bytes_
