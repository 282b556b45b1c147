"""Operations and bytes an algorithm needs, computed from shapes.  Kept
with the benchmark so that no later PR can move a utilisation by changing
how the work is counted."""


def decoder_train_flops_per_token(hidden: int, layers: int, seq_len: int,
                                  vocab: int) -> float:
    """Model FLOPs of one token's forward and backward pass through a dense
    pre-LN decoder with a 4x MLP and a (tied) vocabulary head, recomputed
    operations not counted.  The arithmetic of Alpa's ``compute_gpt_tflops``
    (Megatron-LM's formula): per layer 24 h^2 forward for the four
    projections and the MLP plus 4 s h for scores and values, three times
    that with the backward pass; 6 h V for the head."""
    per_layer = 72 * hidden**2 * (1 + seq_len / (6 * hidden))
    return layers * per_layer + 6 * hidden * vocab


def kv_bytes_per_position(hidden: int, layers: int, cache_itemsize: int) -> int:
    """Bytes of keys and values one cached position holds over all layers."""
    return 2 * layers * hidden * cache_itemsize


def decode_tick_bytes(weight_bytes: float, kv_positions_read: float,
                      hidden: int, layers: int, cache_itemsize: int) -> float:
    """Bytes one decode tick has to read from HBM: every weight once (the
    batch shares them) and the keys and values of every position the rows
    attend to.  Activations and the few embedding rows are left out."""
    return weight_bytes + kv_positions_read * kv_bytes_per_position(
        hidden, layers, cache_itemsize)
