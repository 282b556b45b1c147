"""Parameters, bytes and operations of a served EvaByte decoder
(``model_type`` evabyte: attention over the exact keys of the query's own
aligned window beside one pooled summary for every chunk of the windows
before it, a gated MLP, several prediction heads of one matrix,
``drivers/serve_eva.py``), computed from the keys of the configuration
file.  Kept with the benchmark so that no later PR can move a utilisation
by changing how the work is counted."""

# the two learned pooling vectors of a head are float32 whatever the
# model's dtype; they are counted as parameters, not in the weights' bytes
POOLING_VECTORS = 2


def expert_layers(config: dict) -> int:
    """No layer routes."""
    return 0


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of one MLP's three matrices (``drivers/serve_mla.py`` asks;
    nothing here routes)."""
    return 3 * hidden * expert_width * itemsize


def head_size(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_parameters(config: dict) -> int:
    """One layer: q, k, v and o, the gated MLP's three matrices, two
    norms, and a head's two pooling vectors."""
    h = config["hidden_size"]
    return (4 * h * h + 3 * h * config["intermediate_size"] + 2 * h +
            POOLING_VECTORS * h)


def model_parameters(config: dict) -> int:
    """All layers, the table, the untied head of ``num_pred_heads`` groups
    of columns and the final norm."""
    h, v = config["hidden_size"], config["vocab_size"]
    return (config["num_hidden_layers"] * layer_parameters(config) +
            v * h + h * v * config["num_pred_heads"] + h)


def row_bytes(config: dict, itemsize: int) -> int:
    """Bytes of one row of either kind, keys and values together: a
    position's, or a chunk's summary."""
    return 2 * config["hidden_size"] * itemsize


def cache_bytes_per_row_a_layer(config: dict, itemsize: int,
                                served_context: int) -> int:
    """Bytes one row of the engine holds in one layer: a summary for every
    ``chunk_size`` positions of the served context and the rows of one
    window."""
    slots = -(-served_context // config["chunk_size"]) + \
        config["window_size"]
    return slots * row_bytes(config, itemsize)


def keys_seen(config: dict, position: int) -> tuple:
    """(exact keys, summaries) the query at ``position`` sees: its own
    window's rows up to itself, and a summary for every chunk of the
    windows before."""
    window = config["window_size"]
    return (position % window + 1,
            window // config["chunk_size"] * (position // window))


def attention_flops_a_pair(config: dict) -> int:
    """Operations a pair of a query and a key it sees, over all heads: the
    score's product and the value's, a multiply and an add a channel."""
    return 4 * config["hidden_size"]


def chunk_pairs(config: dict, positions: int) -> tuple:
    """(exact, summary) pairs of a query and a key it sees, over the
    queries at positions ``0 .. positions - 1`` (a chunked admission's
    padded prompt)."""
    window = config["window_size"]
    summaries = window // config["chunk_size"]
    whole, rest = divmod(positions, window)
    return (whole * window * (window + 1) // 2 + rest * (rest + 1) // 2,
            summaries * (window * whole * (whole - 1) // 2 + rest * whole))
