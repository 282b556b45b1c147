"""Parameters, bytes and operations of a served decoder of grouped-query
attention over softmax-routed experts that generates by diffusion over
blocks (``model_type`` sdar_moe: ``drivers/serve_diffusion.py``), computed
from shapes.  Kept with the benchmark so that no later PR can move a
utilisation by changing how the work is counted.

``config`` is the configuration file's dict (the keys of its
``config.json``)."""


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a forward has to read of
    an expert that at least one of its positions chose."""
    return 3 * hidden * expert_width * itemsize


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary:
    ``attention`` (q, k, v, o and the two head norms), ``router``,
    ``routed_expert`` (one of them), ``norms`` (the block's two),
    ``layer`` (all of it, every expert), ``vocabulary`` (embedding, head
    and the final norm)."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    attention = h * (q + 2 * kv) + q * h + 2 * d
    expert = 3 * h * config["moe_intermediate_size"]
    router = h * config["num_experts"]
    return {"attention": attention, "router": router,
            "routed_expert": expert, "norms": 2 * h,
            "layer": attention + router + 2 * h +
            config["num_experts"] * expert,
            "vocabulary": (1 if config["tie_word_embeddings"] else 2) *
            config["vocab_size"] * h + h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration at the depth the file states
    (every layer an expert layer: ``decoder_sparse_step`` 1, no
    ``mlp_only_layers``)."""
    p = layer_parameters(config)
    return config["num_hidden_layers"] * p["layer"] + p["vocabulary"]


def kv_cache_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes one position holds in the caches of all layers: K and V of
    the key/value heads."""
    return config["num_hidden_layers"] * 2 * \
        config["num_key_value_heads"] * config["head_dim"] * itemsize


def block_step_least_bytes(config: dict, experts_touched: float,
                           positions_held: float, itemsize: int) -> dict:
    """The least one block step has to read, by piece: the attention and
    router weights of every layer and the head once; of the routed experts
    the ``experts_touched`` distinct ones its positions chose (summed over
    the layers); of the caches the ``positions_held`` positions its rows
    hold (summed over the rows; the program's attention reads every cache
    to its full length, which is its business and not counted).  The
    embedding's rows, the activations and what is written are left out: a
    true lower bound."""
    p = layer_parameters(config)
    layers = config["num_hidden_layers"]
    return {
        "attention_weights": layers * p["attention"] * itemsize,
        "routers": layers * p["router"] * itemsize,
        "routed_experts": experts_touched * p["routed_expert"] * itemsize,
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
        "caches": positions_held *
        kv_cache_bytes_per_position(config, itemsize),
    }


def block_step_flops(config: dict, rows: int, block: int,
                     positions_held: float) -> dict:
    """Operations of one block step over ``rows`` rows of ``block``
    positions (2 a multiply-add): the projections, the scores and values
    over the positions the rows hold, the router, the chosen experts, the
    head over every position."""
    p = layer_parameters(config)
    layers, tokens = config["num_hidden_layers"], rows * block
    q = config["num_attention_heads"] * config["head_dim"]
    return {
        "projections": 2 * tokens * layers * (p["attention"] - 2 *
                                              config["head_dim"]),
        "attention_core": 2 * 2 * block * positions_held * q * layers,
        "router": 2 * tokens * layers * p["router"],
        "experts": 2 * tokens * layers * config["num_experts_per_tok"] *
        p["routed_expert"],
        "head": 2 * tokens * config["vocab_size"] * config["hidden_size"],
    }
