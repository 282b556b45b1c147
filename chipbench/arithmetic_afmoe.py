"""Parameters and bytes of a served decoder of window and full attention
layers over sigmoid-routed experts beside a shared one (``model_type``
afmoe: ``drivers/serve_lm.py``), computed from shapes.  Kept with the
benchmark so that no later PR can move a utilisation by changing how the
work is counted."""


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose, whatever the
    number of rows."""
    return 3 * hidden * expert_width * itemsize


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary, from
    the keys of the configuration file (norm weights, some thousands, left
    out): ``attention`` (q, k, v, the output gate, o), ``dense_mlp``,
    ``router``, ``shared_expert``, ``routed_expert`` (one of them),
    ``expert_layer`` (router, shared and all routed experts, with its
    attention), ``vocabulary`` (embedding and head)."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    attention = h * q + 2 * h * kv + h * q + q * h
    expert = 3 * h * config["moe_intermediate_size"]
    router = h * config["num_experts"]
    shared = config["num_shared_experts"] * expert
    return {"attention": attention,
            "dense_mlp": 3 * h * config["intermediate_size"],
            "router": router, "shared_expert": shared,
            "routed_expert": expert,
            "expert_layer": attention + router + shared +
            config["num_experts"] * expert,
            "vocabulary": (1 if config["tie_word_embeddings"] else 2) *
            config["vocab_size"] * h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    (``num_dense_layers`` leading dense layers, expert layers after)."""
    p = layer_parameters(config)
    dense = config["num_dense_layers"]
    return (dense * (p["attention"] + p["dense_mlp"]) +
            (config["num_hidden_layers"] - dense) * p["expert_layer"] +
            p["vocabulary"])


def kv_cache_bytes(config: dict, rows: int, served_context: int,
                   itemsize: int) -> dict:
    """Bytes of the engine's resident caches by kind: a ``window`` layer
    holds ``sliding_window`` positions a row, a ``full`` layer the served
    context; a position is K and V of the key/value heads."""
    position = 2 * config["num_key_value_heads"] * config["head_dim"] * \
        itemsize
    out = {"window": 0, "full": 0}
    for kind in config["layer_types"]:
        if kind == "sliding_attention":
            out["window"] += rows * min(config["sliding_window"],
                                        served_context) * position
        else:
            out["full"] += rows * served_context * position
    return out


def decode_tick_bytes(config: dict, rows: int, served_context: int,
                      experts_touched_per_layer: float,
                      itemsize: int) -> dict:
    """Bytes one decode tick has to read, by piece: every attention,
    dense, router, shared-expert and head weight once; of the routed
    experts those that a row chose; the whole of every cache, as the
    program's attention reads it (padded to its length, not to the rows'
    positions); ``rows`` embedding rows.  Activations are left out."""
    p = layer_parameters(config)
    dense = config["num_dense_layers"]
    expert_layers = config["num_hidden_layers"] - dense
    caches = kv_cache_bytes(config, rows, served_context, itemsize)
    return {
        "attention_weights":
            config["num_hidden_layers"] * p["attention"] * itemsize,
        "dense_mlp": dense * p["dense_mlp"] * itemsize,
        "router_and_shared":
            expert_layers * (p["router"] + p["shared_expert"]) * itemsize,
        "routed_experts": expert_layers * experts_touched_per_layer *
            p["routed_expert"] * itemsize,
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
        "embedding_rows": rows * config["hidden_size"] * itemsize,
        "caches": caches["window"] + caches["full"],
    }
