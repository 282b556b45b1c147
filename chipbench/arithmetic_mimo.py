"""Parameters, bytes and operations of a served MiMo-V2-Flash decoder
(``model_type`` mimo_v2_flash: ``drivers/serve_mimo.py``): window layers and
full layers with key/value heads of their own, keys wider than values,
routed experts of which a chip holds a share; computed from shapes.  Kept
with the benchmark so that no later PR can move a utilisation by changing
how the work is counted.

``config`` is the configuration file's dict under the published file's own
names: ``n_routed_experts`` counts the experts HELD here,
``published["n_routed_experts"]`` the router's width;
``hybrid_layer_pattern`` (0 full, 1 window) and ``moe_layer_freq`` (1
routed) name the layers that are built."""


def pattern(config: dict) -> list:
    return config["hybrid_layer_pattern"][:config["num_hidden_layers"]]


def full_layers(config: dict) -> int:
    return pattern(config).count(0)


def window_layers(config: dict) -> int:
    return pattern(config).count(1)


def expert_layers(config: dict) -> int:
    return sum(config["moe_layer_freq"][:config["num_hidden_layers"]])


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose."""
    return 3 * hidden * expert_width * itemsize


def kv_heads(config: dict, sliding: bool) -> int:
    return config["swa_num_key_value_heads" if sliding
                  else "num_key_value_heads"]


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary (norm
    weights, the sinks and the routers' biases, some thousands, left
    out): ``full_attention`` and ``window_attention`` (q, k, v, o),
    ``dense_mlp``, ``router`` (as wide as the published layer),
    ``routed_expert`` (one of them), ``routed_mlp`` (router and the HELD
    routed experts), ``vocabulary`` (embedding and head of the rows
    held)."""
    c, h = config, config["hidden_size"]
    heads, d, dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]

    def attention(kv):
        return h * (heads * d + kv * (d + dv)) + heads * dv * h

    expert = 3 * h * c["moe_intermediate_size"]
    router = h * c["published"]["n_routed_experts"]
    return {"full_attention": attention(kv_heads(c, False)),
            "window_attention": attention(kv_heads(c, True)),
            "dense_mlp": 3 * h * c["intermediate_size"],
            "router": router, "routed_expert": expert,
            "routed_mlp": router + c["n_routed_experts"] * expert,
            "vocabulary": (1 if c["tie_word_embeddings"] else 2) *
            c["vocab_size"] * h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    and its share (beside the norms, the sinks and the routers' biases)."""
    p = layer_parameters(config)
    total = p["vocabulary"]
    for sliding, routed in zip(pattern(config), config["moe_layer_freq"]):
        total += p["window_attention" if sliding else "full_attention"]
        total += p["routed_mlp" if routed else "dense_mlp"]
    return total


def full_layer_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes ONE full layer holds a position: its key heads' and its value
    heads' channels, nothing padded (the caches hold the heads folded into
    the channels, whole lanes)."""
    return itemsize * kv_heads(config, False) * (
        config["head_dim"] + config["v_head_dim"])


def full_cache_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes all full layers hold of one position of one row."""
    return full_layers(config) * full_layer_bytes_per_position(
        config, itemsize)


def ring_bytes_per_row(config: dict, itemsize: int) -> int:
    """Bytes of one row's ring of ONE window layer, whatever the context."""
    return config["sliding_window"] * itemsize * kv_heads(config, True) * (
        config["head_dim"] + config["v_head_dim"])


def full_decode_work(config: dict, held: float, itemsize: int) -> dict:
    """The least the full layers' decode cores do over ``held`` positions
    (summed over the rows and the ticks, one count for all layers: the
    program's counter ``alpa_serving_decode_positions_total``): every
    query head's product with its key head's channels and the
    probabilities' with its value head's, and every held position's keys
    and values read once a layer.  The queries, the output and the new
    position's write are left out."""
    heads, d, dv = (config["num_attention_heads"], config["head_dim"],
                    config["v_head_dim"])
    return {"flops": full_layers(config) * held * heads * 2 * (d + dv),
            "bytes": held * full_cache_bytes_per_position(config, itemsize)}
