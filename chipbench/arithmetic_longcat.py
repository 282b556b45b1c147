"""Parameters and bytes of a served LongCat-Flash decoder (``model_type``
longcat_flash: ``drivers/serve_scmoe.py``) of which a chip holds one share
of each layer's experts, computed from shapes.  Kept with the benchmark so
that no later PR can move a utilisation by changing how the work is
counted.

``config`` is the configuration file's dict under the published file's own
names: ``num_layers`` counts PUBLISHED layers (each two attentions, two
dense MLPs and one expert branch: two cache entries), ``n_routed_experts``
the experts HELD here, ``published["n_routed_experts"]`` with
``zero_expert_num`` the router's width."""


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose."""
    return 3 * hidden * expert_width * itemsize


def expert_layers(config: dict) -> int:
    """Layers that route: every published layer has one expert branch."""
    return config["num_layers"]


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one published layer and of the
    vocabulary (norm weights, some thousands, left out): ``attention``
    (ONE of the two: q_a, q_b, kv_a, kv_b, o), ``dense_mlp`` (one of the
    two), ``router`` (as wide as the published layer with its identity
    experts; its bias, 768 values, left out), ``routed_expert`` (one),
    ``outside_experts`` (both attentions, both MLPs, the router),
    ``layer`` (that and the HELD experts), ``vocabulary`` (embedding and
    head of the rows held)."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    attention = (h * q_rank + q_rank * heads * (dn + dr) +
                 h * (kv_rank + dr) + kv_rank * heads * (dn + dv) +
                 heads * dv * h)
    dense = 3 * h * config["ffn_hidden_size"]
    expert = 3 * h * config["expert_ffn_hidden_size"]
    router = h * (config["published"]["n_routed_experts"] +
                  config["zero_expert_num"])
    outside = 2 * attention + 2 * dense + router
    return {"attention": attention, "dense_mlp": dense, "router": router,
            "routed_expert": expert, "outside_experts": outside,
            "layer": outside + config["n_routed_experts"] * expert,
            "vocabulary": 2 * config["vocab_size"] * h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    and its share (beside the norms and the routers' biases)."""
    p = layer_parameters(config)
    return config["num_layers"] * p["layer"] + p["vocabulary"]


def kv_cache_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes of one position of the resident caches over all layers: each
    of a layer's two attentions holds its latent and the shared rotary
    key, and nothing a head."""
    return 2 * config["num_layers"] * itemsize * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"])


def decode_tick_bytes(config: dict, experts_touched_per_layer: float,
                      positions: float, itemsize: int) -> dict:
    """The least one decode tick has to read, by piece: every attention,
    dense-MLP and router weight once; of the routed experts held those
    that a row chose (``experts_touched_per_layer`` a layer, the
    program's count); of the caches the ``positions`` its active rows
    hold (summed over the rows), not the caches' length; the head's
    slice.  The embedding's rows, every activation and every write are
    left out: a true lower bound."""
    p = layer_parameters(config)
    layers = config["num_layers"]
    return {
        "outside_experts": layers * p["outside_experts"] * itemsize,
        "routed_experts": layers * experts_touched_per_layer *
            p["routed_expert"] * itemsize,
        "cache": positions * kv_cache_bytes_per_position(config, itemsize),
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
    }
