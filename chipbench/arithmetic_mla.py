"""Parameters, bytes and operations of a served decoder of latent attention
(MLA) over group-limited routed experts of which a chip holds a share
(``model_type`` deepseek_v2: ``drivers/serve_mla.py``), computed from
shapes.  Kept with the benchmark so that no later PR can move a
utilisation by changing how the work is counted.

``config`` is the configuration file's dict: ``n_routed_experts`` counts
the experts HELD here, ``published["n_routed_experts"]`` the router's
width."""


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose."""
    return 3 * hidden * expert_width * itemsize


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary (norm
    weights, some thousands, left out): ``attention`` (q_a, q_b, kv_a,
    kv_b, o), ``dense_mlp``, ``router`` (as wide as the published layer),
    ``shared_experts``, ``routed_expert`` (one of them), ``expert_layer``
    (router, shared and the HELD routed experts, with its attention),
    ``vocabulary`` (embedding and head of the rows held)."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    attention = (h * q_rank + q_rank * heads * (dn + dr) +
                 h * (kv_rank + dr) + kv_rank * heads * (dn + dv) +
                 heads * dv * h)
    expert = 3 * h * config["moe_intermediate_size"]
    router = h * config["published"]["n_routed_experts"]
    shared = config["n_shared_experts"] * expert
    return {"attention": attention,
            "dense_mlp": 3 * h * config["intermediate_size"],
            "router": router, "shared_experts": shared,
            "routed_expert": expert,
            "expert_layer": attention + router + shared +
            config["n_routed_experts"] * expert,
            "vocabulary": (1 if config["tie_word_embeddings"] else 2) *
            config["vocab_size"] * h}


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    (``first_k_dense_replace`` leading dense layers, expert layers
    after) and its share."""
    p = layer_parameters(config)
    dense = config["first_k_dense_replace"]
    return (dense * (p["attention"] + p["dense_mlp"]) +
            expert_layers(config) * p["expert_layer"] + p["vocabulary"])


def kv_cache_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes of one position of the resident cache over all layers: a
    layer holds the normed latent and the shared rotary key, and nothing a
    head."""
    return config["num_hidden_layers"] * itemsize * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"])


def per_head_cache_bytes_per_position(config: dict, itemsize: int) -> int:
    """What per-head keys and values of the same model would hold."""
    return config["num_hidden_layers"] * itemsize * \
        config["num_attention_heads"] * (
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"] +
            config["v_head_dim"])


def absorbed_core_work(config: dict, positions: int, itemsize: int) -> dict:
    """The least the decode ticks' attention cores have to do over all
    layers in the absorbed form, for ``positions`` cache positions attended
    (summed over the rows and the ticks; 32 rows at the full 16,384 are
    524,288 a tick): ``flops`` of the scores (every head's query against
    ``kv_lora_rank + qk_rope_head_dim`` channels of every position) and of
    the values (``kv_lora_rank``), and ``bytes`` of those positions' cache
    read once.  The absorption's own two products and the cache's write
    are left out (a thousandth)."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    rank, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {"flops": 2 * layers * heads * positions * (2 * rank + dr),
            "bytes": positions * kv_cache_bytes_per_position(config,
                                                             itemsize)}


def expanded_core_flops(config: dict, queries: int, keys: int) -> int:
    """Operations of ONE layer's expanded attention core for ``queries``
    new positions over ``keys`` cached ones: the keys' and values'
    expansion from their latents, the scores and the values."""
    heads = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    return 2 * heads * (keys * config["kv_lora_rank"] * (dn + dv) +
                        queries * keys * (dn + dr + dv))


def decode_tick_bytes(config: dict, rows: int, served_context: int,
                      experts_touched_per_layer: float,
                      itemsize: int) -> dict:
    """Bytes one decode tick has to read, by piece: every attention,
    dense, router, shared-expert and head weight once; of the routed
    experts held those that a row chose; the whole cache once; ``rows``
    embedding rows.  Activations are left out."""
    p = layer_parameters(config)
    dense, routed = config["first_k_dense_replace"], expert_layers(config)
    return {
        "attention_weights":
            config["num_hidden_layers"] * p["attention"] * itemsize,
        "dense_mlp": dense * p["dense_mlp"] * itemsize,
        "router_and_shared":
            routed * (p["router"] + p["shared_experts"]) * itemsize,
        "routed_experts": routed * experts_touched_per_layer *
            p["routed_expert"] * itemsize,
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
        "embedding_rows": rows * config["hidden_size"] * itemsize,
        "cache": rows * served_context *
            kv_cache_bytes_per_position(config, itemsize),
    }
