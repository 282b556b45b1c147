"""Parameters, bytes and operations of a served dots3-note decoder
(``model_type`` dots3_note: ``drivers/serve_dsa.py``): latent attention over
the positions an indexer selects in its full layers, latent attention of
other widths under a window in its sliding layers, routed experts of which
a chip holds a share; computed from shapes.  Kept with the benchmark so
that no later PR can move a utilisation by changing how the work is
counted.

``config`` is the configuration file's dict under the published file's own
names: ``n_routed_experts`` counts the experts HELD here,
``published["n_routed_experts"]`` the router's width, ``layer_types`` the
published list, of which the leading ``num_hidden_layers`` are built."""


def layer_types(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def full_layers(config: dict) -> int:
    """Layers that select their positions."""
    return layer_types(config).count("full_attention")


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose."""
    return 3 * hidden * expert_width * itemsize


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def _latent_attention(h, heads, q_rank, kv_rank, dn, dr, dv) -> int:
    """q_a, q_b, kv_a, kv_b, the head-wise gate, o."""
    return (h * q_rank + q_rank * heads * (dn + dr) + h * (kv_rank + dr) +
            kv_rank * heads * (dn + dv) + h * heads + heads * dv * h)


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary (norm
    weights and the routers' biases, some thousands, left out):
    ``full_attention`` (with its gate, without its indexer), ``indexer``
    (its three matrices), ``sliding_attention``, ``dense_mlp``, ``router``
    (as wide as the published layer), ``shared_experts``,
    ``routed_expert`` (one of them), ``routed_mlp`` (router, shared and
    the HELD routed experts), ``vocabulary`` (embedding and head of the
    rows held)."""
    c, h = config, config["hidden_size"]
    expert = 3 * h * c["moe_intermediate_size"]
    router = h * c["published"]["n_routed_experts"]
    shared = c["n_shared_experts"] * expert
    return {
        "full_attention": _latent_attention(
            h, c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]),
        "indexer": (c["q_lora_rank"] * c["index_n_heads"] *
                    c["index_head_dim"] + h * c["index_head_dim"] +
                    h * c["index_n_heads"]),
        "sliding_attention": _latent_attention(
            h, c["swa_num_attention_heads"], c["swa_q_lora_rank"],
            c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
            c["swa_qk_rope_head_dim"], c["swa_v_head_dim"]),
        "dense_mlp": 3 * h * c["intermediate_size"],
        "router": router, "shared_experts": shared,
        "routed_expert": expert,
        "routed_mlp": router + shared + c["n_routed_experts"] * expert,
        "vocabulary": (1 if c["tie_word_embeddings"] else 2) *
        c["vocab_size"] * h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    and its share (beside the norms, the index keys' LayerNorm and the
    routers' biases)."""
    p = layer_parameters(config)
    total = p["vocabulary"]
    for i, kind in enumerate(layer_types(config)):
        total += p["full_attention"] + p["indexer"] \
            if kind == "full_attention" else p["sliding_attention"]
        total += p["dense_mlp"] if i < config["first_k_dense_replace"] \
            else p["routed_mlp"]
    return total


LANES = 128


def full_layer_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes a full layer holds a position: the latent, the shared rotary
    key and the index key."""
    return itemsize * (config["kv_lora_rank"] + config["qk_rope_head_dim"] +
                       config["index_head_dim"])


def full_layer_bytes_held_per_position(config: dict, itemsize: int) -> int:
    """Bytes a full layer's cache takes a position: the latent and the
    shared rotary key in one row of whole lanes (the program's
    ``latent_row_width``: 512 + 64 channels lie in 640), and the index
    key."""
    row = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) //
            LANES) * LANES
    return itemsize * (row + config["index_head_dim"])


def ring_bytes_per_row(config: dict, itemsize: int) -> int:
    """Bytes of one row's ring of a sliding layer: the window's latents
    and shared rotary keys, whatever the context."""
    return config["sliding_window_size"] * itemsize * (
        config["swa_kv_lora_rank"] + config["swa_qk_rope_head_dim"])


def kv_cache_bytes_per_position(config: dict, itemsize: int,
                                served_context: int) -> float:
    """Bytes of one position of one row in the resident caches over all
    layers: the full layers' by position as the cache holds them, and the
    sliding layers' rings spread over the served context."""
    kinds = layer_types(config)
    return (kinds.count("full_attention") *
            full_layer_bytes_held_per_position(config, itemsize) +
            kinds.count("sliding_attention") *
            ring_bytes_per_row(config, itemsize) / served_context)


def index_scores_work(config: dict, queries: float, keys: float,
                      itemsize: int) -> dict:
    """The least one full layer's indexer does to score ``keys`` positions
    for each of ``queries`` queries (summed over the rows: ``queries x
    keys`` is the number of scored pairs): a product of ``index_head_dim``
    channels, a relu and a weighted sum a head and pair; every scored
    position's index key read once a row (``keys`` here is then the
    positions read), the queries and the scores left out."""
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    return {"flops": queries * keys * heads * (2 * dim + 3),
            "bytes": keys * dim * itemsize}


def selected_core_work(config: dict, selected: float, itemsize: int) -> dict:
    """The least one full layer's absorbed core does over ``selected``
    gathered positions (summed over the rows): every head's query against
    the latent and the shared rotary key, and the probabilities against
    the latent; each selected position's row (latent and key) read once."""
    heads = config["num_attention_heads"]
    rank, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {"flops": selected * heads * 2 * (rank + dr + rank),
            "bytes": selected * (rank + dr) * itemsize}


def latent_select_decode_bytes(config: dict, held: float, selected: float,
                               itemsize: int) -> float:
    """The least the decode ticks' selecting layers read of their caches:
    the index key of every position held (the indexer scores all of them)
    and the row of every position selected (``held`` and ``selected``
    summed over the layers, the rows and the ticks: the program's counter
    ``alpa_serving_select_positions_total``)."""
    return (index_scores_work(config, 1, held, itemsize)["bytes"] +
            selected_core_work(config, selected, itemsize)["bytes"])
