"""Parameters, bytes and operations of a served decoder of gated short
convolutions and grouped-query attention layers over sigmoid-routed
experts (``model_type`` lfm2_moe: ``drivers/serve_hybrid.py``), computed
from shapes.  Kept with the benchmark so that no later PR can move a
utilisation by changing how the work is counted."""


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's three matrices (gate and up of
    hidden x width, down of width x hidden): what a decode tick has to
    read of an expert that at least one of its rows chose, whatever the
    number of rows."""
    return 3 * hidden * expert_width * itemsize


def conv_mixer_parameters(hidden: int, taps: int) -> int:
    """``W_in`` hidden x 3 hidden, ``W_out`` hidden x hidden, ``taps``
    taps a channel; no bias."""
    return 3 * hidden * hidden + hidden * hidden + taps * hidden


def conv_state_bytes(hidden: int, taps: int, itemsize: int) -> int:
    """Bytes ONE row holds in ONE conv layer: the last ``taps - 1``
    positions of the product the taps run over, whatever the row's
    length."""
    return (taps - 1) * hidden * itemsize


def conv_mixer_bytes(hidden: int, taps: int, rows: int, itemsize: int) -> int:
    """The least ONE conv mixer moves in a decode tick of ``rows`` rows:
    its three weights once, every row's state read and written, every
    row's input read and output written.  What lies between (the 3 hidden
    wide product, the gates, the taps' sum) can stay in fast memory and is
    left out: a lower bound, so a share of the roofline computed from it
    cannot pass 100 %."""
    return (conv_mixer_parameters(hidden, taps) * itemsize +
            2 * rows * conv_state_bytes(hidden, taps, itemsize) +
            2 * rows * hidden * itemsize)


def conv_mixer_flops(hidden: int, taps: int, rows: int) -> int:
    """Operations of ONE conv mixer on ``rows`` new positions: the two
    products (2 a multiply-add), the gate ``B * X``, ``taps``
    multiply-adds a channel, the gate ``C * c``."""
    return rows * (2 * 3 * hidden * hidden + 2 * hidden * hidden +
                   hidden + 2 * taps * hidden + hidden)


def attention_position_bytes(config: dict, itemsize: int) -> int:
    """Bytes ONE position of ONE row holds in ONE attention layer: K and V
    of the key/value heads."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * head * itemsize


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces of one layer and of the vocabulary, from
    the keys of the configuration file, norm weights included (two of the
    hidden size a layer, two of the head size an attention layer, the
    final one): ``conv`` and ``attention`` (a mixer), ``dense_mlp``,
    ``router`` (with its stored bias), ``routed_expert`` (one of them),
    ``vocabulary`` (one table: the head is tied to it)."""
    h = config["hidden_size"]
    head = h // config["num_attention_heads"]
    q, kv = h, config["num_key_value_heads"] * head
    return {"conv": conv_mixer_parameters(h, config["conv_L_cache"]),
            "attention": h * q + 2 * h * kv + q * h + 2 * head,
            "dense_mlp": 3 * h * config["intermediate_size"],
            "router": h * config["num_experts"] + config["num_experts"],
            "routed_expert": 3 * h * config["moe_intermediate_size"],
            "norms": 2 * h,
            "vocabulary": config["vocab_size"] * h + h}


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its layers
    (``layer_types`` a mixer a layer, ``num_dense_layers`` leading dense
    layers, expert layers after)."""
    p = layer_parameters(config)
    total = p["vocabulary"]
    for i, kind in enumerate(config["layer_types"]):
        total += p["conv" if kind == "conv" else "attention"] + p["norms"]
        total += p["dense_mlp"] if i < config["num_dense_layers"] else \
            p["router"] + config["num_experts"] * p["routed_expert"]
    return total


def cache_bytes(config: dict, rows: int, served_context: int,
                itemsize: int) -> dict:
    """Bytes of the engine's resident caches by kind: a ``full`` layer
    holds the served context a row, a ``conv`` layer its state."""
    h, taps = config["hidden_size"], config["conv_L_cache"]
    n_conv = config["layer_types"].count("conv")
    n_full = len(config["layer_types"]) - n_conv
    return {"full": n_full * rows * served_context *
            attention_position_bytes(config, itemsize),
            "conv": n_conv * rows * conv_state_bytes(h, taps, itemsize)}


def decode_tick_bytes(config: dict, rows: int, served_context: int,
                      experts_touched_per_layer: float,
                      itemsize: int) -> dict:
    """Bytes one decode tick has to read, by piece: every mixer's, dense
    MLP's and router's weights and the head once; of the routed experts
    those that a row chose; the whole of every attention cache, as the
    program's attention reads it (to its length, not to the rows'
    positions); the conv layers' states."""
    p = layer_parameters(config)
    dense = config["num_dense_layers"]
    n_conv = config["layer_types"].count("conv")
    n_full = len(config["layer_types"]) - n_conv
    caches = cache_bytes(config, rows, served_context, itemsize)
    return {
        "conv_mixers": n_conv * p["conv"] * itemsize,
        "attention_weights": n_full * p["attention"] * itemsize,
        "dense_mlp": dense * p["dense_mlp"] * itemsize,
        "routers": (len(config["layer_types"]) - dense) * p["router"] *
        itemsize,
        "routed_experts": (len(config["layer_types"]) - dense) *
        experts_touched_per_layer * p["routed_expert"] * itemsize,
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
        "attention_caches": caches["full"],
        "conv_states": 2 * caches["conv"],
    }
