"""Parameters, bytes and operations of a served Nemotron-H decoder
(``model_type`` nemotron_h: layers that are a Mamba-2 mixer, an attention
or routed experts ALONE, ``drivers/serve_ssm.py``), computed from the keys
of the configuration file.  Kept with the benchmark so that no later PR
can move a utilisation by changing how the work is counted.

The file counts the experts HELD here (``n_routed_experts``) and the
vocabulary's slice; the router keeps ``published.n_routed_experts``."""

# bytes of one value of an ssm state, whatever the model's dtype
STATE_ITEMSIZE = 4
# operations a state value a position in the recurrence's LINEAR form: the
# decay's product, ``dt x B^T`` (two), the sum, ``S C`` (a product and a
# sum); what any form of the scan does at least
SCAN_OPS_A_STATE_VALUE = 6


def pattern(config: dict) -> str:
    """The letters of the layers the file runs (of a pattern longer than
    ``num_hidden_layers`` the leading ones)."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def layers_of(config: dict, letter: str) -> int:
    return pattern(config).count(letter)


def expert_layers(config: dict) -> int:
    return layers_of(config, "E")


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of ONE routed expert's TWO matrices (up of hidden x width,
    down of width x hidden; no gate): what a decode tick has to read of an
    expert that at least one of its rows chose."""
    return 2 * hidden * expert_width * itemsize


def mamba_widths(config: dict) -> dict:
    """``inner`` (heads x head size), ``conv`` (what the convolution runs
    over: ``[x | B | C]``), ``in_proj`` (``[z | xBC | dt]``) and ``state``
    (values of one row's ssm state in one layer)."""
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    inner = heads * p
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return {"inner": inner, "conv": conv, "in_proj": inner + conv + heads,
            "state": inner * config["ssm_state_size"]}


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces, each layer's ONE norm included in
    ``mamba``, ``attention`` and ``expert_outside`` (the router with its
    stored bias, the shared expert, the norm); ``routed_expert`` is one of
    them; ``vocabulary`` both tables and the final norm."""
    h, w = config["hidden_size"], mamba_widths(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    routers = config.get("published", config)["n_routed_experts"]
    return {
        "mamba": h * w["in_proj"] + (config["conv_kernel"] + 1) * w["conv"] +
        3 * config["mamba_num_heads"] + w["inner"] + w["inner"] * h + h,
        "attention": h * q + 2 * h * kv + q * h + h,
        "routed_expert": 2 * h * config["moe_intermediate_size"],
        "expert_outside": h * routers + routers + config["n_shared_experts"]
        * 2 * h * config["moe_shared_expert_intermediate_size"] + h,
        "vocabulary": 2 * config["vocab_size"] * h + h,
    }


def model_parameters(config: dict) -> int:
    """Parameters of the whole configuration as the file states its depth
    and its share."""
    p = layer_parameters(config)
    return (layers_of(config, "M") * p["mamba"] +
            layers_of(config, "*") * p["attention"] +
            layers_of(config, "E") * (
                p["expert_outside"] +
                config["n_routed_experts"] * p["routed_expert"]) +
            p["vocabulary"])


def state_bytes_per_row(config: dict, itemsize: int) -> int:
    """Bytes ONE row holds in the Mamba-2 layers' states, over all of
    them, whatever its length: the ssm state (float32) and the last
    ``conv_kernel - 1`` positions of what the convolution runs over."""
    w = mamba_widths(config)
    return layers_of(config, "M") * (
        w["state"] * STATE_ITEMSIZE +
        (config["conv_kernel"] - 1) * w["conv"] * itemsize)


def attention_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes ONE position of ONE row holds over all attention layers: K
    and V of the key/value heads."""
    return layers_of(config, "*") * 2 * config["num_key_value_heads"] * \
        config["head_dim"] * itemsize


def tick_bytes(config: dict, experts_touched_per_layer: float,
               active_rows: float, positions: float, itemsize: int) -> dict:
    """The least one decode tick has to move, by piece: every mixer's,
    router's and shared expert's weights once; of the routed experts held
    those that a row chose (``experts_touched_per_layer`` a layer, the
    program's count); the states of the ``active_rows`` that held a
    request, once in and once out; of the attention caches the
    ``positions`` those rows hold (summed over the rows), not the caches'
    length; the head's slice.  The embedding's rows, every activation,
    the free rows' states and the caches' writes are left out: a true
    lower bound."""
    p = layer_parameters(config)
    return {
        "mamba_weights": layers_of(config, "M") * p["mamba"] * itemsize,
        "attention_weights": layers_of(config, "*") * p["attention"] *
        itemsize,
        "expert_outside": layers_of(config, "E") * p["expert_outside"] *
        itemsize,
        "routed_experts": layers_of(config, "E") *
        experts_touched_per_layer * p["routed_expert"] * itemsize,
        "states": 2 * active_rows * state_bytes_per_row(config, itemsize),
        "attention_caches": positions *
        attention_bytes_per_position(config, itemsize),
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
    }


def mamba_chunk_flops(config: dict, positions: int) -> int:
    """Operations of ONE Mamba-2 mixer on ``positions`` new positions: the
    two projections (2 a multiply-add) and the recurrence in its linear
    form (``SCAN_OPS_A_STATE_VALUE``); the convolution, the gate and the
    norm are left out."""
    h, w = config["hidden_size"], mamba_widths(config)
    return positions * (2 * h * w["in_proj"] + 2 * w["inner"] * h +
                        SCAN_OPS_A_STATE_VALUE * w["state"])
