"""Parameters, bytes and operations of a served Jamba decoder
(``model_type`` jamba: Mamba-1 mixers beside a few attention layers, a
gated MLP behind every mixer, ``drivers/serve_s6.py``), computed from the
keys of the configuration file.  Kept with the benchmark so that no later
PR can move a utilisation by changing how the work is counted."""

# bytes of one value of an ssm state, whatever the model's dtype
STATE_ITEMSIZE = 4
# operations a state value a position in the recurrence's LINEAR form: the
# decay's product with ``dt`` and with the state, ``dt x B`` (two), the sum,
# ``h C`` (a product and a sum); what any form of the scan does at least
SCAN_OPS_A_STATE_VALUE = 6


def attention_layers(config: dict) -> int:
    return sum(i % config["attn_layer_period"] == config["attn_layer_offset"]
               for i in range(config["num_hidden_layers"]))


def mamba_layers(config: dict) -> int:
    return config["num_hidden_layers"] - attention_layers(config)


def expert_layers(config: dict) -> int:
    """No layer routes (``num_experts`` 1)."""
    return 0


def expert_bytes(hidden: int, expert_width: int, itemsize: int) -> int:
    """Bytes of one MLP's three matrices (``drivers/serve_mla.py`` asks;
    nothing here routes)."""
    return 3 * hidden * expert_width * itemsize


def mamba_widths(config: dict) -> dict:
    """``inner`` (the mixer's channels), ``x_proj`` (``[dt | B | C]``) and
    ``state`` (values of one row's ssm state in one layer)."""
    inner = config["mamba_expand"] * config["hidden_size"]
    return {"inner": inner,
            "x_proj": config["mamba_dt_rank"] + 2 * config["mamba_d_state"],
            "state": inner * config["mamba_d_state"]}


def layer_parameters(config: dict) -> dict:
    """Parameters of the pieces: a Mamba mixer, an attention, the MLP that
    is behind either, a layer's two norms, the table (tied) with the final
    norm."""
    h, w = config["hidden_size"], mamba_widths(config)
    inner, n, rank = w["inner"], config["mamba_d_state"], \
        config["mamba_dt_rank"]
    head = h // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    return {
        # in_proj, the taps and their bias, x_proj, the three inner norms,
        # dt_proj with its bias, A_log, D, out_proj
        "mamba": h * 2 * inner + (config["mamba_d_conv"] + 1) * inner +
        inner * w["x_proj"] + w["x_proj"] + rank * inner + inner +
        inner * n + inner + inner * h,
        "attention": 2 * h * h + 2 * h * kv,
        "mlp": 3 * h * config["intermediate_size"],
        "norms": 2 * h,
        "vocabulary": config["vocab_size"] * h + h,
    }


def model_parameters(config: dict) -> int:
    p = layer_parameters(config)
    return (mamba_layers(config) * p["mamba"] +
            attention_layers(config) * p["attention"] +
            config["num_hidden_layers"] * (p["mlp"] + p["norms"]) +
            p["vocabulary"])


def state_bytes_per_row(config: dict, itemsize: int) -> int:
    """Bytes ONE row holds in the Mamba layers' states, over all of them,
    whatever its length: the ssm state (float32) and the last
    ``mamba_d_conv - 1`` positions of the mixer's channels."""
    w = mamba_widths(config)
    return mamba_layers(config) * (
        w["state"] * STATE_ITEMSIZE +
        (config["mamba_d_conv"] - 1) * w["inner"] * itemsize)


def attention_bytes_per_position(config: dict, itemsize: int) -> int:
    """Bytes ONE position of ONE row holds over all attention layers: K
    and V of the key/value heads."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return attention_layers(config) * 2 * config["num_key_value_heads"] * \
        head * itemsize


def tick_bytes(config: dict, active_rows: float, positions: float,
               itemsize: int) -> dict:
    """The least one decode tick has to move, by piece: every layer's
    weights once; the head's table once; both states of the
    ``active_rows`` that held a request, once in and once out; of the
    attention caches the ``positions`` those rows hold (summed over the
    rows), not the caches' length.  The embedding's rows, every
    activation, the free rows' states and the caches' writes are left
    out: a true lower bound."""
    p = layer_parameters(config)
    return {
        "mamba_weights": mamba_layers(config) * p["mamba"] * itemsize,
        "attention_weights": attention_layers(config) * p["attention"] *
        itemsize,
        "mlp_weights": config["num_hidden_layers"] *
        (p["mlp"] + p["norms"]) * itemsize,
        "states": 2 * active_rows * state_bytes_per_row(config, itemsize),
        "attention_caches": positions *
        attention_bytes_per_position(config, itemsize),
        "head": config["vocab_size"] * config["hidden_size"] * itemsize,
    }


def mamba_chunk_flops(config: dict, positions: int) -> int:
    """Operations of ONE Mamba mixer on ``positions`` new positions: the
    four projections (2 a multiply-add) and the recurrence in its linear
    form (``SCAN_OPS_A_STATE_VALUE``); the convolution, the norms and the
    gate are left out."""
    h, w = config["hidden_size"], mamba_widths(config)
    inner = w["inner"]
    return positions * (
        2 * (h * 2 * inner + inner * w["x_proj"] +
             config["mamba_dt_rank"] * inner + inner * h) +
        SCAN_OPS_A_STATE_VALUE * w["state"])


def scan_chunk_bytes(config: dict, positions: int, itemsize: int) -> int:
    """The least bytes ANY form of ONE mixer's scan moves over ``positions``
    new positions: ``x`` in and ``y`` out in the model's dtype, the
    low-rank ``dt``, ``B`` and ``C`` in, the state once in and once out."""
    w = mamba_widths(config)
    return positions * (2 * w["inner"] + w["x_proj"]) * itemsize + \
        2 * w["state"] * STATE_ITEMSIZE
