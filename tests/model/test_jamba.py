"""The Jamba (``model_type`` jamba) kinds of the one decoder definition
(Mamba-1 mixers whose decay is a channel's and a state value's own, a few
attention layers of several query heads on ONE key/value head without
positions, a gated MLP behind every mixer, a tied head) against the plain
reference ``chipbench/references/jamba_decoder.py`` at a toy size on the
CPU: hidden 64, six layers with attention at ``i % 3 == 1``, 128 channels
of 4 state values through rank 4, 4 query heads on one key/value head,
seeded weights with norm weights away from 1 and biases away from 0.
Float32 at full matmul precision, so that what is compared is the
mathematics.  The serving path (chunk edges, padded chunks, the engine's
rows, the toy cell through the benchmark's driver) is
``tests/serve/test_s6_state.py``; the benchmark's cell compares the
bfloat16 program with the same reference on the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                      init_kv_caches, kv_cache_kinds,
                                      kv_cache_shapes, ssm_states,
                                      uniform_kv_caches)
from alpa_tpu.ops import selective_scan
from alpa_tpu.testing import highest, init_params, jitted, shake
from chipbench import arithmetic_jamba, controls_jamba, run

TOY = run.load_json(run.HERE, "configs", "toy-jamba.json")
CELL = run.load_json(run.HERE, "configs", "jamba2-3b-1chip.json")
DRIVER = run.load_module("drivers", "serve_s6")
CONTEXT, S, H, D = 128, 43, TOY["hidden_size"], 128
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config(**kwargs):
    return config_from_hf(TOY, **{"dtype": jnp.float32,
                                  "seq_len": CONTEXT, **kwargs})


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (2, S)): norm weights away from 1, the
    convolutions' and ``dt_proj``'s biases, ``A_log`` and ``D`` away from
    their initial values, so that a forgotten one shows."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    return model, shake(params, ("scale", "D", "conv_bias", "dt_bias",
                                 "A_log")), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits of every position of every sequence."""
    mod, ref = reference
    _model, params, ids = toy
    weights = mod.weights_from_program(params)
    return np.stack([np.asarray(ref.logits(weights, row)) for row in ids])


def logits_of(model, params, ids):
    return np.asarray(highest(jitted(model.apply), params, ids))


# ---- the configuration -------------------------------------------------

def test_config_from_hf_reads_the_catalog_row(catalog_row):
    hf = catalog_row("AI21-Jamba2-3B")["config"]
    assert "rope_theta" not in hf and "rope_parameters" not in hf
    cfg = config_from_hf(hf, dtype=jnp.bfloat16, seq_len=65536)
    assert cfg.num_layers == 28
    assert [i for i, kind in enumerate(cfg.attention) if kind == "full"] == \
        [7, 21]
    assert cfg.attention.count("s6") == 26 and cfg.mlp == "gated"
    assert (cfg.s6_inner, cfg.ssm_state_size, cfg.s6_dt_rank,
            cfg.conv_taps) == (5120, 16, 160, 4)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (20, 1, 128)
    assert cfg.mlp_width == 8192 and cfg.num_experts == 0
    assert cfg.tie_embeddings and not cfg.use_bias
    assert cfg.positions == "rotary" and not cfg.rope_on_full_attention
    assert cfg.activation == "silu" and cfg.norm == "rmsnorm"
    assert cfg.layer_norm_eps == 1e-6 and cfg.seq_len == 65536
    assert cfg.folds_full_caches
    # the cell's file: every key of the row unchanged, nothing cut
    for key, value in hf.items():
        assert CELL[key] == value, key
    assert CELL["reduced"] == []
    tree = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                          jnp.ones((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == \
        arithmetic_jamba.model_parameters(CELL) == 3_029_337_472


@pytest.mark.parametrize("change,match", [
    ({"num_experts": 16}, "num_experts 1"),
    ({"mamba_conv_bias": False}, "convolution with a bias"),
    ({"mamba_proj_bias": True}, "projections without one"),
    ({"sliding_window": 4096}, "sliding_window"),
])
def test_config_from_hf_refuses_what_it_cannot_build(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**TOY, **change})


def test_the_toy_is_the_published_layers_in_small(toy):
    cfg = toy_config()
    assert cfg.attention == ("s6", "full", "s6", "s6", "full", "s6")
    assert ssm_states(cfg) and not uniform_kv_caches(cfg)
    # the gauge's kind of a Mamba-1 layer's entry is a Mamba-2 layer's
    assert kv_cache_kinds(cfg) == ["ssm", "full", "ssm", "ssm", "full",
                                   "ssm"]
    blocks = toy[1]["params"]
    assert sorted(blocks["h0"]) == ["ln1", "ln2", "mlp", "ssm"]
    assert sorted(blocks["h1"]) == ["attn", "ln1", "ln2", "mlp"]
    assert sorted(blocks["h0"]["ssm"]) == [
        "A_log", "D", "b_norm", "c_norm", "conv_bias", "conv_kernel",
        "dt_bias", "dt_norm", "dt_proj", "in_proj", "out_proj", "x_proj"]
    assert blocks["h0"]["ssm"]["in_proj"]["kernel"].shape == (H, 2 * D)
    assert blocks["h0"]["ssm"]["x_proj"]["kernel"].shape == (D, 4 + 2 * 4)
    assert blocks["h0"]["ssm"]["A_log"].shape == (4, D)
    assert blocks["h1"]["attn"]["qkv"]["kernel"].shape == (H, H + 2 * 16)
    assert "lm_head" not in blocks and "wpe" not in blocks
    assert sum(x.size for x in jax.tree_util.tree_leaves(blocks)) == \
        arithmetic_jamba.model_parameters(TOY)


def test_the_mixers_states_are_made_as_the_family_makes_them():
    """``A_log[:, d] = log(1 .. N)``, ``D`` ones, the inner norms' weights
    ones, and a ``dt_proj`` bias whose softplus lies in [0.001, 0.1]:
    float32 whatever the parameters'."""
    cfg = config_from_hf(dict(TOY, num_hidden_layers=1),
                         param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    ssm = init_params(GPTModel(cfg), jax.random.PRNGKey(3),
                      jnp.ones((1, 8), jnp.int32))["params"]["h0"]["ssm"]
    np.testing.assert_allclose(
        ssm["A_log"], np.broadcast_to(np.log(np.arange(1, 5))[:, None],
                                      (4, D)), rtol=1e-6)
    assert (np.asarray(ssm["D"]) == 1).all()
    steps = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert (steps >= 1e-3 * 0.999).all() and (steps <= 0.1 * 1.001).all()
    assert {ssm[k].dtype for k in ("A_log", "D", "dt_bias")} == \
        {jnp.dtype(jnp.float32)}
    assert ssm["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert ssm["dt_proj"].dtype == jnp.bfloat16
    assert all((np.asarray(ssm[k]["scale"], np.float32) == 1).all()
               for k in controls_jamba.INNER_NORMS)
    assert np.asarray(ssm["conv_bias"], np.float32).any()


def test_an_s6_layer_holds_two_states_and_one_head_lies_folded():
    """The conv state in the caches' dtype, the ssm state float32 with the
    CHANNELS minor-most, as large a row whatever the context; the caches
    of ONE key/value head hold it folded into the channels."""
    cfg = toy_config(dtype=jnp.bfloat16)
    shapes = kv_cache_shapes(cfg, 5)
    assert shapes[0] == ((5, 3, D), (5, 4, D))
    assert shapes[1] == (5, CONTEXT, 16)
    assert kv_cache_shapes(toy_config(seq_len=4 * CONTEXT), 5)[0] == shapes[0]
    for (k, v, index), kind in zip(init_kv_caches(cfg, 5),
                                   kv_cache_kinds(cfg)):
        assert index.shape == () and not k.any() and not v.any()
        assert k.dtype == jnp.bfloat16
        assert v.dtype == (jnp.float32 if kind == "ssm" else jnp.bfloat16)
    # at the published widths: 327,680 B a row a layer, 256 B a position
    # a tensor
    real = kv_cache_shapes(DRIVER.model_config(
        CELL, dtype=jnp.bfloat16, seq_len=65536), 1)
    assert real[0] == ((1, 3, 5120), (1, 16, 5120))
    assert real[7] == (1, 65536, 128)


@pytest.mark.parametrize("what", ["the KV block pool", "beam search",
                                  "the speculative verify step",
                                  "disaggregated serving"])
def test_one_key_value_head_is_refused_where_heads_are_indexed(what):
    """ONE key/value head lies folded in ANY configuration, a plain
    multi-query decoder too (``GPTConfig.folds_full_caches``), so the
    paths that index per-head caches (B, positions, heads, channels),
    which took such a model before the fold, refuse it by name; two
    key/value heads they take as ever."""
    cfg = gpt_model.GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                              num_kv_heads=1, vocab_size=64, seq_len=32)
    assert cfg.folds_full_caches
    assert kv_cache_shapes(cfg, 3) == [(3, 32, 16)] * 2
    with pytest.raises(ValueError, match="ONE key/value head") as err:
        gpt_model.require_uniform_kv_caches(cfg, what)
    assert what in str(err.value) and "(1, 32, 16)" in str(err.value)
    gpt_model.require_uniform_kv_caches(
        dataclasses.replace(cfg, num_kv_heads=2), what)


# ---- the program against the reference ---------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    model, params, ids = toy
    assert np.abs(logits_of(model, params, ids) - wanted).max() < TOL


@pytest.mark.parametrize("form", ["public", "kernel"])
def test_the_chunk_scan_is_the_step_position_by_position(form):
    """``s6_chunk_scan`` (and its kernel, interpreted) from a NON-ZERO
    state, with steps of 0 in its tail, against ``s6_step`` a position:
    the outputs and the state of the last REAL position."""
    b, s, d, n, real = 2, 32, 256, 8, 27
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(keys[0], (b, n, d))
    x = jax.random.normal(keys[1], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (b, s, d)) - 2.0)
    dt = dt.at[:, real:].set(0.0)
    a = -jnp.exp(jax.random.normal(keys[3], (n, d)))
    bs = jax.random.normal(keys[4], (b, s, n))
    cs = jax.random.normal(keys[5], (b, s, n))
    assert selective_scan.chunk_fits(state, x)
    scan = selective_scan.s6_chunk_scan if form == "public" else \
        lambda *args: selective_scan.chunk_scan_kernel(*args, interpret=True)
    y, last = highest(jitted(scan), state, x, dt, a, bs, cs)
    want = []
    for t in range(real):
        y_t, state = selective_scan.s6_step(
            state, x[:, t], dt[:, t], a, bs[:, t], cs[:, t])
        want.append(y_t)
    np.testing.assert_allclose(y[:, :real], jnp.stack(want, 1),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(last, state, atol=2e-5, rtol=2e-5)


def _patched(name, make):
    """The wiring that replaces ``gpt_model.<name>`` or
    ``selective_scan.<name>`` by ``make(plain)``."""
    def wiring(monkeypatch, params):
        module = gpt_model if hasattr(gpt_model, name) else selective_scan
        monkeypatch.setattr(module, name, make(getattr(module, name)))
    return wiring


def _configured(**changes):
    def wiring(_monkeypatch, _params):
        return changes
    return wiring


def _weights(change):
    """The wiring that hands the program other parameters than the
    reference was given: ``change(an ssm layer's parameters)``."""
    def wiring(_monkeypatch, params):
        return {"params": {
            name: ({**block, "ssm": change(dict(block["ssm"]))}
                   if isinstance(block, dict) and "ssm" in block else block)
            for name, block in params["params"].items()}}
    return wiring


def _z_first(ssm):
    kernel = ssm["in_proj"]["kernel"]
    ssm["in_proj"] = {"kernel": jnp.concatenate(
        [kernel[:, D:], kernel[:, :D]], axis=1)}
    return ssm


def _one_inner_norm_left_out(monkeypatch, _params):
    monkeypatch.setattr(controls_jamba, "INNER_NORMS", ("b_norm",))
    controls_jamba.inner_norms_left_out(monkeypatch.setattr)


def _norm_behind_the_gate(plain):
    def gated(y, z):
        y = plain(y, z)
        return y * jax.lax.rsqrt(jnp.square(y).mean(-1, keepdims=True) +
                                 1e-6)
    return gated


# how each wrong wiring is planted: a patch of the program, other
# parameters than the reference's, or the fields of the configuration it
# changes (the parameters stay the toy's, where their shapes allow it)
WIRINGS = {
    "z_first_in_the_split": _weights(_z_first),
    "an_inner_norm_left_out": _one_inner_norm_left_out,
    "dt_proj_without_its_bias": lambda m, p: controls_jamba.dt_bias_left_out(
        m.setattr),
    "one_decay_a_channel": lambda m, p: controls_jamba.one_decay_a_channel(
        m.setattr),
    "b_and_c_swapped": _patched(
        "s6_chunk_scan", lambda scan: lambda state, x, dt, a, b, c: scan(
            state, x, dt, a, c, b)),
    "a_norm_behind_the_gate": _patched("s6_gate", _norm_behind_the_gate),
    "d_left_out": _weights(lambda ssm: {**ssm, "D": jnp.zeros_like(
        ssm["D"])}),
    "rotary_on_the_attention_layers": _configured(
        rope_on_full_attention=True),
    "attention_at_the_periods_start": _configured(
        attention=("full", "s6", "s6") * 2),
    "no_mlp_in_a_mamba_layer": _configured(mlp=("none",) + ("gated",) * 5),
}


@pytest.mark.parametrize("wiring", sorted(WIRINGS))
def test_a_wrong_wiring_moves_the_logits(toy, wanted, monkeypatch, wiring):
    """Each of the wirings the equations rule out, planted: the logits
    leave the reference's by a thousand times the tolerance."""
    _model, params, ids = toy
    changes = WIRINGS[wiring](monkeypatch, params) or {}
    if "params" in changes:
        params, changes = changes, {}
    cfg = dataclasses.replace(toy_config(), **changes)
    model = GPTModel(cfg)
    if changes:
        # the toy's parameters where the wrong model has them at their
        # shape, its own draws elsewhere (an attention where a mixer was)
        own = init_params(model, jax.random.PRNGKey(2), ids)
        flat = dict(jax.tree_util.tree_leaves_with_path(params))
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: flat[path] if path in flat and
            flat[path].shape == x.shape else x, own)
    got = logits_of(model, params, ids)
    assert np.abs(got - wanted).max() > 1000 * TOL
