"""The EvaByte (``model_type`` evabyte) kinds of the one decoder definition
(attention whose one softmax runs over the exact keys of the query's own
aligned window and over a learned summary of every chunk of the windows
before it, a float32 residual stream, norms with a unit offset, several
prediction heads of one untied matrix) against the plain reference
``chipbench/references/evabyte_decoder.py`` at a toy size on the CPU:
hidden 64, three layers, 4 heads of 16, windows of 64 in chunks of 4, two
prediction heads, seeded weights with the norms' stored weights away from
0.  Float32 at full matmul precision, so that what is compared is the
mathematics.  The serving path (chunked prefill, the two writes, window
and chunk edges, the engine's rows, the toy cell through the benchmark's
driver) is ``tests/serve/test_eva_cache.py``; the benchmark's cell
compares the bfloat16 program with the same reference on the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf, eva_slots,
                                      init_kv_caches, kv_cache_kinds,
                                      kv_cache_shapes, uniform_kv_caches)
from alpa_tpu.testing import highest, init_params, jitted, shake
from chipbench import arithmetic_evabyte, controls_evabyte, run

TOY = run.load_json(run.HERE, "configs", "toy-evabyte.json")
CELL = run.load_json(run.HERE, "configs", "evabyte-1chip.json")
DRIVER = run.load_module("drivers", "serve_eva")
WINDOW, CHUNK, HEADS = (TOY["window_size"], TOY["chunk_size"],
                        TOY["num_pred_heads"])
# three windows and a part of a fourth, ending inside a chunk
CONTEXT, S = 512, 3 * WINDOW + 27
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
    """(model, parameters, ids (2, S)): the norms' stored weights away
    from 0, so that a forgotten offset or a forgotten weight shows; the
    pooling vectors as the model draws them, at unit spread (the largest
    of a chunk's four weights 0.4 on average: no plain mean)."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    return model, shake(params, ("scale",)), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits of every position of every sequence, the
    heads side by side as the program has them."""
    mod, ref = reference
    _model, params, ids = toy
    weights = mod.weights_from_program(params)
    return np.stack([np.asarray(ref.logits(weights, row)).reshape(S, -1)
                     for row in ids])


def logits_of(model, params, ids):
    return np.asarray(highest(jitted(model.apply), params, ids))


# ---- the configuration -------------------------------------------------

def test_config_from_hf_reads_the_catalog_row(catalog_row):
    hf = catalog_row("EvaByte")["config"]
    cfg = config_from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.num_layers == 32 and cfg.attention == "eva"
    assert (cfg.eva_window, cfg.eva_chunk) == (2048, 16)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (32, 32, 128)
    assert cfg.mlp == "gated" and cfg.mlp_width == 11008
    assert cfg.fp32_residual and cfg.norm_unit_offset and cfg.fp32_logits
    assert cfg.num_pred_heads == 8 and cfg.vocab_size == 320
    assert not cfg.tie_embeddings and not cfg.use_bias
    assert cfg.positions == "rotary" and cfg.rope_theta == 100000.0
    assert cfg.activation == "silu" and cfg.norm == "rmsnorm"
    assert cfg.layer_norm_eps == 1e-5 and cfg.seq_len == 32768
    # the cell's file: every key of the row unchanged but the depth
    for key, value in hf.items():
        assert CELL[key] == (8 if key == "num_hidden_layers" else value), key
    assert CELL["reduced"] == ["num_hidden_layers"]
    assert CELL["published"]["num_hidden_layers"] == hf["num_hidden_layers"]
    tree = jax.eval_shape(
        GPTModel(DRIVER.model_config(CELL, dtype=jnp.bfloat16)).init,
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == \
        arithmetic_evabyte.model_parameters(CELL) == 1_630_932_992
    assert arithmetic_evabyte.model_parameters(hf) == 6_488_330_240


@pytest.mark.parametrize("change,match", [
    ({"attention_class": "softmax"}, "attention_class"),
    ({"num_key_value_heads": 2}, "one key/value head"),
    ({"window_size": 66}, "multiple of chunk_size"),
    ({"tie_word_embeddings": True}, "untied head"),
])
def test_what_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        cfg = config_from_hf({**TOY, **change})
        jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 8), jnp.int32))


def test_the_toy_is_the_published_layer_in_small(toy):
    cfg = toy_config()
    assert kv_cache_kinds(cfg) == ["eva"] * 3 and not uniform_kv_caches(cfg)
    blocks = toy[1]["params"]
    assert sorted(blocks["h0"]) == ["attn", "ln1", "ln2", "mlp"]
    assert sorted(blocks["h0"]["attn"]) == ["mu", "out", "phi", "qkv"]
    assert blocks["h0"]["attn"]["mu"].shape == (4, 16)
    assert blocks["h0"]["attn"]["mu"].dtype == jnp.float32
    assert blocks["lm_head"]["kernel"].shape == (64, HEADS * 320)
    assert "wpe" not in blocks
    assert sum(x.size for x in jax.tree_util.tree_leaves(blocks)) == \
        arithmetic_evabyte.model_parameters(TOY)


def test_a_layers_cache_holds_rows_of_two_kinds():
    """One pair of arrays of one shape a layer, the heads folded into the
    channels: a summary for every chunk of the context first, one
    window's rows behind them; at the published widths 67,108,864 B a row
    a layer, an eighth of a cache of the context's positions."""
    cfg = toy_config(dtype=jnp.bfloat16)
    assert eva_slots(cfg) == (CONTEXT // CHUNK, WINDOW)
    assert kv_cache_shapes(cfg, 5) == [(5, CONTEXT // CHUNK + WINDOW, 64)] * 3
    for k, v, index in init_kv_caches(cfg, 5):
        assert index.shape == () and not k.any() and not v.any()
        assert k.dtype == v.dtype == jnp.bfloat16
    real = DRIVER.model_config(CELL, dtype=jnp.bfloat16, seq_len=32768)
    assert kv_cache_shapes(real, 1) == [(1, 2048 + 2048, 4096)] * 8
    assert 2 * 4096 * 4096 * 2 == 67_108_864 == \
        arithmetic_evabyte.cache_bytes_per_row_a_layer(CELL, 2, 32768)
    assert 8 * 67_108_864 == 536_870_912


@pytest.mark.parametrize("require,what,named", [
    (gpt_model.require_uniform_kv_caches, "the KV block pool",
     "no array holds the context's positions"),
    (gpt_model.require_rollback_by_index, "the tick that verifies a draft",
     "summaries of chunks beside ONE window's rows"),
    (gpt_model.require_one_token_steps, "a static prefix",
     "chunks divide the window"),
])
def test_what_rolls_back_across_a_window_is_refused(require, what, named):
    with pytest.raises(ValueError, match=named) as err:
        require(toy_config(), what)
    assert what in str(err.value)
    require(dataclasses.replace(toy_config(), attention="full"), what)


# ---- the program against the reference ---------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    """All heads' logits at every position of three windows and a part of
    a fourth."""
    model, params, ids = toy
    got = logits_of(model, params, ids)
    assert got.shape == (2, S, HEADS * 320) and got.dtype == np.float32
    assert np.abs(got - wanted).max() < TOL


def test_in_the_first_window_the_layer_is_causal_attention(toy):
    """No summary is seen there: the logits are those of the same weights
    under full causal attention."""
    model, params, ids = toy
    plain = GPTModel(dataclasses.replace(toy_config(), attention="full"))
    got = logits_of(model, params, ids)[:, :WINDOW]
    want = logits_of(plain, params, ids[:, :WINDOW])
    assert np.abs(got - want).max() < TOL


def _patched(name, make):
    """The wiring that replaces ``gpt_model.<name>`` by ``make(plain)``."""
    def wiring(monkeypatch, params):
        monkeypatch.setattr(gpt_model, name, make(getattr(gpt_model, name)))
    return wiring


def _control(name):
    return lambda monkeypatch, params: controls_evabyte.CONTROLS[name](
        monkeypatch.setattr)


def _heads_moved_on(_monkeypatch, params):
    """Head ``j`` read as head ``j + 1``: the head's groups of columns
    turned by one."""
    kernel = params["params"]["lm_head"]["kernel"]
    return {"params": {**params["params"], "lm_head": {
        "kernel": jnp.roll(kernel, -TOY["vocab_size"], axis=1)}}}


# how each wrong wiring is planted: a control of the benchmark, a patch of
# the program, or other parameters than the reference's
WIRINGS = {
    **{name: _control(name) for name in (
        "summaries_left_out", "mean_pooling", "mu_phi_swapped",
        "own_window_summaries", "sliding_window", "pooling_before_rotary",
        "unit_offset_left_out")},
    "phi_pools_the_keys": _patched(
        "eva_pool", lambda pool: lambda k, v, mu, phi, scale: pool(
            k, v, phi, phi, scale)),
    "the_scale_left_out_of_the_pooling": _patched(
        "eva_pool", lambda pool: lambda k, v, mu, phi, scale: pool(
            k, v, mu, phi, 1.0)),
    "a_window_seen_from_two_windows_on": _patched(
        "eva_seen", lambda seen: lambda position, window, chunk, queries:
        jnp.maximum(seen(position, window, chunk, queries) -
                    window // chunk, 0)),
    "head_j_read_as_head_j_plus_1": _heads_moved_on,
}


@pytest.mark.parametrize("wiring", sorted(WIRINGS))
def test_a_wrong_wiring_moves_the_logits(toy, wanted, monkeypatch, wiring):
    """Each of the wirings the equations rule out, planted: the logits
    leave the reference's by a thousand times the tolerance."""
    model, params, ids = toy
    changed = WIRINGS[wiring](monkeypatch, params)
    got = logits_of(model, changed or params, ids)
    assert np.abs(got - wanted).max() > 1000 * TOL
