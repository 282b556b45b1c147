"""The Nemotron-H (``model_type`` nemotron_h) kinds of the one decoder
definition (layers that are a Mamba-2 mixer, a grouped-query attention
without positions or routed experts ALONE, one norm and one residual sum a
layer; ungated squared-ReLU experts with a shared expert of a width of its
own under a sigmoid router with a choice bias; an untied head) against the
plain reference ``chipbench/references/nemotron_h_decoder.py`` at a toy
size on the CPU: hidden 64, the five layers ``MEM*E`` (8 heads of 8 channels
in 2 groups, a state of 16, sub-chunks of 8; 4 query heads over 2 key/value
heads; 8 experts top-3), seeded weights with norm weights away from 1 and
biases away from 0.  Float32 at full matmul precision, so that what is
compared is the mathematics.  The serving path (chunk edges, padded
chunks, the engine's rows) is ``tests/serve/test_ssm_state.py``; the toy
cell through the benchmark's driver, sound and under control (a), is at
the end of this file; the benchmark's cell compares the bfloat16 program with the same reference on
the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model, moe
from alpa_tpu.model.gpt_model import (GPTModel, MLPBlock, config_from_hf,
                                      init_kv_caches, kv_cache_kinds,
                                      kv_cache_shapes, ssm_states,
                                      uniform_kv_caches)
from alpa_tpu.model.moe import DroplessExperts
from alpa_tpu.ops import ssm_scan
from alpa_tpu.testing import highest, init_params, jitted, shake
from chipbench import arithmetic_nemotron, controls_nemotron, run

TOY = run.load_json(run.HERE, "configs", "toy-nemotron.json")
CELL = run.load_json(run.HERE, "configs",
                     "nemotron-3-nano-30b-a3b-1chip.json")
DRIVER = run.load_module("drivers", "serve_ssm")
CONTEXT, S, H = 128, 43, TOY["hidden_size"]
TOL = 2e-5      # float32 at full precision, logits of unit spread
# every expert of a layer in one program, and the file's leading five
# layers: one of each kind and a second ``M`` behind an ``E``
WHOLE = dict(TOY, n_routed_experts=TOY["published"]["n_routed_experts"],
             num_hidden_layers=5)


def toy_config(**kwargs):
    return config_from_hf(WHOLE, **{"dtype": jnp.float32,
                                    "seq_len": CONTEXT, **kwargs})


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference(dict(DRIVER.reference_settings(TOY),
                                   experts_first=0))


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (2, S)): norm weights away from 1, the
    routers' and the convolutions' biases and ``D`` away from their
    initial values, so that a forgotten one shows."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    return model, shake(params, ("scale", "router_bias", "norm", "D",
                                 "conv_bias")), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits of every position of every sequence."""
    mod, ref = reference
    _model, params, ids = toy
    weights = mod.weights_from_program(params)
    return np.stack([np.asarray(ref.logits(weights, row)) for row in ids])


def logits_of(model, params, ids):
    return np.asarray(highest(jitted(model.apply), params, ids)[0])


# ---- the configuration -------------------------------------------------

def test_config_from_hf_reads_the_catalog_row(catalog_row):
    hf = catalog_row("NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")["config"]
    cfg = config_from_hf(hf, dtype=jnp.bfloat16, seq_len=8192)
    assert [cfg.attention.count(k) for k in ("ssm", "none", "full")] == \
        [23, 23, 6]
    assert cfg.mlp.count("experts") == 23 and cfg.mlp.count("none") == 29
    assert all((a == "none") != (m == "none")
               for a, m in zip(cfg.attention, cfg.mlp))
    assert cfg.attention[:6] == ("ssm", "none", "ssm", "none", "ssm", "full")
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
            cfg.ssm_groups, cfg.ssm_chunk, cfg.conv_taps) == \
        (64, 64, 128, 8, 128, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_width) == (4096, 6144)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (32, 2, 128)
    assert (cfg.expert_width, cfg.shared_expert_width) == (1856, 3712)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == (128, 6, 1)
    assert cfg.router_score == "sigmoid" and cfg.router_bias
    assert cfg.norm_topk_prob and cfg.route_scale == 2.5
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    assert cfg.activation == "relu2" and not cfg.expert_gated
    assert not cfg.tie_embeddings and not cfg.use_bias
    assert cfg.positions == "rotary" and not cfg.rope_on_full_attention
    assert cfg.layer_norm_eps == 1e-5 and cfg.seq_len == 8192
    # the cell's file: every key of the row unchanged but what it cut
    for key, value in hf.items():
        assert CELL[key] == value or key in CELL["reduced"], key
    assert sorted(CELL["reduced"]) == ["n_routed_experts", "vocab_size"]
    assert CELL["published"]["n_routed_experts"] == hf["n_routed_experts"]
    assert CELL["published"]["vocab_size"] == hf["vocab_size"]


@pytest.mark.parametrize("change,match", [
    ({"hybrid_override_pattern": "MEM"}, "name every layer"),
    ({"hybrid_override_pattern": "MEMXEME"}, "unknown layers"),
    ({"n_group": 2}, "one group"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_conv_bias": False}, "convolution with a bias"),
    ({"n_shared_experts": 2}, "one shared expert"),
])
def test_config_from_hf_refuses_what_it_cannot_build(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**TOY, **change})


def test_the_toy_is_the_published_layers_in_small():
    cfg = toy_config()
    assert cfg.attention == ("ssm", "none", "ssm", "full", "none")
    assert cfg.mlp == ("none", "experts", "none", "none", "experts")
    assert ssm_states(cfg) and not uniform_kv_caches(cfg)
    assert kv_cache_kinds(cfg) == list(cfg.attention)


def test_a_layer_has_one_norm_and_one_sub_layer(toy):
    blocks = toy[1]["params"]
    assert sorted(blocks["h0"]) == ["ln1", "ssm"]
    assert sorted(blocks["h1"]) == ["ln2", "mlp"]
    assert sorted(blocks["h3"]) == ["attn", "ln1"]
    assert sorted(blocks["h0"]["ssm"]) == [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
        "norm", "out_proj"]
    assert blocks["h0"]["ssm"]["in_proj"]["kernel"].shape == (H, 64 + 128 + 8)
    assert sorted(blocks["h1"]["mlp"]) == ["router", "router_bias",
                                           "shared0", "w_down", "w_up"]
    assert blocks["h1"]["mlp"]["shared0"]["fc_in"]["kernel"].shape == (H, 96)
    assert blocks["h1"]["mlp"]["w_up"].shape == (8, 48, H)
    assert "lm_head" in blocks and "wpe" not in blocks


def test_the_mixers_states_are_made_as_the_family_makes_them():
    """``A_log = log(1 .. H)``, ``D`` ones, and a ``dt_bias`` whose
    softplus lies in [0.001, 0.1]: float32 whatever the parameters'."""
    cfg = config_from_hf(dict(TOY, num_hidden_layers=1),
                         param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    ssm = init_params(GPTModel(cfg), jax.random.PRNGKey(3),
                      jnp.ones((1, 8), jnp.int32))["params"]["h0"]["ssm"]
    np.testing.assert_allclose(ssm["A_log"], np.log(np.arange(1, 9)),
                               rtol=1e-6)
    assert (np.asarray(ssm["D"]) == 1).all()
    steps = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert (steps >= 1e-3 * 0.999).all() and (steps <= 0.1 * 1.001).all()
    assert {ssm[k].dtype for k in ("A_log", "D", "dt_bias")} == \
        {jnp.dtype(jnp.float32)}
    assert ssm["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert np.asarray(ssm["conv_bias"], np.float32).any()


def test_an_ssm_layer_holds_two_states_and_an_expert_layer_nothing():
    """The conv state in the caches' dtype, the ssm state float32, as
    large a row whatever the context; an ``E`` layer's entry is empty."""
    cfg = toy_config(dtype=jnp.bfloat16)
    shapes = kv_cache_shapes(cfg, 5)
    assert shapes[0] == ((5, 3, 128), (5, 8, 8, 16))
    assert shapes[1] == ((5, 0), (5, 0))
    assert shapes[3] == (5, CONTEXT, 2, 16)
    assert kv_cache_shapes(toy_config(seq_len=4 * CONTEXT), 5)[0] == shapes[0]
    for (k, v, index), kind in zip(init_kv_caches(cfg, 5),
                                   kv_cache_kinds(cfg)):
        assert index.shape == () and not k.any() and not v.any()
        assert k.dtype == jnp.bfloat16
        assert v.dtype == (jnp.float32 if kind == "ssm" else jnp.bfloat16)


# ---- the program against the reference ---------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    model, params, ids = toy
    logits, routing = highest(jitted(model.apply), params, ids)
    assert np.abs(np.asarray(logits) - wanted).max() < TOL
    assert routing["experts"].shape == (2, 2 * S, 3)


def test_the_chunked_scan_is_the_loop_over_positions():
    """``ssm_chunk_scan`` from a state, over a length that is no multiple
    of the sub-chunk and with steps of 0 in its tail, against ``ssm_step``
    a position: the outputs and the state of the last REAL position."""
    b, s, h, p, g, n, real = 2, 21, 4, 8, 2, 16, 17
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(keys[0], (b, h, p, n))
    x = jax.random.normal(keys[1], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (b, s, h)) - 2.0)
    dt = dt.at[:, real:].set(0.0)
    a = -jnp.exp(jax.random.normal(keys[3], (h,)))
    bs = jax.random.normal(keys[4], (b, s, g, n))
    cs = jax.random.normal(keys[5], (b, s, g, n))
    y, last = highest(jitted(lambda *args: ssm_scan.ssm_chunk_scan(
        *args, 8, jnp.float32)), state, x, dt, a, bs, cs)
    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        y_t, state = ssm_scan.ssm_step(state, x_t, dt_t, a, b_t, c_t)
        return state, y_t

    by_position = tuple(jnp.moveaxis(v[:, :real], 1, 0)
                        for v in (x, dt, bs, cs))
    state, want = highest(jitted(lambda s, xs: jax.lax.scan(position, s, xs)),
                          state, by_position)
    np.testing.assert_allclose(y[:, :real], jnp.moveaxis(want, 0, 1),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(last, state, atol=2e-5, rtol=2e-5)


def test_a_transposed_grouped_matmul_is_the_plain_one():
    """``grouped_matmul(..., transposed=True)`` of weights stored (G, N, K),
    forward and both gradients, against a row-by-row product with their
    transposes: how an ungated expert's ``w_up`` is stored and met."""
    from alpa_tpu.ops.grouped_matmul import grouped_matmul
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    lhs = jax.random.normal(keys[0], (24, 16))
    rhs = jax.random.normal(keys[1], (3, 16, 8))
    cot = jax.random.normal(keys[2], (24, 8))
    sizes = jnp.asarray([5, 0, 19], jnp.int32)

    def loss(lhs, rhs, matmul):
        out = matmul(lhs, rhs)
        return (out * cot).sum(), out

    def plain(lhs, rhs):
        group = jnp.repeat(jnp.arange(3), sizes, total_repeat_length=24)
        return jnp.einsum("mk,mkn->mn", lhs, rhs[group])

    grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (_, want), (d_lhs, d_rhs) = highest(grads, lhs, rhs, plain)
    (_, got), (t_lhs, t_rhs) = highest(jitted(lambda a, b: grads(
        a, b, lambda a, b: grouped_matmul(a, b, sizes, transposed=True))),
        lhs, rhs.swapaxes(1, 2))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(t_lhs, d_lhs, atol=1e-5)
    np.testing.assert_allclose(t_rhs.swapaxes(1, 2), d_rhs, atol=1e-5)


def _swap_b_and_c(monkeypatch):
    scan = ssm_scan.ssm_chunk_scan
    monkeypatch.setattr(
        ssm_scan, "ssm_chunk_scan",
        lambda state, x, dt, a, b, c, *rest: scan(state, x, dt, a, c, b,
                                                  *rest))


def _wrong_group(monkeypatch):
    scan = ssm_scan.ssm_chunk_scan
    monkeypatch.setattr(
        ssm_scan, "ssm_chunk_scan",
        lambda state, x, dt, a, b, c, *rest: scan(
            state, x, dt, a, jnp.roll(b, 1, axis=2), jnp.roll(c, 1, axis=2),
            *rest))


def _norm_over_all_channels(monkeypatch):
    plain = gpt_model.gated_group_norm
    monkeypatch.setattr(
        gpt_model, "gated_group_norm",
        lambda y, z, weight, groups, eps: plain(y, z, weight, 1, eps))


def _configured(**changes):
    def wiring(_monkeypatch):
        return changes
    return wiring


# how each wrong wiring is planted: a patch of the program, or the fields
# of the configuration it changes (the parameters stay the toy's, where
# their shapes allow it)
WIRINGS = {
    "gate_after_the_norm": lambda m: controls_nemotron.gate_after_norm(
        m.setattr),
    "norm_over_all_channels": _norm_over_all_channels,
    "relu_for_relu2": lambda m: controls_nemotron.relu_not_squared(
        m.setattr),
    "shared_expert_at_the_routed_width": _configured(
        shared_expert_width=None),
    "b_and_c_swapped": _swap_b_and_c,
    "heads_on_the_wrong_group": _wrong_group,
    "an_mlp_in_an_m_layer": _configured(
        mlp=("dense",) + toy_config().mlp[1:]),
    "rotary_on_the_attention_layers": _configured(
        rope_on_full_attention=True),
    "routed_scaling_factor_left_out": _configured(route_scale=1.0),
}


@pytest.mark.parametrize("wiring", sorted(WIRINGS))
def test_a_wrong_wiring_moves_the_logits(toy, wanted, monkeypatch, wiring):
    """Each of the wirings the equations rule out, planted: the logits
    leave the reference's by a thousand times the tolerance."""
    _model, params, ids = toy
    changes = WIRINGS[wiring](monkeypatch) or {}
    cfg = dataclasses.replace(toy_config(), **changes)
    model = GPTModel(cfg)
    if changes:
        # the toy's parameters where the wrong model has them at their
        # shape, its own draws elsewhere (a shared expert cut to the routed
        # width, the MLP an ``M`` layer should not have)
        own = init_params(model, jax.random.PRNGKey(2), ids)
        flat = dict(jax.tree_util.tree_leaves_with_path(params))
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: flat[path] if path in flat and
            flat[path].shape == x.shape else x, own)
    got = logits_of(model, params, ids)
    assert np.abs(got - wanted).max() > 1000 * TOL


@pytest.mark.parametrize("window", [None, 16], ids=["all_rows", "windows"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(toy, window,
                                                           monkeypatch):
    """The guide's test of the cut: an expert layer's result over every
    share of its experts (two chips of four here; the router at its whole
    width in each, the picks of absent experts adding nothing), with the
    shared expert, which every chip computes alike, counted once, is what
    the layer gives with all its experts."""
    if window:
        # the shares walk their rows in windows of 16 (ISSUE 60); the layer
        # that holds every expert never does
        monkeypatch.setattr(
            moe, "expert_window",
            lambda cfg, tokens: window if cfg.experts_held else None)
    _model, params, _ids = toy
    layer = params["params"]["h1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 12, H))
    whole, _ = highest(jitted(DroplessExperts(toy_config()).apply),
                       {"params": layer}, x)
    shared = highest(jitted(MLPBlock(toy_config(), gated=False,
                                     width=96).apply),
                     {"params": layer["shared0"]}, x)
    total = 0.0
    for first in (0, 4):
        cfg = toy_config(experts_held=(first, 4))
        mine = dict(layer, w_up=layer["w_up"][first:first + 4],
                    w_down=layer["w_down"][first:first + 4])
        part, routing = highest(jitted(DroplessExperts(cfg).apply),
                                {"params": mine}, x)
        assert routing["experts"].max() > 3      # the router's whole width
        total = total + (part - shared)
    np.testing.assert_allclose(total + shared, whole, atol=TOL)
    assert np.abs(np.asarray(whole - shared)).max() > 0.01


def test_the_arithmetic_counts_the_programs_parameters():
    """``arithmetic_nemotron.model_parameters`` at the toy's keys is the
    parameter tree's size, the share of the experts included."""
    cfg = config_from_hf(dict(WHOLE, num_hidden_layers=7),
                         experts_held=(4, 4), seq_len=CONTEXT)
    tree = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                          jnp.ones((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == \
        arithmetic_nemotron.model_parameters(TOY)


# ---- the cell's driver ---------------------------------------------------

@pytest.mark.parametrize("control", [None, "state_in_bfloat16"],
                         ids=["sound", "state_in_bfloat16"])
def test_driver_runs_the_toy_cell(toy_context, monkeypatch, control):
    """``chipbench/drivers/serve_ssm.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' biases, controller, warm-up, a closed-loop window over HTTP,
    the check against the reference: correct as it is, and not with the
    ssm state rounded to bfloat16 on its way into the cache (control (a)
    of ``chipbench/controls_nemotron.py``), every request still served."""
    if control:
        controls_nemotron.CONTROLS[control](monkeypatch.setattr)
    obs = DRIVER.run(toy_context("toy-nemotron.reasoning",
                                 "toy-reasoning-ssm", 2.0, 0))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 16, checks
    assert checks["checked_requests"] == 4
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert max(checks["checked_prompts"]) > 2 * TOY["serve"]["prefill_chunk"]
    assert checks["compiles_in_window"] == 0
    if control:
        # the states themselves show it, a hundred times over the limit
        assert not obs["correct"] and checks["over_margin"] > 0, checks
        assert checks["worst_state_diff"] > 10 * TOY["state_rtol"]
        return
    assert obs["correct"] and checks["over_margin"] == 0, checks
    assert checks["worst_state_diff"] < TOY["state_rtol"] / 10
    assert np.shape(checks["state_diffs"]) == (4, 2)
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 3
    assert obs["expert_bytes"] == 2 * 64 * 48 * 4
    obs.update(peaks=None, config=TOY)
    assert run.metric_reader("ssm_state_bytes_per_row")(obs) == \
        arithmetic_nemotron.state_bytes_per_row(TOY, 4)
    assert 25 < run.metric_reader("moe_local_rows_pct")(obs) < 75
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 4
    # no chip, no capture: the readers of the device's time give nothing
    for traced in ("ssm_decode_share_pct", "ssm_tick_hbm_roofline_pct",
                   "ssm_chunk_roofline_pct", "ssm_chunk_share_pct"):
        assert run.metric_reader(traced)(obs) is None
