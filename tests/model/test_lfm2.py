"""The LFM2 (``model_type`` lfm2_moe) kinds of the one decoder definition
(gated short convolutions whose state is the last two positions of a
product beside grouped-query attention layers with head-wise q/k norm, a
leading dense layer, sigmoid-routed experts with a selection bias, a tied
head) against the plain reference
``chipbench/references/lfm2_moe_decoder.py`` at a toy size on the CPU:
hidden 64, 3 taps, five layers (a dense conv, three conv, one attention of
4 query heads over 2 key/value heads), 8 experts top-2, seeded weights with
a non-zero bias.  Float32 at full matmul precision, so that what is
compared is the mathematics: prefill and then decoding through the state
and the cache against the reference's full forward pass, logits and not
tokens.  The benchmark's cell compares the bfloat16 program with the same
reference on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import (GPTModel, ShortConv, TransformerBlock,
                                      config_from_hf, conv_states,
                                      init_kv_caches, kv_cache_kinds,
                                      kv_cache_shapes, uniform_kv_caches,
                                      update_conv_state)
from alpa_tpu.model.model_util import routed_lm_loss
from alpa_tpu.serve.generation import Generator
from alpa_tpu.testing import highest, init_params, jitted
from chipbench import run

TOY = run.load_json(run.HERE, "configs", "toy-lfm2.json")
DRIVER = run.load_module("drivers", "serve_hybrid")
CONTEXT, S, H = 64, 48, TOY["hidden_size"]
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config(**kwargs):
    return config_from_hf(TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT,
                                  **kwargs})


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (3, S)): norm weights away from 1 and the
    routers' biases away from 0, so that a forgotten one shows."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)

    def shake(path, x):
        key = jax.random.PRNGKey(len(str(path)))
        if path[-1].key == "scale":
            return x * jax.random.uniform(key, x.shape, minval=0.5,
                                          maxval=1.5)
        if path[-1].key == "router_bias":
            return 0.05 * jax.random.normal(key, x.shape)
        return x

    return model, jax.tree_util.tree_map_with_path(shake, params), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits of every position of every sequence."""
    mod, ref = reference
    _model, params, ids = toy
    weights = mod.weights_from_program(params)
    return np.stack([np.asarray(ref.logits(weights, row)) for row in ids])


def test_config_from_hf_reads_the_catalog_row(catalog_row):
    """The published ``config.json`` as the catalog holds it: it says
    ``norm_eps``, and has no ``hidden_act``, no ``head_dim`` and no
    ``tie_word_embeddings``."""
    hf = catalog_row("LFM2-8B-A1B")["config"]
    cfg = config_from_hf(hf, dtype=jnp.bfloat16, seq_len=8192)
    assert cfg.attention.count("conv") == 18 and \
        cfg.attention.count("full") == 6
    assert cfg.attention[:3] == ("conv", "conv", "full")
    assert cfg.mlp == 2 * ("gated",) + 22 * ("experts",)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (32, 8, 64)
    assert (cfg.mlp_width, cfg.expert_width) == (7168, 1792)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (32, 4)
    assert cfg.conv_taps == 3 and cfg.layer_norm_eps == 1e-5
    assert cfg.router_score == "sigmoid" and cfg.router_bias
    assert cfg.norm_topk_prob and cfg.route_scale == 1.0
    assert cfg.tie_embeddings and cfg.activation == "silu"
    assert cfg.qk_norm == "head" and cfg.rope_theta == 1e6
    assert not cfg.use_bias and cfg.seq_len == 8192


@pytest.mark.parametrize("change,match", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv"]}, "layer_types"),
    ({"layer_types": 4 * ["conv"] + ["sliding_attention"]}, "unknown"),
])
def test_config_from_hf_refuses_what_it_cannot_build(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**TOY, **change})


def test_the_toy_is_the_published_block_in_small():
    cfg = toy_config()
    assert cfg.attention == 4 * ("conv",) + ("full",)
    assert cfg.mlp == ("gated",) + 4 * ("experts",)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (4, 2, 16)
    assert conv_states(cfg) and not uniform_kv_caches(cfg)
    assert kv_cache_kinds(cfg) == 4 * ["conv"] + ["full"]


def test_a_conv_layer_holds_a_state_and_no_positions():
    """Two positions of the hidden width a row, whatever the context, in a
    triple as every layer's entry is."""
    cfg = toy_config()
    assert kv_cache_shapes(cfg, 5) == 4 * [((5, 2, H), (5, 0))] + \
        [(5, CONTEXT, 2, 16)]
    for (k, v, index), kind in zip(init_kv_caches(cfg, 5),
                                   kv_cache_kinds(cfg)):
        assert index.shape == () and not k.any()
        assert (k.shape, v.shape) == (((5, 2, H), (5, 0)) if kind == "conv"
                                      else 2 * ((5, CONTEXT, 2, 16),))
    longer = toy_config(seq_len=4 * CONTEXT)
    assert kv_cache_shapes(longer, 5)[0] == ((5, 2, H), (5, 0))


def test_the_mixer_equals_the_reference(reference, toy):
    """The mixer alone, one sequence: the program's ``ShortConv`` on
    ``n_op(x)`` against the reference's layer less its residual."""
    mod, _ref = reference
    _model, params, _ids = toy
    block = mod.weights_from_program(params)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (S, H))
    want = mod.short_conv(x, block, TOY["norm_eps"]) - x
    u = mod.rms(x, block["n_op"], TOY["norm_eps"])
    got, cache = highest(
        ShortConv(toy_config()).apply,
        {"params": params["params"]["h1"]["conv"]}, u[None])
    assert cache is None
    np.testing.assert_allclose(got[0], want, atol=TOL)


@pytest.mark.parametrize("layer", [0, 2, 4])
def test_a_block_equals_the_reference(reference, toy, layer):
    """A dense conv block, a routed conv block and the routed attention
    block, each alone on a random sequence."""
    mod, ref = reference
    _model, params, _ids = toy
    cfg, s = toy_config(), ref.s
    b = mod.weights_from_program(params)["blocks"][layer]
    x = jax.random.normal(jax.random.PRNGKey(7 + layer), (S, H))
    if "w_in" in b:
        want = mod.short_conv(x, b, s["norm_eps"])
    else:
        want = mod.attention(x, b, s["head_dim"], s["norm_eps"],
                             s["rope_theta"], 16)
    if "w_r" in b:
        want, _chosen = mod.experts(
            want, b, s["num_experts_per_tok"], s["norm_topk_prob"],
            s["routed_scaling_factor"], s["norm_eps"])
    else:
        want = mod.dense_mlp(want, b, s["norm_eps"])
    block = TransformerBlock(cfg, mlp=cfg.mlp_kind(layer),
                             attention=cfg.attention_kind(layer))
    got = highest(
        block.apply, {"params": params["params"][f"h{layer}"]}, x[None],
        position_ids=jnp.arange(S)[None])[0]
    np.testing.assert_allclose(got[0], want, atol=TOL)


def test_forward_pass_equals_the_reference(toy, wanted):
    model, params, ids = toy
    logits, routing = highest(model.apply, params, ids)
    np.testing.assert_allclose(logits, wanted, atol=TOL)
    assert routing["experts"].shape == (4, 3 * S, 2)


@pytest.mark.parametrize("variant", [
    dict(conv_taps=2), dict(router_bias=False), dict(qk_norm=False),
    dict(tie_embeddings=False), dict(norm_topk_prob=False),
    dict(rope_theta=1e4), dict(router_score="softmax")],
    ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()))
def test_a_wrong_wiring_fails(toy, wanted, variant):
    """Each piece of the wiring alone moves the logits by thousands of the
    tolerance."""
    model, params, ids = toy
    wrong = GPTModel(toy_config(**variant))
    if "conv_taps" in variant:      # the last two taps of three
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x[1:] if path[-2].key == "conv" and
            path[-1].key == "kernel" else x, params)
    if "tie_embeddings" in variant:
        params = {"params": {**params["params"], "lm_head": {
            "kernel": 0.1 * jax.random.normal(
                jax.random.PRNGKey(3), (H, TOY["vocab_size"]))}}}
    logits, _ = highest(wrong.apply, params, ids)
    assert np.abs(np.asarray(logits) - wanted).max() > 1000 * TOL


def test_the_state_of_a_padded_chunk_is_its_rows_last_real_positions():
    """``update_conv_state`` by hand: rows with no, one, two and all real
    positions in a chunk of four."""
    state = jnp.asarray(np.arange(4 * 2 * 1).reshape(4, 2, 1) + 100.0)
    g = jnp.asarray(np.arange(4 * 4 * 1).reshape(4, 4, 1) * 1.0)
    empty = jnp.zeros((4, 0))
    full, (new, _empty, index) = update_conv_state(
        (state, empty, jnp.int32(8)), g, jnp.asarray([8, 9, 10, 30]))
    assert full.shape == (4, 6, 1) and int(index) == 12
    np.testing.assert_array_equal(new[..., 0], [
        [100, 101],     # no real position: the old state
        [103, 4],       # one: the old state's last position, then g_0
        [8, 9],         # two: g_0, g_1
        [14, 15]])      # all four: the chunk's end
    # no lengths: every position is real (a decode step, a verify step)
    _full, (new, _e, _i) = update_conv_state((state, empty, jnp.int32(8)),
                                             g[:, :1])
    np.testing.assert_array_equal(new[..., 0],
                                  [[101, 0], [103, 4], [105, 8], [107, 12]])


CHUNK = 8


@pytest.mark.parametrize("chunk", [None, CHUNK], ids=["bucketed", "chunked"])
def test_prefill_then_decode_equals_the_reference(toy, wanted, chunk):
    """Prompts of 1, 2, 3, chunk - 1, chunk, chunk + 1 and 2 * chunk + 2
    tokens in ONE right-padded batch, through the dense prefill padded to
    a bucket and through the chunk step: the prefill's last logits of
    every row and every decode step's, against the reference's full
    forward pass.  The state of each row is that of its own last real
    position, whatever padding follows it, and crosses chunk edges."""
    model, params, ids = toy
    lengths = [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 2]
    rows = [r % 3 for r in range(len(lengths))]
    prompts = [np.asarray(ids[r, :n]) for r, n in zip(rows, lengths)]
    gen = Generator(model, params, toy_config(), prompt_buckets=[32],
                    prefill_chunk=chunk)
    b = len(lengths)
    with jax.default_matmul_precision("highest"):
        index = jnp.asarray(lengths, jnp.int32)
        if chunk:
            last, caches = gen._run_chunked_prefill(prompts, index, b)
        else:
            last, caches = gen._run_bucketed_prefill(prompts, index, b)
        for i, (r, n) in enumerate(zip(rows, lengths)):
            np.testing.assert_allclose(last[i], wanted[r, n - 1], atol=TOL)
        assert [k.shape[1:] for k, _v, _i in caches] == \
            4 * [(2, H)] + [(CONTEXT, 2, 16)]
        for step in range(10):
            tok = jnp.stack([ids[r, n + step] for r, n in
                             zip(rows, lengths)])[:, None]
            logits, caches, routing = gen._decode(params, tok, index, caches)
            index = index + 1
            assert routing["experts"].shape == (4, b, 2)
            for i, (r, n) in enumerate(zip(rows, lengths)):
                np.testing.assert_allclose(logits[i], wanted[r, n + step],
                                           atol=TOL)


def test_generate_serves_the_reference_s_choice(reference, toy):
    """``Generator.generate`` over prompts of unlike lengths: every token
    it serves has the reference's largest logit at its position."""
    from alpa_tpu.serve.generation import GenerationConfig
    mod, ref = reference
    model, params, ids = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    prompts = [np.asarray(ids[0, :5]), np.asarray(ids[1, :19])]
    with jax.default_matmul_precision("highest"):
        outs = gen.generate(prompts, GenerationConfig(max_new_tokens=12))
    weights = mod.weights_from_program(params)
    for p, out in zip(prompts, outs):
        logits = np.asarray(ref.logits(weights, out))
        rows = logits[len(p) - 1:len(out) - 1]
        deficit = rows.max(-1) - rows[np.arange(12), out[len(p):]]
        assert deficit.max() < TOL


def test_a_static_prefix_snapshots_the_state(toy, wanted):
    """``cache_prefix`` keeps the conv layers' state with the prefix's keys
    and values (it rides in the same list): suffixes of unlike lengths over
    it, one of them empty, decode as the whole sequence does."""
    model, params, ids = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    row = np.asarray(ids[0])
    plen, suffixes = 11, [0, 3, CHUNK + 2]
    with jax.default_matmul_precision("highest"):
        handle = gen.cache_prefix(row[:plen])
        lengths = jnp.asarray([plen + n for n in suffixes], jnp.int32)
        init = [(jnp.repeat(k, 3, axis=0), jnp.repeat(v, 3, axis=0), i)
                for k, v, i in handle.caches]
        last, caches = gen._run_chunked_prefill(
            [row[plen:plen + n] for n in suffixes], lengths, 3, caches=init,
            start=plen, init_last=jnp.repeat(handle.last_logits, 3, axis=0))
        for i, n in enumerate(suffixes):
            np.testing.assert_allclose(last[i], wanted[0, plen + n - 1],
                                       atol=TOL)
        tok = jnp.stack([ids[0, plen + n] for n in suffixes])[:, None]
        logits, _caches, _ = gen._decode(params, tok, lengths, caches)
        for i, n in enumerate(suffixes):
            np.testing.assert_allclose(logits[i], wanted[0, plen + n],
                                       atol=TOL)


def reference_loss(mod, ref, params, batch):
    """The reference's loss as a function of the PROGRAM's parameter tree,
    so that jax.grad gives the reference's gradient leaf for leaf.  Built
    from the reference's own pieces, as its ``lm_loss`` is."""
    s, w = ref.s, mod.weights_from_program(params)
    total = 0.0
    for ids, lab in zip(batch["input_ids"], batch["labels"]):
        x = w["wte"][ids]
        for b in w["blocks"]:
            if "w_in" in b:
                x = mod.short_conv(x, b, s["norm_eps"])
            else:
                x = mod.attention(x, b, s["head_dim"], s["norm_eps"],
                                  s["rope_theta"], 16)
            if "w_r" in b:
                x, _ = mod.experts(
                    x, b, s["num_experts_per_tok"], s["norm_topk_prob"],
                    s["routed_scaling_factor"], s["norm_eps"])
            else:
                x = mod.dense_mlp(x, b, s["norm_eps"])
        logits = mod.head(x, w["wf"], w["wte"], s["norm_eps"])
        total = total + mod.token_losses(logits, lab).sum()
    return total / batch["labels"].size


def test_loss_and_every_gradient_leaf_match_the_reference(reference, toy):
    """The training call (no caches: the causal convolution over the
    whole sequence) differentiates, and its gradient is the reference's."""
    mod, ref = reference
    model, params, ids = toy
    batch = {"input_ids": ids[:2],
             "labels": jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                                          TOY["vocab_size"])}

    def program_loss(p):
        with jax.default_matmul_precision("highest"):
            return routed_lm_loss(model.apply, p, batch, 0.0)[0]

    want = ref.lm_loss(mod.weights_from_program(params), batch["input_ids"],
                       batch["labels"])
    loss, got = jitted(jax.value_and_grad(program_loss))(params)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    loss, wanted_grad = jitted(jax.value_and_grad(
        lambda p: reference_loss(mod, ref, p, batch)))(params)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(wanted_grad)
    # 4 conv x 5 + attention 6, dense 3 + 4 x 4 routed, wte, ln_f
    assert len(flat_got) == 47
    for (path, g), w in zip(flat_got, flat_want):
        if path[-1].key == "router_bias":     # no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.abs(w).max()) > 0, path
        # float32 both sides; 1e-4 of the leaf's largest entry
        np.testing.assert_allclose(
            g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0,
            err_msg=jax.tree_util.keystr(path))
