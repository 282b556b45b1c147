"""The GLM-5 (``model_type`` glm_moe_dsa) kinds of the one decoder definition
(latent attention over the positions a learned indexer selects in EVERY
layer, interleaved rotary pairs on the indexer too, a sigmoid router with a
choice bias over a share of the experts, and a multi-token-prediction
module: a second small stack behind the last layer that shares the model's
embedding and head) against the plain reference
``chipbench/references/glm5_decoder.py`` at a toy size on the CPU where
every mechanism is live: hidden 64, two layers (one dense, one routed) and
the module, 4 index heads of 16 selecting 8 positions, 4 heads of 16 + 8
and 16 (ranks 24 and 16), 16 experts of which 4 are held, 3 picks a token,
seeded weights.  Float32 at full matmul precision, so that what is compared
is the mathematics: the training call, prefill in chunks, the one-token
decode and the two-position verify through the caches, the module's logits
and the selected sets themselves.  The benchmark's cell compares the
bfloat16 program with the same reference on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                      init_kv_caches, kv_cache_kinds,
                                      kv_cache_shapes,
                                      require_rollback_by_index,
                                      require_uniform_kv_caches)
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.testing import highest, init_params, shake
from chipbench import arithmetic_glm5, run

TOY = run.load_json(run.HERE, "configs", "toy-glm5.json")
CELL = run.load_json(run.HERE, "configs", "glm-5-1chip.json")
DRIVER = run.load_module("drivers", "serve_glm5")
MLA = run.load_module("drivers", "serve_mla")
REF = run.load_module("references", "glm5_decoder")
CONTEXT, S = 96, 32
TOL = 5e-5


def toy_config(**kwargs):
    return MLA.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


# norm weights, the index keys' LayerNorm and the routers' biases
SHAKEN = ("scale", "bias", "router_bias")


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    model = GPTModel(cfg)
    params = shake(init_params(model, jax.random.PRNGKey(0),
                               jnp.ones((1, 8), jnp.int32)), SHAKEN)
    return cfg, model, params


@pytest.fixture(scope="module")
def reference():
    return REF.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def wanted(toy, reference):
    """The reference's logits, picks and selections of one sequence, the
    model's and the module's."""
    _, _, params = toy
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (S,), 4,
                                        TOY["vocab_size"]))
    logits, picks, (chosen, real), module = \
        reference.logits_experts_selections(
            REF.weights_from_program(params), ids, (0, S), module=True)
    return {"ids": ids, "logits": np.asarray(logits),
            "picks": np.asarray(picks), "chosen": np.asarray(chosen),
            "real": np.asarray(real), "module_logits": np.asarray(module[0]),
            "module_picks": np.asarray(module[1]),
            "module_chosen": np.asarray(module[2][0]),
            "module_real": np.asarray(module[2][1])}


def as_sets(positions, real):
    return [set(p[:n].tolist()) for p, n in zip(positions, real)]


# ---- the configuration (a) ---------------------------------------------

def parameters(cfg):
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(   # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    return count(shapes), count(shapes.get("mtp", {}))


def test_config_from_hf_reads_the_catalog_rows_config(catalog_row):
    """The catalog row's ``config``, unedited, is the published 78-layer
    model: 743.91 B parameters, 753.86 B with its module."""
    published = catalog_row("GLM-5")["config"]
    cfg = config_from_hf(published)
    assert cfg.num_layers == 78 and cfg.hidden_size == 6144
    assert cfg.attention == "latent" and \
        cfg.mlp == ("gated",) * 3 + ("experts",) * 75
    assert (cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rope_theta, cfg.attn_scale) == (64, 2048, 512, 192, 64, 256,
                                                1e6, None)
    assert cfg.rope_interleaved and cfg.index_rope_interleaved
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == \
        (2048, 32, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.num_shared_experts, cfg.mlp_width) == (256, 8, 2048, 1,
                                                        12288)
    assert cfg.router_score == "sigmoid" and cfg.router_bias and \
        cfg.norm_topk_prob and cfg.route_scale == 2.5
    assert not cfg.attn_gate and not cfg.tie_embeddings
    assert cfg.num_nextn_predict_layers == 1 and cfg.cache_entries == 79
    # the module's block is of the last layer's kinds
    assert cfg.mlp_kind(78) == "experts" and cfg.attention_kind(78) == \
        "latent"
    whole, module = parameters(cfg)
    assert (whole, module) == (753_864_139_008, 9_952_920_576)
    row = dict(published, published={"n_routed_experts": 256})
    # beside the norms, the index keys' LayerNorms and the routers' biases
    assert arithmetic_glm5.model_parameters(row) == 753_862_901_760
    assert arithmetic_glm5.model_parameters(row, module=False) == \
        743_910_014_976
    assert arithmetic_glm5.layer_parameters(row) == {
        "attention": 165_019_648, "indexer": 9_371_648,
        "dense_mlp": 226_492_416, "router": 1_572_864,
        "shared_experts": 37_748_736, "routed_expert": 37_748_736,
        "routed_mlp": 9_702_998_016, "dense_layer": 400_883_712,
        "routed_layer": 9_877_389_312, "vocabulary": 1_903_165_440,
        "module": 9_952_886_784}


def test_the_cells_file_is_the_share_the_issue_counts():
    cfg = MLA.model_config(CELL, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16,
                           seq_len=CELL["serve"]["served_context"])
    assert cfg.num_layers == 5 and cfg.experts_held == (0, 16) and \
        cfg.num_experts == 256 and cfg.vocab_size == 19360
    assert cfg.mlp == ("gated",) + ("experts",) * 4
    assert parameters(cfg) == (4_802_856_704, 893_223_936)
    assert arithmetic_glm5.model_parameters(CELL) == 4_802_740_224
    parts = arithmetic_glm5.layer_parameters(CELL)
    assert (parts["routed_layer"], parts["module"], parts["vocabulary"]) == \
        (817_692_672, 893_190_144, 237_895_680)
    assert CELL["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    rows, context = (CELL["serve"]["engine_rows"],
                     CELL["serve"]["served_context"])
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    # six entries: five layers and the module's block
    assert len(caches) == 6
    assert sum(c.size * 2 + k.size * 2 for c, k, _ in caches) == \
        6 * 1536 * rows * context
    assert arithmetic_glm5.block_bytes_per_position(CELL, 2) == 1408
    assert arithmetic_glm5.block_bytes_held_per_position(CELL, 2) == 1536
    assert arithmetic_glm5.expert_layers(CELL) == 5
    assert arithmetic_glm5.selecting_blocks(CELL) == 6


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    row = catalog_row("GLM-5")
    published = row["config"]
    for key, value in published.items():
        if key in CELL["reduced"]:
            assert CELL[key] != value and CELL["published"][key] == value
        else:
            assert CELL[key] == value, key
    assert CELL["source"].startswith(row["source_url"])


@pytest.mark.parametrize("key,value", [
    ("topk_method", "greedy"), ("scoring_func", "softmax"), ("n_group", 8),
    ("num_nextn_predict_layers", 2), ("qk_head_dim", 128),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})])
def test_what_the_file_says_and_the_program_cannot_is_refused(key, value):
    with pytest.raises(ValueError):
        config_from_hf(dict(TOY, **{key: value}))


def test_the_modules_block_has_a_cache_entry_of_its_own(toy):
    cfg = toy[0]
    assert kv_cache_kinds(cfg) == ["latent_index"] * 3
    assert kv_cache_shapes(cfg, 2) == [((2, CONTEXT, 128),
                                        (2, CONTEXT, 16))] * 3
    assert len(init_kv_caches(cfg, 2)) == 3
    # no ring, no state, no blocks: the index alone rolls a position back
    require_rollback_by_index(cfg, "the tick")
    with pytest.raises(ValueError, match="learned selection"):
        require_uniform_kv_caches(cfg, "the speculative verify step")


@pytest.mark.parametrize("field,value,named", [
    ("attention", ("latent", "sliding"), "ring of the sliding window"),
    ("attention", ("latent", "latent_sliding"), "ring of the window's"),
    ("attention", ("latent", "conv"), "short convolution's state"),
    ("attention", ("latent", "ssm"), "Mamba-2 mixer's states"),
    ("attention", ("latent", "s6"), "Mamba-1 mixer's states"),
    ("attention", ("latent", "eva"), "ONE window's rows"),
    ("block_length", 4, "diffusion over blocks")])
def test_the_tick_refuses_what_no_index_rolls_back(field, value, named):
    cfg = gpt_model.GPTConfig(
        num_layers=2, hidden_size=64, num_heads=4, vocab_size=64,
        seq_len=32, sliding_window=4, conv_taps=3,
        num_nextn_predict_layers=1, **{field: value})
    with pytest.raises(ValueError, match=named):
        require_rollback_by_index(cfg, "the tick that verifies a draft")


# ---- the program against the reference (b) -----------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    """The training call: the model's logits and picks, and the module's
    logits from the model's own hidden states and each position's next
    token."""
    cfg, model, params = toy
    ids = wanted["ids"]

    def call(params, ids):
        (logits, hidden), routing = model.apply(params, ids,
                                                with_hidden=True)
        following = jnp.concatenate(
            [ids[:, 1:], jnp.zeros((1, 1), jnp.int32)], axis=1)
        return logits, routing, model.apply(params, following,
                                            draft_from=hidden)

    logits, routing, drafted = highest(jax.jit(call), params,
                                       jnp.asarray(ids[None]))
    np.testing.assert_allclose(logits[0], wanted["logits"], atol=TOL)
    assert np.array_equal(np.sort(np.asarray(routing["experts"]), -1),
                          np.sort(wanted["picks"], -1))
    # (the last position is fed a pad in both)
    np.testing.assert_allclose(drafted[0], wanted["module_logits"], atol=TOL)


def _prefilled(gen, ids, n):
    """``ids``' first ``n`` through the chunk step: (last logits, caches)."""
    return highest(gen._run_chunked_prefill, [ids[:n]],
                   jnp.asarray([n], jnp.int32), 1)


def test_chunks_then_decode_and_verify_equal_the_reference(toy, reference,
                                                           chunk=8,
                                                           prompt=21):
    """Prefill in chunks (the module's block too), then through the caches
    the one-token ``_decode`` and the two-position ``_verify_draft`` over
    the model's own greedy continuation (so that what the module is fed is
    what the sequence holds): logits, picks and selected sets of both
    queries, the module's logits, picks and selections; a wrong draft is
    rolled back by the index alone and changes nothing after it."""
    cfg, model, params = toy
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    head = np.asarray(jax.random.randint(
        jax.random.PRNGKey(prompt), (prompt,), 4, TOY["vocab_size"]))
    ids = np.asarray(highest(
        gen.generate, [head], GenerationConfig(max_new_tokens=S - prompt))[0])
    assert ids.shape == (S,)
    logits, picks, (chosen, real), module = highest(
        reference.logits_experts_selections,
        REF.weights_from_program(params), ids, (0, S), module=True)
    logits, module_logits = np.asarray(logits), np.asarray(module[0])
    picks = np.concatenate([np.asarray(picks), np.asarray(module[1])])
    chosen = np.concatenate([np.asarray(chosen), np.asarray(module[2][0])])
    real = np.concatenate([np.asarray(real), np.asarray(module[2][1])])
    last, caches = _prefilled(gen, ids, prompt)
    np.testing.assert_allclose(last[0], logits[prompt - 1], atol=TOL)
    # one token a step, as Generator.generate runs it
    one, at = [(k, v, i) for k, v, i in caches], prompt
    for _ in range(3):
        got, one, said = highest(
            gen._decode, params, jnp.asarray(ids[None, at:at + 1]),
            one[0][2], one)
        np.testing.assert_allclose(got[0], logits[at], atol=TOL)
        for layer in range(cfg.num_layers):
            assert as_sets(said["selected"][layer],
                           said["selected_real"][layer]) == as_sets(
                chosen[layer, at:at + 1], real[layer, at:at + 1])
        at += 1
    # the module's entry came back from _decode as it went in (the call
    # took the arrays it was handed: prefilled once more to compare)
    _, caches = _prefilled(gen, ids, prompt)
    assert all(np.array_equal(a, b) for a, b in zip(one[-1][:2],
                                                    caches[-1][:2]))
    # two positions a row: the sure token and a draft, every other tick
    # the token that does follow (kept) and between them a wrong one
    # (taken back)
    at, tick = prompt, 0
    while at + 2 < S:
        right = tick % 2 == 1
        tick += 1
        draft = ids[at + 1] if right else (ids[at + 1] + 1) % 256
        _, caches, said = highest(
            gen._verify_draft, params, jnp.asarray(ids[None, at:at + 1]),
            caches[0][2], caches, jnp.asarray([draft], jnp.int32),
            jnp.asarray([9], jnp.int32), jnp.zeros((1,), bool))
        assert bool(said["offered"][0]) and \
            bool(said["accepted"][0]) == right
        kept = 1 + int(right)
        np.testing.assert_allclose(said["logits"][0, :kept],
                                   logits[at:at + kept], atol=TOL)
        np.testing.assert_allclose(said["drafted_logits"][0, :kept],
                                   module_logits[at:at + kept], atol=TOL)
        for block in range(cfg.cache_entries):
            assert as_sets(said["selected"][block, 0, :kept],
                           said["selected_real"][block, 0, :kept]) == \
                as_sets(chosen[block, at:at + kept],
                        real[block, at:at + kept])
        got = np.asarray(said["experts"]).reshape(2, 1, 2, -1)[:, 0, :kept]
        assert np.array_equal(np.sort(got, -1),
                              np.sort(picks[:, at:at + kept], -1))
        # what the tick drafts is the reference's module's greedy token at
        # the newest confirmed position
        assert int(said["draft"][0]) == \
            int(module_logits[at + kept - 1].argmax())
        assert all(int(i[0]) == at + kept for _, _, i in caches)
        at += kept


# ---- a few queries a row against one (c) --------------------------------

def test_the_few_query_decode_is_the_one_query_decode(toy, wanted):
    """``LatentAttention`` over two new positions a row at per-row indices
    (each query its own selection, one gather) gives what two one-query
    calls give, position by position: logits and selected sets, for rows
    of unlike lengths."""
    cfg, model, params = toy
    ids = wanted["ids"]
    gen = Generator(model, params, cfg, prefill_chunk=8)
    lengths = [13, 26]
    rows = [_prefilled(gen, ids, n)[1] for n in lengths]
    caches = [(jnp.concatenate([a[0], b[0]]), jnp.concatenate([a[1], b[1]]),
               jnp.asarray(lengths, jnp.int32))
              for a, b in zip(*rows)]
    pair = jnp.asarray([[ids[n], ids[n + 1]] for n in lengths], jnp.int32)
    pos = jnp.asarray(lengths, jnp.int32)[:, None] + jnp.arange(2)

    @jax.jit
    def together(params, pair, pos, caches):
        return model.apply(params, pair, pos, caches, return_routing=True)

    @jax.jit
    def one(params, token, pos, caches):
        return model.apply(params, token, pos, caches, return_routing=True)

    logits, _, said = highest(together, params, pair, pos, caches)
    assert said["selected"].shape == (2, 2, 2, cfg.index_topk)
    step = caches
    for slot in (0, 1):
        want, step, told = highest(one, params, pair[:, slot:slot + 1],
                                   pos[:, slot:slot + 1], step)
        np.testing.assert_allclose(logits[:, slot], want[:, 0], atol=TOL)
        for layer in range(2):
            assert as_sets(said["selected"][layer, :, slot],
                           said["selected_real"][layer, :, slot]) == \
                as_sets(told["selected"][layer], told["selected_real"][layer])


def test_the_indexers_pairs_are_the_files(toy, wanted):
    """``indexer_rope_interleave`` false turns other channels together: the
    selection, and with it the logits, are another model's."""
    cfg, model, params = toy
    other = GPTModel(toy_config(index_rope_interleaved=False))
    logits, _ = highest(jax.jit(other.apply), params,
                        jnp.asarray(wanted["ids"][None]))
    assert np.abs(np.asarray(logits[0]) - wanted["logits"]).max() > 1e-3


# ---- the share (f) ------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_whole(reference):
    """The partial results of the four shares of the toy's routed layer
    and of the module's, the shared expert counted once, add up to the
    uncut reference's layer."""
    whole = dict(TOY, n_routed_experts=16, share_index=0)
    cfg = MLA.model_config(whole, dtype=jnp.float32, seq_len=CONTEXT)
    params = shake(init_params(GPTModel(cfg), jax.random.PRNGKey(3),
                               jnp.ones((1, 8), jnp.int32)), SHAKEN)
    weights = REF.weights_from_program(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (S, 64), jnp.float32)
    full = REF.Reference(DRIVER.reference_settings(whole))
    for layer in (weights["layers"][1], weights["module"]["layer"]):
        want, picks, _ = full.layer(x, layer)
        # the layer less its routed experts: attention and shared expert
        none = {"attn": layer["attn"], "mlp": dict(
            layer["mlp"], w_gate_up=layer["mlp"]["w_gate_up"][:0],
            w_down=layer["mlp"]["w_down"][:0])}
        base, _, _ = full.layer(x, none)
        total = base
        for share in range(4):
            held = slice(4 * share, 4 * share + 4)
            part = REF.Reference(DRIVER.reference_settings(
                dict(TOY, share_index=share)))
            out, part_picks, _ = part.layer(x, {
                "attn": layer["attn"], "mlp": dict(
                    layer["mlp"], w_gate_up=layer["mlp"]["w_gate_up"][held],
                    w_down=layer["mlp"]["w_down"][held])})
            assert np.array_equal(part_picks, picks)
            total = total + (out - base)
        np.testing.assert_allclose(total, want, atol=TOL)


# ---- the decode under its selection's mask (ISSUE 59) ----------------------

def test_the_decode_under_the_mask_is_the_gathered_decode(
        selecting_decode, selecting_cores_traced):
    """What the ``selects`` branch calls for a decode
    (``latent_attention_over_selection``) at widths the kernel takes
    (GLM-5's: two queries a row, keys of 192 + 64 and values of 256),
    the kernel interpreted: the cache of four key blocks goes
    under the mask whatever its rows hold, and gives what
    ``latent_attention_gathered`` gives of the same cache and table."""
    args, want = selecting_decode(2, 16, 192, 256, [37, 1023, 2500])
    before = selecting_cores_traced(2).get("under_mask", 0)
    got = gpt_model.latent_attention_over_selection(*args, interpret=True)
    assert selecting_cores_traced(2)["under_mask"] == before + 1
    assert got.shape == want.shape == (3, 2, 16, 256)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("starts,under_mask", [([100, 900], True),
                                               ([100, 3500], False)],
                         ids=["rows-that-hold-little", "rows-that-hold-much"])
def test_the_rule_by_what_the_rows_hold_has_one_answer(
        selecting_decode, selecting_cores_traced, monkeypatch, starts,
        under_mask):
    """A cache longer than a query's gather is worth (here one key block
    of 1,024 positions a (row, query): four blocks are too many to take
    statically) is read ``by_held``: under the mask while the call's rows
    hold no more key blocks than their gathers are worth, gathered beyond;
    one result on both sides."""
    from alpa_tpu.ops import latent_attention as kernels
    monkeypatch.setattr(gpt_model, "GATHER_WORTH_KEY_BLOCKS", 1)
    args, want = selecting_decode(2, 16, 192, 256, starts, seed=1)
    held = int(kernels.decode_blocks(args[5], 2, 4096).sum())
    assert (held <= len(starts) * 2) == under_mask
    before = selecting_cores_traced(2).get("by_held", 0)
    got = jax.jit(lambda *a: gpt_model.latent_attention_over_selection(
        *a[:4], args[4], *a[4:], interpret=True))(*args[:4], *args[5:])
    assert selecting_cores_traced(2)["by_held"] == before + 1
    np.testing.assert_allclose(got, want, atol=1e-5)
