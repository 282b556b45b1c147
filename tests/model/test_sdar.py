"""SDAR (``model_type`` sdar_moe): the Qwen3-MoE block under a block-causal
mask, generated from by diffusion over blocks.  The program's model and its
cached paths (prefill in chunks, the one block step) against the plain
reference of ``chipbench/references/sdar_moe_decoder.py`` on seeded random
weights at a small size, in float32 at full matmul precision; the
published ``config.json`` through ``config_from_hf``; the cell's driver on
its toy configuration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                      reference_attention)
from alpa_tpu.serve.generation import (BlockDiffusion, GenerationConfig,
                                       Generator)
from alpa_tpu.testing import highest, init_params
from chipbench import run

TOY = run.load_json(run.HERE, "configs", "toy-sdar.json")
CELL = run.load_json(run.HERE, "configs", "sdar-30b-a3b-1chip.json")
DRIVER = run.load_module("drivers", "serve_diffusion")
REF = run.load_module("references", "sdar_moe_decoder")
L, MASK, CONTEXT = 4, 250, 96
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config(**kwargs):
    return DRIVER.model_config(TOY, dtype=jnp.float32,
                               param_dtype=jnp.float32, seq_len=CONTEXT,
                               **kwargs)


@pytest.fixture(scope="module")
def reference():
    return REF.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    model = GPTModel(cfg)
    params = init_params(model, jax.random.PRNGKey(3),
                         jnp.ones((1, 8), jnp.int32))
    return cfg, model, params, REF.weights_from_program(params)


def generator(toy, steps=2, remasking="low_confidence_static", chunk=8):
    cfg, model, params, _ = toy
    return Generator(model, params, cfg, prefill_chunk=chunk,
                     diffusion=BlockDiffusion(MASK, steps, remasking))


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(4, MASK, size=n).astype(
        np.int32)


# ---- the configuration ------------------------------------------------

def test_config_from_hf_reads_the_published_config():
    hf = dict(CELL, num_hidden_layers=CELL["published"]["num_hidden_layers"])
    cfg = config_from_hf(hf, block_length=4)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_size) == \
        (2048, 32, 4, 128)
    assert (cfg.num_layers, cfg.vocab_size, cfg.seq_len) == \
        (48, 151936, 32768)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width) == \
        (128, 8, 768)
    assert cfg.mlp == ("experts",) * 48 and cfg.attention == "full"
    assert cfg.norm == "rmsnorm" and cfg.layer_norm_eps == 1e-6
    assert cfg.positions == "rotary" and cfg.rope_theta == 1e6
    assert cfg.qk_norm == "head" and not cfg.use_bias
    assert cfg.norm_topk_prob and cfg.router_score == "softmax"
    assert not cfg.router_bias and cfg.num_shared_experts == 0
    assert not cfg.tie_embeddings and cfg.block_length == 4
    # without a deployment's block length it is the causal block
    assert config_from_hf(hf).block_length == 0


@pytest.mark.parametrize("depth, parameters", [(48, 30_532_122_624),
                                               (6, 4_361_055_744)])
def test_the_parameter_tree_counts_what_the_issue_counts(depth, parameters):
    cfg = config_from_hf(dict(CELL, num_hidden_layers=depth), block_length=4)
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == \
        parameters


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    row = catalog_row("SDAR-30B-A3B-Chat")
    differ = {k for k, v in row["config"].items() if CELL.get(k) != v}
    assert differ == set(CELL["reduced"]) == {"num_hidden_layers"}
    assert CELL["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"]
    assert CELL["num_hidden_layers"] == 6


@pytest.mark.parametrize("hf, message", [
    (dict(CELL, use_sliding_window=True), "use_sliding_window"),
    (dict(CELL, rope_scaling={"type": "yarn"}), "rope_scaling")])
def test_what_the_block_does_not_know_is_refused(hf, message):
    with pytest.raises(ValueError, match=message):
        config_from_hf(hf)


def test_mlp_only_layers_and_the_sparse_step_choose_the_dense_layers():
    hf = dict(TOY, num_hidden_layers=6, mlp_only_layers=[1],
              decoder_sparse_step=2)
    assert config_from_hf(hf).mlp == ("gated", "gated", "gated", "experts",
                                      "gated", "experts")


# ---- the mask ----------------------------------------------------------

def test_the_block_causal_mask_by_hand():
    """Three blocks of 2 over 6 positions: a query sees its own block
    whole and the blocks before it."""
    want = np.array([[1, 1, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0],
                     [1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 0, 0],
                     [1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 1, 1]], bool)
    at = np.arange(6)
    assert (np.asarray(REF.block_causal_mask(at, at, 2)) == want).all()
    # no scores, so a query averages the values of the keys it sees, and
    # the values are the positions themselves
    q = jnp.zeros((1, 6, 1, 4))
    v = jnp.broadcast_to(jnp.arange(6.0)[None, :, None, None], (1, 6, 1, 4))
    seen_mean = [at[want[i]].mean() for i in range(6)]
    out = reference_attention(q, q, v, causal=True, block=2)
    np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]), seen_mean,
                               rtol=1e-6)
    # with an offset a row, as the cached paths call it: the queries at
    # positions 2 and 3 over a cache of 6
    out = reference_attention(q[:, :2], q, v, causal=True, block=2,
                              offset=jnp.asarray([2]))
    np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]), seen_mean[2:4],
                               rtol=1e-6)


@pytest.mark.parametrize("kwargs", [{"window": 4}, {"causal": False},
                                    {"k_positions": jnp.zeros((1, 6),
                                                              jnp.int32)}])
def test_the_block_mask_goes_with_nothing_else(kwargs):
    q = jnp.zeros((1, 6, 1, 4))
    with pytest.raises(ValueError, match="block-causal"):
        reference_attention(q, q, q, **{"causal": True, "block": 2,
                                        **kwargs})


@pytest.mark.parametrize("which", ["program", "reference"])
def test_a_position_sees_its_block_and_no_later_one(toy, reference, which):
    cfg, model, params, weights = toy

    def logits(ids):
        if which == "reference":
            return np.asarray(reference.logits(weights, ids))
        return np.asarray(highest(model.apply, params, ids[None])[0])[0]

    ids = ids_of(12)
    base = logits(ids)
    later_in_block = ids.copy()
    later_in_block[7] = (ids[7] + 1) % MASK     # position 5's own block
    moved = logits(later_in_block)
    assert np.abs(moved[5] - base[5]).max() > 1e-3
    assert np.abs(moved[:4] - base[:4]).max() < TOL
    next_block = ids.copy()
    next_block[8] = (ids[8] + 1) % MASK
    moved = logits(next_block)
    assert np.abs(moved[:8] - base[:8]).max() < TOL
    assert np.abs(moved[8:] - base[8:]).max() > 1e-3


def test_full_forward_equals_the_reference(toy, reference):
    cfg, model, params, weights = toy
    ids = ids_of(24, seed=1)
    ids[[9, 10, 22]] = MASK         # masks are ordinary ids
    want = np.asarray(reference.logits(weights, ids))
    got, routing = highest(model.apply, params, ids[None])
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=TOL)
    _, chosen = reference.hidden(weights, ids)
    assert (np.sort(np.asarray(routing["experts"]), -1) ==
            np.sort(np.stack([np.asarray(c) for c in chosen]), -1)).all()


# ---- the cached paths --------------------------------------------------

def _run_blocks(gen, prompt, new_tokens):
    """One request alone through prefill and block steps, every forward's
    record kept: ``(tokens, [(kind, base, ids, logits, unmasked)], caches)``."""
    cfg = gen.config
    start, block = gen.first_block(prompt)
    lengths = jnp.asarray([start], jnp.int32)
    if start:
        _, caches = gen._run_chunked_prefill([prompt[:start]], lengths, 1)
    else:
        from alpa_tpu.serve.generation import fresh_kv_caches
        caches = [(k, v, lengths) for k, v, _ in fresh_kv_caches(cfg, 1)]
    ids = jnp.asarray(block[None])
    left = jnp.full((1,), gen.denoising_steps, jnp.int32)
    settings = gen.sampling_settings(1, GenerationConfig())
    key = jax.random.PRNGKey(0)
    record, tokens = [], list(prompt)
    while len(tokens) < len(prompt) + new_tokens:
        before, base = np.asarray(ids)[0], int(caches[0][2][0])
        ids, left, unmasked, commits, logits, caches, _, key = highest(
            gen._block_step, gen.params, ids, caches[0][2], caches, left,
            settings, key)
        kind = "commit" if bool(commits[0]) else "denoise"
        record.append((kind, base, before, np.asarray(logits)[0],
                       np.asarray(unmasked)[0]))
        if kind == "commit":
            tokens = tokens[:base] + before.tolist()
            assert int(caches[0][2][0]) == base + L
        else:
            assert int(caches[0][2][0]) == base   # the index stays
    return tokens, record, caches


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 18, 23])
def test_prefill_and_block_steps_equal_the_reference(toy, reference,
                                                     prompt_len):
    """Every remainder of the prompt's length by the block's (3 % 4, 8 % 4,
    13 % 4, 18 % 4, 23 % 4 = 3, 0, 1, 2, 3; 3 is shorter than a block):
    the commit path at every position and the denoise path at every
    (block, forward) state against the reference's whole pass; the cache
    of what was committed against K and V of the whole pass."""
    cfg, model, params, weights = toy
    gen = generator(toy)
    prompt = ids_of(prompt_len, seed=prompt_len)
    tokens, record, caches = _run_blocks(gen, prompt, 12)
    assert gen.decode_traces == 1
    final = np.asarray(tokens, np.int32)
    whole = np.asarray(reference.logits(weights, final))
    kinds = [kind for kind, *_ in record]
    assert kinds.count("commit") >= 3 and kinds.count("denoise") >= 4
    for kind, base, before, logits, unmasked in record:
        if kind == "commit":
            np.testing.assert_allclose(logits, whole[base:base + L],
                                       atol=TOL)
            assert not unmasked.any()
        else:
            state = np.concatenate([final[:base], before])
            want = np.asarray(reference.logits(weights, state,
                                               rows=(base, L)))
            np.testing.assert_allclose(logits, want, atol=TOL)
            # a decided position is never unmasked again
            assert not (unmasked & (before != MASK)).any()
    # the committed cache: K and V of the whole pass under the mask
    held = int(caches[0][2][0])
    fresh = [(k, v, jnp.int32(0)) for k, v, _ in
             gpt_model.init_kv_caches(cfg, 1)]
    _, want_caches = highest(
        model.apply, params, final[None, :held],
        jnp.arange(held)[None], fresh)
    for (k, v, _), (wk, wv, _) in zip(caches, want_caches):
        np.testing.assert_allclose(np.asarray(k)[0, :held],
                                   np.asarray(wk)[0, :held], atol=TOL)
        np.testing.assert_allclose(np.asarray(v)[0, :held],
                                   np.asarray(wv)[0, :held], atol=TOL)


@pytest.mark.parametrize("steps, remasking", [
    (1, "low_confidence_static"), (2, "low_confidence_static"),
    (4, "low_confidence_static"), (3, "low_confidence_dynamic")])
def test_generate_is_the_references_loop(toy, reference, steps, remasking):
    """The same tokens, unmasked at the same forwards, wherever the
    reference's own choices were not near-ties."""
    cfg, model, params, weights = toy
    gen = generator(toy, steps, remasking)
    for n in (5, 8, 14):
        prompt = ids_of(n, seed=10 + n)
        with jax.default_matmul_precision("highest"):
            tokens, forwards = gen.generate_blocks(
                [prompt], GenerationConfig(max_new_tokens=11))
        want, want_forwards, seen = reference.generate(
            weights, prompt, 11, mask_token_id=MASK, denoising_steps=steps,
            remasking=remasking, pad_to=32)
        margins = []
        for _n, _base, block, logits, take in seen:
            top = np.sort(logits, -1)
            margins += list((top[:, -1] - top[:, -2])[take])
        if min(margins) > 1e-3:
            assert tokens[0] == want and forwards[0] == want_forwards
        assert len(tokens[0]) == 11


def test_generate_takes_a_batch_of_unlike_phases(toy):
    gen = generator(toy)
    prompts = [ids_of(n, seed=n) for n in (3, 8, 9, 14)]
    cfg = GenerationConfig(max_new_tokens=10)
    together, _ = gen.generate_blocks(prompts, cfg)
    for p, row in zip(prompts, together):
        assert gen.generate_blocks([p], cfg)[0][0] == row
    out = gen.generate(prompts, cfg)
    assert [list(o[len(p):]) for o, p in zip(out, prompts)] == together
    # a batch of one length comes back as one array
    assert gen.generate(np.stack([prompts[1]] * 2), cfg).shape == (2, 18)


def test_a_generator_is_told_how_its_configuration_generates(toy):
    cfg, model, params, _ = toy
    with pytest.raises(ValueError, match="BlockDiffusion"):
        Generator(model, params, cfg, prefill_chunk=8)
    causal = toy_config(block_length=0)
    with pytest.raises(ValueError, match="no other takes one"):
        Generator(GPTModel(causal), params, causal,
                  diffusion=BlockDiffusion(MASK))
    with pytest.raises(ValueError, match="no multiple of the block"):
        Generator(model, params, cfg, prefill_chunk=6,
                  diffusion=BlockDiffusion(MASK))
    with pytest.raises(ValueError, match="outside the vocabulary"):
        Generator(model, params, cfg, diffusion=BlockDiffusion(4096))
    with pytest.raises(ValueError, match="unknown remasking"):
        BlockDiffusion(MASK, remasking="random")


# ---- the cell's driver -------------------------------------------------

def test_driver_runs_the_toy_cell(toy_context):
    """``chipbench/drivers/serve_diffusion.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' balance, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the replay and the check against the reference."""
    obs = DRIVER.run(toy_context("toy-sdar.reasoning", "toy-reasoning", 3.0, 2))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["checked_states"] == checks["states_due"] >= 4
    assert checks["replay_token_mismatches"] == 0
    assert checks["unmasked_unlike_the_reference"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["compiles_in_window"] == 0
    assert checks["block_step_traces"] == 1
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 2
    assert obs["decode_trace"] == {}    # a CPU trace has no TPU plane
    obs.update(peaks=None, config=TOY, seconds=3.0)
    # 4 tokens in 3 forwards, and a little over where a prompt's tail
    # heads the first block
    assert 1.3 < run.metric_reader("tokens_per_forward")(obs) < 1.6
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 8
    for name in ("block_step_ms", "block_step_hbm_roofline_pct",
                 "block_step_head_ms"):
        assert run.metric_reader(name)(obs) is None
    traced = {**obs, **obs["traced"]}
    ticks = [s for s in traced["program_spans"]
             if s["name"] == "engine.decode-tick"]
    assert ticks and all(
        {"active", "denoising", "committing", "unmasked"} <= set(s["args"])
        and s["args"]["denoising"] + s["args"]["committing"] <=
        s["args"]["active"] for s in ticks)
    assert run.metric_reader("tick_ms")(traced) > 0
    assert run.metric_reader("engine_occupancy_pct")(traced) > 50


def _causal_inside_a_block(monkeypatch):
    plain = gpt_model.reference_attention
    monkeypatch.setattr(
        gpt_model, "reference_attention",
        lambda *args, **kwargs: plain(*args, **{**kwargs, "block": 0}))


def test_driver_reads_a_causal_block_as_not_correct(toy_context,
                                                     monkeypatch):
    """One of the controls the cell's limits are set against, at the toy
    size: the plain causal mask inside a block (mathematics left out, one
    comparison a score saved) serves every request in full and is not
    correct."""
    _causal_inside_a_block(monkeypatch)
    obs = DRIVER.run(toy_context("toy-sdar.reasoning", "toy-reasoning", 3.0, 0))
    checks = obs["checks"]
    assert obs["failed"] == 0 and checks["checked_requests"] == 4
    assert not obs["correct"], checks
