"""The DeepSeek-V2 (``model_type`` deepseek_v2) kinds of the one decoder
definition (latent attention with a latent cache, expanded for several new
positions and absorbed for one; YaRN frequencies on interleaved rotary
pairs; a leading dense layer; group-limited routing over experts of which
the program holds a share, beside two shared ones) against the plain
reference ``chipbench/references/deepseek_v2_decoder.py`` at a toy size on
the CPU: hidden 64, 4 heads of 16 + 8 and 16, ranks 24 and 16, 16 experts
in 4 groups (the best 2 groups, then the best 3 experts) of which 4 are
held, 2 shared, seeded weights.  Float32 at full matmul precision, so that
what is compared is the mathematics: prefill and then decoding through the
latent cache against the reference's full forward pass, logits and not
tokens.  The benchmark's cell compares the bfloat16 program with the same
reference on the chip."""
import dataclasses
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model, moe
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                      kv_cache_shapes,
                                      latent_attention_absorbed,
                                      latent_attention_expanded,
                                      uniform_kv_caches, yarn_inv_freq)
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.testing import highest, init_params
from chipbench import run

TOY = run.load_json(run.HERE, "configs", "toy-deepseek-v2.json")
CELL = run.load_json(run.HERE, "configs", "deepseek-v2-1chip.json")
DRIVER = run.load_module("drivers", "serve_mla")
CONTEXT, S = 96, 48
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config(**kwargs):
    return DRIVER.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (3, S)): norm weights away from 1, so that
    a forgotten one shows."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)

    def shake(path, x):
        if path[-1].key != "scale":
            return x
        return x * jax.random.uniform(jax.random.PRNGKey(len(str(path))),
                                      x.shape, minval=0.5, maxval=1.5)

    return model, jax.tree_util.tree_map_with_path(shake, params), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits of every position of every sequence."""
    mod, ref = reference
    _model, params, ids = toy
    weights = mod.weights_from_program(params)
    return np.stack([np.asarray(ref.logits(weights, row)) for row in ids])


# ---- the configuration ------------------------------------------------

def test_config_from_hf_reads_the_catalog_rows_config():
    """The row's ``config`` as the catalog has it (the cell's file keeps
    every key of it but the three it reduces)."""
    hf = {k: CELL["published"].get(k, v) for k, v in CELL.items()}
    cfg = config_from_hf(hf)
    assert cfg.attention == "latent" and cfg.num_layers == 60
    assert cfg.mlp == ("gated",) + 59 * ("experts",)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_group,
            cfg.topk_group, cfg.num_shared_experts) == (160, 6, 8, 3, 2)
    assert cfg.route_scale == 16.0 and not cfg.norm_topk_prob
    assert (cfg.mlp_width, cfg.expert_width) == (12288, 1536)
    assert cfg.rope_interleaved and cfg.rope_yarn == (40.0, 4096, 32.0,
                                                      1.0, 1.0)
    assert cfg.attn_scale == pytest.approx(0.114721, abs=1e-6)
    assert cfg.experts_held is None and cfg.vocab_size == 102400
    assert cfg.layer_norm_eps == 1e-6 and not cfg.tie_embeddings
    assert uniform_kv_caches(cfg)
    assert kv_cache_shapes(dataclasses.replace(cfg, num_layers=1,
                                               seq_len=4096), 3) == [
        ((3, 4096, 512), (3, 64, 4096))]
    with pytest.raises(ValueError, match="unknown rope_scaling type"):
        config_from_hf({**hf, "rope_scaling": {"type": "linear",
                                                "factor": 2}})
    with pytest.raises(ValueError, match="group_limited_greedy"):
        config_from_hf({**hf, "topk_method": "greedy"})
    afmoe = run.load_json(run.HERE, "configs", "toy-trinity.json")
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({**afmoe, "rope_scaling": hf["rope_scaling"]})


def test_the_cells_file_is_the_program_the_issue_counts():
    """The cell's configuration through the driver: the share, and the
    parameters as ``jax.eval_shape`` counts them."""
    cfg = DRIVER.model_config(CELL, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16, seq_len=16384)
    assert cfg.experts_held == (0, 20) and cfg.num_experts == 160
    assert cfg.num_layers == 5 and cfg.vocab_size == 12800
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    assert all(leaf.dtype == jnp.bfloat16 for _, leaf in leaves)
    norms = sum(leaf.size for path, leaf in leaves
                if path[-1].key == "scale")
    assert norms == 5 * (2 * 5120 + 1536 + 512) + 5120
    assert sum(leaf.size for _, leaf in leaves) - norms == 3_145_400_320
    mlp = shapes["params"]["h1"]["mlp"]
    assert mlp["router"]["kernel"].shape == (5120, 160)
    assert mlp["w_gate_up"].shape == (20, 5120, 3072)
    assert mlp["w_down"].shape == (20, 1536, 5120)
    assert kv_cache_shapes(cfg, 32)[0] == ((32, 16384, 512),
                                           (32, 64, 16384))


def test_yarn_frequencies_and_scale_at_the_published_settings(reference):
    mod, _ = reference
    inv_freq, (low, high) = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0,
                                          1.0)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv_freq[:11], f[:11], rtol=1e-12)
    np.testing.assert_allclose(inv_freq[23:], f[23:] / 40, rtol=1e-12)
    # half way up the ramp, pair 16.5 does not exist: pair 17 is 7/13 up
    np.testing.assert_allclose(
        inv_freq[17], f[17] * (1 - 7 / 13) + f[17] / 40 * 7 / 13,
        rtol=1e-12)
    theirs, low_r, high_r = mod.yarn_frequencies(
        64, 10000.0, CELL["rope_scaling"])
    assert (low_r, high_r) == (10, 23)
    np.testing.assert_allclose(theirs, inv_freq, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m * m == pytest.approx(1.589626, abs=1e-6)
    assert gpt_model.yarn_mscale(40, 0.707) == pytest.approx(m)
    assert 192 ** -0.5 == pytest.approx(0.0721688, abs=1e-7)
    assert mod.softmax_scale(128, 64, CELL["rope_scaling"]) == \
        pytest.approx(0.114721, abs=1e-6)


def test_interleaved_pairs_turn_together():
    """Pair i is channels 2i and 2i + 1: the program keeps the pairs'
    first channels in the first half of what it returns."""
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 1, 8) + 1
    pos = jnp.asarray([[3]], jnp.int32)
    out = np.asarray(gpt_model.apply_rotary(x, pos, 10000.0,
                                            interleaved=True))[0, 0, 0]
    for i in range(4):
        angle = 3 * 10000.0 ** (-2 * i / 8)
        a, b = float(x[0, 0, 0, 2 * i]), float(x[0, 0, 0, 2 * i + 1])
        assert out[i] == pytest.approx(a * math.cos(angle) -
                                       b * math.sin(angle), rel=1e-5)
        assert out[4 + i] == pytest.approx(b * math.cos(angle) +
                                           a * math.sin(angle), rel=1e-5)


def test_group_limited_choice_worked_by_hand(reference):
    """Two tokens over 8 experts in 4 groups of 2, the best 2 groups, then
    the best 3 experts.  Token 0: scores (.05 .20 | .30 .01 | .02 .25 |
    .10 .07): the groups' best are .20 .30 .25 .10, so groups 1 and 2;
    inside them .30 .25 .02 (expert 4, not .20 of the left-out group 0).
    Token 1: the two largest scores share a group: (.40 .35 | .01 .02 |
    .05 .03 | .08 .06): groups 0 and 3; .40 .35 .08."""
    scores = np.array([[.05, .20, .30, .01, .02, .25, .10, .07],
                       [.40, .35, .01, .02, .05, .03, .08, .06]])
    weights, experts, probs = moe.topk_routing(
        jnp.log(jnp.asarray(scores, jnp.float32)), 3, False, "softmax",
        None, 16.0, n_group=4, topk_group=2)
    np.testing.assert_array_equal(experts, [[2, 5, 4], [0, 1, 6]])
    np.testing.assert_allclose(probs, scores, rtol=1e-5)
    np.testing.assert_allclose(
        weights, 16 * np.array([[.30, .25, .02], [.40, .35, .08]]),
        rtol=1e-5)
    # no groups: the three largest wherever they are
    _, free, _ = moe.topk_routing(
        jnp.log(jnp.asarray(scores, jnp.float32)), 3, False, "softmax")
    np.testing.assert_array_equal(free, [[2, 5, 1], [0, 1, 6]])
    mod, _ = reference
    theirs, chosen = mod.route(
        jnp.eye(2, dtype=jnp.float32),
        jnp.log(jnp.asarray(scores, jnp.float32)), 3, 4, 2, False, 16.0)
    np.testing.assert_array_equal(chosen, [[2, 5, 4], [0, 1, 6]])
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(theirs), np.asarray(chosen), -1),
        weights, rtol=1e-5)
    assert np.count_nonzero(theirs) == 6


# ---- the program against the reference --------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    model, params, ids = toy
    logits, routing = highest(model.apply, params, ids)
    np.testing.assert_allclose(logits, wanted, atol=TOL)
    # every token's 3 experts of all 16, whichever are held
    experts = np.asarray(routing["experts"])
    assert experts.shape == (2, 3 * S, 3)
    assert experts.min() >= 0 and experts.max() > 7
    assert routing["expert_counts"].shape == (2, 16)


def _scale_q_pe_by_head(fn):
    def wrong(q_nope, q_pe, *rest, **kwargs):
        heads = 1 + 0.5 * jnp.arange(q_pe.shape[2], dtype=q_pe.dtype)
        return fn(q_nope, q_pe * heads[:, None], *rest, **kwargs)
    return wrong


def _rotate_q_nope(fn):
    def wrong(q_nope, *rest, **kwargs):
        b, s = q_nope.shape[:2]
        pos = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
        return fn(gpt_model.apply_rotary(q_nope, pos, 10000.0), *rest,
                  **kwargs)
    return wrong


def _no_latent_norm(fn):
    def wrong(cfg, name):
        if name == "kv_a_norm":
            return lambda x: x
        return fn(cfg, name)
    return wrong


@pytest.mark.parametrize("variant", [
    ("_latent_attention_blocks", _rotate_q_nope),
    ("_latent_attention_blocks", _scale_q_pe_by_head),
    ("make_norm", _no_latent_norm),
    dict(attn_scale=(16 + 8) ** -0.5), dict(n_group=1, topk_group=1),
    dict(norm_topk_prob=True), dict(num_shared_experts=0),
    dict(route_scale=1.0)],
    ids=["rotary-on-q_nope", "rope-key-per-head", "c-before-its-norm",
         "scale-without-mscale2", "groups-ignored", "weights-renormalised",
         "shared-experts-left-out", "x16-left-out"])
def test_a_wrong_wiring_fails(toy, wanted, variant, monkeypatch):
    """Each piece of the wiring alone: rotary positions given to the
    channels that see none, a rope key that differs from head to head
    (each head's scaled by its own factor, which is what heads that do not
    share ONE key amounts to), the latent used (and cached) before its
    norm, the softmax scale without YaRN's mscale squared, the routing
    groups ignored, the weights renormalised, the shared experts or the
    routed scaling factor left out: each moves the logits by a thousand
    tolerances."""
    _model, params, ids = toy
    if isinstance(variant, dict):
        wrong = GPTModel(toy_config(**variant))
    else:
        name, wrap = variant
        monkeypatch.setattr(gpt_model, name, wrap(getattr(gpt_model, name)))
        wrong = GPTModel(toy_config())
    logits, _ = highest(wrong.apply, params, ids)
    assert np.abs(np.asarray(logits) - wanted).max() > 1000 * TOL


def test_absorbed_and_expanded_agree_on_one_cache():
    """The two paths are one function of the cache: three rows at
    positions of their own, the cache longer than what any row holds."""
    rng = np.random.default_rng(0)
    b, sk, h, dn, dr, dv, r = 3, 40, 4, 16, 8, 16, 16

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    c, k_pe, w = draw(b, sk, r), draw(b, dr, sk), draw(r, h, dn + dv) / 4
    last = jnp.asarray([4, 17, 39], jnp.int32)   # the newest position
    for s in (1, 5):
        q_nope, q_pe = draw(b, s, h, dn), draw(b, s, h, dr)
        args = (q_nope, q_pe, c, k_pe, w, 0.3, last - (s - 1))
        one = highest(latent_attention_absorbed, *args)
        other = highest(latent_attention_expanded, *args)
        assert one.shape == (b, s, h, dv)
        np.testing.assert_allclose(one, other, atol=2e-6)


def test_the_kernel_is_the_blocks():
    """``ops/latent_attention.py`` (interpreted here; compiled for the
    chip in ``tests/serve/test_decode_in_place.py``) against the
    ``jax.numpy`` blocks it replaces on a TPU, at the published head sizes:
    rows at starts of their own, one of which reaches into the second key
    block only with its last queries."""
    from alpa_tpu.ops import latent_attention as kernel
    rng = np.random.default_rng(1)
    b, sk, h, dn, dr, dv, r, sq = 3, 1024, 2, 128, 64, 128, 128, 32

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    c, k_pe, w = draw(b, sk, r), draw(b, dr, sk), draw(r, h, dn + dv) / 8
    q_nope, q_pe = draw(b, sq, h, dn), draw(b, sq, h, dr)
    offset = jnp.asarray([0, 992, 490], jnp.int32)
    assert kernel.fits(q_nope, c, w)
    assert not kernel.fits(q_nope[..., :16], c, w[..., :144])
    got = highest(kernel.expanded, q_nope, q_pe, c, k_pe, w, offset,
                  scale=0.1, interpret=True)
    want = highest(gpt_model._latent_attention_blocks, q_nope, q_pe, c,
                   k_pe, w, offset, scale=0.1)
    np.testing.assert_allclose(got, want, atol=5e-6)
    # off the TPU the dispatcher runs the blocks
    np.testing.assert_allclose(
        highest(latent_attention_expanded, q_nope, q_pe, c, k_pe, w, 0.1,
                offset), want, atol=1e-6)


def test_the_decodes_kernel_is_its_twin():
    """The absorbed kernel (interpreted) against ``_absorbed_core``: rows at
    the first position, at a key block's last and first, and a free row
    whose index has run past the cache."""
    from alpa_tpu.ops import latent_attention as kernel
    rng = np.random.default_rng(2)
    b, sk, h, r, dr = 4, 2048, 16, 128, 64

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    c, k_pe = draw(b, sk, r), draw(b, dr, sk)
    q_lat, q_pe = draw(b, 1, h, r), draw(b, 1, h, dr)
    index = jnp.asarray([0, 1023, 1024, 5000], jnp.int32)
    assert kernel.absorbed_fits(q_lat, c)
    assert not kernel.absorbed_fits(q_lat[..., :16], c[..., :16])
    got = highest(kernel.absorbed, q_lat, q_pe, c, k_pe, index, scale=0.1,
                  interpret=True)
    want = highest(gpt_model._absorbed_core, q_lat, q_pe, c, k_pe, index,
                   scale=0.1)
    np.testing.assert_allclose(got, want, atol=5e-6)


def _decode_all(gen, params, ids, first, caches, wanted_row):
    """Decode ``ids[first:]`` one token at a time; the largest difference
    of any step's logits from the reference's at that position."""
    index = jnp.asarray([first], jnp.int32)
    worst = 0.0
    for t in range(first, len(ids)):
        logits, caches, routing = gen._decode(
            params, jnp.asarray(ids[None, t:t + 1]), index, caches)
        index = index + 1
        worst = max(worst, float(np.abs(
            np.asarray(logits[0]) - wanted_row[t]).max()))
        # one row's three distinct experts in each of the two routed layers
        experts = np.sort(np.asarray(routing["experts"]), -1)
        assert experts.shape == (2, 1, 3) and \
            (np.diff(experts, axis=-1) > 0).all()
    return worst


@pytest.mark.parametrize("prompt_len,chunk", [
    (13, 8), (5, 8), (29, 8), (3, 16), (37, 12), (8, 8)])
def test_chunked_prefill_then_decode_equals_the_reference(
        toy, wanted, prompt_len, chunk):
    """Prompts that are no multiple of the chunk, a cache that is no
    multiple of the key block (96 positions in blocks of 16 or 12 are, in
    blocks of 8 too; 37 tokens in chunks of 12 end in a block that the
    cache's last positions do not fill): the prefill's last logits
    (expanded path) and every decode step's (absorbed path)."""
    model, params, ids = toy
    row = np.asarray(ids[0])
    gen = Generator(model, params, toy_config(), prefill_chunk=chunk)
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_chunked_prefill(
            [row[:prompt_len]], jnp.asarray([prompt_len]), 1)
        np.testing.assert_allclose(last[0], wanted[0, prompt_len - 1],
                                   atol=TOL)
        assert [(c.shape, k.shape) for c, k, _i in caches] == \
            3 * [((1, CONTEXT, 16), (1, 8, CONTEXT))]
        assert _decode_all(gen, params, row, prompt_len, caches,
                           wanted[0]) < TOL


def test_a_cache_that_is_no_multiple_of_the_key_block(toy, wanted):
    """Context 44 in chunks of 8: the sixth key block would pass the
    cache's end, starts early instead, and masks what the fifth held."""
    model, params, ids = toy
    cfg = toy_config(seq_len=44)
    gen = Generator(GPTModel(cfg), params, cfg, prefill_chunk=8)
    row = np.asarray(ids[1])
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_chunked_prefill(
            [row[:35]], jnp.asarray([35]), 1)
        np.testing.assert_allclose(last[0], wanted[1, 34], atol=TOL)
        assert _decode_all(gen, params, row[:44], 35, caches,
                           wanted[1]) < TOL


def test_bucketed_prefill_then_decode_equals_the_reference(toy, wanted):
    """The one dense prefill, right-padded to its bucket: rows of mixed
    lengths in one batch, then decode ticks over rows at unlike
    positions."""
    model, params, ids = toy
    gen = Generator(model, params, toy_config(), prompt_buckets=[32])
    lengths = [21, 5, 30]
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_bucketed_prefill(
            [np.asarray(ids[r, :n]) for r, n in enumerate(lengths)],
            jnp.asarray(lengths, jnp.int32), 3)
        index = jnp.asarray(lengths, jnp.int32)
        for r, n in enumerate(lengths):
            np.testing.assert_allclose(last[r], wanted[r, n - 1], atol=TOL)
        for step in range(12):
            tok = jnp.stack([ids[r, n + step] for r, n in
                             enumerate(lengths)])[:, None]
            logits, caches, _ = gen._decode(params, tok, index, caches)
            index = index + 1
            for r, n in enumerate(lengths):
                np.testing.assert_allclose(logits[r], wanted[r, n + step],
                                           atol=TOL)


def test_rows_of_mixed_lengths_in_one_engine(reference, toy):
    """Five requests over three rows through the engine's chunked
    admission (rows admitted while others decode, freed rows decoded
    along, prompts under a chunk and several chunks long): every served
    token has the reference's largest logit at its position."""
    from alpa_tpu.telemetry import metrics as tmetrics
    mod, ref = reference
    model, params, ids = toy
    registry = tmetrics.get_registry()
    gen = Generator(model, params, toy_config(), prefill_chunk=8)
    prompts = [np.asarray(ids[i % 3, :n])
               for i, n in enumerate([3, 13, 30, 9, 21])]
    new = [12, 20, 16, 30, 7]
    outs = [None] * len(prompts)
    before = registry.snapshot()
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=3,
                                          chunked_admission=True)

        def ask(i):
            outs[i] = engine.submit(
                prompts[i], GenerationConfig(max_new_tokens=new[i]))

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            engine.shutdown()
    after = registry.snapshot()
    weights = mod.weights_from_program(params)
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        logits = np.asarray(ref.logits(weights, out))
        served = out[len(p):]
        rows = logits[len(p) - 1:len(out) - 1]
        deficit = rows.max(-1) - rows[np.arange(n), served]
        assert deficit.max() < TOL, (len(p), deficit.max())

    def rise(name):
        return after[name] - before.get(name, 0)

    # the gauge's kind, and a position's bytes: (16 + 8) float32 a layer
    assert after['alpa_serving_kv_cache_bytes{kind="latent"}'] == \
        3 * CONTEXT * 3 * 24 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == 0
    # 3 rows x 3 experts in each of 2 layers a tick whose routing a later
    # tick read back; of those the rows that landed on experts 4-7
    steps = rise("alpa_serving_decode_steps_total")
    routed = rise("alpa_moe_routed_rows_total")
    assert routed in (18 * (steps - 1), 18 * steps)
    assert 0 < rise("alpa_moe_local_rows_total") < routed
    # of the held experts only: at most 4 a layer a tick
    assert rise("alpa_moe_experts_touched_total") <= 2 * 4 * steps
    # a served token's tick attended over its prompt and the tokens so far
    assert rise("alpa_serving_decode_positions_total") == sum(
        len(p) * n + n * (n + 1) // 2 for p, n in zip(prompts, new))


@pytest.mark.parametrize("what", ["pool", "disaggregated", "speculative",
                                  "beam"])
def test_a_latent_cache_is_refused_by_name(toy, what):
    """The block pool, the disaggregated prefill, the speculative verify
    step and beam search index per-head K and V of one shape: they refuse
    a latent cache, and say so."""
    model, params, ids = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=8)
    with pytest.raises(ValueError, match="hold a latent cache"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "disaggregated":
            PrefillEngine(gen)
        elif what == "speculative":
            gen.generate_speculative(gen, np.asarray(ids[0, :5]))
        else:
            gen.generate_beam(np.asarray(ids[0, :5]), num_beams=2)


# ---- the share --------------------------------------------------------

@pytest.mark.parametrize("window", [None, 16], ids=["all_rows", "windows"])
def test_the_shares_of_a_layer_add_up_to_the_whole(reference, window,
                                                   monkeypatch):
    """One expert layer of 16 experts over four chips: what the four
    shares give (the program's ``DroplessExperts`` told which 4 experts it
    holds, and the reference given the same 4), the shared experts
    counted once, is what the uncut reference gives for the whole layer."""
    if window:
        # the shares walk their rows in windows of 16 (ISSUE 60); the
        # layer that holds every expert never does
        monkeypatch.setattr(
            moe, "expert_window",
            lambda cfg, tokens: window if cfg.experts_held else None)
    mod, _ = reference
    eps = TOY["rms_norm_eps"]
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    # the reference norms its input itself: the program is handed that
    h = mod.rms(x, 1.0, eps)[None]
    layer = moe.DroplessExperts(toy_config(experts_held=None))
    p = init_params(layer, jax.random.PRNGKey(6), h)["params"]
    shared_w = [p[f"shared{i}"] for i in range(2)]

    def ref_part(first, count):
        """shared(h) + the routed part of experts first .. first+count."""
        block = {
            "n2": jnp.ones((64,)), "w_r": p["router"]["kernel"],
            "w_gate_up": p["w_gate_up"][first:first + count],
            "w_down": p["w_down"][first:first + count],
            "s_gate": jnp.concatenate(
                [s["gate"]["kernel"] for s in shared_w], 1),
            "s_up": jnp.concatenate([s["up"]["kernel"] for s in shared_w], 1),
            "s_down": jnp.concatenate(
                [s["down"]["kernel"] for s in shared_w], 0)}
        y, chosen = highest(mod.experts, x, block, 3, 4, 2, False, 16.0,
                            first, eps)
        return np.asarray(y - x), np.asarray(chosen)

    def program_part(held):
        params = dict(p)
        if held is not None:
            params.update(
                w_gate_up=p["w_gate_up"][held[0]:held[0] + held[1]],
                w_down=p["w_down"][held[0]:held[0] + held[1]])
        y, routing = highest(
            moe.DroplessExperts(toy_config(experts_held=held)).apply,
            {"params": params}, h)
        assert ("window_passes" in routing) == bool(window and held)
        return np.asarray(y[0]), np.asarray(routing["experts"])

    whole, chosen = ref_part(0, 16)
    y, experts = program_part(None)
    np.testing.assert_allclose(y, whole, atol=TOL)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    shared = np.asarray(sum(highest(
        lambda s: (jax.nn.silu(h[0] @ s["gate"]["kernel"]) *
                   (h[0] @ s["up"]["kernel"])) @ s["down"]["kernel"], s)
        for s in shared_w))
    from_program, from_reference = shared.copy(), shared.copy()
    for first in (0, 4, 8, 12):
        y, mine = program_part((first, 4))
        # the router is the whole layer's, whichever experts are held
        np.testing.assert_array_equal(mine, experts)
        theirs, _ = ref_part(first, 4)
        np.testing.assert_allclose(y, theirs, atol=TOL)
        # a share alone is not the whole
        assert np.abs(y - whole).max() > 1000 * TOL
        from_program += y - shared
        from_reference += theirs - shared
    np.testing.assert_allclose(from_program, whole, atol=TOL)
    np.testing.assert_allclose(from_reference, whole, atol=TOL)


@pytest.mark.parametrize("window", [None, 16], ids=["all_rows", "windows"])
def test_rows_routed_to_an_absent_expert_take_no_part(window, monkeypatch):
    """All of a token's experts elsewhere: its routed part is exactly 0,
    whatever lies in the rows behind the held groups."""
    if window:
        # the shares walk their rows in windows of 16 (ISSUE 60); the
        # layer that holds every expert never does
        monkeypatch.setattr(
            moe, "expert_window",
            lambda cfg, tokens: window if cfg.experts_held else None)
    cfg = toy_config(num_shared_experts=0, experts_held=(12, 4))
    layer = moe.DroplessExperts(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64), jnp.float32)
    params = init_params(layer, jax.random.PRNGKey(2), h)
    y, routing = layer.apply(params, h)
    experts = np.asarray(routing["experts"])
    elsewhere = (experts < 12).all(-1)
    assert elsewhere.any() and not elsewhere.all()
    assert np.abs(np.asarray(y[0])[elsewhere]).max() == 0.0
    assert np.abs(np.asarray(y[0])[~elsewhere]).min(0).max() > 0.0
    assert int(routing["counts"].sum()) == 64 * 3


# ---- the driver -------------------------------------------------------

def test_the_routers_balance_leaves_the_mean_input_unscored():
    """``balance_routers``: every router loses one direction, that of the
    mean of its layer's input over the schedule's batches, which then
    scores 0 with every expert; nothing else of the model moves."""
    model = GPTModel(toy_config())
    params = init_params(model, jax.random.PRNGKey(3),
                         jnp.ones((1, 8), jnp.int32))
    key = jax.random.PRNGKey(4)
    moved = DRIVER.balance_routers(model, params, key, TOY["vocab_size"])
    changed = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(moved)) if not np.array_equal(a, b)]
    assert changed == [f"['params']['h{i}']['mlp']['router']['kernel']"
                       for i in (1, 2)]
    directions = {}
    for layer in ("h1", "h2"):
        w_new = np.asarray(moved["params"][layer]["mlp"]["router"]["kernel"])
        w_old = np.asarray(params["params"][layer]["mlp"]["router"]["kernel"])
        u, sizes, _ = np.linalg.svd(w_old - w_new)
        assert sizes[1] < 1e-5 * sizes[0]            # one direction
        assert np.abs(u[:, 0] @ w_new).max() < 1e-5  # and none of it left
        directions[layer] = u[:, 0]
    # the first routed layer's input does not depend on any router: the
    # direction is its mean over the schedule's own batches
    mean = 0.0
    for i in range(DRIVER.BALANCE_BATCHES):
        ids = jax.random.randint(jax.random.fold_in(key, i), (1, CONTEXT),
                                 4, TOY["vocab_size"])
        _, state = model.apply(
            params, ids, mutable=["intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name == "ln2")
        mean = mean + np.asarray(
            state["intermediates"]["h1"]["ln2"]["__call__"][0]).mean((0, 1))
    cosine = directions["h1"] @ mean / np.linalg.norm(mean)
    assert abs(cosine) > 0.9999


def test_driver_runs_the_toy_cell(toy_context, checks_the_same_requests):
    """``chipbench/drivers/serve_mla.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' balance, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the check against the reference."""
    obs = DRIVER.run(toy_context("toy-deepseek-v2.longdoc", "toy-longdoc", 3.0,
                                 2, checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["compiles_in_window"] == 0
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 2
    # a CPU trace has no TPU plane
    assert obs["decode_trace"] == {} and obs["chunk_trace"] == {}
    obs.update(peaks=None, config=TOY)
    assert run.metric_reader("kv_cache_bytes_per_position")(obs) == \
        3 * (16 + 8) * 2                # bfloat16 in the driver
    local = run.metric_reader("moe_local_rows_pct")(obs)
    assert 5 < local < 60               # one share of four
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 4
    assert run.metric_reader("mla_decode_roofline_pct")(obs) is None
    assert run.metric_reader("attention_chunk_share_pct")(obs) is None
    spans = [s for s in obs["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 8)
        for s in spans)


def test_driver_holds_the_latent_cache_to_its_precision(
        toy_context, monkeypatch, checks_the_same_requests):
    """The control the cell's limits are set against, at the toy size: a
    latent cache rounded to float8 (e4m3) on its way in serves plausible
    tokens and is not correct."""
    update = gpt_model.update_latent_cache

    def rounded(kv_cache, c, k_pe):
        return update(kv_cache, jax.lax.reduce_precision(c, 4, 3),
                      jax.lax.reduce_precision(k_pe, 4, 3))

    monkeypatch.setattr(gpt_model, "update_latent_cache", rounded)
    obs = DRIVER.run(toy_context("toy-deepseek-v2.longdoc", "toy-longdoc", 3.0,
                                 0, checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and checks["checked_requests"] == 4
    assert not obs["correct"], checks


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    """Every number of the catalog row's ``config`` is in the cell's file
    under the same key, but the three keys it lists as reduced."""
    row = catalog_row("DeepSeek-V2")
    differ = {k for k, v in row["config"].items() if CELL.get(k) != v}
    assert differ == set(CELL["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differ} == {
        k: CELL["published"][k] for k in differ}
