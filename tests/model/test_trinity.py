"""The Trinity (``model_type`` afmoe) kinds of the one decoder definition
(window and full attention layers with caches of their own, grouped-query
heads, head-wise q/k norm, a gated attention output, four norms a block, a
scaled embedding, a leading dense layer, sigmoid-routed experts with a
selection bias beside a shared expert) against the plain reference
``chipbench/references/afmoe_decoder.py`` at a toy size on the CPU: hidden
64, 4 query heads over 2 key/value heads, window 8, 8 experts top-2 with a
shared one, two sliding layers to one full after a leading dense layer,
seeded weights with a non-zero bias.  Float32 at full matmul precision, so
that what is compared is the mathematics: prefill and then decoding
through the ring and full caches against the reference's full forward
pass, logits and not tokens.  The benchmark's cell compares the bfloat16
program with the same reference on the chip."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import moe
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                      init_kv_caches, kv_cache_shapes,
                                      uniform_kv_caches)
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.testing import highest, init_params
from chipbench import run

TOY = run.load_json(run.HERE, "configs", "toy-trinity.json")
DRIVER = run.load_module("drivers", "serve_lm")
WINDOW, CONTEXT, S = TOY["sliding_window"], 64, 48
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config(**kwargs):
    return config_from_hf(TOY, dtype=jnp.float32, seq_len=CONTEXT, **kwargs)


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


def test_config_from_hf_reads_the_afmoe_keys():
    cfg = toy_config()
    assert cfg.mlp == ("gated", "experts", "experts", "experts")
    assert cfg.attention == ("sliding", "sliding", "sliding", "full")
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_size) == (4, 2, 32)
    assert (cfg.mlp_width, cfg.expert_width) == (96, 32)
    assert cfg.seq_len == CONTEXT and cfg.sliding_window == WINDOW
    assert cfg.router_score == "sigmoid" and cfg.router_bias
    assert cfg.norm_topk_prob and cfg.route_scale == 2.826
    assert not uniform_kv_caches(cfg)
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf({**TOY, "layer_types": TOY["layer_types"][:2]})


def test_caches_have_the_shape_of_their_layer():
    """A window layer holds 8 positions, the full layer the served
    context, both with the 2 key/value heads."""
    cfg = toy_config()
    assert kv_cache_shapes(cfg, 5) == 3 * [(5, WINDOW, 2, 32)] + \
        [(5, CONTEXT, 2, 32)]
    for (k, v, index), shape in zip(init_kv_caches(cfg, 5),
                                    kv_cache_shapes(cfg, 5)):
        assert k.shape == v.shape == shape and index.shape == ()
    # the families that know no kinds: one shape, as before
    gpt = dataclasses.replace(cfg, attention="full", num_kv_heads=None,
                              head_dim=None)
    assert set(kv_cache_shapes(gpt, 1)) == {(1, CONTEXT, 4, 16)}


def test_forward_pass_equals_the_reference(toy, wanted):
    model, params, ids = toy
    logits, routing = highest(model.apply, params, ids)
    np.testing.assert_allclose(logits, wanted, atol=TOL)
    assert routing["experts"].shape == (3, 3 * S, 2)


@pytest.mark.parametrize("variant", [
    dict(sliding_window=WINDOW + 1), dict(sliding_window=WINDOW - 1),
    dict(rope_on_full_attention=True), dict(attn_gate=False),
    dict(num_shared_experts=0), dict(router_bias=False),
    dict(post_norms=False), dict(scale_embedding=False),
    dict(qk_norm=False), dict(route_scale=1.0)],
    ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()))
def test_a_wrong_wiring_fails(toy, wanted, variant):
    """Each piece of the wiring alone: a window of one position more or
    fewer, rotary positions given to the full layer, the gate, the shared
    expert or the selection bias left out (and the rest) moves the logits
    by thousands of the tolerance."""
    _model, params, ids = toy
    wrong = GPTModel(toy_config(**variant))
    logits, _ = highest(wrong.apply, params, ids)
    assert np.abs(np.asarray(logits) - wanted).max() > 1000 * TOL


def test_selection_uses_the_bias_and_the_weights_do_not():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 8)).astype(np.float32)
    bias = (0.3 * rng.normal(size=8)).astype(np.float32)
    weights, experts, scores = moe.topk_routing(
        jnp.asarray(logits), 2, True, "sigmoid", jnp.asarray(bias), 2.826)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    chosen = np.argsort(-(s + bias), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    # the bias moved some choices ...
    assert (np.sort(chosen, -1) !=
            np.sort(np.argsort(-s, axis=-1)[:, :2], -1)).any()
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.826,
        rtol=1e-5)
    # ... and is in no weight
    with_bias = np.take_along_axis(s + bias, np.asarray(experts), -1)
    with_bias = with_bias / with_bias.sum(-1, keepdims=True) * 2.826
    assert np.abs(np.asarray(weights) - with_bias).max() > 0.01
    np.testing.assert_allclose(scores, s, rtol=1e-5)


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
        # one row's two distinct experts in each of the three routed layers
        experts = np.sort(np.asarray(routing["experts"]), -1)
        assert experts.shape == (3, 1, 2) and \
            (experts[..., 0] < experts[..., 1]).all()
    return worst


@pytest.mark.parametrize("prompt_len,chunk", [
    (13, 4), (5, 4), (29, 8), (3, 16), (37, 12), (8, 8)])
def test_chunked_prefill_then_decode_equals_the_reference(
        toy, wanted, prompt_len, chunk):
    """Prompts that are no multiple of the chunk (whose padding must not
    reach a ring), chunks shorter and longer than the window, contexts six
    windows long: the prefill's last logits and every decode step's."""
    model, params, ids = toy
    row = np.asarray(ids[0])
    gen = Generator(model, params, toy_config(), prefill_chunk=chunk)
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_chunked_prefill(
            [row[:prompt_len]], jnp.asarray([prompt_len]), 1)
        np.testing.assert_allclose(last[0], wanted[0, prompt_len - 1],
                                   atol=TOL)
        assert [k.shape[1] for k, _v, _i in caches] == 3 * [WINDOW] + \
            [CONTEXT]
        assert _decode_all(gen, params, row, prompt_len, caches,
                           wanted[0]) < TOL


def test_bucketed_prefill_then_decode_equals_the_reference(toy, wanted):
    """The one dense prefill, right-padded to its bucket: rows of mixed
    lengths in one batch, none of whose padding reaches a ring."""
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
    along, prompts under a chunk and several windows long): every served
    token has the reference's largest logit at its position."""
    mod, ref = reference
    model, params, ids = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=4)
    prompts = [np.asarray(ids[i % 3, :n])
               for i, n in enumerate([3, 13, 30, 9, 21])]
    new = [12, 20, 16, 30, 7]
    outs = [None] * len(prompts)
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
    weights = mod.weights_from_program(params)
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        logits = np.asarray(ref.logits(weights, out))
        served = out[len(p):]
        rows = logits[len(p) - 1:len(out) - 1]
        deficit = rows.max(-1) - rows[np.arange(n), served]
        assert deficit.max() < TOL, (len(p), deficit.max())
    with pytest.raises(ValueError, match="exceeding seq_len"):
        engine._make_item(np.zeros(CONTEXT + 1, np.int32), None, None)


def test_engine_counts_the_experts_its_decodes_touched(toy):
    from alpa_tpu.telemetry import metrics as tmetrics
    model, params, ids = toy
    registry = tmetrics.get_registry()
    gen = Generator(model, params, toy_config(), prefill_chunk=4)
    before = registry.snapshot()
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        engine.submit(np.asarray(ids[0, :11]),
                      GenerationConfig(max_new_tokens=9))
    finally:
        engine.shutdown()
    after = registry.snapshot()
    touched = after["alpa_moe_experts_touched_total"] - \
        before.get("alpa_moe_experts_touched_total", 0)
    steps = after["alpa_serving_decode_steps_total"] - \
        before.get("alpa_serving_decode_steps_total", 0)
    # two rows (one of them free, decoded along) choose 2 of 8 experts in
    # each of 3 layers; the last tick's count is read by no later tick
    assert 3 * 2 * (steps - 1) <= touched <= 3 * 4 * steps
    position = 2 * 2 * 32 * 4        # K and V, 2 heads of 32, float32
    assert after['alpa_serving_kv_cache_bytes{kind="window"}'] == \
        3 * 2 * WINDOW * position
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == \
        2 * CONTEXT * position


@pytest.mark.parametrize("what", ["pool", "disaggregated", "speculative",
                                  "beam"])
def test_one_cache_shape_for_all_layers_is_asked_for(toy, what):
    """The block pool, the disaggregated prefill, the speculative verify
    step and beam search index one cache shape for all layers: they refuse
    a configuration whose layers differ, and say why."""
    model, params, ids = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=4)
    with pytest.raises(ValueError, match="one cache shape for all layers"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "disaggregated":
            PrefillEngine(gen)
        elif what == "speculative":
            gen.generate_speculative(gen, np.asarray(ids[0, :5]))
        else:
            gen.generate_beam(np.asarray(ids[0, :5]), num_beams=2)


def test_parameters_are_stored_in_the_stated_dtype():
    cfg = config_from_hf(TOY, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                         seq_len=CONTEXT)
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        want = jnp.float32 if path[-1].key == "router_bias" else jnp.bfloat16
        assert leaf.dtype == want, path


def test_driver_runs_the_toy_cell(toy_context):
    """``chipbench/drivers/serve_lm.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights,
    controller, warm-up, a closed-loop window over HTTP, the traced
    seconds, the check against the reference."""
    obs = DRIVER.run(toy_context("toy-trinity.mixed", "toy-mixed", 3.0, 2))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["compiles_in_window"] == 0
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 3
    assert obs["decode_trace"] == {}        # a CPU trace has no TPU plane
    obs.update(peaks=None, config=TOY)
    per_tick = run.metric_reader("experts_touched_per_tick")(obs)
    assert 2 <= per_tick <= 6           # 3 rows choose 2 of 8 experts
    assert run.metric_reader("prefill_useful_pct")(obs) > 50
    spans = [s for s in obs["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 4)
        for s in spans)


def test_driver_holds_the_decode_program_to_the_reference(toy_context,
                                                          monkeypatch):
    """``correct`` reads the logits of the compiled decode the window
    ran, not those of a program of the check's own: a decode (and only the
    decode) whose logits are all too large by one serves the tokens it
    served (the largest stays the largest) and is not correct."""
    from alpa_tpu.serve import generation
    donating = generation._jit_donating_kv

    def shifted(step):
        def decode(params, token, index, caches):
            logits, caches, routing = step(params, token, index, caches)
            return logits + 1, caches, routing
        return donating(decode)

    monkeypatch.setattr(generation, "_jit_donating_kv", shifted)
    obs = DRIVER.run(toy_context("toy-trinity.mixed", "toy-mixed", 3.0, 0))
    checks = obs["checks"]
    assert obs["failed"] == 0 and checks["checked_requests"] == 4
    assert checks["worst_logit_deficit"] <= TOY["logit_margin"], checks
    assert checks["mean_logit_diff"] > 0.8 and checks["over_margin"] > 0
    assert not obs["correct"], checks
