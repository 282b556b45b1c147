"""Generation by diffusion over blocks through the serving stack: the
unmasking rule, the engine's tick of one forward of a whole block a row
against ``Generator.generate_blocks`` alone and against the plain
reference's loop, requests that end inside a block, what is built on one
token a step refusing such a configuration by name, spans and counters.
"""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import (BlockDiffusion, GenerationConfig,
                                       Generator, choose_unmasked,
                                       sample_positions, unmask_quota)
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.testing import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import run  # noqa: E402

REF = run.load_module("references", "sdar_moe_decoder")
L, MASK, VOCAB = 4, 299, 300


def config(**kwargs):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, seq_len=64, norm="rmsnorm",
        positions="rotary", qk_norm="head", use_bias=False, mlp="experts",
        activation="silu", tie_embeddings=False, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True,
        moe_intermediate_size=32, fused_gate_up=True, block_length=L,
        layer_norm_eps=1e-6), **kwargs})


@pytest.fixture(scope="module")
def toy():
    cfg = config()
    model = GPTModel(cfg)
    return cfg, model, init_params(model, jax.random.PRNGKey(0),
                                   jnp.ones((1, 8), jnp.int32))


def generator(toy, steps=2, remasking="low_confidence_static"):
    cfg, model, params = toy
    return Generator(model, params, cfg, prefill_chunk=8,
                     diffusion=BlockDiffusion(MASK, steps, remasking))


def prompt_of(n, seed=0):
    return np.random.default_rng(seed + n).integers(
        4, MASK, size=n).astype(np.int32)


# ---- the rule ----------------------------------------------------------

@pytest.mark.parametrize("masked, steps, quotas", [
    (4, 1, [4]), (4, 2, [2, 2]), (4, 3, [2, 1, 1]), (4, 4, [1, 1, 1, 1]),
    (3, 2, [2, 1]), (2, 2, [1, 1]), (1, 2, [1]), (3, 4, [1, 1, 1]),
    (1, 4, [1])])
def test_quotas(masked, steps, quotas):
    """A full block of 4 under every budget, and the first blocks a
    prompt's tail leaves fewer masks in."""
    got, left = [], steps
    while masked:
        take = int(unmask_quota(jnp.int32(masked), jnp.int32(left)))
        assert take == REF.unmask_quota(masked, left)
        got.append(take)
        masked, left = masked - take, left - 1
    assert got == quotas


def rule(masked, confidence, left, remasking="low_confidence_static",
         threshold=0.9):
    got = np.asarray(choose_unmasked(
        jnp.asarray([masked], bool), jnp.asarray([confidence], jnp.float32),
        jnp.asarray([left], jnp.int32), remasking, threshold))[0]
    want = REF.choose_unmasked(masked, confidence, left, remasking,
                               threshold)
    assert (got == want).all()
    return got.tolist()


def test_the_static_rule_takes_the_most_confident():
    assert rule([1, 1, 1, 1], [.1, .4, .3, .2], 2) == [0, 1, 1, 0]
    assert rule([1, 0, 0, 1], [.1, .9, .9, .2], 1) == [1, 0, 0, 1]
    # a decided position is never taken, however confident
    assert rule([0, 1, 1, 1], [.99, .1, .3, .2], 3) == [0, 0, 1, 0]
    # ties go to the lower position
    assert rule([1, 1, 1, 1], [.5, .5, .5, .5], 2) == [1, 1, 0, 0]
    assert rule([1, 1, 1, 1], [.2, .5, .2, .5], 4) == [0, 1, 0, 0]


def test_the_dynamic_rule_takes_all_over_the_threshold():
    dyn = "low_confidence_dynamic"
    assert rule([1, 1, 1, 1], [.95, .1, .92, .99], 4, dyn) == [1, 0, 1, 1]
    # and never less than the quota
    assert rule([1, 1, 1, 1], [.1, .4, .3, .2], 2, dyn) == [0, 1, 1, 0]
    assert rule([1, 1, 0, 1], [.95, .1, .99, .2], 3, dyn) == [1, 0, 0, 0]
    assert rule([1, 1, 1, 1], [.7, .1, .3, .2], 4, dyn, .6) == [1, 0, 0, 0]


def test_rows_sample_every_position_under_their_own_settings():
    """Row 0 greedy, row 1 at temperature 0.7 over its 3 largest, row 2 at
    temperature 1.5 over everything: the greedy row takes the argmax, a
    sampled row draws inside its top-k at every position, and a
    confidence is softmax(logits / T) at what was drawn."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (3, L, 50)) * 3
    do_sample = jnp.asarray([False, True, True])
    temperature = jnp.asarray([1.0, 0.7, 1.5])
    top_k = jnp.asarray([0, 3, 0], jnp.int32)
    draws = set()
    key = jax.random.PRNGKey(2)
    for _ in range(20):
        x0, confidence, key = sample_positions(logits, key, do_sample,
                                               temperature, top_k)
        x0, confidence = np.asarray(x0), np.asarray(confidence)
        assert (x0[0] == np.asarray(logits[0]).argmax(-1)).all()
        top3 = np.argsort(np.asarray(logits[1]), -1)[:, -3:]
        assert all(x0[1, i] in top3[i] for i in range(L))
        draws.add(tuple(x0[2]))
        for r, t in enumerate((1.0, 0.7, 1.5)):
            probs = np.asarray(jax.nn.softmax(logits[r] / t, -1))
            np.testing.assert_allclose(
                confidence[r], probs[np.arange(L), x0[r]], rtol=1e-5)
    assert len(draws) > 5


def test_a_sampled_row_is_drawn_in_the_block_step(toy):
    gen = generator(toy)
    cfg = GenerationConfig(max_new_tokens=8, do_sample=True,
                           temperature=1.3, top_k=20)
    a = gen.generate_blocks([prompt_of(6)], cfg, jax.random.PRNGKey(1))[0]
    b = gen.generate_blocks([prompt_of(6)], cfg, jax.random.PRNGKey(2))[0]
    again = gen.generate_blocks([prompt_of(6)], cfg,
                                jax.random.PRNGKey(1))[0]
    assert a == again and a != b and len(a[0]) == 8


# ---- the engine --------------------------------------------------------

def serve(engine, requests):
    """``requests`` [(prompt, cfg)] sent at once; their tokens."""
    out = [None] * len(requests)

    def go(i, prompt, cfg):
        out[i] = engine.submit(prompt, cfg)[len(prompt):].tolist()

    threads = [threading.Thread(target=go, args=(i, p, c))
               for i, (p, c) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


@pytest.mark.parametrize("steps, remasking", [
    (2, "low_confidence_static"), (4, "low_confidence_dynamic")])
def test_the_engine_is_generate_alone_and_the_references_loop(
        toy, steps, remasking):
    """Eight requests on three rows, so that rows are admitted in the
    middle of other rows' blocks; prompts of every remainder by the
    block's length and one shorter than a block; outputs that are no
    multiple of it.  Each request's tokens are what ``generate_blocks``
    gives it alone, and the reference's loop gives the same, unmasked at
    the same forwards, wherever its choices were no near-ties."""
    gen = generator(toy, steps, remasking)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    try:
        sizes = [(3, 10), (8, 7), (9, 5), (14, 13), (2, 3), (16, 9),
                 (11, 10), (7, 6)]
        requests = [(prompt_of(n), GenerationConfig(max_new_tokens=m))
                    for n, m in sizes]
        served = serve(engine, requests)
        assert gen.decode_traces == 1
        reference = REF.Reference({
            "head_dim": 16, "block_length": L, "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "num_experts_per_tok": 2,
            "norm_topk_prob": True, "query_block": 16})
        weights = REF.weights_from_program(gen.params)
        compared = 0
        for (prompt, cfg), tokens in zip(requests, served):
            assert len(tokens) == cfg.max_new_tokens
            alone, forwards = gen.generate_blocks([prompt], cfg)
            assert tokens == alone[0]
            want, want_forwards, seen = reference.generate(
                weights, prompt, cfg.max_new_tokens, mask_token_id=MASK,
                denoising_steps=steps, remasking=remasking, pad_to=32)
            top = np.concatenate([np.sort(s[3], -1)[s[4]] for s in seen])
            if (top[:, -1] - top[:, -2]).min() > 1e-3:
                compared += 1
                assert tokens == want and forwards[0] == want_forwards
        assert compared >= 4
        assert gen.decode_traces == 2       # generate alone: one row
    finally:
        engine.shutdown()


def test_requests_end_inside_a_block(toy):
    """EOS at a position inside a block ends the request there, and
    ``max_new_tokens`` of 1 ends it at a block's first generated token; a
    stream closed inside a block frees its row."""
    gen = generator(toy)
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        prompt = prompt_of(8)
        free = gen.generate_blocks(
            [prompt], GenerationConfig(max_new_tokens=12))[0][0]
        # an EOS that first shows at a position inside a block
        at = next(i for i, t in enumerate(free)
                  if i % L in (1, 2) and t not in free[:i])
        cfg = GenerationConfig(max_new_tokens=12, eos_token_id=free[at])
        assert serve(engine, [(prompt, cfg)])[0] == free[:at + 1]
        assert gen.generate_blocks([prompt], cfg)[0][0] == free[:at + 1]
        assert gen.generate(prompt, cfg)[0, 8:].tolist() == free[:at + 1]
        one = GenerationConfig(max_new_tokens=1)
        assert serve(engine, [(prompt_of(9), one)])[0] == \
            gen.generate_blocks([prompt_of(9)], one)[0][0]
        # cancel: the stream is closed after its first block
        stream = engine.submit_stream(prompt,
                                      GenerationConfig(max_new_tokens=40))
        got = [next(stream) for _ in range(L)]
        stream.close()
        assert got == free[:L]
        rest = list(stream)
        assert len(got) + len(rest) < 40
        # the row is free again: the engine serves on
        assert serve(engine, [(prompt, GenerationConfig(
            max_new_tokens=5))] * 3) == [free[:5]] * 3
    finally:
        engine.shutdown()


def test_a_request_must_fit_in_whole_blocks(toy):
    gen = generator(toy)
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        # 30 + 33 = 63 fits 64 positions, but its last block ends at 64
        engine._make_item(prompt_of(30), GenerationConfig(
            max_new_tokens=33), None)
        with pytest.raises(ValueError, match="in blocks of 4"):
            engine._make_item(prompt_of(31), GenerationConfig(
                max_new_tokens=34), None)
        with pytest.raises(ValueError, match="in blocks of 4"):
            gen.generate(prompt_of(31), GenerationConfig(max_new_tokens=34))
        with pytest.raises(ValueError, match=f"mask token id {MASK}"):
            engine.submit(np.array([5, MASK, 7], np.int32))
        with pytest.raises(ValueError, match=f"mask token id {MASK}"):
            gen.generate(np.array([5, MASK, 7], np.int32))
    finally:
        engine.shutdown()


# ---- what is built on one token a step ---------------------------------

def _kv_pool(gen):
    from alpa_tpu.serve.kv_cache import KVBlockPool
    return KVBlockPool.for_generator(gen)


def _engine(**kwargs):
    def build(gen):
        ContinuousBatchingEngine(gen, max_batch=2, chunked_admission=True,
                                 **kwargs)
    return build


def _disagg(gen):
    from alpa_tpu.serve.disagg import PrefillEngine
    PrefillEngine(gen)


def _prefilled(gen):
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        engine._make_item(prompt_of(8), None, None, prefilled=(None, None))
    finally:
        engine.shutdown()


@pytest.mark.parametrize("what, build", [
    ("KVBlockPool", _kv_pool),
    ("kv_pool", _engine(kv_pool=object())),
    ("a static prefix", _engine(prefix=object())),
    ("a static prefix", lambda gen: gen.cache_prefix(prompt_of(8))),
    ("generate_speculative",
     lambda gen: gen.generate_speculative(gen, prompt_of(8))),
    ("generate_beam", lambda gen: gen.generate_beam(prompt_of(8))),
    ("disagg", _disagg), ("disaggregated", _prefilled)],
    ids=["pool", "engine-pool", "engine-prefix", "cache-prefix",
         "speculative", "beam", "disagg", "prefilled"])
def test_one_token_a_step_refuses_the_configuration_by_name(toy, what,
                                                            build):
    with pytest.raises(ValueError) as refused:
        build(generator(toy))
    message = str(refused.value)
    assert what in message and "one token a row a step" in message
    assert "diffusion over blocks of 4" in message


def test_the_engine_admits_in_chunks(toy):
    with pytest.raises(ValueError, match="chunked_admission=True"):
        ContinuousBatchingEngine(generator(toy), max_batch=2)


# ---- spans and counters ------------------------------------------------

def test_spans_and_counters_over_a_short_run(toy):
    gen = generator(toy)
    registry = tmetrics.get_registry()
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    before = registry.snapshot()
    ttrace.get_recorder().clear()
    ttrace.set_enabled(True)
    try:
        # 8 and 9 prompt tokens: one first block whole, one with a tail
        served = serve(engine, [
            (prompt_of(8), GenerationConfig(max_new_tokens=8)),
            (prompt_of(9), GenerationConfig(max_new_tokens=7))])
    finally:
        ttrace.set_enabled(False)
        engine.shutdown()
        engine._thread.join(timeout=30)
    after = registry.snapshot()

    def rose(name):
        return after[name] - before.get(name, 0.0)

    assert [len(t) for t in served] == [8, 7]
    denoise = rose('alpa_serving_block_forwards_total{phase="denoise"}')
    commit = rose('alpa_serving_block_forwards_total{phase="commit"}')
    unmasked = rose("alpa_serving_block_tokens_unmasked_total")
    # row one: two blocks of 4 in 2 forwards each and the first's commit
    # (it ends at the tick that reads its second block whole); row two:
    # 3 + 4 positions
    assert (denoise, commit, unmasked) == (8, 2, 15)
    assert rose("alpa_serving_blocks_committed_total") == commit
    assert rose("alpa_serving_tokens_total") == 15
    steps = rose("alpa_serving_decode_steps_total")
    assert steps >= 5
    # every step routes rows x L tokens through both layers
    assert rose("alpa_moe_routed_rows_total") == (steps - 1) * 2 * 2 * L * 2
    assert 0 < rose("alpa_moe_experts_touched_total") <= (steps - 1) * 2 * 8
    assert rose("alpa_serving_decode_positions_total") > 0
    spans = ttrace.get_recorder().spans()
    ticks = [s for s in spans if s["name"] == "engine.decode-tick"]
    assert len(ticks) == steps
    assert sum(s["args"]["unmasked"] for s in ticks) == unmasked
    assert sum(s["args"]["denoising"] for s in ticks) == denoise
    assert sum(s["args"]["committing"] for s in ticks) == commit
    assert all(s["args"]["active"] <= 2 for s in ticks)
    names = {s["name"] for s in spans}
    assert {"engine.dispatch", "engine.wait", "engine.deliver",
            "engine.prefill", "engine.admit"} <= names
    assert "engine.sample" not in names
    delivered = sum(s["args"]["tokens"] for s in spans
                    if s["name"] == "engine.deliver")
    assert delivered == 15


def test_the_unmasking_rule_is_a_part_of_the_compiled_step(toy):
    """``unmask`` is a part ``Capture.device_time()`` knows, and the
    compiled block step's instructions lie under it."""
    from alpa_tpu.telemetry import device_time
    assert device_time.part_of("jit(block_step)/unmask/reduce_max") == \
        "unmask"
    assert "unmask" in device_time.PARTS
    gen = generator(toy)
    gen.generate_blocks([prompt_of(8)], GenerationConfig(max_new_tokens=4))
    assert gen._block_step.jitted.__name__ == "block_step"
    parts = device_time.registered_parts("jit_block_step")
    assert parts and "unmask" in set(
        part for by_name in parts for part, _how in by_name.values())
