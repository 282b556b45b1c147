"""A multi-token-prediction module drafting inside the engine's tick
(``ContinuousBatchingEngine._draft_tick``, ``Generator._verify_draft``) at
a toy size on the CPU, float32: the engine emits exactly what
``Generator.generate`` emits one token a step, on random weights (where
nearly every draft is rejected) and on weights built so that every draft is
right (where a tick yields two tokens); a row that samples is offered no
draft and emits what the one-token tick emits; what the tick cannot roll
back is refused by name."""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.telemetry import metrics as tmetrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import run  # noqa: E402

TOY = run.load_json(run.HERE, "configs", "toy-glm5.json")
MLA = run.load_module("drivers", "serve_mla")
CONTEXT, VOCAB = 96, TOY["vocab_size"]
DRAFTS = 'alpa_serving_mtp_drafts_total{result="%s"}'


def toy_config(**kwargs):
    return MLA.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


def a_function_of_the_last_token(params):
    """Weights under which every draft is right: attention's ``out`` and
    every MLP's and expert's down projection zero, in the model and the
    module, so that a layer adds nothing and the model is a function of
    its last token; ``eh_proj`` the identity on the embedding's half, so
    that the module is the same function of ITS last token, the next
    one."""
    def built(path, x):
        names = [p.key for p in path]
        if names[-2:] == ["out", "kernel"] or names[-2:] == \
                ["down", "kernel"] or names[-1] == "w_down":
            return jnp.zeros_like(x)
        if names[-2:] == ["eh_proj", "kernel"]:
            hidden = x.shape[1]
            return jnp.concatenate([jnp.eye(hidden, dtype=x.dtype),
                                    jnp.zeros((hidden, hidden), x.dtype)])
        return x
    return jax.tree_util.tree_map_with_path(built, params)


@pytest.fixture(scope="module")
def generators():
    """One generator a set of weights."""
    cfg = toy_config()
    model = GPTModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.ones((1, 8), jnp.int32))
    return {"random": Generator(model, params, cfg, prefill_chunk=8),
            "every_draft_right": Generator(
                model, a_function_of_the_last_token(params), cfg,
                prefill_chunk=8)}


@pytest.fixture(params=["random", "every_draft_right"])
def served(request, generators):
    return request.param, generators[request.param]


def moved(before, name):
    return tmetrics.get_registry().snapshot().get(name, 0.0) - \
        before.get(name, 0.0)


def test_the_engine_emits_what_generate_emits(served):
    """Ragged prompts, more requests than rows (so that rows are admitted
    into mid-stream), ``max_new_tokens`` odd, even and 1."""
    weights, gen = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, VOCAB, size=n) for n in (5, 19, 30, 12)]
    asked = [7, 10, 1, 6]
    want = [np.asarray(gen.generate(
        [p], GenerationConfig(max_new_tokens=n))[0])[len(p):]
        for p, n in zip(prompts, asked)]
    before = tmetrics.get_registry().snapshot()
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    try:
        got = [None] * len(prompts)

        def submit(i):
            got[i] = engine.submit(
                prompts[i], GenerationConfig(max_new_tokens=asked[i]))

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for p, w, g in zip(prompts, want, got):
            assert np.array_equal(g, np.concatenate([p, w]))
    finally:
        engine.shutdown()
    tokens = sum(asked)
    assert moved(before, "alpa_serving_tokens_total") == tokens
    accepted = moved(before, DRAFTS % "accepted")
    # a forward a row a tick; what a draft adds is a token without one (a
    # request whose last token is a kept draft learns of it a tick late,
    # in a tick whose forward it throws away)
    forwards = moved(before, "alpa_serving_verify_forwards_total")
    assert tokens - accepted <= forwards <= tokens - accepted + len(asked)
    if weights == "every_draft_right":
        # every offered draft is kept: a request of n tokens takes its
        # first tick (no draft yet) and then two tokens a tick
        assert moved(before, DRAFTS % "rejected") == 0
        assert accepted == sum((n - 1) // 2 for n in asked)
    else:
        assert accepted <= 2


def test_two_tokens_a_tick_where_every_draft_is_right(generators):
    gen = generators["every_draft_right"]
    prompt, n = np.arange(4, 15), 21
    want = np.asarray(gen.generate(
        [prompt], GenerationConfig(max_new_tokens=n))[0])
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        assert np.array_equal(engine.submit(
            prompt, GenerationConfig(max_new_tokens=n)), want)
        # one tick without a draft, ten of two tokens, and the one the
        # engine had enqueued before it read the last token
        assert engine.decode_steps <= 1 + n // 2 + 1
    finally:
        engine.shutdown()


def test_a_row_that_samples_is_offered_no_draft(generators):
    """It emits what the one-token tick emits under the same key: the same
    model without its module, served by ``_step``."""
    gen = generators["random"]
    cfg = toy_config(num_nextn_predict_layers=0)
    plain = Generator(
        GPTModel(cfg), {"params": {k: v for k, v in
                                   gen.params["params"].items()
                                   if k != "mtp"}}, cfg, prefill_chunk=8)
    assert plain._verify_draft is None
    prompt = np.arange(7, 24)
    sampling = GenerationConfig(max_new_tokens=9, do_sample=True,
                                temperature=0.8, top_k=12)
    out = []
    before = tmetrics.get_registry().snapshot()
    for g in (gen, plain):
        engine = ContinuousBatchingEngine(g, max_batch=2,
                                          chunked_admission=True)
        try:
            out.append(engine.submit(prompt, sampling))
        finally:
            engine.shutdown()
    assert np.array_equal(*out) and len(out[0]) == len(prompt) + 9
    assert moved(before, DRAFTS % "accepted") == 0 and \
        moved(before, DRAFTS % "rejected") == 0
    assert moved(before, DRAFTS % "not_offered") >= 8


def test_what_the_tick_builds_on_is_asked_for_by_name(generators):
    gen = generators["random"]
    with pytest.raises(ValueError, match="chunked_admission"):
        ContinuousBatchingEngine(gen, max_batch=2)
    with pytest.raises(ValueError, match="static prefix"):
        gen.cache_prefix(np.arange(4, 12))
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    try:
        with pytest.raises(ValueError, match="prefilled"):
            engine.submit_prefilled(np.arange(4, 12), None, [], None)
    finally:
        engine.shutdown()


@pytest.mark.parametrize("field,value,named", [
    ("attention", ("full", "sliding"), "ring of the sliding window"),
    ("attention", ("full", "conv"), "short convolution's state"),
    ("attention", ("full", "ssm"), "Mamba-2 mixer's states")])
def test_a_generator_refuses_to_draft_over_what_no_index_rolls_back(
        field, value, named):
    cfg = GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                    vocab_size=64, seq_len=32, sliding_window=4,
                    conv_taps=3, positions="rotary",
                    num_nextn_predict_layers=1, **{field: value})
    with pytest.raises(ValueError, match=named):
        Generator(GPTModel(cfg), {}, cfg, prefill_chunk=8)
