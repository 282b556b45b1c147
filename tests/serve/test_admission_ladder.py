"""The engine's dense admission (ISSUE 35): a prompt is padded to the
smallest bucket of a two-step ladder that holds it (the engine's cap and
the generator's bucket nearest a quarter of it), every program of the
ladder is compiled before the first admission, so that no admission
compiles whatever its prompt's length, and what is served is what an
engine held to its cap alone serves."""
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import (ADMISSION_STEP, GenerationConfig,
                                       Generator)
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.telemetry.trace import TraceRecorder

# a cap below the context, so that a prompt of the cap's length still has
# room for its tokens; 32 is on the generator's ladder and not the engine's
CFG = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=96,
                vocab_size=61)
BUCKETS, STEP, CAP = [16, 32, 64], 16, 64
LENGTHS = [1, STEP, STEP + 1, CAP]
GREEDY = GenerationConfig(max_new_tokens=4)
PADDED = "alpa_serving_prefill_padded_tokens_total"
FAMILY = 'alpa_serving_dense_prefills_total{bucket="%d"}'


@pytest.fixture(scope="module")
def weights():
    return init_gpt_real(CFG, 1)


def _generator(weights, buckets=BUCKETS, **kwargs):
    model, params = weights
    return Generator(model, params, CFG, prompt_buckets=buckets, **kwargs)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 60, n).astype(np.int32)


@pytest.fixture(scope="module")
def ladder_engine(weights):
    gen = _generator(weights)
    engine = ContinuousBatchingEngine(gen, max_batch=2, prompt_bucket=CAP)
    yield gen, engine
    engine.shutdown()


@pytest.fixture
def recorder():
    rec = TraceRecorder()
    old, was = ttrace.set_recorder(rec), ttrace.set_enabled(True)
    yield rec
    ttrace.set_enabled(was)
    ttrace.set_recorder(old)


@pytest.mark.parametrize("cap, buckets, ladder", [
    (CAP, BUCKETS, [STEP, CAP]),
    (2048, [32, 64, 128, 256, 512, 1024, 2048], [512, 2048]),
    # the nearest to a quarter, where the quarter itself is no bucket
    (96, [32, 64, 96], [32, 96]),
    (64, [48, 64], [48, 64]),
    # no bucket below the cap: the cap alone, as before
    (16, BUCKETS, [16]),
    (64, [64], [64]),
    # a cap that is no bucket of the generator's stays the cap
    (40, BUCKETS, [16, 40]),
])
def test_the_ladder_is_the_cap_and_the_bucket_nearest_a_quarter_of_it(
        weights, cap, buckets, ladder):
    assert ADMISSION_STEP == 0.25
    assert _generator(weights, buckets).admission_ladder(cap) == ladder


def test_the_bucket_rule_is_the_generators_over_the_ladder_it_is_given(
        weights):
    gen = _generator(weights)
    assert [gen._bucket_len(n) for n in (1, 17, 33)] == [16, 32, 64]
    assert [gen._bucket_len(n, [STEP, CAP]) for n in LENGTHS] == \
        [STEP, STEP, CAP, CAP]
    with pytest.raises(ValueError, match="largest bucket 64"):
        gen._bucket_len(CAP + 1, [STEP, CAP])


@pytest.mark.parametrize("n", LENGTHS)
def test_an_admission_pads_to_the_smallest_bucket_that_holds_its_prompt(
        ladder_engine, recorder, n):
    """Read where the benchmark reads it: the padded positions' counter,
    the admissions by program, and the prefill span's ``padded_len``."""
    _gen, engine = ladder_engine
    want = STEP if n <= STEP else CAP
    registry = tmetrics.get_registry()
    before = registry.snapshot()
    engine.submit(_prompt(n), GREEDY)
    after = registry.snapshot()

    def rise(name):
        return after.get(name, 0) - before.get(name, 0)

    assert rise(PADDED) == want
    assert rise("alpa_serving_prefill_prompt_tokens_total") == n
    assert rise(FAMILY % want) == 1
    assert rise(FAMILY % (STEP + CAP - want)) == 0
    (span,) = [s for s in recorder.spans() if s["name"] == "engine.prefill"]
    assert span["args"]["padded_len"] == want
    assert span["args"]["prompt_len"] == n
    assert span["args"]["path"] == "dense" and span["args"]["chunks"] == 1


def test_a_longer_prompt_than_the_cap_is_refused_as_before(ladder_engine):
    _gen, engine = ladder_engine
    assert engine.bucket == CAP
    with pytest.raises(ValueError, match="exceeds engine bucket 64"):
        engine.submit(_prompt(CAP + 1), GREEDY)


@pytest.mark.parametrize("lowers", [True, False])
def test_no_admission_compiles_after_the_first(weights, lowers):
    """The ladder is compiled by the engine, ahead: one trace a step and
    none after, whatever lengths come, in whatever order."""
    gen = _generator(weights)
    if not lowers:
        # as the pipelined (``parallel_method``) prefill, which is a call
        # and has no lowering apart from it: every step compiles in its run
        jitted = gen._prefill
        gen._prefill = lambda *args: jitted(*args)
        gen._parallel_method = "pipelined"
    engine = ContinuousBatchingEngine(gen, max_batch=2, prompt_bucket=CAP)
    try:
        # the first admission runs the lower step alone
        engine.submit(_prompt(3), GREEDY)
        assert gen.prefill_traces == 2          # both steps, no more
        for n in reversed(LENGTHS):
            engine.submit(_prompt(n, seed=n), GREEDY)
        assert gen.prefill_traces == 2
    finally:
        engine.shutdown()


def test_a_ladder_that_fails_to_compile_leaves_the_engine_serving(
        weights, caplog):
    """The engine's thread lives: the fault is logged, and an admission
    compiles its program itself, as every admission did before."""
    gen = _generator(weights)
    jitted, calls = gen._prefill, []

    def prefill(*args):
        calls.append(args[1].shape)
        return jitted(*args)

    def refuse(*_args):
        raise RuntimeError("no compiler today")

    prefill.lower = refuse
    gen._prefill = prefill
    engine = ContinuousBatchingEngine(gen, max_batch=2, prompt_bucket=CAP)
    try:
        prompt = _prompt(STEP + 1)
        out = engine.submit(prompt, GREEDY)
        np.testing.assert_array_equal(out[:len(prompt)], prompt)
        assert len(out) == len(prompt) + GREEDY.max_new_tokens
    finally:
        engine.shutdown()
    assert calls == [(1, CAP)]          # nothing ran ahead: the admission
    assert "compiling the admission ladder failed" in caplog.text


@pytest.mark.parametrize("kind", ["chunked_admission", "prefix"])
def test_an_engine_that_cannot_reach_the_dense_prefill_traces_none(
        weights, kind):
    gen = _generator(weights, prefill_chunk=16)
    kwargs = {"chunked_admission": True} if kind == "chunked_admission" \
        else {"prefix": gen.cache_prefix(_prompt(5, seed=9))}
    traced = gen.prefill_traces                 # the handle's chunk step
    registry = tmetrics.get_registry()
    before = registry.snapshot()
    engine = ContinuousBatchingEngine(gen, max_batch=2, prompt_bucket=CAP,
                                      **kwargs)
    try:
        assert engine._ladder == []
        for n in (3, STEP + 1):
            engine.submit(_prompt(n), GREEDY)
    finally:
        engine.shutdown()
    after = registry.snapshot()
    # the chunk step and nothing else: one program, compiled at most once
    assert gen.prefill_traces == 1 >= traced
    assert not gen._prefill._cache_size()
    assert {k: v for k, v in after.items()
            if k.startswith("alpa_serving_dense_prefills_total")} == \
        {k: v for k, v in before.items()
         if k.startswith("alpa_serving_dense_prefills_total")}


@pytest.mark.parametrize("n", LENGTHS)
def test_the_ladder_serves_what_the_cap_alone_serves(weights,
                                                     ladder_engine, n):
    """The padded tail was never read: greedy tokens of an engine held to
    its cap alone and of the ladder's are equal, and the logits the first
    token is chosen from agree to rounding."""
    capped = _generator(weights, [CAP])
    assert capped.admission_ladder(CAP) == [CAP]
    gen, engine = ladder_engine
    prompt = _prompt(n, seed=100 + n)
    alone = ContinuousBatchingEngine(capped, max_batch=2, prompt_bucket=CAP)
    try:
        np.testing.assert_array_equal(alone.submit(prompt, GREEDY),
                                      engine.submit(prompt, GREEDY))
    finally:
        alone.shutdown()
    logits_cap, caches_cap, padded_cap = capped.prefill_row(prompt, [CAP])
    logits, caches, padded = gen.prefill_row(prompt, [STEP, CAP])
    assert padded_cap == CAP and padded == (STEP if n <= STEP else CAP)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(logits_cap, np.float32),
                               atol=2e-5, rtol=0)
    # caches of the full length whatever the bucket, the prompt's
    # positions the same: a row scatters either result alike
    for (k, v, i), (kc, vc, ic) in zip(caches, caches_cap):
        assert k.shape == kc.shape == (1, CFG.seq_len) + k.shape[2:]
        assert int(i[0]) == int(ic[0]) == n
        np.testing.assert_allclose(np.asarray(k[0, :n], np.float32),
                                   np.asarray(kc[0, :n], np.float32),
                                   atol=2e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(v[0, :n], np.float32),
                                   np.asarray(vc[0, :n], np.float32),
                                   atol=2e-5, rtol=0)
