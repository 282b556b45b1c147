"""The engine's sampling: one device program for all rows of a tick
(``generation.sample_rows``) with each row's settings as data, whose
tokens go into the decode on the device and are read back behind its
enqueue.  Against ``_sample_logits`` row by row, against
``Generator.generate`` stream by stream, and the order of a tick's phases
by its spans."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import (GenerationConfig, Generator,
                                       _sample_logits, _warp_probs_np,
                                       sample_rows)
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.telemetry.trace import TraceRecorder

KEY = jax.random.PRNGKey(7)


def _settings(cfgs):
    return (jnp.asarray([c.do_sample for c in cfgs]),
            jnp.asarray([c.temperature for c in cfgs], jnp.float32),
            jnp.asarray([c.top_k for c in cfgs], jnp.int32))


def _logits(rows, vocab, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(rows, vocab)) * 3, dtype)


# ---- (a) sample_rows against _sample_logits ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_greedy_rows_are_the_argmax_and_leave_the_key(dtype):
    logits = _logits(4, 97, dtype)
    cfgs = [GenerationConfig()] * 4
    tokens, key = sample_rows(logits, KEY, *_settings(cfgs))
    assert tokens.shape == (4, 1) and tokens.dtype == jnp.int32
    np.testing.assert_array_equal(
        tokens[:, 0], _sample_logits(logits, KEY, cfgs[0]))
    np.testing.assert_array_equal(key, KEY)


MIXED = [GenerationConfig(),
         GenerationConfig(do_sample=True),
         GenerationConfig(do_sample=True, temperature=0.7),
         GenerationConfig(do_sample=True, top_k=5),
         GenerationConfig(do_sample=True, temperature=1.6, top_k=11),
         GenerationConfig(do_sample=True, temperature=0.0, top_k=1)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_each_row_is_sample_logits_under_its_own_settings(seed):
    """One key a tick: row r of the program is row r of ``_sample_logits``
    over the same batch with the tick's subkey and r's settings, token for
    token (``top_k`` 0 and ``temperature`` 1.0 among them: the identity)."""
    logits = _logits(len(MIXED), 97, seed=seed)
    key = jax.random.PRNGKey(seed)
    tokens, new_key = sample_rows(logits, key, *_settings(MIXED))
    want_key, sub = jax.random.split(key)
    np.testing.assert_array_equal(new_key, want_key)
    for r, cfg in enumerate(MIXED):
        assert int(tokens[r, 0]) == int(_sample_logits(logits, sub, cfg)[r])


def test_top_k_keeps_the_ties_at_the_kth_value_and_nothing_below():
    row = np.array([0.5, 3.0, 2.0, 2.0, -1.0, 2.0, 1.9, 4.0], np.float32)
    rows = 512
    logits = jnp.tile(row, (rows, 1))
    cfg = GenerationConfig(do_sample=True, top_k=3)     # 4.0, 3.0, 2.0 x3
    tokens, _ = sample_rows(logits, KEY, *_settings([cfg] * rows))
    assert set(np.asarray(tokens[:, 0]).tolist()) == {1, 2, 3, 5, 7}


@pytest.mark.parametrize("cfg", [
    GenerationConfig(do_sample=True, temperature=1.5, top_k=6),
    GenerationConfig(do_sample=True, temperature=0.6, top_k=0),
    GenerationConfig(do_sample=True, temperature=1.0, top_k=4)],
    ids=["t1.5-k6", "t0.6-k0", "t1.0-k4"])
def test_draws_follow_the_warped_probabilities(cfg):
    """Chi-square of 8192 rows of one distribution, one key: the draws are
    ``_warp_probs_np``'s (the 99.9th percentile of chi-square with 15
    degrees of freedom is 37.7)."""
    vocab, rows = 16, 8192
    row = np.asarray(_logits(1, vocab, seed=3))[0]
    tokens, _ = sample_rows(jnp.tile(row, (rows, 1)), KEY,
                            *_settings([cfg] * rows))
    counts = np.bincount(np.asarray(tokens[:, 0]), minlength=vocab)
    p = _warp_probs_np(row, cfg)
    assert counts[p == 0].sum() == 0
    live = p > 0
    chi2 = (((counts - rows * p) ** 2)[live] / (rows * p[live])).sum()
    assert chi2 < 37.7


# ---- the engine ----

CFG = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=48,
                vocab_size=64)
PROMPTS = [np.array([5, 9, 3, 7, 1, 2, 8, 4, 6, 11, 13, 2], np.int32),
           np.array([7, 7, 1], np.int32),
           np.array([2, 40, 17, 9, 33], np.int32),
           np.array([21, 4, 4, 30, 12, 8, 1], np.int32)]


def _generator(paged=False):
    model, params = init_gpt_real(CFG, 1)
    if paged:
        return Generator(model, params, CFG, prefill_chunk=8)
    return Generator(model, params, CFG, prompt_buckets=[16])


def _engine(gen, paged=False, rows=4):
    pool = KVBlockPool.for_generator(gen, max_batch=rows, block_size=8) \
        if paged else None
    return ContinuousBatchingEngine(gen, max_batch=rows, kv_pool=pool)


def _serve(engine, prompts, cfgs):
    """Every request from a thread of its own, so that they share ticks."""
    outs = [None] * len(prompts)

    def ask(i):
        try:
            outs[i] = engine.submit(prompts[i], cfgs[i])
        except Exception as e:  # pylint: disable=broad-except
            outs[i] = e

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    return outs


def _serve_together(engine, prompts, cfgs):
    """``_serve`` with every request in the queue before the engine's loop
    sees any of them: they are queued under the loop's own lock, so one
    admission gives them their rows and their first tick is shared.  (From
    threads of their own the second may be admitted only after the first
    has been served, on a loaded machine.)"""
    with engine._cv:
        items = [engine._make_item(p, cfg, None)
                 for p, cfg in zip(prompts, cfgs)]
        for item in items:
            engine._queue.append(item)
        engine._cv.notify()
    outs = []
    for item in items:
        assert item["done"].wait(120)
        outs.append(item["error"] or np.concatenate(
            [item["prompt"], np.asarray(item["tokens"], np.int32)]))
    return outs


def _sample_ticks():
    snap = tmetrics.get_registry().snapshot()
    return {mode: snap.get(
        f'alpa_serving_sample_ticks_total{{mode="{mode}"}}', 0)
        for mode in ("greedy", "sampled")}


def test_a_greedy_row_beside_sampling_rows_gets_the_greedy_tokens():
    gen = _generator()
    sampling = [GenerationConfig(max_new_tokens=24, do_sample=True,
                                 temperature=t, top_k=k)
                for t, k in ((0.7, 0), (1.3, 5), (1.0, 2))]
    greedy = GenerationConfig(max_new_tokens=12)
    engine = _engine(gen)
    before = _sample_ticks()
    try:
        # the sampling rows are resident before the greedy one arrives
        # (24 tokens each against its 12), so it shares every tick
        streams = [engine.submit_stream(p, c)
                   for p, c in zip(PROMPTS[1:], sampling)]
        firsts = [next(s) for s in streams]
        out = engine.submit(PROMPTS[0], greedy)
        rest = [list(s) for s in streams]
    finally:
        engine.shutdown()
    np.testing.assert_array_equal(
        out, gen.generate(PROMPTS[0][None], greedy)[0])
    assert all(len(r) == 23 for r in rest) and len(firsts) == 3
    after = _sample_ticks()
    assert after["sampled"] - before["sampled"] >= 12


@pytest.fixture
def recorder():
    rec = TraceRecorder()
    old = ttrace.set_recorder(rec)
    prev = ttrace.set_enabled(False)
    yield rec
    ttrace.set_enabled(prev)
    ttrace.set_recorder(old)


def _four_lengths(gen):
    """Four requests, four ``max_new_tokens``; the last ends at an
    ``eos_token_id`` that its greedy stream reaches before its ninth
    token.  Returns the settings and how many tokens that stream has."""
    plain = GenerationConfig(max_new_tokens=9)
    stream = gen.generate(PROMPTS[3][None], plain)[0][len(PROMPTS[3]):]
    cut = next(i for i in range(1, 8) if stream[i] not in stream[:i])
    return [GenerationConfig(max_new_tokens=5),
            GenerationConfig(max_new_tokens=11),
            GenerationConfig(max_new_tokens=7),
            GenerationConfig(max_new_tokens=9,
                             eos_token_id=int(stream[cut]))], cut + 1


def _traced_ticks(engine, recorder, prompts, cfgs):
    ttrace.set_enabled(True)
    outs = _serve(engine, prompts, cfgs)
    # the tick that delivered the last token closes its span a moment
    # later, on the engine's thread
    deadline = time.monotonic() + 30
    while engine._active.any() or len(engine._queue):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    time.sleep(0.05)
    ttrace.set_enabled(False)
    spans = recorder.spans()
    ticks = [s for s in spans if s["name"] == "engine.decode-tick"]

    def children(tick):
        return sorted((s for s in spans if s["name"] != tick["name"] and
                       s["track"] == tick["track"] and
                       tick["ts_us"] <= s["ts_us"] and
                       s["ts_us"] + s["dur_us"] <=
                       tick["ts_us"] + tick["dur_us"]),
                      key=lambda s: s["ts_us"])

    return outs, spans, [(t, children(t)) for t in ticks]


@pytest.mark.parametrize("paged", [False, True], ids=["unpaged", "paged"])
def test_rows_of_different_lengths_share_one_sampling_call(recorder, paged):
    """``max_new_tokens`` and ``eos_token_id`` are no sampling settings:
    four requests that differ in them cost one ``engine.sample`` and one
    ``engine.wait`` a tick, no row is sampled again, and every stream is
    ``Generator.generate``'s token for token (paged: the tick's positions
    reach the pool through ``write_tokens`` behind the read-back)."""
    gen = _generator(paged)
    cfgs, cut = _four_lengths(gen)
    engine = _engine(gen, paged)
    before = _sample_ticks()
    try:
        outs, spans, ticks = _traced_ticks(engine, recorder, PROMPTS, cfgs)
    finally:
        engine.shutdown()
    for p, cfg, out in zip(PROMPTS, cfgs, outs):
        np.testing.assert_array_equal(out, gen.generate(p[None], cfg)[0])
    assert len(outs[3]) == len(PROMPTS[3]) + cut    # ended by its eos
    assert len(ticks) >= 11
    assert not [s for s in spans if s["name"] == "engine.resample"]
    for _tick, kids in ticks:
        names = [k["name"] for k in kids]
        assert names.count("engine.sample") == 1
        assert names.count("engine.wait") == 1
        (sample,) = [k for k in kids if k["name"] == "engine.sample"]
        assert sample["args"] == {"rows": 0}
    after = _sample_ticks()
    assert after["sampled"] == before["sampled"]
    assert after["greedy"] - before["greedy"] == engine.decode_steps


def test_the_host_reads_the_tokens_behind_the_enqueue(recorder):
    """Within every tick: sample, then the decode's dispatch, and only
    then the read-back (``engine.wait``), then deliver."""
    gen = _generator()
    cfgs = [GenerationConfig(max_new_tokens=n) for n in (4, 6, 3, 5)]
    engine = _engine(gen, rows=2)           # admissions between the ticks
    try:
        _outs, _spans, ticks = _traced_ticks(engine, recorder, PROMPTS, cfgs)
    finally:
        engine.shutdown()
    assert len(ticks) >= 6
    for _tick, kids in ticks:
        phases = [k for k in kids if k["name"] in (
            "engine.sample", "engine.dispatch", "engine.wait",
            "engine.deliver")]
        assert [k["name"] for k in phases] == [
            "engine.sample", "engine.dispatch", "engine.wait",
            "engine.deliver"]
        dispatch, wait = phases[1], phases[2]
        assert dispatch["ts_us"] + dispatch["dur_us"] <= wait["ts_us"]


def test_one_sampling_program_whatever_the_settings():
    """The program the first tick compiled serves every later one: no
    setting of a request is a shape or a static argument.  (jax keeps one
    cache for all ``jit``s of a function, so other engines' programs for
    other shapes are in it too: the count must not grow.)"""
    gen = _generator()
    cfgs = [GenerationConfig(max_new_tokens=3),
            GenerationConfig(max_new_tokens=4, do_sample=True),
            GenerationConfig(max_new_tokens=2, do_sample=True,
                             temperature=0.5),
            GenerationConfig(max_new_tokens=5, do_sample=True, top_k=3),
            GenerationConfig(max_new_tokens=3, do_sample=True,
                             temperature=1.7, top_k=9, eos_token_id=1)]
    engine = _engine(gen, rows=3)       # a shape no other test here uses
    try:
        before = engine._sample_rows._cache_size()
        solo = engine.submit(PROMPTS[0], cfgs[0])
        assert engine._sample_rows._cache_size() == before + 1
        outs = _serve(engine, [PROMPTS[i % 4] for i in range(5)], cfgs)
    finally:
        engine.shutdown()
    assert not [o for o in outs if isinstance(o, Exception)]
    assert engine._sample_rows._cache_size() == before + 1
    np.testing.assert_array_equal(
        solo, gen.generate(PROMPTS[0][None], cfgs[0])[0])


def test_a_decode_that_fails_behind_its_enqueue_fails_the_resident_rows():
    """The decode takes the donated caches and then fails: both resident
    requests fail, the engine makes fresh caches, and the next request,
    greedy in a row that a sampling request held, is served as if nothing
    had happened."""
    gen = _generator()
    engine = _engine(gen, rows=2)
    decode = gen._decode
    greedy = GenerationConfig(max_new_tokens=4)
    sampling = GenerationConfig(max_new_tokens=4, do_sample=True, top_k=4)
    both_in = threading.Event()

    def failing(params, token, index, caches):
        if not engine._active.all():
            return decode(params, token, index, caches)
        gen._decode = decode
        both_in.set()
        decode(params, token, index, caches)
        for k, v, _i in caches:         # where donation is not honoured
            if not k.is_deleted():
                k.delete()
                v.delete()
        raise RuntimeError("the device lost the step")

    try:
        want = engine.submit(PROMPTS[1], greedy)
        gen._decode = failing
        outs = _serve_together(engine, PROMPTS[:2], [sampling, sampling])
        assert both_in.is_set()
        assert all(isinstance(o, RuntimeError) and
                   "lost the step" in str(o) for o in outs)
        np.testing.assert_array_equal(engine.submit(PROMPTS[1], greedy),
                                      want)
    finally:
        gen._decode = decode
        engine.shutdown()
    assert engine.step_failures == 1
