"""Which attention core a cached call takes, and what the engine counts of
it (ISSUE 40).

``gpt_model.cached_attention`` chooses between the kernel over key blocks
(``ops/cached_attention.py``: a row's cache is read as far as the row has
written) and ``reference_attention`` (every position the cache can hold)
from the call's shapes alone; the gauge ``alpa_cached_attention_core``
says at trace time which one a program's layers took.  The engine counts
beside the positions its active rows HELD
(``alpa_serving_decode_positions_total``) those their core READ
(``alpa_serving_decode_positions_read_total``).  Nothing here runs on a
chip; the kernel itself is held to the reference in
``tests/ops/test_attention.py`` and compiled for the chip in
``tests/ops/test_tpu_compile.py`` and ``test_decode_in_place.py``.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import (GPTConfig, GPTModel, cached_key_block,
                                      init_gpt_real, init_kv_caches)
from alpa_tpu.serve import engine as engine_module
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.telemetry import metrics as tmetrics

ROWS = 4
# one layer at widths the kernel takes: 16 heads over 4 key/value heads
WIDE = dict(hidden_size=2048, num_layers=1, num_heads=16, num_kv_heads=4,
            seq_len=2048, vocab_size=256, dtype=jnp.bfloat16)
# keys wider than the values: the caches hold the heads folded
FOLDED = dict(hidden_size=2048, num_layers=1, num_heads=64, num_kv_heads=4,
              head_dim=192, v_head_dim=128, seq_len=2048, vocab_size=256,
              dtype=jnp.bfloat16)
NARROW = dict(hidden_size=1024, num_layers=1, num_heads=16, seq_len=2048,
              vocab_size=256, dtype=jnp.bfloat16)


def _series(name):
    """{series: value} of one metric's series so far."""
    return {series: value for series, value in
            tmetrics.get_registry().snapshot().items()
            if series.split("{")[0] == name}


def _core(core, heads, head_dim, queries):
    return (f'alpa_cached_attention_core{{core="{core}",heads="{heads}",'
            f'head_dim="{head_dim}",queries="{queries}"}}')


# (id, configuration, new positions, per-row index, the series that move)
CALLS = [
    ("a-decode-tick", WIDE, 1, True,
     {_core("key_blocks", 4, 128, 1): 1}),
    ("a-block-step", dict(WIDE, block_length=4), 4, True,
     {_core("key_blocks", 4, 128, 4): 1}),
    ("a-narrow-head-decode-tick", NARROW, 1, True,
     {_core("key_blocks", 16, 64, 1): 1}),
    ("a-prefill-chunk", WIDE, 1024, False,
     {_core("query_key_blocks", 4, 128, 1024): 1}),
    ("a-chunk-at-per-row-offsets", WIDE, 1024, True,
     {_core("query_key_blocks", 4, 128, 1024): 1}),
    ("a-chunk-under-a-block-causal-mask", dict(WIDE, block_length=4), 1024,
     False, {_core("query_key_blocks", 4, 128, 1024): 1}),
    ("a-chunk-of-folded-caches", FOLDED, 1024, False,
     {_core("query_key_blocks", 4, 192, 1024): 1}),
    ("a-decode-tick-of-folded-caches", FOLDED, 1, True,
     {_core("key_blocks", 4, 192, 1): 1}),
    ("a-narrow-head-chunk", NARROW, 1024, False,
     {_core("reference", 16, 64, 1024): 1}),
    ("a-chunk-with-a-sink", dict(WIDE, sink_kinds=("full",)), 1024, False,
     {_core("reference", 4, 128, 1024): 1}),
    ("a-folded-chunk-with-a-sink", dict(FOLDED, sink_kinds=("full",)), 1024,
     False, {_core("key_block_walk", 4, 192, 1024): 1}),
    ("a-chunk-in-no-whole-query-blocks", WIDE, 1500, False,
     {_core("reference", 4, 128, 1500): 1}),
    ("a-scalar-index-generate-step", WIDE, 1, False,
     {_core("reference", 4, 128, 1): 1}),
    ("a-ring-layer", dict(WIDE, attention="sliding", sliding_window=512,
                          positions="rotary"), 1, True, {}),
    ("a-cache-in-no-whole-key-blocks", dict(WIDE, seq_len=1536), 1, True,
     {_core("reference", 4, 128, 1): 1}),
]


@pytest.mark.parametrize("config,s,per_row,moves",
                         [c[1:] for c in CALLS], ids=[c[0] for c in CALLS])
def test_a_traced_call_says_which_core_it_took(config, s, per_row, moves):
    """Tracing a cached call of a one-layer model (nothing runs) moves
    exactly one series of ``alpa_cached_attention_core``, by one a full
    layer: ``key_blocks`` for per-row offsets, a few new positions and
    shapes the kernel takes (either view of the cache);
    ``query_key_blocks`` for a chunk's many new positions at any offset
    in heads of whole lanes, per head or folded; ``reference`` (folded
    caches: ``key_block_walk``) for everything else, a sink, heads of 64
    and a ragged chunk among it; a ring layer never reaches the
    choice."""
    cfg = GPTConfig(**config)
    model = GPTModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    index = jax.ShapeDtypeStruct((ROWS,) if per_row else (), jnp.int32)
    ids = jax.ShapeDtypeStruct((ROWS, s), jnp.int32)

    def call(params, ids, index):
        caches = [(k, v, index) for k, v, _ in init_kv_caches(cfg, ROWS)]
        positions = jnp.broadcast_to(jnp.arange(s)[None], ids.shape) + (
            index[:, None] if per_row else index)
        return model.apply(params, ids, positions, caches)

    before = _series("alpa_cached_attention_core")
    jax.eval_shape(call, params, ids, index)
    after = _series("alpa_cached_attention_core")
    assert {series: after[series] - before.get(series, 0)
            for series in after
            if after[series] != before.get(series, 0)} == moves


@pytest.mark.parametrize("config,queries,positions", [
    (WIDE, 1, 1024), (dict(WIDE, block_length=4), 4, 1024),
    (NARROW, 1, 512), (WIDE, 1024, 0), (dict(WIDE, seq_len=1536), 1, 0),
    (dict(WIDE, attention="sliding", sliding_window=512), 1, 0)],
    ids=["wide", "block-step", "narrow", "chunk", "no-whole-blocks", "ring"])
def test_the_key_block_the_engine_counts_by(config, queries, positions):
    """``cached_key_block``: the positions of a key block where a
    configuration's tick takes the kernel, 0 where it takes the
    reference: the same ``fits`` the traced call goes by."""
    assert cached_key_block(GPTConfig(**config), queries) == positions


# ---- the engine's count of what its core read --------------------------

TINY_SEQ, TINY_BLOCK_K = 512, 128
PROMPTS = [np.array([5, 9, 3, 7, 1, 2, 8, 4, 6, 11, 13, 2], np.int32),
           np.array([7, 7, 1], np.int32),
           np.array([2, 40, 17, 9, 33], np.int32)]
NEW_TOKENS = [6, 9, 4]


@pytest.mark.parametrize("core", ["reference", "key_blocks"])
def test_an_engines_window_counts_what_its_core_read(monkeypatch, core):
    """Over a window of an engine's ticks
    ``alpa_serving_decode_positions_read_total`` rises by at least what
    ``alpa_serving_decode_positions_total`` does (a core reads what its
    rows hold, in whole key blocks).  Where the ticks' program takes the
    reference core it is the served context a row a tick; where it takes
    the kernel's (a program lowered for a TPU: here the engine is told
    so, the count is the host's own bookkeeping), every row's positions
    rounded up to whole key blocks, under the served context."""
    from alpa_tpu.ops import cached_attention as ca
    cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=16,
                    seq_len=TINY_SEQ, vocab_size=64)
    monkeypatch.setattr(ca, "BLOCK_ELEMENTS",
                        TINY_BLOCK_K * cfg.kv_heads * cfg.head_size)
    monkeypatch.setattr(engine_module, "_lowered_for_tpu",
                        lambda: core == "key_blocks")
    model, params = init_gpt_real(cfg, 1)
    engine = ContinuousBatchingEngine(Generator(model, params, cfg),
                                      max_batch=ROWS)
    names = ("alpa_serving_decode_positions_total",
             "alpa_serving_decode_positions_read_total",
             "alpa_serving_decode_steps_total")
    before = {name: sum(_series(name).values()) for name in names}

    def ask(i):
        engine.submit(PROMPTS[i],
                      GenerationConfig(max_new_tokens=NEW_TOKENS[i]))

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(PROMPTS))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        engine.shutdown()
    held, read, steps = (sum(_series(name).values()) - before[name]
                         for name in names)
    # every request's every tick: one row-tick a token delivered
    row_ticks = sum(NEW_TOKENS)
    assert 0 < held <= read
    assert steps >= max(NEW_TOKENS)
    if core == "reference":
        assert read == row_ticks * TINY_SEQ
    else:
        # no row here holds more than one key block
        assert read == row_ticks * TINY_BLOCK_K < row_ticks * TINY_SEQ


# ---- a chunk from a cached prefix through the chunk's core (ISSUE 56) ----

def _toy_mimo():
    """``tests/model/test_mimo_v2_flash.py``'s toy at head widths the
    chunk's kernel takes: keys of 192 channels, values of 128."""
    from chipbench import run
    toy = run.load_json(run.HERE, "configs", "toy-mimo.json")
    wide = dict(toy, head_dim=192, swa_head_dim=192, v_head_dim=128,
                swa_v_head_dim=128)
    return run.load_module("drivers", "serve_mla").model_config(
        wide, dtype=jnp.float32, seq_len=128), 5e-5


def _toy_trinity():
    """``tests/model/test_trinity.py``'s toy at heads of 128 channels."""
    from chipbench import run
    from alpa_tpu.model.gpt_model import config_from_hf
    toy = run.load_json(run.HERE, "configs", "toy-trinity.json")
    return config_from_hf(dict(toy, head_dim=128), dtype=jnp.float32,
                          seq_len=128), 2e-5


@pytest.mark.parametrize("toy,full_layers,heads,head_dim", [
    (_toy_mimo, 2, 2, 192), (_toy_trinity, 1, 2, 128)],
    ids=["mimo", "trinity"])
def test_a_chunk_from_a_cached_prefix_gives_the_unchunked_logits(
        monkeypatch, toy, full_layers, heads, head_dim):
    """A prompt prefilled in chunks of 32 from a cached prefix of 21
    positions (``cache_prefix``: the suffix's chunks start at a scalar
    offset that is no multiple of anything) gives the logits of the same
    prompt through the model without a cache, at the tolerance the
    model's own test file holds it to; and every full layer of the chunk
    step said it took the chunk's core.  On a CPU that core is its twin
    (``reference_attention`` or the walk over key blocks): this guards
    the choice and what surrounds it, ``tests/ops/test_attention.py`` the
    kernel."""
    from alpa_tpu.ops import cached_attention as ca
    from alpa_tpu.testing import highest, init_params
    monkeypatch.setattr(ca, "CHUNK_BLOCK_K", 64)
    cfg, tol = toy()
    model = GPTModel(cfg)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (85,), 4,
                                        cfg.vocab_size))
    params = init_params(model, jax.random.PRNGKey(0), ids[None, :8])
    want, _ = highest(model.apply, params, ids[None])
    gen = Generator(model, params, cfg, prefill_chunk=32)
    before = _series("alpa_cached_attention_core")
    handle = highest(gen.cache_prefix, ids[:21])
    last, _ = highest(
        gen._run_chunked_prefill, [ids[21:]],
        jnp.asarray([len(ids)], jnp.int32), 1, caches=handle.caches,
        start=handle.length, init_last=handle.last_logits)
    np.testing.assert_allclose(last[0], want[0, -1], atol=tol)
    np.testing.assert_allclose(handle.last_logits[0], want[0, 20], atol=tol)
    after = _series("alpa_cached_attention_core")
    # one program traced, for the prefix's chunks and the suffix's alike
    assert {series: after[series] - before.get(series, 0)
            for series in after
            if after[series] != before.get(series, 0)} == {
                _core("query_key_blocks", heads, head_dim, 32): full_layers}
