"""What an admission hands the device (ISSUE 33): a number of programs
that does not grow with the model's depth, its fresh one-row caches coming
from ``generation.fresh_kv_caches`` (one program, or none where the
prefill makes its zeros itself) and never from ``init_kv_caches`` called
eagerly, three small programs a layer; and nothing of one admission left
for the next, in the rows or on the device."""
import gc
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import pjit
from jax._src.interpreters import pxla

from alpa_tpu.model.gpt_model import (GPTConfig, GPTModel, config_from_hf,
                                      init_gpt_real, uniform_kv_caches)
from alpa_tpu.serve import engine as engine_module
from alpa_tpu.serve import generation
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import (BlockDiffusion, GenerationConfig,
                                       Generator)
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.testing import init_params

# the toy configurations of the benchmark's chunked cells (data alone)
TOYS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chipbench", "configs")

BUCKET, CHUNK = 32, 16
PATHS = {"dense": {}, "chunked": {"chunked_admission": True}}


def _engine(layers, path, vocab=67):
    # a vocabulary no other test file uses: ``fresh_kv_caches`` is one
    # jit a process, keyed by the configuration
    cfg = GPTConfig(hidden_size=32, num_layers=layers, num_heads=4,
                    seq_len=64, vocab_size=vocab)
    model, params = init_gpt_real(cfg, 1)
    gen = Generator(model, params, cfg, prompt_buckets=[BUCKET],
                    prefill_chunk=CHUNK)
    return gen, ContinuousBatchingEngine(gen, max_batch=2,
                                         prompt_bucket=BUCKET,
                                         **PATHS[path])


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 60, n).astype(np.int32)


# ---- the programs of one admission ----

@pytest.fixture
def dispatched(monkeypatch):
    """Every program the process runs from here on, by name and thread,
    eager primitives (``jit(broadcast_in_dim)``) and jitted calls alike:
    jax's fast path, which would run a program it has seen without coming
    back to Python, is off for programs first called under this fixture,
    and the Python path is counted where it executes."""
    ran = []
    monkeypatch.setattr(pjit, "_get_fastpath_data", lambda *a, **k: None)
    execute = pxla.ExecuteReplicated.__call__

    def counted(self, *args):
        ran.append((threading.get_ident(), self.name))
        return execute(self, *args)

    monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", counted)
    return ran


def _programs_of_one_admission(layers, path, ran, monkeypatch):
    """Names of the programs the engine's thread ran between taking a row
    for a request and giving it, after a warm-up admission."""
    del ran[:]              # a thread's ident may be an ended thread's
    _, engine = _engine(layers, path, vocab=71)
    me = engine._thread.ident
    taken, give = engine._row_taken, engine._give_row

    def eager(*_a, **_k):
        raise AssertionError("init_kv_caches called by an admission")

    def row_taken(rec, item):
        ran.append((me, "ROW TAKEN"))
        return taken(rec, item)

    def give_row(r, item):
        ran.append((me, "ROW GIVEN"))
        return give(r, item)

    cfg = GenerationConfig(max_new_tokens=3)
    try:
        engine.submit(_prompt(20, 0), cfg)              # compiles
        with monkeypatch.context() as patch:
            # the zeros are compiled in: from here on nothing may build
            # caches array by array
            patch.setattr(generation, "init_kv_caches", eager)
            patch.setattr(engine_module, "init_kv_caches", eager)
            patch.setattr(engine, "_row_taken", row_taken)
            patch.setattr(engine, "_give_row", give_row)
            out = engine.submit(_prompt(20, 1), cfg)
        assert len(out) == 23
    finally:
        engine.shutdown()
    mine = [name for ident, name in ran if ident == me]
    lo, hi = mine.index("ROW TAKEN"), mine.index("ROW GIVEN")
    assert mine.count("ROW TAKEN") == 1 and lo < hi
    return mine[lo + 1:hi]


@pytest.mark.parametrize("path", ["dense", "chunked"])
def test_an_admission_dispatches_the_same_programs_at_any_depth(
        path, dispatched, monkeypatch):
    shallow = _programs_of_one_admission(2, path, dispatched, monkeypatch)
    deep = _programs_of_one_admission(6, path, dispatched, monkeypatch)
    assert shallow == deep
    if path == "dense":
        # the prefill builds its own zeros: no program in front of it
        assert shallow == ["jit(prefill)", "jit(scatter_row)"]
    else:
        # one program for all layers' fresh caches, the zeroed row of last
        # logits, the two chunks of a 20-token prompt, the scatter
        assert shallow[0] == "jit(fresh_kv_caches)"
        assert shallow[-3:] == ["jit(chunk_prefill)", "jit(chunk_prefill)",
                                "jit(scatter_row)"]
        assert len(shallow) <= 6


# ---- nothing of one admission is left for the next ----

def _stream_pair(engine, prompts, cfg):
    """Both prompts queued before the engine can take either (the lock is
    re-entrant); their streamed tokens."""
    with engine._cv:
        streams = [engine.submit_stream(p, cfg) for p in prompts]
    return [list(s) for s in streams]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_short_prompt_after_a_long_one_reads_nothing_of_it(path):
    gen, engine = _engine(2, path)
    cfg = GenerationConfig(max_new_tokens=6)
    long_a, long_b = _prompt(29, 2), _prompt(27, 3)
    short_a, short_b = _prompt(3, 4), _prompt(5, 5)
    try:
        alone = {p.tobytes(): list(gen.generate([p], cfg)[0][len(p):])
                 for p in (long_a, long_b, short_a, short_b)}
        # rows 0 and 1 hold the long prompts; then a short prompt goes
        # into each: the row the first long one had, and another
        for pair in ((long_a, long_b), (short_a, short_b)):
            got = _stream_pair(engine, pair, cfg)
            assert got == [alone[p.tobytes()] for p in pair]

        def live():
            # with the lock held the engine's thread is between two turns
            # of its loop: nothing of a tick or an admission is in flight
            gc.collect()
            with engine._cv:
                assert not engine._active.any()
                return len(jax.live_arrays())

        first = live()
        for _ in range(25):                       # 50 admissions
            got = _stream_pair(engine, (short_a, long_b), cfg)
            assert got == [alone[short_a.tobytes()],
                           alone[long_b.tobytes()]]
        assert engine.admissions == 54
        assert live() == first
    finally:
        engine.shutdown()


# ---- a chunk's head sees the one position a row it keeps (PR 48) ----

TOY_CHUNK, TOY_CONTEXT = 8, 64
# the six configurations the benchmark admits in chunks, at their toy
# widths
KINDS = {"trinity": "toy-trinity.json", "deepseek-v2": "toy-deepseek-v2.json",
         "sdar": "toy-sdar.json", "lfm2": "toy-lfm2.json",
         "longcat": "toy-longcat.json", "dots3": "toy-dots3.json"}


def _toy_generator(kind):
    """A float32 generator of the kind's toy configuration, in chunks of 8
    over a context of 64."""
    with open(os.path.join(TOYS, KINDS[kind])) as f:
        toy = json.load(f)
    blocks = {"block_length": toy["serve"]["block_length"]} \
        if "block_length" in toy["serve"] else {}
    cfg = config_from_hf(toy, dtype=jnp.float32, param_dtype=jnp.float32,
                         seq_len=TOY_CONTEXT, **blocks)
    model = GPTModel(cfg)
    params = init_params(model, jax.random.PRNGKey(3),
                         jnp.ones((1, 8), jnp.int32))
    diffusion = BlockDiffusion(toy["serve"]["mask_token_id"]) \
        if blocks else None
    return Generator(model, params, cfg, prefill_chunk=TOY_CHUNK,
                     diffusion=diffusion)


def _with_the_parents_chunk_step(gen):
    """From here on every chunk of ``gen`` runs the chunk step as it was
    before PR 48: the head over all the chunk's positions, one row of it
    read (or none)."""
    model, rings = gen.model, not uniform_kv_caches(gen.config)

    @jax.jit
    def step(params, ids_chunk, lengths, caches, last):
        b, c = ids_chunk.shape
        start = caches[0][2]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
        logits, caches = model.apply(
            params, ids_chunk, pos, caches,
            **({"cache_lengths": lengths} if rings else {}))
        off = lengths - 1 - start
        hit = (off >= 0) & (off < c)
        sel = logits[jnp.arange(b), jnp.clip(off, 0, c - 1)]
        return jnp.where(hit[:, None], sel, last), caches, None

    gen._chunk_prefill = step


@pytest.fixture
def recorder():
    rec = ttrace.TraceRecorder()
    old, was = ttrace.set_recorder(rec), ttrace.set_enabled(True)
    yield rec
    ttrace.set_enabled(was)
    ttrace.set_recorder(old)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_chunks_head_gives_the_parents_logits_and_tokens(kind, recorder):
    """A prompt of two and a half chunks beside one that ends inside the
    second chunk (while the first goes on) and one that ends inside the
    first: the kept rows' logits and every cache are the parent's to the
    bit in float32, the head having run over one position a row in each
    chunk; and the engine, admitting the same prompts a row at a time,
    gives the parent's tokens and says in its spans what its chunks' heads
    ran over."""
    gen = _toy_generator(kind)
    prompts = [_prompt(n, seed) for seed, n in enumerate((20, 11, 5))]
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    cfg = GenerationConfig(max_new_tokens=6)

    def served():
        last, caches = gen._run_chunked_prefill(prompts, lengths, 3)
        engine = ContinuousBatchingEngine(gen, max_batch=2,
                                          chunked_admission=True)
        try:
            tokens = [engine.submit(p, cfg) for p in prompts]
        finally:
            engine.shutdown()
        return last, jax.tree_util.tree_leaves(caches), tokens

    last, caches, tokens = served()
    spans = [s["args"] for s in recorder.spans()
             if s["name"] == "engine.prefill"]
    # the same generator, its chunks through the parent's step
    _with_the_parents_chunk_step(gen)
    want, want_caches, want_tokens = served()

    assert last.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(last), np.asarray(want))
    # every row's logits are its own: no two rows kept the same position
    assert len({np.asarray(row).tobytes() for row in last}) == 3
    for mine, theirs in zip(caches, want_caches):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    for mine, theirs in zip(tokens, want_tokens):
        np.testing.assert_array_equal(mine, theirs)
    # an engine's chunks: 3, 2 and 1, the head over one position in each
    # (SDAR prefills a prompt's whole blocks of 4: 20, 8 and 4)
    assert [(s["chunks"], s["head_rows"]) for s in spans] == [
        (3, 3), (1, 1) if kind == "sdar" else (2, 2), (1, 1)]
