"""A Mamba-2 mixer's states in the serving path (``GPTConfig.attention``
"ssm": a matrix a head and the convolution's last three positions a row a
layer, riding in the list of caches as ``(conv state, ssm state, index)``
beside the attention layers' caches and the expert layers' empty entries):
prompts around the chunk's and the sub-chunk's edges through the compiled
chunk step, the engine's ``_scatter_row`` and ``_decode`` over the engine's
rows; rows admitted while others decode, free rows decoded along; the
paths that refuse such a configuration by name.  (The toy cell through
``chipbench/drivers/serve_ssm.py``, sound and with the state kept in
bfloat16, is ``tests/model/test_nemotron_h.py``'s, where the harness of the
toy cells is.)  At the toy size of ``chipbench/configs/toy-nemotron.json``
(chunks of 16, sub-chunks of 8, this chip's 4 of 8 experts) on the CPU,
float32 at full matmul precision, against the plain reference
``chipbench/references/nemotron_h_decoder.py``: logits, not tokens."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import (GPTModel, init_kv_caches,
                                      kv_cache_kinds)
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params, shake
from chipbench import arithmetic_nemotron, run

TOY = run.load_json(run.HERE, "configs", "toy-nemotron.json")
DRIVER = run.load_module("drivers", "serve_ssm")
CONTEXT, CHUNK = TOY["serve"]["served_context"], TOY["serve"]["prefill_chunk"]
TOL = 2e-5      # float32 at full precision, logits of unit spread
# around the sub-chunk's edge (8), the chunk's (16) and the second chunk's
LENGTHS = [7, 8, 9, 15, 16, 17, 31, 33]
SERVED = 5


def toy_config():
    return run.load_module("drivers", "serve_mla").model_config(
        TOY, dtype=jnp.float32, seq_len=CONTEXT)


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (8, 48), the reference, its weights)."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 48), 0,
                             TOY["vocab_size"])
    params = shake(init_params(model, jax.random.PRNGKey(2), ids),
                   ("scale", "router_bias", "norm", "D", "conv_bias"))
    mod = run.load_module("references", TOY["reference"])
    ref = mod.Reference(DRIVER.reference_settings(TOY))
    return model, params, np.asarray(ids), ref, \
        mod.weights_from_program(params)


@pytest.fixture(scope="module")
def replayed(toy):
    """Eight requests, one a length of ``LENGTHS``, through the compiled
    chunk step (every one a padded last chunk but the sixteen's), the
    engine's ``_scatter_row`` into resident caches of eight rows and
    ``_decode`` over all rows at once, the served ids fed back
    (``drivers/serve_lm.py`` ``_replay``, what the cell's check runs):
    the mean absolute difference from the reference's logits at every
    served position of every request."""
    model, params, ids, ref, weights = toy
    lm = run.load_module("drivers", "serve_lm")
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    group = [{"prompt_ids": ids[r, :n].tolist(),
              "tokens": ids[r, n:n + SERVED].tolist()}
             for r, n in enumerate(LENGTHS)]
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=len(LENGTHS),
                                          chunked_admission=True)
        engine.shutdown()
        # (the whole row, one shape for the reference: what follows a
        # position changes nothing before it)
        refs = [ref.logits(weights, ids[r], rows=(n - 1, SERVED))
                for r, n in enumerate(LENGTHS)]
        return [diff for diff, _experts in lm._replay(
            gen, engine._scatter_row, len(LENGTHS), group, refs)]


@pytest.mark.parametrize("row", range(len(LENGTHS)),
                         ids=[f"prompt{n}" for n in LENGTHS])
def test_prefill_then_decode_equals_the_reference(replayed, row):
    """The prefill's last logits and four decoded positions of a prompt of
    this length, among seven other rows: the state crossed sub-chunks and
    chunks and was left by the row's last real position."""
    assert replayed[row].shape == (SERVED,)
    assert replayed[row].max() < TOL, replayed[row]


def test_an_admission_leaves_the_other_rows_states_as_they_are(toy):
    """``_scatter_row`` of one row's prefill into resident caches full of
    other rows' states: the admitted row holds the prefill's two states,
    every other row's arrays are bit for bit what they were."""
    model, params, ids, _ref, _weights = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    resident = [(jax.random.normal(jax.random.PRNGKey(i), k.shape, k.dtype),
                 jax.random.normal(jax.random.PRNGKey(99 + i), v.shape,
                                   v.dtype), jnp.full((3,), 7, jnp.int32))
                for i, (k, v, _i) in enumerate(init_kv_caches(cfg, 3))]
    before = [(np.asarray(k), np.asarray(v)) for k, v, _ in resident]
    last, row = gen._run_chunked_prefill(
        [ids[0, :21]], jnp.asarray([21], jnp.int32), 1)
    after, _logits = engine._scatter_row(
        resident, row, jnp.zeros((3, cfg.vocab_size)), last, 1)
    for kind, (k0, v0), (k, v, index), (k1, v1, _i) in zip(
            kv_cache_kinds(cfg), before, after, row):
        for was, now, new in ((k0, k, k1), (v0, v, v1)):
            assert (np.asarray(now)[[0, 2]] == was[[0, 2]]).all(), kind
            assert (np.asarray(now)[1] == np.asarray(new)[0]).all(), kind
        assert index.tolist() == [7, 21, 7]
    assert np.asarray(after[0][1])[1].any()


def worst_deficit(ref, weights, prompt, out) -> float:
    """How far under the reference's largest logit the reference holds
    the tokens served after ``prompt``, at its worst position."""
    assert (out[:len(prompt)] == prompt).all()
    n = len(out) - len(prompt)
    # (padded to one shape for the reference: what follows a position
    # changes nothing before it)
    padded = np.zeros((64,), np.int32)
    padded[:len(out)] = out
    rows = np.asarray(ref.logits(weights, padded))[
        len(prompt) - 1:len(out) - 1]
    return float((rows.max(-1) - rows[np.arange(n), out[len(prompt):]]).max())


def test_rows_admitted_into_junk_while_others_decode_serve_the_reference(
        toy):
    """Seven requests over three rows whose states start as junk (what a
    freed row decoded along leaves, made large so that a share of it would
    show): prompts of one token, of a sub-chunk's edge and of several
    chunks, admitted into rows that others freed while the rest decode.
    Every served token has the reference's largest logit at its position."""
    model, params, ids, ref, weights = toy
    prompts = [ids[i % 3, :n] for i, n in enumerate([1, 5, 8, 17, 9, 33, 23])]
    new = [9, 20, 6, 14, 8, 12, 11]
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    outs = [None] * len(prompts)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=3,
                                          chunked_admission=True)
        # no request yet: the engine's thread waits and reads nothing
        engine._caches = [
            (jnp.full_like(k, 1e3), jnp.full_like(v, 1e3), i)
            if kind == "ssm" else (k, v, i)
            for kind, (k, v, i) in zip(kv_cache_kinds(toy_config()),
                                       engine._caches)]
        try:
            threads = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, engine.submit(
                    prompts[i], GenerationConfig(max_new_tokens=new[i]))))
                for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            engine.shutdown()
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        assert worst_deficit(ref, weights, p, out) < TOL, len(p)


def test_the_engine_reports_the_states_by_their_kind(toy):
    model, params, _ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    after = tmetrics.get_registry().snapshot()
    # three mixers of 8 x 8 x 16 float32 and 3 x 128 float32 a row; one
    # attention layer of the context; the expert layers hold nothing
    assert after['alpa_serving_kv_cache_bytes{kind="ssm"}'] == \
        3 * 3 * (8 * 8 * 16 + 3 * 128) * 4 == \
        3 * arithmetic_nemotron.state_bytes_per_row(TOY, 4)
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == \
        3 * CONTEXT * 2 * 2 * 16 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="conv"}'] == 0
    obs = {"counters": ({}, after), "engine_rows": 3}
    assert run.metric_reader("ssm_state_bytes_per_row")(obs) == \
        arithmetic_nemotron.state_bytes_per_row(TOY, 4)


@pytest.mark.parametrize("what", ["pool", "speculative", "beam",
                                  "disaggregated"])
def test_what_rolls_back_by_an_index_refuses_by_name(toy, what):
    """The block pool, the speculative verify step, beam search and the
    disaggregated prefill index positions of one cache shape or roll a
    row back by its index: they refuse a configuration with a Mamba-2
    mixer, whose states no index brings back, and say why."""
    model, params, ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="Mamba-2 mixers"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "speculative":
            gen.generate_speculative(gen, ids[0, :5])
        elif what == "beam":
            gen.generate_beam(ids[0, :5], num_beams=2)
        else:
            PrefillEngine(gen)


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode)/GPTModel/h0/ssm/ssm_mixer/in_proj/dot_general",
     "ssm_mixer"),
    ("jit(decode)/GPTModel/h2/ssm/ssm_mixer/mul", "ssm_mixer"),
    ("jit(chunk_prefill)/GPTModel/h2/ssm/ssm_mixer/while/body/add",
     "ssm_mixer"),
    # a weight the compiler copies keeps its place in the arguments' tree
    ("params['params']['h0']['ssm']['out_proj']['kernel']", "ssm_mixer"),
    ("jit(decode)/GPTModel/h0/ln1/mul", "norm"),
    ("jit(decode)/GPTModel/h1/mlp/moe/shared0/fc_in/dot_general", "moe"),
])
def test_the_capture_names_the_mixer_as_a_part(op_name, part):
    from alpa_tpu.telemetry import device_time
    assert device_time.part_of(op_name) == part
    assert "ssm_mixer" in device_time.PARTS
