"""A short convolution's state in the serving path (``GPTConfig.attention``
"conv": the last two positions of a product a row a layer, riding in the
list of caches as ``(state, empty, index)``): through
``ContinuousBatchingEngine``'s admissions, chunked and dense, rows admitted
while others decode, free rows decoded along, and the paths that refuse
such a configuration by name.  At the toy size of ``tests/model/
test_lfm2.py`` on the CPU, float32 at full matmul precision, against the
plain reference ``chipbench/references/lfm2_moe_decoder.py``: logits, not
tokens."""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTModel, config_from_hf
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import run  # noqa: E402

TOY = run.load_json(run.HERE, "configs", "toy-lfm2.json")
DRIVER = run.load_module("drivers", "serve_hybrid")
CONTEXT, CHUNK = 64, 4
TOL = 2e-5      # float32 at full precision, logits of unit spread


def toy_config():
    return config_from_hf(TOY, dtype=jnp.float32, seq_len=CONTEXT)


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (3, 48), the reference's module, the
    reference, its weights)."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, 48), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), x.shape)
        if path[-1].key == "router_bias" else x, params)
    mod = run.load_module("references", TOY["reference"])
    ref = mod.Reference(DRIVER.reference_settings(TOY))
    return model, params, np.asarray(ids), ref, \
        mod.weights_from_program(params)


def worst_deficit(ref, weights, prompt, out) -> float:
    """How far under the reference's largest logit the reference holds
    the tokens served after ``prompt``, at its worst position."""
    assert (out[:len(prompt)] == prompt).all()
    n = len(out) - len(prompt)
    rows = np.asarray(ref.logits(weights, out))[len(prompt) - 1:len(out) - 1]
    return float((rows.max(-1) - rows[np.arange(n), out[len(prompt):]]).max())


def serve(engine, prompts, new):
    """Every prompt through ``engine`` at once; the rows as they came."""
    outs = [None] * len(prompts)

    def ask(i):
        outs[i] = engine.submit(prompts[i],
                                GenerationConfig(max_new_tokens=new[i]))

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


PROMPTS, NEW = [1, 2, 3, 5, 9, 14, 23], [9, 20, 6, 14, 8, 25, 11]


@pytest.mark.parametrize("chunked", [True, False], ids=["chunked", "dense"])
def test_rows_admitted_while_others_decode_serve_the_reference(toy, chunked):
    """Seven requests over three rows: prompts of one token, of under a
    chunk, of a chunk's edge and of several chunks, admitted into rows
    that others freed while the rest decode.  Every served token has the
    reference's largest logit at its position, under the chunked admission
    (a padded last chunk) and under the dense one (a padded bucket): so
    the two agree, and a request among others serves what it serves
    alone."""
    model, params, ids, ref, weights = toy
    prompts = [ids[i % 3, :n] for i, n in enumerate(PROMPTS)]
    gen = Generator(model, params, toy_config(), prompt_buckets=[8, 32],
                    prefill_chunk=CHUNK if chunked else None)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(
            gen, max_batch=3, chunked_admission=chunked, prompt_bucket=32)
        try:
            outs = serve(engine, prompts, NEW)
        finally:
            engine.shutdown()
        alone = gen.generate([prompts[5]],
                             GenerationConfig(max_new_tokens=NEW[5]))
    for p, n, out in zip(prompts, NEW, outs):
        assert len(out) == len(p) + n
        assert worst_deficit(ref, weights, p, out) < TOL, len(p)
    assert worst_deficit(ref, weights, prompts[5], np.asarray(alone[0])) < TOL


def test_a_free_rows_junk_state_does_not_reach_its_next_request(toy):
    """Every row's state starts as junk (what a freed row decoded along
    leaves, made large here so that a share of it would show), and a row
    that a short request freed is decoded along beside a long one before
    the next request takes it: an admission overwrites the whole row."""
    model, params, ids, ref, weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=2,
                                          chunked_admission=True)
        # no request yet: the engine's thread waits and reads nothing
        engine._caches = [
            (jnp.full_like(k, 1e3) if kind == "conv" else k, v, i)
            for kind, (k, v, i) in zip(toy_config().attention,
                                       engine._caches)]
        try:
            long = [None]
            beside = threading.Thread(target=lambda: long.__setitem__(
                0, engine.submit(ids[0, :5],
                                 GenerationConfig(max_new_tokens=50))))
            beside.start()
            short = engine.submit(ids[1, :7],
                                  GenerationConfig(max_new_tokens=3))
            later = engine.submit(ids[2, :10],
                                  GenerationConfig(max_new_tokens=12))
            beside.join()
        finally:
            engine.shutdown()
    for prompt, out in ((ids[0, :5], long[0]), (ids[1, :7], short),
                        (ids[2, :10], later)):
        assert worst_deficit(ref, weights, prompt, out) < TOL, len(prompt)


def test_the_engine_reports_the_state_by_its_kind(toy):
    model, params, _ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    after = tmetrics.get_registry().snapshot()
    # four conv layers of two positions of 64 float32 a row; one attention
    # layer of K and V of 2 heads of 16 over the context
    assert after['alpa_serving_kv_cache_bytes{kind="conv"}'] == \
        4 * 3 * 2 * 64 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == \
        3 * CONTEXT * 2 * 2 * 16 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="window"}'] == 0


@pytest.mark.parametrize("what", ["pool", "speculative", "beam",
                                  "disaggregated"])
def test_what_rolls_back_by_an_index_refuses_by_name(toy, what):
    """The block pool, the speculative verify step, beam search and the
    disaggregated prefill index positions of one cache shape or roll a
    row back by its index: they refuse a configuration with a
    short-convolution layer, and say why."""
    model, params, ids, _ref, _weights = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="short-convolution layers"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "speculative":
            gen.generate_speculative(gen, ids[0, :5])
        elif what == "beam":
            gen.generate_beam(ids[0, :5], num_beams=2)
        else:
            PrefillEngine(gen)


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode)/GPTModel/h1/conv/short_conv/in_proj/dot_general",
     "short_conv"),
    ("jit(decode)/GPTModel/h1/conv/short_conv/mul", "short_conv"),
    ("jit(chunk_prefill)/GPTModel/h3/conv/short_conv/gather", "short_conv"),
    # a weight the compiler copies keeps its place in the arguments' tree
    ("params['params']['h1']['conv']['out_proj']['kernel']", "short_conv"),
    ("jit(decode)/GPTModel/h1/ln1/mul", "norm"),
    ("jit(decode)/GPTModel/h4/attn/attention/cache_write/"
     "dynamic_update_slice", "attention.cache_write"),
])
def test_the_capture_names_the_mixer_as_a_part(op_name, part):
    from alpa_tpu.telemetry import device_time
    assert device_time.part_of(op_name) == part
    assert "short_conv" in device_time.PARTS


def test_the_compiled_decode_carries_the_scope(toy):
    """The decode's HLO text says which instructions were traced under
    ``short_conv`` (what ``Capture.device_time()`` reads): both products
    of every conv layer among them, and none of the attention layer's."""
    from alpa_tpu.model.gpt_model import CONV_SCOPE, init_kv_caches
    from alpa_tpu.telemetry import device_time
    model, params, _ids, _ref, _weights = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    caches = init_kv_caches(cfg, 2)
    hlo = gen._decode.jitted.lower(
        params, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
        [(k, v) for k, v, _ in caches],
        [jnp.zeros((2,), jnp.int32) for _ in caches]).compile().as_text()
    assert CONV_SCOPE == "short_conv"
    parts = device_time.instruction_parts(hlo)
    conv = [name for name, (part, _how) in parts.items()
            if part == "short_conv"]
    assert len(conv) >= 4 * 2
    for layer in range(4):
        assert f"h{layer}/conv/{CONV_SCOPE}/in_proj" in hlo
        assert f"h{layer}/conv/{CONV_SCOPE}/out_proj" in hlo
    assert f"h4/conv/{CONV_SCOPE}" not in hlo
