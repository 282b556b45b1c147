"""LongCat-Flash on the normal serving path, at the toy size of
``tests/model/test_longcat_flash.py`` on the CPU, float32 at full matmul
precision: ``run_controller`` -> ``register_model`` -> the controller's own
``ContinuousBatchingEngine`` with chunked admission, over HTTP, against the
plain reference ``chipbench/references/longcat_flash_decoder.py`` (logits,
not tokens); the counters the engine feeds from the decode's routing; the
kinds of the eight-for-four cache entries; what refuses a latent cache; and
the parts a capture reads off the compiled decode."""
import json
import os
import sys
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
from alpa_tpu.serve import run_controller
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import device_time
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import run  # noqa: E402

TOY = run.load_json(run.HERE, "configs", "toy-longcat.json")
DRIVER = run.load_module("drivers", "serve_scmoe")
MLA = run.load_module("drivers", "serve_mla")
CONTEXT, CHUNK = 96, 8
TOL = 5e-5      # as tests/model/test_longcat_flash.py


def toy_config():
    return MLA.model_config(TOY, dtype=jnp.float32, seq_len=CONTEXT)


@pytest.fixture(scope="module")
def toy():
    """(generator, ids (3, 48), the reference, its weights)."""
    cfg = toy_config()
    model = GPTModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, 48), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.02 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), x.shape)
        if path[-1].key == "router_bias" else x, params)
    mod = run.load_module("references", TOY["reference"])
    return (Generator(model, params, cfg, prefill_chunk=CHUNK),
            np.asarray(ids), mod.Reference(DRIVER.reference_settings(TOY)),
            mod.weights_from_program(params))


def worst_deficit(ref, weights, prompt, out) -> float:
    """How far the served tokens' logits lie under the reference's largest
    at their positions."""
    logits = np.asarray(ref.logits(weights, out))
    n = len(out) - len(prompt)
    rows = logits[len(prompt) - 1:len(out) - 1]
    return float((rows.max(-1) - rows[np.arange(n), out[len(prompt):]]).max())


def test_the_controller_serves_it_in_chunks_and_counts_its_picks(toy):
    """Five requests over three rows through ``run_controller`` and HTTP
    (rows admitted while others decode, prompts under a chunk and several
    chunks long): every served token has the reference's largest logit,
    every admission ran in chunks, and the routing counters tell the three
    kinds of pick apart."""
    gen, ids, ref, weights = toy
    registry = tmetrics.get_registry()
    prompts = [ids[i % 3, :n].tolist()
               for i, n in enumerate([3, 13, 30, 9, 21])]
    new = [12, 20, 16, 30, 7]
    outs = [None] * len(prompts)
    server = run_controller(port=0)
    try:
        with jax.default_matmul_precision("highest"):
            server.controller.register_model(
                "toy-longcat", gen, engine_rows=3, chunked_admission=True)
            before = registry.snapshot()

            def ask(i):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{server.port}/completions",
                    data=json.dumps({
                        "model": "toy-longcat", "prompt_ids": prompts[i],
                        "max_new_tokens": new[i], "stream": True}).encode(),
                    headers={"Content-Type": "application/json"})
                tokens = []
                with urllib.request.urlopen(req) as r:
                    for raw in r:
                        line = raw.decode().strip()
                        if line.startswith("data: ") and \
                                "token" in json.loads(line[6:]):
                            tokens.append(json.loads(line[6:])["token"])
                outs[i] = prompts[i] + tokens

            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            engine = server.controller._pick_replica("toy-longcat").engine
            assert isinstance(engine, ContinuousBatchingEngine)
            assert engine._chunked and engine.B == 3
            after = registry.snapshot()
    finally:
        server.shutdown()
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        assert worst_deficit(ref, weights, p, np.asarray(out)) < TOL

    def rise(name):
        return after[name] - before.get(name, 0)

    # four cache entries for two published layers: (16 + 8) float32 each
    assert after['alpa_serving_kv_cache_bytes{kind="latent"}'] == \
        3 * CONTEXT * 4 * 24 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == 0
    # 3 rows x 3 picks in each of 2 routed layers a tick whose routing a
    # later tick read back: every pick, the identity ones among them
    steps = rise("alpa_serving_decode_steps_total")
    routed = rise("alpa_moe_routed_rows_total")
    assert routed in (18 * (steps - 1), 18 * steps)
    zero, local = rise("alpa_moe_zero_picks_total"), \
        rise("alpa_moe_local_rows_total")
    assert 0 < zero < routed and 0 < local < routed - zero
    # of the held experts only (4 a layer), never an identity expert
    assert rise("alpa_moe_experts_touched_total") <= 2 * 4 * steps


def test_identity_picks_are_counted_apart_where_all_experts_are_held():
    """No share (``experts_held`` None): identity picks are neither local
    rows nor experts touched."""
    cfg = MLA.model_config(TOY, dtype=jnp.float32, seq_len=CONTEXT,
                           experts_held=None)
    registry = tmetrics.get_registry()
    gen = Generator.__new__(Generator)
    gen.config, gen._window_passes = cfg, ()
    engine = ContinuousBatchingEngine.__new__(ContinuousBatchingEngine)
    engine.gen = gen
    before = registry.snapshot()
    layer = np.asarray([[0, 15, 16], [23, 17, 3], [3, 0, 22]])
    engine._count_routing({"experts": np.stack([layer, layer])})
    after = registry.snapshot()

    def rise(name):
        return after[name] - before.get(name, 0)

    assert rise("alpa_moe_routed_rows_total") == 18
    assert rise("alpa_moe_zero_picks_total") == 8
    assert rise("alpa_moe_local_rows_total") == 0
    assert rise("alpa_moe_experts_touched_total") == 2 * 3   # 0, 3, 15


@pytest.mark.parametrize("what", ["pool", "speculative", "beam"])
def test_its_latent_caches_are_refused_by_name(toy, what):
    gen, ids, _, _ = toy
    with pytest.raises(ValueError, match="hold a latent cache"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "speculative":
            gen.generate_speculative(gen, ids[0, :5])
        else:
            gen.generate_beam(ids[0, :5], num_beams=2)


def test_the_compiled_decode_names_the_parts_of_a_double_layer(toy):
    """Both dense MLPs under ``mlp``, both cores under ``attention``, the
    shortcut branch under ``moe`` with its kernels under
    ``moe.grouped_matmul``, nothing of the model outside a part."""
    gen, _, _, _ = toy
    caches = [(k, v, jnp.zeros((3,), jnp.int32))
              for k, v, _ in init_kv_caches(gen.config, 3)]
    hlo = gen._decode.jitted.lower(
        gen.params, jnp.zeros((3, 1), jnp.int32), jnp.zeros((3,), jnp.int32),
        [(k, v) for k, v, _ in caches],
        [i for _, _, i in caches]).compile().as_text()
    parts = device_time.instruction_parts(hlo)
    seen = {}
    for name, part in parts.items():
        part = part[0] if isinstance(part, tuple) else part
        seen.setdefault(part, []).append(name)
    for part in ("attention", "mlp", "moe", "projection", "head", "norm"):
        assert seen.get(part), (part, sorted(seen))
    assert device_time.part_of(
        "jit(decode)/GPTModel/h0/moe/moe/router/dot_general") == "moe"
    assert device_time.part_of(
        "jit(decode)/GPTModel/h0/moe/moe/grouped_matmul/gmm") == \
        "moe.grouped_matmul"
    assert device_time.part_of(
        "jit(decode)/GPTModel/h1/mlp/down/dot_general") == "mlp"
    assert device_time.part_of(
        "jit(decode)/GPTModel/h1/attn/attention/cache_write/"
        "dynamic_update_slice") == "attention.cache_write"
    assert device_time.part_of(
        "params['params']['h0']['moe']['w_gate_up']") == "moe"
