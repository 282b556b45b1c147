"""The MiMo-V2-Flash (``model_type`` mimo_v2_flash) kinds of the one decoder
definition (window layers whose softmax has a learned sink beside full
layers, each kind with key/value heads and a rotary base of its own; keys
wider than values; rotary positions on a head's leading channels; a scale on
the values; a sigmoid router with a choice bias over a share of the experts)
against the plain reference ``chipbench/references/mimo_v2_flash_decoder.py``
at a toy size on the CPU where every mechanism is live: hidden 64, seven
layers F W W W W W F, a window (8) shorter than the chunk (16), 8 query
heads of 24 channels (8 of them rotated) over 2 (full) and 4 (window)
key/value heads with values of 16, 16 experts of which 4 are held, 3 picks a
token, seeded weights.  Float32 at full matmul precision, so that what is
compared is the mathematics: the training call, prefill in chunks and then
decoding through the four cache shapes, and the engine's chunked admission,
against the reference's full forward pass, logits and not tokens.  The
benchmark's cell compares the bfloat16 program with the same reference on
the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (GPTModel, cached_key_block,
                                      config_from_hf, kv_cache_kinds,
                                      kv_cache_shapes, reference_attention,
                                      require_uniform_kv_caches,
                                      uniform_kv_caches)
from alpa_tpu.ops import cached_attention as kernels
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.testing import highest, init_params, shake
from chipbench import arithmetic_mimo, controls_mimo, run, traffic

TOY = run.load_json(run.HERE, "configs", "toy-mimo.json")
CELL = run.load_json(run.HERE, "configs", "mimo-v2-flash-1chip.json")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
DRIVER = run.load_module("drivers", "serve_mimo")
MLA = run.load_module("drivers", "serve_mla")
REF = run.load_module("references", "mimo_v2_flash_decoder")
CONTEXT, S = 128, 48
TOL = 5e-5


def toy_config(**kwargs):
    return MLA.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


def made(cfg):
    """Norm weights and the routers' biases shaken (the sinks are drawn at
    the scale of a score as they are)."""
    model = GPTModel(cfg)
    return model, shake(init_params(model, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)),
                        ("scale", "router_bias"))


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    return (cfg,) + made(cfg)


@pytest.fixture(scope="module")
def wanted(toy):
    """The reference's logits and picks of one sequence."""
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (S,), 4,
                                        TOY["vocab_size"]))
    logits, picks = REF.Reference(DRIVER.reference_settings(TOY)) \
        .logits_and_experts(REF.weights_from_program(toy[2]), ids, (0, S))
    return ids, np.asarray(logits), np.asarray(picks)


# ---- the configuration ------------------------------------------------

def test_config_from_hf_reads_the_catalog_rows_config(catalog_row):
    cfg = config_from_hf(catalog_row("MiMo-V2-Flash")["config"])
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads) == (4096, 48, 64)
    assert (cfg.kv_heads_of("full"), cfg.kv_heads_of("sliding")) == (4, 8)
    assert (cfg.head_size, cfg.value_size, cfg.rotary_dim) == (192, 128, 64)
    assert (cfg.rope_theta_of("full"), cfg.rope_theta_of("sliding")) == \
        (5_000_000.0, 10_000.0)
    assert cfg.sliding_window == 128 and cfg.value_scale == 0.707
    assert cfg.sink_kinds == ("sliding",) and cfg.unlike_kinds
    assert cfg.attention.count("full") == 9 and \
        cfg.attention.count("sliding") == 39
    assert cfg.attention[:7] == ("full",) + ("sliding",) * 4 + \
        ("full", "sliding")
    assert cfg.mlp == ("gated",) + ("experts",) * 47
    assert (cfg.mlp_width, cfg.expert_width, cfg.num_experts,
            cfg.num_experts_per_tok) == (16384, 2048, 256, 8)
    assert cfg.router_score == "sigmoid" and cfg.router_bias and \
        cfg.norm_topk_prob and cfg.route_scale == 1.0
    assert not cfg.tie_embeddings and not cfg.use_bias and \
        cfg.num_shared_experts == 0 and cfg.qk_norm is False
    assert cfg.layer_norm_eps == 1e-5 and cfg.seq_len == 262144


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    row = catalog_row("MiMo-V2-Flash")
    published = row["config"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mimo-v2-flash-1chip")
    assert entry["source"] == row["source_url"]
    assert CELL["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    for key, value in published.items():
        if key in CELL["reduced"]:
            assert CELL["published"][key] == value != CELL[key], key
        else:
            assert CELL[key] == value, key
    assert CELL["chips_sharing_a_layer"] * CELL["n_routed_experts"] == 256
    assert CELL["vocab_size"] * 8 == published["vocab_size"]
    # the leading dense layer and one whole period: five window, one full
    assert CELL["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0] == \
        published["hybrid_layer_pattern"][5:12]
    assert CELL["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]


def test_the_cells_file_is_the_share_the_issue_counts():
    """ISSUE 51's arithmetic, from the shapes of the program's own
    parameters: 3,430 M parameters, and the caches' bytes."""
    cfg = MLA.model_config(CELL, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16, seq_len=32768)
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    small = 15 * 4096 + 5 * 64 + 6 * 256        # norms, sinks, biases
    assert count == arithmetic_mimo.model_parameters(CELL) + small == \
        3_429_892_096 + small
    assert kv_cache_shapes(cfg, 32)[0] == ((32, 32768, 768),
                                           (32, 32768, 512))
    assert kv_cache_shapes(cfg, 32)[1] == ((32, 128, 8, 192),
                                           (32, 128, 8, 128))
    assert arithmetic_mimo.full_cache_bytes_per_position(CELL, 2) == 5120
    assert arithmetic_mimo.ring_bytes_per_row(CELL, 2) == 655_360
    assert cached_key_block(cfg, 1) == 512
    assert len({shape for entry in kv_cache_shapes(cfg, 1)
                for shape in entry}) == 4


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("n_group", 2), ("n_shared_experts", 1),
    ("swa_head_dim", 128), ("partial_rotary_factor", 0.3),
    ("hybrid_layer_pattern", [0, 1, 2, 1, 1, 1, 0])])
def test_what_the_file_says_and_the_program_cannot_is_refused(key, value):
    with pytest.raises(ValueError):
        config_from_hf(dict(TOY, **{key: value}))


# ---- the caches -------------------------------------------------------

def test_four_cache_shapes(toy):
    cfg = toy[0]
    assert kv_cache_kinds(cfg) == ["full"] + ["window"] * 5 + ["full"]
    shapes = kv_cache_shapes(cfg, 3)
    # a full layer's heads folded into the channels, a ring with its heads
    assert shapes[0] == shapes[6] == ((3, CONTEXT, 2 * 24), (3, CONTEXT, 32))
    assert shapes[1] == ((3, 8, 4, 24), (3, 8, 4, 16))
    assert not uniform_kv_caches(cfg)
    assert cached_key_block(cfg, 1) == 0     # no whole lanes at this size


@pytest.mark.parametrize("what", ["the KV block pool", "beam search",
                                  "generate_speculative",
                                  "disaggregated serving"])
def test_the_caches_are_refused_by_name(toy, what):
    with pytest.raises(ValueError, match="keys wider than their values") \
            as err:
        require_uniform_kv_caches(toy[0], what)
    said = str(err.value)
    assert what in said and "4 shapes in one model" in said and \
        "(1, 8, 4, 24)" in said and "(1, 128, 48)" in said


@pytest.mark.parametrize("rows,queries,offsets", [
    (3, 1, (0, 700, 1535)), (2, 4, (508, 1020)), (1, 16, (1500,))])
def test_the_folded_kernel_is_the_reference(rows, queries, offsets):
    """The kernel at the published head widths (keys of 192 channels,
    values of 128, 64 heads over 4), interpreted, against
    ``reference_attention`` over the unfolded caches; and its twin."""
    keys = jax.random.split(jax.random.PRNGKey(rows), 3)
    q = jax.random.normal(keys[0], (rows, queries, 64, 192), jnp.float32)
    k = jax.random.normal(keys[1], (rows, 1536, 4, 192), jnp.float32)
    v = jax.random.normal(keys[2], (rows, 1536, 4, 128), jnp.float32)
    offset = jnp.asarray(offsets, jnp.int32)
    folded = k.reshape(rows, 1536, 768), v.reshape(rows, 1536, 512)
    assert kernels.folded_fits(q, *folded)
    assert kernels.folded_block_k(*folded) == 512
    want = highest(lambda: reference_attention(q, k, v, causal=True,
                                               offset=offset))
    got = highest(lambda: kernels.folded_cached_attention(
        q, *folded, offset, interpret=True))
    np.testing.assert_allclose(got, want, atol=TOL)
    twin = highest(lambda: gpt_model._attention_over_folded_blocks(
        q, *folded, offset))
    np.testing.assert_allclose(twin, want, atol=TOL)


def test_the_walk_over_key_blocks_takes_a_sink_and_a_ragged_cache():
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, 24, 8, 24), jnp.float32)
    k = jax.random.normal(keys[1], (2, 300, 2, 24), jnp.float32)
    v = jax.random.normal(keys[2], (2, 300, 2, 16), jnp.float32)
    sink = jax.random.normal(keys[3], (8,), jnp.float32)
    for offset in (jnp.int32(270), jnp.asarray([3, 276], jnp.int32)):
        want = highest(lambda: reference_attention(
            q, k, v, causal=True, offset=offset, sink=sink))
        got = highest(lambda: gpt_model._attention_over_folded_blocks(
            q, k.reshape(2, 300, 48), v.reshape(2, 300, 32), offset, sink))
        np.testing.assert_allclose(got, want, atol=TOL)


def test_the_cores_say_which_they_are(toy):
    """The trace-time gauge: a cached full layer of this configuration
    never reads every position its cache can hold."""
    from alpa_tpu.telemetry import metrics as tmetrics
    cfg, model, params = toy
    series = 'alpa_cached_attention_core{core="%s",heads="2",' \
        'head_dim="24",queries="%d"}'
    before = tmetrics.get_registry().snapshot()
    gen = Generator(model, params, cfg, prefill_chunk=16)
    _, caches = gen._run_chunked_prefill(
        [np.arange(4, 24)], jnp.asarray([20], jnp.int32), 1)
    gen._decode(params, jnp.ones((1, 1), jnp.int32), caches[0][2], caches)
    after = tmetrics.get_registry().snapshot()

    def rose(name):
        return after.get(name, 0) - before.get(name, 0)

    assert rose(series % ("key_block_walk", 16)) == 2
    assert rose(series % ("key_block_walk", 1)) == 2
    assert not any(rose(name) for name in after
                   if 'core="reference"' in name and 'head_dim="24"' in name)


# ---- against the reference --------------------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    _, model, params = toy
    ids, logits, picks = wanted
    got, routing = highest(model.apply, params, ids[None])
    np.testing.assert_allclose(got[0], logits, atol=TOL)
    assert (np.sort(routing["experts"], -1) == np.sort(picks, -1)).all()


@pytest.mark.parametrize("control", sorted(controls_mimo.CONTROLS) +
                         ["one_theta"])
def test_a_wrong_wiring_fails(toy, wanted, control, monkeypatch):
    """Leaving out the sink or the value scale, rotating all channels, a
    cache in float8 (the cell's controls: ``chipbench/controls_mimo.py``),
    and one rotary base for both kinds, each moves the logits far beyond
    the tolerance."""
    cfg, model, params = toy
    ids, logits, _ = wanted
    if control in controls_mimo.CONTROLS:
        controls_mimo.CONTROLS[control](TOY, monkeypatch.setattr)
        model = GPTModel(toy_config())
    else:
        model = GPTModel(dataclasses.replace(cfg, sliding_rope_theta=None))
    if control == "cache_in_float8":
        # the cache's precision shows through the cache
        gen = Generator(model, params, cfg, prefill_chunk=16)
        got, _ = highest(gen._run_chunked_prefill, [ids],
                         jnp.asarray([S], jnp.int32), 1)
        assert float(np.abs(np.asarray(got[0]) - logits[-1]).max()) > \
            100 * TOL
        return
    got, _ = highest(model.apply, params, ids[None])
    assert float(np.abs(np.asarray(got[0]) - logits).max()) > 100 * TOL


@pytest.mark.parametrize("chunk,prompt", [(16, 37), (16, 16)])
def test_chunked_prefill_then_decode_equals_the_reference(toy, wanted,
                                                          chunk, prompt):
    """Chunks (longer than the window) through the four cache shapes, then
    decode steps: logits and picks against the reference's full forward."""
    cfg, model, params = toy
    ids, logits, picks = wanted
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    last, caches = highest(
        gen._run_chunked_prefill, [ids[:prompt]],
        jnp.asarray([prompt], jnp.int32), 1)
    np.testing.assert_allclose(last[0], logits[prompt - 1], atol=TOL)
    for t in range(prompt, S):
        out, caches, routing = highest(
            gen._decode, params, jnp.asarray(ids[None, t:t + 1]),
            caches[0][2], caches)
        np.testing.assert_allclose(out[0], logits[t], atol=TOL)
        assert (np.sort(routing["experts"][:, 0], -1) ==
                np.sort(picks[:, t], -1)).all()
    for kind, (k, v, index) in zip(kv_cache_kinds(cfg), caches):
        assert int(index[0]) == S
        assert (k.shape, v.shape) == (
            ((1, 8, 4, 24), (1, 8, 4, 16)) if kind == "window" else
            ((1, CONTEXT, 48), (1, CONTEXT, 32)))


def test_a_long_prompts_chunks_wait_for_the_device(toy, wanted, monkeypatch):
    """A prompt of many chunks holds a bounded number of its row's cache
    sets in flight (``generation.CHUNK_CACHES_AHEAD_BYTES``): the host
    waits for an earlier chunk before it sends the next, and the result is
    what it was."""
    from alpa_tpu.serve import generation
    cfg, model, params = toy
    ids, logits, _ = wanted
    gen = Generator(model, params, cfg, prefill_chunk=8)
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(generation.jax, "block_until_ready",
                        lambda x: waited.append(1) or ready(x))
    run_it = lambda: highest(                               # noqa: E731
        gen._run_chunked_prefill, [ids[:45]], jnp.asarray([45], jnp.int32), 1)
    last, _ = run_it()
    assert not waited                       # six chunks of a small cache
    monkeypatch.setattr(generation, "CHUNK_CACHES_AHEAD_BYTES", 1)
    bounded, _ = run_it()
    assert len(waited) == 6 - 2             # two sets ahead at the least
    run_it()
    # and the prompt before this one is through before its first chunk
    assert len(waited) == 2 * (6 - 2) + 1
    np.testing.assert_allclose(last[0], logits[44], atol=TOL)
    assert (np.asarray(bounded) == np.asarray(last)).all()


def test_rows_of_mixed_lengths_in_one_engine(toy, wanted):
    """The engine's chunked admission and its ticks over rows of unlike
    lengths, through the four cache shapes, against ``Generator.generate``
    and (teacher-forced on the reference's sequence) the reference's
    logits; the gauges say what the caches hold."""
    from alpa_tpu.serve.engine import ContinuousBatchingEngine
    from alpa_tpu.telemetry import metrics as tmetrics
    cfg, model, params = toy
    ids, logits, _ = wanted
    gen = Generator(model, params, cfg, prefill_chunk=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, 256, size=n) for n in (5, 19, 40, 12)]
    ask = GenerationConfig(max_new_tokens=9)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    try:
        got = [np.asarray(engine.submit(p, ask)) for p in prompts]
        after = tmetrics.get_registry().snapshot()
        # the reference's own sequence: its greedy continuation
        served = np.asarray(engine.submit(ids[:30], ask))
    finally:
        engine.shutdown()
    for p, out in zip(prompts, got):
        assert out.tolist() == np.asarray(gen.generate([p], ask)[0]).tolist()
    assert served[30] == int(logits[29].argmax())
    full = 2 * 3 * CONTEXT * (48 + 32) * 4
    window = 5 * 3 * 8 * 4 * (24 + 16) * 4
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == full
    assert after['alpa_serving_kv_cache_bytes{kind="window"}'] == window
    by_array = {name: value for name, value in after.items()
                if name.startswith("alpa_serving_kv_cache_array_bytes") and
                value and ('"3x128x' in name or '"3x8x4x' in name)}
    assert len(by_array) == 4 and sum(by_array.values()) == full + window
    assert by_array['alpa_serving_kv_cache_array_bytes{kind="full",'
                    'array="keys",shape="3x128x48"}'] == 2 * 3 * 128 * 48 * 4
    obs = {"counters": ({}, after), "served_context": CONTEXT,
           "engine_rows": 3}
    assert run.metric_reader("full_cache_bytes_per_position")(obs) == \
        arithmetic_mimo.full_cache_bytes_per_position(TOY, 4) == \
        2 * 2 * (24 + 16) * 4


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """The routed parts that the four shares give add up to what the
    uncut reference gives for the whole layer; the program's share is the
    reference's."""
    whole_cfg = toy_config(experts_held=None)
    model, params = made(whole_cfg)
    layer = REF.weights_from_program(params)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (S, 64), jnp.float32)
    args = (1e-5, 3, True, 1.0)
    whole, picks = REF.routed_mlp(x, layer, *args, 0, S)

    def cut(first):
        return dict(layer, w_gate_up=layer["w_gate_up"][first:first + 4],
                    w_down=layer["w_down"][first:first + 4])

    parts = [REF.routed_mlp(x, cut(first), *args, first, S)
             for first in (0, 4, 8, 12)]
    for _, chosen in parts:
        assert (chosen == picks).all()
    np.testing.assert_allclose(sum(part - x for part, _ in parts) + x,
                               whole, atol=TOL)
    # the program's share 1 of the same layer
    from alpa_tpu.model.moe import DroplessExperts
    held = dataclasses.replace(whole_cfg, experts_held=(4, 4))
    mine = dict(params["params"]["h1"]["mlp"])
    mine.update(w_gate_up=mine["w_gate_up"][4:8], w_down=mine["w_down"][4:8])
    u = REF.rms(x, layer["n2"], 1e-5)
    got, _ = highest(DroplessExperts(held).apply, {"params": mine}, u[None])
    np.testing.assert_allclose(got[0] + x, parts[1][0], atol=TOL)


# ---- the benchmark's side ---------------------------------------------

def test_the_cell_joins_the_lists_its_readers_can_fill():
    cell = "mimo-v2-flash-1chip.longmix"
    entry = next(c for c in BENCH["workloads"] if c["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("mimo-v2-flash-1chip", "longmix-closed64", 1)
    mix = traffic.load_mix("longmix-closed64")
    assert (mix["clients"], mix["pool_size"], mix["sizes_seed"]) == \
        (64, 512, 20261003)
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, group, cell)}
    assert {"out_tokens_per_s", "setup_s", "full_decode_hbm_roofline_pct",
            "window_decode_share_pct", "window_chunk_share_pct",
            "full_cache_bytes_per_position", "attention_chunk_share_pct",
            "moe_local_rows_pct", "decode_head_ms"} <= reported
    assert "kv_cache_bytes_per_position" not in reported
    # (by position among the accepted entries: a later PR's appends leave
    # these standing)
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("full_decode_hbm_roofline_pct")
    assert names[first:first + 4] == [
        "full_decode_hbm_roofline_pct", "window_decode_share_pct",
        "window_chunk_share_pct", "full_cache_bytes_per_position"]
    assert [c["name"] for c in BENCH["workloads"]].index(cell) == 13 and \
        sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_the_new_readers_read_what_the_driver_finds():
    obs = {"peaks": {"hbm_bytes_per_s": 819e9}, "config": CELL,
           "cache_itemsize": 2,
           "traced_counters": (
               {"alpa_serving_decode_positions_total": 1e6},
               {"alpa_serving_decode_positions_total":
                1e6 + 32 * 12000 * 100}),
           "decode_trace": {"decode_s": 1.5, "decode_runs": 100,
                            "full_core_s": 0.4, "full_core_events": 200,
                            "window_core_s": 0.06, "window_core_events": 900},
           "chunk_trace": {"program_s": 2.0, "runs": 40,
                           "window_core_s": 0.5, "window_core_events": 700}}
    # 38.4 M held positions x 5,120 bytes = 196.6 GB: 0.24 s at the peak
    share = run.metric_reader("full_decode_hbm_roofline_pct")(obs)
    assert share == pytest.approx(100 * 38.4e6 * 5120 / 819e9 / 0.4)
    assert run.metric_reader("window_decode_share_pct")(obs) == \
        pytest.approx(4.0)
    assert run.metric_reader("window_chunk_share_pct")(obs) == \
        pytest.approx(25.0)
    # a program without the scopes (the parent's): nothing, and no error
    bare = dict(obs, decode_trace={"decode_s": 1.5}, chunk_trace={},
                counters=({}, {}))
    for name in ("full_decode_hbm_roofline_pct", "window_decode_share_pct",
                 "window_chunk_share_pct", "full_cache_bytes_per_position"):
        assert run.metric_reader(name)(bare) is None
    work = arithmetic_mimo.full_decode_work(CELL, 1000, 2)
    assert work == {"flops": 2 * 1000 * 64 * 2 * 320, "bytes": 5_120_000}


def test_the_head_forgets_the_common_direction_and_nothing_else(toy):
    """``serve_mimo.spread_head``: the head's product with the mean of its
    input is zero afterwards, a vector across that mean reads as before,
    and no other parameter moves."""
    _, model, params = toy
    key = jax.random.PRNGKey(3)
    moved = DRIVER.spread_head(model, params, key, TOY["vocab_size"])
    ids = jax.random.randint(key, (1, CONTEXT), 4, TOY["vocab_size"])
    mean = model.apply(params, ids, return_hidden=True).mean((0, 1))
    u = mean / jnp.linalg.norm(mean)
    was, now = (p["params"]["lm_head"]["kernel"] for p in (params, moved))
    assert float(jnp.abs(u @ was).max()) > 0.01
    np.testing.assert_allclose(u @ now, 0, atol=1e-5)
    across = jnp.eye(64)[0] - u[0] * u
    np.testing.assert_allclose(across @ now, across @ was, atol=1e-5)
    same = jax.tree_util.tree_map(lambda a, b: a is b, params, moved)
    assert all(v for path, v in jax.tree_util.tree_leaves_with_path(same)
               if path[-2].key != "lm_head")


def test_driver_runs_the_toy_cell(toy_context, checks_the_same_requests):
    """``chipbench/drivers/serve_mimo.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' biases, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the check against the reference; and what the
    cell's readers make of it."""
    obs = DRIVER.run(toy_context("toy-mimo.longmix", "toy-longmix", 3.0, 2,
                                 checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 16, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["compiles_in_window"] == 0
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 6
    assert obs["expert_bytes"] == 3 * 64 * 32 * 4
    assert obs["served_context"] == CONTEXT
    # a CPU trace has no TPU plane
    assert obs["decode_trace"] == {} and obs["chunk_trace"] == {}
    obs.update(peaks=None, config=TOY)
    assert run.metric_reader("full_cache_bytes_per_position")(obs) == 640
    local = run.metric_reader("moe_local_rows_pct")(obs)
    assert 10 < local < 45            # 4 of 16 experts
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 4
    for traced in ("full_decode_hbm_roofline_pct", "window_decode_share_pct",
                   "window_chunk_share_pct"):
        assert run.metric_reader(traced)(obs) is None
    spans = [s for s in obs["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 16)
        for s in spans)
