"""What PR 51 added to the benchmark: the plain mimo_v2_flash reference
against cases small enough to compute by hand, the arithmetic of
``arithmetic_mimo.py`` at the published widths, the four new readers on
made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had (by position relative
to the accepted entries, so that the next PR's appends leave these checks
standing).  The driver's CPU rehearsal and the program against the
reference are ``tests/model/test_mimo_v2_flash.py``."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_mimo, run, traffic

ref = run.load_module("references", "mimo_v2_flash_decoder")
driver = run.load_module("drivers", "serve_mimo")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "mimo-v2-flash-1chip.longmix"
CONFIG = run.load_json(run.HERE, "configs", "mimo-v2-flash-1chip.json")
NEW = ["full_decode_hbm_roofline_pct", "window_decode_share_pct",
       "window_chunk_share_pct", "full_cache_bytes_per_position"]
JOINED = ["tick_ms", "engine_occupancy_pct", "hbm_peak_gb.serve",
          "tick_host_ms", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct", "prefill_chunk_ms",
          "attention_chunk_share_pct", "moe_local_rows_pct",
          "decode_head_ms"]


# ---- the reference, by hand -------------------------------------------

def test_the_leading_channels_turn_and_the_others_pass():
    """Position 1, six channels of which four turn: channel 0 pairs with
    channel 2 (the half of the four), channels 4 and 5 are as they were."""
    x = jnp.zeros((2, 1, 6)).at[:, 0, 0].set(1.0).at[:, 0, 5].set(7.0)
    np.testing.assert_allclose(
        ref.rotate(x, 1e4, 4)[1, 0],
        [math.cos(1), 0, math.sin(1), 0, 0, 7.0], atol=1e-6)
    np.testing.assert_allclose(ref.rotate(x, 1e4, 4)[0, 0],
                               [1, 0, 0, 0, 0, 7.0])


def test_the_sink_takes_mass_and_carries_no_value():
    """One head, one channel, two positions whose keys are 0, so every
    score is 0; values 1 and 3; the sink's logit 0 is one more unit in the
    denominator: position 0 reads 1/2 of its own value, position 1 reads
    (1 + 3) / 3; with a window of 1 position 1 sees itself alone: 3 / 2.
    The value scale multiplies what comes out."""
    eye = jnp.ones((1, 1))
    b = {"n1": jnp.ones(1), "w_o": eye,
         "w_qkv": jnp.asarray([[0.0, 0.0, 1.0]]), "sink": jnp.zeros(1)}
    x = jnp.asarray([[1.0], [3.0]])
    # rms(x) is (1, 1): the values are 1 and 1; use w_v to scale instead
    got = ref.attention(x, b, 1, 1, 1, 1, 0, 1e4, 0, 1.0, 0.0, 2) - x
    np.testing.assert_allclose(got[:, 0], [1 / 2, 2 / 3], atol=1e-6)
    got = ref.attention(x, b, 1, 1, 1, 1, 0, 1e4, 1, 0.5, 0.0, 2) - x
    np.testing.assert_allclose(got[:, 0], [1 / 4, 1 / 4], atol=1e-6)
    plain = {name: a for name, a in b.items() if name != "sink"}
    got = ref.attention(x, plain, 1, 1, 1, 1, 0, 1e4, 0, 1.0, 0.0, 2) - x
    np.testing.assert_allclose(got[:, 0], [1.0, 1.0], atol=1e-6)


def test_the_sigmoid_router_chooses_with_the_bias_and_renormalises():
    """Three experts, logits (0, ln 3, -ln 3): scores 1/2, 3/4, 1/4.  The
    bias lifts the last over the first; the two weights are the chosen
    scores over their sum, the bias nowhere in them."""
    u = jnp.asarray([[1.0]])
    w_r = jnp.asarray([[0.0, math.log(3), -math.log(3)]])
    weights, chosen = ref.route(u, w_r, jnp.asarray([0.0, 0.0, 0.4]), 2,
                                True, 1.0)
    assert chosen.tolist() == [[1, 2]]
    np.testing.assert_allclose(weights, [[0, 0.75, 0.25]], atol=1e-6)


# ---- the arithmetic ----------------------------------------------------

def test_parameters_and_caches_at_the_published_widths():
    p = arithmetic_mimo.layer_parameters(CONFIG)
    assert p["full_attention"] == 89_128_960
    assert p["window_attention"] == 94_371_840
    assert p["dense_mlp"] == 201_326_592
    assert p["routed_expert"] == 25_165_824 and p["router"] == 1_048_576
    assert p["vocabulary"] == 2 * 19072 * 4096
    assert arithmetic_mimo.model_parameters(CONFIG) == 3_429_892_096
    whole = dict(CONFIG, num_hidden_layers=48, n_routed_experts=256,
                 vocab_size=152576, **{
                     k: CONFIG["published"][k]
                     for k in ("hybrid_layer_pattern", "moe_layer_freq")})
    assert round(arithmetic_mimo.model_parameters(whole) / 1e9, 1) == 308.8
    assert (arithmetic_mimo.full_layers(CONFIG),
            arithmetic_mimo.window_layers(CONFIG),
            arithmetic_mimo.expert_layers(CONFIG)) == (2, 5, 6)
    assert arithmetic_mimo.full_layer_bytes_per_position(CONFIG, 2) == 2560
    assert arithmetic_mimo.full_cache_bytes_per_position(CONFIG, 2) == 5120
    assert arithmetic_mimo.ring_bytes_per_row(CONFIG, 2) == 655_360
    assert arithmetic_mimo.expert_bytes(4096, 2048, 2) == 50_331_648


def test_the_full_layers_decode_is_bound_by_memory():
    work = arithmetic_mimo.full_decode_work(CONFIG, 32 * 12000, 2)
    assert work["bytes"] == 32 * 12000 * 5120
    # 64 operations a byte against the chip's 240
    assert work["flops"] / work["bytes"] == 2 * 64 * 2 * 320 / 5120 == 16.0


# ---- the readers -------------------------------------------------------

def test_the_new_readers():
    obs = {"peaks": {"hbm_bytes_per_s": 819e9}, "config": CONFIG,
           "cache_itemsize": 2, "served_context": 32768, "engine_rows": 32,
           "traced_counters": (
               {"alpa_serving_decode_positions_total": 5.0},
               {"alpa_serving_decode_positions_total": 5.0 + 4e7}),
           "counters": ({}, {
               'alpa_serving_kv_cache_bytes{kind="full"}':
               2 * 32 * 32768 * 1280 * 2}),
           "decode_trace": {"decode_s": 1.2, "full_core_s": 0.4,
                            "full_core_events": 7, "window_core_s": 0.12,
                            "window_core_events": 9},
           "chunk_trace": {"program_s": 1.6, "window_core_s": 0.12,
                           "window_core_events": 3}}
    assert run.metric_reader("full_decode_hbm_roofline_pct")(obs) == \
        pytest.approx(100 * 4e7 * 5120 / 819e9 / 0.4)
    assert run.metric_reader("window_decode_share_pct")(obs) == \
        pytest.approx(10.0)
    assert run.metric_reader("window_chunk_share_pct")(obs) == \
        pytest.approx(7.5)
    assert run.metric_reader("full_cache_bytes_per_position")(obs) == 5120
    # what the parent's program gives: no scope, no series; and no chip
    bare = dict(obs, decode_trace={"decode_s": 1.2}, chunk_trace={},
                counters=({}, {}))
    for name in NEW:
        assert run.metric_reader(name)(bare) is None
    assert run.metric_reader("full_decode_hbm_roofline_pct")(
        dict(obs, peaks=None)) is None


# ---- the entries -------------------------------------------------------

def test_the_new_entries_follow_what_the_benchmark_had():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("dots3-note-prev-1chip.longctx")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("mimo-v2-flash-1chip") > \
        configs.index("dots3-note-prev-1chip")
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    for name in NEW:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert per_layer.index(name) > per_layer.index(
            "collective_exposed_pct")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "out_tokens_per_s"
    for name in JOINED:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert CELL in entry["workloads"]
        assert entry["workloads"].index(CELL) > entry["workloads"].index(
            "dots3-note-prev-1chip.longctx" if
            "dots3-note-prev-1chip.longctx" in entry["workloads"]
            else entry["workloads"][0])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["out_tokens_per_s"]["workloads"][-1] == CELL or \
        CELL in e2e["out_tokens_per_s"]["workloads"]
    assert CELL not in e2e["gap_p99_ms"]["workloads"]
    assert CELL not in BENCH["per_layer"][per_layer.index(
        "kv_cache_bytes_per_position")]["workloads"]


def test_the_cells_files():
    mix = traffic.load_mix("longmix-closed64")
    assert mix["kind"] == "closed_loop" and mix["clients"] == 64
    assert mix["prompt_len"] == {"median": 6144, "sigma": 0.8, "min": 512,
                                 "max": 28672}
    assert mix["output_len"] == {"median": 768, "sigma": 0.7, "min": 128,
                                 "max": 4096}
    assert (mix["check_requests"], mix["drain_s"], mix["trace_after_s"],
            mix["trace_seconds"]) == (4, 120.0, 5.0, 3.0)
    serve = CONFIG["serve"]
    assert (serve["served_context"], serve["engine_rows"],
            serve["prefill_chunk"]) == (32768, 32, 1024)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= \
        serve["served_context"]
    assert CONFIG["driver"] == "serve_mimo" and callable(driver.run)
    settings = driver.reference_settings(CONFIG)
    assert settings["rotary_dim"] == 64 and settings["experts_first"] == 0
    assert settings["kv_heads"] == {"full": 4, "sliding": 8}
    assert settings["sink_kinds"] == ["sliding"]
    assert settings["pattern"] == [0, 1, 1, 1, 1, 1, 0]
    for name in ("logit_margin", "logit_margin_flipped", "logit_atol",
                 "logit_atol_flipped", "logit_mean_atol",
                 "min_choice_agreement"):
        assert name in CONFIG
    assert "logit_margin_why" in CONFIG and "assumed" in CONFIG
    toy = run.load_json(run.HERE, "configs", "toy-mimo.json")
    assert toy["driver"] == "serve_mimo" and toy["dtype"] == "float32"
