"""What PR 47 added to the benchmark: the plain dots3_note reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_dsa.py`` at the published widths, the four new readers on
made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had (by position relative
to the accepted entries, so that the next PR's appends leave these checks
standing)."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_dsa, run, traffic

ref = run.load_module("references", "dots3_note_decoder")
driver = run.load_module("drivers", "serve_dsa")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "dots3-note-prev-1chip.longctx"
CONFIG = run.load_json(run.HERE, "configs", "dots3-note-prev-1chip.json")
NEW = ["selected_positions_pct", "indexer_decode_share_pct",
       "indexer_chunk_share_pct", "latent_select_decode_roofline_pct"]
JOINED = ["tick_ms", "engine_occupancy_pct", "hbm_peak_gb.serve",
          "tick_host_ms", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct", "prefill_chunk_ms",
          "attention_chunk_share_pct", "kv_cache_bytes_per_position",
          "moe_local_rows_pct", "decode_head_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SERIES = 'alpa_serving_select_positions_total{what="%s"}'


# ---- the reference, by hand -------------------------------------------

def test_the_two_rotations_pair_their_channels_differently():
    """Position 1, four channels (1, 0, 0, 0): the latent attention's
    rotation turns channel 0 with channel 1, the indexer's with channel
    2."""
    x = jnp.zeros((2, 1, 4)).at[:, 0, 0].set(1.0)
    np.testing.assert_allclose(
        ref.rotate(x, 1e4)[1, 0], [math.cos(1), math.sin(1), 0, 0],
        atol=1e-6)
    np.testing.assert_allclose(
        ref.rotate_half(x, 1e4)[1, 0], [math.cos(1), 0, math.sin(1), 0],
        atol=1e-6)
    np.testing.assert_allclose(ref.rotate_half(x, 1e4)[0, 0], [1, 0, 0, 0])


def test_the_layer_norm_has_a_weight_and_a_bias():
    x = jnp.asarray([[1.0, 3.0]])
    np.testing.assert_allclose(
        ref.layer_norm(x, jnp.asarray([2.0, 2.0]), jnp.asarray([0.5, 0.0]),
                       eps=0.0), [[-1.5, 2.0]], atol=1e-6)


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
    weights, chosen = ref.route(u, w_r, jnp.zeros(3), 2, False, 2.0)
    assert chosen.tolist() == [[1, 0]]
    np.testing.assert_allclose(weights, [[1.0, 1.5, 0]], atol=1e-6)


def test_the_selection_by_hand():
    """One index head of two channels, nothing rotated (dr 0): scores are
    w relu(q . k); the two best of each query's past are seen, ties to
    the lower position."""
    # select() with the projections as identities: h = c_q = the vectors
    h = jnp.asarray([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    b = {"w_iq": jnp.eye(2), "w_ik": jnp.eye(2), "n_ik": jnp.ones(2),
         "b_ik": jnp.zeros(2), "w_iw": jnp.ones((2, 1))}
    # dr = 0: nothing is rotated; the LayerNorm makes (1, 0) -> (1, -1),
    # (0, 1) -> (-1, 1), (1, 1) -> (0, 0)
    packed, chosen, real = ref.select(h, h, b, 1, 2, 0, 2, 1e4, 4)
    seen = np.unpackbits(np.asarray(packed), axis=-1)[:, :4]
    assert seen.tolist() == [[1, 0, 0, 0], [1, 1, 0, 0], [1, 0, 1, 0],
                             [1, 1, 0, 0]]
    assert real.tolist() == [1, 2, 2, 2]
    # query 1 = (1, 0) against keys (1,-1), (1,-1): both 1 x w, w = 1/sqrt2
    assert set(chosen[1, :2].tolist()) == {0, 1}
    # query 2 = (0, 1): keys 0, 1 score relu(-1) = 0, key 2 scores 1
    assert chosen[2, 0] == 2 and chosen[2, 1] == 0
    assert seen[2].tolist() == [1, 0, 1, 0]
    # query 3 = (1, 1): every key scores relu(0) = 0: the lowest two
    assert chosen[3, :2].tolist() == [0, 1]


def test_a_window_of_three_by_hand():
    """A sliding layer of one head whose values are the positions'
    latents: with equal scores the output is the mean of the last three
    positions' values."""
    s, hidden = 5, 4
    x = jnp.arange(s * hidden, dtype=jnp.float32).reshape(s, hidden) / 10
    zeros = jnp.zeros
    b = {"n_attn": jnp.ones(hidden), "w_q_a": zeros((hidden, 2)),
         "n_q": jnp.ones(2), "w_q_b": zeros((2, 4)),
         "w_kv_a": jnp.eye(hidden)[:, :4], "n_kv": jnp.ones(2),
         "w_kv_b": jnp.concatenate([zeros((2, 2)), jnp.eye(2)], 1),
         "w_g": zeros((hidden, 1)), "w_o": jnp.eye(2, hidden)}
    eps = 1e-6
    out, chosen, real = ref.attention(x, b, 1, 2, 2, 2, eps, 1e4, False, 3,
                                      None, s, 1)
    assert chosen.size == 0 and real.size == 0
    x = np.asarray(x, np.float64)
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + eps)
    c = h[:, :2] / np.sqrt((h[:, :2] ** 2).mean(-1, keepdims=True) + eps)
    want = np.stack([c[max(0, t - 2):t + 1].mean(0) for t in range(s)])
    # queries are zero, so every seen key scores alike; gates are 1/2
    np.testing.assert_allclose(np.asarray(out)[:, :2] - x[:, :2],
                               0.5 * want, atol=1e-5)


# ---- the arithmetic ---------------------------------------------------

def test_parameters_at_the_published_widths():
    p = arithmetic_dsa.layer_parameters(CONFIG)
    h = 5120
    assert p["full_attention"] == (
        h * 1024 + 1024 * 128 * 192 + h * 576 + 512 * 128 * 256 + h * 128 +
        128 * 128 * h) == 134_676_480
    assert p["indexer"] == 1024 * 64 * 128 + h * 128 + h * 64 == 9_371_648
    assert p["sliding_attention"] == (
        h * 1024 + 1024 * 64 * 256 + h * 1088 + 1024 * 64 * 320 + h * 64 +
        64 * 128 * h) == 90_832_896
    assert p["routed_expert"] == 3 * h * 1536 == 23_592_960
    assert p["dense_mlp"] == 3 * h * 13824 and p["router"] == h * 256
    assert p["vocabulary"] == 2 * 19008 * h
    assert arithmetic_dsa.expert_layers(CONFIG) == 5
    assert arithmetic_dsa.full_layers(CONFIG) == 3
    assert arithmetic_dsa.layer_types(CONFIG) == [
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention", "full_attention"]
    assert arithmetic_dsa.model_parameters(CONFIG) == 5_011_013_632
    assert arithmetic_dsa.expert_bytes(h, 1536, 2) == 47_185_920


def test_cache_bytes_at_the_published_widths():
    assert arithmetic_dsa.full_layer_bytes_per_position(CONFIG, 2) == 1408
    assert arithmetic_dsa.full_layer_bytes_held_per_position(
        CONFIG, 2) == 1536
    assert arithmetic_dsa.ring_bytes_per_row(CONFIG, 2) == 1_116_288
    assert arithmetic_dsa.kv_cache_bytes_per_position(
        CONFIG, 2, 32768) == 3 * 1536 + 3 * 1_116_288 / 32768


def test_the_selecting_layers_work_from_shapes_alone():
    scores = arithmetic_dsa.index_scores_work(CONFIG, 1024, 32768, 2)
    assert scores["flops"] == 1024 * 32768 * 64 * (2 * 128 + 3)
    assert scores["bytes"] == 32768 * 128 * 2
    core = arithmetic_dsa.selected_core_work(CONFIG, 2048, 2)
    assert core["bytes"] == 2048 * 576 * 2 == 2_359_296
    assert core["flops"] == 2048 * 128 * 2 * (576 + 512)
    # a row of 32,768 positions, one layer, one tick: 8.4 MB of index keys
    # and 2.4 MB of rows, where the whole latent cache would be 37.7 MB
    assert arithmetic_dsa.latent_select_decode_bytes(
        CONFIG, 32768, 2048, 2) == 32768 * 256 + 2048 * 1152
    assert 32768 * 1152 == 37_748_736


# ---- the readers ------------------------------------------------------

def obs_of(**kw):
    before = {SERIES % "held": 1000.0, SERIES % "selected": 900.0}
    after = {SERIES % "held": 1000.0 + 3 * 16 * 9000 * 10,
             SERIES % "selected": 900.0 + 3 * 16 * 2048 * 10}
    return {"counters": (before, after), "traced_counters": (before, after),
            "peaks": PEAKS, "config": CONFIG, "cache_itemsize": 2,
            "decode_trace": {"decode_s": 0.2, "decode_runs": 10,
                             "indexer_s": 0.02, "indexer_events": 30,
                             "latent_select_s": 0.01,
                             "latent_select_events": 60},
            "chunk_trace": {"program_s": 0.5, "runs": 5, "indexer_s": 0.1,
                            "indexer_events": 15}, **kw}


def test_selected_positions_pct():
    read = run.metric_reader("selected_positions_pct")
    assert read(obs_of()) == pytest.approx(100 * 2048 / 9000)
    assert read({"counters": ({}, {})}) is None
    assert read({}) is None


def test_indexer_shares():
    assert run.metric_reader("indexer_decode_share_pct")(obs_of()) == \
        pytest.approx(10.0)
    assert run.metric_reader("indexer_chunk_share_pct")(obs_of()) == \
        pytest.approx(20.0)
    for name in ("indexer_decode_share_pct", "indexer_chunk_share_pct"):
        read = run.metric_reader(name)
        # a program without the scope (the parent's), a run without a trace
        assert read(obs_of(decode_trace={"decode_s": 0.2, "attention_s": 0.1},
                           chunk_trace={"program_s": 0.5})) is None
        assert read({}) is None


def test_latent_select_decode_roofline_pct():
    read = run.metric_reader("latent_select_decode_roofline_pct")
    held, selected = 3 * 16 * 9000 * 10, 3 * 16 * 2048 * 10
    least = (held * 256 + selected * 1152) / 819e9
    assert read(obs_of()) == pytest.approx(100 * least / 0.03)
    assert 100 * least / 0.03 < 100
    assert read(obs_of(peaks=None)) is None
    assert read(obs_of(decode_trace={})) is None
    assert read(obs_of(traced_counters=None)) is None


# ---- the entries ------------------------------------------------------

def test_the_new_entries_follow_what_the_benchmark_had():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("longcat-flash-1chip.agent")
    cell = BENCH["workloads"][cells.index(CELL)]
    assert cell == {**cell, "config": "dots3-note-prev-1chip",
                    "traffic": "longctx-closed32", "chips": 1}
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("dots3-note-prev-1chip") > \
        configs.index("longcat-flash-1chip")
    entry = BENCH["configs"][configs.index("dots3-note-prev-1chip")]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"].split(" ")[0]
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]) > names.index("shortcut_moe_chunk_share_pct")
    for name in NEW:
        m = BENCH["per_layer"][names.index(name)]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
        assert callable(run.metric_reader(name))
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in NEW}
    assert {BENCH["per_layer"][names.index(n)]["layer"]
            for n in NEW} <= layers
    for name in JOINED:
        assert BENCH["per_layer"][names.index(name)]["workloads"][-1] == CELL
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["out_tokens_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["gap_p99_ms"]["workloads"]


def test_the_cells_files():
    mix = traffic.load_mix("longctx-closed32")
    assert mix["kind"] == "closed_loop" and mix["clients"] == 32
    assert mix["prompt_len"] == {"median": 8192, "sigma": 0.7, "min": 2048,
                                 "max": 28672}
    assert mix["output_len"] == {"median": 768, "sigma": 0.7, "min": 128,
                                 "max": 4096}
    assert (mix["pool_size"], mix["sizes_seed"], mix["check_requests"],
            mix["drain_s"], mix["trace_after_s"], mix["trace_seconds"]) == \
        (512, 20261002, 4, 120.0, 5.0, 3.0)
    serve = CONFIG["serve"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= \
        serve["served_context"] == 32768
    assert mix["prompt_len"]["min"] >= CONFIG["index_topk"]
    assert serve["engine_rows"] * 2 == mix["clients"]
    assert callable(driver.run) and callable(ref.Reference)
    settings = driver.reference_settings(CONFIG)
    assert settings["full"] == {"heads": 128, "dn": 128, "dr": 64,
                                "dv": 128, "theta": 80000000}
    assert settings["sliding"] == {"heads": 64, "dn": 192, "dr": 64,
                                   "dv": 128, "theta": 50000}
    assert settings["layer_types"] == arithmetic_dsa.layer_types(CONFIG)
    assert settings["experts_first"] == 0 and settings["window"] == 513
