"""What PR 58 added to the benchmark: the plain nemotron_h reference
against cases small enough to compute by hand, the arithmetic of
``arithmetic_nemotron.py`` at the published widths, the five new readers
on made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had.  The driver's CPU
rehearsal and the program against the reference are
``tests/model/test_nemotron_h.py`` and ``tests/serve/test_ssm_state.py``."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_nemotron as arithmetic
from chipbench import run, traffic

ref = run.load_module("references", "nemotron_h_decoder")
driver = run.load_module("drivers", "serve_ssm")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "nemotron-3-nano-30b-a3b-1chip.reasoning"
CONFIG = run.load_json(run.HERE, "configs",
                       "nemotron-3-nano-30b-a3b-1chip.json")
NEW = ["ssm_decode_share_pct", "ssm_tick_hbm_roofline_pct",
       "ssm_chunk_roofline_pct", "ssm_chunk_share_pct",
       "ssm_state_bytes_per_row"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "decode_head_ms",
          "moe_decode_share_pct", "attention_decode_share_pct",
          "attention_chunk_share_pct", "experts_touched_per_tick",
          "moe_local_rows_pct", "moe_decode_hbm_roofline_pct"]


# ---- the reference, by hand -------------------------------------------

def test_the_recurrence_by_hand():
    """One head of one channel and a state of one value, two positions,
    every projection the identity on what it reads: ``xBC`` passes the
    convolution's last tap, ``dt`` = softplus(0 + dt_bias), ``A`` = -1:
    ``S_1 = dt x_1 B_1``, ``S_2 = exp(-dt) S_1 + dt x_2 B_2``, ``y = S C +
    D x``; the gate and the norm are applied to ``y`` after."""
    dt = math.log(1 + math.e)                     # softplus(1)
    # u (S, 1) -> [z | x B C | dt]: z = 2 u, x = B = C = u, dt = 0
    b = {"n": jnp.ones(1), "w_in": jnp.asarray([[2.0, 1.0, 1.0, 1.0, 0.0]]),
         "taps": jnp.asarray([[0.0] * 3, [1.0] * 3]), "conv_b": jnp.zeros(3),
         "dt_bias": jnp.ones(1), "a_log": jnp.zeros(1), "d": jnp.ones(1),
         "w_norm": jnp.ones(1), "w_out": jnp.ones((1, 1))}
    x = jnp.asarray([[1.0], [-1.0]])
    # rms(x) = (+1, -1) (eps 0); xBC = silu(+-1)
    up, down = 1 / (1 + math.exp(-1)), -1 / (1 + math.e)
    s1 = dt * up * up
    s2 = math.exp(-dt) * s1 + dt * down * down
    y = np.asarray([s1 * up + up, s2 * down + down])
    gate = np.asarray([2 / (1 + math.exp(-2)), -2 / (1 + math.exp(2))])
    want = np.sign(y * gate)                      # a norm over one channel
    got, kept = ref.mamba(x, b, 1, 1, 0.0, jnp.asarray([2, 1]))
    np.testing.assert_allclose((got - x)[:, 0], want, atol=1e-6)
    # the state after two positions and after one
    np.testing.assert_allclose(kept.reshape(2), [s2, s1], atol=1e-6)


def test_the_sigmoid_router_chooses_with_the_bias_and_renormalises():
    u = jnp.asarray([[1.0]])
    w_r = jnp.asarray([[0.0, math.log(3), -math.log(3)]])
    weights, chosen = ref.route(u, w_r, jnp.asarray([0.0, 0.0, 0.4]), 2,
                                True, 2.5)
    assert chosen.tolist() == [[1, 2]]
    np.testing.assert_allclose(weights, [[0, 2.5 * 0.75, 2.5 * 0.25]],
                               atol=1e-6)


def test_an_expert_is_ungated_and_squares_its_relu():
    """One held expert (the router's second), one channel: ``relu(u)^2``
    times the routing weight; the shared expert on every token."""
    b = {"n": jnp.ones(1), "w_r": jnp.asarray([[0.0, 0.0]]),
         "b_r": jnp.asarray([0.0, 1.0]), "w_up": jnp.asarray([[[3.0]]]),
         "w_down": jnp.asarray([[[1.0]]]), "s_up": jnp.asarray([[1.0]]),
         "s_down": jnp.asarray([[10.0]])}
    x = jnp.asarray([[1.0], [-1.0]])
    got, chosen = ref.experts(x, b, 1, False, 1.0, 1, 0.0)
    assert chosen.tolist() == [[1], [1]]
    # weight sigmoid(0) = 1/2; u = +-1: 9/2 + 10 and 0 + 0
    np.testing.assert_allclose((got - x)[:, 0], [14.5, 0.0], atol=1e-6)


# ---- the arithmetic ----------------------------------------------------

def test_parameters_and_states_at_the_published_widths():
    p = arithmetic.layer_parameters(CONFIG)
    assert p["mamba"] == 38_744_896 and p["attention"] == 23_399_040
    assert p["routed_expert"] == 9_977_856
    assert p["expert_outside"] == 344_064 + 128 + 19_955_712 + 2_688
    assert p["vocabulary"] == 2 * 16384 * 2688 + 2688
    assert arithmetic.model_parameters(CONFIG) == 3_422_495_040
    whole = dict(CONFIG, n_routed_experts=128, vocab_size=131072)
    assert round(arithmetic.model_parameters(whole) / 1e9, 2) == 31.58
    assert [arithmetic.layers_of(CONFIG, k) for k in "ME*"] == [23, 23, 6]
    assert arithmetic.mamba_widths(CONFIG) == {
        "inner": 4096, "conv": 6144, "in_proj": 10304, "state": 524_288}
    assert arithmetic.state_bytes_per_row(CONFIG, 2) == 49_082_368 == \
        23 * (2_097_152 + 36_864)
    assert arithmetic.attention_bytes_per_position(CONFIG, 2) == 6144
    assert arithmetic.expert_bytes(2688, 1856, 2) == 19_955_712


def test_the_least_a_tick_moves_and_a_chunk_computes():
    least = arithmetic.tick_bytes(CONFIG, 7.6, 64, 64 * 2000, 2)
    assert least["states"] == 2 * 64 * 49_082_368
    assert least["attention_caches"] == 64 * 2000 * 6144
    assert least["routed_experts"] == pytest.approx(23 * 7.6 * 19_955_712)
    assert least["head"] == 16384 * 2688 * 2
    # ISSUE 58's 16.5 ms at 819 GB/s, the state-space layers' bytes first
    assert sum(least.values()) / 819e9 == pytest.approx(0.01666, rel=1e-3)
    assert (least["states"] + least["mamba_weights"]) / sum(
        least.values()) == pytest.approx(0.59, abs=0.005)
    flops = arithmetic.mamba_chunk_flops(CONFIG, 1024)
    assert flops == 1024 * (2 * 2688 * 10304 + 2 * 4096 * 2688 +
                            6 * 524_288)
    # the recurrence is 4 % of a mixer's operations beside its projections
    assert 6 * 524_288 * 1024 / flops == pytest.approx(0.039, abs=0.002)


# ---- the readers -------------------------------------------------------

def test_the_new_readers(monkeypatch):
    from chipbench import device_parts
    entries = {"jit_decode": {"runs": 10, "unscoped_s": 0.0, "parts": {
        "ssm_mixer": 0.12, "moe": 0.06, "attention": 0.02}},
        "jit_chunk_prefill": {"runs": 4, "unscoped_s": 0.0, "parts": {
            "ssm_mixer": 0.08, "moe": 0.12}}}
    monkeypatch.setattr(device_parts, "program", entries.get)
    steps = 10.0
    obs = {"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": CONFIG, "cache_itemsize": 2, "engine_rows": 64,
           "device_trace": {"program_runs": {"jit_decode": [0.025] * 10}},
           "traced_counters": ({}, {
               "alpa_serving_decode_steps_total": steps,
               "alpa_moe_experts_touched_total": steps * 23 * 7.6,
               "alpa_serving_tokens_total": steps * 64,
               "alpa_serving_decode_positions_total": steps * 64 * 2000}),
           "counters": ({}, {
               'alpa_serving_kv_cache_bytes{kind="ssm"}': 64 * 49_082_368})}
    read = run.metric_reader
    assert read("ssm_decode_share_pct")(obs) == pytest.approx(60.0)
    assert read("ssm_chunk_share_pct")(obs) == pytest.approx(40.0)
    assert read("ssm_state_bytes_per_row")(obs) == 49_082_368
    least = sum(arithmetic.tick_bytes(CONFIG, 7.6, 64, 128000, 2).values())
    assert read("ssm_tick_hbm_roofline_pct")(obs) == pytest.approx(
        100 * least / 819e9 / 0.025)
    assert read("ssm_chunk_roofline_pct")(obs) == pytest.approx(
        100 * 4 * 23 * arithmetic.mamba_chunk_flops(CONFIG, 1024) / 197e12 /
        0.08)
    # what the parent's program gives: no part, no series; and no chip
    monkeypatch.setattr(device_parts, "program", lambda name: None)
    bare = dict(obs, counters=({}, {}), traced_counters=({}, {}))
    for name in NEW:
        assert read(name)(bare) is None
    assert read("ssm_tick_hbm_roofline_pct")(dict(obs, peaks=None)) is None


# ---- the entries -------------------------------------------------------

def test_the_new_entries_follow_what_the_benchmark_had():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("glm-5-1chip.longgen")
    cell = BENCH["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron-3-nano-30b-a3b-1chip", "reasoning-closed128", 1)
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("nemotron-3-nano-30b-a3b-1chip") > \
        configs.index("glm-5-1chip")
    entry = BENCH["configs"][configs.index("nemotron-3-nano-30b-a3b-1chip")]
    assert entry["reduced"] == CONFIG["reduced"] == \
        ["n_routed_experts", "vocab_size"]
    assert entry["source"] in CONFIG["source"]
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    for name in NEW:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert per_layer.index(name) > per_layer.index(
            "verify_select_roofline_pct")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "out_tokens_per_s"
    for name in JOINED:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert entry["workloads"][-1] == CELL or CELL in entry["workloads"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["out_tokens_per_s"]["workloads"]
    assert CELL not in e2e["gap_p99_ms"]["workloads"]


def test_the_cells_files():
    mix = traffic.load_mix("reasoning-closed128")
    assert mix["kind"] == "closed_loop" and mix["clients"] == 128
    assert (mix["pool_size"], mix["sizes_seed"]) == (1024, 20261005)
    assert mix["prompt_len"] == {"median": 384, "sigma": 1.0, "min": 32,
                                 "max": 4096}
    assert mix["output_len"] == {"median": 1024, "sigma": 0.6, "min": 128,
                                 "max": 4096}
    assert (mix["check_requests"], mix["drain_s"], mix["trace_after_s"],
            mix["trace_seconds"]) == (4, 120.0, 5.0, 3.0)
    serve = CONFIG["serve"]
    assert (serve["served_context"], serve["engine_rows"],
            serve["prefill_chunk"]) == (8192, 64, 1024)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= \
        serve["served_context"]
    # the checked prompt of several chunks ends inside a padded one
    assert serve["check_context_over"] == 2 * serve["prefill_chunk"]
    assert CONFIG["driver"] == "serve_ssm" and callable(driver.run)
    settings = driver.reference_settings(CONFIG)
    assert (settings["mamba_heads"], settings["mamba_groups"],
            settings["head_dim"], settings["experts_first"]) == (64, 8, 128, 0)
    assert CONFIG["hybrid_override_pattern"].count("M") == 23
    assert len(CONFIG["hybrid_override_pattern"]) == \
        CONFIG["num_hidden_layers"] == 52
    assert CONFIG["published"] == {"n_routed_experts": 128,
                                   "vocab_size": 131072,
                                   "torch_dtype": "bfloat16"}
    for name in ("logit_margin", "logit_atol", "logit_mean_atol",
                 "min_choice_agreement", "state_rtol", "state_heads_over",
                 "logit_margin_why",
                 "assumed",
                 "deployment", "why_reduced"):
        assert CONFIG[name], name
    toy = run.load_json(run.HERE, "configs", "toy-nemotron.json")
    assert toy["driver"] == "serve_ssm" and toy["dtype"] == "float32"
