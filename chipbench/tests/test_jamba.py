"""What PR 61 added to the benchmark: the plain jamba reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_jamba.py`` at the published widths, the four new readers on
made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had.  The driver's CPU
rehearsal and the program against the reference are
``tests/model/test_jamba.py`` and ``tests/serve/test_s6_state.py``."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_jamba as arithmetic
from chipbench import controls_jamba, run, traffic

ref = run.load_module("references", "jamba_decoder")
driver = run.load_module("drivers", "serve_s6")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "jamba2-3b-1chip.longdoc64k"
CONFIG = run.load_json(run.HERE, "configs", "jamba2-3b-1chip.json")
NEW = ["s6_scan_chunk_share_pct", "s6_scan_hbm_roofline_pct",
       "s6_chunk_roofline_pct", "s6_tick_hbm_roofline_pct"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "decode_head_ms",
          "attention_decode_share_pct", "attention_chunk_share_pct",
          "ssm_decode_share_pct", "ssm_chunk_share_pct",
          "ssm_state_bytes_per_row", "full_cache_bytes_per_position"]


# ---- the reference, by hand -------------------------------------------

def test_the_recurrence_by_hand():
    """One channel with TWO state values of unlike decays, two positions,
    every projection the identity on what it reads and every norm over one
    value (a sign): ``x`` passes the convolution's last tap, ``dt`` =
    softplus(1 * 1 + 0), ``A`` = (-1, -2): ``h_1 = dt x_1 B_1``, ``h_2[n] =
    exp(dt A[n]) h_1[n] + dt x_2 B_2``, ``y = sum_n h[n] C + D x``, then
    the gate and NO norm."""
    dt = math.log(1 + math.e)                     # softplus(1)
    # u (S, 1) -> [x | z] = [u | 2 u]; x -> [dt | B B | C C] = x each
    b = {"n1": jnp.ones(1), "w_in": jnp.asarray([[1.0, 2.0]]),
         "taps": jnp.asarray([[0.0], [1.0]]), "conv_b": jnp.zeros(1),
         "w_x": jnp.ones((1, 5)), "n_dt": jnp.ones(1), "n_b": jnp.ones(2),
         "n_c": jnp.ones(2), "w_dt": jnp.ones((1, 1)), "b_dt": jnp.zeros(1),
         "a_log": jnp.asarray([[0.0], [math.log(2)]]), "d": jnp.ones(1),
         "w_out": jnp.ones((1, 1))}
    x = jnp.asarray([[1.0], [-1.0]])
    # rms(x) = (+1, -1) (eps 0); x after the taps = silu(+-1); the norm of
    # dt over one value is its sign, of B and C over two equal values too
    up, down = 1 / (1 + math.exp(-1)), -1 / (1 + math.e)
    dt_1, dt_2 = dt, math.log(1 + math.exp(-1))   # softplus(+1), (-1)
    h1 = np.asarray([dt_1 * up * 1.0] * 2)
    h2 = np.exp(dt_2 * np.asarray([-1.0, -2.0])) * h1 + dt_2 * down * -1.0
    y = np.asarray([h1.sum() * 1.0 + up, h2.sum() * -1.0 + down])
    gate = np.asarray([2 / (1 + math.exp(-2)), -2 / (1 + math.exp(2))])
    got, kept = ref.Reference(
        {"heads": 1, "eps": 0.0, "query_block": 2, "channel_blocks": 1}
    ).mamba(x, b, jnp.asarray([2, 1]))
    np.testing.assert_allclose((got - x)[:, 0], y * gate, atol=1e-6)
    # the state after two positions and after one, (K, N, D)
    np.testing.assert_allclose(kept[:, :, 0], [h2, h1], atol=1e-6)


def test_every_query_head_reads_the_one_key_value_head():
    """Two query heads of one channel on ONE key/value head, no positions:
    position 1 weighs the two values by softmax(q k)."""
    b = {"n1": jnp.ones(1), "w_q": jnp.asarray([[1.0, -1.0]]),
         "w_k": jnp.ones((1, 1)), "w_v": jnp.asarray([[3.0]]),
         "w_o": jnp.asarray([[1.0], [10.0]])}
    x = jnp.asarray([[1.0], [-1.0]])              # u = (+1, -1)
    got = ref.Reference(
        {"heads": 2, "eps": 0.0, "query_block": 2, "channel_blocks": 1}
    ).attention(x, b, 2)[0] - x
    # position 0 sees itself: v = 3 from both heads
    assert float(got[0, 0]) == pytest.approx(33.0)
    # position 1: q = (-1, +1), k = (+1, -1), v = (+3, -3)
    first = math.exp(-1) / (math.exp(-1) + math.exp(1))
    heads = [3 * first - 3 * (1 - first), 3 * (1 - first) - 3 * first]
    assert float(got[1, 0]) == pytest.approx(heads[0] + 10 * heads[1],
                                             abs=1e-5)


# ---- the arithmetic ----------------------------------------------------

def test_parameters_and_states_at_the_published_widths():
    p = arithmetic.layer_parameters(CONFIG)
    assert p["mamba"] == 41_241_792 and p["attention"] == 13_762_560
    assert p["mlp"] == 62_914_560 and p["norms"] == 5_120
    assert p["mamba"] + p["mlp"] + p["norms"] == 104_161_472
    assert p["attention"] + p["mlp"] + p["norms"] == 76_682_240
    assert p["vocabulary"] == 167_772_160 + 2_560
    assert arithmetic.model_parameters(CONFIG) == 3_029_337_472
    assert (arithmetic.mamba_layers(CONFIG),
            arithmetic.attention_layers(CONFIG)) == (26, 2)
    assert arithmetic.mamba_widths(CONFIG) == {
        "inner": 5120, "x_proj": 192, "state": 81_920}
    assert arithmetic.state_bytes_per_row(CONFIG, 2) == 9_318_400 == \
        26 * (327_680 + 30_720)
    assert arithmetic.attention_bytes_per_position(CONFIG, 2) == 1_024
    assert arithmetic.expert_layers(CONFIG) == 0


def test_the_least_a_tick_moves_and_a_chunk_computes():
    least = arithmetic.tick_bytes(CONFIG, 16, 16 * 12000, 2)
    assert least["states"] == 2 * 16 * 9_318_400
    assert least["attention_caches"] == 16 * 12000 * 1024
    assert least["head"] == 65536 * 2560 * 2
    weights = sum(v for k, v in least.items() if k.endswith("weights"))
    assert weights + least["head"] == 2 * 3_029_337_472 - 2 * 2_560
    # ISSUE 61's 8 ms at 819 GB/s
    assert sum(least.values()) / 819e9 == pytest.approx(0.0080, rel=0.01)
    flops = arithmetic.mamba_chunk_flops(CONFIG, 1024)
    assert flops == 1024 * (2 * 41_123_840 + 6 * 81_920)
    # the least bytes any form of a layer's scan moves: 22.0 MB
    assert arithmetic.scan_chunk_bytes(CONFIG, 1024, 2) == \
        1024 * (2 * 5120 + 192) * 2 + 2 * 327_680 == 22_020_096


# ---- the readers -------------------------------------------------------

def test_the_new_readers(monkeypatch):
    from chipbench import device_parts
    entries = {"jit_decode": {"runs": 10, "unscoped_s": 0.0, "parts": {
        "ssm_mixer": 0.04, "ssm_mixer.scan": 0.02, "mlp": 0.04}},
        "jit_chunk_prefill": {"runs": 4, "unscoped_s": 0.0, "parts": {
            "ssm_mixer": 0.06, "ssm_mixer.scan": 0.04, "mlp": 0.10}}}
    monkeypatch.setattr(device_parts, "program", entries.get)
    steps = 10.0
    obs = {"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": CONFIG, "cache_itemsize": 2, "engine_rows": 16,
           "served_context": 65536,
           "device_trace": {"program_runs": {"jit_decode": [0.010] * 10}},
           "traced_counters": ({}, {
               "alpa_serving_decode_steps_total": steps,
               "alpa_serving_tokens_total": steps * 16,
               "alpa_serving_decode_positions_total": steps * 16 * 12000}),
           "counters": ({}, {
               'alpa_serving_kv_cache_bytes{kind="ssm"}': 16 * 9_318_400,
               'alpa_serving_kv_cache_bytes{kind="full"}':
               16 * 65536 * 1024})}
    read = run.metric_reader
    assert read("s6_scan_chunk_share_pct")(obs) == pytest.approx(20.0)
    assert read("s6_scan_hbm_roofline_pct")(obs) == pytest.approx(
        100 * 4 * 26 * 22_020_096 / 819e9 / 0.04)
    assert read("s6_chunk_roofline_pct")(obs) == pytest.approx(
        100 * 4 * 26 * arithmetic.mamba_chunk_flops(CONFIG, 1024) / 197e12 /
        0.10)
    least = sum(arithmetic.tick_bytes(CONFIG, 16, 192000, 2).values())
    assert read("s6_tick_hbm_roofline_pct")(obs) == pytest.approx(
        100 * least / 819e9 / 0.010)
    # PR 58's readers take the mixer under its scope and its gauge's kind
    assert read("ssm_decode_share_pct")(obs) == pytest.approx(60.0)
    assert read("ssm_chunk_share_pct")(obs) == pytest.approx(50.0)
    assert read("ssm_state_bytes_per_row")(obs) == 9_318_400
    # MiMo's reader takes the folded caches of the ONE key/value head
    assert read("full_cache_bytes_per_position")(obs) == 1024
    # what the parent's program gives: no part, no counters; and no chip
    monkeypatch.setattr(device_parts, "program", lambda name: None)
    bare = dict(obs, counters=({}, {}), traced_counters=({}, {}))
    for name in NEW:
        assert read(name)(bare) is None
    assert read("s6_tick_hbm_roofline_pct")(dict(obs, peaks=None)) is None
    # another model's cell, whose program has the part: nothing either
    monkeypatch.setattr(device_parts, "program", entries.get)
    other = dict(obs, config={"hybrid_override_pattern": "M"})
    for name in NEW[1:]:
        assert read(name)(other) is None


# ---- the entries -------------------------------------------------------

def test_the_new_entries_follow_what_the_benchmark_had():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells[-1] == CELL and len(cells) == 17
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("jamba2-3b-1chip", "longdoc64k-closed32", 1)
    entry = BENCH["configs"][-1]
    assert entry["name"] == "jamba2-3b-1chip"
    assert entry["reduced"] == CONFIG["reduced"] == []
    assert entry["source"] in CONFIG["source"]
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    assert per_layer[-4:] == NEW
    for name in NEW:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["unit"], entry["source"]) == \
            ("out_tokens_per_s", "%", "device_trace")
    for name in JOINED:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert entry["workloads"][-1] == CELL
    joined = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())]
    assert sorted(joined) == sorted(JOINED + NEW)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["out_tokens_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["gap_p99_ms"]["workloads"]


def test_the_cells_files():
    mix = traffic.load_mix("longdoc64k-closed32")
    assert mix["kind"] == "closed_loop" and mix["clients"] == 32
    assert (mix["pool_size"], mix["sizes_seed"]) == (512, 20261006)
    assert mix["prompt_len"] == {"median": 8192, "sigma": 0.9, "min": 1024,
                                 "max": 61440}
    assert mix["output_len"] == {"median": 512, "sigma": 0.6, "min": 64,
                                 "max": 2048}
    assert (mix["check_requests"], mix["trace_after_s"],
            mix["trace_seconds"]) == (4, 20.0, 3.0)
    serve = CONFIG["serve"]
    assert (serve["served_context"], serve["engine_rows"],
            serve["prefill_chunk"]) == (65536, 16, 1024)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= \
        serve["served_context"]
    # one checked context passes where no other cell reaches; one in
    # sixteen of the pool's prompts does, four of the first thirty-two
    assert serve["check_context_over"] == 32768
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert sum(p > 32768 for p, _ in pool) == 30
    assert sum(p + o > 32768 for p, o in pool[:32]) >= 4
    assert CONFIG["driver"] == "serve_s6" and callable(driver.run)
    assert driver.reference_settings(CONFIG) == {
        "heads": 20, "eps": 1e-6, "query_block": 256, "channel_blocks": 4}
    assert CONFIG["published"] == {"torch_dtype": "bfloat16"}
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"]) == \
        (28, 65536)
    for name in ("logit_margin", "logit_atol", "logit_mean_atol",
                 "state_rtol_each", "state_memory_over",
                 "probe_logit_rtol", "probe_kv_rtol",
                 "logit_margin_why", "assumed", "deployment",
                 "why_reduced"):
        assert CONFIG[name], name
    for name in ("layer", "mamba", "attention", "mlp", "dtype", "weights",
                 "served_context"):
        assert CONFIG["assumed"][name], name
    assert sorted(controls_jamba.CONTROLS) == sorted([
        "state_in_bfloat16", "padding_steps", "state_reset",
        "inner_norms_left_out", "one_decay_a_channel", "dt_bias_left_out",
        "one_key_block_short", "tick_key_block_short"])
    for name in controls_jamba.CONTROLS:
        assert name in CONFIG["logit_margin_why"], name
    toy = run.load_json(run.HERE, "configs", "toy-jamba.json")
    assert toy["driver"] == "serve_s6" and toy["dtype"] == "float32"
