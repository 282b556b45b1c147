"""What PR 63 added to the benchmark: the plain evabyte reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_evabyte.py`` at the published widths, the four new readers on
made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each by its place among this PR's own entries (a later
PR appends behind them).  The driver's CPU rehearsal and the program
against the reference are ``tests/model/test_evabyte.py`` and
``tests/serve/test_eva_cache.py``."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_evabyte as arithmetic
from chipbench import controls_evabyte, run, traffic

ref = run.load_module("references", "evabyte_decoder")
driver = run.load_module("drivers", "serve_eva")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "evabyte-1chip.longdoc32k"
CONFIG = run.load_json(run.HERE, "configs", "evabyte-1chip.json")
NEW = ["eva_decode_hbm_roofline_pct", "eva_chunk_roofline_pct",
       "summary_keys_pct", "eva_cache_bytes_per_row"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "decode_head_ms",
          "attention_decode_share_pct", "attention_chunk_share_pct"]


# ---- the reference, by hand -------------------------------------------

def test_a_query_sees_its_window_exactly_and_the_chunks_before_pooled():
    """One head of two channels, windows of 2 in chunks of 2, no rotation
    (theta so large that every angle past the first pair's is nothing, and
    the first pair's is undone by equal turns of q and k only in their
    product: so the keys are made position-free by zero queries).  Five
    positions: position 4 (window 2) sees its own key exactly and the
    summaries of chunks 0 and 1, each pooled by softmax weights off its
    keys; with queries of zero every visible key weighs the same, so the
    output is the plain mean of ``v_4``, ``v~_0`` and ``v~_1``."""
    settings = {"heads": 1, "eps": 0.0, "theta": 1e30, "window": 2,
                "chunk": 2, "pred_heads": 1, "query_block": 1}
    b = {"n1": jnp.zeros(2), "w_q": jnp.zeros((2, 2)),
         "w_k": jnp.eye(2), "w_v": jnp.eye(2), "w_o": jnp.eye(2),
         "mu": jnp.zeros((1, 2)), "phi": jnp.asarray([[math.sqrt(2), 0.0]])}
    # after the norm (eps 0, weight 0 -> gain 1) a row is x / rms(x)
    x = jnp.asarray([[1.0, 1.0], [1.0, -1.0], [-1.0, 1.0], [1.0, 1.0],
                     [-1.0, -1.0]])
    got, (k, v, k_sum, v_sum) = ref.Reference(settings).attention(x, b, 1)
    u = np.asarray(x)                       # rms of every row is 1
    # position 0 turns by angle 0; the keys are the normed rows there
    np.testing.assert_allclose(k[0, 0], u[0], atol=1e-6)
    assert k_sum.shape == (2, 1, 2)
    # mu = 0: the keys' summary is the chunk's plain mean
    np.testing.assert_allclose(k_sum[:, 0], [k[:2, 0].mean(0),
                                             k[2:4, 0].mean(0)], atol=1e-6)
    # phi = (sqrt 2, 0): weights softmax(s k . phi) = softmax(k[:, 0]),
    # s = 2^-0.5
    def pooled(keys, values):
        w = np.exp(np.asarray(keys)[:, 0])
        return (w[:, None] * np.asarray(values)).sum(0) / w.sum()
    np.testing.assert_allclose(v_sum[0, 0], pooled(k[:2, 0], v[:2, 0]),
                               atol=1e-6)
    np.testing.assert_allclose(v_sum[1, 0], pooled(k[2:4, 0], v[2:4, 0]),
                               atol=1e-6)
    out = np.asarray(got - x)
    # queries of zero: every visible key weighs the same
    np.testing.assert_allclose(out[0], v[0, 0], atol=1e-6)
    np.testing.assert_allclose(out[1], (v[0, 0] + v[1, 0]) / 2, atol=1e-6)
    # position 2 opens window 1: its own key and chunk 0's summary
    np.testing.assert_allclose(out[2], (v[2, 0] + v_sum[0, 0]) / 2,
                               atol=1e-6)
    np.testing.assert_allclose(
        out[3], (v[2, 0] + v[3, 0] + v_sum[0, 0]) / 3, atol=1e-6)
    np.testing.assert_allclose(
        out[4], (v[4, 0] + v_sum[0, 0] + v_sum[1, 0]) / 3, atol=1e-6)


def test_the_norm_has_a_unit_offset_and_the_heads_lie_side_by_side():
    x = jnp.asarray([[3.0, 4.0]])
    np.testing.assert_allclose(
        ref.rms(x, jnp.asarray([0.0, 1.0]), 0.0),
        [[3 / math.sqrt(12.5), 2 * 4 / math.sqrt(12.5)]], rtol=1e-6)
    logits = ref.head(x, jnp.zeros(2), jnp.arange(12.0).reshape(2, 6), 0.0,
                      pred_heads=2)
    assert logits.shape == (1, 2, 3)
    u = np.asarray(x[0]) / math.sqrt(12.5)
    np.testing.assert_allclose(
        logits[0, 1], u @ np.arange(12.0).reshape(2, 6)[:, 3:], rtol=1e-6)


# ---- the arithmetic ----------------------------------------------------

def test_parameters_and_caches_at_the_published_widths():
    assert arithmetic.layer_parameters(CONFIG) == 202_391_552
    assert arithmetic.model_parameters(CONFIG) == 1_630_932_992
    assert arithmetic.model_parameters(
        dict(CONFIG, **CONFIG["published"])) == 6_488_330_240
    assert arithmetic.row_bytes(CONFIG, 2) == 16_384
    assert arithmetic.cache_bytes_per_row_a_layer(CONFIG, 2, 32768) == \
        67_108_864
    # the keys a query at 30,000 sees
    assert arithmetic.keys_seen(CONFIG, 30000) == (1329, 1792)
    assert arithmetic.keys_seen(CONFIG, 2047) == (2048, 0)
    assert arithmetic.keys_seen(CONFIG, 2048) == (1, 128)
    assert arithmetic.attention_flops_a_pair(CONFIG) == 16_384
    # a first chunk: causal inside the window, no summary
    assert arithmetic.chunk_pairs(CONFIG, 1024) == (1024 * 1025 // 2, 0)
    assert arithmetic.chunk_pairs(CONFIG, 4096) == (
        2 * 2048 * 2049 // 2, 128 * 2048)
    assert arithmetic.expert_layers(CONFIG) == 0


# ---- the readers -------------------------------------------------------

def test_the_new_readers(monkeypatch):
    from chipbench import device_parts
    entries = {"jit_decode": {"runs": 10, "unscoped_s": 0.0, "parts": {
        "attention": 0.03, "attention.summaries": 0.005,
        "attention.cache_write": 0.005, "mlp": 0.04}},
        "jit_chunk_prefill": {"runs": 4, "unscoped_s": 0.0, "parts": {
            "attention": 0.016, "attention.summaries": 0.004,
            "mlp": 0.10}}}
    monkeypatch.setattr(device_parts, "program", entries.get)
    steps = 20.0
    exact, pooled = steps * 16 * 1000, steps * 16 * 800
    pairs = arithmetic.chunk_pairs(CONFIG, 15 * 1024)
    obs = {"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": CONFIG, "cache_itemsize": 2, "engine_rows": 16,
           "traced_counters": ({}, {
               "alpa_serving_decode_steps_total": steps,
               'alpa_serving_eva_keys_total{kind="exact"}': exact,
               'alpa_serving_eva_keys_total{kind="summary"}': pooled}),
           "counters": ({}, {
               "alpa_serving_prefill_padded_tokens_total": 15 * 1024,
               'alpa_serving_eva_chunk_pairs_total{kind="exact"}': pairs[0],
               'alpa_serving_eva_chunk_pairs_total{kind="summary"}':
               pairs[1],
               'alpa_serving_kv_cache_bytes{kind="window"}':
               16 * 268_435_456,
               'alpa_serving_kv_cache_bytes{kind="summary"}':
               16 * 268_435_456})}
    read = run.metric_reader
    assert read("eva_decode_hbm_roofline_pct")(obs) == pytest.approx(
        100 * 10 * 8 * 16 * 1800 * 16_384 / 819e9 / 0.04)
    assert read("eva_chunk_roofline_pct")(obs) == pytest.approx(
        100 * 4 * 8 * sum(pairs) / 15 * 16_384 / 197e12 / 0.02)
    assert read("summary_keys_pct")(obs) == pytest.approx(100 * 800 / 1800)
    assert read("eva_cache_bytes_per_row")(obs) == 536_870_912
    # what the parent's program gives: no part, no counters; and no chip
    monkeypatch.setattr(device_parts, "program", lambda name: None)
    bare = dict(obs, counters=({}, {}), traced_counters=({}, {}))
    for name in NEW:
        assert read(name)(bare) is None
    assert read("eva_decode_hbm_roofline_pct")(dict(obs, peaks=None)) is None
    # another model's cell, whose program has the part: nothing either
    monkeypatch.setattr(device_parts, "program", entries.get)
    other = dict(obs, config={"hybrid_override_pattern": "M"},
                 counters=({}, {}), traced_counters=({}, {}))
    for name in NEW:
        assert read(name)(other) is None


# ---- the entries -------------------------------------------------------

def test_the_new_entries_follow_what_the_benchmark_had():
    cells = [c["name"] for c in BENCH["workloads"]]
    at = cells.index(CELL)
    assert at == 17 and cells[16] == "jamba2-3b-1chip.longdoc64k"
    assert sum(c["chips"] == 4 for c in BENCH["workloads"][:18]) == 1
    cell = BENCH["workloads"][at]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("evabyte-1chip", "longdoc32k-closed32", 1)
    names = [c["name"] for c in BENCH["configs"]]
    entry = BENCH["configs"][names.index("evabyte-1chip")]
    assert names.index("evabyte-1chip") == names.index("jamba2-3b-1chip") + 1
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] in CONFIG["source"]
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    first = per_layer.index(NEW[0])
    assert per_layer[first:first + 4] == NEW
    assert per_layer[first - 1] == "s6_tick_hbm_roofline_pct"
    for name in NEW:
        entry = BENCH["per_layer"][per_layer.index(name)]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "out_tokens_per_s"
    for name in JOINED:
        workloads = BENCH["per_layer"][per_layer.index(name)]["workloads"]
        assert workloads[workloads.index(CELL) - 1] == \
            "jamba2-3b-1chip.longdoc64k"
    joined = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())]
    assert sorted(joined) == sorted(JOINED + NEW)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["out_tokens_per_s"]["workloads"]
    assert CELL not in e2e["gap_p99_ms"]["workloads"]


def test_the_cells_files():
    mix = traffic.load_mix("longdoc32k-closed32")
    assert mix["kind"] == "closed_loop" and mix["clients"] == 32
    assert (mix["pool_size"], mix["sizes_seed"]) == (512, 20261008)
    assert mix["prompt_len"] == {"median": 12288, "sigma": 0.7, "min": 1024,
                                 "max": 28672}
    assert mix["output_len"] == {"median": 1024, "sigma": 0.6, "min": 128,
                                 "max": 4096}
    assert (mix["check_requests"], mix["trace_after_s"],
            mix["trace_seconds"]) == (4, 15.0, 3.0)
    serve = CONFIG["serve"]
    assert (serve["served_context"], serve["engine_rows"],
            serve["prefill_chunk"]) == (32768, 16, 1024)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == \
        serve["served_context"]
    assert serve["check_context_over"] == 24576
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert max(p + o for p, o in pool) == 32768
    # twelve windows of summaries under one query, and a decode that
    # crosses a window's edge: both among the stream's first requests
    assert sum(p + o > 24576 for p, o in pool[:32]) >= 4
    assert sum((p + o - 2) // 2048 > (p - 1) // 2048
               for p, o in pool[:32]) >= 8
    assert CONFIG["driver"] == "serve_eva" and callable(driver.run)
    assert driver.reference_settings(CONFIG) == {
        "heads": 32, "eps": 1e-5, "theta": 100000, "window": 2048,
        "chunk": 16, "pred_heads": 8, "query_block": 256}
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "torch_dtype": "bfloat16"}
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["num_attention_heads"], CONFIG["num_pred_heads"]) == \
        (8, 320, 32, 8)
    for name in driver.LIMITS + ("logit_margin_why", "assumed",
                                 "deployment", "why_reduced"):
        assert CONFIG[name], name
    for name in ("layer", "attention", "pooling", "rotary", "mlp", "dtype",
                 "weights", "unread"):
        assert CONFIG["assumed"][name], name
    assert sorted(controls_evabyte.CONTROLS) == sorted([
        "summaries_left_out", "mean_pooling", "mu_phi_swapped",
        "own_window_summaries", "sliding_window", "prefill_summary_frozen",
        "padding_pooled", "one_window_short", "pooling_before_rotary",
        "stream_in_bfloat16", "unit_offset_left_out"])
    for name in controls_evabyte.CONTROLS:
        assert name in CONFIG["logit_margin_why"], name
    toy = run.load_json(run.HERE, "configs", "toy-evabyte.json")
    assert toy["driver"] == "serve_eva" and toy["dtype"] == "float32"
    assert (toy["window_size"], toy["chunk_size"], toy["num_pred_heads"],
            toy["vocab_size"]) == (64, 4, 2, 320)
