"""What PR 38 added to the benchmark: the plain lfm2_moe reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_lfm2.py`` at the published widths, the three readers on
made-up tables, the cell's files against ISSUE 38 and the catalog row, and
the toy cell through ``drivers/serve_hybrid.py`` on the CPU, sound and
under the four controls the cell's limits are set against.
(``test_contract.py::test_cell_files_exist`` knows the drivers of its day
and fails for the new cell with a KeyError, as for every cell since PR 26;
it is not this PR's to edit, so the same checks are made here.)"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (arithmetic_lfm2, controls_lfm2, observe, run,
                       traffic)

ref = run.load_module("references", "lfm2_moe_decoder")
driver = run.load_module("drivers", "serve_hybrid")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "lfm2-8b-a1b-1chip.chat"
CONFIG = run.load_json(run.HERE, "configs", "lfm2-8b-a1b-1chip.json")
TOY = run.load_json(run.HERE, "configs", "toy-lfm2.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["conv_decode_share_pct", "conv_chunk_roofline_pct",
       "conv_state_bytes_per_row"]
JOINED = ["tick_ms", "engine_occupancy_pct", "tick_host_ms",
          "hbm_peak_gb.serve", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct", "prefill_chunk_ms",
          "decode_head_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def sigmoid(x):
    return 1 / (1 + math.exp(-x))


def silu(x):
    return x * sigmoid(x)


# ---- the reference, by hand ---------------------------------------------

def conv_block(taps):
    """Hidden 1: ``W_in`` = (1, 2, 3) so that B = u, C = 2u, X = 3u and
    g = 3 u^2; ``W_out`` = 1; the norm's weight 1."""
    return {"n_op": np.ones(1, np.float32),
            "w_in": np.array([[1.0, 2.0, 3.0]], np.float32),
            "taps": np.asarray(taps, np.float32).reshape(-1, 1),
            "w_out": np.ones((1, 1), np.float32)}


def test_the_short_convolution_by_hand():
    """x = (1, -1, 1, -1) at hidden 1 and a vanishing eps: the norm gives
    u = x, g = 3 u^2 = 3 at every position, C = 2u.  Taps (k0, k1, k2) =
    (1, 10, 100) meet (g_{t-2}, g_{t-1}, g_t) with zeros before the
    sequence: c = 300, 330, 333, 333; y = C c = 600, -660, 666, -666, and
    the layer returns x + y."""
    x = jnp.asarray([[1.0], [-1.0], [1.0], [-1.0]])
    out = ref.short_conv(x, conv_block([1, 10, 100]), 1e-12)
    np.testing.assert_allclose(out[:, 0], [601, -661, 667, -667], rtol=1e-6)
    # tap 2 alone is the position itself, tap 0 alone two positions back
    out = ref.short_conv(x, conv_block([0, 0, 1]), 1e-12)
    np.testing.assert_allclose(out[:, 0], [7, -7, 7, -7], rtol=1e-6)
    out = ref.short_conv(x, conv_block([1, 0, 0]), 1e-12)
    np.testing.assert_allclose(out[:, 0], [1, -1, 7, -7], rtol=1e-6)


def test_a_later_position_changes_no_earlier_one():
    rng = np.random.default_rng(0)
    block = {"n_op": np.ones(4, np.float32),
             "w_in": rng.normal(size=(4, 12)).astype(np.float32),
             "taps": rng.normal(size=(3, 4)).astype(np.float32),
             "w_out": rng.normal(size=(4, 4)).astype(np.float32)}
    x = rng.normal(size=(6, 4)).astype(np.float32)
    whole = np.asarray(ref.short_conv(jnp.asarray(x), block, 1e-5))
    head = np.asarray(ref.short_conv(jnp.asarray(x[:4]), block, 1e-5))
    np.testing.assert_allclose(whole[:4], head, rtol=1e-6)
    # and position t sees t - 2, not t - 3
    moved = x.copy()
    moved[0] += 1.0
    after = np.asarray(ref.short_conv(jnp.asarray(moved), block, 1e-5))
    assert np.abs(after[2] - whole[2]).max() > 1e-3
    np.testing.assert_allclose(after[3:], whole[3:], rtol=1e-6)


def test_routing_chooses_with_the_bias_and_weighs_without_it():
    """Scores sigmoid(2, 1, 0, -1); the bias lifts expert 3 over expert 1:
    the two chosen are 0 and 3, and their weights are their own scores
    over the scores' sum + 1e-6, the bias in neither."""
    u = jnp.eye(4)[:1]
    w_r = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6])
    weights, chosen = ref.route(u, w_r, bias, 2, True, 1.0)
    assert sorted(chosen[0].tolist()) == [0, 3]
    s0, s3 = sigmoid(2.0), sigmoid(-1.0)
    np.testing.assert_allclose(
        weights[0], [s0 / (s0 + s3 + 1e-6), 0, 0, s3 / (s0 + s3 + 1e-6)],
        rtol=1e-6)
    # without the bias expert 1 is chosen; without the renormalisation the
    # weights are the scores, times the scale
    weights, chosen = ref.route(u, w_r, 0 * bias, 2, False, 2.0)
    assert sorted(chosen[0].tolist()) == [0, 1]
    np.testing.assert_allclose(weights[0], [2 * s0, 2 * sigmoid(1.0), 0, 0],
                               rtol=1e-6)


def test_one_token_through_a_whole_model_by_hand():
    """Hidden 2, vocabulary 3, one conv layer over two experts of width 1,
    one a token.  ids (0): x = (1, 0), its norm (r2, 0) with r2 = sqrt 2.
    ``W_in`` copies channel 0 into B, C and X of channel 0: g = (2, 0);
    alone in its sequence only tap 2 (= 1) meets it: c = (2, 0), y =
    C c = (2 r2, 0): x = (1 + 2 r2, 0).  The norm is (r2, 0) again; the
    router is the identity: scores (sigmoid(r2), 1/2), expert 0, weight
    s / (s + 1e-6); expert e gates and lifts channel e into channel e:
    silu(r2) r2.  The final norm of (x0, 0) is (r2, 0) whatever x0, and
    the tied head gives its products with the table's rows."""
    r2 = math.sqrt(2.0)
    one = np.ones(2, np.float32)
    w_in = np.zeros((2, 6), np.float32)
    w_in[0, [0, 2, 4]] = 1.0
    block = {"n_op": one, "n_ffn": one, "w_in": w_in,
             "taps": np.array([[5, 5], [7, 7], [1, 1]], np.float32),
             "w_out": np.eye(2, dtype=np.float32),
             "w_r": np.eye(2, dtype=np.float32),
             "b_r": np.zeros(2, np.float32),
             "w_gate_up": np.stack([[[1, 1], [0, 0]], [[0, 0], [1, 1]]]
                                   ).astype(np.float32),
             "w_down": np.eye(2, dtype=np.float32).reshape(2, 1, 2)}
    weights = {"wte": np.array([[1, 0], [0, 1], [1, 1]], np.float32),
               "blocks": [block], "wf": one}
    r = ref.Reference({"head_dim": 2, "norm_eps": 1e-12,
                       "rope_theta": 1e6, "num_experts_per_tok": 1,
                       "norm_topk_prob": True,
                       "routed_scaling_factor": 1.0, "query_block": 1})
    hidden, chosen = r.hidden(weights, np.array([0]))
    s = sigmoid(r2)
    np.testing.assert_allclose(
        hidden[0], [1 + 2 * r2 + s / (s + 1e-6) * silu(r2) * r2, 0],
        rtol=1e-6)
    assert chosen[0].tolist() == [[0]]
    np.testing.assert_allclose(r.logits(weights, np.array([0]))[0],
                               [r2, 0, r2], rtol=1e-6)
    # the loss of predicting token 2: two logits of r2 and one of 0
    assert r.lm_loss(weights, np.array([[0]]), np.array([[2]])) == \
        pytest.approx(math.log(2 + math.exp(-r2)), rel=1e-6)


# ---- the arithmetic ---------------------------------------------------------

def test_arithmetic_at_the_published_widths():
    """ISSUE 38's figures, to the parameter."""
    p = arithmetic_lfm2.layer_parameters(CONFIG)
    assert p["conv"] == 12_582_912 + 4_194_304 + 6_144 == 16_783_360
    assert p["attention"] == 10_485_888
    assert p["dense_mlp"] == 44_040_192
    assert p["routed_expert"] == 11_010_048
    assert 32 * p["routed_expert"] == 352_321_536
    assert p["vocabulary"] == 134_217_728 + 2048
    # what the program's parameter tree counts (jax.eval_shape, PR 38)
    assert arithmetic_lfm2.model_parameters(CONFIG) == 4_606_249_728
    whole = {**CONFIG, **{k: CONFIG["published"][k] for k in
                          CONFIG["reduced"]}}
    assert arithmetic_lfm2.model_parameters(whole) == pytest.approx(
        8.34e9, rel=2e-3)
    assert arithmetic_lfm2.expert_bytes(2048, 1792, 2) == 22_020_096
    # a row's state: two positions of 2,048 in bfloat16 a layer, ten layers
    assert arithmetic_lfm2.conv_state_bytes(2048, 3, 2) == 8_192
    caches = arithmetic_lfm2.cache_bytes(CONFIG, 64, 8192, 2)
    assert caches == {"full": 3 * 64 * 8192 * 2_048, "conv": 64 * 81_920}
    assert caches["full"] == 3_221_225_472
    assert arithmetic_lfm2.attention_position_bytes(CONFIG, 2) == 2_048
    # a tick's conv mixer: its weights, 64 rows' state both ways, 64 rows
    # in and out
    assert arithmetic_lfm2.conv_mixer_bytes(2048, 3, 64, 2) == \
        2 * 16_783_360 + 2 * 64 * 8_192 + 2 * 64 * 4_096
    assert arithmetic_lfm2.conv_mixer_flops(2048, 3, 1) == \
        2 * 16_777_216 + 8 * 2048
    tick = arithmetic_lfm2.decode_tick_bytes(CONFIG, 64, 8192, 32, 2)
    assert tick["routed_experts"] == 12 * 32 * 22_020_096      # 8.46 GB
    assert tick["conv_mixers"] == 10 * 2 * 16_783_360          # 0.34 GB
    assert tick["head"] == 268_435_456
    assert sum(tick.values()) == 12_444_100_096                 # 12.4 GB


# ---- the readers ------------------------------------------------------------

class _Capture:
    def __init__(self, table):
        self.table = table

    def device_time(self):
        return self.table


def table_with(parts, runs=100, chunk_parts=None, chunks=40):
    def entry(parts, runs):
        return {"runs": runs, "run_s": [sum(parts.values()) / runs] * runs,
                "parts": parts, "mixed_s": 0.0, "inherited_s": 0.0,
                "unscoped_s": parts.get("unscoped", 0.0)}
    programs = {"jit_decode": entry(parts, runs)}
    if chunk_parts:
        programs["jit_chunk_prefill"] = entry(chunk_parts, chunks)
    return {"programs": {0: programs},
            "busy_s": {0: sum(parts.values())}, "window_us": (0.0, 3e6)}


def obs_with(conv_gauge=None):
    after = {"alpa_serving_decode_steps_total": 100.0}
    if conv_gauge is not None:
        after['alpa_serving_kv_cache_bytes{kind="conv"}'] = conv_gauge
    return {"peaks": PEAKS, "config": CONFIG, "engine_rows": 64,
            "counters": ({}, after),
            "conv_flops_per_chunk": 10 * arithmetic_lfm2.conv_mixer_flops(
                2048, 3, 1024)}


def test_the_new_readers(monkeypatch):
    from alpa_tpu.telemetry import trace
    parts = {"short_conv": 0.06, "moe": 0.9, "moe.grouped_matmul": 0.3,
             "attention": 0.6, "head": 0.04, "norm": 0.1}
    chunk = {"short_conv": 0.08, "moe": 0.1, "moe.grouped_matmul": 0.7,
             "attention": 0.3, "head": 0.06}
    monkeypatch.setattr(trace, "last_capture",
                        lambda: _Capture(table_with(parts, 100, chunk, 40)))
    obs = obs_with(conv_gauge=64 * 81_920)
    assert run.metric_reader("conv_decode_share_pct")(obs) == \
        pytest.approx(100 * 0.06 / 2.0)
    # ten mixers over 1,024 positions are 343.8 GFLOP a chunk: 1.745 ms at
    # 197 TFLOP/s of the 2 ms a chunk
    assert run.metric_reader("conv_chunk_roofline_pct")(obs) == \
        pytest.approx(100 * 40 * 343_765_155_840 / 197e12 / 0.08, rel=1e-6)
    assert 85 < run.metric_reader("conv_chunk_roofline_pct")(obs) < 100
    assert run.metric_reader("conv_state_bytes_per_row")(obs) == 81_920


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_layer(name,
                                                              monkeypatch):
    """The other cells' programs, and the parent's: no such part and no
    such series (or one that reads 0), so no number and no error."""
    from alpa_tpu.telemetry import trace
    read = run.metric_reader(name)
    monkeypatch.setattr(trace, "last_capture", lambda: None)
    assert read(obs_with()) is None
    assert read(obs_with(conv_gauge=0.0)) is None
    monkeypatch.setattr(trace, "last_capture", lambda: _Capture(
        table_with({"moe": 0.9, "attention": 0.6, "head": 0.04})))
    assert read(obs_with(conv_gauge=0.0)) is None
    assert read({"peaks": None, "counters": None, "device_trace": None,
                 "engine_rows": 16}) is None


# ---- the entries --------------------------------------------------------------

def test_the_cells_files():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    cell = cells[CELL]
    assert cell == {
        "name": CELL, "config": "lfm2-8b-a1b-1chip",
        "traffic": "chat-closed128", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert CONFIG["name"] == cell["config"]
    assert CONFIG["driver"] == "serve_hybrid" and callable(driver.run)
    assert callable(ref.Reference) and callable(ref.weights_from_program)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "chipbench/configs/lfm2-8b-a1b-1chip.json"
    assert entry["source"] == ("https://huggingface.co/LiquidAI/"
                               "LFM2-8B-A1B/blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert len(entry["why"]) <= 200
    # one leading dense conv layer, then three whole periods
    assert CONFIG["layer_types"] == ["conv"] + 3 * [
        "conv", "conv", "conv", "full_attention"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == \
        (13, 1)
    assert CONFIG["published"]["num_hidden_layers"] == 24 == \
        len(CONFIG["published"]["layer_types"])
    assert CONFIG["published"]["num_dense_layers"] == 2
    # the period the cut keeps is the published one: layers 3-6, 7-10, ...
    assert CONFIG["published"]["layer_types"][3:15] == \
        CONFIG["layer_types"][1:]
    assert CONFIG["serve"] == {
        "served_context": 8192, "engine_rows": 64, "prefill_chunk": 1024,
        "check_context_over": CONFIG["serve"]["check_context_over"],
        "check_context_under": CONFIG["serve"]["check_context_under"]}
    for name in ("why_reduced", "deployment", "logit_margin_why"):
        assert len(CONFIG[name]) > 200, name
    assert {"head", "hidden_act", "head_dim", "qk_norm", "positions",
            "router", "router_bias", "dtype", "weights",
            "served_context"} <= set(CONFIG["assumed"])
    mix = traffic.load_mix(cell["traffic"])
    # ISSUE 38's table, letter for letter
    assert {k: v for k, v in mix.items() if k != "why"} == {
        "kind": "closed_loop", "clients": 128, "pool_size": 1024,
        "sizes_seed": 20261201,
        "prompt_len": {"median": 512, "sigma": 1.0, "min": 32,
                       "max": 4096},
        "output_len": {"median": 128, "sigma": 0.7, "min": 16,
                       "max": 1024},
        "check_requests": 4, "drain_s": 60.0, "trace_after_s": 5.0,
        "trace_seconds": 3.0}
    # every request fits the served context, its prompt padded to chunks
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert max(-(-p // 1024) * 1024 + o for p, o in pool) <= 8192
    # about three prompts in four end inside their first chunk, and some
    # span several, so that the state crosses chunk edges
    assert 0.7 < np.mean([p <= 1024 for p, _ in pool]) < 0.8
    assert sum(p > 2048 for p, _ in pool) > 50
    # among the first requests sent: a context under and one over the
    # limits the check asks for; the long one spans more than two chunks
    first = [p + o for p, o in pool[:mix["clients"]]]
    assert min(first) < CONFIG["serve"]["check_context_under"] <= 1024
    assert max(first) > CONFIG["serve"]["check_context_over"] >= \
        2 * 1024 + mix["output_len"]["max"]


def test_the_file_keeps_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` is in the cell's file
    under the same key, but the three keys it lists as reduced."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"])
    assert {k: row["config"][k] for k in differ} == {
        k: CONFIG["published"][k] for k in differ}
    assert row["source_url"] == next(
        c for c in BENCH["configs"]
        if c["name"] == CONFIG["name"])["source"]


def test_the_new_entries_are_additions():
    names = [c["name"] for c in BENCH["workloads"]]
    assert names[-1] == CELL and len(names) == 11
    assert [c["name"] for c in BENCH["configs"]][-1] == "lfm2-8b-a1b-1chip"
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    assert per_layer[-3:] == NEW
    layers = {m["layer"] for m in BENCH["per_layer"][:-3]}
    for m in BENCH["per_layer"][-3:]:
        assert m["layer"] in layers and m["workloads"] == [CELL]
        assert m["moves"] == "out_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    assert [m["source"] for m in BENCH["per_layer"][-3:]] == [
        "device_trace", "device_trace", "program_counter"]
    assert BENCH["per_layer"][-2]["better"] == "higher"
    # the cell's name is appended to the lists it joined
    for name in JOINED + ["out_tokens_per_s"]:
        m = next(m for g in ("end_to_end", "per_layer") for m in BENCH[g]
                 if m["name"] == name)
        assert m["workloads"][-1] == CELL, name
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"out_tokens_per_s", "setup_s", "xla_compile_s",
            "state_init_s"} | set(JOINED) | set(NEW) <= reported
    # a chunked cell's tail has no bounded home yet (PERF.md, section 7)
    assert "gap_p99_ms" not in reported


# ---- the driver, on the CPU ----------------------------------------------------

def _toy_context(tmp_path):
    return run.Context(
        cell={"name": "toy-lfm2.chat", "config": "toy-lfm2",
              "traffic": "toy-chat", "chips": 1},
        config=TOY, mix=traffic.load_mix("toy-chat"), seed=2147483659,
        seconds=3.0, trace=2, rehearsal=True, spans=observe.Spans(),
        compile_events=observe.CompileEvents(),
        trace_dir=str(tmp_path / "trace"))


@pytest.mark.parametrize("control", [None, "a", "b", "c", "d"],
                         ids=["sound", "state-in-float8",
                              "state-of-the-chunks-end",
                              "one-expert-left-out", "matrices-in-float8"])
def test_driver_runs_the_toy_cell(tmp_path, monkeypatch, control):
    """``chipbench/drivers/serve_hybrid.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' biases, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the replay and the check against the reference.
    Sound it reads correct; under each of the four controls the cell's
    limits are set against (``chipbench/controls_lfm2.py``) it serves
    every request in full, plausible tokens, and reads not correct."""
    if control is not None:
        controls_lfm2.CONTROLS[control](monkeypatch.setattr)
    obs = driver.run(_toy_context(tmp_path))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4, checks
    assert checks["checked_requests"] == 6
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["compiles_in_window"] == 0
    # the long one crossed chunk edges and ended inside a padded chunk
    assert max(checks["checked_prompts"]) > 3 * 4
    assert any(p % 4 for p in checks["checked_prompts"])
    if control is not None:
        assert not obs["correct"], checks
        return
    assert obs["correct"], checks
    assert checks["over_margin"] == 0
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 4
    assert obs["decode_trace"] == {}    # a CPU trace has no TPU plane
    obs.update(peaks=None, config=TOY, seconds=3.0)
    # four conv layers of two positions of 64 in bfloat16
    assert run.metric_reader("conv_state_bytes_per_row")(obs) == \
        4 * 2 * 64 * 2
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 8
    for name in ("conv_decode_share_pct", "conv_chunk_roofline_pct",
                 "decode_head_ms", "prefill_chunk_ms"):
        assert run.metric_reader(name)(obs) is None
    traced = {**obs, **obs["traced"]}
    assert run.metric_reader("tick_ms")(traced) > 0
    assert run.metric_reader("engine_occupancy_pct")(traced) > 50
    spans = [s for s in traced["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 4)
        for s in spans)


def test_the_depth_reading_at_the_toy_size(capsys):
    """``controls_lfm2 depth``: a line a depth; the float32 program on the
    leading dense layer is the reference's, the bfloat16 program is not,
    and at the toy's depth the program chooses the reference's experts."""
    assert controls_lfm2.main(["depth", "--config", "toy-lfm2", "--layers",
                               "3", "--positions", "16"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [line["depth"] for line in lines] == [1, 2, 3]
    assert lines[0]["f32_mean_diff"] < 1e-5 < lines[0]["bf16_mean_diff"]
    assert lines[0]["bf16_mean_diff"] < lines[2]["bf16_mean_diff"] < 0.05
    assert lines[2]["bf16_agreement"] > 0.9
