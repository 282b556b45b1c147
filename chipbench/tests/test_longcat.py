"""What PR 44 added to the benchmark: the plain longcat_flash reference
against cases small enough to compute by hand, the arithmetic of
``arithmetic_longcat.py`` at the published widths, the three new readers on
made-up ``obs``, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had (by position relative to
the accepted entries, so that the next PR's appends leave these checks
standing)."""
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_longcat, run, traffic

ref = run.load_module("references", "longcat_flash_decoder")
driver = run.load_module("drivers", "serve_scmoe")
mla = run.load_module("drivers", "serve_mla")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "longcat-flash-1chip.agent"
CONFIG = run.load_json(run.HERE, "configs", "longcat-flash-1chip.json")
NEW = ["zero_expert_picks_pct", "scmoe_decode_hbm_roofline_pct",
       "shortcut_moe_chunk_share_pct"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct", "moe_local_rows_pct",
          "kv_cache_bytes_per_position", "attention_chunk_share_pct",
          "decode_head_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
R2 = math.sqrt(2)


def silu(x):
    return x / (1 + math.exp(-x))


# ---- the reference, by hand -------------------------------------------

def test_pairs_turn_in_place():
    """Position 1, one pair, theta anything: (1, 0) turns by one radian
    and stays in its two channels; position 0 does not turn."""
    x = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]])
    np.testing.assert_allclose(
        ref.rotate(x, 1e7)[:, 0], [[1, 0], [math.cos(1), math.sin(1)]],
        atol=1e-6)
    # two pairs: the second turns at theta^(-1/2)
    x = jnp.asarray([[[0.0, 0.0, 1.0, 0.0]], [[0.0, 0.0, 1.0, 0.0]]])
    slow = 1e7 ** -0.5
    np.testing.assert_allclose(
        ref.rotate(x, 1e7)[1, 0], [0, 0, math.cos(slow), math.sin(slow)],
        atol=1e-6)


def test_the_choice_takes_the_bias_and_the_weights_do_not():
    """Two experts and two identity experts, logits (0, 0, 0, 0): p = 1/4
    each; the bias picks outputs 1 and 3 (ties go to the lower index, so
    without it 0 and 1), and the weights are scale x p, the bias nowhere
    in them."""
    u = jnp.asarray([[1.0, 0.0]])
    w_r = jnp.zeros((2, 4))
    weights, chosen = ref.route(u, w_r, jnp.zeros((4,)), 2, 6.0)
    np.testing.assert_array_equal(chosen, [[0, 1]])
    weights, chosen = ref.route(u, w_r, jnp.asarray([0, .5, 0, .25]), 2, 6.0)
    np.testing.assert_array_equal(chosen, [[1, 3]])
    np.testing.assert_allclose(weights, [[0, 1.5, 0, 1.5]])
    # largest p + b first
    _, chosen = ref.route(u, jnp.asarray([[0., 0, 1, 0], [0, 0, 0, 0]]),
                          jnp.asarray([0, .5, 0, .25]), 3, 6.0)
    np.testing.assert_array_equal(chosen[0, :2], [1, 2])


def branch_weights(held=(0, 1)):
    """hidden 2, two experts of width 1 and one identity expert; the router
    is the identity onto the experts and scores the identity expert 0."""
    gate_up = np.array([[[1.0, 1.0], [0.0, 0.0]],     # expert 0 reads ch. 0
                        [[0.0, 0.0], [1.0, 1.0]]])    # expert 1 reads ch. 1
    down = np.array([[[1.0, 0.0]], [[0.0, 1.0]]])     # and writes it back
    return {"w_r": jnp.asarray([[1.0, 0, 0], [0, 1.0, 0]]),
            "b_r": jnp.zeros((3,)),
            "w_gate_up": jnp.asarray(gate_up[list(held)]),
            "w_down": jnp.asarray(down[list(held)])}


def test_the_branch_by_hand():
    """u = (r2, 0), (0, r2), two picks a token of (expert 0, expert 1,
    identity): p = softmax(r2, 0, 0): token 0 takes expert 0 (p0) and, of
    the tied rest, the lower index, expert 1 (p1); with a bias on the
    identity output it takes expert 0 and the identity expert, which adds
    ``2 p1 u`` and multiplies nothing."""
    u = jnp.asarray([[R2, 0.0], [0.0, R2]])
    p0 = math.exp(R2) / (math.exp(R2) + 2)
    p1 = 1 / (math.exp(R2) + 2)
    act = silu(R2) * R2
    out, chosen = ref.scmoe(u, branch_weights(), 2, 2.0, 2, 0)
    np.testing.assert_array_equal(chosen, [[0, 1], [1, 0]])
    # expert 1 on token 0 reads channel 1, which is 0: it gives nothing
    np.testing.assert_allclose(out, [[2 * p0 * act, 0], [0, 2 * p0 * act]],
                               rtol=1e-6)
    b = dict(branch_weights(), b_r=jnp.asarray([0.0, 0.0, 0.5]))
    out, chosen = ref.scmoe(u, b, 2, 2.0, 2, 0)
    np.testing.assert_array_equal(chosen, [[0, 2], [1, 2]])
    np.testing.assert_allclose(
        out, [[2 * p0 * act + 2 * p1 * R2, 0], [0, 2 * p0 * act + 2 * p1 * R2]],
        rtol=1e-6)


def test_the_share_is_what_the_held_experts_give_and_the_identity_part():
    """Given expert 1 alone of a router of two and an identity expert:
    token 1's routed part stays, token 0's is left out, the identity part
    is in both shares; the router's picks are of all three outputs
    whichever are held."""
    u = jnp.asarray([[R2, 0.0], [0.0, R2]])
    b = dict(branch_weights(), b_r=jnp.asarray([0.0, 0.0, 0.5]))
    whole, chosen = ref.scmoe(u, b, 2, 2.0, 2, 0)
    p1 = 1 / (math.exp(R2) + 2)
    identity = 2 * p1 * np.asarray(u)
    parts = []
    for e in (0, 1):
        held = dict(branch_weights((e,)), b_r=b["b_r"])
        part, mine = ref.scmoe(u, held, 2, 2.0, 2, e)
        np.testing.assert_array_equal(mine, chosen)
        parts.append(np.asarray(part))
    np.testing.assert_allclose(parts[1][0], identity[0], rtol=1e-6)
    assert parts[1][1][1] > identity[1][1] + 1e-3
    np.testing.assert_allclose(parts[0] + parts[1] - identity, whole,
                               rtol=1e-6)


def half(n_mlp=(1.0, 1.0), mlp=0.0):
    """One half of a layer, hidden 2, one head of 1 + 2 and 1, ranks 2 and
    1; an attention that returns nothing (``Wo`` zero) and a dense MLP of
    width 1 that reads channel 0 and writes ``mlp`` times it to channel 1."""
    return {"n_attn": jnp.ones((2,)), "n_mlp": jnp.asarray(n_mlp),
            "w_q_a": jnp.eye(2), "n_q": jnp.ones((2,)),
            "w_q_b": jnp.ones((2, 3)), "w_kv_a": jnp.ones((2, 3)),
            "n_kv": jnp.ones((1,)), "w_kv_b": jnp.ones((1, 2)),
            "w_o": jnp.zeros((1, 2)),
            "d_gate": jnp.asarray([[1.0], [0.0]]),
            "d_up": jnp.asarray([[1.0], [0.0]]),
            "d_down": jnp.asarray([[0.0, mlp]])}


def settings(**kw):
    return {"num_attention_heads": 1, "qk_nope_head_dim": 1,
            "qk_rope_head_dim": 2, "v_head_dim": 1, "rms_norm_eps": 1e-12,
            "rope_theta": 1e7, "mla_scale_q_lora": True,
            "mla_scale_kv_lora": True, "moe_topk": 2,
            "routed_scaling_factor": 2.0, "n_routed_experts": 2,
            "experts_first": 0, "query_block": 2, "head_block": 1, **kw}


def test_the_experts_read_the_first_half_and_join_after_the_second():
    """Attentions that return nothing, so a1 = h and a2 = m1.  h = (1, 0):
    u1 = rms(h) x n_mlp of the FIRST half = (r2, 0); m1 = h + MLP_0(u1) =
    (1, silu(r2) r2); the second MLP reads rms(m1) under ITS norm weight;
    the branch is ``scmoe`` of u1 and of nothing the second half made, and
    the layer is m1 + MLP_1 + that."""
    r = ref.Reference(settings())
    act = silu(R2) * R2
    layer = {"first": half(mlp=1.0), "second": half(n_mlp=(3.0, 1.0),
                                                    mlp=1.0),
             "experts": dict(branch_weights(),
                             b_r=jnp.asarray([0.0, 0.0, 0.5]))}
    h = jnp.asarray([[1.0, 0.0], [1.0, 0.0]])
    out, chosen = r.layer(h, layer)
    np.testing.assert_array_equal(chosen, [[0, 2], [0, 2]])
    p0 = math.exp(R2) / (math.exp(R2) + 2)
    p1 = 1 / (math.exp(R2) + 2)
    held_back = np.array([2 * p0 * act + 2 * p1 * R2, 0.0])
    m1 = np.array([1.0, act])
    u2 = 3.0 * m1[0] / math.sqrt((m1 ** 2).mean())
    want = m1 + [0.0, silu(u2) * u2] + held_back
    np.testing.assert_allclose(out[0], want, rtol=1e-6)
    # the first half's norm weight moves the branch, the second's does not
    again, _ = r.layer(h, {**layer, "second": half(n_mlp=(5.0, 1.0),
                                                   mlp=0.0)})
    np.testing.assert_allclose(again[0], m1 + held_back, rtol=1e-6)


def test_the_two_factors_are_the_square_roots_and_sit_on_the_latents():
    """hidden 2 over ranks 2 and 1: the query latent times 1, the key/value
    latent times sqrt 2; a model without the factor and with ``Wkv_b``
    times sqrt 2 is the same function, ``k_pe`` untouched."""
    b = dict(half(), w_o=jnp.ones((1, 2)),
             w_kv_a=jnp.asarray([[1.0, 0.3, -0.2], [0.5, 1.0, 0.7]]),
             w_q_b=jnp.asarray([[1.0, 0.2, 0.4], [-0.3, 1.0, 0.1]]))
    x = jnp.asarray([[1.0, 0.5], [-0.4, 2.0], [0.3, 0.1]])
    args = (1, 1, 2, 1, 1e-12, 1e7)
    scaled = ref.attention(x, b, *args, True, True, 3, 1)
    plain = ref.attention(x, b, *args, False, False, 3, 1)
    assert np.abs(np.asarray(scaled - plain)).max() > 1e-3
    by_hand = ref.attention(x, dict(b, w_kv_b=b["w_kv_b"] * R2), *args,
                            False, False, 3, 1)
    np.testing.assert_allclose(scaled, by_hand, rtol=1e-5)
    # position 0 sees itself alone: its value is c W, c = rms(.) = +-1
    np.testing.assert_allclose(
        np.asarray(scaled - x)[0], [R2, R2], rtol=1e-5)


# ---- the arithmetic ---------------------------------------------------

def test_arithmetic_at_the_published_widths():
    p = arithmetic_longcat.layer_parameters(CONFIG)
    assert p["attention"] == (6144 * 1536 + 1536 * 12288 + 6144 * 576 +
                              512 * 16384 + 8192 * 6144) == 90_570_752
    assert p["dense_mlp"] == 3 * 6144 * 12288 == 226_492_416
    assert p["router"] == 6144 * 768 == 4_718_592
    assert p["outside_experts"] == 638_844_928
    assert p["routed_expert"] == 3 * 6144 * 2048 == 37_748_736
    assert p["layer"] == 638_844_928 + 16 * 37_748_736 == 1_242_824_704
    assert p["vocabulary"] == 2 * 16384 * 6144 == 201_326_592
    assert arithmetic_longcat.model_parameters(CONFIG) == 5_172_625_408
    published = {**CONFIG, **{k: v for k, v in CONFIG["published"].items()
                              if k in CONFIG["reduced"]}}
    assert arithmetic_longcat.model_parameters(published) == \
        28 * (638_844_928 + 512 * 37_748_736) + 2 * 131072 * 6144 == \
        560_664_150_016
    assert arithmetic_longcat.expert_layers(CONFIG) == 4
    assert arithmetic_longcat.expert_bytes(6144, 2048, 2) == 75_497_472
    # eight latent caches of 576 bfloat16 values a position
    assert arithmetic_longcat.kv_cache_bytes_per_position(CONFIG, 2) == \
        8 * 576 * 2 == 9216
    assert 32 * 8192 * 9216 == 2_415_919_104
    tick = arithmetic_longcat.decode_tick_bytes(CONFIG, 6.25, 32 * 2800, 2)
    assert tick == {"outside_experts": 4 * 638_844_928 * 2,
                    "routed_experts": 4 * 6.25 * 75_497_472,
                    "cache": 32 * 2800 * 9216,
                    "head": 16384 * 6144 * 2}
    assert sum(tick.values()) / 819e9 == pytest.approx(9.799e-3, rel=1e-3)


# ---- the readers ------------------------------------------------------

def obs_with(decode_runs=(), chunk=None, zero=None, steps=100, touched=2500,
             positions=100 * 32 * 2800):
    def snap(routed, extra):
        return {"alpa_moe_routed_rows_total": routed,
                "alpa_serving_decode_steps_total": extra["steps"],
                "alpa_moe_experts_touched_total": extra["touched"],
                "alpa_serving_decode_positions_total": extra["positions"],
                **({} if zero is None else
                   {"alpa_moe_zero_picks_total": extra["zero"]})}
    zero_0 = {"steps": 7, "touched": 11, "positions": 4e6, "zero": 1000}
    zero_1 = {"steps": 7 + steps, "touched": 11 + touched,
              "positions": 4e6 + positions, "zero": 1000 + (zero or 0)}
    pair = (snap(7680, zero_0), snap(7680 + 153600, zero_1))
    return {"peaks": PEAKS, "config": CONFIG, "cache_itemsize": 2,
            "engine_rows": 32, "expert_layers": 4, "chunk_trace": chunk,
            "device_trace": {"program_runs": {"jit_decode":
                                              list(decode_runs)}},
            "counters": pair, "traced_counters": pair}


def test_the_new_readers():
    obs = obs_with(decode_runs=[0.0131, 0.0135, 0.0139],
                   chunk={"runs": 20, "program_s": 1.4, "moe_s": 0.56,
                          "moe_events": 900}, zero=51200)
    assert run.metric_reader("zero_expert_picks_pct")(obs) == \
        pytest.approx(100 / 3)
    # 100 ticks that touched 6.25 experts a layer over rows that hold 2,800
    # positions: 8.025 GB a tick, 9.80 ms at 819 GB/s, of a 13.5 ms run
    assert run.metric_reader("scmoe_decode_hbm_roofline_pct")(obs) == \
        pytest.approx(100 * 8.02528e9 / 819e9 / 0.0135, rel=1e-3)
    assert run.metric_reader("scmoe_decode_hbm_roofline_pct")(obs) < 100
    assert run.metric_reader("shortcut_moe_chunk_share_pct")(obs) == \
        pytest.approx(40.0)
    # ticks whose rows touched no held expert still read the rest
    none = obs_with(decode_runs=[0.0135], touched=0)
    assert run.metric_reader("scmoe_decode_hbm_roofline_pct")(none) == \
        pytest.approx(100 * (8.02528e9 - 1.88744e9) / 819e9 / 0.0135, rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_layer(name):
    """The parent's side of a traced run, and the other cells': no such
    counter, run or scope, so no number and no error."""
    read = run.metric_reader(name)
    empty = obs_with()
    assert read(empty) is None
    assert read({"peaks": None, "counters": None, "traced_counters": None,
                 "engine_rows": 16}) is None
    # the accepted serving drivers' obs: no such keys at all
    assert read({"peaks": PEAKS, "engine_rows": 16,
                 "counters": ({}, {"alpa_moe_routed_rows_total": 9.0}),
                 "decode_trace": {"decode_runs": 3, "decode_s": 1.0},
                 "chunk_trace": {"runs": 3, "program_s": 1.0,
                                 "attention_s": .3, "attention_events": 9},
                 "device_trace": {"busy_s": 1.0, "program_runs": {}}}) is None


# ---- BENCHMARK.json ---------------------------------------------------

def test_the_cells_files():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    cell = cells[CELL]
    assert cell == {
        "name": CELL, "config": "longcat-flash-1chip",
        "traffic": "agent-closed64", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert CONFIG["name"] == cell["config"]
    assert CONFIG["driver"] == "serve_scmoe" and callable(driver.run)
    assert callable(ref.Reference) and callable(ref.weights_from_program)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "chipbench/configs/longcat-flash-1chip.json"
    assert entry["source"] == ("https://huggingface.co/meituan-longcat/"
                               "LongCat-Flash-Chat/blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (4, 16, 16384)
    assert CONFIG["published"]["n_routed_experts"] == 512
    assert CONFIG["chips_sharing_a_layer"] == 32
    assert mla.share_of(CONFIG) == (0, 16)
    # every width of the row
    assert {k: CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "ffn_hidden_size", "expert_ffn_hidden_size", "zero_expert_num",
        "moe_topk", "routed_scaling_factor", "rope_theta")} == {
        "hidden_size": 6144, "num_attention_heads": 64,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "zero_expert_num": 256,
        "moe_topk": 12, "routed_scaling_factor": 6, "rope_theta": 10000000}
    for key in ("assumed", "why_reduced", "deployment", "share",
                "logit_margin_why"):
        assert CONFIG[key], key
    assert CONFIG["serve"] == {
        "served_context": 8192, "engine_rows": 32, "prefill_chunk": 1024,
        "check_context_over": 4096, "check_context_under": 1024}
    mix = traffic.load_mix(cell["traffic"])
    # ISSUE 44's table, letter for letter
    assert {k: v for k, v in mix.items() if k != "why"} == {
        "kind": "closed_loop", "clients": 64, "pool_size": 512,
        "sizes_seed": 20261301,
        "prompt_len": {"median": 2048, "sigma": 0.8, "min": 256,
                       "max": 7168},
        "output_len": {"median": 256, "sigma": 0.7, "min": 32,
                       "max": 1024},
        "check_requests": 4, "drain_s": 90.0, "trace_after_s": 5.0,
        "trace_seconds": 3.0}
    # every request fits the served context, its prompt padded to chunks
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert max(-(-p // 1024) * 1024 for p, _ in pool) <= 8192
    assert max(p + o for p, o in pool) <= 8192
    # among the first requests sent: a context under and one over the
    # limits the check asks for
    first = [p + o for p, o in pool[:mix["clients"]]]
    assert min(first) < 1024 and max(first) > 4096


def test_the_new_entries_are_additions():
    names = [c["name"] for c in BENCH["workloads"]]
    assert names.index(CELL) > names.index("lfm2-8b-a1b-1chip.chat")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("longcat-flash-1chip") > \
        configs.index("lfm2-8b-a1b-1chip")
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    at = per_layer.index(NEW[0])
    # appended, together and in order, after the newest the benchmark had
    assert per_layer[at:at + 3] == NEW
    assert at > per_layer.index("conv_state_bytes_per_row")
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in BENCH["per_layer"][at:at + 3]:
        assert m["layer"] in layers and m["workloads"][0] == CELL
        assert m["moves"] == "out_tokens_per_s" and m["unit"] == "%"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    # the cell's name is appended to the lists it joined, after what each
    # list held
    for name in JOINED + ["out_tokens_per_s"]:
        m = next(m for g in ("end_to_end", "per_layer") for m in BENCH[g]
                 if m["name"] == name)
        assert m["workloads"].index(CELL) > \
            m["workloads"].index("deepseek-v2-1chip.longdoc"), name
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"out_tokens_per_s", "setup_s", "xla_compile_s",
            "state_init_s"} | set(JOINED) | set(NEW) == reported
    # a stall of all rows is an admission's whole chunks: no tail of gaps;
    # and the one list whose reader would miscount this configuration
    # (arithmetic_mla.absorbed_core_work multiplies by num_hidden_layers,
    # a key this file has not: it says num_layers, 4, for 8 cores)
    assert "gap_p99_ms" not in reported
    assert "mla_decode_roofline_pct" not in reported
