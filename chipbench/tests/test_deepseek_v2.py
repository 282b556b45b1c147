"""What PR 32 added to the benchmark: the plain deepseek_v2 reference
against cases small enough to compute by hand, the arithmetic of
``arithmetic_mla.py`` at the published widths, the four new readers on
made-up ``obs``, the chunk program's device events matched to the
program's scopes, and the new entries of ``BENCHMARK.json`` against the
files they name, each AFTER what the benchmark had (by position relative
to the accepted entries, so that the next PR's appends leave these checks
standing)."""
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_mla, run, traffic

ref = run.load_module("references", "deepseek_v2_decoder")
driver = run.load_module("drivers", "serve_mla")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "deepseek-v2-1chip.longdoc"
CONFIG = run.load_json(run.HERE, "configs", "deepseek-v2-1chip.json")
NEW = ["mla_decode_roofline_pct", "attention_chunk_share_pct",
       "kv_cache_bytes_per_position", "moe_local_rows_pct"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct"]


def silu(x):
    return x / (1 + math.exp(-x))


# ---- the reference, by hand -------------------------------------------

def test_yarn_at_the_published_settings():
    inv_freq, low, high = ref.yarn_frequencies(64, 10000.0,
                                               CONFIG["rope_scaling"])
    assert (low, high) == (10, 23)
    f = [10000.0 ** (-2 * i / 64) for i in range(32)]
    np.testing.assert_allclose(inv_freq[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], np.array(f[23:]) / 40,
                               rtol=1e-6)
    # pair 12 is 2/13 of the way up the ramp
    assert float(inv_freq[12]) == pytest.approx(
        f[12] * (11 / 13) + f[12] / 40 * (2 / 13), rel=1e-6)
    assert ref.mscale(40, 0.707) ** 2 == pytest.approx(1.589626, abs=1e-6)
    assert ref.softmax_scale(128, 64, CONFIG["rope_scaling"]) == \
        pytest.approx(0.0721688 * 1.589626, rel=1e-6)
    assert ref.softmax_scale(128, 64, None) == pytest.approx(192 ** -0.5)
    plain, _, _ = ref.yarn_frequencies(64, 10000.0, None)
    np.testing.assert_allclose(plain, f, rtol=1e-6)


def test_pairs_turn_in_place():
    """(1, 0) in pair i at position p becomes (cos, sin) of p f_i, in the
    channels 2i and 2i + 1 themselves."""
    x = np.zeros((3, 1, 4), np.float32)
    x[:, 0, 0] = x[:, 0, 2] = 1
    out = np.asarray(ref.rotate(jnp.asarray(x), 100.0, None))
    for p in range(3):
        for i, f in enumerate((1.0, 0.1)):
            assert out[p, 0, 2 * i] == pytest.approx(math.cos(p * f),
                                                     abs=1e-6)
            assert out[p, 0, 2 * i + 1] == pytest.approx(math.sin(p * f),
                                                         abs=1e-6)


def test_the_group_limited_choice():
    """4 experts in 2 groups, the best group, 2 experts a token: the
    largest score is in group 1, so the second pick is its neighbour and
    not the larger score of group 0."""
    scores = np.array([[.30, .15, .35, .20], [.45, .05, .30, .20]])
    weights, chosen = ref.route(jnp.eye(2), jnp.log(jnp.asarray(scores)),
                                2, 2, 1, False, 16.0)
    np.testing.assert_array_equal(chosen, [[2, 3], [0, 1]])
    np.testing.assert_allclose(
        weights, 16 * np.array([[0, 0, .35, .20], [.45, .05, 0, 0]]),
        rtol=1e-5)
    normed, _ = ref.route(jnp.eye(2), jnp.log(jnp.asarray(scores)),
                          2, 2, 1, True, 1.0)
    np.testing.assert_allclose(normed[0], [0, 0, .35 / .55, .20 / .55],
                               rtol=1e-5)


def tiny_weights(experts=(0, 1)):
    """hidden 2, one head with one channel of each kind (no rope channel
    could turn: dr 2), ranks 2; the query's projections are 0, so
    attention is the mean of the values a position sees; kv_a passes the
    hidden state on as the latent, kv_b makes the value of its first
    channel, the output lifts it into channel 0.  Two routed experts of
    width 1 (expert e gates and lifts channel e into channel e), router
    the identity, one a token; a shared expert from the channels' sum into
    channel 1.  ``experts``: which of the two the weights hold."""
    one = np.ones(2, np.float32)
    block = {"n1": one, "n2": one,
             "w_q_a": np.zeros((2, 2), np.float32), "n_q": one,
             "w_q_b": np.zeros((2, 3), np.float32),
             # [c (2) | k_pe (2)]
             "w_kv_a": np.concatenate(
                 [np.eye(2), np.zeros((2, 2))], 1).astype(np.float32),
             "n_kv": one,
             # [k_nope (1) | v (1)] from the latent's first channel
             "w_kv_b": np.array([[0, 1], [0, 0]], np.float32),
             "w_o": np.array([[1, 0]], np.float32),
             "w_r": np.eye(2, dtype=np.float32),
             "w_gate_up": np.stack([[[1, 1], [0, 0]], [[0, 0], [1, 1]]]
                                   ).astype(np.float32)[list(experts)],
             "w_down": np.eye(2, dtype=np.float32).reshape(2, 1, 2)[
                 list(experts)],
             "s_gate": np.ones((2, 1), np.float32),
             "s_up": np.ones((2, 1), np.float32),
             "s_down": np.array([[0.0, 1.0]], np.float32)}
    return {"wte": np.array([[1, 0], [0, 1], [1, 1]], np.float32),
            "blocks": [block], "wf": one,
            "w_head": np.eye(2, dtype=np.float32)}


def settings(first=0):
    return {"num_attention_heads": 1, "qk_nope_head_dim": 1,
            "qk_rope_head_dim": 2, "v_head_dim": 1, "rms_norm_eps": EPS,
            "rope_theta": 10000.0, "rope_scaling": None,
            "num_experts_per_tok": 1, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": False, "routed_scaling_factor": 2.0,
            "experts_first": first, "query_block": 4, "head_block": 1}


R2 = math.sqrt(2.0)
EPS = 1e-12     # the query's latent is all zeros: 0 / sqrt(0 + eps)


def test_one_block_by_hand():
    """ids (0, 1): x = (1, 0), (0, 1); normed (rms 1/sqrt 2) h = (r2, 0),
    (0, r2); the latent is h again normed: the same; its value is its
    first channel: r2, 0.  Position 0 sees itself: attention r2 into
    channel 0; position 1 the mean of r2 and 0."""
    w = tiny_weights()
    r = ref.Reference(settings())
    x1 = np.asarray(r._attention(
        jnp.asarray(w["wte"][[0, 1]]), w["blocks"][0], 1, 1, 2, 1, EPS,
        10000.0, None, 2, 1))
    np.testing.assert_allclose(x1, [[1 + R2, 0], [R2 / 2, 1]], rtol=1e-6)
    # the expert layer on x = (1, 0), (0, 1): h = (r2, 0), (0, r2); the
    # router is the identity, so token 0 takes expert 0 with weight
    # softmax(r2, 0)[0] x 2, and expert 0 gives silu(r2) r2 into channel 0;
    # the shared expert silu(r2) r2 into channel 1
    x = jnp.asarray(w["wte"][[0, 1]])
    out, chosen = r._experts(x, w["blocks"][0], 1, 1, 1, False, 2.0, 0, EPS)
    p = math.exp(R2) / (math.exp(R2) + 1)
    act = silu(R2) * R2
    np.testing.assert_array_equal(chosen, [[0], [1]])
    np.testing.assert_allclose(
        out, [[1 + 2 * p * act, act], [0, 1 + 2 * p * act + act]],
        rtol=1e-6)


def test_the_share_is_what_the_held_experts_give():
    """Given expert 1 alone of a router of two: token 1's routed part
    stays, token 0's is left out, the shared expert is in both; the
    router's choice is of both experts whichever are held."""
    x = jnp.asarray(tiny_weights()["wte"][[0, 1]])
    whole, chosen = ref.experts(x, tiny_weights()["blocks"][0], 1, 1, 1,
                                False, 2.0, 0, EPS)
    parts = []
    for e in (0, 1):
        part, mine = ref.experts(x, tiny_weights((e,))["blocks"][0], 1, 1,
                                 1, False, 2.0, e, EPS)
        np.testing.assert_array_equal(mine, chosen)
        parts.append(np.asarray(part - x))
    act = silu(R2) * R2
    shared = np.array([[0, act], [0, act]])
    np.testing.assert_allclose(parts[1][0], shared[0], rtol=1e-6)
    assert parts[1][1][1] > act + 1e-3
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole - x,
                               rtol=1e-6)


def test_every_head_reads_the_one_rope_key():
    """Two heads with the same query weights score alike: ``k_pe`` is one
    key a position, not one a head."""
    rng = np.random.default_rng(0)
    h, heads, dn, dr, dv, rank = 8, 2, 2, 2, 2, 4
    one = np.ones(h, np.float32)
    w_q_head = rng.normal(size=(3, dn + dr)).astype(np.float32)
    block = {"n1": one, "n2": one,
             "w_q_a": rng.normal(size=(h, 3)).astype(np.float32),
             "n_q": np.ones(3, np.float32),
             "w_q_b": np.concatenate([w_q_head, w_q_head], 1),
             "w_kv_a": rng.normal(size=(h, rank + dr)).astype(np.float32),
             "n_kv": np.ones(rank, np.float32),
             "w_kv_b": np.tile(rng.normal(size=(rank, dn + dv)), (1, 2)
                               ).astype(np.float32),
             "w_o": np.concatenate([np.eye(dv, h), -np.eye(dv, h)]
                                   ).astype(np.float32)}
    x = jnp.asarray(rng.normal(size=(6, h)), jnp.float32)
    out = ref.attention(x, block, heads, dn, dr, dv, 1e-6, 10000.0, None,
                        3, 1)
    # the two heads' outputs are equal and the output projection subtracts
    np.testing.assert_allclose(out, x, atol=1e-6)


# ---- the arithmetic ---------------------------------------------------

def test_arithmetic_at_the_published_widths():
    p = arithmetic_mla.layer_parameters(CONFIG)
    # ISSUE 32's sizing
    assert p["attention"] == 149_225_472
    assert p["router"] == 5120 * 160
    assert p["shared_experts"] == 2 * p["routed_expert"] == 47_185_920
    assert p["dense_mlp"] == 188_743_680
    assert p["expert_layer"] == 669_089_792
    assert p["vocabulary"] == 2 * 12800 * 5120
    assert arithmetic_mla.model_parameters(CONFIG) == 3_145_400_320
    published = dict(CONFIG, **{k: CONFIG["published"][k] for k in
                                CONFIG["reduced"]})
    assert round(arithmetic_mla.model_parameters(published) / 1e9, 1) \
        == 235.7
    assert arithmetic_mla.expert_layers(CONFIG) == 4
    assert arithmetic_mla.expert_bytes(5120, 1536, 2) == 47_185_920
    assert arithmetic_mla.kv_cache_bytes_per_position(CONFIG, 2) == 5760
    assert arithmetic_mla.per_head_cache_bytes_per_position(CONFIG, 2) == \
        409_600
    # one tick of 32 rows at the full context
    work = arithmetic_mla.absorbed_core_work(CONFIG, 32 * 16384, 2)
    assert work["flops"] == 2 * 32 * 5 * 128 * 16384 * (576 + 512)
    assert work["bytes"] == 32 * 16384 * 5760
    # 242 operations a byte: the v5e's ridge is 197e12 / 819e9 = 240.5
    assert round(work["flops"] / work["bytes"]) == 242
    # the expanded form: 0.12 TFLOP for every 1,024 keys a chunk reads
    per_block = arithmetic_mla.expanded_core_flops(CONFIG, 1024, 1024)
    assert round(per_block / 1e12, 2) == 0.12
    assert arithmetic_mla.expanded_core_flops(CONFIG, 1024, 16384) == \
        16 * per_block
    tick = arithmetic_mla.decode_tick_bytes(CONFIG, 32, 16384, 14, 2)
    assert round(tick["cache"] / 1e9, 2) == 3.02
    assert round(tick["attention_weights"] / 1e9, 2) == 1.49
    assert round(tick["routed_experts"] / 1e9, 2) == 2.64
    assert round((tick["dense_mlp"] + tick["router_and_shared"] +
                  tick["head"]) / 1e9, 2) == 0.89


# ---- the readers ------------------------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def obs_with(decode_trace=None, chunk_trace=None, local=None,
             positions=None):
    def snap(routed, mine):
        out = {'alpa_serving_kv_cache_bytes{kind="latent"}':
               32 * 16384 * 5760,
               'alpa_serving_kv_cache_bytes{kind="full"}': 0}
        if local is not None:
            out.update(alpa_moe_routed_rows_total=routed,
                       alpa_moe_local_rows_total=mine)
        return out
    return {"decode_trace": decode_trace or {},
            "chunk_trace": chunk_trace or {}, "config": CONFIG,
            "engine_rows": 32, "served_context": 16384,
            "cache_itemsize": 2, "peaks": PEAKS,
            "traced_counters": None if positions is None else (
                {"alpa_serving_decode_positions_total": 4e6},
                {"alpa_serving_decode_positions_total": 4e6 + positions}),
            "counters": (snap(7680, 1000),
                         snap(7680 + 768000, 1000 + (local or 0)))}


def test_the_new_readers():
    found = {"decode_runs": 100, "decode_s": 2.0, "attention_s": 1.2,
             "attention_events": 4000, "moe_s": 0.5, "moe_events": 900}
    obs = obs_with(found, {"runs": 20, "program_s": 1.0,
                           "attention_s": 0.31, "attention_events": 800},
                   local=96000, positions=100 * 32 * 16384)
    # 100 ticks of 32 rows at the full context: a tick's cores are 0.7301
    # TFLOP / 197 T = 3.706 ms (its 3.02 GB / 819 G are 3.687 ms); in 1.2 s
    # of the scope
    assert run.metric_reader("mla_decode_roofline_pct")(obs) == \
        pytest.approx(100 * 100 * 0.7301e12 / 197e12 / 1.2, rel=1e-3)
    # rows that hold a third of the context: a third of the work
    obs["traced_counters"][1]["alpa_serving_decode_positions_total"] = \
        4e6 + 100 * 32 * 16384 / 3
    assert run.metric_reader("mla_decode_roofline_pct")(obs) == \
        pytest.approx(100 * 100 * 0.7301e12 / 197e12 / 1.2 / 3, rel=1e-3)
    assert run.metric_reader("mla_decode_roofline_pct")(obs) < 100
    assert run.metric_reader("attention_chunk_share_pct")(obs) == \
        pytest.approx(31.0)
    assert run.metric_reader("kv_cache_bytes_per_position")(obs) == 5760
    assert run.metric_reader("moe_local_rows_pct")(obs) == \
        pytest.approx(12.5)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_layer(name):
    """The parent's side of a traced run, and the other cells': no such
    span, counter or series, so no number and no error."""
    read = run.metric_reader(name)
    empty = obs_with()
    empty["counters"] = ({}, {"alpa_serving_decode_steps_total": 9.0})
    assert read(empty) is None
    assert read({"peaks": None, "counters": None,
                 "engine_rows": 16}) is None
    # the accepted serving drivers' obs: no such keys at all
    assert read({"peaks": PEAKS, "engine_rows": 16,
                 "counters": ({}, {'alpa_serving_kv_cache_bytes'
                                   '{kind="full"}': 1e9}),
                 "decode_trace": {"decode_runs": 3, "decode_s": 1.0},
                 "device_trace": {"busy_s": 1.0}}) is None


HLO = '''
HloModule jit_chunk_prefill
ENTRY %main {
  %while.3 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(chunk_prefill)/GPTModel/h1/attn/attention/while"}
  %fusion.5 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f.1, metadata={op_name="jit(chunk_prefill)/GPTModel/h1/attn/attention/while/body/bkr,rhd->bkhd/dot_general"}
  %fusion.8 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f.2, metadata={op_name="jit(chunk_prefill)/GPTModel/h1/attn/q_b/dot_general"}
  ROOT %gmm.2 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_prefill)/GPTModel/h1/mlp/moe/grouped_matmul/pallas_call"}
}
'''


def test_chunk_events_are_matched_by_the_scopes_of_the_hlo(tmp_path,
                                                           monkeypatch):
    scoped = run.load_module("drivers", "train_lm").scoped_instructions
    assert scoped(HLO, "attention") == {"while.3", "fusion.5"}
    # one chunk from 10 to 30 us (its loop from 10 to 16 holds the
    # loop's body, counted once), a decode with an instruction of the same
    # name, a chunk that straddles the window's end
    device = {0: [("%while.3 = (s32[], f32[8]{0}) while(%t)", 10_000, 16_000),
                  ("%fusion.5 = bf16[8]{0} fusion(%a)", 10_000, 12_000),
                  ("%fusion.5 = bf16[8]{0} fusion(%a)", 12_500, 15_500),
                  ("%fusion.8 = bf16[8]{0} fusion(%a)", 16_000, 20_000),
                  ("%gmm.2 = bf16[8,4]{1,0} custom-call(%a)", 20_000, 30_000),
                  ("%fusion.5 = bf16[8]{0} fusion(%a)", 40_000, 41_000),
                  ("%fusion.5 = bf16[8]{0} fusion(%a)", 48_000, 49_000)]}
    modules = {0: [("jit_chunk_prefill(7)", 10_000, 30_000),
                   ("jit_decode(123)", 40_000, 42_000),
                   ("jit_chunk_prefill(7)", 48_000, 52_000)]}
    host = [(driver.xplane.WINDOW_SPAN, 5_000, 50_000)]
    monkeypatch.setattr(driver.xplane, "find_xplane", lambda d: d)
    monkeypatch.setattr(driver.xplane, "read_trace",
                        lambda path: (device, host, modules))
    found = driver.read_program_trace(
        str(tmp_path), driver.CHUNK_PROGRAM, HLO,
        {"attention": "attention", "grouped_matmul": "grouped_matmul"},
        scoped)
    assert found == {
        "runs": 1, "program_s": pytest.approx(20e-6),
        "attention_s": pytest.approx(6e-6), "attention_events": 3,
        "grouped_matmul_s": pytest.approx(10e-6),
        "grouped_matmul_events": 1}
    assert run.metric_reader("attention_chunk_share_pct")(
        {"chunk_trace": found}) == pytest.approx(30.0)
    assert driver.read_program_trace(str(tmp_path), "jit_prefill", HLO, {},
                                     scoped) == {}


# ---- the entries of BENCHMARK.json ------------------------------------

def test_the_cells_files():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    cell = cells[CELL]
    assert cell == {
        "name": CELL, "config": "deepseek-v2-1chip",
        "traffic": "longdoc-closed64", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert CONFIG["name"] == cell["config"]
    assert CONFIG["driver"] == "serve_mla" and callable(driver.run)
    assert callable(ref.Reference) and callable(ref.weights_from_program)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "chipbench/configs/deepseek-v2-1chip.json"
    assert entry["source"] == ("https://huggingface.co/deepseek-ai/"
                               "DeepSeek-V2/blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 20, 12800)
    assert CONFIG["published"]["n_routed_experts"] == 160
    assert CONFIG["chips_sharing_a_layer"] == 8
    assert driver.share_of(CONFIG) == (0, 20)
    assert CONFIG["serve"] == {
        "served_context": 16384, "engine_rows": 32, "prefill_chunk": 1024,
        "check_context_over": 8192, "check_context_under": 1024}
    mix = traffic.load_mix(cell["traffic"])
    # ISSUE 32's table, letter for letter
    assert {k: v for k, v in mix.items() if k != "why"} == {
        "kind": "closed_loop", "clients": 64, "pool_size": 512,
        "sizes_seed": 20261001,
        "prompt_len": {"median": 4096, "sigma": 0.8, "min": 512,
                       "max": 14336},
        "output_len": {"median": 384, "sigma": 0.7, "min": 64,
                       "max": 1536},
        "check_requests": 4, "drain_s": 90.0, "trace_after_s": 5.0,
        "trace_seconds": 3.0}
    # every request fits the served context, its prompt padded to chunks
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert max(-(-p // 1024) * 1024 for p, _ in pool) <= 16384
    assert max(p + o for p, o in pool) <= 16384
    # among the first requests sent: a context under and one over the
    # limits the check asks for
    first = [p + o for p, o in pool[:mix["clients"]]]
    assert min(first) < 1024 and max(first) > 8192


def test_the_new_entries_are_additions():
    names = [c["name"] for c in BENCH["workloads"]]
    assert names.index(CELL) > names.index("trinity-mini-1chip.mixed")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("deepseek-v2-1chip") > \
        configs.index("trinity-mini-1chip")
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    at = per_layer.index(NEW[0])
    # appended, together and in order, after the newest the benchmark had
    assert per_layer[at:at + 4] == NEW
    assert at > per_layer.index("prefill_chunk_ms")
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in BENCH["per_layer"][at:at + 4]:
        assert m["layer"] in layers and m["workloads"] == [CELL]
        assert m["moves"] == "out_tokens_per_s" and m["unit"] in ("%", "B")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    # the cell's name is appended to the lists it joined, after Trinity's
    for name in JOINED + ["out_tokens_per_s"]:
        m = next(m for g in ("end_to_end", "per_layer") for m in BENCH[g]
                 if m["name"] == name)
        assert m["workloads"].index(CELL) > \
            m["workloads"].index("trinity-mini-1chip.mixed"), name
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"out_tokens_per_s", "setup_s", "xla_compile_s",
            "state_init_s"} | set(JOINED) | set(NEW) == reported
    # a stall of all rows is an admission's whole chunks: no tail of gaps
    assert "gap_p99_ms" not in reported
