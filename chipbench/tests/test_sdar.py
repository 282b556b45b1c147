"""What PR 36 added to the benchmark: the plain sdar_moe reference and its
generation loop against cases small enough to compute by hand, the
arithmetic of ``arithmetic_sdar.py`` at the published widths, the four new
readers on made-up ``obs``, and the new entries of ``BENCHMARK.json``
against the files they name, each AFTER what the benchmark had (by position
relative to the accepted entries, so that the next PR's appends leave these
checks standing)."""
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_sdar, run, traffic

ref = run.load_module("references", "sdar_moe_decoder")
driver = run.load_module("drivers", "serve_diffusion")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "sdar-30b-a3b-1chip.reasoning"
CONFIG = run.load_json(run.HERE, "configs", "sdar-30b-a3b-1chip.json")
NEW = ["tokens_per_forward", "block_step_ms", "block_step_hbm_roofline_pct",
       "block_step_head_ms"]
JOINED = ["tick_ms", "tick_host_ms", "engine_occupancy_pct",
          "hbm_peak_gb.serve", "prefill_chunk_ms", "moe_decode_share_pct",
          "attention_decode_share_pct", "experts_touched_per_tick",
          "moe_decode_hbm_roofline_pct"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def silu(x):
    return x / (1 + math.exp(-x))


# ---- the reference, by hand -------------------------------------------

def test_the_mask_of_three_blocks():
    at = np.arange(6)
    seen = np.asarray(ref.block_causal_mask(at, at, 2))
    assert seen.tolist() == [[1, 1, 0, 0, 0, 0]] * 2 + \
        [[1, 1, 1, 1, 0, 0]] * 2 + [[1, 1, 1, 1, 1, 1]] * 2
    # blocks of one position: the causal mask
    assert (np.asarray(ref.block_causal_mask(at, at, 1)) ==
            np.tril(np.ones((6, 6), bool))).all()


def test_softmax_routing_by_hand():
    """Logits (1, 3, 2, 0) over four experts, two a token: experts 1 and 2,
    weights e^3 and e^2 over their sum (``norm_topk_prob``), or over the
    sum of all four without it; a tie goes to the lower index."""
    h = jnp.eye(4)[:1]
    w_r = jnp.asarray([[1.0, 3.0, 2.0, 0.0]] + [[0.0] * 4] * 3)
    weights, chosen = ref.route(h, w_r, 2, True)
    assert chosen.tolist() == [[1, 2]]
    e = [math.exp(x) for x in (1, 3, 2, 0)]
    np.testing.assert_allclose(
        weights[0], [0, e[1] / (e[1] + e[2]), e[2] / (e[1] + e[2]), 0],
        rtol=1e-6)
    weights, _ = ref.route(h, w_r, 2, False)
    np.testing.assert_allclose(weights[0, 1], e[1] / sum(e), rtol=1e-6)
    _, chosen = ref.route(h, jnp.asarray([[2.0, 5.0, 5.0, 1.0]] +
                                         [[0.0] * 4] * 3), 2, True)
    assert chosen.tolist() == [[1, 2]]


def test_one_expert_layer_by_hand():
    """One token, hidden 2, two experts of width 1, one chosen: x + w *
    silu(h g) * (h u) * d with h = rms(x) and w = 1."""
    x = jnp.asarray([[3.0, 4.0]])
    h = np.array([3.0, 4.0]) / math.sqrt(12.5 + 1e-6)
    b = {"n2": jnp.ones((2,)), "w_r": jnp.asarray([[0.0, 1.0], [0.0, 0.0]]),
         # expert e: [gate | up] (2, 2) and down (1, 2)
         "w_gate_up": jnp.asarray([[[9.0, 9.0], [9.0, 9.0]],
                                   [[1.0, 0.5], [0.0, 2.0]]]),
         "w_down": jnp.asarray([[[7.0, 7.0]], [[2.0, -1.0]]])}
    y, chosen = ref.experts(x, b, 1, True, 1e-6)
    assert chosen.tolist() == [[1]]
    inner = silu(h[0]) * (0.5 * h[0] + 2.0 * h[1])
    np.testing.assert_allclose(y[0], [3 + 2 * inner, 4 - inner], rtol=1e-5)


def test_attention_sees_the_later_positions_of_its_block():
    """One head of two channels, identity projections, theta large enough
    that positions hardly turn anything: position 0 of a block of two
    averages both positions' values, with blocks of one its own alone."""
    eye = jnp.eye(2)
    b = {"n1": jnp.ones((2,)), "w_q": eye * 0.0, "w_k": eye, "w_v": eye,
         "wq_n": jnp.ones((2,)), "wk_n": jnp.ones((2,)), "w_o": eye}
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    h = np.asarray(ref.rms(x, jnp.ones((2,)), 1e-6))
    both = ref.attention(x, b, 2, 2, 1e-6, 1e6, 2)
    np.testing.assert_allclose(both[0], x[0] + h.mean(0), rtol=1e-5)
    np.testing.assert_allclose(both[1], x[1] + h.mean(0), rtol=1e-5)
    alone = ref.attention(x, b, 2, 1, 1e-6, 1e6, 2)
    np.testing.assert_allclose(alone[0], x[0] + h[0], rtol=1e-5)
    np.testing.assert_allclose(alone[1], x[1] + h.mean(0), rtol=1e-5)


@pytest.mark.parametrize("masked, left, quota", [
    (4, 2, 2), (2, 1, 2), (3, 2, 2), (1, 1, 1), (4, 4, 1), (4, 3, 2),
    (4, 1, 4), (1, 2, 1), (2, 2, 1)])
def test_the_quota(masked, left, quota):
    assert ref.unmask_quota(masked, left) == quota


def test_the_rule_by_hand():
    static, dynamic = "low_confidence_static", "low_confidence_dynamic"
    take = ref.choose_unmasked([1, 1, 1, 1], [.1, .4, .3, .2], 2, static, .9)
    assert take.tolist() == [False, True, True, False]
    # ties to the lower position; a decided position is never taken
    take = ref.choose_unmasked([0, 1, 1, 1], [.9, .5, .5, .5], 3, static, .9)
    assert take.tolist() == [False, True, False, False]
    take = ref.choose_unmasked([1, 1, 1, 1], [.95, .1, .92, .3], 4, dynamic,
                               .9)
    assert take.tolist() == [True, False, True, False]
    take = ref.choose_unmasked([1, 1, 1, 1], [.5, .1, .2, .3], 2, dynamic,
                               .9)
    assert take.tolist() == [True, False, False, True]
    with pytest.raises(ValueError, match="unknown remasking"):
        ref.choose_unmasked([1], [.5], 1, "random", .9)


class _Scripted(ref.Reference):
    """The loop around scripted logits: position p prefers token 10 + p,
    the more strongly the later it lies in its block."""

    def __init__(self):
        self.s = {"block_length": 4}
        self.passes = []

    def logits(self, w, ids, rows=None):
        self.passes.append(list(ids))
        out = np.zeros((rows[1], 40), np.float32)
        for i in range(rows[1]):
            out[i, 10 + rows[0] + i] = 1.0 + i
        return out


def test_the_loop_by_hand():
    """A prompt of 6 in blocks of 4, 2 denoising forwards a block: the
    first block is [p4, p5, mask, mask] and takes one position a forward
    (the later first: it is the more confident), then its commit; the next
    takes positions 3 and 2, then 1 and 0.  Seven tokens end inside the
    third block."""
    loop = _Scripted()
    tokens, forwards, seen = loop.generate(
        None, [1, 2, 3, 4, 5, 6], 7, mask_token_id=0, denoising_steps=2)
    assert tokens == [16, 17, 18, 19, 20, 21, 22]
    #         block 1: 7 at forward 1, 6 at 2, commit 3;  block 2: 4, 5, (6)
    assert forwards == [2, 1, 5, 5, 4, 4, 8]
    assert [(n, base) for n, base, *_ in seen] == [
        (1, 4), (2, 4), (4, 8), (5, 8), (7, 12), (8, 12)]
    assert seen[0][2].tolist() == [5, 6, 0, 0]
    assert seen[0][4].tolist() == [False, False, False, True]
    assert seen[2][4].tolist() == [False, False, True, True]
    # every forward is a whole pass over the prompt and the blocks so far
    assert loop.passes[1] == [1, 2, 3, 4, 5, 6, 0, 17]
    assert loop.passes[3] == [1, 2, 3, 4, 5, 6, 16, 17, 0, 0, 20, 21]
    # an EOS ends it at its position
    tokens, forwards, _ = _Scripted().generate(
        None, [1, 2, 3, 4, 5, 6], 7, mask_token_id=0, denoising_steps=2,
        eos_token_id=19)
    assert tokens == [16, 17, 18, 19]
    # padding with masks changes nothing but the pass's length
    padded = _Scripted()
    assert padded.generate(None, [1, 2, 3, 4, 5, 6], 7, mask_token_id=0,
                           denoising_steps=2, pad_to=16)[:2] == \
        (tokens + [20, 21, 22], [2, 1, 5, 5, 4, 4, 8])
    assert all(len(p) == 16 for p in padded.passes)


# ---- the arithmetic -----------------------------------------------------

def test_arithmetic_at_the_published_widths():
    p = arithmetic_sdar.layer_parameters(CONFIG)
    assert p == {"attention": 18_874_624, "router": 262_144,
                 "routed_expert": 4_718_592, "norms": 4096,
                 "layer": 623_120_640, "vocabulary": 622_331_904}
    assert arithmetic_sdar.model_parameters(CONFIG) == 4_361_055_744
    assert arithmetic_sdar.model_parameters(
        dict(CONFIG, num_hidden_layers=48)) == 30_532_122_624
    assert arithmetic_sdar.kv_cache_bytes_per_position(CONFIG, 2) == 12_288
    assert arithmetic_sdar.expert_bytes(2048, 768, 2) == 9_437_184
    # a step of 32 rows that touches every expert and holds 1,024
    # positions a row
    least = arithmetic_sdar.block_step_least_bytes(CONFIG, 6 * 128,
                                                   32 * 1024, 2)
    assert least == {"attention_weights": 226_495_488, "routers": 3_145_728,
                     "routed_experts": 7_247_757_312, "head": 622_329_856,
                     "caches": 402_653_184}
    flops = arithmetic_sdar.block_step_flops(CONFIG, 32, 4, 32 * 1024)
    assert flops["experts"] == 2 * 128 * 6 * 8 * 4_718_592
    assert flops["head"] == 2 * 128 * 151_936 * 2048
    # memory bounds the step: its operations at the peak take a tenth
    assert sum(flops.values()) / 197e12 < 0.15 * sum(least.values()) / 819e9


# ---- the readers ---------------------------------------------------------

def obs_with(runs=None, steps=0.0, touched=0.0, positions=0.0,
             unmasked=0.0, denoise=0.0, commit=0.0):
    after = {"alpa_serving_decode_steps_total": 50 + steps,
             "alpa_moe_experts_touched_total": 7 + touched,
             "alpa_serving_decode_positions_total": 3 + positions,
             "alpa_serving_block_tokens_unmasked_total": 11 + unmasked,
             'alpa_serving_block_forwards_total{phase="denoise"}':
             5 + denoise,
             'alpa_serving_block_forwards_total{phase="commit"}':
             2 + commit}
    before = {"alpa_serving_decode_steps_total": 50.0,
              "alpa_moe_experts_touched_total": 7.0,
              "alpa_serving_decode_positions_total": 3.0,
              "alpa_serving_block_tokens_unmasked_total": 11.0,
              'alpa_serving_block_forwards_total{phase="denoise"}': 5.0,
              'alpa_serving_block_forwards_total{phase="commit"}': 2.0}
    return {"peaks": PEAKS, "config": CONFIG, "cache_itemsize": 2,
            "engine_rows": 32, "counters": (before, after),
            "traced_counters": (before, after),
            "device_trace": {"program_runs": runs or {}}}


def test_the_new_readers():
    runs = {"jit_block_step": [0.0150, 0.0151, 0.0149, 0.0152, 0.0300],
            "jit_chunk_prefill": [0.028]}
    obs = obs_with(runs, steps=200, touched=200 * 6 * 128,
                   positions=200 * 32 * 1024, unmasked=200 * 32 * 4 / 3,
                   denoise=200 * 32 * 2 / 3, commit=200 * 32 / 3)
    assert run.metric_reader("tokens_per_forward")(obs) == \
        pytest.approx(4 / 3)
    assert run.metric_reader("block_step_ms")(obs) == pytest.approx(15.1)
    # 8.502 GB a step at 819 GB/s are 10.38 ms of the 15.1
    assert run.metric_reader("block_step_hbm_roofline_pct")(obs) == \
        pytest.approx(100 * 8_502_381_568 / 819e9 / 0.0151, rel=1e-6)
    assert run.metric_reader("block_step_hbm_roofline_pct")(obs) < 100
    # rows that hold nothing and touch half the experts: about half
    half = obs_with(runs, steps=200, touched=200 * 6 * 64, positions=1)
    assert 35 < run.metric_reader("block_step_hbm_roofline_pct")(half) < 40


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_layer(name):
    """The parent's side of a traced run, and the other cells': no such
    program, counter or series, so no number and no error."""
    read = run.metric_reader(name)
    assert read(obs_with()) is None
    assert read(obs_with({"jit_decode": [0.01] * 9}, steps=9,
                         touched=500)) is None
    assert read({"peaks": None, "counters": None, "device_trace": None,
                 "engine_rows": 16}) is None
    assert read({"peaks": PEAKS, "engine_rows": 16, "config": CONFIG,
                 "cache_itemsize": 2,
                 "counters": ({}, {"alpa_serving_decode_steps_total": 9.0}),
                 "device_trace": {"program_runs": {
                     "jit_decode": [0.0102] * 150}}}) is None


# ---- the entries ---------------------------------------------------------

def test_the_cells_files():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    cell = cells[CELL]
    assert cell == {
        "name": CELL, "config": "sdar-30b-a3b-1chip",
        "traffic": "reasoning-closed64", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert CONFIG["name"] == cell["config"]
    assert CONFIG["driver"] == "serve_diffusion" and callable(driver.run)
    assert callable(ref.Reference) and callable(ref.weights_from_program)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "chipbench/configs/sdar-30b-a3b-1chip.json"
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 6
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "torch_dtype": "bfloat16"}
    serve = CONFIG["serve"]
    assert {k: serve[k] for k in (
        "served_context", "engine_rows", "prefill_chunk", "block_length",
        "denoising_steps", "remasking", "mask_token_id")} == {
            "served_context": 8192, "engine_rows": 32,
            "prefill_chunk": 1024, "block_length": 4, "denoising_steps": 2,
            "remasking": "low_confidence_static", "mask_token_id": 151669}
    assert serve["mask_token_id"] < CONFIG["vocab_size"]
    assert serve["check_states"] >= 16
    for name in ("why_reduced", "deployment", "logit_margin_why"):
        assert len(CONFIG[name]) > 200
    assert {"block", "mask_token_id", "block_length", "generation",
            "remasking", "logits", "dtype", "weights", "served_context",
            "router_balance"} <= set(CONFIG["assumed"])
    mix = traffic.load_mix(cell["traffic"])
    # ISSUE 36's table, letter for letter
    assert {k: v for k, v in mix.items() if k not in ("why",
                                                      "sizes_seed")} == {
        "kind": "closed_loop", "clients": 64, "pool_size": 512,
        "prompt_len": {"median": 384, "sigma": 1.0, "min": 32,
                       "max": 4096},
        "output_len": {"median": 512, "sigma": 0.6, "min": 128,
                       "max": 2048},
        "check_requests": 4, "drain_s": 120.0, "trace_after_s": 5.0,
        "trace_seconds": 3.0}
    others = [f for f in os.listdir(os.path.join(run.HERE, "traffic"))
              if f != "reasoning-closed64.json"]
    assert mix["sizes_seed"] not in [
        run.load_json(run.HERE, "traffic", f).get("sizes_seed")
        for f in others]
    # every request fits the served context in whole blocks, its prompt
    # padded to chunks
    pool = traffic.request_pool(mix, mix["pool_size"])
    assert max(-(-p // 1024) * 1024 for p, _ in pool) <= 8192
    assert max(-(-(p + o) // 4) * 4 for p, o in pool) <= 6144 <= 8192
    # no request is shorter than a block, and every remainder shows
    assert {p % 4 for p, _ in pool} == {0, 1, 2, 3}
    assert min(o for _, o in pool) >= 128 >= 4 * serve["check_states"]
    # among the first requests sent: a context under and one over the
    # limits the check asks for
    first = [p + o for p, o in pool[:mix["clients"]]]
    assert min(first) < serve["check_context_under"]
    assert max(first) > serve["check_context_over"]


def test_the_new_entries_are_additions():
    names = [c["name"] for c in BENCH["workloads"]]
    assert names.index(CELL) > names.index("deepseek-v2-1chip.longdoc")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("sdar-30b-a3b-1chip") > \
        configs.index("deepseek-v2-1chip")
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    at = per_layer.index(NEW[0])
    # appended, together and in order, after the newest the benchmark had
    assert per_layer[at:at + 4] == NEW
    assert at > per_layer.index("chip_busy_min_pct")
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in BENCH["per_layer"][at:at + 4]:
        assert m["layer"] in layers and m["workloads"][0] == CELL
        assert m["moves"] == "out_tokens_per_s"
        assert m["unit"] in ("%", "ms", "count")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    # the cell's name is appended to the lists it joined, after DeepSeek's
    for name in JOINED + ["out_tokens_per_s"]:
        m = next(m for g in ("end_to_end", "per_layer") for m in BENCH[g]
                 if m["name"] == name)
        assert m["workloads"].index(CELL) > \
            m["workloads"].index("deepseek-v2-1chip.longdoc"), name
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"out_tokens_per_s", "setup_s", "xla_compile_s",
            "state_init_s"} | set(JOINED) | set(NEW) <= reported
    # tokens arrive a block at a time behind whole admissions: no tail of
    # gaps; and the decode's own program does not run here
    assert not {"gap_p99_ms", "decode_head_ms",
                "decode_hbm_roofline_pct"} & reported
