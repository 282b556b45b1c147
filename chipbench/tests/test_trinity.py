"""What PR 30 added to the benchmark: the plain afmoe reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_afmoe.py`` at the published widths, the four readers on
made-up ``obs``, the matching of the decode's device events to the
program's scopes, and the contract's checks for cells whose driver is
``serve_lm`` (``test_contract.py::test_cell_files_exist`` and
``test_olmoe.py``'s twin of it know the drivers of their day and fail for
the new cell with a KeyError, and ``test_olmoe.py::
test_the_new_entries_are_additions`` pins the END of ``per_layer`` to PR
26's three; none is this PR's to edit, so the same checks are made here
with the tables they need)."""
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_afmoe, run, traffic

ref = run.load_module("references", "afmoe_decoder")
driver = run.load_module("drivers", "serve_lm")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "trinity-mini-1chip.mixed"
CONFIG = run.load_json(run.HERE, "configs", "trinity-mini-1chip.json")
R2 = math.sqrt(2.0)
SCALE = 2.0


def sigmoid(x):
    return 1 / (1 + math.exp(-x))


def silu(x):
    return x * sigmoid(x)


def tiny_weights(layers=("sliding_attention",)):
    """hidden 2, one query and one key/value head of 2 channels, two
    experts of width 1, one a token, a shared expert, vocabulary 3.  The q
    and k projections are 0, so attention is the mean of the v it sees; v,
    the output projection and the router are the identity; the gate's
    projection is 0, so the gate is sigmoid(0) = 1/2; expert e gates and
    lifts channel e and writes it back to channel e; the shared expert
    does the same with the sum of both channels into channel 0."""
    eye, zero = np.eye(2, dtype=np.float32), np.zeros((2, 2), np.float32)
    one = np.ones(2, np.float32)
    block = {"n1": one, "n2": one, "n3": one, "n4": one,
             "w_q": zero, "w_k": zero, "w_v": eye, "wq_n": one, "wk_n": one,
             "w_g": zero, "w_o": eye, "w_r": eye,
             "b_r": np.zeros(2, np.float32),
             "w_gate_up": np.stack([[[1, 1], [0, 0]], [[0, 0], [1, 1]]]
                                   ).astype(np.float32),
             "w_down": eye.reshape(2, 1, 2),
             "s_gate": np.ones((2, 1), np.float32),
             "s_up": np.ones((2, 1), np.float32),
             "s_down": np.array([[1.0, 0.0]], np.float32)}
    return {"wte": np.array([[1, 0], [0, 1], [1, 1]], np.float32),
            "blocks": [dict(block) for _ in layers], "wf": one,
            "w_head": np.array([[1, 0, 1], [0, 1, 1]], np.float32)}


def settings(layers=("sliding_attention",), **kwargs):
    return {"head_dim": 2, "layer_types": list(layers), "sliding_window": 2,
            "rms_norm_eps": 1e-12, "rope_theta": 10000.0,
            "num_experts_per_tok": 1, "route_norm": True,
            "route_scale": SCALE, "scale_embedding": True,
            "query_block": 1, **kwargs}


def test_one_token_by_hand():
    """ids (0): x = sqrt(2) (1, 0); rms gives (r2, 0), which is v and,
    alone in its context, the heads' output; the gate halves it, the post
    norm makes it (r2, 0) again: x = (2 r2, 0).  Its rms is (r2, 0); the
    router's scores are (sigmoid(r2), 1/2): expert 0, and with route_norm
    its weight is 1 x route_scale whatever the score.  Expert 0 and the
    shared expert both compute silu(r2) r2 into channel 0; the post norm
    of (3 silu(r2) r2, 0) is (r2, 0): x = (3 r2, 0), the last rms gives
    (r2, 0) and the head (r2, 0, r2)."""
    r = ref.Reference(settings())
    w = tiny_weights()
    np.testing.assert_allclose(r.logits(w, np.array([0])), [[R2, 0.0, R2]],
                               atol=1e-6)
    x, chosen = r.hidden(w, np.array([0]))
    np.testing.assert_allclose(x, [[3 * R2, 0.0]], rtol=1e-6)
    assert chosen[0].tolist() == [[0]]
    # without the post norms' levelling the sizes show: the routed sum is
    # route_scale x silu(r2) r2, the shared one silu(r2) r2
    routed, _ = ref.route(jnp.array([[R2, 0.0]]), jnp.eye(2), jnp.zeros(2),
                          1, True, SCALE)
    np.testing.assert_allclose(routed, [[SCALE, 0.0]], rtol=1e-6)
    unnormed, _ = ref.route(jnp.array([[R2, 0.0]]), jnp.eye(2),
                            jnp.zeros(2), 1, False, 1.0)
    np.testing.assert_allclose(unnormed, [[sigmoid(R2), 0.0]], rtol=1e-6)


def test_two_tokens_and_the_window_by_hand():
    """ids (0, 1, 0) through one sliding layer of window 2: position 2
    sees positions 1 and 2 and not 0; through a full layer it sees all
    three.  With q = k = 0 attention is the mean of the v it sees, v =
    rms(x): v0 = v2 = (r2, 0), v1 = (0, r2)."""
    ids = np.array([0, 1, 0])
    got = {}
    for kind in ("sliding_attention", "full_attention"):
        r = ref.Reference(settings((kind,)))
        x, _ = r.hidden(tiny_weights((kind,)), ids)
        got[kind] = np.asarray(x)

    def after_attention(mean_v, x):
        half = np.asarray(mean_v) / 2                  # the gate
        post = half / math.sqrt((half ** 2).mean())
        return np.asarray(x) + post

    emb = R2 * np.array([[1.0, 0], [0, 1], [1, 0]])
    want = {"sliding_attention": after_attention([R2 / 2, R2 / 2], emb[2]),
            "full_attention": after_attention([2 * R2 / 3, R2 / 3], emb[2])}
    for kind, mid in want.items():
        # the expert layer on top, by the reference's own piece
        out, _ = ref.experts(jnp.asarray(mid[None], jnp.float32),
                             {k: jnp.asarray(v) for k, v in
                              tiny_weights()["blocks"][0].items()},
                             1, True, SCALE, 1e-12)
        np.testing.assert_allclose(got[kind][2], out[0], rtol=1e-5)
    assert np.abs(got["sliding_attention"][2] -
                  got["full_attention"][2]).max() > 0.05
    # positions 0 and 1 see the same keys under both kinds
    np.testing.assert_allclose(got["sliding_attention"][:2],
                               got["full_attention"][:2], rtol=1e-6)
    # the future is hidden
    r = ref.Reference(settings())
    np.testing.assert_allclose(
        r.logits(tiny_weights(), np.array([0, 2]))[0],
        r.logits(tiny_weights(), np.array([0]))[0], atol=1e-6)
    np.testing.assert_allclose(
        r.logits(tiny_weights(), ids, rows=(1, 2)),
        r.logits(tiny_weights(), ids)[1:], atol=1e-6)


def test_routing_chooses_with_the_bias_and_weighs_without_it():
    h = jnp.eye(4)[:2]
    w_r = jnp.array([[0.0, 3, 1, 2], [5, 5, 5, 5], [0, 0, 0, 0],
                     [0, 0, 0, 0]])
    bias = jnp.array([0.0, 0.0, 0.5, 0.0])
    weights, chosen = ref.route(h, w_r, jnp.zeros(4), 2, True, 1.0)
    assert chosen.tolist() == [[1, 3], [0, 1]]       # ties break low
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    biased, chosen = ref.route(h, w_r, bias, 2, True, 2.0)
    # sigmoid(1) + 0.5 = 1.23 beats sigmoid(3) = 0.95 and sigmoid(2) = 0.88
    assert chosen.tolist() == [[2, 1], [2, 0]]
    s1, s3 = sigmoid(1), sigmoid(3)
    np.testing.assert_allclose(
        biased[0], [0, 2 * s3 / (s1 + s3), 2 * s1 / (s1 + s3), 0], rtol=1e-6)


def test_rotation_is_of_sliding_layers_only():
    """Positions reach the scores of a sliding layer and of no full one:
    with q = k = ones the full layer's scores are the same at every
    distance, the sliding layer's are not."""
    q = ref.rotate(jnp.ones((4, 1, 4)), 1e4)[:, 0]
    assert float(q[0] @ q[2]) == pytest.approx(float(q[1] @ q[3]), rel=1e-6)
    assert float(q[0] @ q[2]) != pytest.approx(float(q[0] @ q[1]), rel=1e-3)
    w = tiny_weights()
    w["blocks"][0]["w_q"] = w["blocks"][0]["w_k"] = np.eye(2, dtype=np.float32)
    ids = np.array([0, 1, 1, 0, 2, 0])
    outs = []
    for kind in ("full_attention", "sliding_attention"):
        r = ref.Reference(settings((kind,), sliding_window=16))
        outs.append(np.asarray(r.hidden(w, ids)[0]))
    assert np.abs(outs[0] - outs[1]).max() > 1e-3


def test_arithmetic_at_the_published_widths():
    p = arithmetic_afmoe.layer_parameters(CONFIG)
    # ISSUE 30's sizing: 27.26 M, 37.75 M, 0.26 M, 6.29 M, 839.1 M, 820.0 M
    assert p["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert p["dense_mlp"] == 3 * 2048 * 6144
    assert p["routed_expert"] == p["shared_expert"] == 3 * 2048 * 1024
    assert p["expert_layer"] == 839122944
    assert p["vocabulary"] == 2 * 200192 * 2048
    assert arithmetic_afmoe.model_parameters(CONFIG) == 4241489920
    published = dict(CONFIG, **{k: CONFIG["published"][k] for k in
                                ("num_hidden_layers", "num_dense_layers")})
    assert round(arithmetic_afmoe.model_parameters(published) / 1e9, 2) \
        == 26.12
    assert arithmetic_afmoe.expert_bytes(2048, 1024, 2) == 12582912
    caches = arithmetic_afmoe.kv_cache_bytes(CONFIG, 16, 16384, 2)
    assert caches == {"window": 4 * 16 * 2048 * 2048,
                      "full": 16 * 16384 * 2048}
    tick = arithmetic_afmoe.decode_tick_bytes(CONFIG, 16, 16384, 82, 2)
    assert round(sum(tick.values()) / 1e9, 2) == 6.15
    assert round(tick["routed_experts"] / 1e9, 2) == 4.13
    assert tick["head"] == 200192 * 2048 * 2
    # what a tick reads of the routed experts follows the experts touched
    half = arithmetic_afmoe.decode_tick_bytes(CONFIG, 16, 16384, 41, 2)
    assert half["routed_experts"] * 2 == tick["routed_experts"]


HLO = '''
HloModule jit_decode
ENTRY %main {
  %fusion.7 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/GPTModel/h1/mlp/moe/mul" stack_frame_id=1}
  %gmm.4 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/GPTModel/h1/mlp/moe/grouped_matmul/cond/branch_0_fun/jit(gmm)/pallas_call" stack_frame_id=2}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(decode)/GPTModel/h1/attn/attention/reduce_max"}
  %fusion.11 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(decode)/GPTModel/h1/attn/qkv/dot_general"}
  ROOT %dot.5 = f32[8]{0} dot(%a, %b), metadata={op_name="jit(decode)/GPTModel/lm_head/dot_general"}
}
'''


def test_decode_events_are_matched_by_the_scopes_of_the_hlo(tmp_path,
                                                            monkeypatch):
    scoped = run.load_module("drivers", "train_lm").scoped_instructions
    assert scoped(HLO, "attention") == {"fusion.9"}
    assert scoped(HLO, "moe") == {"fusion.7", "gmm.4"}
    # one decode run from 10 to 20 us, one prefill with the same
    # instruction names, one decode that straddles the window's end
    device = {0: [("%fusion.7 = bf16[8]{0} fusion(%a)", 10_000, 12_000),
                  ("%gmm.4 = bf16[8,4]{1,0} custom-call(%a)", 12_000, 15_000),
                  ("%fusion.9 = f32[8]{0} fusion(%a)", 15_000, 19_000),
                  ("%dot.5 = f32[8]{0} dot(%a, %b)", 19_000, 20_000),
                  ("%fusion.7 = bf16[8]{0} fusion(%a)", 30_000, 39_000),
                  ("%gmm.4 = bf16[8,4]{1,0} custom-call(%a)", 48_000, 49_000)]}
    modules = {0: [("jit_decode(123)", 10_000, 20_000),
                   ("jit_chunk_prefill(7)", 30_000, 40_000),
                   ("jit_decode(123)", 48_000, 52_000)]}
    host = [(driver.xplane.WINDOW_SPAN, 5_000, 50_000)]
    monkeypatch.setattr(driver.xplane, "find_xplane", lambda d: d)
    monkeypatch.setattr(driver.xplane, "read_trace",
                        lambda path: (device, host, modules))
    found = driver.read_decode_trace(
        str(tmp_path), HLO, {"moe": "moe", "grouped_matmul":
                             "grouped_matmul", "attention": "attention"},
        scoped)
    assert found == {
        "decode_runs": 1, "decode_s": pytest.approx(10e-6),
        "moe_s": pytest.approx(5e-6), "moe_events": 2,
        "grouped_matmul_s": pytest.approx(3e-6), "grouped_matmul_events": 1,
        "attention_s": pytest.approx(4e-6), "attention_events": 1}
    monkeypatch.setattr(driver.xplane, "read_trace",
                        lambda path: (device, host, {0: modules[0][1:2]}))
    assert driver.read_decode_trace(str(tmp_path), HLO, {}, scoped) == {}


def obs_with(decode_trace, touched=None, steps=100):
    def snap(t, s):
        return {} if touched is None else {
            "alpa_moe_experts_touched_total": t,
            "alpa_serving_decode_steps_total": s}
    return {"decode_trace": decode_trace, "expert_layers": 4,
            "expert_bytes": 12582912,
            "counters": (snap(1000, 10), snap(1000 + (touched or 0) * 10,
                                              10 + steps * 10)),
            "traced_counters": (snap(5000, 50),
                                snap(5000 + (touched or 0), 50 + steps)),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_of_the_decode():
    found = {"decode_runs": 100, "decode_s": 0.9, "moe_s": 0.45,
             "moe_events": 900, "grouped_matmul_s": 0.4,
             "grouped_matmul_events": 800, "attention_s": 0.27,
             "attention_events": 700}
    obs = obs_with(found, touched=20000)
    assert run.metric_reader("moe_decode_share_pct")(obs) == \
        pytest.approx(50.0)
    assert run.metric_reader("attention_decode_share_pct")(obs) == \
        pytest.approx(30.0)
    # 200,000 experts touched in 1,000 ticks of 4 layers
    assert run.metric_reader("experts_touched_per_tick")(obs) == \
        pytest.approx(50.0)
    # the traced seconds: 20,000 experts of 12.58 MB are 251.7 GB... over
    # 819 GB/s 0.3073 s of the 0.4 the kernels took
    assert run.metric_reader("moe_decode_hbm_roofline_pct")(obs) == \
        pytest.approx(100 * 20000 * 12582912 / 819e9 / 0.4)
    assert run.metric_reader("moe_decode_hbm_roofline_pct")(obs) < 100


@pytest.mark.parametrize("name", [
    "moe_decode_share_pct", "attention_decode_share_pct",
    "experts_touched_per_tick", "moe_decode_hbm_roofline_pct"])
def test_readers_find_nothing_in_a_program_without_the_layer(name):
    read = run.metric_reader(name)
    assert read(obs_with({})) is None
    assert read({"peaks": None, "counters": None}) is None
    # the accepted serving driver's obs: no such keys at all
    assert read({"peaks": {"hbm_bytes_per_s": 819e9},
                 "counters": ({}, {"alpa_serving_decode_steps_total": 9}),
                 "device_trace": {"busy_s": 1.0}}) is None


def test_cell_files_exist_for_every_driver():
    """``test_contract.py::test_cell_files_exist`` with ``serve_lm`` and
    ``train_lm`` in its table of drivers."""
    wants = {"train": {"lm_batches"}, "train_lm": {"lm_batches"},
             "serve": {"closed_loop", "open_loop"},
             "serve_lm": {"closed_loop", "open_loop"}}
    for cell in BENCH["workloads"]:
        config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
        assert config["name"] == cell["config"]
        mix = traffic.load_mix(cell["traffic"])
        assert callable(run.load_module("drivers", config["driver"]).run)
        reference = run.load_module("references", config["reference"])
        assert callable(reference.Reference)
        assert callable(reference.weights_from_program)
        assert mix["kind"] in wants[config["driver"]], cell["name"]
    cells = {c["name"]: c for c in BENCH["workloads"]}
    assert cells[CELL]["chips"] == cells["opt-1.3b.steady-b"]["chips"] == 1
    assert [c["name"] for c in BENCH["workloads"]][-2:] == \
        ["opt-1.3b.steady-b", CELL]


def test_the_new_entries_are_additions():
    """PR 26's three metrics stay together and in order, with PR 30's four
    after them (a PR adds at the end of a list), each with a reader of its
    own name and a layer that was there; the new cells are at the end of
    every list they joined."""
    names = [m["name"] for m in BENCH["per_layer"]]
    new = ["moe_decode_share_pct", "attention_decode_share_pct",
           "experts_touched_per_tick", "moe_decode_hbm_roofline_pct"]
    assert names[-7:] == ["moe_device_share_pct",
                          "grouped_matmul_roofline_pct",
                          "expert_load_max_over_mean"] + new
    layers = {m["layer"] for m in BENCH["per_layer"][:-4]}
    for m in BENCH["per_layer"][-4:]:
        assert m["layer"] in layers and m["workloads"] == [CELL]
        assert m["moves"] == "out_tokens_per_s"
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"out_tokens_per_s", "setup_s", "xla_compile_s", "state_init_s",
            "tick_ms", "tick_host_ms", "engine_occupancy_pct",
            "hbm_peak_gb.serve"} | set(new) == reported
    # its count would reckon every expert as read in every tick
    assert "decode_hbm_roofline_pct" not in reported
    # the tail of the gaps is no bounded metric of a closed loop whose
    # every admission stalls all rows for its whole chunks (PERF.md, PR
    # 30: the percentile sits on a ladder of chunks, and one run in six
    # reads a step lower), nor then the two metrics that move it
    assert not {"gap_p99_ms", "queue_wait_ms", "prefill_useful_pct"} & \
        reported

    def of(cell):
        return {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, cell)}
    assert of("opt-1.3b.steady-b") == of("opt-1.3b.steady")
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            cells = m.get("workloads", [])
            for cell in ("opt-1.3b.steady-b", CELL):
                if cell in cells:
                    assert cells.index(cell) >= len(cells) - 2, m["name"]


# the ``config`` of the catalog's row Trinity-Mini
# (/opt/skills/guides/model-configs/architectures.jsonl)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": 8 * (3 * ["sliding_attention"] + ["full_attention"]),
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_the_configuration_keeps_every_published_width():
    config = CONFIG
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "trinity-mini-1chip")
    assert entry["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == \
        ["num_hidden_layers", "num_dense_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    for key in config["reduced"]:
        assert config["published"][key] == PUBLISHED[key], key
    # one leading dense layer and one whole period
    assert config["num_hidden_layers"] == 5 and \
        config["num_dense_layers"] == 1
    assert config["layer_types"] == 4 * ["sliding_attention"] + \
        ["full_attention"]
    assert config["layer_types"][1:] == PUBLISHED["layer_types"][4:8]
    serve = config["serve"]
    assert (serve["engine_rows"], serve["served_context"],
            serve["prefill_chunk"]) == (16, 16384, 1024)
    for key in ("embedding_scale", "four_norms", "qk_norm", "positions",
                "attention_gate", "router", "router_bias", "dtype",
                "weights", "served_context"):
        assert key in config["assumed"], key
    for word in ("pipeline stages", "16 rows", "16,384", "1,024"):
        assert word in config["deployment"], word
    # the rehearsal's configuration has the same keys for the driver
    toy = run.load_json(run.HERE, "configs", "toy-trinity.json")
    documentation = {"published", "why_reduced", "assumed", "deployment",
                     "logit_margin_why"}
    assert set(config) - documentation == set(toy)
    assert set(config["serve"]) == set(toy["serve"])


def test_the_mixes_are_the_ones_the_issue_names():
    mix = traffic.load_mix("mixed-closed32")
    assert (mix["kind"], mix["clients"], mix["pool_size"],
            mix["check_requests"]) == ("closed_loop", 32, 256, 4)
    assert mix["prompt_len"] == {"median": 3072, "sigma": 1.0, "min": 128,
                                 "max": 14336}
    assert mix["output_len"] == {"median": 192, "sigma": 0.7, "min": 32,
                                 "max": 1024}
    chat = traffic.load_mix("chat-closed16")
    assert (mix["drain_s"], mix["trace_seconds"]) == \
        (chat["drain_s"], chat["trace_seconds"])
    assert mix["sizes_seed"] not in (chat["sizes_seed"], 20260927)
    sizes = traffic.request_pool(mix, mix["pool_size"])
    serve = CONFIG["serve"]
    assert max(p + o for p, o in sizes) <= serve["served_context"]
    chunk = serve["prefill_chunk"]
    assert max(-(-p // chunk) * chunk for p, _ in sizes) <= \
        serve["served_context"]
    # among the first hundred of the pool (a window completes more, and
    # the callers walk the pool in order): a context past 8,192 and one
    # under the window
    contexts = [p + o for p, o in sizes[:100]]
    assert max(contexts) > serve["check_context_over"]
    assert min(contexts) < serve["check_context_under"]
    steady, b = (traffic.load_mix(n) for n in ("chat-poisson",
                                               "chat-poisson-b"))
    assert b["sizes_seed"] == 20260927 != steady["sizes_seed"]
    same = set(steady) - {"sizes_seed", "why"}
    assert {k: b[k] for k in same} == {k: steady[k] for k in same}
    assert traffic.open_loop(b, 1, 100, 51.0) != \
        traffic.open_loop(steady, 1, 100, 51.0)
