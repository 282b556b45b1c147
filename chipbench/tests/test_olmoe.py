"""What PR 26 added to the benchmark: the plain OLMoE reference against
cases small enough to compute by hand, the arithmetic of
``arithmetic_moe.py``, the three readers, the trace matching of
``drivers/train_lm.py``, and the contract's checks for a cell whose driver
is ``train_lm`` (``test_contract.py::test_cell_files_exist`` knows two
drivers and fails for that cell with a KeyError; it is not this PR's to
edit, so the same check is made here with the table it needs)."""
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic_moe, run, traffic

ref = run.load_module("references", "olmoe_decoder")
driver = run.load_module("drivers", "train_lm")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "olmoe-1b-7b-1chip.train"
R2 = math.sqrt(2.0)


def sigmoid(x):
    return 1 / (1 + math.exp(-x))


def tiny_weights():
    """hidden 2, one head, one layer, two experts of width 1, one a token,
    vocabulary 3.  The q and k projections are 0, so attention is the
    causal mean of v; v, the output projection and the router are the
    identity; expert e gates and lifts channel e and writes it back to
    channel e."""
    eye, zero = np.eye(2, dtype=np.float32), np.zeros((2, 2), np.float32)
    one = np.ones(2, np.float32)
    block = {"w1": one, "w_q": zero, "w_k": zero, "w_v": eye,
             "wq_n": one, "wk_n": one, "w_o": eye, "w2": one, "w_r": eye,
             "w_gate": eye.reshape(2, 2, 1), "w_up": eye.reshape(2, 2, 1),
             "w_down": eye.reshape(2, 1, 2)}
    return {"wte": np.array([[1, 0], [0, 1], [1, 1]], np.float32),
            "blocks": [block], "wf": one,
            "w_head": np.array([[1, 0, 1], [0, 1, 1]], np.float32)}


SETTINGS = {"num_heads": 1, "rms_norm_eps": 1e-12, "rope_theta": 10000.0,
            "num_experts_per_tok": 1, "norm_topk_prob": False,
            "router_aux_loss_coef": 0.01, "token_block": 2}


def test_rms_and_rotation_by_hand():
    # mean of squares (1 + 4 + 9 + 36) / 4 = 12.5
    np.testing.assert_allclose(
        ref.rms(jnp.array([1.0, 2.0, 3.0, 6.0]), 2.0, 0.0),
        2 * np.array([1, 2, 3, 6]) / math.sqrt(12.5), rtol=1e-6)
    # one head of 2 channels: one pair, angle = position x 1
    x = jnp.array([[[1.0, 0.0]], [[1.0, 0.0]], [[0.0, 1.0]]])
    np.testing.assert_allclose(
        ref.rotate(x, 10000.0)[:, 0],
        [[1, 0], [math.cos(1), math.sin(1)], [-math.sin(2), math.cos(2)]],
        atol=1e-6)
    # 4 channels: channel i pairs with i + 2, the second pair at
    # theta^(-1/2) = 0.01 radians a position
    y = ref.rotate(jnp.array([[[0.0] * 4], [[1.0, 1.0, 0.0, 0.0]]]), 1e4)
    np.testing.assert_allclose(
        y[1, 0], [math.cos(1), math.cos(0.01), math.sin(1), math.sin(0.01)],
        atol=1e-6)
    # what the scores see depends on the distance alone
    q = ref.rotate(jnp.ones((4, 1, 4)), 1e4)[:, 0]
    assert float(q[0] @ q[2]) == pytest.approx(float(q[1] @ q[3]), rel=1e-6)
    assert float(q[0] @ q[2]) != pytest.approx(float(q[0] @ q[1]), rel=1e-3)


def one_token_expected():
    """ids (0): x = (1, 0); rms gives (r2, 0), which is v and, alone in its
    context, the attention's output: x = (1 + r2, 0).  Its rms is (r2, 0)
    again; the router's logits are (r2, 0): expert 0 with p = sigmoid(r2).
    Expert 0: gate = up = r2, silu(r2) r2 = 2 sigmoid(r2), written to
    channel 0.  x = (1 + r2 + 2 p^2, 0); the last rms gives (r2, 0) and
    the head (r2, 0, r2)."""
    return sigmoid(R2), [R2, 0.0, R2]


def test_one_token_by_hand():
    r = ref.Reference(SETTINGS)
    w = tiny_weights()
    p, logits = one_token_expected()
    np.testing.assert_allclose(r.logits(w, np.array([0])), [logits],
                               atol=1e-6)
    x, routing = r.hidden(w, np.array([0]))
    np.testing.assert_allclose(x, [[1 + R2 + 2 * p * p, 0.0]], rtol=1e-6)
    chosen, prob_sums, counts = routing[0]
    assert chosen.tolist() == [[0]] and counts.tolist() == [1, 0]
    np.testing.assert_allclose(prob_sums, [p, 1 - p], rtol=1e-6)


def test_two_tokens_by_hand():
    """ids (0, 1): x0 = (1, 0), x1 = (0, 1); v0 = (r2, 0), v1 = (0, r2).
    Position 0 attends to itself, position 1 to the mean a = r2 / 2 of
    both: x0 = (1 + r2, 0), x1 = (a, 1 + a).  Token 0 goes to expert 0 as
    above.  Token 1: n = sqrt((a^2 + (1 + a)^2) / 2), h = (a, 1 + a) / n,
    expert 1 with p1 = sigmoid(h[1] - h[0]); it adds p1 h[1]^2
    sigmoid(h[1]) to channel 1.  One token an expert: the balancing term
    is 2 (1/2 P0 + 1/2 P1) = 1."""
    r = ref.Reference(SETTINGS)
    w = tiny_weights()
    a = R2 / 2
    n = math.sqrt((a * a + (1 + a)**2) / 2)
    h = (a / n, (1 + a) / n)
    p1 = sigmoid(h[1] - h[0])
    x1 = (a, 1 + a + p1 * h[1] * h[1] * sigmoid(h[1]))
    m = math.sqrt((x1[0]**2 + x1[1]**2) / 2)
    want1 = [x1[0] / m, x1[1] / m, (x1[0] + x1[1]) / m]
    p0, want0 = one_token_expected()
    ids = np.array([0, 1])
    np.testing.assert_allclose(r.logits(w, ids), [want0, want1], atol=1e-6)
    np.testing.assert_allclose(r.logits(w, ids, rows=(1, 1)), [want1],
                               atol=1e-6)
    losses, chosen, prob_sums, counts = r.position_losses(
        w, ids, np.array([2, 0]))
    assert chosen.tolist() == [[[0], [1]]] and counts.tolist() == [[1, 1]]
    np.testing.assert_allclose(prob_sums, [[p0 + 1 - p1, 1 - p0 + p1]],
                               rtol=1e-6)
    lse0 = math.log(2 * math.exp(R2) + 1)
    lse1 = math.log(sum(math.exp(v) for v in want1))
    np.testing.assert_allclose(losses, [lse0 - R2, lse1 - want1[0]],
                               rtol=1e-6)
    loss = r.lm_loss(w, ids[None], np.array([[2, 0]]))
    assert loss == pytest.approx(
        (lse0 - R2 + lse1 - want1[0]) / 2 + 0.01 * 1.0, rel=1e-6)
    # the future is hidden: another second token leaves position 0 alone
    np.testing.assert_allclose(r.logits(w, np.array([0, 2]))[0], want0,
                               atol=1e-6)


def test_routing_takes_the_k_largest_and_breaks_ties_low():
    h = jnp.eye(4)[:2]
    w_r = jnp.array([[0.0, 3, 1, 2], [5, 5, 5, 5], [0, 0, 0, 0],
                     [0, 0, 0, 0]])
    weights, chosen, probs = ref.route(h, w_r, 2, False)
    assert chosen.tolist() == [[1, 3], [0, 1]]
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    assert (np.asarray(weights) > 0).sum(-1).tolist() == [2, 2]
    np.testing.assert_allclose(weights[1], [0.25, 0.25, 0, 0], rtol=1e-6)
    normed, _, _ = ref.route(h, w_r, 2, True)
    np.testing.assert_allclose(normed.sum(-1), 1.0, rtol=1e-6)


def test_arithmetic_at_the_published_widths():
    flops = arithmetic_moe.moe_decoder_train_flops_per_token(
        2048, 1, 4096, 50304, 1024, 64, 8)
    # experts 302.0, projections 100.7, scores and values 100.7, router
    # 0.8, head 618.1 MFLOP a token (ISSUE 26: 1,122.2)
    assert flops == 301989888 + 100663296 + 100663296 + 786432 + 618135552
    assert round(flops / 1e6, 1) == 1122.2
    # 16 layers: the head is then 4 % and no longer 55 %
    deep = arithmetic_moe.moe_decoder_train_flops_per_token(
        2048, 16, 4096, 50304, 1024, 64, 8)
    assert deep == 16 * (flops - 618135552) + 618135552
    work, bytes_ = arithmetic_moe.expert_grouped_matmul_work(
        8192, 2048, 1024, 64, 8, 2)
    assert work == 8192 * 301989888             # the experts' share, exactly
    rows = 8192 * 8
    assert bytes_ == 3 * 2 * ((rows * 2048 + 64 * 2048 * 2048 + rows * 2048)
                              + (rows * 1024 + 64 * 1024 * 2048 +
                                 rows * 2048))
    assert work / bytes_ > 197e12 / 819e9       # the operations bound it


HLO = '''
HloModule jit_step
%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %mul.3 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/jvp(GPTModel)/h0/mlp/moe/mul" stack_frame_id=1}
}
ENTRY %main {
  %fusion.7 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/jvp(GPTModel)/h0/mlp/moe/mul" stack_frame_id=1}
  %gmm.4 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(GPTModel)/h0/mlp/moe/grouped_matmul/cond/branch_0_fun/jit(gmm)/pallas_call" stack_frame_id=2}
  %tgmm.2 = bf16[2,8,4]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(GPTModel))/h0/mlp/moe/grouped_matmul/cond/branch_0_fun/jit(tgmm)/pallas_call" stack_frame_id=3}
  %sort.1 = s32[8]{0} sort(%c), metadata={op_name="jit(f)/transpose(jvp(moe))/sort"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/jvp(GPTModel)/h0/attn/smoething/mul"}
  ROOT %dot.5 = f32[8]{0} dot(%a, %b), metadata={op_name="jit(f)/jvp(GPTModel)/lm_head/dot_general"}
}
'''


def test_trace_events_are_matched_by_the_scopes_of_the_hlo():
    moe = driver.scoped_instructions(HLO, "moe")
    assert moe == {"mul.3", "fusion.7", "gmm.4", "tgmm.2", "sort.1"}
    assert driver.scoped_instructions(HLO, "grouped_matmul") == \
        {"gmm.4", "tgmm.2"}
    # an event's name is its whole instruction; times in nanoseconds
    events = [("%fusion.7 = bf16[8]{0} fusion(%a)", 0, 2_000_000_000),
              ("%gmm.4 = bf16[8,4]{1,0} custom-call(%a, %b)", 2e9, 5e9),
              ("%fusion.9 = f32[8]{0} fusion(%a)", 5e9, 9e9),
              ("%tgmm.2 = bf16[2,8,4] custom-call(%a)", 9e9, 12e9)]
    assert driver.scope_seconds(events, (0, 12e9), moe) == (8.0, 3)
    # clipped to the window, and an event outside it does not count
    assert driver.scope_seconds(events, (1e9, 4e9), moe) == (3.0, 2)


def obs_with(expert_trace, gauge=None):
    after = {} if gauge is None else \
        {"alpa_moe_expert_load_max_over_mean": gauge}
    return {"expert_trace": expert_trace, "counters": ({}, after),
            "device_trace": {"busy_s": 0.4, "window_s": 0.5},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "grouped_matmul_work": (3 * 2.4739e12, 3 * 5.6e9)}


def test_readers_of_the_expert_layer():
    found = {"expert_path_s": 0.1, "expert_path_events": 500,
             "grouped_matmul_s": 0.06, "grouped_matmul_events": 18}
    obs = obs_with(found, gauge=2.5)
    assert run.metric_reader("moe_device_share_pct")(obs) == \
        pytest.approx(25.0)
    # 7.42 TFLOP at 197 TFLOP/s is 37.7 ms of the 60 the kernels took
    assert run.metric_reader("grouped_matmul_roofline_pct")(obs) == \
        pytest.approx(100 * 3 * 2.4739e12 / 197e12 / 0.06)
    assert run.metric_reader("expert_load_max_over_mean")(obs) == 2.5
    # bytes bound it where the rows are few
    thin = dict(obs, grouped_matmul_work=(1e9, 819e9 * 0.03))
    assert run.metric_reader("grouped_matmul_roofline_pct")(thin) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("name", ["moe_device_share_pct",
                                  "grouped_matmul_roofline_pct",
                                  "expert_load_max_over_mean"])
def test_readers_find_nothing_in_a_program_without_the_layer(name):
    read = run.metric_reader(name)
    assert read(obs_with({})) is None
    assert read({"peaks": None, "counters": None, "device_trace": None}) \
        is None
    rehearsal = dict(obs_with({}), peaks=None, device_trace=None)
    assert read(rehearsal) is None


def test_cell_files_exist_for_every_driver():
    """``test_contract.py::test_cell_files_exist`` with ``train_lm`` in its
    table of drivers."""
    wants = {"train": {"lm_batches"}, "train_lm": {"lm_batches"},
             "serve": {"closed_loop", "open_loop"}}
    for cell in BENCH["workloads"]:
        config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
        assert config["name"] == cell["config"]
        mix = traffic.load_mix(cell["traffic"])
        assert callable(run.load_module("drivers", config["driver"]).run)
        reference = run.load_module("references", config["reference"])
        assert callable(reference.Reference)
        assert callable(reference.weights_from_program)
        assert mix["kind"] in wants[config["driver"]], cell["name"]
    assert CELL in {c["name"] for c in BENCH["workloads"]}


def test_the_new_entries_are_additions():
    """PR 24's seven metrics stay together and in order, with PR 26's
    three after them (a PR adds at the end of a list), each with a reader
    of its own name and a layer that was there."""
    names = [m["name"] for m in BENCH["per_layer"]]
    seven = ["reshard_wait_ms_per_step", "reshard_busy_ms_per_step",
             "reshard_mb_per_step", "driver_launch_ms", "tick_host_ms",
             "queue_wait_ms", "prefill_useful_pct"]
    new = ["moe_device_share_pct", "grouped_matmul_roofline_pct",
           "expert_load_max_over_mean"]
    assert names[-10:] == seven + new
    layers = {m["layer"] for m in BENCH["per_layer"][:-3]}
    for m in BENCH["per_layer"][-3:]:
        assert m["layer"] in layers and m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.metrics_of(BENCH, g, CELL)}
    assert {"train_tokens_per_s", "setup_s", "train_mfu_pct", "plan_s",
            "hbm_peak_gb.train", "host_dispatch_ms.train",
            "driver_launch_ms", "xla_compile_s", "state_init_s"} | set(new) \
        == reported
    serving = {m["name"] for g in ("end_to_end", "per_layer")
               for m in run.metrics_of(BENCH, g, "opt-1.3b.longprompt")}
    steady = {m["name"] for g in ("end_to_end", "per_layer")
              for m in run.metrics_of(BENCH, g, "opt-1.3b.steady")}
    assert serving == steady


# the ``config`` of the catalog's row OLMoE-1B-7B-0125-Instruct
# (/opt/skills/guides/model-configs/architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_the_configuration_keeps_every_published_width():
    config = run.load_json(run.HERE, "configs", "olmoe-1b-7b-1chip.json")
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "olmoe-1b-7b-1chip")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 1
    # the rehearsal's configuration has the same keys for the driver
    toy = run.load_json(run.HERE, "configs", "toy-olmoe.json")
    documentation = {"published", "why_reduced", "assumed", "deployment",
                     "loss_rtol_why", "position_why"}
    assert set(config) - documentation == set(toy)
    mix = traffic.load_mix("lm-b2")
    assert (mix["batch"], mix["micro_batches"], mix["trace_steps"]) == \
        (2, 1, 3)
    long = traffic.load_mix("longprompt-poisson")
    assert long["kind"] == "open_loop" and long["rate_per_s"] == 1.68
    sizes = traffic.request_pool(long, 86)
    assert all(1024 <= p <= 1900 and 16 <= o <= 64 for p, o in sizes)
    assert max(p + o for p, o in sizes) <= 2048     # OPT's positions
