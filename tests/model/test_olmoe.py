"""The OLMoE kinds of the one decoder definition (RMSNorm, rotary
positions, q/k norm, no bias, dropless top-k routed experts, an untied
head) against the plain reference ``chipbench/references/olmoe_decoder.py``
at a toy size on the CPU: hidden 64, 8 experts, 2 a token, 2 layers, seeded
weights.  Float32 activations at full matmul precision, so that what is
compared is the mathematics; the benchmark's cell compares the bfloat16
program with the same reference on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training import train_state

import alpa_tpu
from alpa_tpu.model import moe
from alpa_tpu.model.gpt_model import (GPTConfig, GPTModel, TransformerBlock,
                                      config_from_hf)
from alpa_tpu.model.model_util import routed_lm_loss
from alpa_tpu.ops.grouped_matmul import grouped_matmul
from alpa_tpu.testing import highest, init_params, jitted
from chipbench import run

AUX = 0.01
TOY = run.load_json(run.HERE, "configs", "toy-olmoe.json")
B, S = 2, 32


def toy_config(**kwargs):
    return config_from_hf(TOY, dtype=jnp.float32, **kwargs)


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference({
        "num_heads": TOY["num_attention_heads"],
        "rms_norm_eps": TOY["rms_norm_eps"],
        "rope_theta": float(TOY["rope_theta"]),
        "num_experts_per_tok": TOY["num_experts_per_tok"],
        "norm_topk_prob": TOY["norm_topk_prob"],
        "router_aux_loss_coef": AUX, "token_block": 16})


@pytest.fixture(scope="module")
def toy():
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                             TOY["vocab_size"])
    labels = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)
    # norm weights away from 1, so that a forgotten one shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * jax.random.uniform(
            jax.random.PRNGKey(len(str(path))), x.shape, minval=0.5,
            maxval=1.5) if path[-1].key == "scale" else x, params)
    return model, params, {"input_ids": ids, "labels": labels}


def program_loss(model, params, batch):
    return highest(routed_lm_loss, model.apply, params, batch, AUX)[0]


def reference_loss(mod, ref, params, batch):
    """The reference's loss as a function of the PROGRAM's parameter tree,
    so that jax.grad gives the reference's gradient leaf for leaf.  Built
    from the reference's own pieces, as its ``lm_loss`` is."""
    s, w = ref.s, mod.weights_from_program(params)
    total, prob_sums, counts = 0.0, 0.0, 0.0
    for ids, lab in zip(batch["input_ids"], batch["labels"]):
        x = w["wte"][ids]
        for b in w["blocks"]:
            x = mod.attention(x, b, s["num_heads"], s["rms_norm_eps"],
                              s["rope_theta"])
            x, _, p, c = mod.experts(x, b, s["num_experts_per_tok"],
                                     s["norm_topk_prob"], s["rms_norm_eps"],
                                     16)
            prob_sums, counts = prob_sums + p, counts + c
        logits = mod.head(x, w["wf"], w["w_head"], s["rms_norm_eps"])
        total = total + mod.token_losses(logits, lab).sum()
    n_tokens = batch["labels"].size
    n_rows = n_tokens * len(w["blocks"])
    balance = prob_sums.shape[-1] * (
        (jax.lax.stop_gradient(counts) / n_rows) * (prob_sums / n_rows)).sum()
    return total / n_tokens + AUX * balance


def test_logits_match_reference(toy, reference):
    model, params, batch = toy
    mod, ref = reference
    logits, routing = highest(jitted(model.apply), params,
                              batch["input_ids"])
    weights = mod.weights_from_program(params)
    for i in range(B):
        want = ref.logits(weights, batch["input_ids"][i])
        # float32 against float32: summation order only
        np.testing.assert_allclose(logits[i], want, atol=2e-5, rtol=0)
        _, chosen, _, counts = ref.position_losses(
            weights, batch["input_ids"][i], batch["labels"][i])
        got = routing["experts"].reshape(2, B, S, -1)[:, i]
        assert (np.sort(got, -1) == np.sort(chosen, -1)).all()
    assert routing["expert_counts"].sum() == 2 * B * S * 2
    last = ref.logits(weights, batch["input_ids"][0], rows=(S - 4, 4))
    np.testing.assert_allclose(last, logits[0, -4:], atol=2e-5, rtol=0)


def test_loss_matches_reference(toy, reference):
    model, params, batch = toy
    mod, ref = reference
    got = float(jitted(lambda p: program_loss(model, p, batch))(params))
    want = ref.lm_loss(mod.weights_from_program(params), batch["input_ids"],
                       batch["labels"])
    assert got == pytest.approx(want, rel=2e-6)
    # the differentiable composition used below is the same loss
    assert float(reference_loss(mod, ref, params, batch)) == \
        pytest.approx(want, rel=2e-6)
    # the load-balancing term is in it: k at an even routing, more here
    _, routing = jitted(model.apply)(params, batch["input_ids"])
    assert float(routing["load_balance_loss"]) > 2.0


def test_every_gradient_leaf_matches_reference(toy, reference):
    model, params, batch = toy
    mod, ref = reference
    got = jitted(jax.grad(lambda p: program_loss(model, p, batch)))(params)
    want = jitted(jax.grad(
        lambda p: reference_loss(mod, ref, p, batch)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == 23     # 10 a layer, wte, ln_f, lm_head
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path
        # float32 both sides; 1e-4 of the leaf's largest entry
        np.testing.assert_allclose(
            g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0,
            err_msg=jax.tree_util.keystr(path))


def test_every_token_to_the_same_experts_still_agrees(toy, reference):
    """A router of zeros gives every expert the same probability and every
    token the first two experts: two groups of all the rows, six empty
    ones.  Nothing is dropped: logits and loss still are the reference's."""
    model, params, batch = toy
    mod, ref = reference
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if "router" in
        jax.tree_util.keystr(path) else x, params)
    logits, routing = highest(jitted(model.apply), params,
                              batch["input_ids"])
    assert (np.asarray(routing["expert_counts"]) ==
            [[B * S, B * S, 0, 0, 0, 0, 0, 0]] * 2).all()
    weights = mod.weights_from_program(params)
    for i in range(B):
        np.testing.assert_allclose(
            logits[i], ref.logits(weights, batch["input_ids"][i]),
            atol=2e-5, rtol=0)
    got = float(jitted(lambda p: program_loss(model, p, batch))(params))
    assert got == pytest.approx(
        ref.lm_loss(weights, batch["input_ids"], batch["labels"]), rel=2e-6)


# the last four have more groups than row tiles (256 rows over 16 groups:
# a row tile of 128, so two tiles), as a served chunk has: groups smaller
# than a tile, empty groups between them, and one group with a quarter of
# the rows
@pytest.mark.parametrize("sizes", [
    [40, 0, 3, 85], [128, 0, 0, 0], [0, 0, 0, 128], [32, 32, 32, 32],
    [1, 126, 1, 0],
    [16] * 16,
    [20, 9, 17, 0, 23, 14, 0, 0, 31, 6, 19, 25, 0, 80, 11, 1],
    [64, 13, 0, 12, 14, 13, 0, 12, 64, 13, 12, 13, 0, 13, 0, 13],
    [0, 0, 0, 0, 0, 0, 0, 129, 0, 0, 0, 0, 0, 0, 0, 127]],
    ids=["uneven", "first-only", "last-only", "even", "single-rows",
         "more-groups-than-tiles", "small-and-empty-groups",
         "a-quarter-on-a-group", "straddling-the-tile"])
def test_grouped_matmul_against_a_loop_over_experts(sizes):
    m, k, n = sum(sizes), 64, 32
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    cot = jax.random.normal(jax.random.PRNGKey(2), (m, n))

    def loop(lhs, rhs):
        out, start = [], 0
        for g, size in enumerate(sizes):
            out.append(lhs[start:start + size] @ rhs[g])
            start += size
        return jnp.concatenate(out)

    def kernel(lhs, rhs):
        return grouped_matmul(lhs, rhs, jnp.asarray(sizes))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda a, b: (kernel(a, b) * cot).sum(), (0, 1)))(lhs, rhs)
        want = jax.value_and_grad(
            lambda a, b: (loop(a, b) * cot).sum(), (0, 1))(lhs, rhs)
        np.testing.assert_allclose(kernel(lhs, rhs), loop(lhs, rhs),
                                   atol=1e-4)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-5)
    # an empty group's weights get a zero gradient, not garbage
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[1][1][g]).any()


def _train_step(method, model):
    # plain SGD: Adam's normalised step would turn a last-bit difference of a
    # near-zero gradient into a visible one
    tx = optax.sgd(0.1)

    def create_state(params):
        return train_state.TrainState.create(apply_fn=model.apply,
                                             params=params, tx=tx)

    @alpa_tpu.parallelize(method=method, donate_argnums=())
    def step(state, batch):
        def loss_fn(p):
            return routed_lm_loss(state.apply_fn, p, batch, AUX)
        (loss, routing), grads = alpa_tpu.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        return (state.apply_gradients(grads=grads), loss,
                routing["expert_counts"])

    return create_state, step


def test_shard_parallel_on_four_devices_agrees_with_one(toy):
    model, params, batch = toy
    batch = {k: jnp.concatenate([v, v[::-1]]) for k, v in batch.items()}
    results = {}
    for n in (1, 4):
        create_state, step = _train_step(
            alpa_tpu.ShardParallel(devices=jax.devices()[:n]), model)
        state, loss, counts = step(create_state(params), batch)
        results[n] = (jax.device_get(state.params), float(loss),
                      np.asarray(counts))
        hlo = step.get_last_executable().get_hlo_text()
        # the plan came out, and the kernels are in the program
        assert "while" in hlo or "custom-call" in hlo or "fusion" in hlo
    assert results[4][1] == pytest.approx(results[1][1], rel=1e-5)
    assert (results[4][2] == results[1][2]).all()
    for a, b in zip(jax.tree_util.tree_leaves(results[4][0]),
                    jax.tree_util.tree_leaves(results[1][0])):
        # one step of 0.1 x gradient: summation order only
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


def test_the_plan_treats_the_expert_path_as_recorded(toy):
    """What the ILP's equation walk does with the expert path on a
    four-device mesh, for the four-chip cell that follows (PERF.md, PR
    26): the sorts follow their operand, top_k and the kernels' cond are
    replicated barriers."""
    from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
    from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
    from alpa_tpu.shard_parallel.solver import plan_auto_sharding
    model, params, batch = toy
    flat, tree = jax.tree_util.tree_flatten((params, batch))
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]

    def flat_fn(*leaves):
        p, b = jax.tree_util.tree_unflatten(tree, leaves)
        return jax.grad(lambda q: routed_lm_loss(model.apply, q, b,
                                                 AUX)[0])(p)

    mesh = LocalPhysicalDeviceMesh(jax.devices()[:4])
    _, _, _, _, (graph, choice) = plan_auto_sharding(
        flat_fn, avals, [""] * len(avals), [len(flat) - 2, len(flat) - 1],
        mesh, AutoShardingOption(), return_graph=True)
    labels = [n.label for n in graph.nodes]
    prims = {e.primitive.name for e in graph.flat_eqns}
    assert {"sort", "top_k", "cond"} <= prims
    assert "barrier:top_k" in labels and "barrier:cond" in labels
    assert "barrier:sort" not in labels
    assert len(choice) == len(graph.nodes)


def test_driver_runs_the_toy_cell(toy_context):
    """``chipbench/drivers/train_lm.py`` end to end on the CPU: plan,
    state, reference, per-position check, warm-up, window, traced part."""
    ctx = toy_context("toy-olmoe.train", "toy-lm", 1.0, 2)
    # the registry is the process's: an engine of routed layers in an
    # earlier test has fed the same counter
    from alpa_tpu.telemetry import metrics as tmetrics
    fed = tmetrics.get_registry().snapshot().get(
        "alpa_moe_routed_rows_total", 0)
    obs = run.load_module("drivers", "train_lm").run(ctx)
    # every part of ``correct`` but ``falls``: a second of steps at 1e-4 on
    # fresh uniform batches of 256 tokens moves the loss less than the
    # batches differ (here the step runs over pytest's 8 virtual devices)
    checks = obs["checks"]
    assert checks["matches_reference"] and checks["positions_match"], checks
    assert checks["all_finite"] and obs["failed"] == 0
    assert checks["compiles_in_window"] == 0
    assert obs["attempted"] == len(obs["steps"]) >= 1
    before, after = obs["counters"]
    # the warm-up step's routing, and nothing fed inside the window
    rows = 2 * 4 * 64 * 2       # layers x batch x positions x k
    assert before["alpa_moe_routed_rows_total"] - fed == rows
    assert after["alpa_moe_routed_rows_total"] == \
        before["alpa_moe_routed_rows_total"]
    assert after["alpa_moe_dropped_rows_total"] == 0
    assert after["alpa_moe_expert_load_max_over_mean"] >= 1.0
    read = run.metric_reader("expert_load_max_over_mean")
    assert read(obs) == after["alpa_moe_expert_load_max_over_mean"]
    assert obs["train_flops_per_token"] > 0
    assert obs["expert_trace"] == {}        # a CPU trace has no TPU plane


def test_record_routing_feeds_the_registry():
    from alpa_tpu.telemetry import metrics as tmetrics
    registry = tmetrics.get_registry()
    before = registry.snapshot()
    moe.record_routing(np.array([[4, 0, 2, 2], [2, 2, 2, 2]]),
                       dropped_rows=3)
    after = registry.snapshot()
    assert after["alpa_moe_routed_rows_total"] - \
        before.get("alpa_moe_routed_rows_total", 0) == 16
    assert after["alpa_moe_dropped_rows_total"] - \
        before.get("alpa_moe_dropped_rows_total", 0) == 3
    assert after["alpa_moe_expert_load_max_over_mean"] == 2.0


@pytest.mark.parametrize("m,k,n,groups,ratio", [
    (65536, 2048, 2048, 64, (128 + 63) * 512 / 65536),
    (8192, 2048, 2048, 128, (64 + 127) * 128 / 8192),
    (128, 1024, 2048, 128, 128.0)],
    ids=["olmoe-train", "trinity-chunk", "trinity-decode"])
def test_a_traced_grouped_matmul_sets_the_padded_work_gauge(m, k, n, groups,
                                                            ratio):
    """Tracing a call (nothing runs) leaves, under the call's rows and
    groups, the bound on multiplied over useful rows that its row tile
    gives: ``(m / tm + groups - 1) x tm / m``."""
    from alpa_tpu.telemetry import metrics as tmetrics
    jax.eval_shape(grouped_matmul,
                   jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
                   jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
                   jax.ShapeDtypeStruct((groups,), jnp.int32))
    series = ('alpa_grouped_matmul_padded_work_ratio'
              f'{{m="{m}",groups="{groups}"}}')
    assert tmetrics.get_registry().snapshot()[series] == ratio


def test_legacy_capacity_path_reports_its_drops():
    """The GShard top-2 layer drops rows beyond an expert's capacity; its
    caller reads how many from ``intermediates`` and feeds the counter."""
    from alpa_tpu.telemetry import metrics as tmetrics
    cfg = moe.MoEConfig(hidden_size=32, num_experts=4, expert_group_size=64,
                        capacity_factor=0.5, mlp_ratio=2)
    layer = moe.MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32))
    params = init_params(layer, jax.random.PRNGKey(1), x)
    _, state = layer.apply(params, x, mutable=["intermediates"])
    kept, dropped = moe.legacy_routing(state["intermediates"])
    assert kept.sum() + dropped == 2 * 64 and dropped > 0
    registry = tmetrics.get_registry()
    was = registry.snapshot().get("alpa_moe_dropped_rows_total", 0)
    moe.record_routing(kept, dropped_rows=dropped)
    assert registry.snapshot()["alpa_moe_dropped_rows_total"] == \
        was + dropped
    # with room for every row nothing is dropped
    roomy = moe.MoEMLP(moe.MoEConfig(
        hidden_size=32, num_experts=4, expert_group_size=64,
        capacity_factor=4.0, mlp_ratio=2))
    _, state = roomy.apply(params, x, mutable=["intermediates"])
    assert moe.legacy_routing(state["intermediates"])[1] == 0


@pytest.mark.parametrize("kind,names", [
    ("dense", {"fc_in", "fc_out"}), ("gated", {"gate", "up", "down"}),
    ("experts", {"router", "w_gate", "w_up", "w_down"})])
def test_block_takes_its_mlp_kind_from_the_configuration(kind, names):
    cfg = GPTConfig(hidden_size=32, num_heads=2, seq_len=8, vocab_size=64,
                    num_layers=1, mlp=kind, intermediate_size=48,
                    num_experts=4, num_experts_per_tok=2, activation="silu")
    block = TransformerBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    params = init_params(block, jax.random.PRNGKey(1), x)["params"]
    assert set(params["mlp"]) == names
    out = block.apply({"params": params}, x)
    assert len(out) == (3 if kind == "experts" else 2)
    assert out[0].shape == x.shape and bool(jnp.isfinite(out[0]).all())


def test_layers_may_differ_and_the_default_block_is_gpt2s():
    mixed = GPTConfig(hidden_size=32, num_heads=2, seq_len=8, vocab_size=64,
                      num_layers=2, mlp=("dense", "experts"),
                      intermediate_size=48, num_experts=4,
                      num_experts_per_tok=2)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = init_params(GPTModel(mixed), jax.random.PRNGKey(0),
                         ids)["params"]
    assert set(params["h0"]["mlp"]) == {"fc_in", "fc_out"}
    assert "router" in params["h1"]["mlp"]
    logits, routing = GPTModel(mixed).apply({"params": params}, ids)
    assert routing["expert_counts"].shape == (1, 4)
    # the defaults are the block the GPT and OPT cells run: LayerNorm with
    # bias, learned positions, a fused biased qkv, a tied head
    plain = GPTModel(GPTConfig(hidden_size=32, num_heads=2, seq_len=8,
                               vocab_size=64, num_layers=1))
    params = init_params(plain, jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"wte", "wpe", "h0", "ln_f"}
    assert set(params["h0"]["ln1"]) == {"scale", "bias"}
    assert set(params["h0"]["attn"]["qkv"]) == {"kernel", "bias"}
    assert plain.apply({"params": params}, ids).shape == (1, 8, 64)


def test_config_from_hf_refuses_what_it_cannot_build():
    cfg = toy_config()
    assert (cfg.norm, cfg.positions, cfg.qk_norm, cfg.mlp) == \
        ("rmsnorm", "rotary", True, "experts")
    assert not cfg.use_bias and not cfg.tie_embeddings
    with pytest.raises(ValueError, match="model_type"):
        config_from_hf(dict(TOY, model_type="mamba"))
    # grouped-query attention builds since PR 30 (tests/model/test_trinity.py)
    assert config_from_hf(dict(TOY, num_key_value_heads=2)).kv_heads == 2
    assert cfg.kv_heads == cfg.num_heads and cfg.num_kv_heads is None
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(dict(TOY, rope_scaling={"type": "yarn"}))
