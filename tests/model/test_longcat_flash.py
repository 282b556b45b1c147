"""The LongCat-Flash (``model_type`` longcat_flash) kinds of the one decoder
definition (a published layer as two blocks: latent attention and a dense
gated MLP each, the first with routed and identity experts whose sum is
held back and joins the stream after the second; the two latents' factors;
a router wider than the experts with matrices, of which the program holds
a share) against the plain reference
``chipbench/references/longcat_flash_decoder.py`` at a toy size on the CPU:
hidden 64, 4 heads of 16 + 8 and 16, ranks 24 and 16, 16 experts of which
4 are held beside 8 identity experts, 3 picks a token, seeded weights.
Float32 at full matmul precision, so that what is compared is the
mathematics: prefill and then decoding through the two-a-layer latent
caches against the reference's full forward pass, logits and not tokens.
The benchmark's cell compares the bfloat16 program with the same reference
on the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import moe
from alpa_tpu.model.gpt_model import (SHORTCUT_MLP, GPTModel,
                                      TransformerBlock, config_from_hf,
                                      kv_cache_kinds, kv_cache_shapes,
                                      latent_kv_caches, uniform_kv_caches)
from alpa_tpu.serve.generation import Generator
from alpa_tpu.testing import highest, init_params
from chipbench import arithmetic_longcat, run

TOY = run.load_json(run.HERE, "configs", "toy-longcat.json")
CELL = run.load_json(run.HERE, "configs", "longcat-flash-1chip.json")
DRIVER = run.load_module("drivers", "serve_scmoe")
MLA = run.load_module("drivers", "serve_mla")
CONTEXT, S = 96, 48
# float32 at full precision, logits of unit spread; a layer's 12 (here 3)
# weights are 6 p and not renormalised, so the routed sum is some 6 times
# a renormalised one's and its rounding with it
TOL = 5e-5


def toy_config(**kwargs):
    return MLA.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


@pytest.fixture(scope="module")
def reference():
    mod = run.load_module("references", TOY["reference"])
    return mod, mod.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (3, S)): norm weights away from 1 and
    router biases away from 0, so that a forgotten one shows."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, S), 0,
                             TOY["vocab_size"])
    params = init_params(model, jax.random.PRNGKey(2), ids)

    def shake(path, x):
        key = jax.random.PRNGKey(len(jax.tree_util.keystr(path)))
        if path[-1].key == "scale":
            return x * jax.random.uniform(key, x.shape, minval=0.5,
                                          maxval=1.5)
        if path[-1].key == "router_bias":
            return 0.02 * jax.random.normal(key, x.shape)
        return x

    return model, jax.tree_util.tree_map_with_path(shake, params), ids


@pytest.fixture(scope="module")
def wanted(reference, toy):
    """The reference's logits (3, S, V) and picks (3, layers, S, k)."""
    mod, ref = reference
    _, params, ids = toy
    weights = mod.weights_from_program(params)
    out = [ref.logits_and_experts(weights, row, (0, S)) for row in ids]
    return (np.stack([np.asarray(o[0]) for o in out]),
            np.stack([np.asarray(o[1]) for o in out]))


# ---- the configuration ------------------------------------------------

def published_hf():
    return {k: CELL["published"].get(k, v) for k, v in CELL.items()}


def test_config_from_hf_reads_the_catalog_rows_config():
    """The row's ``config`` as the catalog has it (the cell's file keeps
    every key of it but the three it reduces), under the file's own names:
    no ``num_hidden_layers``, ``intermediate_size``, ``num_experts_per_tok``,
    ``num_key_value_heads``, ``hidden_act`` or ``tie_word_embeddings``."""
    hf = published_hf()
    for absent in ("num_hidden_layers", "intermediate_size", "hidden_act",
                   "num_experts_per_tok", "num_key_value_heads",
                   "tie_word_embeddings"):
        assert absent not in hf
    cfg = config_from_hf(hf)
    # a published layer is two blocks, and two cache entries
    assert cfg.num_layers == 56 and cfg.attention == "latent"
    assert cfg.mlp == 28 * (SHORTCUT_MLP, "gated")
    assert (cfg.hidden_size, cfg.num_heads) == (6144, 64)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert cfg.q_lora_scale == 2.0
    assert cfg.kv_lora_scale == pytest.approx(12 ** 0.5)
    assert (cfg.num_experts, cfg.num_zero_experts,
            cfg.num_experts_per_tok) == (512, 256, 12)
    assert cfg.route_scale == 6.0 and not cfg.norm_topk_prob
    assert cfg.router_bias and cfg.router_score == "softmax"
    assert (cfg.mlp_width, cfg.expert_width) == (12288, 2048)
    assert cfg.rope_theta == 1e7 and cfg.rope_interleaved
    assert cfg.rope_yarn is None and cfg.attn_scale is None
    assert cfg.activation == "silu" and not cfg.tie_embeddings
    assert cfg.layer_norm_eps == 1e-5 and not cfg.use_bias
    assert cfg.experts_held is None and cfg.vocab_size == 131072
    assert cfg.seq_len == 131072
    assert latent_kv_caches(cfg) and uniform_kv_caches(cfg)
    small = dataclasses.replace(cfg, num_layers=2, seq_len=4096,
                                mlp=cfg.mlp[:2])
    assert kv_cache_kinds(small) == ["latent", "latent"]
    assert kv_cache_shapes(small, 3) == 2 * [
        ((3, 4096, 512), (3, 64, 4096))]
    assert config_from_hf({**hf, "mla_scale_q_lora": False,
                           "mla_scale_kv_lora": False}).kv_lora_scale == 1.0
    with pytest.raises(ValueError, match="identity zero experts"):
        config_from_hf({**hf, "zero_expert_type": "copy"})
    with pytest.raises(ValueError, match="attention_method"):
        config_from_hf({**hf, "attention_method": "MHA"})
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({**hf, "rope_scaling": {"type": "yarn", "factor": 2}})


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    """Every number of the catalog row's ``config`` is in the cell's file
    under the same key, but the three keys it lists as reduced."""
    row = catalog_row("LongCat-Flash-Chat")
    differ = {k for k, v in row["config"].items() if CELL.get(k) != v}
    assert differ == set(CELL["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differ} == {
        k: CELL["published"][k] for k in differ}
    assert CELL["source"].startswith(row["source_url"])
    # the row's own keys, and the model type it leaves out, are enough
    assert config_from_hf({**row["config"], "model_type": "longcat_flash"}) \
        == config_from_hf(published_hf())


def _count(cfg):
    """(parameters beside the norms and the routers' biases, those)."""
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    small = sum(leaf.size for path, leaf in leaves
                if path[-1].key in ("scale", "router_bias"))
    return shapes, sum(leaf.size for _, leaf in leaves) - small, small


def test_the_cells_file_is_the_program_the_issue_counts():
    """The cell's configuration through the driver: the share, and the
    parameters as ``jax.eval_shape`` counts them, cut and published."""
    cfg = MLA.model_config(CELL, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16, seq_len=8192)
    assert cfg.experts_held == (0, 16)
    assert (cfg.num_experts, cfg.num_zero_experts) == (512, 256)
    assert cfg.num_layers == 8 and cfg.vocab_size == 16384
    shapes, counted, small = _count(cfg)
    assert all(leaf.dtype == jnp.bfloat16 or path[-1].key == "router_bias"
               for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    assert small == 8 * (2 * 6144 + 1536 + 512) + 6144 + 4 * 768
    assert counted == 5_172_625_408 == \
        arithmetic_longcat.model_parameters(CELL)
    first, second = shapes["params"]["h0"], shapes["params"]["h1"]
    assert first["moe"]["router"]["kernel"].shape == (6144, 768)
    assert first["moe"]["router_bias"].shape == (768,)
    assert first["moe"]["w_gate_up"].shape == (16, 6144, 4096)
    assert first["moe"]["w_down"].shape == (16, 2048, 6144)
    assert first["mlp"]["gate"]["kernel"].shape == (6144, 12288)
    assert "moe" not in second and sorted(second) == sorted(
        k for k in first if k != "moe")
    assert kv_cache_shapes(cfg, 32) == 8 * [((32, 8192, 512),
                                             (32, 64, 8192))]
    assert arithmetic_longcat.kv_cache_bytes_per_position(CELL, 2) == 9216
    # the published model: 560.7 B
    _, published, _ = _count(config_from_hf(published_hf()))
    assert published == 560_664_150_016 == \
        arithmetic_longcat.model_parameters(published_hf())


# ---- the model against the reference ----------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    model, params, ids = toy
    logits, routing = highest(model.apply, params, ids)
    np.testing.assert_allclose(logits, wanted[0], atol=TOL)
    # one routing a published layer, every pick as the router numbers it
    experts = np.asarray(routing["experts"]).reshape(2, 3, S, 3)
    np.testing.assert_array_equal(
        np.sort(experts.transpose(1, 0, 2, 3), -1), np.sort(wanted[1], -1))
    assert experts.max() >= 16 and experts.max() < 24   # identity picks
    assert routing["expert_counts"].shape == (2, 16)


@pytest.mark.parametrize("variant", [
    "no_q_scale", "no_kv_scale", "bias_in_the_weights", "renormalised",
    "shortcut_joins_at_once", "shortcut_never_joins", "no_identity"])
def test_a_wrong_wiring_fails(toy, wanted, variant, monkeypatch):
    """What the comparison can tell: each of these moves the logits by a
    thousand tolerances."""
    _, params, ids = toy
    cfg = toy_config()
    if variant == "no_q_scale":
        cfg = dataclasses.replace(cfg, q_lora_scale=1.0)
    elif variant == "no_kv_scale":
        cfg = dataclasses.replace(cfg, kv_lora_scale=1.0)
    elif variant == "renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif variant == "bias_in_the_weights":
        plain = moe.topk_routing

        def biased(logits, k, norm, score, bias, *rest):
            w, e, s = plain(logits, k, norm, score, bias, *rest)
            return w + 6.0 * jnp.take_along_axis(
                jnp.broadcast_to(bias, s.shape), e, -1), e, s
        monkeypatch.setattr(moe, "topk_routing", biased)
    elif variant == "no_identity":
        # the identity picks treated as absent experts
        plain = moe.topk_routing

        def dropped(*args):
            w, e, s = plain(*args)
            return jnp.where(e >= 16, 0.0, w), e, s
        monkeypatch.setattr(moe, "topk_routing", dropped)
    else:
        call = TransformerBlock.__call__

        def rewired(self, x, *args, **kwargs):
            out = call(self, x, *args, **kwargs)
            if len(out) < 4:
                return out
            x, cache, routing, held_back = out
            if variant == "shortcut_joins_at_once":
                x = x + held_back
            return x, cache, routing, 0 * held_back
        monkeypatch.setattr(TransformerBlock, "__call__", rewired)
    out = highest(GPTModel(cfg).apply, params, ids)
    assert np.abs(np.asarray(out[0]) - wanted[0]).max() > 1000 * TOL


def test_the_shortcut_leaves_after_the_first_half_and_joins_after_the_second(
        toy):
    """Block by block: the first block's ``x`` is the dense half alone (a
    plain "gated" block of the same weights gives it), what it holds back
    is its experts applied to its own normed stream, and the second block
    adds exactly that to what it gives without."""
    _, params, ids = toy
    cfg = toy_config()
    p0, p1 = params["params"]["h0"], params["params"]["h1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))

    def block(kind, p, x, **kw):
        return highest(
            lambda: TransformerBlock(cfg, mlp=kind, attention="latent").apply(
                {"params": p}, x, None, True, pos, **kw))

    m1, _, routing, held_back = block(SHORTCUT_MLP, p0, x)
    dense = {k: v for k, v in p0.items() if k != "moe"}
    plain, _ = block("gated", dense, x)
    np.testing.assert_array_equal(m1, plain)
    # u1: the normed stream after the first attention
    captured = highest(lambda: TransformerBlock(
        cfg, mlp="gated", attention="latent").apply(
            {"params": dense}, x, None, True, pos,
            capture_intermediates=lambda mdl, _: mdl.name == "ln2",
            mutable=["intermediates"]))[1]
    u1 = captured["intermediates"]["ln2"]["__call__"][0]
    want, again = highest(moe.DroplessExperts(cfg).apply,
                          {"params": p0["moe"]}, u1)
    np.testing.assert_allclose(held_back, want, atol=1e-6)
    np.testing.assert_array_equal(routing["experts"], again["experts"])
    assert float(jnp.abs(held_back).max()) > 0.1
    without, _ = block("gated", p1, m1)
    with_it, _ = block("gated", p1, m1, shortcut=held_back)
    np.testing.assert_allclose(with_it, without + held_back, atol=1e-6)


def test_experts_that_give_nothing_leave_the_dense_double_layer(toy):
    """The experts' down matrices zeroed and the identity experts biased
    out of every choice: the model is the dense stack of the same blocks,
    all of the kind "gated"."""
    model, params, ids = toy
    quiet = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if path[-1].key == "w_down" else
        jnp.where(jnp.arange(24) >= 16, -10.0, 0.0)
        if path[-1].key == "router_bias" else x, params)
    logits, routing = highest(model.apply, quiet, ids)
    assert np.asarray(routing["experts"]).max() < 16
    dense = {"params": {
        k: {n: v for n, v in block.items() if n != "moe"}
        if isinstance(block, dict) else block
        for k, block in params["params"].items()}}
    plain = highest(GPTModel(toy_config(mlp="gated")).apply, dense, ids)
    np.testing.assert_allclose(logits, plain, atol=1e-6)
    # and with the experts as they are it is another model
    loud, _ = highest(model.apply, params, ids)
    assert np.abs(np.asarray(loud) - np.asarray(plain)).max() > 1000 * TOL


def test_a_block_held_back_for_no_one_is_refused():
    cfg = toy_config(num_layers=3, mlp=(SHORTCUT_MLP, "gated", SHORTCUT_MLP))
    with pytest.raises(ValueError, match="held back for a next block"):
        init_params(GPTModel(cfg), jax.random.PRNGKey(0),
                    jnp.ones((1, 8), jnp.int32))


def _decode_all(gen, params, row, start, caches, wanted_row):
    """Decode ``row`` from ``start`` on, feeding its own ids: the largest
    distance of a step's logits from the reference's."""
    index = jnp.asarray([start], jnp.int32)
    worst = 0.0
    for t in range(start, len(row)):
        logits, caches, _ = gen._decode(
            params, jnp.asarray(row[t:t + 1])[None], index, caches)
        worst = max(worst, float(np.abs(
            np.asarray(logits[0]) - wanted_row[t]).max()))
        index = index + 1
    return worst


@pytest.mark.parametrize("prompt_len,chunk", [
    (13, 8), (5, 8), (29, 8), (3, 16), (37, 12), (8, 8)])
def test_chunked_prefill_then_decode_equals_the_reference(
        toy, wanted, prompt_len, chunk):
    """Prompts that are no multiple of the chunk: the prefill's last logits
    (expanded path, both caches of every layer) and every decode step's
    (absorbed path) through FOUR cache entries for two published layers."""
    model, params, ids = toy
    row = np.asarray(ids[0])
    gen = Generator(model, params, toy_config(), prefill_chunk=chunk)
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_chunked_prefill(
            [row[:prompt_len]], jnp.asarray([prompt_len]), 1)
        np.testing.assert_allclose(last[0], wanted[0][0, prompt_len - 1],
                                   atol=TOL)
        assert [(c.shape, k.shape) for c, k, _i in caches] == \
            4 * [((1, CONTEXT, 16), (1, 8, CONTEXT))]
        assert _decode_all(gen, params, row, prompt_len, caches,
                           wanted[0][0]) < TOL


def test_bucketed_prefill_then_decode_equals_the_reference(toy, wanted):
    """The one dense prefill, right-padded to its bucket: rows of mixed
    lengths in one batch, then decode ticks over rows at unlike positions,
    whose picks are the reference's."""
    model, params, ids = toy
    gen = Generator(model, params, toy_config(), prompt_buckets=[32])
    lengths = [21, 5, 30]
    with jax.default_matmul_precision("highest"):
        last, caches = gen._run_bucketed_prefill(
            [np.asarray(ids[r, :n]) for r, n in enumerate(lengths)],
            jnp.asarray(lengths, jnp.int32), 3)
        index = jnp.asarray(lengths, jnp.int32)
        for r, n in enumerate(lengths):
            np.testing.assert_allclose(last[r], wanted[0][r, n - 1],
                                       atol=TOL)
        for step in range(12):
            tok = jnp.stack([ids[r, n + step] for r, n in
                             enumerate(lengths)])[:, None]
            logits, caches, routing = gen._decode(params, tok, index, caches)
            index = index + 1
            assert routing["experts"].shape == (2, 3, 3)
            for r, n in enumerate(lengths):
                np.testing.assert_allclose(logits[r], wanted[0][r, n + step],
                                           atol=TOL)
                np.testing.assert_array_equal(
                    np.sort(np.asarray(routing["experts"][:, r]), -1),
                    np.sort(wanted[1][r, :, n + step], -1))


# ---- the three kinds of pick ------------------------------------------

def _layer_params(cfg, h, bias=None):
    layer = moe.DroplessExperts(cfg)
    p = init_params(layer, jax.random.PRNGKey(6), h)["params"]
    if bias is not None:
        p = {**p, "router_bias": jnp.asarray(bias, jnp.float32)}
    return layer, p


def test_identity_picks_add_their_weight_times_the_input():
    """Every pick forced onto an identity expert (a bias that enters the
    choice and not the weights): the layer gives exactly ``(sum w) x`` with
    ``w = 6 p`` of the full-width softmax, and no expert's row is counted.
    Every pick forced off them: the routed sum alone, as worked by hand."""
    cfg = toy_config(experts_held=None)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 64), jnp.float32)
    layer, p = _layer_params(cfg, h, np.where(np.arange(24) >= 16, 10., 0.))
    y, routing = highest(layer.apply, {"params": p}, h)
    probs = np.asarray(highest(
        lambda: jax.nn.softmax(h[0] @ p["router"]["kernel"], -1)))
    experts = np.asarray(routing["experts"])
    assert (experts >= 16).all() and int(routing["zero_picks"]) == 120
    assert int(routing["counts"].sum()) == 0
    w = 6.0 * np.take_along_axis(probs, experts, -1).sum(-1)
    np.testing.assert_allclose(y[0], w[:, None] * np.asarray(h[0]),
                               rtol=1e-6, atol=1e-7)
    # a token with none: the routed sum alone
    _, p = _layer_params(cfg, h, np.where(np.arange(24) >= 16, -10., 0.))
    y, routing = highest(layer.apply, {"params": p}, h)
    experts = np.asarray(routing["experts"])
    assert (experts < 16).all() and int(routing["zero_picks"]) == 0
    assert int(routing["counts"].sum()) == 120
    want = np.zeros((40, 64), np.float32)
    x = np.asarray(h[0], np.float64)
    for t in range(40):
        for e in experts[t]:
            gate_up = x[t] @ np.asarray(p["w_gate_up"][e], np.float64)
            hidden = gate_up[:32] / (1 + np.exp(-gate_up[:32])) * gate_up[32:]
            want[t] += 6.0 * probs[t, e] * (
                hidden @ np.asarray(p["w_down"][e], np.float64))
    np.testing.assert_allclose(y[0], want, atol=TOL)


@pytest.mark.parametrize("window", [None, 16], ids=["all_rows", "windows"])
def test_the_shares_of_a_layer_add_up_to_the_whole(reference, window,
                                                   monkeypatch):
    """The share test: one expert branch of 8 experts and 4 identity
    experts over four chips.  What the four shares give (the program's
    ``DroplessExperts`` told which 2 experts it holds, and the reference
    given the same 2), the identity part, which every share computes alike
    for its own tokens, counted once, is what the uncut reference gives
    for the whole layer."""
    if window:
        # the shares walk their rows in windows of 16 (ISSUE 60); the
        # layer that holds every expert never does
        monkeypatch.setattr(
            moe, "expert_window",
            lambda cfg, tokens: window if cfg.experts_held else None)
    mod, _ = reference
    cfg = dataclasses.replace(toy_config(experts_held=None), num_experts=8,
                              num_zero_experts=4)
    u = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    _, p = _layer_params(cfg, u[None])
    p = {**p, "router_bias": 0.05 * jax.random.normal(
        jax.random.PRNGKey(8), (12,))}

    def ref_part(first, count):
        block = {"w_r": p["router"]["kernel"], "b_r": p["router_bias"],
                 "w_gate_up": p["w_gate_up"][first:first + count],
                 "w_down": p["w_down"][first:first + count]}
        y, chosen = highest(mod.scmoe, u, block, 3, 6.0, 8, first)
        return np.asarray(y), np.asarray(chosen)

    def program_part(held):
        params = dict(p)
        if held is not None:
            params.update(
                w_gate_up=p["w_gate_up"][held[0]:held[0] + held[1]],
                w_down=p["w_down"][held[0]:held[0] + held[1]])
        y, routing = highest(
            moe.DroplessExperts(dataclasses.replace(
                cfg, experts_held=held)).apply, {"params": params}, u[None])
        return np.asarray(y[0]), routing

    whole, chosen = ref_part(0, 8)
    y, routing = program_part(None)
    experts = np.asarray(routing["experts"])
    np.testing.assert_allclose(y, whole, atol=TOL)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    picked_identity = (experts >= 8).sum()
    assert 0 < picked_identity < experts.size
    assert int(routing["zero_picks"]) == picked_identity
    # the identity part alone: a share that holds no expert of the layer
    probs = np.asarray(highest(
        lambda: jax.nn.softmax(u @ p["router"]["kernel"], -1)))
    identity = (6.0 * np.where(experts >= 8, np.take_along_axis(
        probs, experts, -1), 0.0).sum(-1))[:, None] * np.asarray(u)
    from_program, from_reference = identity.copy(), identity.copy()
    for first in (0, 2, 4, 6):
        y, mine = program_part((first, 2))
        # the router is the whole layer's, whichever experts are held
        np.testing.assert_array_equal(mine["experts"], experts)
        np.testing.assert_array_equal(mine["counts"], routing["counts"])
        theirs, _ = ref_part(first, 2)
        np.testing.assert_allclose(y, theirs, atol=TOL)
        # a share alone is not the whole
        assert np.abs(y - whole).max() > 1000 * TOL
        from_program += y - identity
        from_reference += theirs - identity
    np.testing.assert_allclose(from_program, whole, atol=TOL)
    np.testing.assert_allclose(from_reference, whole, atol=TOL)


@pytest.mark.parametrize("window", [None, 16], ids=["all_rows", "windows"])
def test_rows_behind_the_groups_take_no_part(window, monkeypatch):
    """All of a token's picks on absent or identity experts: its routed
    part is exactly the identity part, whatever lies in the rows behind
    the held groups."""
    if window:
        # the shares walk their rows in windows of 16 (ISSUE 60); the
        # layer that holds every expert never does
        monkeypatch.setattr(
            moe, "expert_window",
            lambda cfg, tokens: window if cfg.experts_held else None)
    cfg = toy_config(experts_held=(12, 4))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64), jnp.float32)
    layer, p = _layer_params(cfg, h)
    y, routing = highest(layer.apply, {"params": p}, h)
    experts = np.asarray(routing["experts"])
    held = (experts >= 12) & (experts < 16)
    elsewhere = ~held.any(-1)
    assert elsewhere.any() and not elsewhere.all()
    probs = np.asarray(highest(
        lambda: jax.nn.softmax(h[0] @ p["router"]["kernel"], -1)))
    identity = (6.0 * np.where(experts >= 16, np.take_along_axis(
        probs, experts, -1), 0.0).sum(-1))[:, None] * np.asarray(h[0])
    np.testing.assert_allclose(np.asarray(y[0])[elsewhere],
                               identity[elsewhere], rtol=1e-6, atol=1e-7)
    assert np.abs(np.asarray(y[0]) - identity)[~elsewhere].max() > 0.01
    assert int(routing["counts"].sum()) + int(routing["zero_picks"]) == 64 * 3


# ---- the driver -------------------------------------------------------

def test_the_routers_balance_leaves_the_mean_input_unscored():
    """``balance_routers``: every router loses one direction, that of the
    mean of its layer's input, which then scores 0 with every output, the
    identity experts' too; nothing else of the model moves."""
    model = GPTModel(toy_config())
    params = init_params(model, jax.random.PRNGKey(3),
                         jnp.ones((1, 8), jnp.int32))
    moved = DRIVER.balance_routers(model, params, jax.random.PRNGKey(4),
                                   TOY["vocab_size"])
    changed = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(moved)) if not np.array_equal(a, b)]
    assert changed == [f"['params']['h{i}']['moe']['router']['kernel']"
                       for i in (0, 2)]
    for layer in ("h0", "h2"):
        w_new = np.asarray(moved["params"][layer]["moe"]["router"]["kernel"])
        w_old = np.asarray(params["params"][layer]["moe"]["router"]["kernel"])
        assert w_new.shape == (64, 24)
        u, sizes, _ = np.linalg.svd(w_old - w_new)
        assert sizes[1] < 1e-5 * sizes[0]            # one direction
        assert np.abs(u[:, 0] @ w_new).max() < 1e-5  # and none of it left


def test_driver_runs_the_toy_cell(toy_context, checks_the_same_requests):
    """``chipbench/drivers/serve_scmoe.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' balance, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the check against the reference; and what the
    cell's readers make of it."""
    obs = DRIVER.run(toy_context("toy-longcat.agent", "toy-agent", 3.0, 2,
                                 checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["compiles_in_window"] == 0
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 2
    assert obs["expert_bytes"] == 3 * 64 * 32 * 2
    # a CPU trace has no TPU plane
    assert obs["decode_trace"] == {} and obs["chunk_trace"] == {}
    obs.update(peaks=None, config=TOY)
    # four cache entries for two published layers; bfloat16 in the driver
    assert run.metric_reader("kv_cache_bytes_per_position")(obs) == \
        4 * (16 + 8) * 2
    local = run.metric_reader("moe_local_rows_pct")(obs)
    assert 5 < local < 35               # 4 of 24 outputs
    zero = run.metric_reader("zero_expert_picks_pct")(obs)
    assert 15 < zero < 55               # 8 of 24 outputs
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 4
    assert run.metric_reader("scmoe_decode_hbm_roofline_pct")(obs) is None
    assert run.metric_reader("shortcut_moe_chunk_share_pct")(obs) is None
    assert run.metric_reader("mla_decode_roofline_pct")(obs) is None
    spans = [s for s in obs["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 8)
        for s in spans)


@pytest.mark.parametrize("control", ["cache_in_float8",
                                     "matrices_in_float8",
                                     "identity_left_out"])
def test_driver_fails_a_control(toy_context, monkeypatch, control,
                                checks_the_same_requests):
    """The controls the cell's limits are set against
    (``chipbench/controls_longcat.py``), planted at the toy size: each
    serves plausible tokens and is not correct."""
    from chipbench import controls_longcat
    controls_longcat.CONTROLS[control](TOY, monkeypatch.setattr)
    obs = DRIVER.run(toy_context("toy-longcat.agent", "toy-agent", 3.0, 0,
                                 checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and checks["checked_requests"] == 4
    assert not obs["correct"], checks
