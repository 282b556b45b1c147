"""An expert layer that holds a share of its experts, walked a window of its
sorted rows at a time (``moe.windowed_expert_sum``, ISSUE 60), against the
same layer over all its rows at once, on the CPU in float32 with the
kernels interpreted: a gated layer, an ungated one (squared ReLU, ``w_up``
stored transposed: Nemotron-H's) and one with identity experts
(LongCat-Flash's), with no row local, fewer than a window, exactly one, two
and three windows' worth and every row local; the rule that picks the
window from a call's shape (``moe.expert_window``) at the six cells' chunk
and tick shapes; and the counters a generator and an engine feed from what
their chunk steps said.  The shares of a layer against the plain references
are in the configurations' own files (``test_the_shares_..._the_whole``,
each also under a window)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import moe
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import highest, init_params
from chipbench import run

T, K, H = 32, 3, 32
ROWS = T * K
HELD = (4, 4)
KINDS = {
    "gated": {},
    "ungated": {"expert_gated": False, "activation": "relu2"},
    "identity": {"num_zero_experts": 8, "router_bias": True},
}


def layer_config(kind):
    return GPTConfig(**{**dict(
        hidden_size=H, num_experts=16, num_experts_per_tok=K,
        moe_intermediate_size=16, mlp="experts", fused_gate_up=True,
        activation="silu", num_shared_experts=1, experts_held=HELD,
        dtype=jnp.float32), **KINDS[kind]})


def steered(kind, towards):
    """(layer, parameters, x (1, T, H)): the router steered by a channel
    of the input that is the same in every token: ``towards`` -1 no pick on
    a held expert, +1 every pick on one, 0 as the weights fall."""
    layer = moe.DroplessExperts(layer_config(kind))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, H), jnp.float32)
    x = x.at[..., 0].set(4.0)
    params = init_params(layer, jax.random.PRNGKey(4), x)
    kernel = params["params"]["router"]["kernel"]
    kernel = kernel.at[0].set(0.0).at[
        0, HELD[0]:HELD[0] + HELD[1]].set(25.0 * towards)
    params["params"]["router"]["kernel"] = kernel
    return layer, params, x


def applied(layer, params, x, window, monkeypatch):
    monkeypatch.setattr(moe, "expert_window", lambda *_: window)
    return highest(layer.apply, params, x)


# (towards, the window from the local rows, passes): a window of its own
# for each count of passes the issue names, and a last window that would
# pass the end of the rows (every row local, two thirds of them a window)
CASES = {
    "none_local": (-1, lambda local: 16, 0),
    "under_one_window": (0, lambda local: local + 5, 1),
    "exactly_one_window": (0, lambda local: local, 1),
    "two_passes": (0, lambda local: -(-local // 2), 2),
    "three_passes": (0, lambda local: -(-local // 3), 3),
    "all_local_in_one": (1, lambda local: local, 1),
    "all_local_last_window_starts_earlier": (
        1, lambda local: 2 * local // 3, 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_windows_give_what_all_rows_give(kind, case, monkeypatch):
    towards, window_of, passes = CASES[case]
    layer, params, x = steered(kind, towards)
    want, routing = applied(layer, params, x, None, monkeypatch)
    assert "window_passes" not in routing
    local = int(routing["counts"][HELD[0]:HELD[0] + HELD[1]].sum())
    assert local == {-1: 0, 1: ROWS}.get(towards, local) and \
        (towards or 8 < local < ROWS // 2)
    window = window_of(local)
    got, said = applied(layer, params, x, window, monkeypatch)
    assert int(said["window_passes"]) == passes == -(-local // window)
    for name in routing:
        np.testing.assert_array_equal(said[name], routing[name])
    # the order of a float32 sum of at most k terms
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert float(jnp.abs(want).max()) > 0.01


def test_a_gradient_through_the_windows_is_jaxs_own_error(monkeypatch):
    """No third path for a gradient (``windowed_expert_sum``'s docstring):
    under ``jit`` the loop's trip count is traced, and such a loop is not
    differentiated in reverse."""
    layer, params, x = steered("gated", 0)
    monkeypatch.setattr(moe, "expert_window", lambda *_: 16)
    with pytest.raises(ValueError, match="Reverse-mode differentiation"):
        jax.jit(jax.grad(lambda p: layer.apply(p, x)[0].sum()))(params)
    monkeypatch.setattr(moe, "expert_window", lambda *_: None)
    jax.jit(jax.grad(lambda p: layer.apply(p, x)[0].sum()))(params)


# ---- the rule ------------------------------------------------------------

# cell: (the window of a chunk of 1,024 positions, the tokens of its tick:
# the engine's rows, two positions a row where the tick verifies a draft)
CELLS = {
    "longcat-flash-1chip": (512, 32),
    "nemotron-3-nano-30b-a3b-1chip": (768, 64),
    "mimo-v2-flash-1chip": (1024, 32),
    "glm-5-1chip": (1024, 2 * 16),
    "deepseek-v2-1chip": (1536, 32),
    "dots3-note-prev-1chip": (2048, 16),
}


def cell_config(name):
    hf = run.load_json(run.HERE, "configs", name + ".json")
    assert hf["serve"]["prefill_chunk"] == 1024
    return hf, run.load_module("drivers", "serve_mla").model_config(hf)


@pytest.mark.parametrize("step", ["chunk", "tick", "all_experts_held"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_rule_takes_a_cells_chunks_and_leaves_its_ticks(name, step):
    window, tick = CELLS[name]
    hf, cfg = cell_config(name)
    rows = hf["serve"]["engine_rows"]
    assert tick in (rows, rows * (1 + cfg.num_nextn_predict_layers))
    if step == "chunk":
        assert moe.expert_window(cfg, 1024) == window
        assert window % 128 == 0 and \
            window * 4 <= 1024 * cfg.num_experts_per_tok
        expected = 1024 * cfg.num_experts_per_tok * cfg.experts_held[1] / (
            cfg.num_experts + cfg.num_zero_experts)
        assert window == 2 * expected
    elif step == "tick":
        assert moe.expert_window(cfg, tick) is None
    else:
        whole = dataclasses.replace(cfg, experts_held=None)
        assert moe.expert_window(whole, 1024) is None
        assert moe.expert_window(whole, 65536) is None


# ---- the counters ----------------------------------------------------------

CHUNK = 256


@pytest.fixture(scope="module")
def served():
    """A decoder of two expert layers that hold 4 of 64 experts, top-2,
    whose chunk of 256 positions walks a window of 128 of its 512 rows
    (and whose decode of a few rows walks none)."""
    cfg = GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        seq_len=2 * CHUNK + 64, positions="rotary", norm="rmsnorm",
        mlp="experts", num_experts=64, num_experts_per_tok=2,
        moe_intermediate_size=16, fused_gate_up=True, activation="silu",
        experts_held=(8, 4), tie_embeddings=False, dtype=jnp.float32)
    assert moe.expert_window(cfg, CHUNK) == 128
    assert moe.expert_window(cfg, 4) is None
    model = GPTModel(cfg)
    params = init_params(model, jax.random.PRNGKey(0),
                         jnp.ones((1, 8), jnp.int32))
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (CHUNK + 40,), 0, cfg.vocab_size))
    return cfg, model, params, prompt


def counters():
    snapshot = tmetrics.get_registry().snapshot()
    return (snapshot.get("alpa_moe_window_calls_total", 0.0),
            snapshot.get("alpa_moe_window_passes_total", 0.0))


def test_a_generators_chunks_feed_the_counters(served, monkeypatch):
    """Two chunks of two expert layers: four calls counted once the
    tokens are read (the second chunk's padding is one token 216 times,
    whose picks may all land here: a call then takes more passes than
    one); and the tokens are those of the same prompt prefilled over all
    rows."""
    cfg, model, params, prompt = served
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    calls, passes = counters()
    out = gen.generate(prompt[None], GenerationConfig(max_new_tokens=3))
    now = counters()
    assert now[0] - calls == 4 and 0 < now[1] - passes <= 4 * 4
    assert not gen._window_passes
    monkeypatch.setattr("alpa_tpu.serve.generation.expert_window",
                        lambda *_: None)
    monkeypatch.setattr(moe, "expert_window", lambda *_: None)
    plain = Generator(model, params, cfg, prefill_chunk=CHUNK)
    np.testing.assert_array_equal(
        plain.generate(prompt[None], GenerationConfig(max_new_tokens=3)),
        out)
    assert counters() == now


def test_an_engines_admissions_feed_the_counters(served):
    """A chunked admission's passes are counted with the next tick's
    routing, which the host reads behind the tokens sampled after it."""
    cfg, model, params, prompt = served
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=2,
                                      chunked_admission=True)
    calls, _ = counters()
    try:
        tokens = engine.submit(prompt, GenerationConfig(max_new_tokens=4))
    finally:
        engine.shutdown()
    assert len(tokens) == len(prompt) + 4
    assert counters()[0] - calls == 4
    assert not gen._window_passes
