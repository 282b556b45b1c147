"""The plain reference against cases small enough to compute by hand."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.run import load_module

ref = load_module("references", "pre_ln_decoder")

LSE = math.log(math.e + 1 / math.e + 1)     # logsumexp of (1, -1, 0)
GELU_1 = 0.8411920      # 0.5 * (1 + tanh(sqrt(2/pi) * 1.044715))


def tiny_weights(mlp: bool):
    """hidden 2, one head, one block, vocabulary 3, eps 0.  q and k are 0,
    so attention is the causal mean of v; v, the output projection and
    (with ``mlp``) both MLP matrices are the identity."""
    eye, zero = np.eye(2, dtype=np.float32), np.zeros((2, 2), np.float32)
    block = {
        "ln1_g": np.ones(2, np.float32), "ln1_b": np.zeros(2, np.float32),
        "w_qkv": np.concatenate([zero, zero, eye], axis=1),
        "b_qkv": np.zeros(6, np.float32),
        "w_o": eye, "b_o": np.zeros(2, np.float32),
        "ln2_g": np.ones(2, np.float32), "ln2_b": np.zeros(2, np.float32),
        "w_fc": eye if mlp else zero, "b_fc": np.zeros(2, np.float32),
        "w_proj": eye if mlp else zero, "b_proj": np.zeros(2, np.float32),
    }
    return {"wte": np.array([[1, 0], [0, 1], [1, 1]], np.float32),
            # rows 0, 1 are never read with pos_offset 2
            "wpe": np.array([[9, 9], [9, 9], [0, 0], [0, 2]], np.float32),
            "blocks": [block],
            "lnf_g": np.ones(2, np.float32), "lnf_b": np.zeros(2, np.float32)}


SETTINGS = {"num_heads": 1, "activation": "relu", "layer_norm_eps": 0.0,
            "pos_offset": 2}


def test_layer_norm_and_gelu_by_hand():
    out = ref.layer_norm(jnp.array([1.0, 2.0, 3.0, 6.0]), 1.0, 0.0, 0.0)
    # mean 3, variance (4 + 1 + 0 + 9) / 4 = 3.5
    np.testing.assert_allclose(
        out, np.array([-2, -1, 0, 3]) / math.sqrt(3.5), rtol=1e-6)
    np.testing.assert_allclose(ref.gelu_tanh(jnp.float32(1.0)), GELU_1,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.gelu_tanh(jnp.float32(-1.0)),
                               GELU_1 - 1.0, rtol=1e-5)


def test_logits_and_loss_by_hand():
    """ids (0, 1): x0 = (1, 0), x1 = (0, 1) + (0, 2) = (0, 3).  LayerNorm of
    two channels is (+1, -1) or (-1, +1).  v0 = (1, -1), v1 = (-1, 1);
    position 0 attends to itself, position 1 to the mean, (0, 0).  So
    x0 = (2, -1), x1 = (0, 3); the final LayerNorm gives (1, -1) and
    (-1, 1); against wte the logits are (1, -1, 0) and (-1, 1, 0)."""
    r = ref.Reference(SETTINGS)
    w = tiny_weights(mlp=False)
    logits = r.logits(w, np.array([0, 1]))
    np.testing.assert_allclose(logits, [[1, -1, 0], [-1, 1, 0]], atol=1e-6)
    np.testing.assert_allclose(r.logits(w, np.array([0, 1]), rows=(1, 1)),
                               [[-1, 1, 0]], atol=1e-6)
    # labels (1, 2): losses LSE + 1 and LSE - 0
    loss = r.lm_loss(w, np.array([[0, 1]]), np.array([[1, 2]]))
    assert loss == pytest.approx(LSE + 0.5, rel=1e-6)


@pytest.mark.parametrize("activation,expected", [
    # after attention x0 = (2, -1), x1 = (0, 3); LayerNorm 2 gives (1, -1)
    # and (-1, 1); the MLP adds act() of that
    ("relu", [[3.0, -1.0], [0.0, 4.0]]),
    ("gelu", [[2 + GELU_1, -1 + (GELU_1 - 1)], [GELU_1 - 1, 3 + GELU_1]]),
])
def test_block_by_hand(activation, expected):
    w = tiny_weights(mlp=True)
    x = ref.embed(w["wte"], w["wpe"], jnp.array([0, 1]), 2)
    np.testing.assert_allclose(x, [[1, 0], [0, 3]])
    out = ref.block(x, w["blocks"][0], 1, activation, 0.0)
    np.testing.assert_allclose(out, expected, atol=2e-6)


def test_causal_mask_hides_the_future():
    r = ref.Reference(SETTINGS)
    w = tiny_weights(mlp=True)
    a = r.logits(w, np.array([0, 1]))
    b = r.logits(w, np.array([0, 2]))
    np.testing.assert_allclose(a[0], b[0], atol=1e-6)
    # (two channels after a LayerNorm are always +-1, so the logits cannot
    # show that position 1 did change; the block's own output can)
    outs = [ref.block(ref.embed(w["wte"], w["wpe"], jnp.array(ids), 2),
                      w["blocks"][0], 1, "relu", 0.0) for ids in ([0, 1],
                                                                  [0, 2])]
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-6)
    assert not np.allclose(outs[0][1], outs[1][1])


def test_weights_from_program_names():
    """The adapter reads the flax names of the program's GPTModel."""
    import jax
    from alpa_tpu.model.gpt_model import GPTModel
    from chipbench import program, run
    config = run.load_json(run.HERE, "configs", "toy-opt.json")
    gcfg = program.gpt_config(config)
    model = GPTModel(gcfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    w = ref.weights_from_program(params)
    assert len(w["blocks"]) == 2
    assert w["wpe"].shape == (128 + 2, 64) and w["wte"].shape == (256, 64)
    # and the reference agrees with the program's own forward pass
    ids = np.arange(3, 19, dtype=np.int32)
    got = ref.Reference(program.reference_settings(config)).logits(w, ids)
    want = model.apply(params, ids[None])[0].astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=0.06)    # bf16 activations
