"""FollowParallel after a pipeshard executable + tied embeddings across
meshes (VERDICT r1 next#10; ref alpa/follow_parallel.py:25 and the
ReplicatedDistributedArray role, alpa/device_mesh.py:1697).

The tied embedding table is consumed by BOTH the first stage (token
embedding) and the last stage (lm head): one logical tensor resident on
two meshes, with gradient contributions from both summed by the runtime.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training import train_state

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.follow_parallel import FollowParallel
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import cross_entropy_loss
from alpa_tpu.pipeline_parallel.layer_construction import ManualLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import assert_allclose, init_params


def _tied_gpt_setup():
    alpa_tpu.init(cluster="local")
    config = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                       seq_len=16, vocab_size=64, tie_embeddings=True,
                       pipeline_boundary_every=1)
    model = GPTModel(config)
    rng = jax.random.PRNGKey(0)
    batch = {
        "input_ids": jax.random.randint(rng, (8, 16), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                     64),
    }
    params = init_params(model, rng, batch["input_ids"])
    tx = optax.sgd(0.01)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params, tx=tx)
    return model, config, state, batch


def _loss_fn(apply_fn, params, batch):
    logits = apply_fn(params, batch["input_ids"])
    return cross_entropy_loss(logits.astype(jnp.float32), batch["labels"])


class TestFollowPipeshard:

    def test_tied_embeddings_train_then_follow_eval(self):
        model, _config, state, batch = _tied_gpt_setup()
        method = PipeshardParallel(
            num_micro_batches=2, layer_option=ManualLayerOption(),
            stage_option=UniformStageOption(num_stages=2))

        @alpa_tpu.parallelize(method=method, batch_argnums=(1,),
                              donate_argnums=())
        def train_step(state, batch):
            def loss_fn(p):
                return _loss_fn(state.apply_fn, p, batch)
            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        # serial oracle: tied-embedding grads must sum the embed + lm-head
        # contributions (one logical tensor on two meshes)
        def serial_step(state, batch):
            def loss_fn(p):
                return _loss_fn(state.apply_fn, p, batch)
            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        state_p, loss_p = train_step(state, batch)
        # ReplicatedDistributedArray role (ref device_mesh.py:1697): the
        # tied table is one logical tensor placed on BOTH the embedding
        # mesh and the lm-head mesh.
        t_ex = train_step.get_last_executable()
        multi_mesh = [v for v, places in t_ex.input_place.items()
                      if len(places) >= 2]
        assert multi_mesh, "no input replicated across meshes"
        emb_shape = np.asarray(
            state.params["params"]["wte"]["embedding"]).shape
        assert any(tuple(v.aval.shape) == emb_shape for v in multi_mesh), (
            f"tied embedding table not multi-mesh resident: "
            f"{[tuple(v.aval.shape) for v in multi_mesh]}")
        state_s, loss_s = serial_step(state, batch)
        assert_allclose(float(loss_s), float(loss_p), 2e-3, 2e-3)
        assert_allclose(jax.device_get(state_s.params),
                        jax.device_get(state_p.params), 2e-3, 2e-3)

        # eval step follows the train step's placement
        def eval_step(state, batch):
            return _loss_fn(state.apply_fn, state.params, batch)

        follow = FollowParallel(train_step, (state, batch))
        efn = alpa_tpu.parallelize(eval_step, method=follow,
                                   batch_argnums=(1,))
        loss_e = efn(state_p, batch)
        ref = eval_step(jax.device_get(state_p), batch)
        assert_allclose(float(ref), float(loss_e), 2e-3, 2e-3)

        ex = efn.get_last_executable()
        report = getattr(ex, "follow_report", None)
        assert report is not None
        assert report["followed"] > 0
        assert report["mismatched"] == 0, report


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
