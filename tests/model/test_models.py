"""Model zoo smoke + parallelization tests."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training import train_state

import alpa_tpu
from alpa_tpu import ShardParallel
from alpa_tpu.model.bert_model import BertConfig, BertForMaskedLM
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel, init_kv_caches
from alpa_tpu.model.moe import MoEConfig, MoELMModel
from alpa_tpu.model.model_util import cross_entropy_loss
from alpa_tpu.model.wide_resnet import WResNetConfig, WideResNet
from alpa_tpu.testing import init_params, jitted


class TestGPT:

    def test_forward_and_cache_decode(self):
        """Incremental decoding with KV cache == full forward."""
        cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                        seq_len=16, vocab_size=64)
        model = GPTModel(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (2, 16), 0, 64)
        params = init_params(model, rng, ids)
        full_logits = jitted(model.apply)(params, ids)

        caches = init_kv_caches(cfg, batch_size=2)
        decode = jitted(model.apply)
        for t in range(16):
            step_ids = ids[:, t:t + 1]
            pos = jnp.full((2, 1), t, jnp.int32)
            logits, caches = decode(params, step_ids, pos, caches)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full_logits[:, -1]),
                                   rtol=2e-4, atol=2e-4)


class TestMoE:

    @pytest.mark.slow
    def test_moe_trains_with_expert_parallel(self):
        cfg = MoEConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, seq_len=16, num_experts=4,
                        expert_group_size=32, moe_every=2, ep_axis=None)
        model = MoELMModel(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (8, 16), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        params = init_params(model, rng, ids)
        state = train_state.TrainState.create(apply_fn=model.apply,
                                              params=params,
                                              tx=optax.adam(1e-3))

        @alpa_tpu.parallelize(method=ShardParallel())
        def step(state, batch):

            def loss_fn(p):
                logits, aux = state.apply_fn(p, batch["ids"])
                return cross_entropy_loss(
                    logits.astype(jnp.float32),
                    batch["labels"]) + 0.01 * aux

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        batch = {"ids": ids, "labels": labels}
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses

    def test_gating_respects_capacity(self):
        from alpa_tpu.model.moe import top2_gating
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 4))
        combine, dispatch, aux = top2_gating(logits, capacity=8)
        assert combine.shape == (2, 32, 4, 8)
        # each expert slot used by at most one token
        per_slot = dispatch.sum(axis=1)  # (G, E, C)
        assert float(per_slot.max()) <= 1.0 + 1e-6
        assert np.isfinite(float(aux))

    def test_moe_decode_matches_full_context(self):
        """Mixtral-style MoE decoding: KV-cached incremental decode
        equals the full-context forward.  capacity_factor >= num_experts
        guarantees no capacity drops, which would otherwise make routing
        depend on how many tokens share the pass."""
        from alpa_tpu.model.moe import init_moe_kv_caches
        cfg = MoEConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, seq_len=16, num_experts=4,
                        capacity_factor=4.0, expert_group_size=32,
                        moe_every=2, ep_axis=None)
        model = MoELMModel(cfg)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (2, 10)).astype(np.int32)
        params = init_params(model, jax.random.PRNGKey(0), jnp.asarray(ids))
        apply = jitted(model.apply)
        full, _aux = apply(params, jnp.asarray(ids))
        full = np.asarray(full)

        caches = init_moe_kv_caches(cfg, 2)
        logits_p, caches = apply(params, jnp.asarray(ids[:, :6]), None,
                                 caches)
        np.testing.assert_allclose(np.asarray(logits_p), full[:, :6],
                                   rtol=5e-4, atol=5e-4)
        for t in range(6, 10):
            # learned position table: absolute positions must be passed
            # for incremental decode (the Generator does this)
            pos = jnp.full((2, 1), t, jnp.int32)
            step, caches = apply(params, jnp.asarray(ids[:, t:t + 1]), pos,
                                 caches)
            np.testing.assert_allclose(np.asarray(step)[:, 0], full[:, t],
                                       rtol=5e-4, atol=5e-4)

    def test_moe_serves_through_generator(self):
        """The serving Generator drives the MoE LM unchanged (cache-as-
        invars contract parity)."""
        from alpa_tpu.serve.generation import GenerationConfig, Generator
        cfg = MoEConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, seq_len=32, num_experts=4,
                        capacity_factor=4.0, expert_group_size=64,
                        moe_every=2, ep_axis=None)
        model = MoELMModel(cfg)
        params = init_params(model, jax.random.PRNGKey(0),
                             jnp.ones((1, 8), jnp.int32))
        gen = Generator(model, params, cfg, batch_size=1,
                        prompt_buckets=[8])
        out = gen.generate(np.array([[1, 2, 3]], np.int32),
                           GenerationConfig(max_new_tokens=5))
        assert out.shape == (1, 8)
        # greedy replay without cache
        replay = np.array([[1, 2, 3]], np.int32)
        for _ in range(5):
            lg, _aux = jitted(model.apply)(params, jnp.asarray(replay))
            nxt = np.argmax(np.asarray(lg[:, -1]), -1)
            replay = np.concatenate([replay, nxt[:, None].astype(np.int32)],
                                    axis=1)
        np.testing.assert_array_equal(out, replay)


class TestBert:

    def test_mlm_forward_and_train(self):
        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, seq_len=16)
        model = BertForMaskedLM(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (4, 16), 0, 64)
        params = init_params(model, rng, ids)
        apply = jitted(model.apply)
        logits = apply(params, ids)
        assert logits.shape == (4, 16, 64)
        # bidirectional: perturbing a late token changes early logits
        ids2 = ids.at[:, -1].set((ids[:, -1] + 1) % 64)
        logits2 = apply(params, ids2)
        assert not np.allclose(np.asarray(logits[:, 0]),
                               np.asarray(logits2[:, 0]))

    def test_attention_mask_blocks_padding(self):
        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, seq_len=16)
        model = BertForMaskedLM(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (2, 16), 0, 64)
        mask = jnp.concatenate([jnp.ones((2, 12), jnp.int32),
                                jnp.zeros((2, 4), jnp.int32)], axis=1)
        params = init_params(model, rng, ids, mask)
        apply = jitted(model.apply)
        base = apply(params, ids, mask)
        # changing tokens under the padding mask must not change valid
        # positions' logits
        ids2 = ids.at[:, -1].set((ids[:, -1] + 7) % 64)
        out2 = apply(params, ids2, mask)
        np.testing.assert_allclose(np.asarray(base[:, :12]),
                                   np.asarray(out2[:, :12]),
                                   rtol=1e-6, atol=1e-6)
        # without the mask they do change (sanity)
        out3 = apply(params, ids2)
        assert not np.allclose(np.asarray(base[:, :12]),
                               np.asarray(out3[:, :12]))

    def test_pretraining_heads_and_loss(self):
        from alpa_tpu.model.bert_model import (BertForPreTraining,
                                               bert_pretraining_loss)
        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, seq_len=16, tie_word_embeddings=True)
        model = BertForPreTraining(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (4, 16), 0, 64)
        params = init_params(model, rng, ids)
        # tied decoder: no separate (H, V) decoder kernel in the tree
        flat = jax.tree_util.tree_leaves_with_path(params)
        assert not any("decoder/" in jax.tree_util.keystr(p).replace(
            "']['", "/") and l.ndim == 2 for p, l in flat)
        mlm_logits, nsp_logits = jitted(model.apply)(params, ids)
        assert mlm_logits.shape == (4, 16, 64)
        assert nsp_logits.shape == (4, 2)

        mlm_labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                        64)
        mlm_weights = (jax.random.uniform(jax.random.PRNGKey(2),
                                          (4, 16)) < 0.15).astype(
                                              jnp.float32)
        nsp_labels = jnp.array([0, 1, 0, 1])

        def loss_fn(p):
            ml, nl = model.apply(p, ids)
            return bert_pretraining_loss(ml, nl, mlm_labels, mlm_weights,
                                         nsp_labels)

        loss, grads = jitted(jax.value_and_grad(loss_fn))(params)
        assert np.isfinite(float(loss))
        # the tied embedding table receives gradient from the MLM head
        g_emb = grads["params"]["bert"]["word_embeddings"]["embedding"]
        assert float(jnp.abs(g_emb).max()) > 0


class TestWideResNet:

    def test_forward_and_parallel_train(self):
        cfg = WResNetConfig(num_layers=50, width_factor=1, num_classes=10)
        model = WideResNet(cfg)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (8, 32, 32, 3))
        y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 10)
        params = init_params(model, rng, x)
        state = train_state.TrainState.create(apply_fn=model.apply,
                                              params=params,
                                              tx=optax.sgd(1e-2))

        @alpa_tpu.parallelize(method=alpa_tpu.DataParallel())
        def step(state, batch):

            def loss_fn(p):
                logits = state.apply_fn(p, batch["x"])
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["y"]).mean()

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        state, loss = step(state, {"x": x, "y": y})
        assert np.isfinite(float(loss))


class TestUNetAndConformer:

    def test_unet_forward_and_grad(self):
        from alpa_tpu.model.unet_2d import UNet2D, UNetConfig
        cfg = UNetConfig(block_channels=(16, 32), layers_per_block=1,
                         attention_resolutions=(1,), num_heads=2,
                         time_embed_dim=32)
        model = UNet2D(cfg)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 16, 16, 3))
        t = jnp.array([1, 5])
        params = init_params(model, rng, x, t)
        out = jitted(model.apply)(params, x, t)
        assert out.shape == (2, 16, 16, 3)
        g = jitted(jax.grad(lambda p: (model.apply(p, x, t)**2).mean()))(
            params)
        assert np.isfinite(float(
            jax.tree_util.tree_leaves(g)[0].sum()))

    def test_unet_condition_model(self):
        from alpa_tpu.model.unet_2d import (UNet2DConditionModel,
                                            UNetConditionConfig)
        cfg = UNetConditionConfig(in_channels=4, out_channels=4,
                                  block_out_channels=(16, 32),
                                  down_block_types=("CrossAttnDownBlock2D",
                                                    "DownBlock2D"),
                                  layers_per_block=1, attention_head_dim=8,
                                  cross_attention_dim=24)
        model = UNet2DConditionModel(cfg)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 16, 16, 4))
        t = jnp.array([3, 11])
        ctx = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 24))
        params = init_params(model, rng, x, t, ctx)
        apply = jitted(model.apply)
        out = apply(params, x, t, ctx)
        assert out.shape == (2, 16, 16, 4)
        # conditioning actually conditions: different context, different out
        out2 = apply(params, x, t, ctx + 1.0)
        assert not np.allclose(np.asarray(out), np.asarray(out2))
        g = jitted(jax.grad(
            lambda p: (model.apply(p, x, t, ctx)**2).mean()))(params)
        assert np.isfinite(float(jax.tree_util.tree_leaves(g)[0].sum()))

    def test_unet_auto_sharding_nontrivial(self):
        """The intra-op planner picks a non-trivial (parallel) strategy
        for the UNet's convs on an 8-device mesh (VERDICT r1 next#9)."""
        from alpa_tpu.model.unet_2d import UNet2D, UNetConfig
        from alpa_tpu.util import count_communication_primitives
        cfg = UNetConfig(block_channels=(16, 32), layers_per_block=1,
                         attention_resolutions=(), num_heads=2,
                         time_embed_dim=32)
        model = UNet2D(cfg)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (8, 16, 16, 3))
        t = jnp.arange(8)
        params = init_params(model, rng, x, t)
        state = train_state.TrainState.create(apply_fn=model.apply,
                                              params=params,
                                              tx=optax.sgd(1e-2))

        @alpa_tpu.parallelize(method=ShardParallel())
        def step(state, batch):

            def loss_fn(p):
                out = state.apply_fn(p, batch["x"], batch["t"])
                return (out**2).mean()

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        s, l = step(state, {"x": x, "t": t})
        assert np.isfinite(float(l))
        hlo = step.get_last_executable().get_hlo_text()
        total, ar, ag, rs, a2a = count_communication_primitives(hlo)
        assert total > 0, "UNet compiled with no parallelism at all"

    def test_conformer_asr_with_lengths(self):
        from alpa_tpu.model.conformer import (ConformerConfig,
                                              ConformerForASR)
        cfg = ConformerConfig(num_mel_bins=20, hidden_size=64,
                              num_layers=2, num_heads=4,
                              conv_kernel_size=7, vocab_size=30)
        model = ConformerForASR(cfg)
        rng = jax.random.PRNGKey(0)
        feats = jax.random.normal(rng, (4, 64, 20))
        lengths = jnp.array([64, 48, 32, 16])
        params = init_params(model, rng, feats, lengths)
        apply = jitted(model.apply)
        log_probs, out_lens = apply(params, feats, lengths)
        assert log_probs.shape == (4, 16, 30)     # T subsampled 4x
        assert list(np.asarray(out_lens)) == [16, 12, 8, 4]
        # log-probs normalized
        np.testing.assert_allclose(
            np.asarray(jnp.exp(log_probs).sum(-1)), 1.0, rtol=1e-3)
        # padding invariance: corrupting frames past a row's length must
        # not change its valid outputs
        feats2 = feats.at[1, 48:].set(99.0)
        lp2, _ = apply(params, feats2, lengths)
        np.testing.assert_allclose(np.asarray(log_probs[1, :12]),
                                   np.asarray(lp2[1, :12]), rtol=1e-4,
                                   atol=1e-4)
        # pad-WIDTH invariance: the same audio padded to a different batch
        # width must give the same valid log-probs (no norm reading stats
        # off the time axis)
        solo = jnp.zeros((1, 32, 20)).at[0, :].set(feats[2, :32])
        lp_solo, _ = apply(params, solo, jnp.array([32]))
        np.testing.assert_allclose(np.asarray(log_probs[2, :8]),
                                   np.asarray(lp_solo[0, :8]), rtol=1e-4,
                                   atol=1e-4)

    def test_conformer_forward_parallel(self):
        from alpa_tpu.model.conformer import Conformer, ConformerConfig
        cfg = ConformerConfig(hidden_size=64, num_layers=2, num_heads=4,
                              conv_kernel_size=7)
        model = Conformer(cfg)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (8, 32, 20))
        params = init_params(model, rng, x)
        out = jitted(model.apply)(params, x)
        assert out.shape == (8, 32, 64)
        state = train_state.TrainState.create(apply_fn=model.apply,
                                              params=params,
                                              tx=optax.adam(1e-3))

        @alpa_tpu.parallelize(method=ShardParallel())
        def step(state, batch):

            def loss_fn(p):
                y = state.apply_fn(p, batch["x"])
                return (y**2).mean()

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), loss

        s, l = step(state, {"x": x})
        assert np.isfinite(float(l))


class TestExpertParallelStructure:

    def test_ep_sharding_uses_all_to_all_dispatch(self):
        """Expert parallelism dispatches tokens with the GShard all-to-all
        pattern (explicit shard_map exchange), NOT all-gathers, and
        matches the dense-dispatch numerics for the same grouping."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from alpa_tpu.model.moe import MoEConfig, MoEMLP
        from alpa_tpu.util import count_communication_primitives

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("ep",))
        # expert_group_size 32 -> 8 groups either way (divisible by ep=8)
        kw = dict(vocab_size=64, hidden_size=64, num_layers=1,
                  num_heads=4, seq_len=32, num_experts=8,
                  expert_group_size=32, moe_every=1)
        m = MoEMLP(MoEConfig(ep_axis="ep", **kw))
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (8, 32, 64))
        with jax.set_mesh(mesh):
            params = init_params(m, rng, x)
            f = jax.jit(lambda p, xx: m.apply(p, xx)[0],
                        in_shardings=(None, NamedSharding(mesh, P("ep"))))
            hlo = f.lower(params, x).compile().as_text()
            out_sharded = f(params, x)
        total, ar, ag, rs, a2a = count_communication_primitives(hlo)
        assert a2a >= 2, (total, ar, ag, rs, a2a)
        assert ag == 0, f"dispatch fell back to all-gathers: {ag}"
        out_ref = jitted(MoEMLP(MoEConfig(ep_axis=None, **kw)).apply)(
            params, x)[0]
        np.testing.assert_allclose(np.asarray(out_sharded),
                                   np.asarray(out_ref), rtol=2e-5,
                                   atol=2e-5)


class TestDynamicScale:
    """Mixed-precision loss scaling (ref model_util.py TrainState +
    dynamic scale): scale backs off on overflow, grows after a streak of
    finite steps, and the update is jit-compatible inside a parallel
    train step."""

    def test_scale_state_machine(self):
        from alpa_tpu.model.model_util import DynamicScaleState
        s = DynamicScaleState.create(init_scale=1024.0)
        s = s.replace(growth_interval=2)
        # overflow -> backoff
        s1 = s.update(jnp.bool_(False))
        assert float(s1.scale) == 512.0
        # two finite steps -> growth
        s2 = s1.update(jnp.bool_(True))
        assert float(s2.scale) == 512.0 and int(s2.fine_count) == 1
        s3 = s2.update(jnp.bool_(True))
        assert float(s3.scale) == 1024.0

    def test_scaled_training_step(self):
        from alpa_tpu.model.model_util import (TrainState, all_finite,
                                               cross_entropy_loss)
        cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                        seq_len=16, vocab_size=64, dtype=jnp.bfloat16)
        model = GPTModel(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (8, 16), 0, 64)
        params = init_params(model, rng, ids)
        state = TrainState.create_with_scale(
            apply_fn=model.apply, params=params, tx=optax.sgd(1e-2),
            use_dynamic_scale=True)

        @alpa_tpu.parallelize(method=alpa_tpu.DataParallel(),
                              donate_argnums=())
        def train_step(state, batch):
            ds = state.dynamic_scale

            def loss_fn(p):
                logits = state.apply_fn(p, batch["ids"])
                return cross_entropy_loss(
                    logits.astype(jnp.float32), batch["labels"]) * ds.scale

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
            grads = jax.tree_util.tree_map(lambda g: g / ds.scale, grads)
            finite = all_finite(grads)
            ds2 = ds.update(finite)
            # only apply updates when grads are finite
            new_state = state.apply_gradients(grads=jax.tree_util.tree_map(
                lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads))
            return new_state.replace(dynamic_scale=ds2), loss / ds.scale

        batch = {"ids": ids,
                 "labels": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 16), 0, 64)}
        losses = []
        for _ in range(4):
            state, loss = train_step(state, batch)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]
        assert float(state.dynamic_scale.scale) >= 1.0


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
