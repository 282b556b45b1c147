"""Torch frontend: fx -> jax conversion parity with torch eager
(ref alpa/torch tests)."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax
import jax.numpy as jnp

import alpa_tpu
from alpa_tpu.torch_frontend import functionalize, set_mode


def _compare(module, *torch_inputs, rtol=1e-4):
    fn, params = functionalize(module)
    with torch.no_grad():
        expected = module(*torch_inputs).numpy()
    jax_inputs = [jnp.asarray(t.numpy()) for t in torch_inputs]
    got = np.asarray(fn(params, *jax_inputs))
    np.testing.assert_allclose(got, expected, rtol=rtol, atol=rtol)
    return fn, params, jax_inputs


class _MHAWrap(torch.nn.Module):
    """Self-attention through nn.MultiheadAttention as an fx leaf."""

    def __init__(self, mha):
        super().__init__()
        self.mha = mha

    def forward(self, x):
        out, _ = self.mha(x, x, x)
        return out


class TestConversion:

    def test_mlp(self):
        m = torch.nn.Sequential(
            torch.nn.Linear(16, 32), torch.nn.ReLU(),
            torch.nn.Linear(32, 8), torch.nn.Softmax(dim=-1))
        _compare(m, torch.randn(4, 16))

    def test_functional_ops(self):

        class Net(torch.nn.Module):

            def __init__(self):
                super().__init__()
                self.fc = torch.nn.Linear(8, 8)

            def forward(self, x):
                h = torch.nn.functional.gelu(self.fc(x))
                h = h.transpose(0, 1).contiguous()
                h = h.view(-1)
                return (h * 2 + 1).mean()

        _compare(Net(), torch.randn(3, 8))

    def test_embedding_layernorm(self):

        class Net(torch.nn.Module):

            def __init__(self):
                super().__init__()
                self.emb = torch.nn.Embedding(32, 16)
                self.ln = torch.nn.LayerNorm(16)
                self.head = torch.nn.Linear(16, 4)

            def forward(self, ids):
                return self.head(self.ln(self.emb(ids)))

        m = Net()
        fn, params = functionalize(m)
        ids_t = torch.randint(0, 32, (2, 6))
        with torch.no_grad():
            expected = m(ids_t).numpy()
        got = np.asarray(fn(params, jnp.asarray(ids_t.numpy())))
        np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)

    def test_conv_bn_pool(self):
        m = torch.nn.Sequential(
            torch.nn.Conv2d(3, 8, 3, padding=1),
            torch.nn.BatchNorm2d(8),
            torch.nn.ReLU(),
            torch.nn.MaxPool2d(2),
            torch.nn.Flatten(1),
            torch.nn.Linear(8 * 4 * 4, 10),
        ).eval()
        _compare(m, torch.randn(2, 3, 8, 8))

    def test_avg_pools_and_group_norm(self):
        m = torch.nn.Sequential(
            torch.nn.Conv2d(3, 8, 3, padding=1),
            torch.nn.GroupNorm(4, 8),
            torch.nn.ReLU(),
            torch.nn.AvgPool2d(2),
            torch.nn.AdaptiveAvgPool2d((1, 1)),
            torch.nn.Flatten(1),
        ).eval()
        _compare(m, torch.randn(2, 3, 8, 8))

    def test_conv_transpose2d(self):
        for groups, opad in ((1, 0), (2, 1)):
            m = torch.nn.Sequential(
                torch.nn.ConvTranspose2d(4, 6, 3, stride=2, padding=1,
                                         output_padding=opad,
                                         groups=groups)).eval()
            _compare(m, torch.randn(2, 4, 5, 5))

    def test_batch_norm_1d(self):
        m = torch.nn.Sequential(torch.nn.Linear(8, 16),
                                torch.nn.BatchNorm1d(16)).eval()
        # populate non-trivial running stats
        with torch.no_grad():
            m[1].running_mean += torch.randn(16) * 0.1
            m[1].running_var += torch.rand(16)
        _compare(m, torch.randn(4, 8))

    def test_batch_norm_no_tracked_stats(self):
        """track_running_stats=False modules use batch statistics even in
        eval mode (torch semantics) and must not KeyError on the missing
        running_mean/var buffers."""
        m = torch.nn.Sequential(
            torch.nn.Linear(8, 16),
            torch.nn.BatchNorm1d(16, track_running_stats=False)).eval()
        _compare(m, torch.randn(4, 8))

    def test_multihead_attention(self):
        for batch_first in (True, False):
            m = torch.nn.MultiheadAttention(16, 4,
                                            batch_first=batch_first).eval()
            # trace through a wrapper module so fx sees a call_module node
            wrap = _MHAWrap(m).eval()
            fn, params = functionalize(wrap)
            x = torch.randn((2, 6, 16) if batch_first else (6, 2, 16))
            with torch.no_grad():
                expected = wrap(x).numpy()
            got = np.asarray(fn(params, jnp.asarray(x.numpy())))
            np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    def test_scaled_dot_product_attention(self):

        class Net(torch.nn.Module):

            def forward(self, q, k, v):
                return torch.nn.functional.scaled_dot_product_attention(
                    q, k, v, is_causal=True)

        q = torch.randn(2, 4, 8, 16)
        _compare(Net(), q, torch.randn(2, 4, 8, 16),
                 torch.randn(2, 4, 8, 16))

    def test_sdpa_causal_cross_length(self):
        """torch's is_causal is TOP-LEFT aligned when lq != lk (ADVICE r3)."""
        from alpa_tpu.torch_frontend.converter import \
            _scaled_dot_product_attention
        q = torch.randn(2, 4, 5, 16)
        k = torch.randn(2, 4, 9, 16)
        v = torch.randn(2, 4, 9, 16)
        with torch.no_grad():
            expected = torch.nn.functional.scaled_dot_product_attention(
                q, k, v, is_causal=True).numpy()
        got = np.asarray(_scaled_dot_product_attention(
            jnp.asarray(q.numpy()), jnp.asarray(k.numpy()),
            jnp.asarray(v.numpy()), is_causal=True))
        np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    def test_batch_norm_training_uses_batch_stats(self):
        """training=True normalizes with batch statistics and warns that
        running-stat updates are dropped (ADVICE r3)."""
        import warnings as _warnings
        from alpa_tpu.torch_frontend.converter import _batch_norm
        x = torch.randn(8, 6)
        rm, rv = torch.randn(6) * 0.1, torch.rand(6) + 0.5
        w, b = torch.randn(6), torch.randn(6)
        with torch.no_grad():
            expected = torch.nn.functional.batch_norm(
                x, rm.clone(), rv.clone(), w, b, training=True).numpy()
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            got = np.asarray(_batch_norm(
                jnp.asarray(x.numpy()), jnp.asarray(rm.numpy()),
                jnp.asarray(rv.numpy()), jnp.asarray(w.numpy()),
                jnp.asarray(b.numpy()), training=True))
        assert any("training=True" in str(r.message) for r in rec)
        np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    def test_unmapped_op_clear_error(self):

        class Net(torch.nn.Module):

            def forward(self, x):
                return torch.fft.fft(x).real

        fn, params = functionalize(Net())
        with pytest.raises(NotImplementedError, match="no jax mapping"):
            fn(params, jnp.ones((4,)))


class TestTrainConverted:

    def test_train_torch_model_with_parallelize(self):
        """The converted function trains under @alpa_tpu.parallelize."""
        import optax

        m = torch.nn.Sequential(torch.nn.Linear(16, 32), torch.nn.Tanh(),
                                torch.nn.Linear(32, 1))
        fn, params = functionalize(m)
        set_mode("dist")
        x = jnp.asarray(np.random.RandomState(0).randn(64, 16),
                        jnp.float32)
        y = jnp.asarray(np.random.RandomState(1).randn(64, 1), jnp.float32)
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)

        @alpa_tpu.parallelize(method=alpa_tpu.DataParallel(),
                              batch_argnums=(2, 3),
                              donate_argnums=(0, 1))
        def step(params, opt_state, x, y):

            def loss_fn(p):
                out = fn(p, x)
                return ((out - y)**2).mean()

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(params)
            updates, opt_state2 = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state2, loss

        losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8, losses


def _make_resnet18(num_classes=10):
    """Stock torchvision resnet18 structure, built directly in torch
    (torchvision isn't installed in this image; this is the same
    BasicBlock/ResNet layout, ref torchvision.models.resnet)."""

    class BasicBlock(torch.nn.Module):
        def __init__(self, cin, cout, stride=1):
            super().__init__()
            self.conv1 = torch.nn.Conv2d(cin, cout, 3, stride, 1,
                                         bias=False)
            self.bn1 = torch.nn.BatchNorm2d(cout)
            self.relu = torch.nn.ReLU(inplace=True)
            self.conv2 = torch.nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
            self.bn2 = torch.nn.BatchNorm2d(cout)
            self.down = None
            if stride != 1 or cin != cout:
                self.down = torch.nn.Sequential(
                    torch.nn.Conv2d(cin, cout, 1, stride, bias=False),
                    torch.nn.BatchNorm2d(cout))

        def forward(self, x):
            identity = x if self.down is None else self.down(x)
            out = self.relu(self.bn1(self.conv1(x)))
            out = self.bn2(self.conv2(out))
            out += identity
            return self.relu(out)

    class ResNet18(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = torch.nn.Conv2d(3, 64, 7, 2, 3, bias=False)
            self.bn1 = torch.nn.BatchNorm2d(64)
            self.relu = torch.nn.ReLU(inplace=True)
            self.maxpool = torch.nn.MaxPool2d(3, 2, 1)
            layers = []
            cin = 64
            for cout, stride in ((64, 1), (64, 1), (128, 2), (128, 1),
                                 (256, 2), (256, 1), (512, 2), (512, 1)):
                layers.append(BasicBlock(cin, cout, stride))
                cin = cout
            self.layers = torch.nn.Sequential(*layers)
            self.avgpool = torch.nn.AdaptiveAvgPool2d((1, 1))
            self.fc = torch.nn.Linear(512, num_classes)

        def forward(self, x):
            x = self.maxpool(self.relu(self.bn1(self.conv1(x))))
            x = self.layers(x)
            x = self.avgpool(x)
            x = torch.flatten(x, 1)
            return self.fc(x)

    return ResNet18()


class TestResNet18:

    def test_resnet18_converts_and_matches_eager(self):
        m = _make_resnet18().eval()
        _compare(m, torch.randn(2, 3, 32, 32), rtol=5e-3)

    @pytest.mark.slow
    def test_resnet18_trains_on_mesh(self):
        """Converted resnet18 trains end-to-end under @parallelize on the
        8-device mesh (VERDICT r2 next #9).  BatchNorm uses frozen
        running stats (eval-mode functionalization); conv/fc/affine
        weights train."""
        import optax

        m = _make_resnet18(num_classes=10)
        fn, params, buffers = functionalize(m, split_buffers=True)
        set_mode("dist")
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(16, 3, 32, 32), jnp.float32)
        y = jnp.asarray(rs.randint(0, 10, (16,)), jnp.int32)
        tx = optax.adam(3e-3)
        opt_state = tx.init(params)

        @alpa_tpu.parallelize(method=alpa_tpu.DataParallel(),
                              batch_argnums=(2, 3),
                              donate_argnums=(0, 1))
        def step(params, opt_state, x, y):

            def loss_fn(p):
                logits = fn({**p, **buffers}, x)
                onehot = jax.nn.one_hot(y, 10)
                return -(jax.nn.log_softmax(logits) * onehot).sum(-1).mean()

            loss, grads = alpa_tpu.value_and_grad(loss_fn)(params)
            updates, opt_state2 = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state2, loss

        losses = []
        for _ in range(15):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        # 16 random samples, 10 classes: adam should be well on the way
        # to memorizing them
        assert losses[-1] < losses[0] * 0.7, losses


class TestOptimAndTrainer:

    def test_adam_matches_torch_adam(self):
        """Functional adam == torch.optim.Adam trajectories (the reference
        ships a placeholder here, ref alpa/torch/optim/adam.py:24)."""
        from alpa_tpu.torch_frontend.optim import adam

        m = torch.nn.Linear(4, 3)
        x = torch.randn(8, 4)
        y = torch.randn(8, 3)
        opt = torch.optim.Adam(m.parameters(), lr=1e-2)
        fn, params = functionalize(m)
        optim_func, _init, state = adam(lr=1e-2)(params)

        xj, yj = jnp.asarray(x.numpy()), jnp.asarray(y.numpy())
        for _ in range(5):
            # torch side
            opt.zero_grad()
            loss = ((m(x) - y)**2).mean()
            loss.backward()
            opt.step()
            # jax side
            grads = jax.grad(
                lambda p: ((fn(p, xj) - yj)**2).mean())(params)
            params, state = optim_func(params, state, grads)
        with torch.no_grad():
            want = m(x).numpy()
        got = np.asarray(fn(params, xj))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_trainer_loop(self):
        """TorchTrainer: torch module in, parallel train steps out
        (ref alpa/torch/trainer.py train_torch_module)."""
        from alpa_tpu.torch_frontend import TorchTrainer
        from alpa_tpu.torch_frontend.optim import sgd

        # the data and the weights are drawn: unseeded, one draw in four
        # loses less than a fifth of its loss in ten steps
        torch.manual_seed(0)
        m = torch.nn.Sequential(torch.nn.Linear(16, 32), torch.nn.Tanh(),
                                torch.nn.Linear(32, 1))
        trainer = TorchTrainer(
            m, loss_func=lambda out, tgt: ((out - tgt)**2).mean(),
            optim_gen=sgd(lr=5e-2, momentum=0.9),
            method=alpa_tpu.DataParallel())
        x = torch.randn(64, 16)
        y = torch.randn(64, 1)
        losses = trainer.fit([(x, y)] * 10)
        assert losses[-1] < losses[0] * 0.8, losses


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
