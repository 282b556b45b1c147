"""Single-program SPMD pipeline (shard_map + ppermute) tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alpa_tpu.parallel.spmd_pipeline import (spmd_pipeline,
                                             spmd_pipeline_1f1b,
                                             stack_pytrees)


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


class TestSpmdPipeline:

    def test_forward_matches_serial(self):
        mesh = _mesh((2, 4), ("dp", "pp"))
        S = 4
        Ws = [
            jax.random.normal(jax.random.PRNGKey(i), (16, 16)) * 0.3
            for i in range(S)
        ]
        stacked = jax.device_put(jnp.stack(Ws), NamedSharding(mesh, P("pp")))
        x = jax.random.normal(jax.random.PRNGKey(9), (8, 16))

        def stage_fn(W, x, _):
            return jnp.tanh(x @ W)

        def pipelined(stacked, x):
            mbs = x.reshape(4, 2, 16)
            y = spmd_pipeline(stage_fn, stacked, mbs, mesh=mesh)
            return y.reshape(8, 16)

        with jax.set_mesh(mesh):
            out = jax.jit(pipelined)(stacked, x)
        h = x
        for W in Ws:
            h = jnp.tanh(h @ W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(h),
                                   rtol=1e-5, atol=1e-6)

    def test_gradients_match_serial(self):
        mesh = _mesh((8,), ("pp",))
        S = 8
        Ws = [
            jax.random.normal(jax.random.PRNGKey(i), (8, 8)) * 0.3
            for i in range(S)
        ]
        stacked_host = jnp.stack(Ws)
        stacked = jax.device_put(stacked_host, NamedSharding(mesh, P("pp")))
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 8))

        def stage_fn(W, x, _):
            return jnp.tanh(x @ W)

        def loss_p(stacked, x):
            mbs = x.reshape(2, 2, 8)
            y = spmd_pipeline(stage_fn, stacked, mbs, mesh=mesh)
            return (y**2).mean()

        def loss_s(stacked, x):
            h = x
            for s in range(S):
                h = jnp.tanh(h @ stacked[s])
            return (h**2).mean()

        with jax.set_mesh(mesh):
            gp = jax.jit(jax.grad(loss_p))(stacked, x)
        gs = jax.grad(loss_s)(stacked_host, x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gs),
                                   rtol=1e-4, atol=1e-6)


class TestSpmdPipeline1F1B:
    """Single-program 1F1B: loss + grads + input cotangents from one
    interleaved scan must match serial autodiff (VERDICT r1 next#8)."""

    def _setup(self, S, M, dim=8, mb=2):
        mesh = _mesh((S,), ("pp",))
        Ws = [
            jax.random.normal(jax.random.PRNGKey(i), (dim, dim)) * 0.3
            for i in range(S)
        ]
        stacked_host = jnp.stack(Ws)
        stacked = jax.device_put(stacked_host,
                                 NamedSharding(mesh, P("pp")))
        x = jax.random.normal(jax.random.PRNGKey(9), (M * mb, dim))
        labels = jax.random.normal(jax.random.PRNGKey(7), (M * mb, dim))
        return mesh, stacked_host, stacked, x, labels

    @staticmethod
    def _stage_fn(W, x, _):
        return jnp.tanh(x @ W)

    @staticmethod
    def _loss_fn(y, lbl):
        return jnp.mean((y - lbl) ** 2)

    @pytest.mark.parametrize("S,M", [(4, 4), (4, 8), (8, 8)])
    def test_matches_serial(self, S, M):
        mesh, stacked_host, stacked, x, labels = self._setup(S, M)
        mb = x.shape[0] // M

        def run(stacked, x, labels):
            mbs = x.reshape(M, mb, -1)
            lbls = labels.reshape(M, mb, -1)
            return spmd_pipeline_1f1b(self._stage_fn, self._loss_fn,
                                      stacked, mbs, lbls, mesh=mesh)

        with jax.set_mesh(mesh):
            loss, wgrad, dx = jax.jit(run)(stacked, x, labels)

        def loss_s(stacked, x):
            h = x
            for s in range(S):
                h = jnp.tanh(h @ stacked[s])
            # mean over microbatches of per-microbatch means == global
            # mean when microbatches are equal sized
            return jnp.mean((h - labels) ** 2)

        ls = loss_s(stacked_host, x)
        gs, dxs = jax.grad(loss_s, argnums=(0, 1))(stacked_host, x)
        np.testing.assert_allclose(float(loss), float(ls), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(wgrad), np.asarray(gs),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(dx).reshape(x.shape), np.asarray(dxs),
            rtol=1e-4, atol=1e-6)

    def test_collectives_present(self):
        """Both directions of the pipeline ride ppermute (fwd
        activations + bwd cotangents), not all-gathers."""
        S, M = 4, 4
        mesh, _, stacked, x, labels = self._setup(S, M)
        mb = x.shape[0] // M

        def run(stacked, x, labels):
            mbs = x.reshape(M, mb, -1)
            lbls = labels.reshape(M, mb, -1)
            return spmd_pipeline_1f1b(self._stage_fn, self._loss_fn,
                                      stacked, mbs, lbls, mesh=mesh)

        with jax.set_mesh(mesh):
            hlo = (jax.jit(run).lower(stacked, x, labels).compile()
                   .as_text())
        assert "collective-permute" in hlo


class TestGraftEntry:

    def test_dryrun_multichip(self):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "graft_entry",
            os.path.join(os.path.dirname(__file__), "..", "..",
                         "__graft_entry__.py"))
        ge = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ge)
        ge.dryrun_multichip(8)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
