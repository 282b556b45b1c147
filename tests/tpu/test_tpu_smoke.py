"""TPU smoke subset (ref tests/tpu/, SURVEY.md §4.7).

Runs only on a real TPU backend (skipped on the CPU mesh the main suite
uses):

  ALPA_TPU_TEST_ON_TPU=1 python -m pytest tests/tpu/ -q   # on TPU hosts

Unlike the reference — whose TPU support was intra-op-only and partial
(ref shard_parallel/compile_executable.py:83-85 raising NotImplementedError
for TPU grad-acc) — every alpa_tpu path is TPU-first, so this subset just
sanity-runs the core flows on the real chip.
"""
import numpy as np
import pytest

import jax


@pytest.fixture(autouse=True)
def on_tpu():
    """Skip off the chip.  Asked inside a fixture, not at import: no
    module may touch the backend while pytest collects."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        pytest.skip(f"requires a TPU backend, jax found {platform!r}")


class TestTpuSmoke:

    def test_shard_parallel_train(self):
        import alpa_tpu
        from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        state, batch = create_mlp_train_state_and_batch(batch_size=64)
        step = get_mlp_train_step(alpa_tpu.ShardParallel(),
                                  use_value_and_grad=True)
        for _ in range(3):
            state, loss = step(state, batch)
        assert np.isfinite(float(loss))

    def test_grad_accumulation(self):
        import alpa_tpu
        from alpa_tpu.testing import (assert_allclose,
                                      create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        s_a, batch = create_mlp_train_state_and_batch(batch_size=64)
        s_b, _ = create_mlp_train_state_and_batch(batch_size=64)
        full = get_mlp_train_step(alpa_tpu.ShardParallel(),
                                  use_value_and_grad=True)
        acc = get_mlp_train_step(
            alpa_tpu.ShardParallel(num_micro_batches=4),
            use_value_and_grad=True)
        s_a, la = full(s_a, batch)
        s_b, lb = acc(s_b, batch)
        assert_allclose(float(la), float(lb), 1e-2, 1e-2)

    def test_flash_attention_kernel(self):
        import jax.numpy as jnp

        from alpa_tpu.model.gpt_model import attention, reference_attention
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (2, 512, 8, 64), jnp.bfloat16)
                   for kk in ks)
        out = jax.jit(lambda q, k, v: attention(q, k, v, causal=True))(
            q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        diff = float(jnp.abs(out.astype(jnp.float32) -
                             ref.astype(jnp.float32)).max())
        assert diff < 0.05, diff

    def test_generation(self):
        from alpa_tpu.model.gpt_model import GPTConfig
        from alpa_tpu.serve import GenerationConfig, get_model
        gen = get_model(GPTConfig(hidden_size=64, num_layers=2,
                                  num_heads=4, seq_len=64, vocab_size=128))
        out = gen.generate(np.array([[1, 2, 3]], np.int32),
                           GenerationConfig(max_new_tokens=4))
        assert out.shape == (1, 7)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
