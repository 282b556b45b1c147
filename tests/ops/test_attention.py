"""Flash / ring attention vs the einsum reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from alpa_tpu.model.gpt_model import reference_attention, update_kv_cache
from alpa_tpu.ops.flash_attention import flash_attention
from alpa_tpu.ops.ring_attention import make_ring_attention_fn, ring_attention


def _rand_qkv(b=2, s=128, h=4, d=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), dtype) * 0.5 for k in ks)


class TestFlashAttention:

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        q, k, v = _rand_qkv()
        out = flash_attention(q, k, v, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_backward_matches_reference(self):
        q, k, v = _rand_qkv(s=64)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True)**2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=True)**2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_uneven_blocks(self):
        q, k, v = _rand_qkv(s=96)  # not a multiple of default block sizes
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_backward_matches_reference(self, causal):
        """The VMEM-resident regime uses the real pallas backward kernels
        (dq; dk/dv off saved out+logsumexp) — gradients must match the
        reference, including across block boundaries (s > block sizes)."""
        from alpa_tpu.ops.flash_attention import VMEM_RESIDENT_LIMIT
        q, k, v = _rand_qkv(s=512, d=64)
        itemsize = jnp.dtype(q.dtype).itemsize
        assert 2 * 512 * 64 * itemsize <= VMEM_RESIDENT_LIMIT

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=causal)**2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=causal)**2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_streaming_backward_falls_back(self):
        """Beyond the VMEM budget the backward takes the chunked
        recompute path and still matches the reference."""
        from alpa_tpu.ops.flash_attention import VMEM_RESIDENT_LIMIT
        q, k, v = _rand_qkv(b=1, s=16384, h=1, d=64)
        assert 2 * 16384 * 64 * 4 > VMEM_RESIDENT_LIMIT

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True)**2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=True)**2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)


class TestRingAttention:

    def _mesh(self, n=4):
        devs = np.array(jax.devices()[:n])
        return Mesh(devs, ("sp",))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=64)
        attn = make_ring_attention_fn(mesh, "sp")
        with jax.set_mesh(mesh):
            out = jax.jit(lambda q, k, v: attn(q, k, v, causal=causal))(
                q, k, v)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_flow(self):
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=64)
        attn = make_ring_attention_fn(mesh, "sp")

        def loss(q, k, v):
            return (attn(q, k, v, causal=True)**2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=True)**2).sum()

        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])


class TestUlyssesAttention:

    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]), ("sp",))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference_exactly(self, causal):
        from alpa_tpu.ops.ulysses_attention import make_ulysses_attention_fn
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=64, h=8)
        attn = make_ulysses_attention_fn(mesh, "sp")
        with jax.set_mesh(mesh):
            out = jax.jit(lambda q, k, v: attn(q, k, v, causal=causal))(
                q, k, v)
        ref = reference_attention(q, k, v, causal=causal)
        # all-to-all only moves data; differences are float reduction order
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients(self):
        from alpa_tpu.ops.ulysses_attention import make_ulysses_attention_fn
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=64, h=8)
        attn = make_ulysses_attention_fn(mesh, "sp")

        def loss(q, k, v):
            return (attn(q, k, v, causal=True)**2).sum()

        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(
            lambda q, k, v:
            (reference_attention(q, k, v, causal=True)**2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    def test_composes_with_flash_kernel(self):
        """Ulysses SP + the pallas flash kernel per head shard: the
        all-to-all hands each device the FULL sequence for its heads, so
        the blocked kernel applies unchanged — fwd and grads match the
        reference."""
        from alpa_tpu.ops.flash_attention import flash_attention
        from alpa_tpu.ops.ulysses_attention import make_ulysses_attention_fn
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=256, h=8, d=32)
        attn = make_ulysses_attention_fn(mesh, "sp",
                                         attn_fn=flash_attention)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda q, k, v: attn(q, k, v, causal=True))(
                q, k, v)
            g = jax.jit(jax.grad(
                lambda q, k, v: (attn(q, k, v, causal=True)**2).sum(),
                argnums=(0, 1, 2)))(q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        gr = jax.grad(
            lambda q, k, v:
            (reference_attention(q, k, v, causal=True)**2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)

    def test_indivisible_heads_clear_error(self):
        from alpa_tpu.ops.ulysses_attention import make_ulysses_attention_fn
        mesh = self._mesh()
        q, k, v = _rand_qkv(s=64, h=6)  # 6 heads, 4-way axis
        attn = make_ulysses_attention_fn(mesh, "sp")
        with pytest.raises(Exception, match="divisible|not divisible"):
            with jax.set_mesh(mesh):
                jax.jit(lambda q, k, v: attn(q, k, v))(q, k, v)


class TestStreamingFlash:

    def test_long_sequence_streaming_path(self):
        """k/v beyond the VMEM-resident limit take the HBM-streaming
        kernel; result must match the reference exactly."""
        from alpa_tpu.ops.flash_attention import (VMEM_RESIDENT_LIMIT,
                                                  flash_attention)
        s, d = 16384, 64
        assert 2 * s * d * 4 > VMEM_RESIDENT_LIMIT  # streaming triggers
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (1, s, 1, d)) * 0.5 for kk in ks)
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---- a few new queries a row over the row's written cache (ISSUE 40) ----

CACHED_SEQ, CACHED_BLOCK_K = 512, 128
# large, finite, of both signs: what no model writes and a sum would show
STALE = 3e38


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (32, 4, 128), (32, 8, 64), (32, 32, 64)],
    ids=["grouped-128", "grouped-64", "multi-head-64"])
@pytest.mark.parametrize("block", [0, 4])
@pytest.mark.parametrize("s", [1, 4])
def test_cached_attention_reads_what_the_rows_hold(monkeypatch, s, block,
                                                   heads, kv_heads,
                                                   head_dim):
    """``ops/cached_attention.py``'s kernel (interpreted) against
    ``reference_attention`` over the caches ``update_kv_cache`` writes, in
    both views of a cache (heads of whole lanes as named; narrower ones
    with the positions in the lanes): a row at the cache's start, a row
    whose positions end on a key block's edge and one a step past it, a
    row that fills the cache, and a free row whose index is past the edge
    (not written, reads the whole cache, as today).  What lies past each
    row's reach is large and finite, and reaches no output: bit for bit
    the output over zeros there."""
    from alpa_tpu.ops import cached_attention as ca
    monkeypatch.setattr(ca, "BLOCK_ELEMENTS",
                        CACHED_BLOCK_K * kv_heads * head_dim)
    unit = block or 1
    # the positions a row holds once the step's are written: they end on
    # a block's edge where the mask goes by blocks (``update_kv_cache``)
    held = np.asarray([max(s, unit), CACHED_BLOCK_K, CACHED_BLOCK_K + unit,
                       CACHED_SEQ])
    index = jnp.asarray(list(held - s) + [CACHED_SEQ + 8], jnp.int32)
    rows = len(index)
    rng = np.random.default_rng(s + block + kv_heads + head_dim)
    shape = (rows, CACHED_SEQ, kv_heads, head_dim)
    within = (np.arange(CACHED_SEQ)[None, :] <
              np.append(held, CACHED_SEQ)[:, None])[:, :, None, None]
    written = (rng.normal(size=shape), rng.normal(size=shape))
    signs = rng.choice([-STALE, STALE], size=shape)

    def draw(*dims):
        return jnp.asarray(rng.normal(size=dims), jnp.bfloat16)

    q = draw(rows, s, heads, head_dim)
    k, v = (draw(rows, s, kv_heads, head_dim) for _ in range(2))
    assert ca.fits(q, jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert ca.block_k(kv_heads, head_dim) == CACHED_BLOCK_K

    def attend(stale, core):
        cache = tuple(jnp.asarray(np.where(within, x, stale), jnp.bfloat16)
                      for x in written) + (index,)
        k_full, v_full, _ = update_kv_cache(cache, k, v)
        return np.asarray(core(q, k_full, v_full), np.float32)

    def kernel(q, k_full, v_full):
        return ca.cached_attention(q, k_full, v_full, index, block=block,
                                   interpret=True)

    def reference(q, k_full, v_full):
        return reference_attention(q, k_full, v_full, causal=True,
                                   offset=index, block=block)

    dirty, clean = attend(signs, kernel), attend(0.0, kernel)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    # the reference rounds its scores to bfloat16 before the softmax
    np.testing.assert_allclose(clean, attend(0.0, reference), atol=3e-2,
                               rtol=3e-2)


@pytest.mark.parametrize("what,q_shape,cache_shape,takes", [
    ("sdar-block-step", (32, 4, 32, 128), (32, 8192, 4, 128), True),
    ("trinity-decode", (16, 1, 32, 128), (16, 16384, 4, 128), True),
    ("lfm2-decode", (64, 1, 32, 64), (64, 8192, 8, 64), True),
    ("opt-decode", (4, 1, 32, 64), (4, 2048, 32, 64), True),
    ("a-prefill-chunk", (1, 1024, 32, 128), (1, 8192, 4, 128), False),
    ("a-cache-of-no-whole-key-block", (4, 1, 32, 128), (4, 1000, 4, 128),
     False),
    ("many-wide-kv-heads", (4, 1, 32, 128), (4, 8192, 32, 128), False),
    ("queries-in-no-whole-sublanes", (4, 1, 12, 64), (4, 2048, 12, 64),
     False)])
def test_cached_attention_fits(what, q_shape, cache_shape, takes):
    from alpa_tpu.ops import cached_attention as ca
    assert ca.fits(jax.ShapeDtypeStruct(q_shape, jnp.bfloat16),
                   jax.ShapeDtypeStruct(cache_shape, jnp.bfloat16)) is takes
