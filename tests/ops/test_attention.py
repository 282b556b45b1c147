"""Fused / ring attention vs the einsum reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from alpa_tpu.model.gpt_model import (GPTConfig, SelfAttention,
                                      get_attention_fn,
                                      reference_attention, update_kv_cache)
from alpa_tpu.ops import flash_attention as fa
from alpa_tpu.ops.ring_attention import make_ring_attention_fn, ring_attention
from alpa_tpu.testing import init_params



def _rand_qkv(b=2, s=128, h=4, d=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), dtype) * 0.5 for k in ks)


def _interpreted(causal, **blocks):
    return lambda q, k, v: fa.flash_attention(q, k, v, causal=causal,
                                              interpret=True, **blocks)


def _out_and_grads(core, q, k, v, do):
    out, vjp = jax.vjp(core, q, k, v)
    return [np.asarray(x, np.float32) for x in (out,) + vjp(do)]


class TestFlashAttention:
    """``ops/flash_attention.py``'s kernels (interpreted) against
    ``reference_attention``."""

    @pytest.mark.parametrize("remat", [False, True],
                             ids=["plain", "checkpoint"])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dim", [64, 128])
    def test_output_and_gradients_match_reference(self, dim, causal, remat):
        """bfloat16 operands as the models pass them, both head widths
        (two heads of 64 side by side in a group of lanes, one of 128 a
        group; two groups a row): the output and all three gradients are
        the reference's to the rounding of bfloat16 (both multiply
        bfloat16 and accumulate in float32; they differ in the order of
        the sums), across block boundaries (two blocks a row) and under
        ``jax.checkpoint`` (the forward kernel runs twice)."""
        heads = 2 * fa.heads_a_group(dim)
        q, k, v = _rand_qkv(b=1, s=256, h=heads, d=dim, dtype=jnp.bfloat16)
        do = _rand_qkv(b=1, s=256, h=heads, d=dim, dtype=jnp.bfloat16)[1]
        kernel = _interpreted(causal, block_q=128, block_k=128)
        reference = lambda q, k, v: reference_attention(  # noqa: E731
            q, k, v, causal=causal)
        if remat:
            kernel, reference = (jax.checkpoint(kernel),
                                 jax.checkpoint(reference))
        for got, want in zip(_out_and_grads(kernel, q, k, v, do),
                             _out_and_grads(reference, q, k, v, do)):
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_float32_inputs_match_to_float32(self):
        """Nothing in the kernels rounds to bfloat16 on its own: float32
        operands give the reference's float32 result."""
        q, k, v = _rand_qkv(b=1, s=256, h=2, d=64)
        do = _rand_qkv(b=1, s=256, h=2, d=64)[2]
        for got, want in zip(
                _out_and_grads(_interpreted(True, block_q=128, block_k=128),
                               q, k, v, do),
                _out_and_grads(lambda q, k, v: reference_attention(
                    q, k, v, causal=True), q, k, v, do)):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64)])
    def test_blocks_of_two_sizes(self, block_q, block_k):
        """Query and key blocks of different sizes: the diagonal crosses
        more than one block of the other kind."""
        q, k, v = _rand_qkv(b=1, s=256, h=2, d=64)
        do = _rand_qkv(b=1, s=256, h=2, d=64)[0]
        for got, want in zip(
                _out_and_grads(_interpreted(True, block_q=block_q,
                                            block_k=block_k), q, k, v, do),
                _out_and_grads(lambda q, k, v: reference_attention(
                    q, k, v, causal=True), q, k, v, do)):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_four_narrow_heads_share_a_group_of_lanes(self):
        q, k, v = _rand_qkv(b=2, s=256, h=8, d=32)
        do = _rand_qkv(b=2, s=256, h=8, d=32)[2]
        assert fa.heads_a_group(32) == 4
        for got, want in zip(
                _out_and_grads(_interpreted(True, block_q=128, block_k=128),
                               q, k, v, do),
                _out_and_grads(lambda q, k, v: reference_attention(
                    q, k, v, causal=True), q, k, v, do)):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("seq,dim,block", [
        (512, 64, 512), (1024, 64, 512), (4096, 128, 512), (2048, 256, 256)])
    def test_blocks_follow_the_call(self, seq, dim, block):
        assert fa.blocks(seq, dim) == (block, block)

    def test_no_operand_of_a_product_is_cast_to_float32(self):
        """The kernels feed the matrix unit the dtype they are handed:
        every ``dot_general`` inside them multiplies bfloat16 operands
        into float32."""
        q, k, v = _rand_qkv(b=1, s=256, h=1, d=128, dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: _interpreted(
            True, block_q=128, block_k=128)(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)

        def products(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn
                for value in eqn.params.values():
                    for sub in (value if isinstance(value, (tuple, list))
                                else [value]):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            yield from products(inner)

        found = [eqn for eqn in products(jaxpr.jaxpr)
                 if eqn.invars[0].aval.ndim == 2]
        # two products a pair of blocks forward and five backward, once
        # in the loop over masked pairs and once in the loop over the rest
        assert len(found) == 2 * (2 + 5)
        for eqn in found:
            assert {v.aval.dtype for v in eqn.invars} == {
                jnp.dtype(jnp.bfloat16)}
            assert eqn.outvars[0].aval.dtype == jnp.float32


def _traced_core(config, seq, padding_bias=False):
    """Which core ``SelfAttention`` without a cache traces at these
    shapes: "fused" where the program holds the kernels' ``pallas_call``
    (``lax.platform_dependent`` traces both branches)."""
    attn = SelfAttention(config)
    x = jax.ShapeDtypeStruct((2, seq, config.hidden_size), config.dtype)
    bias = jnp.zeros((2, 1, 1, seq), jnp.float32) if padding_bias else None
    positions = jnp.broadcast_to(jnp.arange(seq), (2, seq))

    def apply(x):
        params = init_params(attn, jax.random.PRNGKey(0), x,
                             position_ids=positions, padding_bias=bias)
        return attn.apply(params, x, position_ids=positions,
                          padding_bias=bias)[0]

    text = str(jax.make_jaxpr(apply)(x))
    return "fused" if "pallas_call" in text else "reference"


_WIDE = dict(hidden_size=256, num_heads=4, num_layers=1,
             dtype=jnp.bfloat16, vocab_size=128)


@pytest.mark.parametrize("what,overrides,seq,bias,core", [
    ("causal", {}, 512, False, "fused"),
    ("no-mask", dict(causal=False), 1024, False, "fused"),
    ("grouped-heads", dict(num_kv_heads=2), 512, False, "reference"),
    ("window", dict(attention="sliding", sliding_window=128,
                    positions="rotary"), 512, False, "reference"),
    ("block-mask", dict(block_length=4), 512, False, "reference"),
    ("padding-bias", dict(causal=False), 512, True, "reference"),
    ("odd-length", {}, 520, False, "reference"),
    ("short-length", {}, 256, False, "reference"),
    ("ring", dict(attention_impl="ring", sp_axis="sp"), 512, False, None),
])
def test_the_shapes_choose_the_core(what, overrides, seq, bias, core):
    """``attention_impl`` "reference" is the core the call chooses: the
    fused kernels for full attention with a causal mask or none, one
    length their blocks divide, from ``MIN_SEQ`` positions;
    ``reference_attention`` for everything the kernels do not take."""
    config = GPTConfig(seq_len=seq, **{**_WIDE, **overrides})
    if core is None:
        # the sequence-parallel cores are chosen by the field, as before
        assert get_attention_fn(config).func is ring_attention
        return
    assert _traced_core(config, seq, bias) == core


def test_flash_is_no_value_of_attention_impl():
    with pytest.raises(ValueError, match="unknown attention_impl"):
        get_attention_fn(GPTConfig(attention_impl="flash"))


@pytest.mark.parametrize("what,q_shape,k_shape,takes", [
    ("gpt-1.3b-train", (8, 1024, 32, 64), (8, 1024, 32, 64), True),
    ("olmoe-train", (2, 4096, 16, 128), (2, 4096, 16, 128), True),
    ("the-shortest", (16, 512, 32, 64), (16, 512, 32, 64), True),
    ("the-longest", (1, 16384, 8, 128), (1, 16384, 8, 128), True),
    ("grouped-heads", (2, 1024, 32, 128), (2, 1024, 4, 128), False),
    ("queries-over-a-longer-cache", (2, 512, 8, 64), (2, 1024, 8, 64),
     False),
    ("no-whole-blocks", (2, 1000, 8, 64), (2, 1000, 8, 64), False),
    ("too-short", (2, 256, 8, 64), (2, 256, 8, 64), False),
    ("an-odd-head-of-64", (2, 512, 7, 64), (2, 512, 7, 64), False),
    ("heads-of-no-whole-lanes", (2, 512, 8, 96), (2, 512, 8, 96), False),
    ("too-long", (1, 32768, 8, 128), (1, 32768, 8, 128), False)])
def test_flash_attention_fits(what, q_shape, k_shape, takes):
    assert fa.fits(jax.ShapeDtypeStruct(q_shape, jnp.bfloat16),
                   jax.ShapeDtypeStruct(k_shape, jnp.bfloat16)) is takes


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
        """Ulysses SP + the fused kernels per head shard: the all-to-all
        hands each device the FULL sequence for its heads, so the blocked
        kernels apply unchanged — fwd and grads match the reference."""
        from alpa_tpu.ops.ulysses_attention import make_ulysses_attention_fn
        mesh = self._mesh()
        # two heads of 64 a device: one group of lanes
        q, k, v = _rand_qkv(s=256, h=8, d=64)
        attn = make_ulysses_attention_fn(
            mesh, "sp", attn_fn=lambda q, k, v, causal: fa.flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128,
                interpret=True))
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


def test_a_long_row_stays_in_the_kernels_fast_memory():
    """A row of several blocks a side (the streaming kernel this one
    replaces took over from 8,192 positions; this one keeps a row of up
    to ``MAX_SEQ``): forward and backward against the reference."""
    q, k, v = _rand_qkv(b=1, s=1024, h=2, d=64)
    do = _rand_qkv(b=1, s=1024, h=2, d=64)[1]
    for got, want in zip(
            _out_and_grads(_interpreted(True, block_q=256, block_k=256),
                           q, k, v, do),
            _out_and_grads(lambda q, k, v: reference_attention(
                q, k, v, causal=True), q, k, v, do)):
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


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


# ---- many new queries a row over the row's written cache (ISSUE 56) ----

CHUNK_SEQ, CHUNK_S, CHUNK_BLOCK_K = 256, 64, 64
# finite, and far above anything written: a sum that took it in would show
JUNK = 1e4
# (id, query heads, key/value heads, Dk, Dv, heads folded into the
# channels, block of the block-causal mask, dtype): MiMo's full layers (16
# heads a group, keys of one and a half lane tiles), Trinity's (8 heads a
# group), SDAR's mask, and Trinity's again in the cache's own 16 bits, where
# two key/value heads share a 32-bit sublane
CHUNK_LAYOUTS = [
    ("folded-4x192-4x128", 64, 4, 192, 128, True, 0, jnp.float32),
    ("per-head-4x128", 32, 4, 128, 128, False, 0, jnp.float32),
    ("block-causal-4", 32, 4, 128, 128, False, 4, jnp.float32),
    ("per-head-4x128-bfloat16", 32, 4, 128, 128, False, 0, jnp.bfloat16),
]
# a row's first new position: a scalar (the chunk step's) or one a row
CHUNK_OFFSETS = [
    ("from-the-start", 0),
    ("from-inside-a-key-block", CHUNK_BLOCK_K + 36),
    ("to-the-caches-end", CHUNK_SEQ - CHUNK_S),
    ("per-row", [0, CHUNK_BLOCK_K + 36, CHUNK_SEQ - CHUNK_S]),
]


@pytest.mark.parametrize("offset", [c[1] for c in CHUNK_OFFSETS],
                         ids=[c[0] for c in CHUNK_OFFSETS])
@pytest.mark.parametrize("heads,kv_heads,dk,dv,folded,block,dtype",
                         [c[1:] for c in CHUNK_LAYOUTS],
                         ids=[c[0] for c in CHUNK_LAYOUTS])
def test_chunk_attention_reads_what_its_queries_see(monkeypatch, heads,
                                                    kv_heads, dk, dv, folded,
                                                    block, dtype, offset):
    """``ops/cached_attention.py`` ``chunk_attention`` (interpreted, four
    query blocks over four key blocks) against ``reference_attention`` in
    both layouts it takes, at a scalar and at per-row offsets.  What lies
    past every query's reach is large and finite, and reaches no output:
    bit for bit the output over zeros there."""
    from alpa_tpu.ops import cached_attention as ca
    group = heads // kv_heads
    monkeypatch.setattr(ca, "QUERY_ROWS", CHUNK_S // 4 * group)
    monkeypatch.setattr(ca, "CHUNK_BLOCK_K", CHUNK_BLOCK_K)
    offset = jnp.asarray(offset, jnp.int32)
    rows = offset.size
    rng = np.random.default_rng(heads + dk + block + rows)
    # the last position any of a row's queries sees
    last = np.asarray(offset).reshape(-1) + CHUNK_S - 1
    if block:
        last = (last // block + 1) * block - 1
    within = (np.arange(CHUNK_SEQ)[None, :] <= last[:, None])[:, :, None,
                                                              None]
    q = jnp.asarray(rng.normal(size=(rows, CHUNK_S, heads, dk)), dtype)
    written = [rng.normal(size=(rows, CHUNK_SEQ, kv_heads, d))
               for d in (dk, dv)]
    signs = [rng.choice([-JUNK, JUNK], size=x.shape) for x in written]

    def caches(beyond):
        return [jnp.asarray(np.where(within, x, junk if beyond else 0.0),
                            dtype) for x, junk in zip(written, signs)]

    def kernel(k, v):
        if folded:
            k, v = (x.reshape(rows, CHUNK_SEQ, -1) for x in (k, v))
        assert ca.chunk_fits(q, k, v)
        return np.asarray(ca.chunk_attention(q, k, v, offset, block=block,
                                             interpret=True), np.float32)

    dirty, clean = kernel(*caches(True)), kernel(*caches(False))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    want = np.asarray(reference_attention(
        q, *caches(False), causal=True, offset=offset, block=block),
        np.float32)
    # (in 16 bits the reference rounds its scores before the softmax)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(clean, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("what,q_shape,k_shape,v_shape,takes", [
    ("mimo-chunk", (1, 1024, 64, 192), (1, 32768, 768), (1, 32768, 512),
     True),
    ("trinity-chunk", (1, 1024, 32, 128), (1, 16384, 4, 128),
     (1, 16384, 4, 128), True),
    ("sdar-chunk", (1, 1024, 32, 128), (1, 8192, 4, 128), (1, 8192, 4, 128),
     True),
    ("a-short-chunk-of-one-query-block", (2, 128, 32, 128),
     (2, 2048, 4, 128), (2, 2048, 4, 128), True),
    ("lfm2-chunk-heads-of-64", (1, 1024, 32, 64), (1, 8192, 8, 64),
     (1, 8192, 8, 64), False),
    ("opt-prefill-heads-of-64", (1, 1024, 32, 64), (1, 2048, 32, 64),
     (1, 2048, 32, 64), False),
    ("sixteen-queries", (32, 16, 32, 128), (32, 8192, 4, 128),
     (32, 8192, 4, 128), False),
    ("a-chunk-in-no-whole-query-blocks", (1, 1000, 32, 128),
     (1, 8192, 4, 128), (1, 8192, 4, 128), False),
    ("a-cache-in-no-whole-key-blocks", (1, 1024, 32, 128),
     (1, 8000, 4, 128), (1, 8000, 4, 128), False),
    ("an-odd-number-of-heads-in-16-bits", (1, 1024, 24, 128),
     (1, 8192, 3, 128), (1, 8192, 3, 128), False),
    ("folded-values-of-half-a-lane-tile", (1, 1024, 64, 192),
     (1, 32768, 768), (1, 32768, 256), False)])
def test_chunk_attention_fits(what, q_shape, k_shape, v_shape, takes):
    """Which shapes the kernel over query blocks and key blocks takes:
    more than ``MAX_QUERIES`` new queries a row in whole query blocks,
    heads of whole lanes as the cache lies; heads of 64, a tick's or a
    block step's few queries and ragged chunks or caches it leaves to the
    cores they had (a sink is the caller's to leave:
    ``tests/serve/test_cached_attention_core.py``)."""
    from alpa_tpu.ops import cached_attention as ca
    q, k, v = (jax.ShapeDtypeStruct(shape, jnp.bfloat16)
               for shape in (q_shape, k_shape, v_shape))
    assert ca.chunk_fits(q, k, v) is takes
    # the two kernels never take the same call
    assert not (takes and (ca.folded_fits(q, k, v) if len(k_shape) == 3
                           else ca.fits(q, k)))


# ---- a selecting decode under its selection's mask (ISSUE 59) -------------

SELECT_BLOCK_K, SELECT_SEQ, SELECT_TOPK = 128, 1024, 48
# (id, queries a row, the rows' first new positions, what else): the kernel's
# key blocks are of 128 positions here, the cache of eight
UNDER_MASK_CASES = [
    ("rows-of-unlike-lengths", 1, [40, 300, 777], None),
    ("rows-of-unlike-lengths-two-queries", 2, [40, 300, 777], None),
    ("a-row-holds-fewer-than-topk", 1, [10, 500], None),
    ("a-row-holds-fewer-than-topk-two-queries", 2, [10, 500], None),
    # the last query at a block's last position, and at the next one's first
    ("a-length-ends-on-a-blocks-edge", 1, [255, 256, 1023], None),
    ("a-length-ends-on-a-blocks-edge-two-queries", 2, [254, 255, 1022], None),
    ("a-block-with-no-selected-position", 1, [600, 900], "a-block-unselected"),
    ("a-block-with-no-selected-position-two-queries", 2, [600, 900],
     "a-block-unselected"),
    # ``chipbench/controls_glm5.py`` ``second_query_reuses_first``
    ("a-table-shared-by-both-queries", 2, [40, 300, 777], "shared"),
]


@pytest.mark.parametrize("queries,starts,what",
                         [c[1:] for c in UNDER_MASK_CASES],
                         ids=[c[0] for c in UNDER_MASK_CASES])
def test_a_decode_under_the_mask_is_the_gather(monkeypatch, queries, starts,
                                               what):
    """``ops/latent_attention.py`` ``absorbed_under_mask`` (interpreted)
    over the mask ``mask_of`` makes of ``selected_positions``'s table,
    against the copy it replaces: ``latent_attention_gathered`` at two
    queries a row, the one-query gather as ``LatentAttention`` wrote it
    out until ISSUE 59 at one.  What the cache holds past a row's newest
    position is large and finite and reaches no output."""
    from alpa_tpu.model import gpt_model as gm
    from alpa_tpu.ops import latent_attention as la
    monkeypatch.setattr(la, "DECODE_BLOCK_K", SELECT_BLOCK_K)
    heads, rank, dr, dn, dv = 16, 128, 64, 64, 32
    b, sk, k = len(starts), SELECT_SEQ, SELECT_TOPK
    rng = np.random.default_rng(queries + sum(starts))
    index = jnp.asarray(starts, jnp.int32)
    q_pos = index[:, None] + jnp.arange(queries)[None]
    scores = rng.normal(size=(b, queries, sk)).astype(np.float32)
    if what == "a-block-unselected":
        scores[:, :, 2 * SELECT_BLOCK_K:3 * SELECT_BLOCK_K] -= 100.0
    scores = jnp.where(jnp.arange(sk)[None, None] <= q_pos[..., None],
                       scores, -jnp.inf)
    positions, real = gm.selected_positions(scores, k)
    if what == "shared":
        positions = jnp.broadcast_to(positions[:, :1], positions.shape)
        real = jnp.broadcast_to(real[:, :1], real.shape)
    chosen = gm.mask_of(positions.reshape(b * queries, k),
                        real.reshape(b * queries), sk).reshape(b, queries, sk)
    if what == "a-block-unselected":
        assert not np.asarray(chosen)[
            :, :, 2 * SELECT_BLOCK_K:3 * SELECT_BLOCK_K].any()
    if what is None:
        assert (np.asarray(chosen) != 0).tolist() == np.asarray(
            gm.selected_mask(scores, k)).tolist()
    written = np.arange(sk)[None, :, None] < (
        np.asarray(index) + queries)[:, None, None]
    cache = jnp.asarray(np.where(
        written, rng.normal(size=(b, sk, gm.latent_row_width(rank, dr))),
        rng.choice([-3e4, 3e4], size=(b, sk, 1))), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(b, queries, heads, dn)),
                         jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(b, queries, heads, dr)), jnp.float32)
    w_kv_b = jnp.asarray(rng.normal(size=(rank, heads, dn + dv)) * 0.1,
                         jnp.float32)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kv_b[..., :dn])
    assert la.under_mask_fits(q_pe, cache, rank)
    got = jnp.einsum("bqhr,rhd->bqhd", la.absorbed_under_mask(
        q_lat, q_pe, cache, chosen, index, scale=0.09, interpret=True),
        w_kv_b[..., dn:])
    if queries == 1:
        taken = jnp.take_along_axis(cache, positions[:, 0, :, None], axis=1)
        want = gm.latent_attention_absorbed(
            q_nope, q_pe, taken[..., :rank],
            taken[..., rank:rank + dr].swapaxes(1, 2), w_kv_b, 0.09,
            real[:, 0] - 1)
    else:
        want = gm.latent_attention_gathered(q_nope, q_pe, cache, w_kv_b,
                                            0.09, positions, real)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("n,k,held", [
    (1024, 48, [0, 1, 47, 48]), (1024, 48, [48, 48, 48]),
    (4096, 300, [5, 299, 300, 300]), (200, 16, [0, 7, 16])],
    ids=["real-below-k", "every-slot-real", "blocks-of-many-lanes",
         "positions-in-no-whole-lanes"])
def test_the_mask_of_a_table_is_the_mask_it_was_compacted_from(n, k, held):
    """``gpt_model.mask_of`` inverts ``positions_of``: mask -> table ->
    mask and table -> mask -> table, a row holding fewer than ``k`` (its
    later slots name a position in range, which the mask must not hold)
    included."""
    from alpa_tpu.model import gpt_model as gm
    rng = np.random.default_rng(n + k)
    mask = np.zeros((len(held), n), bool)
    for row, count in zip(mask, held):
        row[rng.choice(n, size=count, replace=False)] = True
    real = jnp.asarray(held, jnp.int32)
    table = gm.positions_of(jnp.asarray(mask), k)
    back = gm.mask_of(table, real, n)
    assert back.dtype == jnp.int8 and back.shape == mask.shape
    assert ((np.asarray(back) != 0) == mask).all()
    live = np.arange(k)[None] < np.asarray(held)[:, None]
    assert (np.asarray(gm.positions_of(back != 0, k))[live] ==
            np.asarray(table)[live]).all()
