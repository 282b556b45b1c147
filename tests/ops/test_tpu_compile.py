"""Compile the Pallas flash-attention kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described and not attached.  What it refuses here
(block shapes the Mosaic lowering cannot tile, a kernel that asks for
more VMEM than it may have) the chip refuses too; interpret mode — all
the CPU tests of ``test_attention.py`` use — shows none of it.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every pytest
worker imports every test file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports the function under the module's name
fa = importlib.import_module("alpa_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _fwd(q, k, v):
    return fa._flash_forward(q, k, v, causal=True)


def _bwd(q, k, v, out, lse, do):
    return fa._flash_backward_kernels(q, k, v, out, lse, do, causal=True,
                                      q_offset=0)


def _public_grad(q, k, v):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


# (id, function, (B, S, H, D), takes the backward's residuals,
#  Pallas kernels expected in the compiled program)
CASES = [
    ("fwd-resident-d64", _fwd, (8, 1024, 32, 64), False, 1),
    ("fwd-resident-d128", _fwd, (2, 2048, 16, 128), False, 1),
    ("fwd-resident-limit-d64", _fwd, (1, 8192, 32, 64), False, 1),
    ("fwd-streaming-d128", _fwd, (1, 32768, 8, 128), False, 1),
    ("bwd-kernels-d64", _bwd, (8, 1024, 32, 64), True, 2),
    ("bwd-kernels-d128", _bwd, (2, 2048, 16, 128), True, 2),
    ("public-grad-d64", _public_grad, (8, 1024, 32, 64), False, 3),
]


@pytest.mark.parametrize("fn,shape,residuals,n_kernels",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_flash_kernels_compile_for_v5e(one_chip, fn, shape, residuals,
                                       n_kernels):
    b, s, h, _ = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    args = [x, x, x]
    if residuals:
        lse = jax.ShapeDtypeStruct((b * h, s), jnp.float32,
                                   sharding=one_chip)
        args += [x, lse, x]
    # the default backend here is the CPU: the kernels must pick compiled
    # mode from the platform they are lowered for, not from the backend
    assert jax.default_backend() == "cpu"
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == n_kernels


# the expert layer's grouped matmuls at OLMoE's widths, 64 groups, batch 2
# of 4096 tokens with 8 experts each: (rows, contracted, produced)
GROUPED_CASES = [("gate-up", (65536, 2048, 2048)),
                 ("down", (65536, 1024, 2048))]


@pytest.mark.parametrize("shape", [c[1] for c in GROUPED_CASES],
                         ids=[c[0] for c in GROUPED_CASES])
def test_grouped_matmul_compiles_for_v5e(one_chip, shape):
    """Forward and both gradients: three Pallas kernels, none interpreted,
    all inside the kernel's fast memory at the tiling the op chooses."""
    from alpa_tpu.ops.grouped_matmul import grouped_matmul
    m, k, n = shape

    def value_and_grads(lhs, rhs, sizes, cot):
        def loss(lhs, rhs):
            out = grouped_matmul(lhs, rhs, sizes)
            return (out.astype(jnp.float32) * cot.astype(jnp.float32)).sum()
        return jax.value_and_grad(loss, argnums=(0, 1))(lhs, rhs)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert jax.default_backend() == "cpu"
    hlo = jax.jit(value_and_grads).lower(
        spec((m, k), jnp.bfloat16), spec((64, k, n), jnp.float32),
        spec((64,), jnp.int32), spec((m, n), jnp.bfloat16)
    ).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
