"""Compile the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described and not attached.  What it refuses here
(block shapes the Mosaic lowering cannot tile, a kernel that asks for
more VMEM than it may have) the chip refuses too; interpret mode — all
the CPU tests of ``test_attention.py`` use — shows none of it.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every pytest
worker imports every test file.
"""
import functools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from alpa_tpu.ops import flash_attention as fa


KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fwd(q, k, v):
    block_q, block_k = fa.blocks(q.shape[1], q.shape[3])
    return fa._forward(q, k, v, True, block_q, block_k, False)


def _bwd(q, k, v, out, lse, do):
    block_q, block_k = fa.blocks(q.shape[1], q.shape[3])
    return fa._backward(q, k, v, out, lse, do, True, block_q, block_k,
                        False)


def _public_grad(q, k, v):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


# (id, function, (B, S, H, D), takes the backward's residuals,
#  Pallas kernels expected in the compiled program): the two training
# cells' shapes and the longest row ``fits`` takes
CASES = [
    ("fwd-d64", _fwd, (8, 1024, 32, 64), False, 1),
    ("fwd-d128", _fwd, (2, 4096, 16, 128), False, 1),
    ("fwd-longest-d64", _fwd, (1, 16384, 4, 64), False, 1),
    ("fwd-longest-d128", _fwd, (1, 16384, 4, 128), False, 1),
    ("bwd-d64", _bwd, (8, 1024, 32, 64), True, 1),
    ("bwd-d128", _bwd, (2, 4096, 16, 128), True, 1),
    ("bwd-longest-d128", _bwd, (1, 16384, 4, 128), True, 1),
    ("public-grad-d64", _public_grad, (8, 1024, 32, 64), False, 2),
]


@pytest.mark.parametrize("fn,shape,residuals,n_kernels",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_flash_kernels_compile_for_v5e(one_chip, fn, shape, residuals,
                                       n_kernels):
    b, s, h, _ = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    assert fa.fits(x, x)
    args = [x, x, x]
    if residuals:
        lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32,
                                   sharding=one_chip)
        args += [x, lse, x]
    # the default backend here is the CPU: the kernels are compiled
    # because the program is lowered for a TPU
    assert jax.default_backend() == "cpu"
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count(KERNEL) == n_kernels


def _block_step(shape, remat):
    """``value_and_grad`` of one ``TransformerBlock`` at a cell's batch,
    length, heads and head width, and its abstract arguments."""
    from alpa_tpu.model.gpt_model import GPTConfig, TransformerBlock
    b, s, h, d = shape
    cfg = GPTConfig(hidden_size=h * d, num_heads=h, num_layers=1, seq_len=s,
                    dtype=jnp.bfloat16, vocab_size=512)
    block = TransformerBlock(cfg)
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16)
    params = jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))

    def step(params, x):
        apply = lambda p, x: block.apply(p, x)[0]   # noqa: E731
        if remat:
            apply = jax.checkpoint(apply)
        return jax.value_and_grad(
            lambda p: apply(p, x).astype(jnp.float32).sum())(params)

    return step, params, x


def _scores(text, seq):
    """The arrays of a program's text that are a row's whole scores:
    ``[., ., seq, seq]``."""
    return set(re.findall(r"\w+\[\d+,\d+,%d,%d\]" % (seq, seq), text))


# (id, (B, S, H, D), under jax.checkpoint, kernels): the GPT cell's block
# (rematerialised: the forward kernel twice) and OLMoE's
BLOCK_CASES = [
    ("gpt-1.3b-train", (8, 1024, 32, 64), True, 3),
    ("olmoe-train", (2, 4096, 16, 128), False, 2),
]


@pytest.mark.parametrize("shape,remat,n_kernels",
                         [c[1:] for c in BLOCK_CASES],
                         ids=[c[0] for c in BLOCK_CASES])
def test_a_blocks_step_keeps_no_scores_on_v5e(one_chip, shape, remat,
                                              n_kernels):
    """The whole ``value_and_grad`` of one block at a training cell's
    shapes, lowered for one v5e: its attention is the kernels (under the
    scope a capture reads them by), and no array of a row's whole scores
    is left in the program: what ``reference_attention`` would have saved
    for its backward pass is dead beside the kernels' branch."""
    from alpa_tpu.model.gpt_model import ATTENTION_SCOPE
    from alpa_tpu.telemetry.device_time import part_of
    step, params, x = _block_step(shape, remat)
    on_chip = lambda a: jax.ShapeDtypeStruct(   # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    text = jax.jit(step).lower(jax.tree_util.tree_map(on_chip, params),
                               on_chip(x)).compile().as_text()
    kernels = [line for line in text.splitlines() if KERNEL in line]
    assert len(kernels) == n_kernels
    for line in kernels:
        assert part_of(re.search(r'op_name="([^"]*)"', line).group(
            1)) == ATTENTION_SCOPE
    assert not _scores(text, shape[1])


@pytest.mark.parametrize("devices,n_kernels", [(1, 3), (2, 0)],
                         ids=["one-device", "two-devices"])
def test_a_mesh_of_two_plans_the_reference_core(topo, devices, n_kernels):
    """The GPT cell's block under ``ShardParallel`` on described v5e
    devices: on one the step holds its three kernels, on a mesh of two
    none (``shard_parallel/kernel_choice.py``: the planner binds the
    choice's ``reference_attention``, whose einsums it can shard)."""
    import alpa_tpu
    step, params, x = _block_step((8, 1024, 32, 64), True)
    alpa_tpu.init("local", devices=topo.devices[:devices])
    try:
        planned = alpa_tpu.parallelize(
            lambda params, x: step(params, x),
            method=alpa_tpu.ShardParallel(), static_argnums=(),
            donate_argnums=())
        text = planned.get_executable(params, x)[0].get_hlo_text()
    finally:
        alpa_tpu.shutdown()
    assert text.count(KERNEL) == n_kernels
    assert bool(_scores(text, 1024) or
                re.search(r"\[\d+,\d+,512,1024\]", text)) == (devices == 2)


# the serving cells' ticks over their written caches, as (id, rows, served
# context, heads, key/value heads, head width, new positions a row, the
# mask's block): SDAR's block step, Trinity's, LFM2's and OPT-1.3B's
# decodes
CACHED_CASES = [
    ("sdar-block-step", 32, 8192, 32, 4, 128, 4, 4),
    ("trinity-decode", 16, 16384, 32, 4, 128, 1, 0),
    ("lfm2-decode", 64, 8192, 32, 8, 64, 1, 0),
    ("opt-decode", 4, 2048, 32, 32, 64, 1, 0),
]


@pytest.mark.parametrize("rows,seq_len,heads,kv_heads,dim,s,block",
                         [c[1:] for c in CACHED_CASES],
                         ids=[c[0] for c in CACHED_CASES])
def test_cached_attention_compiles_for_v5e(one_chip, rows, seq_len, heads,
                                           kv_heads, dim, s, block):
    """``ops/cached_attention.py`` at the cells' shapes, in both views of a
    cache: one Pallas kernel, inside its fast memory, and no array of a
    cache's size beside the caches themselves (the kernel is handed each
    cache as it lies: a view, not a copy)."""
    from alpa_tpu.ops import cached_attention as ca

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, cache = spec(rows, s, heads, dim), spec(rows, seq_len, kv_heads, dim)
    assert ca.fits(q, cache)
    assert jax.default_backend() == "cpu"
    compiled = jax.jit(functools.partial(
        ca.cached_attention, block=block)).lower(
            q, cache, cache, spec(rows, dtype=jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


# the expert layers' grouped matmuls at published widths, as (id, (rows,
# contracted, produced), groups, the weights' dtype, gradients too, Pallas
# kernels expected): OLMoE's training step (batch 2 of 4096 tokens with 8
# of 64 experts each, float32 parameters), Trinity's served chunk of 1,024
# positions and its decode tick of 16 rows (8 of 128 experts, bfloat16
# parameters, forward only), and the chunk's shapes under a gradient
GROUPED_CASES = [
    ("gate-up", (65536, 2048, 2048), 64, jnp.float32, True, 3),
    ("down", (65536, 1024, 2048), 64, jnp.float32, True, 3),
    ("trinity-chunk-gate-up", (8192, 2048, 2048), 128, jnp.bfloat16, False,
     1),
    ("trinity-chunk-down", (8192, 1024, 2048), 128, jnp.bfloat16, False, 1),
    ("trinity-decode-gate-up", (128, 2048, 2048), 128, jnp.bfloat16, False,
     1),
    ("trinity-decode-down", (128, 1024, 2048), 128, jnp.bfloat16, False, 1),
    ("trinity-chunk-gate-up-grad", (8192, 2048, 2048), 128, jnp.bfloat16,
     True, 3),
    ("trinity-chunk-down-grad", (8192, 1024, 2048), 128, jnp.bfloat16, True,
     3),
    ("nemotron-decode-down", (384, 1856, 2688), 8, jnp.bfloat16, False, 1),
    ("nemotron-chunk-down", (6144, 1856, 2688), 8, jnp.bfloat16, False, 1),
]


@pytest.mark.parametrize("shape,groups,dtype,grads,n_kernels",
                         [c[1:] for c in GROUPED_CASES],
                         ids=[c[0] for c in GROUPED_CASES])
def test_grouped_matmul_compiles_for_v5e(one_chip, shape, groups, dtype,
                                         grads, n_kernels):
    """Forward, and both gradients where a step takes them: one or three
    Pallas kernels, none interpreted, all inside the kernel's fast memory
    at the tiling the op chooses for the shape."""
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
    args = [spec((m, k), jnp.bfloat16), spec((groups, k, n), dtype),
            spec((groups,), jnp.int32)]
    if grads:
        hlo = jax.jit(value_and_grads).lower(
            *args, spec((m, n), jnp.bfloat16)).compile().as_text()
    else:
        hlo = jax.jit(grouped_matmul).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == n_kernels


# (rows, contracted, produced, groups) of a call -> its tiles: OLMoE's six
# (forward, gradient of the rows with the weights transposed, gradient of
# the weights; gate-and-up and down) keep PR 26's, the decode keeps what
# PR 26's cut down to divisors gave it, the chunk gets the sweep's (PERF.md,
# PR 31)
TILING_CASES = [
    ("olmoe-gate-up-forward", (65536, 2048, 2048, 64), (512, 1024, 1024)),
    ("olmoe-gate-up-rows-gradient", (65536, 2048, 2048, 64),
     (512, 1024, 1024)),
    ("olmoe-gate-up-weights-gradient", (65536, 2048, 2048, 64),
     (512, 1024, 1024)),
    ("olmoe-down-forward", (65536, 1024, 2048, 64), (512, 1024, 1024)),
    ("olmoe-down-rows-gradient", (65536, 2048, 1024, 64),
     (512, 1024, 1024)),
    ("olmoe-down-weights-gradient", (65536, 1024, 2048, 64),
     (512, 1024, 1024)),
    ("trinity-decode-gate-up", (128, 2048, 2048, 128), (128, 1024, 1024)),
    ("trinity-decode-down", (128, 1024, 2048, 128), (128, 1024, 1024)),
    ("trinity-chunk-gate-up", (8192, 2048, 2048, 128), (128, 2048, 1024)),
    ("trinity-chunk-down", (8192, 1024, 2048, 128), (128, 1024, 2048)),
    ("toy-rows-no-multiple-of-the-tile", (192, 48, 40, 4), (64, 48, 40)),
    # a width of 29 x 64 halves to under a lane tile and is walked whole;
    # 2,688 = 21 lane tiles halves to one and takes seven, or three where
    # the row tile leaves no room for seven
    ("nemotron-decode-up", (384, 2688, 1856, 8), (128, 896, 1856)),
    ("nemotron-decode-down", (384, 1856, 2688, 8), (128, 1856, 896)),
    ("nemotron-chunk-up", (6144, 2688, 1856, 8), (512, 384, 1856)),
    ("nemotron-chunk-down", (6144, 1856, 2688, 8), (512, 1856, 384)),
]


@pytest.mark.parametrize("call,tiles", [c[1:] for c in TILING_CASES],
                         ids=[c[0] for c in TILING_CASES])
def test_grouped_matmul_tiling_follows_the_shape(call, tiles):
    from alpa_tpu.ops import grouped_matmul as gm
    assert gm.tiling(*call) == tiles
    m, _, _, groups = call
    assert gm.padded_work_ratio(m, groups, tiles[0]) == \
        (m // tiles[0] + groups - 1) * tiles[0] / m


def test_grouped_matmul_kernels_get_the_rules_tiles(monkeypatch):
    """What reaches the three kernels at a shape where the rule departs
    from ``TILING``: the forward and the rows' gradient take the rule's
    tiles for their own (rows, contracted, produced); the weights'
    gradient keeps its produced tiles within ``TILING``'s."""
    from alpa_tpu.ops import grouped_matmul as gm
    seen = []

    def record(kernel):
        def call(*args, tiling, interpret, **static):
            seen.append((kernel.__name__, tiling))
            return kernel(*args, tiling=tiling, interpret=interpret,
                          **static)
        call.__name__ = kernel.__name__
        return call

    monkeypatch.setattr(gm, "gmm", record(gm.gmm))
    monkeypatch.setattr(gm, "tgmm", record(gm.tgmm))
    m, k, n, groups = 8192, 2048, 2048, 128
    jax.eval_shape(
        jax.grad(lambda a, b, s: gm.grouped_matmul(a, b, s).sum().astype(
            jnp.float32), (0, 1)),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups,), jnp.int32))
    assert set(seen) == {("gmm", (128, 2048, 1024)),
                         ("tgmm", (128, 1024, 1024))}


# dots3-note's full layers at their published widths: the indexer's scores
# of a chunk and of a decode, the chunk's expanded core under the
# selection's mask, the decode's absorbed core over the gathered rows
SELECTING_CASES = [
    ("index-scores-chunk", "index_scores",
     [((1, 1024, 64, 128), jnp.bfloat16), ((1, 1024, 64), jnp.float32),
      ((1, 32768, 128), jnp.bfloat16), ((1, 1024), jnp.int32)]),
    ("index-scores-decode", "index_scores",
     [((16, 1, 64, 128), jnp.bfloat16), ((16, 1, 64), jnp.float32),
      ((16, 32768, 128), jnp.bfloat16), ((16, 1), jnp.int32)]),
    ("expanded-under-a-mask", "expanded",
     [((1, 1024, 128, 128), jnp.bfloat16), ((1, 1024, 128, 64), jnp.bfloat16),
      ((1, 32768, 640), jnp.bfloat16), ((1, 64, 32768), jnp.bfloat16),
      ((512, 128, 256), jnp.bfloat16), ((1,), jnp.int32),
      ((1, 1024, 32768), jnp.int8)]),
    ("absorbed-over-the-selection", "absorbed",
     [((16, 1, 128, 512), jnp.bfloat16), ((16, 1, 128, 64), jnp.bfloat16),
      ((16, 2048, 512), jnp.bfloat16), ((16, 64, 2048), jnp.bfloat16),
      ((16,), jnp.int32)]),
    # glm-5-1chip's blocks at their published widths (PR 53): the indexer's
    # scores of a verify's two queries a row, the absorbed core over each
    # query's own selection (the queries folded into the rows), and the
    # chunk's expanded core with keys of 192 channels handed in as 256
    ("index-scores-verify", "index_scores",
     [((16, 2, 32, 128), jnp.bfloat16), ((16, 2, 32), jnp.float32),
      ((16, 24576, 128), jnp.bfloat16), ((16, 2), jnp.int32)]),
    ("absorbed-over-two-selections", "absorbed",
     [((32, 1, 64, 512), jnp.bfloat16), ((32, 1, 64, 64), jnp.bfloat16),
      ((32, 2048, 512), jnp.bfloat16), ((32, 64, 2048), jnp.bfloat16),
      ((32,), jnp.int32)]),
    ("expanded-keys-of-192-as-256", "expanded",
     [((1, 1024, 64, 256), jnp.bfloat16), ((1, 1024, 64, 64), jnp.bfloat16),
      ((1, 24576, 640), jnp.bfloat16), ((1, 64, 24576), jnp.bfloat16),
      ((512, 64, 512), jnp.bfloat16), ((1,), jnp.int32),
      ((1, 1024, 24576), jnp.int8)]),
    # the decode of both selecting cells under its selection's mask (ISSUE
    # 59): GLM-5's two queries a row of 64 heads over 24,576 positions and
    # dots3-note's one of 128 heads over 32,768, the cache's rows of 640
    # channels as they lie
    ("under-the-mask-verify", "absorbed_under_mask",
     [((16, 2, 64, 512), jnp.bfloat16), ((16, 2, 64, 64), jnp.bfloat16),
      ((16, 24576, 640), jnp.bfloat16), ((16, 2, 24576), jnp.int8),
      ((16,), jnp.int32)]),
    ("under-the-mask-decode", "absorbed_under_mask",
     [((16, 1, 128, 512), jnp.bfloat16), ((16, 1, 128, 64), jnp.bfloat16),
      ((16, 32768, 640), jnp.bfloat16), ((16, 1, 32768), jnp.int8),
      ((16,), jnp.int32)]),
]


@pytest.mark.parametrize("kernel,shapes", [c[1:] for c in SELECTING_CASES],
                         ids=[c[0] for c in SELECTING_CASES])
def test_selecting_latent_kernels_compile_for_v5e(one_chip, kernel, shapes):
    from alpa_tpu.ops import latent_attention as la

    def call(*args):
        if kernel == "index_scores":
            return la.index_scores(*args)
        if kernel == "absorbed":
            return la.absorbed(*args, scale=192 ** -0.5)
        if kernel == "absorbed_under_mask":
            return la.absorbed_under_mask(*args, scale=192 ** -0.5)
        *rest, selected = args
        return la.expanded(*rest, scale=192 ** -0.5, selected=selected)

    compiled = jax.jit(call).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes]).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    if kernel == "absorbed_under_mask":
        # by its name in a device trace, and the cache handed in as it
        # lies: no array of its size beside it
        assert "%" + la.UNDER_MASK_NAME in text
        assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
    if kernel == "expanded":
        # by its name in a device trace, and the selection handed in as it
        # lies: the program makes no array of the mask's shape beside it
        assert "%" + la.CHUNK_UNDER_MASK_NAME in text
        queries, keys = shapes[-1][0][1:]
        made = [line.strip()[:120] for line in text.splitlines()
                if re.search(r"= \S*\[(1,)?%d,%d\]" % (queries, keys), line)
                and " parameter(" not in line]
        assert not made, made


_SELECTING_DECODES = {}


def _selecting_decode(one_chip, name):
    """The compiled tick of the selecting configuration ``name`` at ONE
    layer of its published widths, its cell's rows and served context
    (``_decode``; ``_verify_draft`` where a module drafts, whose block
    selects too); compiled once a session.  ``(compiled, rows, context)``."""
    if name in _SELECTING_DECODES:
        return _SELECTING_DECODES[name]
    import json
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    with open(os.path.join(root, "chipbench", "configs",
                           name + ".json")) as f:
        hf = dict(json.load(f), num_hidden_layers=1)
    rows, context = hf["serve"]["engine_rows"], hf["serve"]["served_context"]
    cfg = run.load_module("drivers", "serve_mla").model_config(
        hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=context)
    model = GPTModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=1024)
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    args = (params, spec(rows, 1), spec(rows),
            on_chip([(k, v) for k, v, _ in caches]),
            [spec(rows) for _ in caches])
    if gen._verify_draft is None:
        lowered = gen._decode.jitted.lower(*args)
    else:
        lowered = gen._verify_draft.jitted.lower(
            *args, spec(rows), spec(rows), spec(rows, dtype=jnp.bool_))
    _SELECTING_DECODES[name] = lowered.compile(), rows, context
    return _SELECTING_DECODES[name]


def _moved_whole(text, rows, context):
    """The instructions of a compiled tick that copy or transpose a
    selecting layer's whole cache (rows of 640 channels, index keys of
    128)."""
    whole = (r"= \S*\[(%d,%d|%d),(640|128)\]\S* "
             r"(copy|copy-start|transpose)\(") % (rows, context,
                                                 rows * context)
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(whole, line)]


def test_a_dots3_decode_is_one_query_a_row_on_v5e(one_chip):
    """``_decode`` of ``dots3-note-prev-1chip`` (its leading layer, which
    selects, at the published widths, 16 rows, served context 32,768) in
    the form the tick has since ISSUE 59: one query a row runs the
    one-query index kernel, and ONE core a selecting layer, chosen in the
    tick by what its rows hold (the cache's 32 key blocks are more than a
    query's gather is worth): a conditional whose one side is the kernel
    under the selection's mask over the cache as it lies and whose other
    is the absorbed kernel over ONE gathered selection a row.  No array of
    it has a second query, and no whole cache is copied for either
    side."""
    from alpa_tpu.model import gpt_model
    from alpa_tpu.ops import latent_attention as la
    compiled, rows, context = _selecting_decode(one_chip,
                                                "dots3-note-prev-1chip")
    assert context // la.DECODE_BLOCK_K > gpt_model.GATHER_WORTH_KEY_BLOCKS
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if KERNEL in line]
    scores = [k for k in kernels if re.search(
        r"= f32\[%d,1,%d\]" % (rows, context), k)]
    cores = [k for k in kernels if re.search(
        r"= bf16\[%d,128,512\]" % rows, k)]
    assert len(scores) == 1 and len(cores) == 2, (len(scores), len(cores))
    under_mask = [k for k in cores if "%" + la.UNDER_MASK_NAME in k]
    gathered = [k for k in cores if k not in under_mask]
    assert len(under_mask) == 1 and len(gathered) == 1
    assert "bf16[%d,%d,640]" % (rows, context) in under_mask[0]
    assert "bf16[%d,2048,512]" % rows in gathered[0]
    # both under the one conditional of the layer's ``latent_select``
    assert all("/latent_select/cond/branch_0_fun/cond/branch_" in k
               for k in cores)
    assert len(re.findall(r" conditional\(.*/latent_select/", text)) == 1
    assert len(re.findall(r"= \S*\[%d,2048,640\]\S* gather\(" % rows,
                          text)) == 1
    assert not re.search(r"\[%d,2,%d\]" % (rows, context), text)
    assert _moved_whole(text, rows, context) == []


# the tick's temporaries at the parent of PR 54, where the selection was a
# sort (``jax.lax.top_k``): the same compile, one sort a selecting block
SORTED_DECODE_TEMP_BYTES = {"glm-5-1chip": 177_382_912,
                            "dots3-note-prev-1chip": 36_941_824}


@pytest.mark.parametrize("name", ["glm-5-1chip", "dots3-note-prev-1chip"])
def test_a_selecting_decode_sorts_nothing_on_v5e(one_chip, name):
    """The tick of a configuration whose layers select (GLM-5: two
    queries a row, its one layer here and its module; dots3-note: one
    query a row) holds no sort: the 2,048 positions a query attends over
    come from counts and a compaction (``selected_positions``, PR 54);
    and its temporaries are no more than they were with the sort, within
    0.1 GB.  GLM-5's fell by more than one block's gathered rows (``bf16[
    65536,640]``, 84 MB) when its decode stopped gathering (ISSUE 59)."""
    compiled, _, _ = _selecting_decode(one_chip, name)
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    assert "top_k" not in text.lower()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < SORTED_DECODE_TEMP_BYTES[name] + 100e6, temp
    if name == "glm-5-1chip":
        assert temp < SORTED_DECODE_TEMP_BYTES[name] - 65536 * 640 * 2, temp


def test_a_glm5_tick_gathers_no_selected_rows_on_v5e(one_chip):
    """The tick of ``glm-5-1chip`` that verifies and drafts (one layer and
    the module's block, both selecting, at the published widths, 16 rows,
    served context 24,576: 24 key blocks, which two queries' gathers are
    worth whatever the rows hold): each block's decode is the kernel under
    the selection's mask, named, in no conditional; no gather fetches
    2,048 rows a query (``bf16[65536,640]``, or by row and query), no
    array holds them, and no whole cache is copied for the kernel."""
    from alpa_tpu.model import gpt_model
    from alpa_tpu.ops import latent_attention as la
    compiled, rows, context = _selecting_decode(one_chip, "glm-5-1chip")
    assert context // la.DECODE_BLOCK_K <= \
        2 * gpt_model.GATHER_WORTH_KEY_BLOCKS
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if KERNEL in line]
    cores = [k for k in kernels if "%" + la.UNDER_MASK_NAME in k]
    assert len(cores) == 2 and len(kernels) == 4, (len(cores), len(kernels))
    assert all("/latent_select/" in k for k in cores)
    assert not re.search(r" conditional\(.*/latent_select/", text)
    assert not re.search(r"\[(%d|%d,2048|%d,4096),640\]" % (
        rows * 2 * 2048, rows * 2, rows), text)
    assert not re.search(r"= \S*,640\]\S* gather\(", text)
    assert _moved_whole(text, rows, context) == []


# ---- a prefill chunk's head (PR 48) -------------------------------------

def test_a_trinity_chunk_holds_no_chunk_of_logits_on_v5e(one_chip):
    """The chunk step of one admission of ``trinity-mini-1chip`` as its
    cell compiles it (the published widths, the cell's depth, one row of
    1,024 positions, served context 16,384, bfloat16): its head runs over
    the one position it keeps, so no array of it is a chunk's logits
    (``[1024, 200192]``, ``[1, 1024, 200192]``), and the one row's are
    there."""
    import json
    from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf,
                                          init_kv_caches)
    from alpa_tpu.serve.generation import Generator
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs",
                           "trinity-mini-1chip.json")) as f:
        hf = json.load(f)
    chunk, vocab = hf["serve"]["prefill_chunk"], hf["vocab_size"]
    assert (chunk, vocab) == (1024, 200192)
    cfg = config_from_hf(hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                         seq_len=hf["serve"]["served_context"])
    model = GPTModel(cfg)
    on_chip = lambda tree: jax.tree_util.tree_map(   # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    text = gen._chunk_prefill.lower(
        params,
        jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1))),
        jax.ShapeDtypeStruct((1, vocab), jnp.bfloat16, sharding=one_chip)
    ).compile().as_text()
    assert text.startswith("HloModule jit_chunk_prefill")
    assert re.search(r"bf16\[1,%d\]" % vocab, text)
    assert not re.search(r"\[(\d+,)?%d,%d\]" % (chunk, vocab), text)
    # its full layer runs the kernel over query blocks and key blocks (PR
    # 56): no array holds a chunk's scores against the whole cache
    assert "cached_attention_query_key_blocks" in text
    assert not re.search(r"\[[\d,]*%d,%d\]" % (chunk, cfg.seq_len), text)


_PIPESHARD = {}


def _pipeshard_on_v5e(topo, num_layers=2, hidden=256, seq=128):
    """Two pipeline stages of two chips compiled for the described 2x2
    from shapes: the rehearsal cell's method at widths a product's
    sharding shows at, ``num_layers // 2`` rematerialised blocks a stage
    (the rehearsal cell's depth by default).  Compiled once a shape."""
    if (num_layers, hidden, seq) in _PIPESHARD:
        return _PIPESHARD[num_layers, hidden, seq]
    import alpa_tpu
    from alpa_tpu import PipeshardParallel
    from alpa_tpu.model.gpt_model import GPTConfig
    from alpa_tpu.pipeline_parallel.layer_construction import (
        ManualLayerOption)
    from alpa_tpu.pipeline_parallel.stage_construction import (
        UniformStageOption)
    from alpa_tpu.testing import get_gpt_train_step
    cfg = GPTConfig(vocab_size=1024, hidden_size=hidden,
                    num_layers=num_layers, num_heads=4, seq_len=seq,
                    dtype=jnp.bfloat16,
                    remat_blocks=True,
                    pipeline_boundary_every=num_layers // 2)
    method = PipeshardParallel(
        num_micro_batches=2, pipeline_schedule="1f1b",
        layer_option=ManualLayerOption(),
        stage_option=UniformStageOption(num_stages=2))
    alpa_tpu.init("local", devices=topo.devices)
    step, create_state, batch = get_gpt_train_step(cfg, 4, method)
    executable, _ = step.get_executable(
        jax.eval_shape(create_state),
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
    return _PIPESHARD.setdefault((num_layers, hidden, seq), executable)


def test_pipeshard_stages_gather_no_accumulator_on_v5e(topo):
    """No backward stage gathers a summed weight gradient or holds a
    kernel's whole on both chips, each update program gathers a kernel
    once (``tests/pipeline_parallel/test_donated_accumulators.py`` holds
    the same on four virtual CPU devices)."""
    from alpa_tpu.testing import donated_accumulator_faults
    assert donated_accumulator_faults(_pipeshard_on_v5e(topo)) == []


def test_pipeshard_forward_stage_gathers_no_logits_on_v5e(topo):
    """The same two stages (ISSUE 52): what a forward stage hands its
    backward stage leaves as the forward stage's plan produces it and
    enters as the backward stage's plan was given it, so ``stage_1_fwd``
    ends in no all-gather of the float32 logits (the parent's did: its
    backward stage had chosen "whole" at no price and the output was
    pinned to that), neither forward stage gathers a block's residual
    for its backward stage, and unification moves nothing of the four.
    ``stage_1_bwd`` gathers the logits' bfloat16 gradient no more either
    (the parent's did, once a run): the table's product contracts over
    the sequence the gradient is sharded over and reduce-scatters its
    sum onto the sharded accumulator."""
    from alpa_tpu.testing import (donated_accumulator_faults,
                                  gathers_by_shape, handed_over_faults)
    executable = _pipeshard_on_v5e(topo)
    stages = {e.name: e for e in executable.stage_execs}
    gathered = {name: gathers_by_shape(e.compiled.as_text())
                for name, e in stages.items()}
    assert gathered["stage_1_fwd"]["f32[2,128,1024]"] == 0
    assert gathered["stage_1_bwd"]["f32[2,128,1024]"] == 0
    assert gathered["stage_1_bwd"]["bf16[2,128,1024]"] == 0
    for name in ("stage_0_fwd", "stage_1_fwd"):
        assert gathered[name]["bf16[2,128,256]"] == 0
        assert gathered[name]["f32[2,128,256]"] == 0
    assert handed_over_faults(executable) == []
    assert donated_accumulator_faults(executable) == []
    assert [e.unify_overrides for e in executable.stage_execs] == [0] * 4
    for name in ("stage_0_bwd", "stage_1_bwd"):
        assert stages[name].plan_stats["given_sharded"] > 0
    # the logits leave over the sequence, as the head's product makes them
    logits, = [k for k, v in enumerate(stages["stage_1_fwd"].outvars)
               if v.aval.shape == (2, 128, 1024)]
    assert not stages["stage_1_fwd"].out_shardings[
        logits].is_fully_replicated


def test_pipeshard_backward_stage_holds_one_block_at_a_time_on_v5e(topo):
    """A backward stage of four rematerialised blocks recomputes a block
    when it comes to it: its temporaries stay under two blocks' attention
    scores (``memory_analysis()``; 22 MB here, where the scheduler, free
    to recompute every block first, held 42).  What holds it there is the
    barrier a differentiated block is lowered with, which
    ``make_constrained_fun`` keeps when it evaluates the block anew."""
    micro_batch, heads, seq = 2, 4, 1024
    executable = _pipeshard_on_v5e(topo, num_layers=8, hidden=512, seq=seq)
    scores_a_chip = micro_batch * heads * seq * seq * 4 // 2
    for e in executable.stage_execs:
        if e.name.endswith("_bwd"):
            held = e.compiled.memory_analysis().temp_size_in_bytes
            assert 0 < held < 2 * scores_a_chip, (e.name, held)


# ---- keys wider than values, the heads folded (PR 51) --------------------

def test_folded_cached_attention_compiles_for_v5e(one_chip):
    """``ops/cached_attention.py`` ``folded_cached_attention`` at the
    ``mimo-v2-flash-1chip`` cell's shapes (32 rows of 32,768 positions, 64
    query heads over 4 key/value heads, keys of 192 channels and values of
    128, the heads folded into 768 and 512 channels): one Pallas kernel,
    inside its fast memory, handed each cache as it lies."""
    from alpa_tpu.ops import cached_attention as ca

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, keys, values = (spec(32, 1, 64, 192), spec(32, 32768, 768),
                       spec(32, 32768, 512))
    assert ca.folded_fits(q, keys, values)
    assert ca.folded_block_k(keys, values) == 512
    compiled = jax.jit(ca.folded_cached_attention).lower(
        q, keys, values, spec(32, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the caches as they were named: no padding, no other order
    assert "bf16[32,32768,768]{2,1,0:T(8,128)(2,1)}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


# ---- a prefill chunk's full layers (PR 56) -------------------------------

# (id, queries (B, s, H, D), keys, values, block of the block-causal mask):
# the chunk step's full layers of the three cells that reach the kernel
CHUNK_CASES = [
    ("mimo", (1, 1024, 64, 192), (1, 32768, 768), (1, 32768, 512), 0),
    ("trinity", (1, 1024, 32, 128), (1, 16384, 4, 128), (1, 16384, 4, 128),
     0),
    ("sdar", (1, 1024, 32, 128), (1, 8192, 4, 128), (1, 8192, 4, 128), 4),
]


@pytest.mark.parametrize("q_shape,k_shape,v_shape,block",
                         [c[1:] for c in CHUNK_CASES],
                         ids=[c[0] for c in CHUNK_CASES])
def test_chunk_attention_compiles_for_v5e(one_chip, q_shape, k_shape,
                                          v_shape, block):
    """``ops/cached_attention.py`` ``chunk_attention`` at the cells' chunk
    shapes, in both layouts of a cache: one Pallas kernel and no loop
    around it, handed each cache as it lies (no array of a cache's size
    beside the caches), and no float32 array as large as one head's
    scores of one key block (they stay in the kernel's fast memory)."""
    from alpa_tpu.ops import cached_attention as ca

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, keys, values = spec(*q_shape), spec(*k_shape), spec(*v_shape)
    assert ca.chunk_fits(q, keys, values)
    compiled = jax.jit(functools.partial(
        ca.chunk_attention, block=block)).lower(
            q, keys, values, spec(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    assert "cached_attention_query_key_blocks" in text
    assert " while(" not in text
    held = [math.prod(int(d) for d in dims.split(","))
            for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(held, default=0) < q_shape[1] * ca.CHUNK_BLOCK_K
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_a_mimo_tick_and_chunk_read_what_the_rows_hold_on_v5e(one_chip):
    """The decode tick and the chunk step of ``mimo-v2-flash-1chip`` as its
    cell compiles them (the published widths, the cell's depth, 32 rows,
    served context 32,768, bfloat16).  The tick: its two full layers run
    the folded kernel, beside the caches it holds megabytes, and no array
    of a full cache's size is a copy or a transpose.  The chunk: no array
    holds a chunk's scores against every position the cache can hold."""
    import json
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    hf = run.load_json(run.HERE, "configs", "mimo-v2-flash-1chip.json")
    serve = hf["serve"]
    rows, chunk, context = (serve["engine_rows"], serve["prefill_chunk"],
                            serve["served_context"])
    cfg = run.load_module("drivers", "serve_mla").model_config(
        hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=context)
    model = GPTModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    tick = gen._decode.jitted.lower(
        params, spec(rows, 1), spec(rows),
        on_chip([(k, v) for k, v, _ in caches]),
        [spec(rows) for _ in caches]).compile()
    text = tick.as_text()
    assert text.startswith("HloModule jit_decode")
    assert text.count("cached_attention_folded_key_blocks") >= 2
    assert tick.memory_analysis().temp_size_in_bytes < 64 * 2**20
    moved = [line for line in text.splitlines()
             if re.search(r"= bf16\[%d,%d,(768|512)\]" % (rows, context),
                          line) and re.search(r" (copy|transpose)\(", line)]
    assert not moved, moved[:3]
    step = gen._chunk_prefill.lower(
        params, spec(1, chunk), spec(1),
        on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1))),
        spec(1, cfg.vocab_size, dtype=jnp.bfloat16)).compile()
    assert step.memory_analysis().temp_size_in_bytes < 2**30
    assert not re.search(r"\[[\d,]*%d,%d\]" % (chunk, context),
                         step.as_text())
    # its two full layers run the kernel over query blocks and key blocks
    # (one program lowered for both), and no loop walks the key blocks
    assert "cached_attention_query_key_blocks" in step.as_text()
    assert not [line for line in step.as_text().splitlines()
                if " while(" in line and "/attention/" in line]


def test_a_nemotron_tick_updates_every_state_in_place_on_v5e(one_chip):
    """The decode tick of ``nemotron-3-nano-30b-a3b-1chip`` at its
    published widths, its cell's 64 rows and served context, and ONE
    period of its depth (``MEMEM*EME``: four Mamba-2 mixers, an attention,
    four expert layers of this chip's 8 experts 1,856 wide, which the
    grouped matmul walks whole).  Both states of every ``M`` layer and the
    attention layer's caches are aliased to the results that replace them,
    and the tick's temporaries hold no array of an ssm state's shape: 3.1
    GB of states at full depth move once in and once out, in place."""
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    hf = dict(run.load_json(run.HERE, "configs",
                            "nemotron-3-nano-30b-a3b-1chip.json"),
              num_hidden_layers=9)
    rows, context = hf["serve"]["engine_rows"], hf["serve"]["served_context"]
    cfg = run.load_module("drivers", "serve_mla").model_config(
        hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=context)
    assert cfg.attention.count("ssm") == 4 and cfg.experts_held == (0, 8)
    model = GPTModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=1024)
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    tick = gen._decode.jitted.lower(
        params, spec(rows, 1), spec(rows),
        on_chip([(k, v) for k, v, _ in caches]),
        [spec(rows) for _ in caches]).compile()
    memory = tick.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for k, v, _ in caches
               for x in (k, v))
    assert held == 4 * rows * (64 * 64 * 128 * 4 + 3 * 6144 * 2) + \
        rows * context * 2 * 2 * 128 * 2
    assert memory.alias_size_in_bytes == held
    assert memory.temp_size_in_bytes < 64 * 2**20
    text = tick.as_text()
    head = text[:text.index("\n")]
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         head)
    # (both arrays of all nine entries, an expert layer's empty ones too)
    assert len(aliased) == 2 * 9
    state = r"f32\[%d,64,64,128\]" % rows
    # an ssm state is a parameter, or comes out of the one fusion that
    # reads it and writes it; nothing copies, transposes or converts one
    moved = [line for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|convert|bitcast-convert)"
                          r"\(" % state, line)]
    assert not moved, moved[:3]
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * 4


@pytest.mark.parametrize("name,rows,window", [
    ("longcat-flash-1chip", 12288, 512),
    ("nemotron-3-nano-30b-a3b-1chip", 6144, 768)])
def test_a_chunks_expert_layer_walks_a_window_on_v5e(one_chip, monkeypatch,
                                                     name, rows, window):
    """One expert layer of a cell that holds a share of its experts, at the
    published widths, over a prefill chunk's 1,024 positions (LongCat: 16 of
    768 router outputs held, 12 picks a token; Nemotron: 8 of 128, 6 picks,
    ungated): the compiled layer holds no array of all ``tokens x k`` rows
    of the hidden size, in any type, and both grouped matmuls run over the
    rule's window with a row tile of 128 (ISSUE 60)."""
    from alpa_tpu.model import moe
    from alpa_tpu.ops import grouped_matmul as gm
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    cfg = run.load_module("drivers", "serve_mla").model_config(
        run.load_json(run.HERE, "configs", name + ".json"),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert 1024 * cfg.num_experts_per_tok == rows
    assert moe.expert_window(cfg, 1024) == window
    seen = []

    def recording(*args, tiling, **static):
        seen.append((args[0].shape[0], tiling[0]))
        return gmm(*args, tiling=tiling, **static)

    gmm = gm.gmm
    monkeypatch.setattr(gm, "gmm", recording)
    layer = moe.DroplessExperts(cfg)
    x = jax.ShapeDtypeStruct((1, 1024, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    seen.clear()
    text = jax.jit(layer.apply).lower(params, x).compile().as_text()
    # (each call traced for the TPU and for the platforms that interpret)
    assert set(seen) == {(window, 128)} and len(seen) == 4, seen
    assert text.count(KERNEL) == 2
    assert not re.search(r"\[%d,%d\]" % (rows, cfg.hidden_size), text)
    assert not re.search(r"\[1024,%d,%d\]" % (cfg.num_experts_per_tok,
                                               cfg.hidden_size), text)
    assert re.search(r" while\(", text)


def test_a_jamba_tick_and_chunk_keep_their_named_bytes_on_v5e(one_chip):
    """The decode tick and the chunk step of ``jamba2-3b-1chip`` at its
    published widths, its cell's 16 rows and served context of 65,536, and
    ONE Mamba-1 and ONE attention layer.  The tick: both states of the
    mixer and both caches of the attention's one key/value head are
    aliased to the results that replace them, at their NAMED bytes (an ssm
    state 16 x 5,120 float32 = 327,680 B a row, a cache 128 x 2 B a
    position a tensor: no 16 state values padded to 128 lanes, no one head
    padded to a tile of 16), nothing copies a state, and its attention is
    the folded kernel over key blocks (20 query rows padded to 32).  The
    chunk: its attention is the kernel over query blocks and key blocks
    (64 positions x 20 heads a block), its recurrence the kernel over
    positions, and no loop walks either."""
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    hf = dict(run.load_json(run.HERE, "configs", "jamba2-3b-1chip.json"),
              num_hidden_layers=2, attn_layer_period=2, attn_layer_offset=1)
    serve = hf["serve"]
    rows, chunk, context = (serve["engine_rows"], serve["prefill_chunk"],
                            serve["served_context"])
    cfg = run.load_module("drivers", "serve_s6").model_config(
        hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=context)
    assert cfg.attention == ("s6", "full") and (rows, context) == (16, 65536)
    model = GPTModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    tick = gen._decode.jitted.lower(
        params, spec(rows, 1), spec(rows),
        on_chip([(k, v) for k, v, _ in caches]),
        [spec(rows) for _ in caches]).compile()
    memory = tick.memory_analysis()
    held = rows * (16 * 5120 * 4 + 3 * 5120 * 2) + \
        rows * context * 2 * 128 * 2
    assert held == sum(x.size * x.dtype.itemsize for k, v, _ in caches
                       for x in (k, v))
    assert memory.alias_size_in_bytes == held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    # (the arguments: the weights, the caches, the ids and the indices)
    assert memory.argument_size_in_bytes < weights + held + 2**20
    assert memory.temp_size_in_bytes < 16 * 2**20
    text = tick.as_text()
    head = text[:text.index("\n")]
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         head)
    assert len(aliased) == 2 * 2
    assert "f32[%d,16,5120]{2,1,0:T(8,128)}" % rows in head
    assert "bf16[%d,%d,128]{2,1,0:T(8,128)(2,1)}" % (rows, context) in head
    moved = [line for line in text.splitlines()
             if re.search(r"= (f32\[%d,16,5120\]|bf16\[%d,%d,128\])\S* "
                          r"(copy|transpose|convert)\(" % (rows, rows,
                                                           context), line)]
    assert not moved, moved[:3]
    assert "cached_attention_folded_key_blocks" in text
    step = gen._chunk_prefill.lower(
        params, spec(1, chunk), spec(1),
        on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1))),
        spec(1, cfg.vocab_size, dtype=jnp.bfloat16)).compile()
    assert step.memory_analysis().temp_size_in_bytes < 2**28
    text = step.as_text()
    assert "cached_attention_query_key_blocks" in text
    assert "selective_scan_positions" in text
    assert not [line for line in text.splitlines() if " while(" in line]
    assert not re.search(r"\[[\d,]*%d,%d\]" % (chunk, context), text)


def test_an_evabyte_tick_and_chunk_keep_their_named_bytes_on_v5e(one_chip):
    """The decode tick and the chunk step of ``evabyte-1chip`` at its
    published widths, its cell's 16 rows and served context of 32,768, and
    ONE layer.  The tick: the layer's one pair of arrays (2,048 summaries
    and 2,048 window rows of 32 heads x 128 channels, folded) is aliased
    to the results that replace it at its NAMED bytes, 67,108,864 B a row,
    nothing pads or copies it, and its attention is the folded kernel over
    key blocks with the cache in two parts (32 key/value heads of whole
    lanes, one query head each).  The chunk: its attention is the kernel
    over query blocks and key blocks, and no loop walks the cache.  The
    gauge names a kernel for both."""
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import metrics as tmetrics
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from chipbench import run
    hf = dict(run.load_json(run.HERE, "configs", "evabyte-1chip.json"),
              num_hidden_layers=1)
    serve = hf["serve"]
    rows, chunk, context = (serve["engine_rows"], serve["prefill_chunk"],
                            serve["served_context"])
    cfg = run.load_module("drivers", "serve_eva").model_config(
        hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=context)
    assert cfg.attention == "eva" and (rows, context) == (16, 32768)
    model = GPTModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def cores():
        return {key.split('core="')[1].split('"')[0] + "/" +
                key.split('queries="')[1].split('"')[0]: value
                for key, value in tmetrics.get_registry().snapshot().items()
                if key.startswith("alpa_cached_attention_core") and
                'heads="32"' in key and 'head_dim="128"' in key}

    before = cores()
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32)))
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    tick = gen._decode.jitted.lower(
        params, spec(rows, 1), spec(rows),
        on_chip([(k, v) for k, v, _ in caches]),
        [spec(rows) for _ in caches]).compile()
    memory = tick.memory_analysis()
    held = rows * 67_108_864
    assert held == sum(x.size * x.dtype.itemsize for k, v, _ in caches
                       for x in (k, v))
    assert memory.alias_size_in_bytes == held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    # (the arguments: the weights, the caches, the ids and the indices)
    assert memory.argument_size_in_bytes < weights + held + 2**20
    assert memory.temp_size_in_bytes < 16 * 2**20
    text = tick.as_text()
    head = text[:text.index("\n")]
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                          head)) == 2
    named = r"bf16\[%d,4096,4096\]" % rows
    assert "bf16[%d,4096,4096]{2,1,0:T(8,128)(2,1)}" % rows in head
    moved = [line for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|convert)\(" % named,
                          line)]
    assert not moved, moved[:3]
    assert "cached_attention_folded_key_blocks" in text
    step = gen._chunk_prefill.lower(
        params, spec(1, chunk), spec(1),
        on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1))),
        spec(1, cfg.vocab_size, dtype=jnp.bfloat16)).compile()
    assert step.memory_analysis().temp_size_in_bytes < 2**27
    text = step.as_text()
    assert "cached_attention_query_key_blocks" in text
    assert not [line for line in text.splitlines() if " while(" in line]
    after = cores()
    assert {name: after[name] - before.get(name, 0) for name in after
            if after[name] != before.get(name, 0)} == {
                "key_blocks/1": 1.0, "query_key_blocks/%d" % chunk: 1.0}
