"""Collective resharding lowering + quantized transfer codec (ISSUE 7).

Oracle 1: every lossless strategy is bit-exact against the
``direct_p2p`` path — at the executor level (one edge, every eligible
collective) and end-to-end on the unified graph executor (forced
strategy over the 4-stage MLP train step, grouped + donated, registers
and overlap modes).  Oracle 2: the codec's documented error contract,
property-style over seeded shapes.  Oracle 3: strategy selection — ``auto``
keeps an edge direct where the direct move is chip to chip and takes
``aligned_relayout`` elsewhere (ISSUE 46: no edge hands an array to the
host), a forced strategy is taken exactly where it is eligible and
degrades to direct elsewhere, and decisions replay from the compile
cache."""
import numpy as np
import pytest

import alpa_tpu
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel import cross_mesh_resharding as cmr
from alpa_tpu.pipeline_parallel import reshard_codec as codec


@pytest.fixture(autouse=True)
def _restore_knobs():
    prev = (global_config.reshard_strategy,
            global_config.reshard_quantize,
            global_config.reshard_quantize_min_bytes,
            global_config.pipeline_dispatch_mode)
    yield
    (global_config.reshard_strategy,
     global_config.reshard_quantize,
     global_config.reshard_quantize_min_bytes,
     global_config.pipeline_dispatch_mode) = prev


def _two_meshes(n_src=4, n_dst=4):
    devs = jax.devices()
    return (Mesh(np.array(devs[:n_src]), ("x",)),
            Mesh(np.array(devs[n_src:n_src + n_dst]), ("x",)))


class _Aval:

    def __init__(self, shape, dtype=np.float32):
        self.shape = shape
        self.dtype = np.dtype(dtype)


# ---------------------------------------------------------------------
# strategy selection (cost model + eligibility + cache)
# ---------------------------------------------------------------------

CASES = {
    "rowshard->replicated": (P("x", None), P()),
    "rowshard->colshard": (P("x", None), P(None, "x")),
    "replicated->rowshard": (P(), P("x", None)),
    "rowshard->rowshard": (P("x", None), P("x", None)),
}

# the strategies each edge is eligible for besides direct_p2p
ELIGIBLE = {
    "rowshard->replicated": {"slice_all_gather", "aligned_relayout"},
    "rowshard->colshard": {"all_to_all", "aligned_relayout"},
    "replicated->rowshard": {"aligned_relayout"},
    "rowshard->rowshard": set(),
}

# what ``auto`` takes: direct where the direct move is 1:1, else the
# landing whose move is
AUTO = {
    "rowshard->replicated": "aligned_relayout",
    "rowshard->colshard": "aligned_relayout",
    "replicated->rowshard": "aligned_relayout",
    "rowshard->rowshard": "direct_p2p",
}


def _shardings(case):
    src_mesh, dst_mesh = _two_meshes()
    ss, ds = CASES[case]
    return NamedSharding(src_mesh, ss), NamedSharding(dst_mesh, ds)


class TestStrategySelection:

    @pytest.mark.parametrize("case", list(CASES))
    def test_default_knobs_choose_by_the_shardings(self, case):
        # only a candidate whose wire leg moves chip to chip; among
        # those none has a price, and the first offered wins
        src, dst = _shardings(case)
        strat, _, opts = cmr.choose_strategy((8, 8), 4, src, dst)
        assert strat == AUTO[case]
        assert opts[strat]["chip_to_chip"]
        assert opts["direct_p2p"]["chip_to_chip"] == \
            (AUTO[case] == "direct_p2p")

    def test_link_stats_pinned_4p4(self):
        # rowshard -> replicated, (8,8) f32: direct sends each 64 B
        # shard to all 4 replicas (4 msgs, 256 B per link, 1024 B
        # total); the scattered landing is a 1:1 aligned move (1 msg,
        # 64 B per link, 256 B total)
        src, dst = _shardings("rowshard->replicated")
        _, _, opts = cmr.choose_strategy((8, 8), 4, src, dst)
        d = opts["direct_p2p"]["stats"]
        assert (d["max_link_messages"], d["max_link_bytes"],
                d["total_bytes"]) == (4, 256.0, 1024.0)
        s = opts["slice_all_gather"]["stats"]
        assert (s["max_link_messages"], s["max_link_bytes"],
                s["total_bytes"]) == (1, 64.0, 256.0)

    @pytest.mark.parametrize("case", list(CASES))
    @pytest.mark.parametrize("forced", [s for s in cmr.RESHARD_STRATEGIES
                                        if s != "direct_p2p"])
    def test_forced_strategy_taken_where_eligible(self, forced, case):
        # a forced strategy pins the edges it is eligible for; the
        # others fall back to direct_p2p
        global_config.reshard_strategy = forced
        src, dst = _shardings(case)
        strat, _, opts = cmr.choose_strategy((8, 8), 4, src, dst)
        assert set(opts) - {"direct_p2p"} == ELIGIBLE[case]
        assert strat == (forced if forced in ELIGIBLE[case]
                         else "direct_p2p")

    def test_resolve_strategy_replays_from_cache(self):
        global_config.reshard_strategy = "slice_all_gather"
        src, dst = _shardings("rowshard->replicated")
        s1, c1, hit1 = cmr.resolve_strategy((8, 8), 4, src, dst)
        s2, c2, hit2 = cmr.resolve_strategy((8, 8), 4, src, dst)
        assert not hit1 and hit2
        assert s1 == s2 == "slice_all_gather"
        assert c1 == c2

    def test_cache_key_covers_reshard_strategy(self):
        # same edge, another forced strategy → an independent decision
        src, dst = _shardings("rowshard->replicated")
        s1, _, _ = cmr.resolve_strategy((8, 8), 4, src, dst)
        global_config.reshard_strategy = "slice_all_gather"
        s2, _, hit2 = cmr.resolve_strategy((8, 8), 4, src, dst)
        assert not hit2
        assert (s1, s2) == ("aligned_relayout", "slice_all_gather")

    def test_plan_resharding_carries_strategy(self):
        global_config.reshard_strategy = "slice_all_gather"
        src, dst = _shardings("rowshard->replicated")
        spec = cmr.plan_resharding((8, 8), 4, src, dst)
        assert spec.strategy == "slice_all_gather"
        assert spec.wire_messages == 1
        assert spec.wire_bytes == 256.0
        assert set(spec.strategy_costs) == set(spec.strategy_stats)
        assert cmr.format_resharding_plan().count("slice_all_gather") > 0


# ---------------------------------------------------------------------
# executor bit-exactness (one edge, every strategy)
# ---------------------------------------------------------------------

class TestExecutorBitExactness:

    def _run(self, case_src, case_dst, strategy):
        src_mesh, dst_mesh = _two_meshes()
        src = NamedSharding(src_mesh, case_src)
        dst = NamedSharding(dst_mesh, case_dst)
        shape = (8, 8)
        x = np.arange(64, dtype=np.float32).reshape(shape) * 0.37 - 11.0
        val = jax.device_put(jnp.asarray(x), src)
        _, _, opts = cmr.choose_strategy(shape, 4, src, dst)
        assert strategy in opts, f"{strategy} ineligible for this edge"
        t = cmr.CollectiveTransfer(_Aval(shape), src, dst, strategy,
                                   opts[strategy]["landing"],
                                   opts[strategy]["relayout_first"])
        out = t(val)
        assert out.sharding.is_equivalent_to(dst, 2)
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_slice_all_gather(self):
        self._run(P("x", None), P(), "slice_all_gather")

    def test_all_to_all(self):
        self._run(P("x", None), P(None, "x"), "all_to_all")

    def test_reduce_scatter_gather(self):
        self._run(P(), P(), "reduce_scatter_gather")

    def test_make_transfer_weight_never_quantized(self):
        global_config.reshard_quantize = "int8"
        global_config.reshard_quantize_min_bytes = 1
        src_mesh, dst_mesh = _two_meshes()
        src = NamedSharding(src_mesh, P("x", None))
        dst = NamedSharding(dst_mesh, P())
        t = cmr.make_transfer(_Aval((8, 8)), src, dst, cross=True,
                              weight=True)
        assert not isinstance(t, codec.QuantizedTransfer)
        t2 = cmr.make_transfer(_Aval((8, 8)), src, dst, cross=True,
                               weight=False)
        assert isinstance(t2, codec.QuantizedTransfer)

    def test_make_transfer_same_mesh_stays_direct(self):
        global_config.reshard_strategy = "slice_all_gather"
        src_mesh, _ = _two_meshes()
        sh = NamedSharding(src_mesh, P("x", None))
        t = cmr.make_transfer(_Aval((8, 8)), sh, sh, cross=False)
        assert isinstance(t, cmr.DirectTransfer)

    def test_quantized_transfer_within_bound(self):
        global_config.reshard_quantize_min_bytes = 1
        src_mesh, dst_mesh = _two_meshes()
        src = NamedSharding(src_mesh, P("x", None))
        dst = NamedSharding(dst_mesh, P())
        rng = np.random.default_rng(7)
        x = rng.standard_normal((8, 8)).astype(np.float32) * 5
        val = jax.device_put(jnp.asarray(x), src)
        t = codec.maybe_quantized_transfer(_Aval((8, 8)), src, dst,
                                           "int8")
        assert t is not None
        out = t(val)
        assert out.sharding.is_equivalent_to(dst, 2)
        # whole array is one block: error ≤ amax / 254
        bound = np.abs(x).max() / 250 + 1e-7
        assert np.abs(np.asarray(out) - x).max() <= bound


# ---------------------------------------------------------------------
# no edge hands an array to the host (ISSUE 46)
# ---------------------------------------------------------------------

@pytest.fixture
def host_fetches(monkeypatch):
    """Counts reads of ``ArrayImpl._value``: what ``jax.device_put``
    does to an array it cannot move shard by shard."""
    from jax._src import array as jarray
    fetched = []
    inner = jarray.ArrayImpl._value.fget

    def counted(self):
        fetched.append(self.shape)
        return inner(self)

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counted))
    return fetched


def _stage_meshes():
    """Two 1x2 submeshes with the axes a pipeshard plan gives them."""
    devs = jax.devices()
    return (Mesh(np.array(devs[:2]).reshape(1, 2), ("mesh0", "mesh1")),
            Mesh(np.array(devs[2:4]).reshape(1, 2), ("mesh0", "mesh1")))


# (source spec, destination spec) -> (strategy, relayout on the source
# mesh first, host fetches of the direct device_put)
EDGES = {
    "sharded->replicated": (P(None, "mesh1"), P(),
                            "aligned_relayout", False, 1),
    "dim0->dim1": (P("mesh1"), P(None, "mesh1"),
                   "aligned_relayout", False, 1),
    "replicated->sharded": (P(), P(None, "mesh1"),
                            "aligned_relayout", True, 1),
    "replicated->replicated": (P(), P(), "direct_p2p", None, 0),
    "1:1": (P(None, "mesh1"), P(None, "mesh1"), "direct_p2p", None, 0),
}


@pytest.mark.parametrize("edge", list(EDGES))
def test_auto_crosses_chip_to_chip(edge, host_fetches):
    src_spec, dst_spec, strategy, relayout_first, direct_fetches = \
        EDGES[edge]
    mesh_a, mesh_b = _stage_meshes()
    src = NamedSharding(mesh_a, src_spec)
    dst = NamedSharding(mesh_b, dst_spec)
    shape = (4, 16, 32)
    x = (np.arange(np.prod(shape)).reshape(shape) * 0.37 - 11.0) \
        .astype(jnp.bfloat16)
    val = jax.block_until_ready(jax.device_put(jnp.asarray(x), src))

    t = cmr.make_transfer(_Aval(shape, jnp.bfloat16), src, dst, cross=True)
    if strategy == "direct_p2p":
        assert isinstance(t, cmr.DirectTransfer)
    else:
        assert isinstance(t, cmr.CollectiveTransfer)
        assert t.strategy == strategy
        assert t.relayout_first == relayout_first
        # the wire leg is the 1:1 move, on whichever side the landing is
        wire = (t.inter_sharding, dst) if relayout_first else \
            (src, t.inter_sharding)
        assert cmr.shard_structures_match(shape, *wire)

    del host_fetches[:]
    out = jax.block_until_ready(t(val))
    assert host_fetches == []               # the edge stays on the chips
    # ... where jax's own move of the same edge does not (this says when
    # jax stops doing so, and the rule can go)
    want = jax.block_until_ready(jax.device_put(val, dst))
    assert len(host_fetches) == direct_fetches
    assert out.sharding.is_equivalent_to(dst, len(shape))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_forced_strategy_honoured_and_unaligned_meshes_stay_direct():
    mesh_a, mesh_b = _stage_meshes()
    src = NamedSharding(mesh_a, P(None, "mesh1"))
    dst = NamedSharding(mesh_b, P())
    aval = _Aval((4, 16, 32), jnp.bfloat16)
    # the parent's path, for whoever wants the comparison
    global_config.reshard_strategy = "direct_p2p"
    assert isinstance(cmr.make_transfer(aval, src, dst, cross=True),
                      cmr.DirectTransfer)
    # a forced landing that goes through the host is still taken
    global_config.reshard_strategy = "slice_all_gather"
    t = cmr.make_transfer(aval, src, dst, cross=True)
    assert t.strategy == "slice_all_gather"
    assert t.inter_sharding.spec == P(None, None, "mesh1")
    # ... also against a plan that chose otherwise under another knob
    plan = cmr.plan_resharding(aval.shape, 2, src, dst)
    assert plan.strategy == "slice_all_gather"
    global_config.reshard_strategy = "auto"
    assert cmr.make_transfer(aval, src, dst, cross=True,
                             plan=plan).strategy == "aligned_relayout"
    # meshes whose axes do not line up (names, and 4 chips against 2):
    # no landing is a 1:1 move, and the edge stays direct
    devs = jax.devices()
    four = Mesh(np.array(devs[4:8]), ("x",))
    src4 = NamedSharding(four, P(None, "x"))
    strat, _, opts = cmr.choose_strategy(aval.shape, 2, src4, dst)
    assert "aligned_relayout" not in opts
    assert not any(o["chip_to_chip"] for o in opts.values())
    assert strat == "direct_p2p"
    assert isinstance(cmr.make_transfer(aval, src4, dst, cross=True),
                      cmr.DirectTransfer)


def test_two_leg_executor_guards_the_runtime_sharding(host_fetches):
    """An array that arrives in another layout than the plan assumed
    takes plain device_put (right, through the host) and is counted."""
    from alpa_tpu.telemetry import metrics as _tmetrics
    mesh_a, mesh_b = _stage_meshes()
    src = NamedSharding(mesh_a, P(None, "mesh1"))
    dst = NamedSharding(mesh_b, P())
    shape = (4, 16, 32)
    t = cmr.make_transfer(_Aval(shape), src, dst, cross=True)
    x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    fam = _tmetrics.get_registry().get("alpa_reshard_runtime_fallback_total")
    before = fam.value
    planned = t(jax.device_put(jnp.asarray(x), src))
    assert fam.value == before and host_fetches == []
    other = t(jax.device_put(jnp.asarray(x),
                             NamedSharding(mesh_a, P("mesh1"))))
    assert fam.value == before + 1 and len(host_fetches) == 1
    for out in (planned, other):
        assert out.sharding.is_equivalent_to(dst, 3)
        np.testing.assert_array_equal(np.asarray(out), x)


# ---------------------------------------------------------------------
# codec error contract (seeded, property-style)
# ---------------------------------------------------------------------

def _block_bounds(x, frac):
    """Per-element error bound: ``frac`` of the element's block max."""
    flat = np.ravel(np.asarray(x, dtype=np.float32))
    nb = -(-flat.size // codec.BLOCK)
    blocks = np.pad(flat, (0, nb * codec.BLOCK - flat.size)) \
        .reshape(nb, codec.BLOCK)
    amax = np.abs(blocks).max(axis=1, keepdims=True)
    return (np.broadcast_to(amax * frac, blocks.shape)
            .reshape(-1)[:flat.size])


class TestCodecContract:

    SHAPES = [(515,), (256,), (8, 8), (1000, 3), (7,), (1,), (3, 5, 7)]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_int8_error_bound(self, seed):
        rng = np.random.default_rng(seed)
        for shape in self.SHAPES:
            x = (rng.standard_normal(shape) *
                 rng.uniform(0.01, 100)).astype(np.float32)
            q, s = codec.encode(jnp.asarray(x), "int8")
            y = np.asarray(codec.decode(q, s, shape, np.float32, "int8"))
            # documented: ≤ amax_block/254 (1/250 + eps gives slack for
            # the fp32 scale arithmetic)
            bound = _block_bounds(x, 1 / 250) + 1e-7
            err = np.abs(np.ravel(y) - np.ravel(x))
            assert (err <= bound).all(), shape

    @pytest.mark.parametrize("seed", [0, 1])
    def test_fp8_error_bound(self, seed):
        if not codec.have_fp8():
            pytest.skip("no float8_e4m3fn in this jax build")
        rng = np.random.default_rng(seed)
        for shape in self.SHAPES:
            x = (rng.standard_normal(shape) *
                 rng.uniform(0.01, 100)).astype(np.float32)
            q, s = codec.encode(jnp.asarray(x), "fp8")
            y = np.asarray(codec.decode(q, s, shape, np.float32, "fp8"))
            # documented: ≤ 7% of the block max magnitude
            bound = _block_bounds(x, 0.07) + 1e-7
            err = np.abs(np.ravel(y) - np.ravel(x))
            assert (err <= bound).all(), shape

    def test_zeros_bit_exact(self):
        for mode in ("int8",) + (("fp8",) if codec.have_fp8() else ()):
            x = jnp.zeros((300,), jnp.float32)
            q, s = codec.encode(x, mode)
            y = codec.decode(q, s, (300,), np.float32, mode)
            np.testing.assert_array_equal(np.asarray(y),
                                          np.zeros(300, np.float32))

    def test_bf16_roundtrip_bound(self):
        rng = np.random.default_rng(3)
        x = (rng.standard_normal((400,)) * 4).astype(jnp.bfloat16)
        q, s = codec.encode(jnp.asarray(x), "int8")
        y = np.asarray(codec.decode(q, s, (400,), jnp.bfloat16,
                                    "int8")).astype(np.float32)
        xf = np.asarray(x).astype(np.float32)
        # int8 step + one bf16 rounding of the decoded value
        bound = _block_bounds(xf, 1 / 250 + 1 / 128) + 1e-6
        assert (np.abs(y - xf) <= bound).all()

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_error_bound_table_is_the_contract(self, seed):
        """ISSUE 14 satellite: the ``codec.ERROR_BOUND`` table — the
        single source of truth the numerics certification composes —
        must hold property-style on adversarial inputs: exact zeros,
        denormals, ragged tails, bf16 payloads."""
        rng = np.random.default_rng(seed)
        modes = ("int8",) + (("fp8",) if codec.have_fp8() else ())
        for mode in modes:
            frac = codec.ERROR_BOUND[mode]
            for shape in [(codec.BLOCK,), (codec.BLOCK + 3,), (5,),
                          (2, codec.BLOCK - 1)]:
                n = int(np.prod(shape))
                x = (rng.standard_normal(n) *
                     rng.uniform(1e-3, 1e3)).astype(np.float32)
                # sprinkle exact zeros and denormals into every block
                x[rng.integers(0, n, size=max(1, n // 7))] = 0.0
                x[rng.integers(0, n, size=max(1, n // 11))] = 1e-40
                x = x.reshape(shape)
                q, s = codec.encode(jnp.asarray(x), mode)
                y = np.asarray(codec.decode(q, s, shape, np.float32,
                                            mode))
                bound = _block_bounds(x, frac) + 1e-7
                err = np.abs(np.ravel(y) - np.ravel(x))
                assert (err <= bound).all(), (mode, shape)
            # bf16 payload: table bound + one bf16 rounding step
            xb = (rng.standard_normal(300) * 2).astype(jnp.bfloat16)
            q, s = codec.encode(jnp.asarray(xb), mode)
            yb = np.asarray(codec.decode(q, s, (300,), jnp.bfloat16,
                                         mode)).astype(np.float32)
            xf = np.asarray(xb).astype(np.float32)
            bound = _block_bounds(xf, frac + 1 / 128) + 1e-6
            assert (np.abs(yb - xf) <= bound).all(), mode

    def test_error_bound_values_pinned(self):
        """The documented bounds the plan verifier composes: int8 is
        blockmax/254 (symmetric int8 over 127 steps), fp8 is 7%."""
        assert codec.ERROR_BOUND["int8"] == 1.0 / 254.0
        assert codec.ERROR_BOUND["fp8"] == 0.07

    def test_eligibility_gating(self):
        global_config.reshard_quantize_min_bytes = 65536
        big, small = _Aval((256, 256)), _Aval((8, 8))
        assert codec.eligible(big, "int8")
        assert not codec.eligible(small, "int8")        # below threshold
        assert not codec.eligible(_Aval((256, 256), np.int32), "int8")
        assert not codec.eligible(_Aval((256, 256), np.float16), "int8")
        assert not codec.eligible(big, "off")
        assert codec.eligible(_Aval((256, 256), jnp.bfloat16), "int8")

    def test_wire_bytes_reduction(self):
        # fp32 → int8 with one fp32 scale per 256 elements: ≥ 3.5x
        n = 1024 * 256
        ratio = (n * 4) / codec.wire_bytes((1024, 256), 4, "int8")
        assert ratio >= 3.5

    def test_passthrough_bit_exact(self):
        """Lossless path sanity: with the codec off (or the edge
        ineligible) a cross-mesh fp32/bf16 transfer is bit-exact."""
        src_mesh, dst_mesh = _two_meshes()
        src = NamedSharding(src_mesh, P("x", None))
        dst = NamedSharding(dst_mesh, P())
        for dtype in (np.float32, jnp.bfloat16):
            x = (np.arange(64).reshape(8, 8) * 0.123).astype(dtype)
            t = cmr.make_transfer(_Aval((8, 8), dtype), src, dst,
                                  cross=True)
            assert isinstance(t, cmr.CollectiveTransfer)  # codec off
            assert t.strategy == "aligned_relayout"
            out = t(jax.device_put(jnp.asarray(x), src))
            np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ---------------------------------------------------------------------
# end-to-end: forced strategies on the unified graph executor
# ---------------------------------------------------------------------

def _run_mlp(mode, strategy="auto", quantize="off", n_steps=2):
    from alpa_tpu import PipeshardParallel
    from alpa_tpu.pipeline_parallel.layer_construction import (
        AutoLayerOption)
    from alpa_tpu.pipeline_parallel.stage_construction import (
        UniformStageOption)
    from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                  get_mlp_train_step)
    global_config.pipeline_dispatch_mode = mode
    global_config.reshard_strategy = strategy
    global_config.reshard_quantize = quantize
    if quantize != "off":
        global_config.reshard_quantize_min_bytes = 1
    method = PipeshardParallel(
        num_micro_batches=2,
        layer_option=AutoLayerOption(layer_num=4),
        stage_option=UniformStageOption(num_stages=4))
    step = get_mlp_train_step(method, use_value_and_grad=False)
    state, batch = create_mlp_train_state_and_batch(
        batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
        num_layers=4, manual_pipeline_layer=False)
    val = None
    for _ in range(n_steps):
        state, val = step(state, batch)
    return state, val, step.get_last_executable()


@pytest.mark.parametrize("strategy",
                         ["slice_all_gather", "reduce_scatter_gather",
                          "all_to_all"])
def test_forced_strategy_bitwise_on_graph_executor(strategy):
    """The 4-stage donated MLP train step (grouped direct baseline)
    must be bit-identical when every eligible cross-mesh edge is forced
    onto a collective strategy, in both lowered modes."""
    alpa_tpu.init("local")
    state_d, val_d, _ = _run_mlp("registers", "direct_p2p")
    state_c, val_c, ex = _run_mlp("registers", strategy)
    text = ex._register_programs["registers"].text
    if strategy != "all_to_all":
        # these strategies are eligible on this model's replicated
        # destination edges — the program must actually use them
        assert f"strategy={strategy}" in text
    for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                    jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(val_d), np.asarray(val_c))
    state_o, val_o, _ = _run_mlp("overlap", strategy)
    for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                    jax.tree_util.tree_leaves(state_o.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(val_d), np.asarray(val_o))


def test_quantized_end_to_end_close_and_weights_lossless():
    """Opt-in int8 codec on the same train step: activation edges are
    quantized (counters move), weight edges (microbatch-invariant,
    ``var_key[1] < 0``) never are, and the loss stays within a few
    percent of the lossless run."""
    from alpa_tpu.telemetry import metrics as _tmetrics
    alpa_tpu.init("local")
    _, val_d, _ = _run_mlp("registers", "direct_p2p")
    reg = _tmetrics.get_registry()
    fam = reg.get("alpa_reshard_quantized_edges_total")
    before = fam.labels("int8").value if fam else 0.0
    _, val_q, ex = _run_mlp("registers", quantize="int8")
    text = ex._register_programs["registers"].text
    assert "strategy=quantized" in text
    for line in text.splitlines():
        if ", -1)" in line:     # weight edge
            assert "strategy=quantized" not in line
    fam = reg.get("alpa_reshard_quantized_edges_total")
    assert fam is not None and fam.labels("int8").value > before
    saved = reg.get("alpa_reshard_quantized_bytes_saved_total")
    assert saved is not None and saved.value > 0
    np.testing.assert_allclose(np.asarray(val_q), np.asarray(val_d),
                               rtol=0.1, atol=1e-3)
