"""A Mamba-1 mixer's states in the serving path (``GPTConfig.attention``
"s6": ``ssm_state_size`` float32 values a channel and the convolution's
last three positions a row a layer, riding in the list of caches as
``(conv state, ssm state, index)`` beside the folded caches of the
attention layers' ONE key/value head): prompts around the chunk's edges
through the compiled chunk step, the engine's ``_scatter_row`` and
``_decode`` over the engine's rows; rows admitted while others decode,
free rows decoded along; the paths that refuse such a configuration by
name; the toy cell through ``chipbench/drivers/serve_s6.py``, sound and
with the state kept in bfloat16.  At the toy size of
``chipbench/configs/toy-jamba.json`` (chunks of 16) on the CPU, float32 at
full matmul precision, against the plain reference
``chipbench/references/jamba_decoder.py``: logits, not tokens."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import (GPTModel, init_kv_caches,
                                      kv_cache_kinds,
                                      require_one_token_steps)
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params, shake
from chipbench import arithmetic_jamba, controls_jamba, observe, run, traffic

TOY = run.load_json(run.HERE, "configs", "toy-jamba.json")
DRIVER = run.load_module("drivers", "serve_s6")
CONTEXT, CHUNK = TOY["serve"]["served_context"], TOY["serve"]["prefill_chunk"]
TOL = 2e-5      # float32 at full precision, logits of unit spread
# one chunk less one, exactly, and one; a padded last chunk; two whole
LENGTHS = [1, 15, 16, 17, 23, 32, 33, 43]
SERVED = 5


def toy_config():
    return DRIVER.model_config(TOY, dtype=jnp.float32, seq_len=CONTEXT)


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (8, 48), the reference, its weights)."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 48), 0,
                             TOY["vocab_size"])
    params = shake(init_params(model, jax.random.PRNGKey(2), ids),
                   ("scale", "D", "conv_bias", "dt_bias", "A_log"))
    mod = run.load_module("references", TOY["reference"])
    ref = mod.Reference(DRIVER.reference_settings(TOY))
    return model, params, np.asarray(ids), ref, \
        mod.weights_from_program(params)


@pytest.fixture(scope="module")
def replayed(toy):
    """Eight requests, one a length of ``LENGTHS``, through the compiled
    chunk step, the engine's ``_scatter_row`` into resident caches of
    eight rows and ``_decode`` over all rows at once, the served ids fed
    back (``drivers/serve_lm.py`` ``_replay`` under the driver's
    ``keeps_states``, what the cell's check runs): the mean absolute
    difference from the reference's logits at every served position of
    every request, and the states the replay kept."""
    model, params, ids, ref, weights = toy
    lm = run.load_module("drivers", "serve_lm")
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    group = [{"prompt_ids": ids[r, :n].tolist(),
              "tokens": ids[r, n:n + SERVED].tolist()}
             for r, n in enumerate(LENGTHS)]
    kinds = kv_cache_kinds(toy_config())
    keeping = DRIVER.keeps_states(run.load_module)(
        gen, [rec["tokens"] for rec in group],
        [i for i, kind in enumerate(kinds) if kind == "ssm"])
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=len(LENGTHS),
                                          chunked_admission=True)
        engine.shutdown()
        # (the whole row, one shape for the reference: what follows a
        # position changes nothing before it)
        wanted = [ref.logits_and_states(
            weights, ids[r], rows=(n - 1, SERVED),
            at=(n, n + SERVED - 1)) for r, n in enumerate(LENGTHS)]
        diffs = [diff for diff, _experts in lm._replay(
            keeping, engine._scatter_row, len(LENGTHS), group,
            [logits for logits, _ in wanted])]
    return diffs, keeping.states, [jnp.stack(s) for _, s in wanted]


@pytest.mark.parametrize("row", range(len(LENGTHS)),
                         ids=[f"prompt{n}" for n in LENGTHS])
def test_prefill_then_decode_equals_the_reference(replayed, row):
    """The prefill's last logits and four decoded positions of a prompt of
    this length, among seven other rows, and every mixer's state after the
    prefill and after the last decoded position: the state crossed chunks
    and was left by the row's last real position."""
    diffs, got, want = replayed
    assert diffs[row].shape == (SERVED,)
    assert diffs[row].max() < TOL, diffs[row]
    assert got[row].shape == want[row].shape == (4, 2, 4, 128)
    np.testing.assert_allclose(got[row], want[row], atol=TOL)


def test_an_admission_leaves_the_other_rows_states_as_they_are(toy):
    """``_scatter_row`` of one row's prefill into resident caches full of
    other rows' states: the admitted row holds the prefill's two states,
    every other row's arrays are bit for bit what they were."""
    model, params, ids, _ref, _weights = toy
    cfg = toy_config()
    gen = Generator(model, params, cfg, prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    resident = [(jax.random.normal(jax.random.PRNGKey(i), k.shape, k.dtype),
                 jax.random.normal(jax.random.PRNGKey(99 + i), v.shape,
                                   v.dtype), jnp.full((3,), 7, jnp.int32))
                for i, (k, v, _i) in enumerate(init_kv_caches(cfg, 3))]
    before = [(np.asarray(k), np.asarray(v)) for k, v, _ in resident]
    last, row = gen._run_chunked_prefill(
        [ids[0, :21]], jnp.asarray([21], jnp.int32), 1)
    after, _logits = engine._scatter_row(
        resident, row, jnp.zeros((3, cfg.vocab_size)), last, 1)
    for kind, (k0, v0), (k, v, index), (k1, v1, _i) in zip(
            kv_cache_kinds(cfg), before, after, row):
        for was, now, new in ((k0, k, k1), (v0, v, v1)):
            assert (np.asarray(now)[[0, 2]] == was[[0, 2]]).all(), kind
            assert (np.asarray(now)[1] == np.asarray(new)[0]).all(), kind
        assert index.tolist() == [7, 21, 7]
    assert np.asarray(after[0][1])[1].any()


def worst_deficit(ref, weights, prompt, out) -> float:
    """How far under the reference's largest logit the reference holds
    the tokens served after ``prompt``, at its worst position."""
    assert (out[:len(prompt)] == prompt).all()
    n = len(out) - len(prompt)
    # (padded to one shape for the reference: what follows a position
    # changes nothing before it)
    padded = np.zeros((64,), np.int32)
    padded[:len(out)] = out
    rows = np.asarray(ref.logits(weights, padded))[
        len(prompt) - 1:len(out) - 1]
    return float((rows.max(-1) - rows[np.arange(n), out[len(prompt):]]).max())


def test_rows_admitted_into_junk_while_others_decode_serve_the_reference(
        toy):
    """Seven requests over three rows whose states start as junk (what a
    freed row decoded along leaves, made large so that a share of it would
    show): prompts of one token, of a chunk's edge and of several chunks,
    admitted into rows that others freed while the rest decode.  Every
    served token has the reference's largest logit at its position."""
    model, params, ids, ref, weights = toy
    prompts = [ids[i % 3, :n] for i, n in enumerate([1, 5, 16, 17, 9, 33, 23])]
    new = [9, 20, 6, 14, 8, 12, 11]
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    outs = [None] * len(prompts)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=3,
                                          chunked_admission=True)
        # no request yet: the engine's thread waits and reads nothing
        engine._caches = [
            (jnp.full_like(k, 1e3), jnp.full_like(v, 1e3), i)
            if kind == "ssm" else (k, v, i)
            for kind, (k, v, i) in zip(kv_cache_kinds(toy_config()),
                                       engine._caches)]
        try:
            threads = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, engine.submit(
                    prompts[i], GenerationConfig(max_new_tokens=new[i]))))
                for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            engine.shutdown()
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        assert worst_deficit(ref, weights, p, out) < TOL, len(p)


def test_the_engine_reports_the_states_by_their_kind(toy):
    model, params, _ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    after = tmetrics.get_registry().snapshot()
    # four mixers of 4 x 128 float32 and 3 x 128 float32 a row; two
    # attention layers of the context, one key/value head of 16
    assert after['alpa_serving_kv_cache_bytes{kind="ssm"}'] == \
        3 * 4 * (4 * 128 + 3 * 128) * 4 == \
        3 * arithmetic_jamba.state_bytes_per_row(TOY, 4)
    assert after['alpa_serving_kv_cache_bytes{kind="full"}'] == \
        3 * CONTEXT * arithmetic_jamba.attention_bytes_per_position(TOY, 4)
    obs = {"counters": ({}, after), "engine_rows": 3}
    assert run.metric_reader("ssm_state_bytes_per_row")(obs) == \
        arithmetic_jamba.state_bytes_per_row(TOY, 4)


@pytest.mark.parametrize("what", ["pool", "speculative", "beam",
                                  "disaggregated"])
def test_what_rolls_back_by_an_index_refuses_by_name(toy, what):
    """The block pool, the speculative verify step, beam search and the
    disaggregated prefill index positions of one cache shape or roll a
    row back by its index: they refuse a configuration with a Mamba-1
    mixer, whose states no index brings back, and say why."""
    model, params, ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    # a configuration of one token a step: nothing to refuse there
    require_one_token_steps(toy_config(), "a static prefix")
    with pytest.raises(ValueError, match="Mamba-1 mixers"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "speculative":
            gen.generate_speculative(gen, ids[0, :5])
        elif what == "beam":
            gen.generate_beam(ids[0, :5], num_beams=2)
        else:
            PrefillEngine(gen)


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode)/GPTModel/h0/ssm/ssm_mixer/in_proj/dot_general",
     "ssm_mixer"),
    ("jit(decode)/GPTModel/h2/ssm/ssm_mixer/selective_scan/mul",
     "ssm_mixer.scan"),
    ("jit(chunk_prefill)/GPTModel/h2/ssm/ssm_mixer/selective_scan/"
     "jit(_chunk_scan)/selective_scan_positions", "ssm_mixer.scan"),
    # a weight the compiler copies keeps its place in the arguments' tree
    ("params['params']['h0']['ssm']['dt_proj']", "ssm_mixer"),
    ("jit(decode)/GPTModel/h0/ln2/mul", "norm"),
    ("jit(decode)/GPTModel/h0/mlp/gate/dot_general", "mlp"),
])
def test_the_capture_names_the_scan_as_a_part_of_the_mixer(op_name, part):
    from alpa_tpu.telemetry import device_time
    assert device_time.part_of(op_name) == part
    assert "ssm_mixer.scan" in device_time.PARTS
    entry = {"parts": {"ssm_mixer": 2.0, "ssm_mixer.scan": 1.0, "mlp": 4.0}}
    assert device_time.part_seconds(entry, "ssm_mixer") == 3.0


# ---- the cell's driver ---------------------------------------------------

def walk_sees_too_little(patch, ticks: bool):
    """The CPU's stand-in of controls (g) (``ticks`` false) and (h): the
    walk over the folded caches, which is the toy's attention in the chunk
    step and in the tick, hides the eight newest keys from queries past
    position 40 (the kernels those controls shorten run on a TPU alone)."""
    from alpa_tpu.model import gpt_model
    walk = gpt_model._attention_over_folded_blocks

    def short(q, k_cache, v_cache, offset, sink=None):
        if (q.shape[1] == 1) == ticks:
            offset = jnp.where(offset > 40, offset - 8, offset)
        return walk(q, k_cache, v_cache, offset, sink)

    patch(gpt_model, "_attention_over_folded_blocks", short)


@pytest.mark.parametrize("blind", ["chunk", "tick"])
def test_the_probe_shows_what_the_attention_read(toy, monkeypatch, blind):
    """The driver's check over three requests made by hand, with the chunk
    step's or the tick's attention blind to a row's newest keys: the pass
    under ``attention_probe`` (the same compiled programs, the mixers and
    MLPs silent, the queries sharp) shows it in the logits and in the K
    and V the second attention layer wrote, hundreds of times over what a
    sound run reads (``test_driver_runs_the_toy_cell``)."""
    import types
    model, params, ids, _ref, _weights = toy
    walk_sees_too_little(monkeypatch.setattr, blind == "tick")
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    records = [{"kind": "measured", "cut": False, "error": None,
                "prompt_ids": np.tile(ids[r], 2)[:n].tolist(),
                "tokens": np.tile(ids[r + 3], 2)[:m].tolist()}
               for r, (n, m) in enumerate([(20, 6), (75, 20), (33, 9)])]
    ctx = types.SimpleNamespace(mix={"check_requests": 3}, seed=3,
                                load=run.load_module)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=3,
                                          chunked_admission=True)
        engine.shutdown()
        checks = DRIVER._check(ctx, run.load_module("drivers", "serve_lm"),
                               gen, engine._scatter_row, 3, records, TOY)
    assert checks["checked_prompts"][0] == 75
    assert checks["probe_logit_diff"] > 100 * TOY["probe_logit_rtol"], checks
    assert checks["probe_kv_diff"] > 100 * TOY["probe_kv_rtol"], checks


@pytest.mark.parametrize("control", [None, "state_in_bfloat16"],
                         ids=["sound", "state_in_bfloat16"])
def test_driver_runs_the_toy_cell(tmp_path, monkeypatch, control):
    """``chipbench/drivers/serve_s6.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    head's spread, controller, warm-up, a closed-loop window over HTTP,
    the check against the reference: correct as it is, and not with the
    ssm state rounded to bfloat16 on its way into the cache (control (a)
    of ``chipbench/controls_jamba.py``), every request still served."""
    if control:
        controls_jamba.CONTROLS[control](monkeypatch.setattr)
    ctx = run.Context(
        cell={"name": "toy-jamba.longdoc64k", "config": "toy-jamba",
              "traffic": "toy-longdoc64k", "chips": 1},
        config=TOY, mix=traffic.load_mix("toy-longdoc64k"), seed=2147483659,
        seconds=1.5, trace=0, rehearsal=True, spans=observe.Spans(),
        compile_events=observe.CompileEvents(),
        trace_dir=str(tmp_path / "trace"))
    obs = DRIVER.run(ctx)
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 8, checks
    assert checks["checked_requests"] == 4
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert max(checks["checked_prompts"]) > 2 * CHUNK
    assert checks["compiles_in_window"] == 0
    if control:
        # the states themselves show it, tens of times over the limit
        assert not obs["correct"] and checks["over_margin"] > 0, checks
        assert checks["worst_state_diff"] > 10 * TOY["state_rtol_each"]
        # (the probe's mixers are silent: it reads as sound)
        assert checks["probe_logit_diff"] < TOY["probe_logit_rtol"] / 10
        return
    assert obs["correct"] and checks["over_margin"] == 0, checks
    assert checks["worst_state_diff"] < TOY["state_rtol_each"] / 10
    assert checks["probe_logit_diff"] < TOY["probe_logit_rtol"] / 10
    assert checks["probe_kv_diff"] < TOY["probe_kv_rtol"] / 10
    assert np.shape(checks["state_diffs"]) == (4, 2)
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 0
    obs.update(peaks=None, config=TOY)
    assert run.metric_reader("ssm_state_bytes_per_row")(obs) == \
        arithmetic_jamba.state_bytes_per_row(TOY, 4)
    # no chip, no capture: the readers of the device's time give nothing
    for traced in ("s6_scan_chunk_share_pct", "s6_scan_hbm_roofline_pct",
                   "s6_chunk_roofline_pct", "s6_tick_hbm_roofline_pct",
                   "ssm_decode_share_pct", "ssm_chunk_share_pct"):
        assert run.metric_reader(traced)(obs) is None
