"""A cache of rows of two kinds in the serving path (``GPTConfig.attention``
"eva": a pooled summary for every chunk of the context and the rows of ONE
aligned window, in one pair of arrays a layer, written twice a position):
prompts around every edge (a chunk's, a window's, a prefill chunk's)
through the compiled chunk step, the engine's ``_scatter_row`` and
``_decode`` over the engine's rows, a decode that crosses two window edges,
rows admitted while others decode and into a freed row's junk, the
program's counters of the keys of both kinds, the paths that refuse such a
configuration by name, the kernels' mask of a cache in two parts, and the
toy cell through ``chipbench/drivers/serve_eva.py``, sound and with the
summary a prefill began left unfinished.  At the toy size of
``chipbench/configs/toy-evabyte.json`` (windows of 64 in chunks of 4,
prefill chunks of 16) on the CPU, float32 at full matmul precision,
against the plain reference ``chipbench/references/evabyte_decoder.py``:
logits and the caches' own rows, not tokens."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
from alpa_tpu.ops import cached_attention
from alpa_tpu.serve.disagg import PrefillEngine
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.serve.kv_cache import KVBlockPool
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params, shake
from chipbench import (arithmetic_evabyte, controls_evabyte, observe, run,
                       traffic)

TOY = run.load_json(run.HERE, "configs", "toy-evabyte.json")
DRIVER = run.load_module("drivers", "serve_eva")
CONTEXT, CHUNK = TOY["serve"]["served_context"], TOY["serve"]["prefill_chunk"]
WINDOW, POOLED = TOY["window_size"], TOY["chunk_size"]
TOL = 2e-5      # float32 at full precision, logits of unit spread
# (prompt, served): a chunk of 4 less one, exactly, and one; a prefill
# chunk's edge; a window less one, exactly, and one; a padded last prefill
# chunk in the third window; and a decode that crosses two window edges
REQUESTS = [(3, 6), (4, 6), (5, 6), (15, 6), (16, 6), (17, 6), (63, 6),
            (64, 6), (65, 6), (141, 6), (60, 140)]


def toy_config():
    return DRIVER.model_config(TOY, dtype=jnp.float32, seq_len=CONTEXT)


@pytest.fixture(scope="module")
def toy():
    """(model, parameters, ids (11, 208), the reference, its weights)."""
    model = GPTModel(toy_config())
    ids = jax.random.randint(jax.random.PRNGKey(0), (len(REQUESTS), 208), 0,
                             TOY["vocab_size"])
    params = shake(init_params(model, jax.random.PRNGKey(2), ids[:, :8]),
                   ("scale",))
    mod = run.load_module("references", TOY["reference"])
    ref = mod.Reference(DRIVER.reference_settings(TOY))
    return model, params, np.asarray(ids), ref, \
        mod.weights_from_program(params)


def records(ids):
    return [{"prompt_ids": ids[r, :n].tolist(),
             "tokens": ids[r, n:n + m].tolist()}
            for r, (n, m) in enumerate(REQUESTS)]


@pytest.fixture(scope="module")
def replayed(toy):
    """The requests of ``REQUESTS`` through the compiled chunk step, the
    engine's ``_scatter_row`` into resident caches of eleven rows and
    ``_decode`` over all rows at once, the served ids fed back (the
    driver's ``_replay``, what the cell's check runs): a request's
    difference from the reference's logits at every served position and
    head, and its worst written slot of either kind against the
    reference's pooled and turned keys and values."""
    model, params, ids, ref, weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    group = records(ids)
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=len(group),
                                          chunked_admission=True)
        engine.shutdown()
        wanted = [DRIVER._wanted(ref, weights, rec, TOY) for rec in group]
        got = DRIVER._replay(gen, engine._scatter_row, len(group), group,
                             [logits for logits, _ in wanted])
    return [(diff, DRIVER.cache_diff(caches, want, TOY))
            for (diff, caches), (_, want) in zip(got, wanted)]


@pytest.mark.parametrize("row", range(len(REQUESTS)),
                         ids=[f"prompt{n}_served{m}" for n, m in REQUESTS])
def test_prefill_then_decode_equals_the_reference(replayed, row):
    """The prefill's last logits (the first head's) and every decoded
    position's (all heads') of a prompt of this length, among ten other
    rows; and what the programs wrote: every full chunk's summary the row
    holds, the one a padded prefill began and the ticks finished among
    them, and its current window's rows."""
    diff, written = replayed[row]
    served = REQUESTS[row][1]
    assert diff.shape == (served, TOY["num_pred_heads"])
    assert not np.isnan(diff[:, 0]).any() and not np.isnan(diff[1:]).any()
    assert np.nanmax(diff) < TOL, diff
    assert written["window"] < TOL and written["summary"] < TOL, written


def test_an_admission_leaves_the_other_rows_caches_as_they_are(toy):
    """``_scatter_row`` of one row's prefill into resident caches full of
    other rows' rows and summaries: the admitted row holds the prefill's,
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
        [ids[0, :77]], jnp.asarray([77], jnp.int32), 1)
    after, _logits = engine._scatter_row(
        resident, row, jnp.zeros((3, cfg.vocab_size)), last, 1)
    for (k0, v0), (k, v, index), (k1, v1, _i) in zip(before, after, row):
        for was, now, new in ((k0, k, k1), (v0, v, v1)):
            assert (np.asarray(now)[[0, 2]] == was[[0, 2]]).all()
            assert (np.asarray(now)[1] == np.asarray(new)[0]).all()
        assert index.tolist() == [7, 77, 7]


def worst_deficit(ref, weights, prompt, out) -> float:
    """How far under the reference's largest first-head logit the
    reference holds the bytes served after ``prompt``, at its worst
    position."""
    assert (out[:len(prompt)] == prompt).all()
    n = len(out) - len(prompt)
    # (padded to one shape for the reference: what follows a position
    # changes nothing before it)
    padded = np.zeros((256,), np.int32)
    padded[:len(out)] = out
    rows = np.asarray(ref.logits(weights, padded))[
        len(prompt) - 1:len(out) - 1, 0]
    return float((rows.max(-1) - rows[np.arange(n), out[len(prompt):]]).max())


def test_rows_admitted_into_junk_while_others_decode_serve_the_reference(
        toy):
    """Seven requests over three rows whose caches start as junk (what a
    freed row decoded along leaves, made large so that a share of it would
    show): prompts inside a chunk, at a window's edge and of several
    windows, answers that cross a window's edge, admitted into rows that
    others freed while the rest decode.  Every served byte has the
    reference's largest logit at its position; and the engine counted the
    keys of both kinds every tick's and every chunk's queries saw."""
    model, params, ids, ref, weights = toy
    sizes = [(1, 9), (5, 70), (64, 6), (61, 14), (130, 8), (33, 40), (77, 11)]
    prompts = [ids[i % 3, :n] for i, (n, _) in enumerate(sizes)]
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    outs = [None] * len(prompts)
    before = tmetrics.get_registry().snapshot()
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(gen, max_batch=3,
                                          chunked_admission=True)
        # no request yet: the engine's thread waits and reads nothing
        engine._caches = [(jnp.full_like(k, 1e3), jnp.full_like(v, 1e3), i)
                          for k, v, i in engine._caches]
        try:
            threads = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, engine.submit(
                    prompts[i], GenerationConfig(max_new_tokens=sizes[i][1]))))
                for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            engine.shutdown()
    for p, (_, new), out in zip(prompts, sizes, outs):
        assert len(out) == len(p) + new
        assert worst_deficit(ref, weights, p, out) < TOL, len(p)
    after = tmetrics.get_registry().snapshot()

    def rose(series):
        return after[series] - before.get(series, 0)

    # a request's ticks sit at its prompt's length and the new - 1 after
    seen = [arithmetic_evabyte.keys_seen(TOY, t)
            for n, new in sizes for t in range(n, n + new)]
    assert rose('alpa_serving_eva_keys_total{kind="exact"}') == \
        sum(exact for exact, _ in seen)
    assert rose('alpa_serving_eva_keys_total{kind="summary"}') == \
        sum(pooled for _, pooled in seen) > 0
    pairs = [arithmetic_evabyte.chunk_pairs(TOY, -(-n // CHUNK) * CHUNK)
             for n, _ in sizes]
    assert rose('alpa_serving_eva_chunk_pairs_total{kind="exact"}') == \
        sum(exact for exact, _ in pairs)
    assert rose('alpa_serving_eva_chunk_pairs_total{kind="summary"}') == \
        sum(pooled for _, pooled in pairs) > 0


def test_the_arithmetic_counts_pairs_as_the_mask_shows_them():
    """``chunk_pairs`` against the count by hand, a query at a time."""
    for positions in (16, 64, 80, 208):
        seen = [arithmetic_evabyte.keys_seen(TOY, t)
                for t in range(positions)]
        assert arithmetic_evabyte.chunk_pairs(TOY, positions) == (
            sum(exact for exact, _ in seen),
            sum(pooled for _, pooled in seen))


def test_the_engine_reports_the_cache_by_its_two_kinds(toy):
    model, params, _ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    engine.shutdown()
    after = tmetrics.get_registry().snapshot()
    # three layers, keys and values, 64 channels of float32 a slot
    a_slot = 3 * 2 * 64 * 4
    assert after['alpa_serving_kv_cache_bytes{kind="window"}'] == \
        3 * WINDOW * a_slot
    assert after['alpa_serving_kv_cache_bytes{kind="summary"}'] == \
        3 * (CONTEXT // POOLED) * a_slot
    obs = {"counters": ({}, after), "engine_rows": 3}
    assert run.metric_reader("eva_cache_bytes_per_row")(obs) == \
        3 * arithmetic_evabyte.cache_bytes_per_row_a_layer(TOY, 4, CONTEXT)
    # no capture, no traced counters: the other readers give nothing
    for traced in ("eva_decode_hbm_roofline_pct", "eva_chunk_roofline_pct",
                   "summary_keys_pct"):
        assert run.metric_reader(traced)({**obs, "peaks": None,
                                          "config": TOY}) is None


@pytest.mark.parametrize("what", ["pool", "speculative", "beam",
                                  "disaggregated", "prefix"])
def test_what_rolls_back_across_a_window_refuses_by_name(toy, what):
    """The block pool, the speculative verify step, beam search, the
    disaggregated prefill and a static prefix index positions of one cache
    shape or resume a row at any position: they refuse a configuration
    whose cache holds ONE window's rows, and say why."""
    model, params, ids, _ref, _weights = toy
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="ONE window's rows"):
        if what == "pool":
            KVBlockPool.for_generator(gen, block_size=8)
        elif what == "speculative":
            gen.generate_speculative(gen, ids[0, :5])
        elif what == "beam":
            gen.generate_beam(ids[0, :5], num_beams=2)
        elif what == "prefix":
            gen.cache_prefix(ids[0, :5])
        else:
            PrefillEngine(gen)


def test_a_chunk_that_straddles_a_window_is_refused(toy):
    model, params, _ids, _ref, _weights = toy
    for chunk in (48, 6):
        with pytest.raises(ValueError, match="must divide the window"):
            Generator(model, params, toy_config(), prefill_chunk=chunk)
    # and an engine that would prefill a whole prompt in one dense step
    gen = Generator(model, params, toy_config(), prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="chunked_admission=True"):
        ContinuousBatchingEngine(gen, max_batch=2)


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode)/GPTModel/h0/attn/attention/eva_summaries/reduce_sum",
     "attention.summaries"),
    ("jit(decode)/GPTModel/h0/attn/attention/cache_write/"
     "dynamic_update_slice", "attention.cache_write"),
    ("jit(decode)/GPTModel/h0/attn/attention/jit(_eva_core)/"
     "cached_attention_folded_key_blocks", "attention"),
    ("jit(decode)/GPTModel/h0/attn/qkv/dot_general", "projection"),
])
def test_the_capture_names_the_summaries_a_part_of_the_attention(op_name,
                                                                  part):
    from alpa_tpu.telemetry import device_time
    assert device_time.part_of(op_name) == part
    entry = {"parts": {"attention": 2.0, "attention.summaries": 1.0,
                       "attention.cache_write": 0.5, "mlp": 4.0}}
    assert device_time.part_seconds(entry, "attention") == 3.5


# ---- the kernels' mask of a cache in two parts ---------------------------

@pytest.mark.parametrize("queries", [1, 1024], ids=["tick", "chunk"])
def test_the_kernels_take_a_cache_in_two_parts(queries):
    """Both kernels (interpreted) over folded caches whose first 2,048
    slots every query sees as far as ``seen`` says and whose last 2,048
    are causal from ``offset``, against the product over every slot under
    the same mask: rows with no summary, with a part of a key block's and
    with several blocks', and the slots between fetched by none."""
    heads, dim, held = 2, 128, 2048
    rng = np.random.default_rng(0)

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k_cache, v_cache = (rnd(3, 2 * held, heads * dim) for _ in range(2))
    q = rnd(3, queries, heads, dim)
    seen = jnp.asarray([0, 136, 1920], jnp.int32)
    offset = jnp.asarray([1024, 0, 1024] if queries > 1 else [5, 2047, 0],
                         jnp.int32)
    if queries == 1:
        assert cached_attention.eva_fits(q, k_cache, held)
        take = cached_attention.folded_cached_attention
    else:
        assert cached_attention.chunk_fits(q, k_cache, v_cache)
        take = cached_attention.chunk_attention
    got = take(q, k_cache, v_cache, offset, seen=seen, exact_from=held,
               interpret=True)
    want = gpt_model._eva_attention_over_slots(q, k_cache, v_cache, offset,
                                               seen, held=held)
    np.testing.assert_allclose(got, want, atol=2e-6)


# ---- the cell's driver ---------------------------------------------------

@pytest.mark.parametrize("control", [None, "prefill_summary_frozen"],
                         ids=["sound", "prefill_summary_frozen"])
def test_driver_runs_the_toy_cell(tmp_path, monkeypatch, control):
    """``chipbench/drivers/serve_eva.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    head's spread, controller, warm-up, a closed-loop window over HTTP,
    the check against the reference: correct as it is, and not with the
    summary of the chunk a prompt ends in left as the prefill wrote it
    (control (f) of ``chipbench/controls_evabyte.py``), every request
    still served."""
    if control:
        controls_evabyte.CONTROLS[control](monkeypatch.setattr)
    ctx = run.Context(
        cell={"name": "toy-evabyte.longdoc32k", "config": "toy-evabyte",
              "traffic": "toy-longdoc32k", "chips": 1},
        config=TOY, mix=traffic.load_mix("toy-longdoc32k"), seed=2147483659,
        seconds=2.0, trace=0, rehearsal=True, spans=observe.Spans(),
        compile_events=observe.CompileEvents(),
        trace_dir=str(tmp_path / "trace"))
    obs = DRIVER.run(ctx)
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 8, checks
    assert checks["checked_requests"] == 4
    assert checks["long_context_checked"] and \
        checks["edge_crossings_checked"] >= 1, checks
    assert checks["compiles_in_window"] == 0
    if control:
        # the summaries themselves show it, thousands of times over
        assert not obs["correct"] and checks["over_margin"] > 0, checks
        assert checks["worst_summary_diff"] > 1000 * TOY["cache_rtol"]
        assert checks["worst_window_row_diff"] < TOY["cache_rtol"] / 10 or \
            checks["worst_logit_diff"] > TOY["logit_atol"]
        return
    assert obs["correct"] and checks["over_margin"] == 0, checks
    assert checks["worst_summary_diff"] < TOY["cache_rtol"] / 10
    assert checks["worst_window_row_diff"] < TOY["cache_rtol"] / 10
    assert checks["probe_logit_diff"] < TOY["probe_logit_rtol"] / 10
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 0
    obs.update(peaks=None, config=TOY)
    assert run.metric_reader("eva_cache_bytes_per_row")(obs) == \
        3 * arithmetic_evabyte.cache_bytes_per_row_a_layer(TOY, 4, CONTEXT)
