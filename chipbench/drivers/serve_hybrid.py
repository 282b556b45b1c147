"""Driver of the serving cells whose model mixes layers that hold a state
with layers that hold a cache of positions (``model_type`` lfm2_moe: gated
short convolutions beside grouped-query attention, under sigmoid-routed
experts): the model from the configuration's own keys
(``alpa_tpu.model.gpt_model.config_from_hf``) behind ``run_controller`` +
``register_model(engine_rows=..., chunked_admission=True)``, loaded over
HTTP by the clients of ``drivers/serve.py``.  It keeps the window, clocks,
warm-up and ``obs`` of ``drivers/serve_lm.py`` (it loads that file for
``_warm_up``, ``balance_router_biases``, ``_replay`` and ``_pick``, and
``drivers/serve_mla.py`` for ``_closed_loop`` and ``read_program_trace``,
``drivers/serve_diffusion.py`` for ``_memory_analysis``),
so that every reader of the serving cells works on it.

What this driver does differently:

* the routers' biases are set by load as ``drivers/serve_lm.py`` sets
  Trinity's (the published model has such a bias and no auxiliary loss).
* ``correct`` (``_check``) is ``drivers/serve_lm.py``'s comparison with
  this configuration's reference (``references/lfm2_moe_decoder.py``): the
  checked requests (the longest context, which must pass
  ``serve.check_context_over``: several chunks, so that the conv layers'
  state crosses chunk edges and ends inside a padded chunk; the shortest,
  under ``serve.check_context_under``: inside its first chunk; and
  ``check_requests`` - 2 from the seed) go once more through the window's
  own compiled ``_chunk_prefill``, the engine's ``_scatter_row`` into
  resident caches of the engine's rows, and ``_decode`` over all those rows
  at once, the served ids fed back (``serve_lm._replay``).  Two
  comparisons at every served position: the reference's logit of the
  served token against the reference's largest (``logit_margin``), and
  the replayed programs' logits against the reference's, the mean
  absolute difference over the vocabulary (``logit_atol``, and
  ``logit_mean_atol`` for the mean over all positions);
  ``min_choice_agreement`` is a floor on the share of the reference's
  choices of experts that are the program's.  Every position is held to
  the one pair of limits: after twelve routed layers of 32 near-tied
  sigmoids some 85 % of the positions have another expert than the
  reference's somewhere, and no fault tried leaves the others standing, so
  a narrower pair for those has no reading on its far side (the worst
  readings are reported apart all the same: ``worst_logit_diff`` and
  ``worst_logit_deficit`` where ``_decode`` chose the reference's experts
  in every layer, ``*_flipped`` elsewhere).  A conv layer's state has no
  mask to hide it: a state taken from a chunk's padding, or kept in a
  lower precision, is in every later logit of its row.
  ``chipbench/controls_lfm2.py`` plants the faults the limits are set
  against.
* the engine's resident state is reported by kind (``kv_cache_bytes``:
  ``full`` and ``conv``), and after the traced seconds the device events
  inside the runs of ``jit_decode`` are summed by the program's scopes
  (``obs["decode_trace"]``, the keys the decode's readers take); the conv
  mixers' device time is read by its readers from the program's own table
  (``chipbench/device_parts.py``).  The compiler's ``memory_analysis`` of
  the decode, the chunk step and the initialiser goes on an info line.
"""
import time

import numpy as np

from chipbench import arithmetic_lfm2, device_parts, observe, program, stats

DECODE_PROGRAM = "jit_decode"


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"head_dim": config["hidden_size"] // config["num_attention_heads"],
            "norm_eps": config["norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "routed_scaling_factor": float(config["routed_scaling_factor"]),
            "query_block": config["reference_query_block"]}


LIMITS = ("logit_margin", "logit_atol", "logit_mean_atol",
          "min_choice_agreement")


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import jax.numpy as jnp
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = lm._pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    chunk = serve["prefill_chunk"]
    rows = mix["output_len"]["max"]
    # one shape for every checked request
    length = -(-(mix["prompt_len"]["max"] + rows) // chunk) * chunk
    if length > serve["served_context"]:
        raise ValueError("the mix's longest prompt and output do not fit "
                         "the served context")
    limits = {name: config[name] for name in LIMITS}
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
    diff_sum = 0.0
    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        refs, wants, deficits = [], [], []
        for rec in group:
            n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
            ids = np.zeros((length,), np.int32)
            ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
            # the row that predicts served token k: position n_prompt-1+k
            logits, ref_experts = reference.logits_and_experts(
                weights, ids, rows=(n_prompt - 1, rows))
            logits = logits[:n_out]
            served = jnp.asarray(rec["tokens"], jnp.int32)
            chosen = jnp.take_along_axis(logits, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(logits.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            wants.append(np.asarray(ref_experts)[:, :n_out])
        replayed = lm._replay(generator, scatter_row, engine_rows, group,
                              refs)
        del refs
        for deficit, want, (diff, got) in zip(deficits, wants, replayed):
            # which of the reference's experts the program chose too
            found = (want[..., :, None] == got[..., None, :]).any(-1)
            same = found.all(-1).all(0)
            known = (got >= 0).all(-1).all(0)
            common += int(found[:, known].sum())
            choices += want[:, known].size
            positions += len(deficit)
            flipped += int((~same & known).sum())
            diff_sum += float(diff.sum())
            for name, values, kind in (
                    ("deficit", deficit, "logit_margin"),
                    ("diff", diff, "logit_atol")):
                for which, where in (("_same", same), ("_flipped", ~same)):
                    if where.any():
                        worst[name + which] = max(
                            worst[name + which], float(values[where].max()))
                # (a NaN is over every limit)
                bad += int((~(values <= limits[kind])).sum())
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_prompts": [len(rec["prompt_ids"]) for rec in picked],
            "checked_positions": positions, "over_margin": bad,
            "long_context_checked": has_long,
            "short_context_checked": has_short,
            "positions_with_a_flip": flipped,
            "choice_agreement": common / choices if choices else 0.0,
            "worst_logit_deficit": worst["deficit_same"],
            "worst_logit_deficit_flipped": worst["deficit_flipped"],
            "worst_logit_diff": worst["diff_same"],
            "worst_logit_diff_flipped": worst["diff_flipped"],
            "mean_logit_diff": diff_sum / positions if positions
            else float("inf"), **limits}


def run(ctx):
    import jax
    import jax.numpy as jnp
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import CONV_SCOPE  # noqa: F401
    from alpa_tpu.model import moe
    from alpa_tpu.model.gpt_model import (ATTENTION_SCOPE, GPTModel,
                                          config_from_hf, init_kv_caches)
    from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
    from alpa_tpu.serve import run_controller
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace
    base = ctx.load("drivers", "serve")
    lm = ctx.load("drivers", "serve_lm")
    mla = ctx.load("drivers", "serve_mla")
    memory_analysis = ctx.load("drivers", "serve_diffusion")._memory_analysis
    scoped_instructions = ctx.load("drivers", "train_lm").scoped_instructions

    config, mix, serve = ctx.config, ctx.mix, ctx.config["serve"]
    if mix["kind"] != "closed_loop":
        raise ValueError("this driver's cells are closed loops")
    dtype = jnp.dtype(config["dtype"])
    gcfg = config_from_hf(config, dtype=dtype, param_dtype=dtype,
                          seq_len=serve["served_context"])
    vocab = gcfg.vocab_size
    ttrace.set_enabled(ctx.trace == 1)
    registry = tmetrics.get_registry()
    timers = {}

    # the weights: on the device, from the seed, in one jitted call
    tic = time.perf_counter()
    model = GPTModel(gcfg)
    key = program.key_from_seed(ctx.seed)
    initialiser = jax.jit(
        lambda key: model.init(key, jnp.ones((1, 8), jnp.int32)))
    params = initialiser(key)
    params = lm.balance_router_biases(model, params,
                                      jax.random.fold_in(key, 1), vocab)
    generator = Generator(model, params, gcfg,
                          prefill_chunk=serve["prefill_chunk"])
    jax.block_until_ready(generator.params)
    timers["state_init_s"] = time.perf_counter() - tic
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    ctx.info({"info": "state", "weight_bytes": weight_bytes,
              "parameters": sum(
                  x.size for x in jax.tree_util.tree_leaves(params)),
              "memory": observe.device_memory(jax.local_devices())})

    name = config["name"]
    server = run_controller(port=0)
    engine = None
    try:
        server.controller.register_model(
            name, generator, engine_rows=serve["engine_rows"],
            chunked_admission=True)
        client = base._Client(ctx, server.port, name)
        tic = time.perf_counter()
        # prompts under one chunk, of several and of two and a bit; every
        # row filled and one request more
        lm._warm_up(client, vocab, ctx.seed, serve["engine_rows"],
                    serve["prefill_chunk"], serve["prefill_chunk"],
                    mix["prompt_len"]["max"])
        timers["warmup_s"] = time.perf_counter() - tic
        # the controller's own engine, as its streaming path built it
        engine = server.controller._pick_replica(name).engine
        ctx.info({"info": "engine", "rows": engine.B,
                  "prefill_chunk": generator.prefill_chunk,
                  "served_context": gcfg.seq_len,
                  "kv_cache_bytes": {
                      kind: registry.snapshot().get(
                          f'alpa_serving_kv_cache_bytes{{kind="{kind}"}}')
                      for kind in ("full", "conv")},
                  "memory": observe.device_memory(jax.local_devices())})

        compiles_before = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0)
        trace = program.DeviceTrace(ctx) if ctx.trace else None
        setup_s = observe.seconds_since_process_start()
        counters_t0 = registry.snapshot()
        window_t0_us = ttrace.now_us()
        t0 = time.perf_counter()
        # (stop, the threads that send, the threads that wait for answers)
        stop, senders, workers = mla._closed_loop(ctx, client, mix, vocab)
        traced_counters = None
        if ctx.trace == 1:
            base._sleep_until(t0 + min(mix["trace_after_s"],
                                       ctx.seconds / 2))
            # the registry INSIDE the traced seconds: starting and stopping
            # the profiler takes seconds in which the engine goes on
            trace.start()
            traced_counters = registry.snapshot()
            base._sleep_until(time.perf_counter() + mix["trace_seconds"])
            traced_counters = (traced_counters, registry.snapshot())
            trace.stop()
        base._sleep_until(t0 + ctx.seconds)
        t1 = time.perf_counter()
        window_t1_us = ttrace.now_us()
        client.end_window(cut=True)
        counters = (counters_t0, registry.snapshot())
        compiles_in_window = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0) - compiles_before
        memory = observe.device_memory(jax.local_devices())

        if ctx.trace == 2:
            # the same traffic for the traced seconds, inside a capture:
            # the closed loop's callers simply carry on
            trace.warm_up()
            trace.start()
            traced_counters = registry.snapshot()
            base._sleep_until(time.perf_counter() + mix["trace_seconds"])
            traced_counters = (traced_counters, registry.snapshot())
            trace.stop()
        memory_run = observe.device_memory(jax.local_devices())

        stop.set()
        client.close_cut_requests(stop=True)
        for t in senders:
            t.join(timeout=30)
        # the drain: requests that were due get a stated time to finish
        deadline = t1 + mix["drain_s"]
        for t in list(workers):
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        drain_end = time.perf_counter()

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        # the shapes of the decode's arguments, for its HLO text
        caches = abstract([(k, v) for k, v, _ in engine._caches])
        indices = abstract([i for _, _, i in engine._caches])
        engine_rows, scatter_row = engine.B, engine._scatter_row
    finally:
        if engine is not None:
            engine.shutdown()
            engine._thread.join(timeout=30)
        server.shutdown()
    # the check makes resident caches of its own: the engine's go first
    del engine, server

    # the decode program's device seconds by the program's scopes; the
    # compiler's account of memory
    decode_trace = {}
    if trace is not None:
        decode = generator._decode.jitted.lower(
            abstract(params),
            jax.ShapeDtypeStruct((engine_rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((engine_rows,), jnp.int32),
            caches, indices).compile()
        chunk_step = generator._chunk_prefill.lower(
            abstract(params),
            jax.ShapeDtypeStruct((1, serve["prefill_chunk"]), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            abstract(jax.eval_shape(lambda: init_kv_caches(gcfg, 1))),
            jax.ShapeDtypeStruct((1, gcfg.vocab_size), dtype)).compile()
        ctx.info({"info": "memory_analysis", **{
            what: memory_analysis(compiled) for what, compiled in (
                ("decode", decode), ("chunk_prefill", chunk_step),
                ("initialiser", initialiser.lower(key).compile()))}})
        if trace.capture is not None:
            try:
                found = mla.read_program_trace(
                    trace.dir, DECODE_PROGRAM, decode.as_text(),
                    {"moe": moe.SCOPE, "grouped_matmul": MATMUL_SCOPE,
                     "attention": ATTENTION_SCOPE}, scoped_instructions)
                if found:
                    decode_trace = {"decode_runs": found.pop("runs"),
                                    "decode_s": found.pop("program_s"),
                                    **found}
            except (FileNotFoundError, ValueError):
                if not ctx.rehearsal:    # a CPU trace has no TPU plane
                    raise
            ctx.info({"info": "decode_trace", **decode_trace})
            # for the record: the decode and the chunk step by part of the
            # model, ms a run (the program's own table)
            programs = next(iter(((device_parts.table() or {}).get(
                "programs") or {}).values()), {})
            for what in (DECODE_PROGRAM, "jit_chunk_prefill"):
                entry = programs.get(what)
                if entry is not None and entry["runs"]:
                    ctx.info({"info": "device_parts", "program": what,
                              "runs": entry["runs"],
                              **{part: round(1e3 * s / entry["runs"], 4)
                                 for part, s in sorted(
                                     entry["parts"].items(),
                                     key=lambda kv: -kv[1])},
                              **{key: round(1e3 * entry[key] /
                                            entry["runs"], 4) for key in
                                 ("mixed_s", "inherited_s")}})
    requests = [r for r in client.records if r["kind"] == "measured"]
    records = [r for r in requests if not r["cut"]]
    for rec in records:
        if rec["error"] is None and len(rec["tokens"]) < rec["asked"]:
            rec["error"] = "due and not answered by the end of the drain"
    failed = sum(r["error"] is not None for r in records)
    ctx.info({"info": "ttft_ms", "sorted": sorted(
        round(w * 1e3, 3) for w in stats.ttft_waits(requests, drain_end))})
    # where in the window the process stood still, if it did: the longest
    # silences between any two tokens, [seconds, at which second]
    times = sorted(t for r in requests for t in r["token_times"]
                   if t0 <= t <= t1)
    ctx.info({"info": "stalls", "longest_silences": sorted(
        ([round(b - a, 3), round(a - t0, 3)]
         for a, b in zip(times, times[1:])), reverse=True)[:4]})
    # the gaps between a request's consecutive tokens: an info line and no
    # metric (a stall of all rows is an admission's whole chunks)
    gaps = [t - rec["token_times"][k - 1] for rec, k, t in
            stats.window_tokens({"window": (t0, t1), "requests": requests})
            if k > 0]
    ctx.info({"info": "gaps_ms", "count": len(gaps), **{
        f"p{q}": round(stats.percentile(gaps, q) * 1e3, 3)
        for q in (50, 90, 99, 99.9) if gaps}})
    tic = time.perf_counter()
    checks = _check(ctx, lm, generator, scatter_row, engine_rows,
                    client.records, config)
    timers["check_s"] = time.perf_counter() - tic
    checks["compiles_in_window"] = compiles_in_window
    checks["errors"] = sorted({r["error"] for r in records
                               if r["error"]})[:5]
    conv_layers = config["layer_types"].count("conv")
    return {
        "correct": bool(
            failed == 0 and checks["over_margin"] == 0 and
            checks["checked_requests"] >= mix["check_requests"] and
            checks["long_context_checked"] and
            checks["short_context_checked"] and
            checks["choice_agreement"] >= config["min_choice_agreement"] and
            checks["mean_logit_diff"] <= config["logit_mean_atol"] and
            compiles_in_window == 0),
        "attempted": len(records),
        "failed": failed,
        "checks": checks,
        "setup_s": setup_s,
        "timers": timers,
        "window": (t0, t1),
        "drain_end": drain_end,
        "requests": requests,
        "engine_rows": engine_rows,
        "served_context": gcfg.seq_len,
        "weight_bytes": weight_bytes,
        "cache_itemsize": dtype.itemsize,
        "expert_layers": lm._expert_layers(gcfg),
        # one routed expert's three matrices, as the decode reads them
        "expert_bytes": arithmetic_lfm2.expert_bytes(
            gcfg.hidden_size, gcfg.expert_width, dtype.itemsize),
        # the operations of the conv mixers of one chunk step
        "conv_flops_per_chunk": conv_layers *
        arithmetic_lfm2.conv_mixer_flops(
            gcfg.hidden_size, gcfg.conv_taps, serve["prefill_chunk"]),
        "decode_trace": decode_trace,
        "program_spans": trace.program_spans() if trace else [],
        "program_window_us": (window_t0_us, window_t1_us),
        "counters": counters,
        "memory": memory,
        "memory_run": memory_run,
        "device_trace": trace.summary() if trace else None,
        # the registry at the start and the end of the traced seconds
        "traced_counters": traced_counters,
        # what the readers of spans see in place of the window's: the
        # traced interval, and every request that streamed in it
        "traced": {"window": trace.interval,
                   "program_window_us": trace.interval_us,
                   "requests": [r for r in client.records
                                if r["kind"] != "warmup"]}
        if ctx.trace == 2 else {},
    }
