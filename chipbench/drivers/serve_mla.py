"""Driver of the serving cells whose model is a decoder of latent attention
(MLA) over group-limited routed experts, of which this chip holds one
share (``model_type`` deepseek_v2): the model from the configuration's own
keys (``alpa_tpu.model.gpt_model.config_from_hf``) behind
``run_controller`` + ``register_model`` with the deployment's sizes (the
configuration's ``serve``), loaded over HTTP by the clients of
``drivers/serve.py`` (its closed loop with the callers started one after
another: ``_closed_loop``).  It keeps the window, clocks, warm-up, replay
and ``obs`` of ``drivers/serve_lm.py`` (it loads that file for ``_warm_up``,
``_replay``, ``_pick`` and ``read_decode_trace``), so that every reader of
the serving cells works on it.

What this driver does differently:

* the configuration file counts the experts HELD here
  (``n_routed_experts``) and the vocabulary's slice; the router keeps the
  published width (``published.n_routed_experts``), and the program is
  told its share (``share_index``: ``GPTConfig.experts_held``).  The
  reference is given the same share.
* the published model has no selection bias to balance: it was balanced
  in training (three auxiliary losses).  Random weights prefer a few
  experts for all tokens, and here that also decides how many of a
  token's picks land on this chip at all.  The stand-in lives in the
  model's own parameters (``balance_routers``, one fixed schedule): every
  router matrix is made orthogonal to the mean of its layer's input, the
  common direction the preference comes from.
* ``correct`` as ``drivers/serve_lm.py`` decides it (the served token's
  deficit under the reference's largest logit; the logits of the window's
  own compiled ``_chunk_prefill``, ``_scatter_row`` and ``_decode`` at the
  engine's rows against the reference's, by whether the program chose the
  reference's experts), against ``references/deepseek_v2_decoder.py``.
* after the traced seconds the device events inside the runs of
  ``jit_decode`` are summed by the program's scopes as there, and those
  inside the runs of ``jit_chunk_prefill`` too (``obs["chunk_trace"]``).
"""
import threading
import time

import numpy as np

from chipbench import arithmetic_mla, observe, program, stats, traffic, \
    xplane

CHUNK_PROGRAM = "jit_chunk_prefill"
# the closed loop's callers start this far apart
START_EVERY_S = 0.02

# balance_routers' schedule: batches a layer and uniform token ids a batch
BALANCE_BATCHES, BALANCE_TOKENS = 4, 1024


def balance_routers(model, params, key, vocab):
    """Every expert layer's router made orthogonal to the mean of its
    input (the normed hidden state before the MLP), layer after layer in
    the model's order, the mean taken over ``BALANCE_BATCHES`` batches of
    ``BALANCE_TOKENS`` uniform token ids with the earlier layers' routers
    already moved: ``W -= u (u^T W)``, ``u`` the mean's direction.  Random
    weights give all tokens' hidden states a common direction that grows
    layer by layer, and ``W^T`` of it is a bias that prefers a few experts
    for every token; a trained router's load is even, by its auxiliary
    losses.  Nothing else of the router changes: what distinguishes one
    token's scores from another's stays."""
    import jax
    import jax.numpy as jnp
    tokens = min(BALANCE_TOKENS, model.config.seq_len)
    layers = sorted((k for k, block in params["params"].items()
                     if "router" in block.get("mlp", {})),
                    key=lambda k: int(k.lstrip("h")))

    @jax.jit
    def mean_inputs(params, ids):
        _, state = model.apply(
            params, ids, mutable=["intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name == "ln2")
        return {k: state["intermediates"][k]["ln2"]["__call__"][0].astype(
            jnp.float32).mean((0, 1)) for k in layers}

    for at, layer in enumerate(layers):
        mean = sum(mean_inputs(params, jax.random.randint(
            jax.random.fold_in(key, at * BALANCE_BATCHES + i),
            (1, tokens), 4, vocab))[layer] for i in range(BALANCE_BATCHES))
        u = mean / jnp.linalg.norm(mean)

        def moved(path, x, layer=layer, u=u):
            if path[1].key != layer or path[-2].key != "router":
                return x
            w = x.astype(jnp.float32)
            return (w - jnp.outer(u, u @ w)).astype(x.dtype)

        # the same tree, its big leaves shared
        params = jax.tree_util.tree_map_with_path(moved, params)
    return params


def _closed_loop(ctx, client, mix, vocab):
    """``drivers/serve.py``'s closed loop with the callers started one
    after another, ``START_EVERY_S`` apart, so that the pool's first
    requests reach the engine in the pool's order.  Started at once, 64
    callers race for the engine's queue; a window of this mix admits some
    85 requests whose prompts take two thirds of its time, so the order of
    the first 64 decided which of them fell inside it, and the runs'
    tokens per second spread by 3 % (PERF.md, PR 32).  Returns what that
    loop returns: (stop, the threads that send, the threads that wait)."""
    source = traffic.closed_loop(mix, ctx.seed, vocab)
    source_lock = threading.Lock()
    stop = threading.Event()

    def caller():
        while not stop.is_set():
            with source_lock:
                req = next(source)
            client.request(req, time.perf_counter())

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(mix["clients"])]
    for t in threads:
        t.start()
        time.sleep(START_EVERY_S)
    return stop, threads, threads


def share_of(config: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    held = config["n_routed_experts"]
    return config["share_index"] * held, held


def model_config(config: dict, **overrides):
    """The program's configuration of a configuration file: its keys as
    Hugging Face names them, the router at its published width, the share
    of its experts that is held here."""
    from alpa_tpu.model.gpt_model import config_from_hf
    hf = dict(config,
              n_routed_experts=config["published"]["n_routed_experts"])
    return config_from_hf(
        hf, **{"experts_held": share_of(config), **overrides})


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "rope_scaling",
            "num_experts_per_tok", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor")
    return {**{k: config[k] for k in keys},
            "experts_first": share_of(config)[0],
            "query_block": config["reference_query_block"],
            "head_block": config["reference_head_block"]}


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (``drivers/serve_lm.py``'s,
    against this configuration's reference)."""
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
    limits = {name: config[name] for name in (
        "logit_margin", "logit_margin_flipped", "logit_atol",
        "logit_atol_flipped", "logit_mean_atol", "min_choice_agreement")}
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
            for name, values in (("deficit", deficit), ("diff", diff)):
                for which, where in (("_same", same), ("_flipped", ~same)):
                    if where.any():
                        worst[name + which] = max(
                            worst[name + which], float(values[where].max()))
            bad += int(
                (deficit[same] > limits["logit_margin"]).sum() +
                (deficit[~same] > limits["logit_margin_flipped"]).sum() +
                (diff[same] > limits["logit_atol"]).sum() +
                (diff[~same] > limits["logit_atol_flipped"]).sum() +
                (~np.isfinite(deficit)).sum() + (~np.isfinite(diff)).sum())
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
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


def read_program_trace(trace_dir: str, name: str, hlo_text: str,
                       scopes: dict, scoped_instructions) -> dict:
    """Device seconds inside the runs of the program ``name`` in the traced
    window (chip 0), whole and by scope: ``{"runs", "program_s",
    "<key>_s", "<key>_events"}`` for every ``key: scope`` of ``scopes``
    (``drivers/serve_lm.py`` ``read_decode_trace``, for any program, and
    with an event that holds other events counted for its own time
    only)."""
    device, host, modules = xplane.read_trace(xplane.find_xplane(trace_dir))
    window = [(s, e) for n, s, e in host if n == xplane.WINDOW_SPAN]
    if not window or not device:
        return {}
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    chip = min(device)
    runs = sorted((s, e) for label, s, e in modules.get(chip, ())
                  if xplane.module_label(label) == name and
                  lo <= s and e <= hi)
    if not runs:
        return {}
    starts = [s for s, _ in runs]
    inside = []
    for label, s, e in device[chip]:
        i = np.searchsorted(starts, s, side="right") - 1
        if i >= 0 and e <= runs[i][1]:
            inside.append((s, -e, label.partition(" = ")[0].lstrip("%")))
    # an event that holds others (a ``while`` and the instructions of its
    # body) keeps its own time only: what it holds is counted once
    inside.sort()
    own, open_events = [], []
    for at, (s, minus_e, _label) in enumerate(inside):
        while open_events and -inside[open_events[-1]][1] <= s:
            open_events.pop()
        if open_events:
            own[open_events[-1]] -= -minus_e - s
        own.append(-minus_e - s)
        open_events.append(at)
    inside = [(label, d) for (_s, _e, label), d in zip(inside, own)]
    out = {"runs": len(runs), "program_s": sum(d for _, d in inside) / 1e9}
    for key, scope in scopes.items():
        names = scoped_instructions(hlo_text, scope)
        hits = [d for label, d in inside if label in names]
        out[key + "_s"] = sum(hits) / 1e9
        out[key + "_events"] = len(hits)
    return out


def run(ctx):
    import jax
    import jax.numpy as jnp
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import LatentAttention  # noqa: F401
    from alpa_tpu.model import moe
    from alpa_tpu.model.gpt_model import ATTENTION_SCOPE, GPTModel
    from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
    from alpa_tpu.serve import run_controller
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace
    base = ctx.load("drivers", "serve")
    lm = ctx.load("drivers", "serve_lm")
    scoped_instructions = ctx.load("drivers", "train_lm").scoped_instructions

    config, mix, serve = ctx.config, ctx.mix, ctx.config["serve"]
    if mix["kind"] != "closed_loop":
        raise ValueError("this driver's cells are closed loops")
    dtype = jnp.dtype(config["dtype"])
    gcfg = model_config(config, dtype=dtype, param_dtype=dtype,
                        seq_len=serve["served_context"])
    vocab = gcfg.vocab_size
    ttrace.set_enabled(ctx.trace == 1)
    registry = tmetrics.get_registry()
    timers = {}

    # the weights: on the device, from the seed, in one jitted call
    tic = time.perf_counter()
    model = GPTModel(gcfg)
    key = program.key_from_seed(ctx.seed)
    params = jax.jit(
        lambda key: model.init(key, jnp.ones((1, 8), jnp.int32)))(key)
    params = balance_routers(model, params, jax.random.fold_in(key, 1),
                             vocab)
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
        lm._warm_up(client, vocab, ctx.seed, serve["engine_rows"],
                    serve["prefill_chunk"], serve["prefill_chunk"],
                    mix["prompt_len"]["max"])
        timers["warmup_s"] = time.perf_counter() - tic
        # the controller's own engine, as its streaming path built it
        engine = server.controller._pick_replica(name).engine
        ctx.info({"info": "engine", "rows": engine.B,
                  "prefill_chunk": generator.prefill_chunk,
                  "served_context": gcfg.seq_len,
                  "memory": observe.device_memory(jax.local_devices())})

        compiles_before = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0)
        trace = program.DeviceTrace(ctx) if ctx.trace else None
        setup_s = observe.seconds_since_process_start()
        counters_t0 = registry.snapshot()
        window_t0_us = ttrace.now_us()
        t0 = time.perf_counter()
        # (stop, the threads that send, the threads that wait for answers)
        stop, senders, workers = _closed_loop(ctx, client, mix, vocab)
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

    # both programs' device seconds by the program's scopes
    decode_trace, chunk_trace = {}, {}
    if trace is not None and trace.capture is not None:
        scopes = {"moe": moe.SCOPE, "grouped_matmul": MATMUL_SCOPE,
                  "attention": ATTENTION_SCOPE}
        try:
            hlo = generator._decode.jitted.lower(
                abstract(params),
                jax.ShapeDtypeStruct((engine_rows, 1), jnp.int32),
                jax.ShapeDtypeStruct((engine_rows,), jnp.int32),
                caches, indices).compile().as_text()
            decode_trace = lm.read_decode_trace(
                trace.dir, hlo, scopes, scoped_instructions)
            from alpa_tpu.model.gpt_model import init_kv_caches
            hlo = generator._chunk_prefill.lower(
                abstract(params),
                jax.ShapeDtypeStruct((1, serve["prefill_chunk"]), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
                abstract(jax.eval_shape(lambda: init_kv_caches(gcfg, 1))),
                jax.ShapeDtypeStruct((1, vocab), dtype)).compile().as_text()
            chunk_trace = read_program_trace(
                trace.dir, CHUNK_PROGRAM, hlo, scopes, scoped_instructions)
        except (FileNotFoundError, ValueError):
            if not ctx.rehearsal:    # a CPU trace has no TPU plane
                raise
        ctx.info({"info": "decode_trace", **decode_trace})
        ctx.info({"info": "chunk_trace", **chunk_trace})
    requests = [r for r in client.records if r["kind"] == "measured"]
    records = [r for r in requests if not r["cut"]]
    for rec in records:
        if rec["error"] is None and len(rec["tokens"]) < rec["asked"]:
            rec["error"] = "due and not answered by the end of the drain"
    failed = sum(r["error"] is not None for r in records)
    ctx.info({"info": "ttft_ms", "sorted": sorted(
        round(w * 1e3, 3) for w in stats.ttft_waits(requests, drain_end))})
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
        "expert_layers": arithmetic_mla.expert_layers(config),
        # one routed expert's three matrices, as the decode reads them
        "expert_bytes": arithmetic_mla.expert_bytes(
            gcfg.hidden_size, gcfg.expert_width, dtype.itemsize),
        "decode_trace": decode_trace,
        "chunk_trace": chunk_trace,
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
