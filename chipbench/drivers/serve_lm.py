"""Driver of the serving cells whose model is built from the
configuration's own keys (those of its Hugging Face ``config.json``:
``alpa_tpu.model.gpt_model.config_from_hf``), today a decoder of window
and full attention layers over routed experts: the model behind
``run_controller`` + ``register_model`` with the deployment's sizes (the
configuration's ``serve``: engine rows, served context, prefill chunk),
loaded over HTTP by the clients of ``drivers/serve.py``, whose window,
clocks and ``obs`` this driver keeps (it loads that file for ``_Client``,
``_closed_loop`` and ``_open_loop``), so that every reader of the serving
cells works on it.

What this driver does differently:

* parameters are stored in the configuration's ``dtype`` (the published
  ``torch_dtype``), made on the device from the seed in one jitted call;
  the routers' biases are then set as the published model sets them, by
  load and not by gradient (``balance_router_biases``, one fixed schedule):
  a bias of zeros would leave the difference between what chooses an
  expert and what weighs it untested, and random weights without it
  prefer a few experts for all tokens, by an amount that changes from
  seed to seed and with it the work of a tick.
* the warm-up fills every engine row and admits one more, with prompts of
  one chunk, of several, and past the window.
* ``correct`` never runs over the declared context: the reference's full
  forward pass goes over prompt plus served tokens, padded to ONE shape
  (the mix's longest prompt plus longest output).  The checked requests
  are the one with the longest context (which must pass
  ``serve.check_context_over``: the rings have wrapped, the full layer is
  long), the shortest (under ``serve.check_context_under``, the window)
  and ``check_requests`` - 2 drawn from the seed.  Two comparisons at
  every served position.  What the window served: the reference's logit
  of the served token against the reference's largest
  (``logit_margin``); at a vocabulary of 200,192 the largest logits lie
  close together, so this catches a token from a wrong cache position or
  a wrong layer, and no rounding.  And the logits of the programs the
  window ran against the reference's, the mean absolute difference over
  the vocabulary (``logit_atol``, and ``logit_mean_atol`` for the mean
  over all positions), which is what a lower precision moves: after the
  window the checked requests go once more through the generator's
  compiled ``_chunk_prefill`` (``_run_chunked_prefill``), the engine's
  ``_scatter_row`` into resident caches of the engine's rows, and
  ``_decode`` over all those rows at once, the served ids teacher-forced
  (``_replay``): the same compiled programs at the same shapes, rows of
  unlike lengths in one tick.  ``_decode`` also says which experts each
  row took: the limits depend on whether the program's router chose the
  reference's experts at that position in every layer (a near-tie
  between two experts flips on the rounding of the bfloat16 activations,
  and the position then computes another function), and
  ``min_choice_agreement`` is a floor on the share of the reference's
  choices that are the program's.  The prefill's own last position, one
  a request, comes without its experts and is held to the wider limit.
* after the traced seconds, before the trace is reduced and deleted, the
  device events inside the runs of ``jit_decode`` are summed by the
  program's scopes ``moe``, ``grouped_matmul`` and ``attention``
  (``obs["decode_trace"]``): an event's name is its HLO instruction, and
  the compiled decode's HLO text says under which ``jax.named_scope`` each
  instruction was traced (``drivers/train_lm.py`` does the same for the
  train step).
"""
import threading
import time

import numpy as np

from chipbench import arithmetic_afmoe, observe, program, stats, traffic, \
    xplane

DECODE_PROGRAM = "jit_decode"


def _warm_up(client, vocab, seed, rows, chunk, window, longest):
    """Compile what the window will use, and nothing else: the chunk step
    (one shape, however long the prompt), the decode over all rows, the
    scatter of an admission and the sampling.  ``rows`` + 1 requests at
    once fill every row and admit one more; among them a prompt under one
    chunk, one of several chunks and one past the window."""
    rng = np.random.default_rng(seed)
    lengths = [min(longest, n) for n in
               (chunk // 2, 2 * chunk + 3, window + chunk + 5)]
    lengths += [24 + i for i in range(rows + 1 - len(lengths))]
    threads = []
    for i, n in enumerate(lengths):
        req = {"prompt_ids": rng.integers(4, vocab, size=n).tolist(),
               "max_new_tokens": 6 + i % 7}
        t = threading.Thread(target=client.request,
                             args=(req, time.perf_counter(), "warmup"))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    bad = [r["error"] for r in client.records if r["error"]]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")


# balance_router_biases' schedule: batches, uniform token ids a batch, and
# the first step (the last is a tenth of it)
BALANCE_STEPS, BALANCE_TOKENS, BALANCE_STEP = 30, 2048, 0.05


def balance_router_biases(model, params, key, vocab):
    """The routers' biases as the published model gets them: not by
    gradient, but raised where an expert is chosen less than its share
    and lowered where more (``b += step * sign(mean load - load)``, the
    auxiliary-loss-free balancing the bias exists for), here over
    ``BALANCE_STEPS`` batches of up to ``BALANCE_TOKENS`` uniform token
    ids with the step falling from ``BALANCE_STEP`` to a tenth of it.
    Random weights prefer a few experts for all tokens (a common
    direction in the hidden states grows layer by layer); a trained
    router does not, and the bytes a decode tick reads follow how many
    experts its rows touch."""
    import jax
    import jax.numpy as jnp
    tokens = min(BALANCE_TOKENS, model.config.seq_len)

    @jax.jit
    def pushes(params, ids, step):
        _logits, routing = model.apply(params, ids)
        counts = routing["expert_counts"].astype(jnp.float32)   # (L, E)
        return step * jnp.sign(counts.mean(-1, keepdims=True) - counts)

    layers = sorted((k for k, block in params["params"].items()
                     if "router_bias" in block.get("mlp", {})),
                    key=lambda k: int(k.lstrip("h")))

    def moved(params, push):
        """The same tree, its big leaves shared, with the biases moved."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x + push[layers.index(path[1].key)]
            if path[-1].key == "router_bias" else x, params)

    for i in range(BALANCE_STEPS):
        ids = jax.random.randint(jax.random.fold_in(key, i),
                                 (1, tokens), 4, vocab)
        step = BALANCE_STEP * 10.0 ** (-i / (BALANCE_STEPS - 1))
        params = moved(params, pushes(params, ids, jnp.float32(step)))
    return params


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"head_dim": config["head_dim"],
            "layer_types": config["layer_types"],
            "sliding_window": config["sliding_window"],
            "rms_norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "route_norm": config["route_norm"],
            "route_scale": config["route_scale"],
            "scale_embedding": config["mup_enabled"],
            "query_block": config["reference_query_block"]}


def _expert_layers(cfg) -> int:
    return sum(cfg.mlp_kind(i) == "experts" for i in range(cfg.num_layers))


def _replay(generator, scatter_row, rows, group, refs):
    """The requests of ``group`` (at most ``rows``) once more through the
    programs the window ran, at the window's shapes: each prompt through
    the compiled chunk step (``_run_chunked_prefill``), its caches and
    last logits into a row of resident caches of ``rows`` rows (the
    engine's ``scatter_row``), then ``_decode`` over all rows at once,
    every row fed the token it served (the rows left over decode along,
    as an engine's free rows do).  ``refs``: a request's reference logits
    at the positions that predict its served tokens, (served, V) float32.

    A request: ``(diff, experts)``: at each of those positions the mean
    over the vocabulary of |program's logits - reference's|, (served,),
    and the experts ``_decode`` says the row took there in every routed
    layer, (layers, served, k), -1 at the first position (the prefill's
    last logits, which come without them)."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import init_kv_caches
    cfg = generator.config

    @jax.jit
    def row_diff(logits, ref, row, at):
        return jnp.abs(logits[row].astype(jnp.float32) - ref[at]).mean()

    caches = [(k, v, jnp.zeros((rows,), jnp.int32))
              for k, v, _i in init_kv_caches(cfg, rows)]
    logits = jnp.zeros((rows, cfg.vocab_size), cfg.dtype)
    for r, rec in enumerate(group):
        prompt = np.asarray(rec["prompt_ids"], np.int32)
        last, row = generator._run_chunked_prefill(
            [prompt], jnp.asarray([len(prompt)], jnp.int32), 1)
        caches, logits = scatter_row(caches, row, logits, last, r)
    served = [rec["tokens"] for rec in group]
    diffs = [[row_diff(logits, ref, r, 0)] for r, ref in enumerate(refs)]
    chosen = []
    for k in range(max(map(len, served)) - 1):
        token = np.zeros((rows, 1), np.int32)
        for r, ids in enumerate(served):
            token[r, 0] = ids[min(k, len(ids) - 1)]
        logits, caches, routing = generator._decode(
            generator.params, jnp.asarray(token), caches[0][2], caches)
        chosen.append(routing["experts"])
        for r, ids in enumerate(served):
            if k + 1 < len(ids):
                diffs[r].append(row_diff(logits, refs[r], r, k + 1))
    out = []
    for r, ids in enumerate(served):
        experts = -np.ones((_expert_layers(cfg), len(ids),
                            cfg.num_experts_per_tok), np.int32)
        if len(ids) > 1:
            experts[:, 1:] = np.asarray(jnp.stack(
                [layers[:, r] for layers in chosen[:len(ids) - 1]], axis=1))
        out.append((np.asarray(jnp.stack(diffs[r]), np.float64), experts))
    return out


def _pick(done, mix, seed, context_over, context_under):
    """The requests to check: the longest context, the shortest, and
    others drawn from the seed, ``check_requests`` in all."""
    def context(rec):
        return len(rec["prompt_ids"]) + len(rec["tokens"])
    if not done:
        return [], False, False
    by_length = sorted(range(len(done)), key=lambda i: context(done[i]))
    picks = [by_length[-1], by_length[0]]
    for i in np.random.default_rng(seed).permutation(len(done)):
        if len(picks) >= mix["check_requests"]:
            break
        if int(i) not in picks:
            picks.append(int(i))
    picks = list(dict.fromkeys(picks))
    return ([done[i] for i in picks],
            context(done[by_length[-1]]) > context_over,
            context(done[by_length[0]]) < context_under)


def _check(ctx, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import jax.numpy as jnp
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = _pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    chunk = serve["prefill_chunk"]
    rows = mix["output_len"]["max"]
    # one shape for every checked request
    length = -(-(mix["prompt_len"]["max"] + rows) // chunk) * chunk
    if length > serve["served_context"]:
        raise ValueError("the mix's longest prompt and output do not fit "
                         "the served context")
    margin = config["logit_margin"]
    margin_flipped = config["logit_margin_flipped"]
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
    diff_sum = 0.0
    contexts = [len(rec["prompt_ids"]) + len(rec["tokens"])
                for rec in picked]
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
        replayed = _replay(generator, scatter_row, engine_rows, group, refs)
        del refs
        for deficit, want, (diff, got) in zip(deficits, wants, replayed):
            n_out = len(deficit)
            # which of the reference's experts the program chose too
            found = (want[..., :, None] == got[..., None, :]).any(-1)
            same = found.all(-1).all(0)
            known = (got >= 0).all(-1).all(0)
            common += int(found[:, known].sum())
            choices += want[:, known].size
            positions += n_out
            flipped += int((~same & known).sum())
            diff_sum += float(diff.sum())
            for name, values in (("deficit", deficit), ("diff", diff)):
                if same.any():
                    worst[name + "_same"] = max(
                        worst[name + "_same"], float(values[same].max()))
                if (~same).any():
                    worst[name + "_flipped"] = max(
                        worst[name + "_flipped"],
                        float(values[~same].max()))
            bad += int((deficit[same] > margin).sum() +
                       (deficit[~same] > margin_flipped).sum() +
                       (diff[same] > config["logit_atol"]).sum() +
                       (diff[~same] > config["logit_atol_flipped"]).sum() +
                       (~np.isfinite(deficit)).sum() +
                       (~np.isfinite(diff)).sum())
    agreement = common / choices if choices else 0.0
    mean_diff = diff_sum / positions if positions else float("inf")
    return {"checked_requests": len(picked), "checked_contexts": contexts,
            "checked_positions": positions, "over_margin": bad,
            "long_context_checked": has_long,
            "short_context_checked": has_short,
            "positions_with_a_flip": flipped,
            "choice_agreement": agreement,
            "worst_logit_deficit": worst["deficit_same"],
            "worst_logit_deficit_flipped": worst["deficit_flipped"],
            "worst_logit_diff": worst["diff_same"],
            "worst_logit_diff_flipped": worst["diff_flipped"],
            "mean_logit_diff": mean_diff,
            "logit_margin": margin, "logit_margin_flipped": margin_flipped,
            "logit_atol": config["logit_atol"],
            "logit_atol_flipped": config["logit_atol_flipped"],
            "logit_mean_atol": config["logit_mean_atol"],
            "min_choice_agreement": config["min_choice_agreement"]}


def read_decode_trace(trace_dir: str, hlo_text: str, scopes: dict,
                      scoped_instructions) -> dict:
    """Device seconds inside the runs of the decode program in the traced
    window (chip 0: one chip, one program at a time), whole and by scope:
    ``{"decode_s", "decode_runs", "<key>_s", "<key>_events"}`` for every
    ``key: scope`` of ``scopes``."""
    device, host, modules = xplane.read_trace(xplane.find_xplane(trace_dir))
    window = [(s, e) for n, s, e in host if n == xplane.WINDOW_SPAN]
    if not window or not device:
        return {}
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    chip = min(device)
    runs = sorted((s, e) for name, s, e in modules.get(chip, ())
                  if xplane.module_label(name) == DECODE_PROGRAM and
                  lo <= s and e <= hi)
    if not runs:
        return {}
    starts = [s for s, _ in runs]
    inside = []
    for name, s, e in device[chip]:
        i = np.searchsorted(starts, s, side="right") - 1
        if i >= 0 and e <= runs[i][1]:
            inside.append((name.partition(" = ")[0].lstrip("%"), e - s))
    out = {"decode_runs": len(runs),
           "decode_s": sum(d for _, d in inside) / 1e9}
    for key, scope in scopes.items():
        names = scoped_instructions(hlo_text, scope)
        hits = [d for name, d in inside if name in names]
        out[key + "_s"] = sum(hits) / 1e9
        out[key + "_events"] = len(hits)
    return out


def run(ctx):
    import jax
    import jax.numpy as jnp
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model import moe
    from alpa_tpu.model.gpt_model import (ATTENTION_SCOPE, GPTModel,
                                          config_from_hf)
    from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
    from alpa_tpu.serve import run_controller
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace
    base = ctx.load("drivers", "serve")
    scoped_instructions = ctx.load("drivers", "train_lm").scoped_instructions

    config, mix, serve = ctx.config, ctx.mix, ctx.config["serve"]
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
    params = jax.jit(
        lambda key: model.init(key, jnp.ones((1, 8), jnp.int32)))(key)
    params = balance_router_biases(model, params, jax.random.fold_in(key, 1),
                                   vocab)
    generator = Generator(model, params, gcfg,
                          prefill_chunk=serve["prefill_chunk"])
    jax.block_until_ready(generator.params)
    timers["state_init_s"] = time.perf_counter() - tic
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    expert_layers = _expert_layers(gcfg)
    ctx.info({"info": "state", "weight_bytes": weight_bytes,
              "memory": observe.device_memory(jax.local_devices())})

    name = config["name"]
    closed = mix["kind"] == "closed_loop"
    server = run_controller(port=0)
    engine = None
    try:
        server.controller.register_model(
            name, generator, engine_rows=serve["engine_rows"],
            chunked_admission=True)
        client = base._Client(ctx, server.port, name)
        tic = time.perf_counter()
        _warm_up(client, vocab, ctx.seed, serve["engine_rows"],
                 serve["prefill_chunk"], gcfg.sliding_window,
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
                      for kind in ("window", "full")},
                  "memory": observe.device_memory(jax.local_devices())})

        compiles_before = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0)
        trace = program.DeviceTrace(ctx) if ctx.trace else None
        setup_s = observe.seconds_since_process_start()
        counters_t0 = registry.snapshot()
        window_t0_us = ttrace.now_us()
        t0 = time.perf_counter()
        # (stop, the threads that send, the threads that wait for answers)
        if closed:
            stop, senders, workers = base._closed_loop(ctx, client, mix,
                                                       vocab)
        else:
            stop, senders, workers = base._open_loop(
                client, traffic.open_loop(mix, ctx.seed, vocab, ctx.seconds),
                t0)
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
        client.end_window(cut=closed)
        counters = (counters_t0, registry.snapshot())
        compiles_in_window = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0) - compiles_before
        memory = observe.device_memory(jax.local_devices())

        if ctx.trace == 2:
            # the same traffic for the traced seconds, inside a capture
            trace.warm_up()
            trace.start()
            traced_counters = registry.snapshot()
            if not closed:
                more = traffic.open_loop(mix, ctx.seed + 1, vocab,
                                         mix["trace_seconds"])
                stop_more, send_more, _ = base._open_loop(
                    client, more, time.perf_counter())
                senders = senders + send_more
            base._sleep_until(time.perf_counter() + mix["trace_seconds"])
            traced_counters = (traced_counters, registry.snapshot())
            trace.stop()
            if not closed:
                stop_more.set()
        memory_run = observe.device_memory(jax.local_devices())

        stop.set()
        client.close_cut_requests(stop=closed)
        for t in senders:
            t.join(timeout=30)
        # the drain: requests that were due get a stated time to finish
        deadline = t1 + mix["drain_s"]
        for t in list(workers):
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        drain_end = time.perf_counter()
        # the shapes of the decode's arguments, for its HLO text
        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
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

    # the decode program's device seconds by the program's scopes
    decode_trace = {}
    if trace is not None and trace.capture is not None:
        try:
            hlo = generator._decode.jitted.lower(
                abstract(params),
                jax.ShapeDtypeStruct((engine_rows, 1), jnp.int32),
                jax.ShapeDtypeStruct((engine_rows,), jnp.int32),
                caches, indices).compile().as_text()
            decode_trace = read_decode_trace(
                trace.dir, hlo,
                {"moe": moe.SCOPE, "grouped_matmul": MATMUL_SCOPE,
                 "attention": ATTENTION_SCOPE}, scoped_instructions)
        except (FileNotFoundError, ValueError):
            if not ctx.rehearsal:    # a CPU trace has no TPU plane
                raise
        ctx.info({"info": "decode_trace", **decode_trace})
    requests = [r for r in client.records if r["kind"] == "measured"]
    records = [r for r in requests if not r["cut"]]
    for rec in records:
        if rec["error"] is None and len(rec["tokens"]) < rec["asked"]:
            rec["error"] = "due and not answered by the end of the drain"
    failed = sum(r["error"] is not None for r in records)
    ctx.info({"info": "ttft_ms", "sorted": sorted(
        round(w * 1e3, 3) for w in stats.ttft_waits(requests, drain_end))})
    times = sorted(t for r in requests for t in r["token_times"]
                   if t0 <= t <= t1)
    ctx.info({"info": "stalls",
              "latest_sends": sorted(
                  ([r["sent"] - r["due"], r["due"] - t0] for r in requests
                   if r["sent"] is not None), reverse=True)[:3],
              "longest_silences": sorted(
                  ([b - a, a - t0] for a, b in zip(times, times[1:])),
                  reverse=True)[:3]})
    # the gaps between a request's consecutive tokens, as ``gap_p99_ms``
    # pools them: in this cell an info line and no metric (a stall of all
    # rows is an admission's whole chunks, so the tail is a ladder of them)
    gaps = [t - rec["token_times"][k - 1] for rec, k, t in
            stats.window_tokens({"window": (t0, t1), "requests": requests})
            if k > 0]
    ctx.info({"info": "gaps_ms", "count": len(gaps), **{
        f"p{q}": round(stats.percentile(gaps, q) * 1e3, 3)
        for q in (50, 90, 98, 98.5, 99, 99.5, 99.9) if gaps}})
    tic = time.perf_counter()
    checks = _check(ctx, generator, scatter_row, engine_rows,
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
        "weight_bytes": weight_bytes,
        "cache_itemsize": dtype.itemsize,
        "expert_layers": expert_layers,
        # one routed expert's three matrices, as the decode reads them
        "expert_bytes": arithmetic_afmoe.expert_bytes(
            gcfg.hidden_size, gcfg.expert_width, dtype.itemsize),
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
