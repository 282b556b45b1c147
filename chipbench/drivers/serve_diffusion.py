"""Driver of the serving cells whose model generates by diffusion over
blocks (``model_type`` sdar_moe): the model from the configuration's own
keys (``alpa_tpu.model.gpt_model.config_from_hf``, the block length from
the configuration's ``serve``) behind ``run_controller`` +
``register_model(engine_rows=..., chunked_admission=True)``, loaded over
HTTP by the clients of ``drivers/serve.py``.  It keeps the window, clocks,
warm-up and ``obs`` of ``drivers/serve_lm.py`` and ``drivers/serve_mla.py``
(it loads those files for ``_warm_up``, ``_pick``, ``_closed_loop``,
``balance_routers`` and ``read_program_trace``), so that every reader of
the serving cells works on it.

What this driver does differently:

* a tick of the engine is one forward of a whole block a row
  (``Generator._block_step``, the program ``jit_block_step``) and yields
  between none and ``block_length`` tokens a row; prompt ids are drawn
  from the ids below the mask token, which no prompt may hold.
* the routers are balanced as ``drivers/serve_mla.py`` balances them (the
  published model has no selection bias).
* ``correct`` (``_check``): after the window the checked requests go once
  more through the window's own compiled programs at the window's shapes:
  ``_chunk_prefill`` over each prompt's whole blocks, the engine's
  ``_scatter_row`` into resident caches of the engine's rows, then
  ``_block_step`` over all those rows, forward after forward, each row in
  its own phase (``_replay``).  The replay is handed every row's block
  from the host and is teacher-forced on the tokens: a position the step
  unmasks takes the token the window SERVED there, and a step that would
  have put another is counted (``replay_token_mismatches``; the same
  program on the same ids gives the same tokens, so any is a fault).
  Against the reference (``references/sdar_moe_decoder.py``):

  - the commit path: at every position of every block the served tokens
    cover whole, the logits of the forward that commits it against the
    reference's ONE whole pass over prompt plus served tokens (mean
    absolute difference over the vocabulary: ``logit_atol``,
    ``logit_mean_atol``);
  - the denoise path: for ``serve.check_states`` seeded (block, forward)
    states a request, a whole pass of the reference over prompt, committed
    blocks and the block as that forward met it (masks where it was
    masked): the served token's reference logit within ``logit_margin`` of
    the reference's largest at each position the forward unmasked; the
    step's logits against the reference's at the block's positions
    (``logit_atol``); and the positions it unmasked the reference's own
    choice by the same rule, unless the reference's confidences of the
    weakest position taken and the strongest left differ by less than
    ``confidence_log_margin`` (in their logarithms);
  - a position where the step chose another expert than the reference in
    any layer is held to the wider limits (``*_flipped``), and
    ``min_choice_agreement`` is a floor on the share of the reference's
    choices that are the program's, as in the two expert cells;
  - every request due answered in full by the end of the drain, and no
    compilation inside the window.
* after the traced seconds the device events inside the runs of
  ``jit_block_step`` are summed by the program's scopes
  (``obs["decode_trace"]``, under the keys the decode's readers take), and
  the compiler's ``memory_analysis`` of the block step, the chunk step and
  the initialiser goes on an info line.
"""
import time

import numpy as np

from chipbench import arithmetic_sdar, device_parts, observe, program, stats

BLOCK_PROGRAM = "jit_block_step"


def model_config(config: dict, **overrides):
    """The program's configuration of a configuration file: its keys as
    Hugging Face names them, the block length the deployment states."""
    from alpa_tpu.model.gpt_model import config_from_hf
    return config_from_hf(
        config, **{"block_length": config["serve"]["block_length"],
                   **overrides})


def diffusion_settings(config: dict):
    from alpa_tpu.serve.generation import BlockDiffusion
    serve = config["serve"]
    return BlockDiffusion(mask_token_id=serve["mask_token_id"],
                          denoising_steps=serve["denoising_steps"],
                          remasking=serve["remasking"],
                          threshold=serve["confidence_threshold"])


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"head_dim": config["head_dim"],
            "block_length": config["serve"]["block_length"],
            "rms_norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "query_block": config["reference_query_block"]}


def _memory_analysis(compiled) -> dict:
    """The compiler's account of a program's memory, in bytes."""
    ma = compiled.memory_analysis()
    return {name: int(getattr(ma, name + "_size_in_bytes"))
            for name in ("argument", "output", "alias", "temp")}


def _states_to_check(rng, blocks: int, wanted: int) -> dict:
    """``{block: forward}``: which denoising forward (0 or 1; a block
    that takes one is checked at it) of which of a request's ``blocks``
    whole blocks the reference is run at, ``wanted`` of them or all."""
    picked = rng.permutation(blocks)[:wanted]
    return {int(b): int(rng.integers(0, 2)) for b in picked}


def _replay(generator, scatter_row, rows, group, wanted, ref_mod, eps,
            hiddens, weights):
    """The requests of ``group`` (at most ``rows``) once more through the
    programs the window ran, at the window's shapes (module docstring).
    ``wanted``: a request's ``_states_to_check``.  ``hiddens``: a request's
    reference hidden states (before the final norm) at the positions from
    its first block's start on, from the reference's whole pass.

    A request: ``{"commit_diff": (positions,), "commit_experts": (layers,
    positions, k), "states": [...], "mismatches": n}``; a state holds the
    block's first position, its ids as the forward met them, the budget it
    had left, the positions it unmasked, its logits (L, V) (on the
    device) and its experts (layers, L, k)."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.serve.generation import (GenerationConfig,
                                           fresh_kv_caches, row_length)
    cfg = generator.config
    length, mask = cfg.block_length, generator.diffusion.mask_token_id
    steps = generator.denoising_steps

    @jax.jit
    def commit_diff(logits, row, hidden, at, wf, w_head):
        want = ref_mod.head(jax.lax.dynamic_slice_in_dim(
            hidden, at, length, axis=0), wf, w_head, eps)
        return jnp.abs(logits[row].astype(jnp.float32) - want).mean(-1)

    caches = [(k, v, jnp.zeros((rows,), jnp.int32))
              for k, v, _i in fresh_kv_caches(cfg, rows)]
    resident = jnp.zeros((rows, cfg.vocab_size), cfg.dtype)
    blocks = np.full((rows, length), mask, np.int32)
    left = np.full((rows,), steps, np.int32)
    state = []
    for r, rec in enumerate(group):
        prompt = np.asarray(rec["prompt_ids"], np.int32)
        start, first = generator.first_block(prompt)
        if start:
            last, row = generator._run_chunked_prefill(
                [prompt[:start]], row_length(start), 1)
        else:
            last = resident[:1]
            row = [(k, v, row_length(0)) for k, v, _ in
                   fresh_kv_caches(cfg, 1)]
        caches, resident = scatter_row(caches, row, resident, last, r)
        blocks[r] = first
        served = np.asarray(rec["prompt_ids"] + rec["tokens"], np.int32)
        state.append({
            "served": served, "start": start, "base": start,
            # the position past the last block the served tokens fill
            "covered": len(served) // length * length,
            "forward": 0, "candidate": None,
            "out": {"commit_diff": [], "commit_experts": [], "states": [],
                    "mismatches": 0}})
    settings = generator.sampling_settings(rows, GenerationConfig())
    key = jax.random.PRNGKey(0)
    live = [r for r, st in enumerate(state) if st["base"] < st["covered"]]
    while live:
        ids_in, left_in = blocks.copy(), left.copy()
        new_ids, _left, unmasked, _commits, logits, caches, routing, key = \
            generator._block_step(
                generator.params, jnp.asarray(ids_in), caches[0][2], caches,
                jnp.asarray(left_in), settings, key)
        new_ids, unmasked, experts = jax.device_get(
            (new_ids, unmasked, routing["experts"]))
        experts = experts.reshape(experts.shape[0], rows, length, -1)
        for r in range(rows):
            st = state[r] if r < len(state) else None
            if (ids_in[r] == mask).any():
                took = unmasked[r]
                if st is not None and r in live:
                    at = st["base"] + np.nonzero(took)[0]
                    # teacher-forced: what the window served there
                    want = st["served"][at]
                    st["out"]["mismatches"] += int(
                        (new_ids[r][took] != want).sum())
                    block = (st["base"] - st["start"]) // length
                    if block in wanted[r] and (
                            st["forward"] == 0 or
                            wanted[r][block] == st["forward"]):
                        st["candidate"] = {
                            "base": st["base"], "ids": ids_in[r].copy(),
                            "left": int(left_in[r]), "took": took.copy(),
                            "logits": logits[r], "experts": experts[:, r]}
                    st["forward"] += 1
                    blocks[r][took] = want
                else:
                    blocks[r] = new_ids[r]
                left[r] = max(left_in[r] - 1, 1)
            else:
                if st is not None and r in live:
                    st["out"]["commit_diff"].append(commit_diff(
                        logits, r, hiddens[r], st["base"] - st["start"],
                        weights["wf"], weights["w_head"]))
                    st["out"]["commit_experts"].append(experts[:, r])
                    if st["candidate"] is not None:
                        st["out"]["states"].append(st["candidate"])
                    st.update(base=st["base"] + length, forward=0,
                              candidate=None)
                    if st["base"] >= st["covered"]:
                        live.remove(r)
                blocks[r] = mask
                left[r] = steps
    out = []
    for st in state:
        o = st["out"]
        o["commit_diff"] = np.concatenate(
            [np.asarray(d, np.float64) for d in o["commit_diff"]]) \
            if o["commit_diff"] else np.zeros((0,))
        o["commit_experts"] = np.concatenate(o["commit_experts"], axis=1) \
            if o["commit_experts"] else None
        out.append(o)
    return out


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    length, mask = serve["block_length"], serve["mask_token_id"]
    eps, pad = config["rms_norm_eps"], config["reference_pad"]
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = lm._pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    limits = {name: config[name] for name in (
        "logit_margin", "logit_margin_flipped", "logit_atol",
        "logit_atol_flipped", "logit_mean_atol", "min_choice_agreement",
        "confidence_log_margin")}
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0,
             "commit_diff_same": 0.0, "commit_diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
    states = states_due = mismatches = unlike = near_ties = 0
    diff_sum, confidence_gap, widest_unlike = 0.0, 0.0, 0.0

    def padded(ids):
        """``ids`` filled up with masks to the reference's next shape: a
        later block is seen by no position."""
        n = -(-len(ids) // pad) * pad
        return np.concatenate([ids, np.full((n - len(ids),), mask,
                                            np.int32)])

    def tally(name, values, same):
        nonlocal bad
        if len(values) == 0:
            return
        for which, where in (("_same", same), ("_flipped", ~same)):
            if where.any():
                worst[name + which] = max(worst[name + which],
                                          float(values[where].max()))
        kind = "logit_margin" if name == "deficit" else "logit_atol"
        bad += int((values[same] > limits[kind]).sum() +
                   (values[~same] > limits[kind + "_flipped"]).sum() +
                   (~np.isfinite(values)).sum())

    def agreement(want, got):
        """``(same (positions,), found)``: where the program chose the
        reference's experts in every layer, and which of the reference's
        choices it made."""
        found = (want[..., :, None] == got[..., None, :]).any(-1)
        return found.all(-1).all(0), found

    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        rng = np.random.default_rng(ctx.seed)
        hiddens, ref_experts, wanted = [], [], []
        for rec in group:
            served = np.asarray(rec["prompt_ids"] + rec["tokens"], np.int32)
            start = len(rec["prompt_ids"]) // length * length
            covered = len(served) // length * length
            hidden, chosen = reference.hidden(weights,
                                              padded(served[:covered]))
            hiddens.append(hidden[start:covered])
            ref_experts.append(np.stack(
                [np.asarray(c[start:covered]) for c in chosen]))
            wanted.append(_states_to_check(
                rng, (covered - start) // length, serve["check_states"]))
            states_due += len(wanted[-1])
        replayed = _replay(generator, scatter_row, engine_rows, group,
                           wanted, ref_mod, eps, hiddens, weights)
        del hiddens
        for rec, want, got in zip(group, ref_experts, replayed):
            served = np.asarray(rec["prompt_ids"] + rec["tokens"], np.int32)
            mismatches += got["mismatches"]
            # the commit path, at every position of the whole blocks
            if got["commit_experts"] is not None:
                same, found = agreement(want, got["commit_experts"])
                common += int(found.sum())
                choices += found.size
                flipped += int((~same).sum())
                positions += len(same)
                diff_sum += float(got["commit_diff"].sum())
                tally("commit_diff", got["commit_diff"], same)
            # the denoise path, at the seeded states
            for st in got["states"]:
                states += 1
                ids = np.concatenate([served[:st["base"]], st["ids"]])
                logits, chosen = reference.logits_and_experts(
                    weights, padded(ids), rows=(st["base"], length))
                logits = np.asarray(logits, np.float64)
                mine = np.asarray(st["logits"], np.float64)
                same, found = agreement(np.asarray(chosen), st["experts"])
                common += int(found.sum())
                choices += found.size
                flipped += int((~same).sum())
                positions += length
                diff = np.abs(mine - logits).mean(-1)
                diff_sum += float(diff.sum())
                tally("diff", diff, same)
                took = st["took"]
                tokens = served[st["base"]:st["base"] + length]
                deficit = logits.max(-1) - logits[np.arange(length), tokens]
                tally("deficit", deficit[took], same[took])
                # the rule, on the reference's confidences
                masked = st["ids"] == mask
                shifted = logits - logits.max(-1, keepdims=True)
                log_conf = -np.log(np.exp(shifted).sum(-1))
                choice = ref_mod.choose_unmasked(
                    masked, np.exp(log_conf), st["left"], serve["remasking"],
                    serve["confidence_threshold"])
                own = mine - mine.max(-1, keepdims=True)
                confidence_gap = max(confidence_gap, float(np.abs(
                    -np.log(np.exp(own).sum(-1)) - log_conf)[masked].max()))
                if (choice != took).any():
                    # how far apart the reference holds the positions the
                    # two choices differ in
                    gap = abs(log_conf[choice & ~took].min() -
                              log_conf[took & ~choice].max()) \
                        if (choice & ~took).any() and (took & ~choice).any() \
                        else float("inf")
                    if gap > limits["confidence_log_margin"]:
                        unlike += 1
                    else:
                        near_ties += 1
                    widest_unlike = max(widest_unlike, gap)
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_positions": positions, "checked_states": states,
            "states_due": states_due,
            "over_margin": bad,
            "replay_token_mismatches": mismatches,
            "unmasked_unlike_the_reference": unlike,
            "unmasked_at_a_near_tie": near_ties,
            "widest_gap_unmasked_unlike": widest_unlike,
            "worst_log_confidence_diff": confidence_gap,
            "long_context_checked": has_long,
            "short_context_checked": has_short,
            "positions_with_a_flip": flipped,
            "choice_agreement": common / choices if choices else 0.0,
            "worst_logit_deficit": worst["deficit_same"],
            "worst_logit_deficit_flipped": worst["deficit_flipped"],
            "worst_logit_diff": worst["diff_same"],
            "worst_logit_diff_flipped": worst["diff_flipped"],
            "worst_commit_logit_diff": worst["commit_diff_same"],
            "worst_commit_logit_diff_flipped": worst["commit_diff_flipped"],
            "mean_logit_diff": diff_sum / positions if positions
            else float("inf"), **limits}


def run(ctx):
    import jax
    import jax.numpy as jnp
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.serve.generation import BlockDiffusion  # noqa: F401
    from alpa_tpu.model import moe
    from alpa_tpu.model.gpt_model import (ATTENTION_SCOPE, GPTModel,
                                          init_kv_caches)
    from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
    from alpa_tpu.serve import run_controller
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace
    base = ctx.load("drivers", "serve")
    lm = ctx.load("drivers", "serve_lm")
    mla = ctx.load("drivers", "serve_mla")
    scoped_instructions = ctx.load("drivers", "train_lm").scoped_instructions

    config, mix, serve = ctx.config, ctx.mix, ctx.config["serve"]
    if mix["kind"] != "closed_loop":
        raise ValueError("this driver's cells are closed loops")
    dtype = jnp.dtype(config["dtype"])
    gcfg = model_config(config, dtype=dtype, param_dtype=dtype,
                        seq_len=serve["served_context"])
    # prompts hold ids below the mask token's
    vocab = serve["mask_token_id"]
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
    params = mla.balance_routers(model, params, jax.random.fold_in(key, 1),
                                 vocab)
    generator = Generator(model, params, gcfg,
                          prefill_chunk=serve["prefill_chunk"],
                          diffusion=diffusion_settings(config))
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
                  "block_length": gcfg.block_length,
                  "denoising_steps": generator.denoising_steps,
                  "block_step_traces": generator.decode_traces,
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
        # the shapes of the block step's arguments, for its HLO text
        caches = abstract([(k, v) for k, v, _ in engine._caches])
        indices = abstract([i for _, _, i in engine._caches])
        more = abstract((engine._left, engine._settings, engine._key))
        engine_rows, scatter_row = engine.B, engine._scatter_row
    finally:
        if engine is not None:
            engine.shutdown()
            engine._thread.join(timeout=30)
        server.shutdown()
    # the check makes resident caches of its own: the engine's go first
    del engine, server

    # the block step's device seconds by the program's scopes, under the
    # keys the decode's readers take; the compiler's account of memory
    decode_trace = {}
    if trace is not None:
        block_step = generator._block_step.jitted.lower(
            abstract(params),
            jax.ShapeDtypeStruct((engine_rows, gcfg.block_length),
                                 jnp.int32),
            jax.ShapeDtypeStruct((engine_rows,), jnp.int32),
            caches, indices, *more).compile()
        chunk_step = generator._chunk_prefill.lower(
            abstract(params),
            jax.ShapeDtypeStruct((1, serve["prefill_chunk"]), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            abstract(jax.eval_shape(lambda: init_kv_caches(gcfg, 1))),
            jax.ShapeDtypeStruct((1, gcfg.vocab_size), dtype)).compile()
        ctx.info({"info": "memory_analysis", **{
            what: _memory_analysis(compiled) for what, compiled in (
                ("block_step", block_step), ("chunk_prefill", chunk_step),
                ("initialiser", initialiser.lower(key).compile()))}})
        if trace.capture is not None:
            try:
                found = mla.read_program_trace(
                    trace.dir, BLOCK_PROGRAM, block_step.as_text(),
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
            # for the record: a block step by part of the model, ms a run
            entry = device_parts.program(BLOCK_PROGRAM)
            if entry is not None:
                ctx.info({"info": "block_step_parts", "runs": entry["runs"],
                          **{part: round(1e3 * s / entry["runs"], 4)
                             for part, s in entry["parts"].items()},
                          **{key: round(entry[key] / sum(
                              entry["parts"].values()), 4) for key in
                             ("mixed_s", "inherited_s", "unscoped_s")}})
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
    # metric (tokens arrive a block at a time, behind whole admissions)
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
    checks["block_step_traces"] = generator.decode_traces
    checks["errors"] = sorted({r["error"] for r in records
                               if r["error"]})[:5]
    return {
        "correct": bool(
            failed == 0 and checks["over_margin"] == 0 and
            checks["replay_token_mismatches"] == 0 and
            checks["unmasked_unlike_the_reference"] == 0 and
            checks["checked_requests"] >= mix["check_requests"] and
            checks["checked_states"] == checks["states_due"] and
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
        "expert_layers": gcfg.num_layers,
        # one routed expert's three matrices, as a forward reads them
        "expert_bytes": arithmetic_sdar.expert_bytes(
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
