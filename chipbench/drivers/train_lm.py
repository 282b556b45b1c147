"""Driver of the training cells whose model is built from the
configuration's own keys (those of its Hugging Face ``config.json``:
``alpa_tpu.model.gpt_model.config_from_hf``), today a decoder of routed
experts: one ``@alpa_tpu.parallelize`` train step of ``GPTModel`` under
the method the configuration names, fed a fresh batch from the host at
every step until ``--seconds`` have passed.

The order of a run is ``train.py``'s: plan and compile from shapes; create
the state already placed; the plain reference's loss on the state's own
parameters and the first batch; one warm-up step on that batch, whose loss
is compared with the reference's; the window; in a traced run,
``trace_steps`` more steps inside a capture of the program.

What this driver does differently:

* the window's loop enqueues each step one ahead of the step it waits for
  (``run_steps``), where ``train.py`` waits for every step before it calls
  the next: ``train_tokens_per_s`` is then what the device sustains, and
  the host's dispatch (``host_dispatch_ms.train``, ``driver_launch_ms``)
  shows in it only if it outlasts a step.

* ``correct`` also compares, for ``check_sequences`` sequences of the
  first batch, the loss of EVERY position as the program's forward pass
  gives it (its own jitted program, outside the window) with the
  reference's, and every token's experts with the reference's.  A near-tie
  between two experts flips on the rounding of the bfloat16 activations:
  positions whose experts are the reference's in every layer must agree
  within ``position_atol``, the others within ``position_atol_flipped``,
  all of them on average within ``position_mean_atol``, and at least
  ``min_choice_agreement`` of all choices must be the reference's (the
  configuration says where the numbers come from).
* the step returns each expert's rows; after the warm-up step and after
  the traced steps, never inside the window, the driver hands them to
  ``alpa_tpu.model.moe.record_routing``, which feeds the registry.
* before the capture's trace is reduced and deleted, the device events of
  the expert path and of its grouped matmuls are summed
  (``obs["expert_trace"]``).  The events carry no scope names on this chip
  (an event's name is its HLO instruction); the compiled step's HLO text
  does (``metadata.op_name`` holds the ``jax.named_scope`` path), so the
  instructions under the scopes ``moe`` and ``grouped_matmul`` are looked
  up there and the events matched by instruction name.
"""
import math
import re
import time

import numpy as np

from chipbench import arithmetic_moe, observe, program, traffic, xplane

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def scoped_instructions(hlo_text: str, scope: str) -> set:
    """Names of the HLO instructions traced under ``jax.named_scope(scope)``
    (forward, or its transpose in the backward pass): the scope is a whole
    component of the instruction's ``op_name`` path, bare or wrapped as in
    ``transpose(jvp(moe))``."""
    inside = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    names = set()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and inside.search(m.group(2)):
            names.add(m.group(1))
    return names


def scope_seconds(events: list, window: tuple, names: set) -> tuple:
    """(seconds, count) of the device events [(name, start_ns, end_ns)]
    inside ``window`` whose HLO instruction is one of ``names``."""
    lo, hi = window
    total, count = 0.0, 0
    for name, start, end in events:
        if name.partition(" = ")[0].lstrip("%") in names:
            d = min(end, hi) - max(start, lo)
            if d > 0:
                total, count = total + d / 1e9, count + 1
    return total, count


def read_expert_trace(trace_dir: str, hlo_text: str, expert_scope: str,
                      matmul_scope: str) -> dict:
    """The expert path's and the grouped matmuls' device seconds in the
    traced window (chip 0: one mesh, one program)."""
    device, host, _ = xplane.read_trace(xplane.find_xplane(trace_dir))
    window = [(s, e) for n, s, e in host if n == xplane.WINDOW_SPAN]
    if not window or not device:
        return {}
    window = (min(s for s, _ in window), max(e for _, e in window))
    events = device[min(device)]
    moe_s, moe_n = scope_seconds(
        events, window, scoped_instructions(hlo_text, expert_scope))
    gmm_s, gmm_n = scope_seconds(
        events, window, scoped_instructions(hlo_text, matmul_scope))
    return {"expert_path_s": moe_s, "expert_path_events": moe_n,
            "grouped_matmul_s": gmm_s, "grouped_matmul_events": gmm_n}


def run(ctx):
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.create_state_parallel import CreateStateParallel
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model import moe
    from alpa_tpu.model.gpt_model import GPTModel, config_from_hf
    from alpa_tpu.model.model_util import routed_lm_loss
    from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace
    base = ctx.load("drivers", "train")

    config, mix = ctx.config, ctx.mix
    knobs = config["train"]
    alpa_tpu.init(cluster="local")
    method, _ = base._method(config, mix)
    gcfg = config_from_hf(config, dtype=jnp.dtype(config["dtype"]),
                          attention_impl=knobs["attention_impl"],
                          remat_blocks=knobs["remat_blocks"])
    model = GPTModel(gcfg)
    aux_coef = config["router_aux_loss_coef"]
    shape = (mix["batch"], gcfg.seq_len)
    k_init = program.key_from_seed(ctx.seed)
    # one optimizer object: it is part of the state's tree structure
    tx = optax.adam(knobs["learning_rate"])

    def create_state():
        params = model.init(k_init, jnp.ones(shape, jnp.int32))
        return train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx)

    @alpa_tpu.parallelize(method=method, static_argnums=(),
                          donate_argnums=(0,))
    def train_step(state, batch):

        def loss_fn(p):
            return routed_lm_loss(state.apply_fn, p, batch, aux_coef)

        (loss, routing), grads = alpa_tpu.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        return (state.apply_gradients(grads=grads), loss,
                routing["expert_counts"])

    @jax.jit
    def forward_check(params, input_ids, labels):
        """The program's forward pass alone: the loss of every position
        and every token's experts."""
        logits, routing = model.apply(params, input_ids)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        return losses, routing["experts"]

    batches = traffic.lm_batches(mix, ctx.seed, gcfg.seq_len,
                                 gcfg.vocab_size)
    first = next(batches)
    abstract = (jax.eval_shape(create_state),
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), first))

    # the program's own spans: on for planning and compiling in every mode
    ttrace.set_enabled(True)
    timers = {}
    tic = time.perf_counter()
    executable, _ = train_step.get_executable(*abstract)
    timers["get_executable_s"] = time.perf_counter() - tic

    tic = time.perf_counter()
    state = alpa_tpu.parallelize(
        create_state, method=CreateStateParallel(train_step, abstract),
        batch_argnums=())()
    jax.block_until_ready(state)
    timers["state_init_s"] = time.perf_counter() - tic
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(state.params))
    ctx.info({"info": "state", "params": n_params,
              "memory": observe.device_memory(jax.local_devices())})

    # the reference and the program's forward pass, on the state's own
    # parameters, before the first step donates them
    tic = time.perf_counter()
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference({
        "num_heads": config["num_attention_heads"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "num_experts_per_tok": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "router_aux_loss_coef": aux_coef,
        "token_block": config["reference_token_block"]})
    spot = jax.local_devices()[0]
    weights = ref_mod.weights_from_program(
        base._gather_to(state.params, spot))
    ref_loss, ref_positions, ref_experts = reference.batch_losses(
        weights, first["input_ids"], first["labels"])
    n_check = min(config["check_sequences"], mix["batch"])
    del weights
    timers["reference_s"] = time.perf_counter() - tic
    tic = time.perf_counter()
    got_positions, got_experts = jax.device_get(forward_check(
        state.params, first["input_ids"][:n_check],
        first["labels"][:n_check]))
    timers["forward_check_s"] = time.perf_counter() - tic
    position_checks = _compare_positions(
        config, np.asarray(ref_positions[:n_check]),
        np.asarray(ref_experts[:, :n_check]),
        got_positions, got_experts.reshape(
            got_experts.shape[0], n_check, -1, got_experts.shape[-1]))
    ctx.info({"info": "position_check", **position_checks})

    # ... and off from here on, but in a traced run of its own
    ttrace.set_enabled(ctx.trace == 1)
    setup_spans = ttrace.get_recorder().spans()
    ttrace.get_recorder().clear()
    tic = time.perf_counter()
    state, loss, counts = train_step(state, first)
    jax.block_until_ready((state, loss))
    timers["warmup_step_s"] = time.perf_counter() - tic
    first_loss = float(loss)
    # the routing of the warm-up step: the gauge it sets is in both of the
    # window's snapshots, which is where its reader finds it
    moe.record_routing(jax.device_get(counts))
    base._describe(ctx, executable)

    compiles_before = ctx.compile_events.counts.get(
        observe.CompileEvents.COMPILE, 0)

    def enqueue(state):
        with ctx.spans.span("make_batch"):
            batch = next(batches)
        called = time.perf_counter()
        with ctx.spans.span("step_call"):
            state, loss, counts = train_step(state, batch)
        return state, (loss, counts, called, time.perf_counter())

    def run_steps(state, more):
        """Steps while ``more(steps enqueued)``, each enqueued ONE AHEAD:
        the host makes the next batch and calls the next step while the
        device runs the last, and only then waits for the last, as a
        training loop does.  (``train.py`` waits for every step before it
        calls the next, so its 12 ms of dispatch are idle time of the
        chip; at this cell's 0.14 s a step that idle time, 2 to 3 ms by
        the host's mood, moved the throughput by 1.4 % between processes:
        PERF.md, PR 26.)  A step's times are (called, returned, done),
        done when the host saw its loss ready; its state is by then the
        next step's donated input."""
        steps, out, enqueued = [], [], 1
        state, ahead = enqueue(state)
        while ahead is not None:
            loss, counts, called, returned = ahead
            ahead = None
            if more(enqueued):
                state, ahead = enqueue(state)
                enqueued += 1
            with ctx.spans.span("step_wait"):
                jax.block_until_ready(loss)
            steps.append((called, returned, time.perf_counter()))
            out.append((loss, counts))
        return state, steps, out

    registry = tmetrics.get_registry()
    setup_s = observe.seconds_since_process_start()
    counters_t0 = registry.snapshot()
    window_t0_us = ttrace.now_us()
    t0 = time.perf_counter()
    # the step that is under way when the time is up is the window's last
    state, steps, out = run_steps(
        state, lambda _: time.perf_counter() - t0 < ctx.seconds)
    steps = [tuple(t - t0 for t in times) for times in steps]
    losses = [loss for loss, _ in out]
    window_t1_us = ttrace.now_us()
    counters = (counters_t0, registry.snapshot())
    compiles_in_window = ctx.compile_events.counts.get(
        observe.CompileEvents.COMPILE, 0) - compiles_before
    memory = observe.device_memory(jax.local_devices())
    ctx.info({"info": "steps", "ms": [
        [round((ret - call) * 1e3, 1), round((done - call) * 1e3, 1)]
        for call, ret, done in steps]})

    # the capture: a few more steps straight after the window
    trace, traced_steps, expert_trace = None, [], {}
    if ctx.trace:
        trace = program.DeviceTrace(ctx)
        if ctx.trace == 2:
            trace.warm_up()
        trace.start()
        state, traced_steps, out = run_steps(
            state, lambda enqueued: enqueued < mix["trace_steps"])
        with ctx.spans.span("record_routing"):
            for _, counts in out:
                moe.record_routing(jax.device_get(counts))
        trace.stop()
        losses += [loss for loss, _ in out]
        ctx.info({"info": "traced_steps",
                  "done_s": [t[2] - traced_steps[0][0]
                             for t in traced_steps],
                  "call_s": [t[1] - t[0] for t in traced_steps]})
        try:
            expert_trace = read_expert_trace(
                trace.dir, executable.get_hlo_text(), moe.SCOPE,
                MATMUL_SCOPE)
        except (FileNotFoundError, ValueError):
            if not ctx.rehearsal:    # a CPU trace has no TPU plane
                raise
        ctx.info({"info": "expert_trace", **expert_trace})
    memory_run = observe.device_memory(jax.local_devices())

    losses = [float(x) for x in losses]
    finite = [math.isfinite(x) for x in [first_loss] + losses]
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    falls = (len(losses) >= 3 and
             sum(losses[-3:]) / 3 < first_loss)
    checks = {"first_loss": first_loss, "reference_loss": ref_loss,
              "rel_diff": rel, "rtol": config["loss_rtol"],
              "matches_reference": rel <= config["loss_rtol"],
              "positions_match": position_checks["ok"],
              "all_finite": all(finite), "falls": falls,
              "last_losses": losses[-3:],
              "compiles_in_window": compiles_in_window}
    tokens_per_step = mix["batch"] * gcfg.seq_len
    return {
        "correct": bool(checks["matches_reference"] and
                        checks["positions_match"] and all(finite) and
                        falls and compiles_in_window == 0),
        "attempted": len(steps),
        "failed": sum(not ok for ok in finite[1:len(steps) + 1]),
        "checks": checks,
        "setup_s": setup_s,
        "timers": timers,
        "steps": steps,
        "tokens_per_step": tokens_per_step,
        "train_flops_per_token":
            arithmetic_moe.moe_decoder_train_flops_per_token(
                gcfg.hidden_size, gcfg.num_layers, gcfg.seq_len,
                gcfg.vocab_size, gcfg.mlp_width, gcfg.num_experts,
                gcfg.num_experts_per_tok),
        # the grouped matmuls' work in the traced steps, and their time
        "grouped_matmul_work": tuple(
            gcfg.num_layers * len(traced_steps) * x
            for x in arithmetic_moe.expert_grouped_matmul_work(
                tokens_per_step, gcfg.hidden_size, gcfg.mlp_width,
                gcfg.num_experts, gcfg.num_experts_per_tok,
                jnp.dtype(gcfg.dtype).itemsize)),
        "expert_trace": expert_trace,
        "program_spans": setup_spans + trace.program_spans() if trace
        else setup_spans,
        "program_window_us": (window_t0_us, window_t1_us),
        "counters": counters,
        "memory": memory,
        "memory_run": memory_run,
        "device_trace": trace.summary() if trace else None,
        # what the readers of spans see in place of the window's
        "traced": {"program_window_us": trace.interval_us}
        if ctx.trace == 2 else {},
    }


def _compare_positions(config, ref_losses, ref_experts, got_losses,
                       got_experts) -> dict:
    """The per-position check.  Losses are (sequences, S); experts are
    (layers, sequences, S, k)."""
    k = ref_experts.shape[-1]
    got_sorted, ref_sorted = np.sort(got_experts, -1), np.sort(ref_experts, -1)
    # how many of a token's k experts are the reference's
    common = (got_sorted[..., :, None] == ref_sorted[..., None, :]).any(-1)
    agreement = float(common.mean())
    same = (got_sorted == ref_sorted).all(-1).all(0)       # (sequences, S)
    diff = np.abs(np.asarray(got_losses, np.float64) - ref_losses)
    worst_same = float(diff[same].max()) if same.any() else 0.0
    worst_flipped = float(diff[~same].max()) if (~same).any() else 0.0
    ok = (worst_same <= config["position_atol"] and
          worst_flipped <= config["position_atol_flipped"] and
          float(diff.mean()) <= config["position_mean_atol"] and
          agreement >= config["min_choice_agreement"] and
          bool(np.isfinite(diff).all()))
    return {"ok": bool(ok), "positions": int(diff.size),
            "choices_per_token": int(k), "choice_agreement": agreement,
            "positions_with_a_flip": int((~same).sum()),
            "worst_abs_diff_same_experts": worst_same,
            "worst_abs_diff_flipped": worst_flipped,
            "mean_abs_diff": float(diff.mean()),
            "position_atol": config["position_atol"],
            "position_atol_flipped": config["position_atol_flipped"],
            "position_mean_atol": config["position_mean_atol"],
            "min_choice_agreement": config["min_choice_agreement"]}
