"""Driver of the training cells: one ``@alpa_tpu.parallelize`` train step of
``GPTModel`` under the method the configuration names, fed a fresh batch
from the host at every step until ``--seconds`` have passed.

Order of a run: plan and compile from shapes (``get_executable``); create
the state already placed (``CreateStateParallel``); the plain reference's
loss on the state's own parameters and the first batch; one warm-up step on
that batch, whose loss is compared with the reference's; the window; in a
traced run (``--trace 1`` or ``2``), ``trace_steps`` more steps inside a
capture of the program (its spans and jax's profiler).

The program's own spans are on while the step is planned and compiled (the
planner's ``compile`` spans are what ``plan_s`` reads) and off from the
warm-up step on, in ``--trace 0`` and ``--trace 2`` alike; ``--trace 1``
keeps them on through its window, as it always has.
"""
import math
import time

from chipbench import arithmetic, observe, program, traffic


def _method(config, mix):
    import alpa_tpu
    par = config["train"]["parallel"]
    if par["method"] == "shard":
        return alpa_tpu.ShardParallel(), 0
    if par["method"] == "pipeshard":
        from alpa_tpu.pipeline_parallel.layer_construction import (
            ManualLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        stages = par["stages"]
        method = alpa_tpu.PipeshardParallel(
            num_micro_batches=mix["micro_batches"],
            pipeline_schedule=par["schedule"],
            layer_option=ManualLayerOption(),
            stage_option=UniformStageOption(num_stages=stages))
        return method, config["num_hidden_layers"] // stages
    raise ValueError(f"unknown parallel method {par['method']!r}")


def _gather_to(params, device):
    """The state's parameters on one device, for the reference."""
    import jax
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, device),
                                  params)


def _emptiest(devices):
    def in_use(d):
        return (d.memory_stats() or {}).get("bytes_in_use", 0)
    return min(devices, key=in_use)


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.create_state_parallel import CreateStateParallel
    from alpa_tpu.model.gpt_model import GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace

    config, mix = ctx.config, ctx.mix
    knobs = config["train"]
    alpa_tpu.init(cluster="local")
    method, boundary_every = _method(config, mix)
    gcfg = program.gpt_config(
        config, attention_impl=knobs["attention_impl"],
        remat_blocks=knobs["remat_blocks"],
        pipeline_boundary_every=boundary_every)
    model = GPTModel(gcfg)
    shape = (mix["batch"], gcfg.seq_len)
    k_init = program.key_from_seed(ctx.seed)
    # one optimizer object: it is part of the state's tree structure, and a
    # second one would miss the executable cache and compile again (PR 22)
    tx = optax.adam(knobs["learning_rate"])

    def create_state():
        params = model.init(k_init, jnp.ones(shape, jnp.int32))
        return train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx)

    @alpa_tpu.parallelize(method=method, static_argnums=(),
                          donate_argnums=(0,))
    def train_step(state, batch):

        def loss_fn(p):
            return gpt_lm_loss(state.apply_fn, p, batch)

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

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

    # the reference, on the state's own parameters, before the first step
    # donates them
    tic = time.perf_counter()
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(program.reference_settings(config))
    spot = _emptiest(jax.local_devices())
    weights = ref_mod.weights_from_program(_gather_to(state.params, spot))
    ref_loss = reference.lm_loss(weights,
                                 jax.device_put(first["input_ids"], spot),
                                 jax.device_put(first["labels"], spot))
    del weights
    timers["reference_s"] = time.perf_counter() - tic

    # ... and off from here on, but in a traced run of its own
    ttrace.set_enabled(ctx.trace == 1)
    setup_spans = ttrace.get_recorder().spans()
    ttrace.get_recorder().clear()
    tic = time.perf_counter()
    state, loss = train_step(state, first)
    jax.block_until_ready((state, loss))
    timers["warmup_step_s"] = time.perf_counter() - tic
    first_loss = float(loss)
    _describe(ctx, executable)

    compiles_before = ctx.compile_events.counts.get(
        observe.CompileEvents.COMPILE, 0)
    steps, losses = [], []

    def one_step(state):
        with ctx.spans.span("make_batch"):
            batch = next(batches)
        called = time.perf_counter()
        with ctx.spans.span("step_call"):
            state, loss = train_step(state, batch)
        returned = time.perf_counter()
        with ctx.spans.span("step_wait"):
            jax.block_until_ready((state, loss))
        return state, loss, (called, returned, time.perf_counter())

    registry = tmetrics.get_registry()
    setup_s = observe.seconds_since_process_start()
    counters_t0 = registry.snapshot()
    window_t0_us = ttrace.now_us()
    t0 = time.perf_counter()
    while not steps or steps[-1][2] < ctx.seconds:
        state, loss, times = one_step(state)
        steps.append(tuple(t - t0 for t in times))
        losses.append(loss)
    window_t1_us = ttrace.now_us()
    counters = (counters_t0, registry.snapshot())
    compiles_in_window = ctx.compile_events.counts.get(
        observe.CompileEvents.COMPILE, 0) - compiles_before
    memory = observe.device_memory(jax.local_devices())
    ctx.info({"info": "dispatch", "mode": (getattr(
        executable, "last_dispatch_stats", None) or {}).get("mode")})
    # every step of the window [call to return, call to done], for the
    # record: a host that was slow for a while shows here
    ctx.info({"info": "steps", "ms": [
        [round((ret - call) * 1e3, 1), round((done - call) * 1e3, 1)]
        for call, ret, done in steps]})

    # the capture: a few more steps straight after the window, so that
    # the profiler's own cost is in none of the window's host timings
    trace, traced_steps = None, []
    if ctx.trace:
        trace = program.DeviceTrace(ctx)
        if ctx.trace == 2:
            trace.warm_up()
        trace.start()
        for _ in range(mix["trace_steps"]):
            state, loss, times = one_step(state)
            traced_steps.append(tuple(t - t0 for t in times))
            losses.append(loss)
        trace.stop()
        # a step inside a capture against one outside: what tracing costs
        ctx.info({"info": "traced_steps",
                  "step_s": [t[2] - t[0] for t in traced_steps],
                  "call_s": [t[1] - t[0] for t in traced_steps]})
    memory_run = observe.device_memory(jax.local_devices())

    losses = [float(x) for x in losses]
    finite = [math.isfinite(x) for x in [first_loss] + losses]
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    falls = (len(losses) >= 3 and
             sum(losses[-3:]) / 3 < first_loss)
    checks = {"first_loss": first_loss, "reference_loss": ref_loss,
              "rel_diff": rel, "rtol": config["loss_rtol"],
              "matches_reference": rel <= config["loss_rtol"],
              "all_finite": all(finite), "falls": falls,
              "last_losses": losses[-3:],
              "compiles_in_window": compiles_in_window}
    return {
        "correct": bool(checks["matches_reference"] and all(finite) and
                        falls and compiles_in_window == 0),
        "attempted": len(steps),
        "failed": sum(not ok for ok in finite[1:len(steps) + 1]),
        "checks": checks,
        "setup_s": setup_s,
        "timers": timers,
        "steps": steps,
        "tokens_per_step": mix["batch"] * gcfg.seq_len,
        "train_flops_per_token": arithmetic.decoder_train_flops_per_token(
            gcfg.hidden_size, gcfg.num_layers, gcfg.seq_len,
            gcfg.vocab_size),
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


def _describe(ctx, executable):
    """Lines for the record, before the result line: where the stages sit,
    and what the compiler says the step program needs."""
    meshes = getattr(getattr(executable, "mesh_group", None), "meshes", None)
    if meshes:
        ctx.info({"info": "stage_devices",
                  "stages": [sorted(d.id for d in m.flat_devices)
                             for m in meshes]})
    compiled = getattr(executable, "compiled", None)
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
            ctx.info({"info": "compiled_memory",
                      "argument_bytes": ma.argument_size_in_bytes,
                      "output_bytes": ma.output_size_in_bytes,
                      "alias_bytes": ma.alias_size_in_bytes,
                      "temp_bytes": ma.temp_size_in_bytes})
        except Exception as e:  # pylint: disable=broad-except
            ctx.info({"info": "compiled_memory", "error": repr(e)})
