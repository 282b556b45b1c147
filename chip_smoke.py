"""Smoke run of alpa_tpu's main paths on the TPU: the quickest proof that
the system still starts on the chip.

    python chip_smoke.py              # one chip: flash check, GPT train step,
                                      #           OPT serving over HTTP
    python chip_smoke.py --chips 4    # four chips: 2-stage pipeshard on two
                                      #   1x2 submeshes vs ShardParallel on the
                                      #   4-chip mesh, and nothing else

Every phase runs through the entry points a user would call
(``@alpa_tpu.parallelize``, ``alpa_tpu.serve.get_model`` + ``run_controller``)
at published widths — GPT 1.3B (hidden 2048, 32 heads, vocab 51200, seq
1024, bf16) with depth as the only cut, OPT-1.3B at full depth — with
weights from ``--seed``.  Each phase prints one JSON line; any phase that
fails makes the script exit non-zero.  The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and it is printed only when every phase ran on a TPU and passed.  Without
an accelerator the script exits non-zero and prints no such line.

This parent process never imports jax: a process that has touched jax
holds the chip.  Each phase is a child process (``--phase NAME``), run one
at a time, so the train state is gone from the device before the serve
phase allocates.  The numbers printed are smoke output, not benchmark
results.

``--tiny`` is the CPU rehearsal: the same control flow at toy sizes, on
whatever backend jax finds; its last line carries ``"rehearsal": true``.
"""
import argparse
import json
import os
import subprocess
import sys
import time

# Depth is the only cut from the published GPT 1.3B config (24 layers):
# fp32 params + fp32 adam at depth 24 are ~15.8 GB and cannot fit 16 GB.
# Settled from compiled.memory_analysis() for the described v5e (see
# CHANGES.md, PR 22).
TRAIN_DEPTH = 18
PIPESHARD_DEPTH = 14
TRAIN_BATCH = 8
TRAIN_STEPS = 6
PIPESHARD_STEPS = 3
# bf16 activations: the two 4-chip programs split the batch and order
# their reductions differently; losses are O(10) and agree to this
LOSS_RTOL = 2e-2
# flash vs reference attention in bf16, relative to the largest |reference|
FLASH_TOL = 2e-2
PHASE_TIMEOUT_S = 1000

ONE_CHIP_PHASES = ("train", "serve")
FOUR_CHIP_PHASES = ("pipeshard", "shard4")


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _require(ok, message):
    """A phase's check.  Not ``assert``: ``python -O`` must not turn the
    smoke into a run that checks nothing."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {message}")


########################################
# children: everything below imports jax
########################################


class _Setup:
    """Seconds spent tracing, lowering and compiling, from jax's own
    monitoring events, plus persistent compile-cache hits and misses."""

    def __init__(self):
        import jax.monitoring
        self.seconds = {}
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        self.seconds[event] = self.seconds.get(event, 0.0) + secs

    def _evt(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def reset(self):
        self.seconds.clear()
        self.counts.clear()

    def report(self):
        s, c = self.seconds, self.counts
        return {
            "jaxpr_trace_s": round(
                s.get("/jax/core/compile/jaxpr_trace_duration", 0.0), 2),
            "lower_to_mlir_s": round(s.get(
                "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.0), 2),
            "xla_compile_s": round(
                s.get("/jax/core/compile/backend_compile_duration", 0.0), 2),
            "compile_cache_hits": c.get(
                "/jax/compilation_cache/cache_hits", 0),
            "compile_cache_misses": c.get(
                "/jax/compilation_cache/cache_misses", 0),
        }


def _start(tiny: bool, n_chips: int):
    """Common child set-up: the compile cache, the device check, the
    set-up clock.  Returns (device dict, _Setup, compile cache dir)."""
    import jax
    from alpa_tpu.platform import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    setup = _Setup()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not tiny:
        if device["platform"] != "tpu":
            sys.exit(f"chip_smoke: needs a TPU, jax found {device}")
        if device["count"] != n_chips:
            sys.exit(f"chip_smoke: this phase needs {n_chips} chip(s), "
                     f"jax found {device}")
    return device, setup, cache_dir


def _memory(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def _gpt_config(depth: int, tiny: bool, boundary_every: int = 0):
    import dataclasses
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import GPTConfig, config_from_spec
    if tiny:
        return GPTConfig(hidden_size=64, num_layers=depth, num_heads=4,
                         seq_len=64, vocab_size=256, dtype=jnp.bfloat16,
                         remat_blocks=True,
                         pipeline_boundary_every=boundary_every)
    # published 1.3B widths: hidden 2048, 32 heads, vocab 51200, seq 1024
    return dataclasses.replace(
        config_from_spec("1.3B", dtype=jnp.bfloat16,
                         attention_impl="reference", remat_blocks=True,
                         pipeline_boundary_every=boundary_every),
        num_layers=depth)


def _build_train(method, config, batch_size, seed):
    """The GPT train step under ``method``, and what it runs on:
    (train_step, create_state, batch, abstract (state, batch))."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.model.gpt_model import GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss

    model = GPTModel(config)
    k_ids, k_labels, k_init = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch_size, config.seq_len)
    batch = {
        "input_ids": np.asarray(jax.random.randint(
            k_ids, shape, 0, config.vocab_size)),
        "labels": np.asarray(jax.random.randint(
            k_labels, shape, 0, config.vocab_size)),
    }

    # one optimizer object: it is part of the state's tree structure, and
    # a second one would miss the executable cache and compile again
    tx = optax.adam(1e-4)

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

    abstract = (jax.eval_shape(create_state),
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
    return train_step, create_state, batch, abstract


def _train(method, config, batch_size, n_steps, seed, setup):
    """Compile the train step from shapes, create the state already
    placed (CreateStateParallel), run ``n_steps`` after one warm-up, each
    ended by block_until_ready.  Returns (executable, numbers); the memory
    of every local device is read while the state is still alive."""
    import jax
    import numpy as np

    import alpa_tpu
    from alpa_tpu.create_state_parallel import CreateStateParallel
    from alpa_tpu.telemetry import trace as ttrace

    setup.reset()
    train_step, create_state, batch, abstract = _build_train(
        method, config, batch_size, seed)

    # compile from shapes; the alpa_tpu spans of category "compile" time
    # the ILP and the stage construction inside it
    ttrace.set_enabled(True)
    tic = time.perf_counter()
    executable, _ = train_step.get_executable(*abstract)
    compile_s = time.perf_counter() - tic
    spans = {}
    for s in ttrace.get_recorder().spans():
        if s["category"] == "compile":
            spans[s["name"]] = round(
                spans.get(s["name"], 0.0) + s["dur_us"] / 1e6, 2)
    ttrace.set_enabled(False)

    tic = time.perf_counter()
    state = alpa_tpu.parallelize(
        create_state, method=CreateStateParallel(train_step, abstract),
        batch_argnums=())()
    jax.block_until_ready(state)
    init_s = time.perf_counter() - tic
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(state.params))

    losses, step_s = [], []
    for _ in range(n_steps + 1):        # the first step is the warm-up
        tic = time.perf_counter()
        state, loss = train_step(state, batch)
        jax.block_until_ready((state, loss))
        step_s.append(round(time.perf_counter() - tic, 4))
        losses.append(float(loss))
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")

    return executable, {
        "model": {"hidden": config.hidden_size, "heads": config.num_heads,
                  "vocab": config.vocab_size, "seq": config.seq_len,
                  "depth": config.num_layers, "batch": batch_size,
                  "dtype": str(np.dtype(config.dtype)),
                  "params": n_params},
        "setup": {"compile_total_s": round(compile_s, 2),
                  "alpa_compile_spans_s": spans, **setup.report(),
                  "state_init_s": round(init_s, 2)},
        "warmup_step_s": step_s[0], "step_s": step_s[1:],
        "losses": [round(x, 4) for x in losses],
        "memory": _memory(jax.local_devices()),
    }


def _flash_check(tiny: bool, seed: int):
    """The attention core the shapes choose (``gpt_model.attention``),
    fwd+bwd, against ``reference_attention``.  On the chip the Pallas
    kernels are compiled: the program must hold two ``tpu_custom_call``s
    (forward, backward); the tiny shapes do not fit them."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import attention, reference_attention

    shape = (2, 128, 2, 64) if tiny else (8, 1024, 32, 64)
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kx, shape, jnp.bfloat16)
                  for kx in (kq, kk, kv, kw))

    def run(attn):
        # w is an argument: a closed-over array would be baked into the
        # program as a 32 MB constant (and into its compile-cache entry)
        def f(q, k, v, w):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * w).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = run(lambda q, k, v: attention(q, k, v, causal=True))
    ref = run(lambda q, k, v: reference_attention(q, k, v, causal=True))
    n_kernels = flash.lower(q, k, v, w).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')
    on_tpu = jax.devices()[0].platform == "tpu"
    _require(n_kernels == (2 if on_tpu else 0),
             f"{n_kernels} compiled Pallas kernels in the flash program")
    tic = time.perf_counter()
    (_, out_f), grads_f = jax.block_until_ready(flash(q, k, v, w))
    flash_s = time.perf_counter() - tic
    (_, out_r), grads_r = jax.block_until_ready(ref(q, k, v, w))
    errs = {}
    for name, a, b in (("out", out_f, out_r), ("dq", grads_f[0], grads_r[0]),
                       ("dk", grads_f[1], grads_r[1]),
                       ("dv", grads_f[2], grads_r[2])):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        _require(bool(jnp.isfinite(a).all()), f"flash {name} not finite")
        errs[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        _require(errs[name] < FLASH_TOL, f"flash {name} off: {errs}")
    return {"shape": list(shape), "pallas_kernels_compiled": n_kernels,
            "max_rel_err_vs_reference": {k: round(v, 5)
                                         for k, v in errs.items()},
            "tolerance": FLASH_TOL, "first_call_s": round(flash_s, 2)}


def phase_train(args):
    import alpa_tpu
    device, setup, cache_dir = _start(args.tiny, 1)
    _emit({"phase": "flash", "device": device,
           **_flash_check(args.tiny, args.seed)})
    alpa_tpu.init(cluster="local")
    config = _gpt_config(2 if args.tiny else TRAIN_DEPTH, args.tiny)
    _, result = _train(alpa_tpu.ShardParallel(), config,
                       2 if args.tiny else TRAIN_BATCH, TRAIN_STEPS,
                       args.seed, setup)
    _emit({"phase": "train", "device": device, "method": "ShardParallel",
           "compile_cache_dir": cache_dir, **result})


def phase_serve(args):
    """get_model("opt-1.3b") on run_controller; POST /completions over
    HTTP (one streamed) against a direct Generator call in-process."""
    import urllib.request
    import jax
    import numpy as np
    from alpa_tpu.model.gpt_model import GPTConfig
    from alpa_tpu.serve import get_model, run_controller
    from alpa_tpu.serve.generation import GenerationConfig

    device, setup, cache_dir = _start(args.tiny, 1)
    # The streamed path decodes with the engine's own program, the direct
    # call with Generator's.  At the TPU's default precision an fp32
    # matmul is one bf16 pass, and with random weights the two programs
    # then break a near-tie in the logits differently (seen on the chip,
    # PR 22: same ids up to the 6th new token).  Full fp32 precision makes
    # "equal greedy ids" a statement about the serving path and not about
    # rounding.
    jax.config.update("jax_default_matmul_precision", "highest")
    name = "opt-1.3b"
    spec = (GPTConfig(hidden_size=64, num_layers=2, num_heads=4, seq_len=128,
                      vocab_size=256) if args.tiny else name)
    tic = time.perf_counter()
    gen = get_model(spec, rngkey=jax.random.PRNGKey(args.seed))
    jax.block_until_ready(gen.params)
    load_s = time.perf_counter() - tic
    cfg = gen.config
    if not args.tiny:   # full depth and the published widths
        _require((cfg.hidden_size, cfg.num_layers, cfg.num_heads,
                  cfg.seq_len, cfg.vocab_size) == (2048, 24, 32, 2048, 50272),
                 f"not the published opt-1.3b config: {cfg}")

    server = run_controller(port=0)
    try:
        server.controller.register_model(name, gen)
        url = f"http://127.0.0.1:{server.port}/completions"
        rs = np.random.RandomState(args.seed)
        new_tokens = 8
        requests = []
        for n_prompt, stream in ((8, False), (21, False), (13, True)):
            prompt = rs.randint(4, cfg.vocab_size, (n_prompt,)).tolist()
            tic = time.perf_counter()
            want = np.asarray(gen.generate(
                np.asarray(prompt, np.int32)[None],
                GenerationConfig(max_new_tokens=new_tokens)))[0].tolist()
            direct_s = time.perf_counter() - tic
            body = {"model": name, "prompt_ids": prompt,
                    "max_new_tokens": new_tokens}
            if stream:
                body["stream"] = True
            req = urllib.request.Request(url, data=json.dumps(body).encode())
            tic = time.perf_counter()
            with urllib.request.urlopen(req, timeout=600) as resp:
                if stream:
                    got = list(prompt)
                    for raw in resp:
                        line = raw.decode().strip()
                        if line.startswith("data: "):
                            event = json.loads(line[6:])
                            _require("error" not in event, event)
                            if "token" in event:
                                got.append(event["token"])
                else:
                    got = json.load(resp)["output_ids"][0]
            http_s = time.perf_counter() - tic
            _require(len(got) == n_prompt + new_tokens, got)
            _require(got == want,
                     f"HTTP (stream={stream}) and the direct Generator call "
                     f"disagree: {got} vs {want}")
            requests.append({"prompt_tokens": n_prompt, "stream": stream,
                             "new_token_ids": got[n_prompt:],
                             "direct_s": round(direct_s, 2),
                             "http_s": round(http_s, 2)})
    finally:
        server.shutdown()
    _emit({"phase": "serve", "device": device, "model": name,
           "config": {"hidden": cfg.hidden_size, "depth": cfg.num_layers,
                      "heads": cfg.num_heads, "seq": cfg.seq_len,
                      "vocab": cfg.vocab_size,
                      "dtype": str(np.dtype(cfg.dtype))},
           "matmul_precision": "highest", "compile_cache_dir": cache_dir,
           "setup": {"get_model_s": round(load_s, 2), **setup.report()},
           "requests": requests, "http_equals_direct": True,
           "memory": _memory(jax.local_devices())})


def _stage_dp_solver():
    """Which stage-DP solver this checkout gets: the C++ one built by
    ``make`` at first use, or the Python one (no compiler, or no .so)."""
    import numpy as np
    from alpa_tpu.pipeline_parallel import stage_dp
    native = stage_dp._load_native() is not None
    if not native:
        print("chip_smoke: warning: libstage_dp.so could not be built or "
              "loaded; the Python stage-DP solver is used",
              file=sys.stderr, flush=True)
    # one small solve through whichever solver was found: 4 layers on 4
    # devices, submeshes of 1, 2 or 4
    rs = np.random.RandomState(0)
    costs = np.cumsum(np.cumsum(rs.rand(4, 4, 3) + 0.1, axis=1), axis=0)
    tic = time.perf_counter()
    stage_dp.stage_dp_solve(costs, [1, 2, 4], 4, 4)
    return {"solver": "native" if native else "python",
            "small_solve_s": round(time.perf_counter() - tic, 4)}


def phase_four_chips(args):
    """One of the two programs of the four-chip comparison
    (``args.phase``: "pipeshard" or "shard4")."""
    import jax
    import alpa_tpu
    from alpa_tpu.pipeline_parallel.layer_construction import (
        ManualLayerOption)
    from alpa_tpu.pipeline_parallel.stage_construction import (
        UniformStageOption)

    name = args.phase
    device, setup, cache_dir = _start(args.tiny, 4)
    alpa_tpu.init("local")
    depth = 2 if args.tiny else PIPESHARD_DEPTH
    extra = {}
    if name == "pipeshard":
        extra["stage_dp"] = _stage_dp_solver()
        # two layers of depth/2 blocks -> 2 stages on two 1x2 submeshes
        config = _gpt_config(depth, args.tiny, boundary_every=depth // 2)
        method = alpa_tpu.PipeshardParallel(
            num_micro_batches=4, pipeline_schedule="1f1b",
            layer_option=ManualLayerOption(),
            stage_option=UniformStageOption(num_stages=2))
    else:
        config = _gpt_config(depth, args.tiny)
        method = alpa_tpu.ShardParallel()
    executable, result = _train(
        method, config, 8 if args.tiny else TRAIN_BATCH, PIPESHARD_STEPS,
        args.seed, setup)
    if name == "pipeshard":
        stage_devices = [sorted(d.id for d in m.flat_devices)
                         for m in executable.mesh_group.meshes]
        extra["stage_devices"] = stage_devices
        _require(len(stage_devices) == 2 and
                 not set(stage_devices[0]) & set(stage_devices[1]) and
                 sorted(sum(stage_devices, [])) == sorted(
                     d.id for d in jax.local_devices()),
                 f"stages do not split the four devices: {stage_devices}")
    if device["platform"] == "tpu":     # the CPU backend reports no stats
        _require(all(m["bytes_in_use"] for m in result["memory"]),
                 f"a device holds no share: {result['memory']}")
    _emit({"phase": name, "device": device,
           "method": type(method).__name__,
           "compile_cache_dir": cache_dir, **extra, **result})


PHASES = {"train": phase_train, "serve": phase_serve,
          "pipeshard": phase_four_chips, "shard4": phase_four_chips}


########################################
# parent: never imports jax
########################################


def _run_phase(name: str, args) -> list:
    """Run one phase as a child; echo its stdout; return its JSON lines.
    A child that fails, or outlives its limit, ends the run non-zero."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--seed", str(args.seed), "--chips", str(args.chips)]
    if args.tiny:
        cmd.append("--tiny")
    env = dict(os.environ)
    if args.tiny and args.chips == 4:
        # the rehearsal of the four-chip path runs on virtual CPU devices
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
    tic = time.time()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    lines = []
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        sys.exit(f"chip_smoke: phase {name} exceeded {PHASE_TIMEOUT_S} s")
    for line in out.splitlines():
        print(line, flush=True)
        if line.startswith("{"):
            try:
                lines.append(json.loads(line))
            except ValueError:
                pass
    if proc.returncode != 0:
        sys.exit(f"chip_smoke: phase {name} failed (exit code "
                 f"{proc.returncode}) after {time.time() - tic:.0f} s")
    mine = [x for x in lines if x.get("phase") == name]
    if len(mine) != 1:
        sys.exit(f"chip_smoke: phase {name} printed no result line")
    return lines


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal at toy sizes")
    parser.add_argument("--phase", default=None,
                        choices=tuple(PHASES),
                        help="internal: run one phase in this process")
    args = parser.parse_args()
    if args.phase:
        PHASES[args.phase](args)
        return 0

    tic = time.time()
    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    results = []
    for name in phases:
        results += _run_phase(name, args)
    devices = [r["device"] for r in results]
    device = devices[0]
    if any(d != device for d in devices):
        sys.exit(f"chip_smoke: phases ran on different devices: {devices}")
    if not args.tiny and (device["platform"] != "tpu" or
                          device["count"] != args.chips):
        sys.exit(f"chip_smoke: expected {args.chips} TPU chip(s), "
                 f"ran on {device}")
    if args.chips == 4:
        by_phase = {r["phase"]: r for r in results}
        pipe, shard = (by_phase[p]["losses"] for p in FOUR_CHIP_PHASES)
        rel = [abs(a - b) / max(abs(b), 1e-9) for a, b in zip(pipe, shard)]
        agree = max(rel) <= LOSS_RTOL
        _emit({"phase": "compare", "pipeshard_losses": pipe,
               "shard_parallel_losses": shard,
               "max_rel_diff": round(max(rel), 5), "rtol": LOSS_RTOL,
               "agree": agree})
        if not agree:
            sys.exit("chip_smoke: pipeshard and ShardParallel losses "
                     "disagree")
    _emit({"phase": "total", "seconds": round(time.time() - tic, 1)})
    last = {"ok": True, "device": device}
    if args.tiny:
        last["rehearsal"] = True
    _emit(last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
