"""Profile-backed breakdown of where the bench step's time goes
(VERDICT r4 next #2: "measured >=45% of peak OR a written
profile-backed breakdown of exactly where the remaining time goes").

Times nested sub-programs of the official bench config on the chip —
pure dominant-shape matmuls (the achievable-MXU ceiling), forward
only, forward+backward, the full train step, and the lm-head+CE leg —
each in a child process of its own (the parent never touches jax), one
at a time, ended by a scalar readback.  The
differences attribute step time to forward / backward / optimizer /
logits+CE, and the pure-matmul ceiling separates "XLA didn't reach
peak on these shapes" from "the model adds overhead".

Writes benchmark/results/mfu_breakdown.json.  The peak-FLOPs framing
and the leg attribution math live in scripts/perf_tool.py /
alpa_tpu.telemetry.perf (ISSUE 9: one MFU formula) — this script only
runs the timed legs.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.perf_tool import attribute_legs, mfu_summary  # noqa: E402

_CHILD = r'''
import json, sys, time
sys.path.insert(0, "__REPO__")
import jax, jax.numpy as jnp, optax
import numpy as np
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import gpt_lm_loss
from alpa_tpu.util import compute_gpt_tflops

leg = "__LEG__"
config = GPTConfig(hidden_size=2048, num_layers=16, num_heads=32,
                   seq_len=1024, vocab_size=51200, dtype=jnp.bfloat16,
                   attention_impl="reference", remat_blocks=True)
B = 8

def timeit(fn, *args, iters=8):
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: None, out)
    # scalar readback: the work has finished
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32))
          if hasattr(jax.tree_util.tree_leaves(out)[0], 'astype')
          else 0.0)
    tic = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]
                  .astype(jnp.float32)))
    return (time.perf_counter() - tic) / iters

if leg == "matmul_ceiling":
    # the model's dominant shapes: qkv (2048x6144), mlp (2048x8192 and
    # 8192x2048), attention batch dots; all bf16
    tokens = B * config.seq_len
    x = jnp.ones((tokens, 2048), jnp.bfloat16)
    w1 = jnp.ones((2048, 8192), jnp.bfloat16)
    w2 = jnp.ones((8192, 2048), jnp.bfloat16)

    @jax.jit
    def mm(x):
        for _ in range(8):
            x = (x @ w1) @ w2
        return x

    t = timeit(mm, x)
    flops = 8 * 2 * (tokens * 2048 * 8192 + tokens * 8192 * 2048)
    print(json.dumps({"leg": leg, "s": t,
                      "tflops": flops / t / 1e12}))
    sys.exit(0)

model = GPTModel(config)
rng = jax.random.PRNGKey(0)
ids = jnp.zeros((B, config.seq_len), jnp.int32)
params = model.init(rng, ids)
batch = dict(input_ids=ids, labels=ids)
tx = optax.adam(1e-4)
opt_state = tx.init(params)

def loss_fn(p):
    return gpt_lm_loss(model.apply, p, batch)

if leg == "forward":
    f = jax.jit(loss_fn)
    t = timeit(f, params)
elif leg == "forward_hidden":
    # forward WITHOUT the lm head + CE (return_hidden mean as sink)
    @jax.jit
    def fh(p):
        h = model.apply(p, ids, return_hidden=True)
        return jnp.mean(h.astype(jnp.float32))
    t = timeit(fh, params)
elif leg == "fwd_bwd":
    g = jax.jit(lambda p: jax.value_and_grad(loss_fn)(p)[0])
    t = timeit(g, params)
elif leg == "train_step":
    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def run(p, o):
        p, o, loss = step(p, o)
        return loss
    t = timeit(run, params, opt_state)
    tfl = compute_gpt_tflops(B, config.seq_len, config.num_layers,
                             config.hidden_size, config.vocab_size, 1, t)
    print(json.dumps({"leg": leg, "s": t, "tflops_per_chip": tfl}))
    sys.exit(0)
else:
    raise SystemExit("unknown leg " + leg)
print(json.dumps({"leg": leg, "s": t}))
'''


def _child_src(leg: str) -> str:
    return _CHILD.replace("__REPO__", REPO).replace("__LEG__", leg)

LEGS = ["matmul_ceiling", "forward_hidden", "forward", "fwd_bwd",
        "train_step"]


def main():
    out_path = os.path.join(REPO, "benchmark", "results",
                            "mfu_breakdown.json")
    results = {}

    def flush(attribution=None):
        """Write after EVERY leg: an outer timeout must not discard
        completed legs."""
        peak = mfu_summary(0.0)
        report = {"config": "h2048-l16 bs8 seq1024 bf16 (official "
                            "bench)",
                  "generation": peak["generation"],
                  "peak_bf16_tflops": peak["peak_bf16_tflops"],
                  "legs": results, "attribution": attribution or {}}
        tfl = results.get("train_step", {}).get("tflops_per_chip")
        if tfl is not None:
            report["mfu"] = mfu_summary(tfl)["mfu"]
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
        return report

    for leg in LEGS:
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _child_src(leg)],
                capture_output=True, text=True, timeout=600)
            line = proc.stdout.strip().splitlines()[-1] if \
                proc.stdout.strip() else "{}"
            try:
                results[leg] = json.loads(line)
            except json.JSONDecodeError:
                results[leg] = {"bad_stdout": proc.stdout[-200:],
                                "rc": proc.returncode}
            if proc.returncode != 0:
                results[leg]["rc"] = proc.returncode
                results[leg]["stderr_tail"] = proc.stderr[-300:]
        except subprocess.TimeoutExpired:
            results[leg] = {"timeout": True}
            flush()
            break
        flush()

    # subtraction-based attribution (seconds) — shared with perf_tool
    report = flush(attribute_legs(results))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
