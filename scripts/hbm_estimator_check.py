"""Validate bench.py's HBM estimator against measured device memory.
bench.py's HBM gate rides on estimate_hbm_gb; this compares it with the
chip's own peak_bytes_in_use for each gated shape rung, smallest first.

Run on the machine with the chip (no arguments).  The parent never
touches jax; each rung runs in a child process of its own, one at a
time, so that its peak is its own.  The child does 2 train steps and
prints the measured stats.  Results append to
benchmark/results/hbm_estimator_check.jsonl.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (shape, opt_variant, chunked_ce) — the bench's gated rungs, smallest
# first.  Estimates per bench.py HBM accounting; all below the 16 GB
# gate by construction.
RUNGS = [
    ("h1024l8", "adam", False),
    ("h2048l16", "adam", False),       # bench.py's default config
    ("h2048l16", "bf16adam", False),
    ("h2048l24", "bf16adam", True),
]

SHAPES = {"h1024l8": (1024, 8), "h2048l16": (2048, 16),
          "h2048l24": (2048, 24)}

_CHILD_SRC = r'''
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, optax
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import gpt_lm_loss
from bench import estimate_hbm_gb

hidden, layers, opt_variant, chunked = {hidden}, {layers}, {opt!r}, {chunked}
config = GPTConfig(hidden_size=hidden, num_layers=layers,
                   num_heads=hidden // 64, seq_len=1024, vocab_size=51200,
                   dtype=jnp.bfloat16, attention_impl="reference",
                   remat_blocks=True)
batch_size = 8
est = estimate_hbm_gb(config, batch_size,
                      optimizer_bytes_per_param=6.0 if opt_variant ==
                      "bf16adam" else 8.0, chunked_ce=chunked)
model = GPTModel(config)
rng = jax.random.PRNGKey(0)
ids = jnp.zeros((batch_size, config.seq_len), jnp.int32)
params = model.init(rng, ids)
if opt_variant == "bf16adam":
    tx = optax.adam(1e-4, mu_dtype=jnp.bfloat16)
else:
    tx = optax.adam(1e-4)
opt_state = tx.init(params)
batch = dict(input_ids=ids, labels=ids)

def loss_fn(p):
    return gpt_lm_loss(model.apply, p, batch, chunked=chunked)

@jax.jit
def step(params, opt_state, batch):
    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss

for _ in range(2):
    params, opt_state, loss = step(params, opt_state, batch)
    float(loss)  # scalar readback: the step has finished
d = jax.devices()[0]
stats = d.memory_stats() or {{}}
print(json.dumps({{
    "est_gb": round(est, 2),
    "peak_gb": round(stats.get("peak_bytes_in_use", 0) / 1e9, 2),
    "in_use_gb": round(stats.get("bytes_in_use", 0) / 1e9, 2),
    "limit_gb": round(stats.get("bytes_limit", 0) / 1e9, 2),
    "raw_keys": sorted(stats)[:12],
}}))
'''


def main():
    out_path = os.path.join(REPO, "benchmark", "results",
                            "hbm_estimator_check.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for shape, opt, chunked in RUNGS:
        hidden, layers = SHAPES[shape]
        src = _CHILD_SRC.format(repo=REPO, hidden=hidden, layers=layers,
                                opt=opt, chunked=chunked)
        tic = time.time()
        try:
            proc = subprocess.run([sys.executable, "-c", src],
                                  capture_output=True, text=True,
                                  timeout=600)
            line = proc.stdout.strip().splitlines()[-1] if \
                proc.stdout.strip() else "{}"
            try:
                payload = json.loads(line)
            except json.JSONDecodeError:
                # non-JSON child output (crash mid-print, warning) must
                # record a failure, not abort the remaining rungs
                payload = {"bad_stdout_tail": proc.stdout[-200:]}
            rec = {"rung": shape, "opt": opt, "chunked_ce": chunked,
                   "wall_s": round(time.time() - tic, 1), **payload}
            if proc.returncode != 0:
                rec["rc"] = proc.returncode
                rec["stderr_tail"] = proc.stderr[-400:]
        except subprocess.TimeoutExpired:
            rec = {"rung": shape, "opt": opt, "timeout": True,
                   "wall_s": round(time.time() - tic, 1)}
        if "peak_gb" in rec and rec.get("peak_gb"):
            rec["est_over_measured"] = round(
                rec["est_gb"] / max(rec["peak_gb"], 1e-9), 3)
        print(json.dumps(rec), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(rec) + "\n")
        if rec.get("timeout"):
            print(json.dumps({"stopping": "rung timed out"}), flush=True)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
