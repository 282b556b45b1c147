"""On-chip config sweep for the bench model: attention impl x remat x loss.

Prints one JSON line per config (batch 8, seq 1024 unless a case says
otherwise).  One process."""
import json
import time

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

import alpa_tpu
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import gpt_lm_loss
from alpa_tpu.util import compute_gpt_tflops


def run_one(attention_impl, remat, chunked, batch_size=8,
            hidden=768, layers=12, seq_len=1024, remat_policy=None):
    config = GPTConfig(hidden_size=hidden, num_layers=layers,
                      num_heads=hidden // 64,
                      seq_len=seq_len, vocab_size=51200,
                      dtype=jnp.bfloat16, attention_impl=attention_impl,
                      remat_blocks=remat, remat_policy=remat_policy)
    model = GPTModel(config)
    rng = jax.random.PRNGKey(0)
    input_ids = jax.random.randint(rng, (batch_size, config.seq_len), 0,
                                   config.vocab_size)
    labels = jax.random.randint(rng, (batch_size, config.seq_len), 0,
                                config.vocab_size)
    params = model.init(rng, input_ids)
    tx = optax.adam(1e-4)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params, tx=tx)

    @alpa_tpu.parallelize(method=alpa_tpu.ShardParallel(),
                          donate_argnums=(0,))
    def train_step(state, batch):
        def loss_fn(p):
            return gpt_lm_loss(state.apply_fn, p, batch, chunked=chunked)
        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    batch = {"input_ids": input_ids, "labels": labels}
    for _ in range(3):
        state, loss = train_step(state, batch)
        float(loss)
    n_iter = 10
    tic = time.perf_counter()
    for _ in range(n_iter):
        state, loss = train_step(state, batch)
    float(loss)
    latency = (time.perf_counter() - tic) / n_iter
    tflops = compute_gpt_tflops(batch_size, config.seq_len,
                                config.num_layers, config.hidden_size,
                                config.vocab_size, 1, latency)
    print(json.dumps({"attn": attention_impl, "remat": remat,
                      "policy": remat_policy,
                      "chunked_ce": chunked, "batch": batch_size,
                      "hidden": hidden, "layers": layers, "seq": seq_len,
                      "latency_s": round(latency, 5),
                      "tflops": round(tflops, 2)}), flush=True)
    del state, params
    return tflops


# (attn, remat, chunked, hidden, layers)
SWEEPS = {
    # attention impl x remat x loss at GPT-125M bs8
    "impl": [
        ("reference", False, False, 768, 12),
        ("reference", False, True, 768, 12),
        ("flash", False, True, 768, 12),
        ("reference", True, True, 768, 12),
        ("flash", True, True, 768, 12),
    ],
    # model-size sweep at batch 8
    "size": [
        ("reference", False, False, 1024, 24),
        ("reference", True, False, 1024, 24),
        ("reference", True, False, 1536, 24),
        ("reference", True, True, 2048, 16),
    ],
    # second rung: find the peak around GPT-1.3B-class shapes
    "size2": [
        ("reference", True, False, 2048, 16),
        ("reference", True, True, 2048, 24),
        ("reference", True, True, 2560, 16),
    ],
    # remat-policy rung: "dots" saves matmul outputs (est 14.4 GB at
    # h2048 l16 bs8: 4.8 GB saved dots + 9.6 GB params/adam), so these
    # cases run at bs 4
    "policy": [
        dict(attention_impl="reference", remat=True, chunked=False,
             hidden=2048, layers=16, remat_policy="dots", batch_size=4),
        dict(attention_impl="reference", remat=False, chunked=False,
             hidden=2048, layers=16, batch_size=4),
        dict(attention_impl="reference", remat=True, chunked=False,
             hidden=2048, layers=16, remat_policy="dots", batch_size=4,
             seq_len=2048),
    ],
}


def main():
    import sys
    alpa_tpu.init(cluster="local")
    configs = SWEEPS[sys.argv[1] if len(sys.argv) > 1 else "impl"]
    for case in configs:
        kw = dict(case) if isinstance(case, dict) else dict(
            zip(("attention_impl", "remat", "chunked", "hidden", "layers"),
                case))
        try:
            run_one(kw.pop("attention_impl"), kw.pop("remat"),
                    kw.pop("chunked"), **kw)
        except Exception as e:  # pylint: disable=broad-except
            print(json.dumps({"case": repr(case),
                              "error": repr(e)[:200]}), flush=True)


if __name__ == "__main__":
    main()
