"""Long-context training with ring attention (sequence parallelism).

The sequence dim is sharded over an 'sp' mesh axis: each device holds
S / ring_size tokens of every layer's activations, and ring attention
(alpa_tpu.ops.ring_attention) rotates k/v around the ring while online-
softmax statistics combine exactly — context length scales with the
ring, not with one device's memory.  A capability axis the GPU
reference does not have (its longest context is one GPU's memory).

  python examples/long_context.py --seq 4096 --ring 4   # CPU mesh
  python examples/long_context.py --platform tpu ...    # real chips

Trains a compact GPT-style stack and reports loss + per-device sequence
shard.
"""
import argparse
import time

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--ring", type=int, default=4)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    if args.platform == "cpu":
        from alpa_tpu.platform import pin_cpu_platform
        pin_cpu_platform(args.dp * args.ring)

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from alpa_tpu.ops.ring_attention import make_ring_attention_fn

    n_dev = args.dp * args.ring
    devices = np.array(jax.devices()[:n_dev]).reshape(args.dp, args.ring)
    mesh = Mesh(devices, ("dp", "sp"))
    ring_attn = make_ring_attention_fn(mesh, "sp")

    H, NH, S, V = args.hidden, 4, args.seq, 512
    B, L = args.dp, args.layers
    hd = H // NH
    rng = np.random.RandomState(0)

    params = {
        "wte": jnp.asarray(rng.randn(V, H) * 0.02, jnp.float32),
        "blocks": [{
            "qkv": jnp.asarray(rng.randn(H, 3 * H) * 0.02),
            "out": jnp.asarray(rng.randn(H, H) * 0.02),
            "fc_in": jnp.asarray(rng.randn(H, 4 * H) * 0.02),
            "fc_out": jnp.asarray(rng.randn(4 * H, H) * 0.02),
        } for _ in range(L)],
    }

    def block_fn(p, x):
        b, s, h = x.shape
        q, k, v = jnp.split(x @ p["qkv"], 3, axis=-1)
        o = ring_attn(q.reshape(b, s, NH, hd), k.reshape(b, s, NH, hd),
                      v.reshape(b, s, NH, hd), causal=True)
        x = x + o.reshape(b, s, h) @ p["out"]
        return x + jax.nn.relu(x @ p["fc_in"]) @ p["fc_out"]

    def loss_fn(params, ids, labels):
        x = params["wte"][ids]
        # activations sharded (dp, sp): each device holds S/ring tokens
        x = jax.lax.with_sharding_constraint(x, P("dp", "sp", None))
        for p in params["blocks"]:
            x = block_fn(p, x)
        logits = x @ params["wte"].T
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()

    tx = optax.adam(args.lr)
    opt_state = tx.init(params)

    def train_step(params, opt_state, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels)
        upd, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, upd), opt_state, loss

    ids = jax.device_put(
        jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32),
        NamedSharding(mesh, P("dp", "sp")))
    labels = jax.device_put(
        jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32),
        NamedSharding(mesh, P("dp", "sp")))

    with jax.set_mesh(mesh):
        step = jax.jit(train_step)
        losses = []
        tic = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, ids, labels)
            losses.append(float(loss))
    wall = time.perf_counter() - tic
    assert losses[-1] < losses[0], losses
    print(f"mesh (dp={args.dp}, sp={args.ring})  seq {S} "
          f"({S // args.ring} tokens/device)  "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"{wall / args.steps:.2f}s/step")


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
