"""Time, on the chip, the recurrence of ONE Mamba-1 mixer
(``ops/selective_scan.py``) over a prefill chunk, in every form it can
take, beside the mixer's four projections, and the tick's step over the
engine's rows; each form is held to the loop over positions:

    chiprun -- python3 scripts/time_selective_scan.py
    chiprun -- python3 scripts/time_selective_scan.py --tiles 256 512 1024

One JSON line a form: ``ms`` a call (the median of ``--repeat`` runs of
``--inner`` calls that end in one ``block_until_ready``) and ``max_diff``
of ``y`` and of the last state from ``loop`` (``lax.scan`` over the
positions, one ``s6_step`` each: what any platform but a TPU runs).  The
forms: ``loop``; ``associative`` (``lax.associative_scan`` over (positions,
N, D) float32 operands); ``kernel`` at each of ``--tiles`` channels a
program and ``--positions`` positions a program; ``projections`` (in_proj,
x_proj, dt_proj and out_proj of the same chunk in bfloat16: what the scan
is to be compared with); ``step`` (``s6_step`` over ``--rows`` rows, the
state donated).  ``--tiny`` rehearses the control flow on a CPU at toy
widths, the kernel interpreted.
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from alpa_tpu.ops import selective_scan as ss  # noqa: E402


def inputs(rows, s, d, n, rank, hidden):
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    bf = jnp.bfloat16
    return dict(
        state=jax.random.normal(ks[0], (rows, n, d), jnp.float32),
        x=jax.random.normal(ks[1], (rows, s, d), bf),
        dt=jax.nn.softplus(
            jax.random.normal(ks[2], (rows, s, d), jnp.float32) - 4.0),
        a=-jnp.exp(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
                   )[:, None] * jnp.ones((n, d), jnp.float32),
        b=jax.random.normal(ks[4], (rows, s, n), bf),
        c=jax.random.normal(ks[5], (rows, s, n), bf),
        u=jax.random.normal(ks[6], (rows, s, hidden), bf),
        w_in=jax.random.normal(ks[7], (hidden, 2 * d), bf) * 0.02,
        w_x=jax.random.normal(ks[8], (d, rank + 2 * n), bf) * 0.02,
        w_dt=jax.random.normal(ks[9], (rank, d), bf) * 0.02,
        w_out=jax.random.normal(ks[3], (d, hidden), bf) * 0.02)


def associative(state, x, dt, a, b, c):
    """The recurrence as ``lax.associative_scan`` of (decay, increment)
    pairs over the positions."""
    decay = jnp.exp(dt[:, :, None, :] * a)
    add = (dt * x.astype(jnp.float32))[:, :, None, :] * \
        b.astype(jnp.float32)[..., None]
    add = add.at[:, 0].add(decay[:, 0] * state)

    def join(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    _, h = jax.lax.associative_scan(join, (decay, add), axis=1)
    return (h * c.astype(jnp.float32)[..., None]).sum(2), h[:, -1]


def projections(u, x, w_in, w_x, w_dt, w_out, rank):
    xz = u @ w_in
    low = x @ w_x
    dt = low[..., :rank] @ w_dt
    return xz.sum() + dt.sum() + (x @ w_out).sum()


def timed(fn, args, inner, repeat):
    out = fn(*args)
    jax.block_until_ready(out)
    runs = []
    for _ in range(repeat):
        tic = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - tic) / inner * 1e3)
    return statistics.median(runs), out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tiles", type=int, nargs="*",
                        default=[256, 512, 1024])
    parser.add_argument("--positions", type=int, nargs="*",
                        default=[128, 256])
    parser.add_argument("--rows", type=int, default=16)
    parser.add_argument("--inner", type=int, default=5)
    parser.add_argument("--repeat", type=int, default=5)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    s, d, n, rank, hidden = (32, 256, 8, 4, 64) if args.tiny else \
        (1024, 5120, 16, 160, 2560)
    if args.tiny:
        args.tiles, args.positions, args.inner, args.repeat = \
            [128, 256], [16], 1, 1
    v = inputs(1, s, d, n, rank, hidden)
    scan_args = tuple(v[k] for k in ("state", "x", "dt", "a", "b", "c"))
    say = functools.partial(print, flush=True)
    ms, want = timed(jax.jit(ss._scan_positions), scan_args, args.inner,
                     args.repeat)
    say(json.dumps({"form": "loop", "ms": ms}))

    def diff(got):
        return [float(jnp.abs(g - w).max()) for g, w in zip(got, want)]

    ms, got = timed(jax.jit(associative), scan_args, args.inner, args.repeat)
    say(json.dumps({"form": "associative", "ms": ms, "max_diff": diff(got)}))
    for tile in args.tiles:
        for positions in args.positions:
            ss.CHANNELS, ss.POSITIONS = tile, positions
            kernel = jax.jit(functools.partial(ss.chunk_scan_kernel,
                                               interpret=args.tiny))
            try:
                ms, got = timed(kernel, scan_args, args.inner, args.repeat)
            except Exception as e:  # pylint: disable=broad-except
                # a tile the compiler refuses is a finding, not a failure
                say(json.dumps({"form": "kernel", "channels": tile,
                                "positions": positions,
                                "error": str(e)[:300]}))
                continue
            say(json.dumps({"form": "kernel", "channels": tile,
                            "positions": positions, "ms": ms,
                            "max_diff": diff(got)}))
    ms, _ = timed(jax.jit(functools.partial(projections, rank=rank)),
                  tuple(v[k] for k in ("u", "x", "w_in", "w_x", "w_dt",
                                       "w_out")), args.inner, args.repeat)
    say(json.dumps({"form": "projections", "ms": ms}))
    rows = inputs(args.rows, 1, d, n, rank, hidden)
    step = jax.jit(ss.s6_step, donate_argnums=0)
    state = rows["state"]
    step_args = (rows["x"][:, 0], rows["dt"][:, 0], rows["a"],
                 rows["b"][:, 0], rows["c"][:, 0])
    _, state = step(state, *step_args)
    runs = []
    for _ in range(args.repeat):
        tic = time.perf_counter()
        for _ in range(args.inner * 10):
            _, state = step(state, *step_args)
        jax.block_until_ready(state)
        runs.append((time.perf_counter() - tic) / (args.inner * 10) * 1e3)
    say(json.dumps({"form": "step", "rows": args.rows,
                    "ms": statistics.median(runs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
