"""The controls behind the limits of
``nemotron-3-nano-30b-a3b-1chip.reasoning``
(``configs/nemotron-3-nano-30b-a3b-1chip.json``: ``logit_margin_why``): the
cell's own command, through ``drivers/serve_ssm.py`` and its check, with
one piece of the program at fault.  Each must serve every request in full
and read ``"correct": false``:

    python3 -m chipbench.controls_nemotron <control> --seed <n>
        [--seconds 20] [--workload nemotron-3-nano-30b-a3b-1chip.reasoning]

``state_in_bfloat16``     (a) the ssm state rounded to bfloat16 on its way
                          into the cache, once a position in the decode and
                          once a chunk in the prefill, as a cache of that
                          dtype would hold it: the nearest precision below
                          the float32 the configuration states
``padding_steps``         (b) a padded chunk's positions left to decay and
                          feed the state (``dt`` not zeroed past the row's
                          length)
``state_reset``           (c) the scan of a chunk started from zeros and
                          not from the row's state
``one_expert_left_out``   (d) one expert a token left out (the program
                          routes to one fewer)
``relu_not_squared``      (e) ``relu`` for ``relu^2``, in the routed and
                          the shared experts
``gate_after_norm``       (f) the gate ``silu(z)`` applied after the
                          grouped norm and not before it

``tests/model/test_nemotron_h.py`` and ``tests/serve/test_ssm_state.py``
plant them at the toy size.
"""
import argparse
import dataclasses
import sys


def state_in_bfloat16(patch=setattr):
    """(a)"""
    import jax
    from alpa_tpu.ops import ssm_scan
    step, scan = ssm_scan.ssm_step, ssm_scan.ssm_chunk_scan

    def stored(result):
        y, state = result
        # (bfloat16's eight bits of exponent and seven of mantissa; a cast
        # there and back is excess precision the TPU compiler takes away)
        return y, jax.lax.reduce_precision(state, 8, 7)

    patch(ssm_scan, "ssm_step", lambda *args: stored(step(*args)))
    patch(ssm_scan, "ssm_chunk_scan", lambda *args: stored(scan(*args)))


def padding_steps(patch=setattr):
    """(b)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "real_steps", lambda dt, index, lengths: dt)


def state_reset(patch=setattr):
    """(c)"""
    import jax.numpy as jnp
    from alpa_tpu.ops import ssm_scan
    scan = ssm_scan.ssm_chunk_scan
    patch(ssm_scan, "ssm_chunk_scan",
          lambda state, *args: scan(jnp.zeros_like(state), *args))


def one_expert_left_out(patch=setattr):
    """(d)"""
    from alpa_tpu.model import gpt_model
    plain = gpt_model.config_from_hf

    def fewer(hf, **kwargs):
        cfg = plain(hf, **kwargs)
        return dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)

    patch(gpt_model, "config_from_hf", fewer)


def relu_not_squared(patch=setattr):
    """(e)"""
    from alpa_tpu.model import gpt_model
    plain = gpt_model.activation_fn
    patch(gpt_model, "activation_fn",
          lambda name: plain("relu" if name == "relu2" else name))


def gate_after_norm(patch=setattr):
    """(f)"""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from alpa_tpu.model import gpt_model

    def late(y, z, weight, groups, eps):
        y = y.astype(jnp.float32)
        grouped = y.reshape(y.shape[:-1] + (groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.square(grouped).mean(-1, keepdims=True) + eps)
        return grouped.reshape(y.shape) * weight.astype(jnp.float32) * \
            nn.silu(z.astype(jnp.float32))

    patch(gpt_model, "gated_group_norm", late)


CONTROLS = {f.__name__: f for f in (
    state_in_bfloat16, padding_steps, state_reset, one_expert_left_out,
    relu_not_squared, gate_after_norm)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS))
    parser.add_argument("--seed", type=int, default=5800000099)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument(
        "--workload", default="nemotron-3-nano-30b-a3b-1chip.reasoning")
    args = parser.parse_args(argv)
    from chipbench import run
    CONTROLS[args.which]()
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
