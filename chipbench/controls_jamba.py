"""The controls behind the limits of ``jamba2-3b-1chip.longdoc64k``
(``configs/jamba2-3b-1chip.json``: ``logit_margin_why``): the cell's own
command, through ``drivers/serve_s6.py`` and its check, with one piece of
the program at fault.  Each must serve every request in full and read
``"correct": false``:

    python3 -m chipbench.controls_jamba <control> --seed <n>
        [--seconds 51] [--workload jamba2-3b-1chip.longdoc64k]

``state_in_bfloat16``    (a) the ssm state rounded to bfloat16 on its way
                         into the cache, once a position in the decode and
                         once a chunk in the prefill, as a cache of that
                         dtype would hold it: the nearest precision below
                         the float32 the configuration states
``padding_steps``        (b) a padded chunk's positions left to decay and
                         feed the state (``dt`` not zeroed past the row's
                         length)
``state_reset``          (c) the scan of a chunk started from zeros and not
                         from the row's state
``inner_norms_left_out`` (d) the three RMSNorms on ``dt``, ``B`` and ``C``
                         left out (plain Mamba-1 has none)
``one_decay_a_channel``  (e) ``A[n, d]`` replaced by its mean over ``n``:
                         Mamba-2's one decay a channel
``dt_bias_left_out``     (f) ``dt_proj`` without its bias
``one_key_block_short``  (g) the chunk's attention reading one key block
                         too few once a row holds more than 32,768
                         positions
``tick_key_block_short`` (h) the same of the tick's attention (its key
                         blocks are 4,096 positions): under the served
                         weights neither (g) nor (h) moves a logit or a
                         state, and both are read off the pass under
                         ``serve_s6.attention_probe``

``tests/model/test_jamba.py`` and ``tests/serve/test_s6_state.py`` plant
them at the toy size.
"""
import argparse
import sys


def state_in_bfloat16(patch=setattr):
    """(a)"""
    import jax
    from alpa_tpu.ops import selective_scan
    step, scan = selective_scan.s6_step, selective_scan.s6_chunk_scan

    def stored(result):
        y, state = result
        # (bfloat16's eight bits of exponent and seven of mantissa; a cast
        # there and back is excess precision the TPU compiler takes away)
        return y, jax.lax.reduce_precision(state, 8, 7)

    patch(selective_scan, "s6_step", lambda *args: stored(step(*args)))
    patch(selective_scan, "s6_chunk_scan",
          lambda *args: stored(scan(*args)))


def padding_steps(patch=setattr):
    """(b)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "real_steps", lambda dt, index, lengths: dt)


def state_reset(patch=setattr):
    """(c)"""
    import jax.numpy as jnp
    from alpa_tpu.ops import selective_scan
    scan = selective_scan.s6_chunk_scan
    patch(selective_scan, "s6_chunk_scan",
          lambda state, *args: scan(jnp.zeros_like(state), *args))


INNER_NORMS = ("dt_norm", "b_norm", "c_norm")


def inner_norms_left_out(patch=setattr):
    """(d)"""
    from flax import linen as nn
    from alpa_tpu.model import gpt_model
    plain = gpt_model.make_norm

    class Passes(nn.Module):
        """The norm's weight, and its input as it came."""
        param_dtype: object

        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
            return x

    patch(gpt_model, "make_norm", lambda config, name: Passes(
        config.param_dtype, name=name) if name in INNER_NORMS
        else plain(config, name))


def one_decay_a_channel(patch=setattr):
    """(e)"""
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    rates = gpt_model.s6_rates
    patch(gpt_model, "s6_rates", lambda a_log: jnp.broadcast_to(
        rates(a_log).mean(0, keepdims=True), a_log.shape))


def dt_bias_left_out(patch=setattr):
    """(f)"""
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    steps = gpt_model.s6_dt
    patch(gpt_model, "s6_dt", lambda low, kernel, bias: steps(
        low, kernel, jnp.zeros_like(bias)))


# positions past which (g) reads a key block too few
SHORT_PAST = 32768


def one_key_block_short(patch=setattr, past=SHORT_PAST, chunk=True):
    """(g)"""
    import jax.numpy as jnp
    from alpa_tpu.ops import cached_attention
    read = cached_attention.blocks_read

    def short(last, per_block, seq_len):
        blocks = read(last, per_block, seq_len)
        # (the chunk's kernel or the tick's: their key blocks are of
        # unlike sizes)
        if (per_block == cached_attention.CHUNK_BLOCK_K) != chunk:
            return blocks
        return jnp.where(blocks * per_block > past, blocks - 1, blocks)

    patch(cached_attention, "blocks_read", short)


def tick_key_block_short(patch=setattr, past=SHORT_PAST):
    """(h)"""
    one_key_block_short(patch, past, chunk=False)


CONTROLS = {f.__name__: f for f in (
    state_in_bfloat16, padding_steps, state_reset, inner_norms_left_out,
    one_decay_a_channel, dt_bias_left_out, one_key_block_short,
    tick_key_block_short)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS))
    parser.add_argument("--seed", type=int, default=6100000099)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--workload", default="jamba2-3b-1chip.longdoc64k")
    args = parser.parse_args(argv)
    from chipbench import run
    CONTROLS[args.which]()
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
