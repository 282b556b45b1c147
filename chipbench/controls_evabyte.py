"""The controls behind the limits of ``evabyte-1chip.longdoc32k``
(``configs/evabyte-1chip.json``: ``logit_margin_why``): the cell's own
command, through ``drivers/serve_eva.py`` and its check, with one piece of
the program at fault.  Each must serve every request in full and read
``"correct": false``:

    python3 -m chipbench.controls_evabyte <control> --seed <n>
        [--seconds 51] [--workload evabyte-1chip.longdoc32k]

``summaries_left_out``     (a) a query sees its own window only
``mean_pooling``           (b) ``k~`` and ``v~`` the chunk's plain means
``mu_phi_swapped``         (c) ``phi`` pools the keys and ``mu`` the values
``own_window_summaries``   (d) a query also sees the summaries of its own
                           window's chunks that are full before it (a
                           chunk step: before its first query)
``sliding_window``         (e) the exact keys are the last ``window``
                           positions, the repo's ring reused wrongly: a
                           tick past the first window sees every slot of
                           the window's rows, the window before's among
                           them (a pass without a cache: positions ``t -
                           window + 1 .. t``)
``prefill_summary_frozen`` (f) the summary of the chunk a prompt ends in
                           left as the prefill wrote it (the prefill
                           writes no summary of a chunk that is all
                           padding; a tick writes a chunk's summary at the
                           chunk's last position, and only into a slot
                           that holds none)
``padding_pooled``         (g) a padded chunk's positions past the
                           prompt's end pooled into summaries that are
                           read: as (f), but the prefill writes the
                           summaries of its padding too, and the ticks
                           leave them
``one_window_short``       (h) the tick reading a window's worth of
                           summaries too few
``pooling_before_rotary``  (i) a prefill chunk's summaries pooled from
                           the keys as they were before the rotary
                           positions
``stream_in_bfloat16``     (j) the residual stream kept in the model's
                           dtype
``unit_offset_left_out``   (k) a norm's gain its stored weight, not one
                           more

``tests/model/test_evabyte.py`` and ``tests/serve/test_eva_cache.py``
plant them at the toy size.
"""
import argparse
import sys


def summaries_left_out(patch=setattr):
    """(a)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "eva_seen", lambda position, window, chunk, queries:
          position * 0)


def mean_pooling(patch=setattr):
    """(b)"""
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "eva_pool", lambda k, v, mu, phi, scale: (
        k.astype(jnp.float32).mean(-3), v.astype(jnp.float32).mean(-3)))


def mu_phi_swapped(patch=setattr):
    """(c)"""
    from alpa_tpu.model import gpt_model
    pool = gpt_model.eva_pool
    patch(gpt_model, "eva_pool", lambda k, v, mu, phi, scale: pool(
        k, v, phi, mu, scale))


def own_window_summaries(patch=setattr):
    """(d)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "eva_seen", lambda position, window, chunk, queries:
          position // chunk)


def sliding_window(patch=setattr):
    """(e)"""
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    reach = gpt_model.eva_reach

    def last_window(position, window, queries):
        if queries > 1:
            return reach(position, window, queries)
        return jnp.where(position >= window, window - 1, position % window)

    patch(gpt_model, "eva_reach", last_window)
    patch(gpt_model, "eva_exact_from", lambda position, window:
          jnp.maximum(position - window + 1, 0))


def _ticks_leave_what_is_there(patch):
    """A tick writes a chunk's summary at the chunk's last position, and
    only into a slot that holds none (all zeros)."""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "eva_tick_writes", lambda index, chunk, held_now: (
        index % chunk == chunk - 1) & ~held_now().any(-1))


def prefill_summary_frozen(patch=setattr):
    """(f)"""
    from alpa_tpu.model import gpt_model
    _ticks_leave_what_is_there(patch)
    patch(gpt_model, "eva_chunks_taken", lambda first, lengths:
          first < lengths[:, None])


def padding_pooled(patch=setattr):
    """(g)"""
    _ticks_leave_what_is_there(patch)


def one_window_short(patch=setattr):
    """(h)"""
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    seen = gpt_model.eva_seen

    def short(position, window, chunk, queries):
        sound = seen(position, window, chunk, queries)
        if queries > 1:
            return sound
        return jnp.maximum(sound - window // chunk, 0)

    patch(gpt_model, "eva_seen", short)


def pooling_before_rotary(patch=setattr):
    """(i)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "eva_keys_to_pool", lambda unrotated, rotated:
          unrotated)


def stream_in_bfloat16(patch=setattr):
    """(j)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "stream_dtype", lambda config: config.dtype)


def unit_offset_left_out(patch=setattr):
    """(k)"""
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "norm_gain", lambda scale: scale)


CONTROLS = {f.__name__: f for f in (
    summaries_left_out, mean_pooling, mu_phi_swapped, own_window_summaries,
    sliding_window, prefill_summary_frozen, padding_pooled,
    one_window_short, pooling_before_rotary, stream_in_bfloat16,
    unit_offset_left_out)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS))
    parser.add_argument("--seed", type=int, default=6300000099)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--workload", default="evabyte-1chip.longdoc32k")
    args = parser.parse_args(argv)
    from chipbench import run
    CONTROLS[args.which]()
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
