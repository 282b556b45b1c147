"""The controls behind the limits of ``mimo-v2-flash-1chip.longmix``
(``configs/mimo-v2-flash-1chip.json``: ``logit_margin_why``): the cell's
own command, through ``drivers/serve_mimo.py`` and its check, with one piece
of the program at fault.  Each must serve every request in full and read
``"correct": false``:

    python3 -m chipbench.controls_mimo <control> --seed <n> [--seconds 20]
        [--workload mimo-v2-flash-1chip.longmix]

``sink_left_out``        the window layers' softmax without its learned
                         sink (a plain softmax over the visible keys)
``values_unscaled``      the heads' weighted values not multiplied by
                         ``attention_value_scale``
``rotary_all_channels``  rotary positions on all of a head's channels, not
                         on the leading ``head_dim x partial_rotary_factor``
``cache_in_float8``      the keys and values rounded to float8 e4m3 on
                         their way into the caches (the full layers' and the
                         rings'), everything else as it is: the nearest
                         precision below the bfloat16 the configuration
                         states, in the state the decode reads

``tests/model/test_mimo_v2_flash.py`` plants the four at the toy size.
"""
import argparse
import sys


def _float8(a):
    import jax
    return jax.lax.reduce_precision(a, 4, 3)


def sink_left_out(config, patch=setattr):
    del config
    from alpa_tpu.model import gpt_model
    plain = gpt_model.reference_attention
    patch(gpt_model, "reference_attention",
          lambda *args, sink=None, **kwargs: plain(*args, **kwargs))


def _configured(patch, **forced):
    """Every ``GPTConfig`` made from a file's keys gets ``forced``."""
    from alpa_tpu.model import gpt_model
    from_hf = gpt_model.config_from_hf
    patch(gpt_model, "config_from_hf",
          lambda hf, **kwargs: from_hf(hf, **{**kwargs, **forced}))


def values_unscaled(config, patch=setattr):
    del config
    _configured(patch, value_scale=1.0)


def rotary_all_channels(config, patch=setattr):
    del config
    _configured(patch, rotary_dim=0)


def cache_in_float8(config, patch=setattr):
    del config
    from alpa_tpu.model import gpt_model
    rows, ring = gpt_model.update_kv_cache, gpt_model.update_ring_cache
    patch(gpt_model, "update_kv_cache",
          lambda kv_cache, k, v: rows(kv_cache, _float8(k), _float8(v)))
    patch(gpt_model, "update_ring_cache",
          lambda kv_cache, k, v, lengths=None: ring(
              kv_cache, _float8(k), _float8(v), lengths))


CONTROLS = {f.__name__: f for f in (sink_left_out, values_unscaled,
                                    rotary_all_channels, cache_in_float8)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS))
    parser.add_argument("--seed", type=int, default=5100000099)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--workload", default="mimo-v2-flash-1chip.longmix")
    args = parser.parse_args(argv)
    from chipbench import run
    _, cell, _ = run.find_cell(args.workload)
    CONTROLS[args.which](
        run.load_json(run.HERE, "configs", cell["config"] + ".json"))
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
