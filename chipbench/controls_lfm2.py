"""The controls behind the limits of ``lfm2-8b-a1b-1chip.chat``
(``configs/lfm2-8b-a1b-1chip.json``: ``logit_margin_why``): the cell's own
command, through ``drivers/serve_hybrid.py`` and its ``_check``, with one
piece of the program (or of the reference) at fault.  Each must serve every
request in full and read ``"correct": false``:

    python3 -m chipbench.controls_lfm2 <a|b|c|d> --seed <n> [--seconds 20]
        [--workload lfm2-8b-a1b-1chip.chat]

(a) the conv state rounded to float8 e4m3 on its way into the cache
(b) the state of a padded chunk taken from the chunk's end and not from the
    row's last real position
(c) one expert a token left out (the program routes to one fewer)
(d) every matrix rounded to e4m3 (on the reference's side, where the
    difference is the same: two copies of the weights do not fit the chip)

and the reading that says where the sound side's distance comes from:

    python3 -m chipbench.controls_lfm2 depth [--layers 13] [--positions 512]
        [--seed <n>] [--config lfm2-8b-a1b-1chip]

the TRAINING call (no cache, no engine) of the configuration's weights in
bfloat16 against the float32 reference, models cut after 1 .. ``--layers``
layers, one line a depth: the mean absolute logit difference, the share of
the reference's choices that are the program's, and the share of positions
that keep every pick; for the leading dense layers the float32 program too.

``chipbench/tests/test_lfm2.py`` plants the same faults at the toy size.
"""
import argparse
import dataclasses
import json
import sys


def _float8(a):
    import jax
    return jax.lax.reduce_precision(a, 4, 3)


def state_in_float8(patch=setattr):
    """(a)"""
    from alpa_tpu.model import gpt_model
    update = gpt_model.update_conv_state

    def rounded(kv_cache, g, lengths=None):
        full, (state, empty, index) = update(kv_cache, g, lengths)
        return full, (_float8(state), empty, index)

    patch(gpt_model, "update_conv_state", rounded)


def state_of_the_chunks_end(patch=setattr):
    """(b)"""
    from alpa_tpu.model import gpt_model
    update = gpt_model.update_conv_state
    patch(gpt_model, "update_conv_state",
          lambda kv_cache, g, lengths=None: update(kv_cache, g, None))


def one_expert_left_out(patch=setattr):
    """(c)"""
    from alpa_tpu.model import gpt_model
    plain = gpt_model.config_from_hf

    def fewer(hf, **kwargs):
        cfg = plain(hf, **kwargs)
        return dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)

    patch(gpt_model, "config_from_hf", fewer)


def matrices_in_float8(patch=setattr):
    """(d): what the driver loads as its reference reads every matrix,
    the tied head too, through e4m3."""
    import jax
    import jax.numpy as jnp
    from chipbench import run
    load = run.load_module

    def low(tree):
        return jax.tree_util.tree_map(
            lambda a: _float8(jnp.asarray(a, jnp.float32))
            if jnp.ndim(a) >= 2 else jnp.asarray(a, jnp.float32), tree)

    def loading(kind, name):
        mod = load(kind, name)
        if kind == "references":
            head = mod.head
            mod._f32 = low
            mod.head = lambda x, wf, wte, eps: head(
                x, wf, _float8(jnp.asarray(wte, jnp.float32)), eps)
        return mod

    patch(run, "load_module", loading)


CONTROLS = {"a": state_in_float8, "b": state_of_the_chunks_end,
            "c": one_expert_left_out, "d": matrices_in_float8}


def depth(config_name, layers, positions, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from alpa_tpu.model.gpt_model import GPTModel, config_from_hf
    from chipbench import program, run
    hf = run.load_json(run.HERE, "configs", config_name + ".json")
    layers = min(layers, hf["num_hidden_layers"])
    types = hf["layer_types"][:layers]
    hf.update(num_hidden_layers=layers, layer_types=types)
    driver = run.load_module("drivers", hf["driver"])
    lm = run.load_module("drivers", "serve_lm")
    ref_mod = run.load_module("references", hf["reference"])
    reference = ref_mod.Reference(driver.reference_settings(hf))
    seq_len = hf["serve"]["served_context"]
    bf16 = jnp.dtype(hf["dtype"])
    cfg = config_from_hf(hf, dtype=bf16, param_dtype=bf16, seq_len=seq_len)
    model = GPTModel(cfg)
    key = program.key_from_seed(seed)
    params = jax.jit(
        lambda k: model.init(k, jnp.ones((1, 8), jnp.int32)))(key)
    params = lm.balance_router_biases(model, params,
                                      jax.random.fold_in(key, 1),
                                      cfg.vocab_size)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (1, positions), 4,
                             cfg.vocab_size)
    weights = ref_mod.weights_from_program(params)
    for d in range(1, layers + 1):
        routed = d > hf["num_dense_layers"]
        cut = {"params": {k: v for k, v in params["params"].items()
                          if not k.startswith("h") or int(k[1:]) < d}}
        hf_cut = dict(hf, num_hidden_layers=d, layer_types=types[:d])
        blocks = dict(weights, blocks=weights["blocks"][:d])
        if routed:
            want, chosen = reference.logits_and_experts(
                blocks, np.asarray(ids[0]), rows=(0, positions))
        else:
            want, chosen = reference.logits(blocks, np.asarray(ids[0])), None
        want = np.asarray(want)
        line = {"depth": d, "kind": types[d - 1],
                "logit_std": float(want.std())}
        # (in float32 the grouped matmul does not fit the fast memory:
        # the float32 program is read on the dense layers alone)
        for name, dtype in (("bf16", bf16), ("f32", jnp.float32))[
                :1 if routed else 2]:
            sub = GPTModel(config_from_hf(hf_cut, dtype=dtype,
                                          param_dtype=bf16, seq_len=seq_len))
            forward = jax.jit(lambda p, i, sub=sub: sub.apply(p, i))
            if dtype == jnp.float32:
                with jax.default_matmul_precision("highest"):
                    out = forward(cut, ids)
            else:
                out = forward(cut, ids)
            logits, routing = out if isinstance(out, tuple) else (out, None)
            diff = np.abs(np.asarray(logits[0], np.float32) - want).mean(-1)
            line[name + "_mean_diff"] = float(diff.mean())
            line[name + "_max_diff"] = float(diff.max())
            if routing is not None and chosen is not None:
                ref_experts = np.asarray(chosen)     # (layers, S, k)
                got = np.asarray(routing["experts"]).reshape(
                    ref_experts.shape[0], positions, -1)
                found = (ref_experts[..., :, None] ==
                         got[..., None, :]).any(-1)
                line[name + "_agreement"] = float(found.mean())
                line[name + "_positions_same"] = float(
                    found.all(-1).all(0).mean())
        print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS) + ["depth"])
    parser.add_argument("--seed", type=int, default=3800000099)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--workload", default="lfm2-8b-a1b-1chip.chat")
    parser.add_argument("--config", default="lfm2-8b-a1b-1chip")
    parser.add_argument("--layers", type=int, default=13)
    parser.add_argument("--positions", type=int, default=512)
    args = parser.parse_args(argv)
    if args.which == "depth":
        depth(args.config, args.layers, args.positions, args.seed)
        return 0
    from chipbench import run
    CONTROLS[args.which]()
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
