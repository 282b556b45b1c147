"""The controls behind the limits of ``longcat-flash-1chip.agent``
(``configs/longcat-flash-1chip.json``: ``logit_margin_why``): the cell's own
command, through ``drivers/serve_scmoe.py`` and its check, with one piece of
the program (or of the reference) at fault.  Each must serve every request
in full and read ``"correct": false``:

    python3 -m chipbench.controls_longcat <control> --seed <n> [--seconds 20]
        [--workload longcat-flash-1chip.agent]

``cache_in_float8``     the latent caches (``c`` and ``k_pe`` of all eight)
                        rounded to float8 e4m3 on their way in, everything
                        else as it is: the nearest precision below the
                        bfloat16 the configuration states, in the state the
                        decode reads
``matrices_in_float8``  every matrix rounded to e4m3 (on the reference's
                        side, where the difference is the same: two copies
                        of the weights do not fit the chip)
``identity_left_out``   the picks of an identity expert treated as absent
                        experts' (the program adds nothing for them): a
                        third of the router's weight, which no tolerance
                        may hide

and the reading that says where the sound side's distance comes from:

    python3 -m chipbench.controls_longcat depth [--layers 4] [--positions 2048]
        [--seed <n>] [--config longcat-flash-1chip]

the configuration's weights in bfloat16 against the float32 reference,
models of 1 .. ``--layers`` published layers, three lines a depth: (A) the
TRAINING call (no cache, no engine, no attention kernel), (B) the same
positions in chunks through the latent caches (the expanded core), (C) 16
decode steps over per-row indices after them (the absorbed core); each
with the mean absolute logit difference over the vocabulary, the logits'
correlation with the reference's, and the share of the reference's picks
that are the program's, layer by layer.  Where A, B and C read alike, the
distance is the precision's over the depth and not the serving path's.

``tests/model/test_longcat_flash.py`` plants the three faults at the toy
size.
"""
import argparse
import json
import sys


def _float8(a):
    import jax
    return jax.lax.reduce_precision(a, 4, 3)


def cache_in_float8(config, patch=setattr):
    del config
    from alpa_tpu.model import gpt_model
    update = gpt_model.update_latent_cache
    patch(gpt_model, "update_latent_cache",
          lambda kv_cache, c, k_pe: update(kv_cache, _float8(c),
                                           _float8(k_pe)))


def matrices_in_float8(config, patch=setattr):
    """What the driver loads as its reference reads every matrix through
    e4m3 (the reference upcasts each where it is used, ``_f32``)."""
    del config
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
            mod._f32 = low
        return mod

    patch(run, "load_module", loading)


def identity_left_out(config, patch=setattr):
    import jax.numpy as jnp
    from alpa_tpu.model import moe
    experts = config["published"]["n_routed_experts"]
    plain = moe.topk_routing

    def without(*args, **kwargs):
        weights, chosen, scores = plain(*args, **kwargs)
        return jnp.where(chosen >= experts, 0.0, weights), chosen, scores

    patch(moe, "topk_routing", without)


CONTROLS = {f.__name__: f for f in (cache_in_float8, matrices_in_float8,
                                    identity_left_out)}


def depth(config_name, layers, positions, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from alpa_tpu.model.gpt_model import GPTModel, init_kv_caches
    from alpa_tpu.serve.generation import Generator
    from chipbench import program, run
    hf = run.load_json(run.HERE, "configs", config_name + ".json")
    mla = run.load_module("drivers", "serve_mla")
    driver = run.load_module("drivers", hf["driver"])
    ref_mod = run.load_module("references", hf["reference"])
    bf16, chunk = jnp.dtype(hf["dtype"]), hf["serve"]["prefill_chunk"]
    tail = 16

    def distance(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        every = max(1, len(got) // 64)
        return {"mean_diff": float(np.abs(got - want).mean()),
                "correlation": float(np.mean([
                    np.corrcoef(g, w)[0, 1]
                    for g, w in zip(got[::every], want[::every])]))}

    def agreement(got, want):
        found = (np.asarray(want)[..., :, None] ==
                 np.asarray(got)[..., None, :]).any(-1)
        return [float(x) for x in found.mean((1, 2))]

    for d in range(1, min(layers, hf["num_layers"]) + 1):
        cut = dict(hf, num_layers=d)
        cfg = mla.model_config(cut, dtype=bf16, param_dtype=bf16,
                               seq_len=hf["serve"]["served_context"])
        model = GPTModel(cfg)
        key = program.key_from_seed(seed)
        params = jax.jit(
            lambda k: model.init(k, jnp.ones((1, 8), jnp.int32)))(key)
        params = driver.balance_routers(
            model, params, jax.random.fold_in(key, 1), cfg.vocab_size)
        ids = jax.random.randint(jax.random.fold_in(key, 2),
                                 (1, positions + tail), 4, cfg.vocab_size)
        reference = ref_mod.Reference(driver.reference_settings(cut))
        want, picks = reference.logits_and_experts(
            ref_mod.weights_from_program(params), np.asarray(ids[0]),
            (0, positions + tail))
        want, picks = np.asarray(want), np.asarray(picks)
        logits, routing = jax.jit(model.apply)(params, ids)
        print(json.dumps({
            "depth": d, "path": "A training call", "logit_std":
            float(want.std()), **distance(logits[0], want),
            "picks_kept_by_layer": agreement(np.asarray(
                routing["experts"]).reshape(d, positions + tail, -1),
                picks)}), flush=True)
        caches = init_kv_caches(cfg, 1)
        step = jax.jit(lambda p, i, pos, c: model.apply(p, i, pos, c))
        rows = []
        for at in range(0, positions, chunk):
            n = min(chunk, positions - at)
            out, caches = step(params, ids[:, at:at + n],
                               at + jnp.arange(n)[None], caches)
            rows.append(out[0])
        print(json.dumps({
            "depth": d, "path": "B chunks through the caches",
            **distance(jnp.concatenate(rows), want[:positions])}),
            flush=True)
        gen = Generator(model, params, cfg, prefill_chunk=chunk)
        caches = [(c, k, jnp.full((1,), positions, jnp.int32))
                  for c, k, _ in caches]
        rows, chosen = [], []
        for t in range(positions, positions + tail):
            out, caches, routing = gen._decode(params, ids[:, t:t + 1],
                                               caches[0][2], caches)
            rows.append(out[0])
            chosen.append(routing["experts"][:, 0])
        print(json.dumps({
            "depth": d, "path": "C decode", **distance(
                jnp.stack(rows), want[positions:]),
            "picks_kept_by_layer": agreement(
                np.stack(chosen, 1), picks[:, positions:])}), flush=True)
        del params, gen, caches


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS) + ["depth"])
    parser.add_argument("--seed", type=int, default=4400000099)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--workload", default="longcat-flash-1chip.agent")
    parser.add_argument("--config", default="longcat-flash-1chip")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--positions", type=int, default=2048)
    args = parser.parse_args(argv)
    if args.which == "depth":
        depth(args.config, args.layers, args.positions, args.seed)
        return 0
    from chipbench import run
    _, cell, _ = run.find_cell(args.workload)
    CONTROLS[args.which](
        run.load_json(run.HERE, "configs", cell["config"] + ".json"))
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
