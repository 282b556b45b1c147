"""The controls behind the limits of ``dots3-note-prev-1chip.longctx``
(``configs/dots3-note-prev-1chip.json``: ``logit_margin_why``): the cell's
own command, through ``drivers/serve_dsa.py`` and its check, with one piece
of the program at fault.  Each must serve every request in full and read
``"correct": false``:

    python3 -m chipbench.controls_dots3 <control> --seed <n> [--seconds 20]
        [--workload dots3-note-prev-1chip.longctx]

``cache_in_float8``   the latent caches (the full layers' rows and index
                      keys, the sliding layers' rings) rounded to float8
                      e4m3 on their way in, everything else as it is: the
                      nearest precision below the bfloat16 the
                      configuration states, in the state the decode reads
``recent_positions``  the indexer's scores replaced by the positions
                      themselves, so that a query selects the most recent
                      ``index_topk`` positions: a window where the model
                      learned a selection
``gates_left_out``    the head-wise gates left out (every gate 1)

and the reading that says where the sound side's distance comes from:

    python3 -m chipbench.controls_dots3 depth [--layers 6] [--positions 3072]
        [--seed <n>] [--config dots3-note-prev-1chip]

the configuration's weights in bfloat16 against the float32 reference,
models of the leading 1 .. ``--layers`` layers, three lines a depth: (A)
the TRAINING call (no cache, no engine; the first two depths only: it
holds every head's scores at once), (B) the same positions in chunks
through the caches (the index kernel, the selection's mask, the expanded
core under it; the rings; the last 64 positions also by themselves), (C)
16 decode steps over per-row indices after them (the index kernel, the
top-k, the gather, the absorbed core); each
with the mean absolute logit difference over the vocabulary, the logits'
correlation with the reference's, the share of the reference's picks that
are the program's and, of C, the share of the reference's selected
positions that are the program's, layer by layer.  Where A, B and C read
alike, the distance is the precision's over the depth and not the serving
path's.

``tests/model/test_dots3_note.py`` plants the three faults at the toy
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
    rows, ring = (gpt_model.update_latent_index_cache,
                  gpt_model.update_latent_ring)
    patch(gpt_model, "update_latent_index_cache",
          lambda kv_cache, c, k_pe, k_index: rows(
              kv_cache, _float8(c), _float8(k_pe), _float8(k_index)))
    patch(gpt_model, "update_latent_ring",
          lambda kv_cache, c, k_pe, lengths=None: ring(
              kv_cache, _float8(c), _float8(k_pe), lengths))


def recent_positions(config, patch=setattr):
    del config
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model

    def by_position(q_index, weights, keys, q_pos):
        del weights
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, keys.shape[1]), 2)
        seen = k_pos <= jnp.broadcast_to(
            q_pos, q_index.shape[:2])[:, :, None]
        return jnp.where(seen, k_pos.astype(jnp.float32), -jnp.inf)

    patch(gpt_model, "index_scores", by_position)


def gates_left_out(config, patch=setattr):
    del config
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model
    patch(gpt_model, "head_gates", jnp.ones_like)


CONTROLS = {f.__name__: f for f in (cache_in_float8, recent_positions,
                                    gates_left_out)}


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
        return [round(float(x), 4) for x in found.mean((1, 2))]

    for d in range(1, min(layers, hf["num_hidden_layers"]) + 1):
        cut = dict(hf, num_hidden_layers=d)
        cfg = mla.model_config(cut, dtype=bf16, param_dtype=bf16,
                               seq_len=hf["serve"]["served_context"])
        model = GPTModel(cfg)
        key = program.key_from_seed(seed)
        params = jax.jit(
            lambda k: model.init(k, jnp.ones((1, 8), jnp.int32)))(key)
        if d > hf["first_k_dense_replace"]:
            params = mla.balance_routers(
                model, params, jax.random.fold_in(key, 1), cfg.vocab_size)
        ids = jax.random.randint(jax.random.fold_in(key, 2),
                                 (1, positions + tail), 4, cfg.vocab_size)
        reference = ref_mod.Reference(driver.reference_settings(cut))
        want, picks, (chosen, real) = reference.logits_experts_selections(
            ref_mod.weights_from_program(params), np.asarray(ids[0]),
            (0, positions + tail))
        want, picks = np.asarray(want), np.asarray(picks)
        if d <= 2:
            out = jax.jit(model.apply)(params, ids)
            logits, routing = out if isinstance(out, tuple) else (out, None)
            print(json.dumps({
                "depth": d, "path": "A training call", "logit_std":
                float(want.std()), **distance(logits[0], want),
                "picks_kept_by_layer": agreement(np.asarray(
                    routing["experts"]).reshape(len(picks),
                                                positions + tail, -1), picks)
                if routing else []}), flush=True)
        gen = Generator(model, params, cfg, prefill_chunk=chunk)
        caches = init_kv_caches(cfg, 1)
        lengths = jnp.asarray([positions], jnp.int32)
        rows = []
        step = jax.jit(lambda p, i, pos, c: model.apply(
            p, i, pos, c, cache_lengths=lengths))
        for at in range(0, positions, chunk):
            n = min(chunk, positions - at)
            out, caches = step(params, ids[:, at:at + n],
                               at + jnp.arange(n)[None], caches)
            rows.append(out[0])
        got = jnp.concatenate(rows)
        # (the last positions apart: what the decode's come after)
        print(json.dumps({
            "depth": d, "path": "B chunks through the caches",
            **distance(got, want[:positions]), "last_64": distance(
                got[-64:], want[positions - 64:positions])}), flush=True)
        caches = [(c, k, jnp.full((1,), positions, jnp.int32))
                  for c, k, _ in caches]
        rows, said = [], []
        for t in range(positions, positions + tail):
            out, caches, routing = gen._decode(params, ids[:, t:t + 1],
                                               caches[0][2], caches)
            rows.append(out[0])
            said.append(jax.device_get(routing))
        kept = []
        for layer in range(len(chosen)):
            hits = total = 0
            for at, step_said in enumerate(said):
                t = positions + at
                got = set(step_said["selected"][layer, 0][
                    :step_said["selected_real"][layer, 0]].tolist())
                ref = set(np.asarray(chosen[layer, t][
                    :int(real[layer, t])]).tolist())
                hits, total = hits + len(got & ref), total + len(ref)
            kept.append(round(hits / total, 4))
        print(json.dumps({
            "depth": d, "path": "C decode", **distance(
                jnp.stack(rows), want[positions:]),
            "picks_kept_by_layer": agreement(
                np.stack([s["experts"][:, 0] for s in said], 1),
                picks[:, positions:]) if len(picks) else [],
            "selected_kept_by_layer": kept}), flush=True)
        del params, gen, caches


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("which", choices=sorted(CONTROLS) + ["depth"])
    parser.add_argument("--seed", type=int, default=4700000099)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--workload",
                        default="dots3-note-prev-1chip.longctx")
    parser.add_argument("--config", default="dots3-note-prev-1chip")
    parser.add_argument("--layers", type=int, default=6)
    parser.add_argument("--positions", type=int, default=3072)
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
