"""Driver of the serving cells whose model is a dots3-note decoder
(``model_type`` dots3_note): latent attention over the positions a learned
indexer selects in its full layers, latent attention of other widths under
a window (a ring of latents) in its sliding layers, head-wise gates, and
routed experts of which this chip holds one share.

It is ``drivers/serve_mla.py``'s run with the pieces that know the model
put in its place, and nothing else: the same window, clocks, warm-up,
closed loop, router balance (``balance_routers``: the routers live where
DeepSeek-V2's do), traces and ``obs``, so that every reader of the serving
cells works on it.  ``run`` loads a copy of that module of its own
(``ctx.load`` makes a fresh one each time) and binds, in that copy:

* ``reference_settings``: what ``references/dots3_note_decoder.py`` needs
  of the configuration file's keys;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_dsa.py``: how many layers
  route and what one expert's matrices weigh;
* ``_closed_loop``: the same closed loop with every caller on a part of
  the stream of its own, so that no race between two callers decides what
  the engine admits when (``_closed_loop`` below says what that cost);
* ``read_program_trace`` and (through ``ctx.load``) ``serve_lm``'s
  ``read_decode_trace``: both also sum the device events under the
  program's scopes ``indexer`` and ``latent_select``
  (``obs["decode_trace"]``, ``obs["chunk_trace"]``: ``indexer_s``,
  ``latent_select_s``);
* ``_check``: ``serve_mla._check``'s comparison (the window's own compiled
  ``_chunk_prefill``, ``_scatter_row`` and ``_decode`` replayed at the
  window's shapes against the reference's full forward pass) with the
  SELECTION beside the experts: ``_decode`` says which positions each
  selecting layer's query attended over, the reference which it selects,
  and a position counts as flipped where any of its picks OR any of its
  selected sets differs from the reference's; ``min_selection_agreement``
  is a floor on the share of the reference's selected positions that are
  the program's, as ``min_choice_agreement`` is on the picks.  Every
  checked request's reference runs over its own context rounded up to
  ``reference_length_step`` positions, not over the mix's longest.
"""
import threading
import time

import numpy as np

from chipbench import arithmetic_dsa, traffic

# the closed loop's callers start this far apart (``serve_mla``'s)
START_EVERY_S = 0.02


def _closed_loop(ctx, client, mix, vocab):
    """``serve_mla._closed_loop`` whose callers each walk a part of the
    stream of their own: caller k sends the stream's requests k, k +
    clients, k + 2 clients, ...  There the callers draw from one stream in
    the order they come free, and two that finish in one tick race for
    the next request: which of them gets the prompt of 20,000 positions
    and which the one of 3,000 then decides what the engine admits when.
    A window of this mix holds some 35 admissions whose prompts block all
    rows for up to 2.8 s each, so one such race moved a window's tokens by
    6.6 % (380.3 tokens/s where three runs read 356.7 to the digit:
    PERF.md, PR 47).  Here what a caller sends next does not depend on
    who else came free.  Returns what that loop returns: (stop, the
    threads that send, the threads that wait)."""
    source = traffic.closed_loop(mix, ctx.seed, vocab)
    drawn, lock = [], threading.Lock()
    stop = threading.Event()

    def request(n):
        with lock:
            while len(drawn) <= n:
                drawn.append(next(source))
            return drawn[n]

    def caller(k):
        n = k
        while not stop.is_set():
            client.request(request(n), time.perf_counter())
            n += mix["clients"]

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(mix["clients"])]
    for t in threads:
        t.start()
        time.sleep(START_EVERY_S)
    return stop, threads, threads


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    def widths(prefix):
        return {"heads": config[prefix + "num_attention_heads"],
                "dn": config[prefix + "qk_nope_head_dim"],
                "dr": config[prefix + "qk_rope_head_dim"],
                "dv": config[prefix + "v_head_dim"],
                "theta": config[prefix + "rope_theta"]}
    keys = ("index_n_heads", "index_head_dim", "index_topk", "rms_norm_eps",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    return {**{k: config[k] for k in keys},
            "full": widths(""), "sliding": widths("swa_"),
            "layer_types": arithmetic_dsa.layer_types(config),
            "window": config["sliding_window_size"],
            "rescale": config["apply_mla_qkv_lora_rescale"],
            "experts_first": config["share_index"] *
            config["n_routed_experts"],
            "query_block": config["reference_query_block"],
            "head_block": config["reference_head_block"]}


def _replay(generator, scatter_row, rows, group, refs):
    """``serve_lm._replay`` (which says what is replayed, and how) that also
    keeps what ``_decode`` said of its selecting layers.  A request:
    ``(diff, experts, selected, real)``: ``selected`` (selecting layers,
    served, index_topk) int32 on the device and ``real`` (selecting
    layers, served), the positions each served position's query attended
    over and how many of them are real; ``real`` is 0 at the first
    position (the prefill's last logits, which come without them)."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import init_kv_caches, routed_mlp
    cfg = generator.config

    @jax.jit
    def row_diff(logits, ref, row, at):
        return jnp.abs(logits[row].astype(jnp.float32) - ref[at]).mean()

    caches = [(k, v, jnp.zeros((rows,), jnp.int32))
              for k, v, _i in init_kv_caches(cfg, rows)]
    logits = jnp.zeros((rows, cfg.vocab_size), cfg.dtype)
    for r, rec in enumerate(group):
        prompt = np.asarray(rec["prompt_ids"], np.int32)
        last, row = generator._run_chunked_prefill(
            [prompt], jnp.asarray([len(prompt)], jnp.int32), 1)
        caches, logits = scatter_row(caches, row, logits, last, r)
    served = [rec["tokens"] for rec in group]
    diffs = [[row_diff(logits, ref, r, 0)] for r, ref in enumerate(refs)]
    said = []
    for k in range(max(map(len, served)) - 1):
        token = np.zeros((rows, 1), np.int32)
        for r, ids in enumerate(served):
            token[r, 0] = ids[min(k, len(ids) - 1)]
        logits, caches, routing = generator._decode(
            generator.params, jnp.asarray(token), caches[0][2], caches)
        said.append({name: value[:, :len(group)]
                     for name, value in routing.items()})
        for r, ids in enumerate(served):
            if k + 1 < len(ids):
                diffs[r].append(row_diff(logits, refs[r], r, k + 1))
    routed = sum(routed_mlp(cfg.mlp_kind(i)) for i in range(cfg.num_layers))
    selecting = sum(cfg.selects(cfg.attention_kind(i))
                    for i in range(cfg.num_layers))
    out = []
    for r, ids in enumerate(served):
        steps = said[:len(ids) - 1]
        experts = -np.ones((routed, len(ids), cfg.num_experts_per_tok),
                           np.int32)
        selected = jnp.zeros((selecting, len(ids), cfg.index_topk),
                             jnp.int32)
        real = np.zeros((selecting, len(ids)), np.int32)
        if steps:
            experts[:, 1:] = np.asarray(jnp.stack(
                [s["experts"][:, r] for s in steps], axis=1))
            selected = selected.at[:, 1:].set(jnp.stack(
                [s["selected"][:, r] for s in steps], axis=1))
            real[:, 1:] = np.asarray(jnp.stack(
                [s["selected_real"][:, r] for s in steps], axis=1))
        out.append((np.asarray(jnp.stack(diffs[r]), np.float64), experts,
                    selected, real))
    return out


def _selected_in_common(got, got_real, want, want_real, positions):
    """(layers, served) int: of the positions a served position's query
    attended over in each selecting layer (``got`` (layers, served, k),
    the first ``got_real`` real), how many the reference selects too
    (``want``, ``want_real``).  ``positions``: how many the sequence has."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one_layer(got, got_real, want, want_real):
        n, k = got.shape
        rows = jnp.arange(n)[:, None]
        ranks = jnp.arange(k)[None, :]
        # the reference's selection, a row a served position; what is not
        # real goes to a column of its own
        where = jnp.where(ranks < want_real[:, None], want, positions)
        wanted = jnp.zeros((n, positions + 1), bool).at[rows, where].set(
            True)[:, :positions]
        hits = jnp.take_along_axis(wanted, got, axis=1) & \
            (ranks < got_real[:, None])
        return hits.sum(-1)

    return np.stack([np.asarray(one_layer(*layer)) for layer in zip(
        got, jnp.asarray(got_real), want, jnp.asarray(want_real))])


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import gc
    import jax.numpy as jnp
    # the engine's resident caches go now and not at some later
    # collection: the reference's longest sequence needs their room
    gc.collect()
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = lm._pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    step = config["reference_length_step"]
    limits = {name: config[name] for name in (
        "logit_margin", "logit_margin_flipped", "logit_atol",
        "logit_atol_flipped", "logit_mean_atol", "min_choice_agreement",
        "min_selection_agreement")}
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
    selected_common = selected_wanted = 0
    diff_sum = 0.0
    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        refs, wants, deficits = [], [], []
        for rec in group:
            n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
            length = -(-(n_prompt + n_out) // step) * step
            if length > serve["served_context"]:
                raise ValueError("a checked request's context does not fit "
                                 "the served context")
            ids = np.zeros((length,), np.int32)
            ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
            # the row that predicts served token k: position n_prompt-1+k
            rows = length - (n_prompt - 1)
            logits, ref_experts, (ref_selected, ref_real) = \
                reference.logits_experts_selections(
                    weights, ids, rows=(n_prompt - 1, rows))
            logits = logits[:n_out]
            served = jnp.asarray(rec["tokens"], jnp.int32)
            chosen = jnp.take_along_axis(logits, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(logits.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            wants.append((np.asarray(ref_experts)[:, :n_out],
                          ref_selected[:, :n_out],
                          np.asarray(ref_real)[:, :n_out], length))
        replayed = _replay(generator, scatter_row, engine_rows, group, refs)
        del refs
        for deficit, want, got in zip(deficits, wants, replayed):
            want_experts, want_selected, want_real, length = want
            diff, got_experts, got_selected, got_real = got
            # which of the reference's experts the program chose too
            found = (want_experts[..., :, None] ==
                     got_experts[..., None, :]).any(-1)
            known = (got_experts >= 0).all(-1).all(0)
            # and how many of its selected positions
            hits = _selected_in_common(got_selected, got_real,
                                       want_selected, want_real, length)
            same = found.all(-1).all(0) & \
                ((hits == want_real) & (got_real == want_real)).all(0)
            common += int(found[:, known].sum())
            choices += want_experts[:, known].size
            selected_common += int(hits[:, known].sum())
            selected_wanted += int(want_real[:, known].sum())
            positions += len(deficit)
            flipped += int((~same & known).sum())
            diff_sum += float(diff.sum())
            for name, values in (("deficit", deficit), ("diff", diff)):
                for which, where in (("_same", same), ("_flipped", ~same)):
                    if where.any():
                        worst[name + which] = max(
                            worst[name + which], float(values[where].max()))
            bad += int(
                (deficit[same] > limits["logit_margin"]).sum() +
                (deficit[~same] > limits["logit_margin_flipped"]).sum() +
                (diff[same] > limits["logit_atol"]).sum() +
                (diff[~same] > limits["logit_atol_flipped"]).sum() +
                (~np.isfinite(deficit)).sum() + (~np.isfinite(diff)).sum())
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_positions": positions, "over_margin": bad,
            "long_context_checked": has_long,
            "short_context_checked": has_short,
            "positions_with_a_flip": flipped,
            "choice_agreement": common / choices if choices else 0.0,
            "selection_agreement": selected_common / selected_wanted
            if selected_wanted else 0.0,
            "worst_logit_deficit": worst["deficit_same"],
            "worst_logit_deficit_flipped": worst["deficit_flipped"],
            "worst_logit_diff": worst["diff_same"],
            "worst_logit_diff_flipped": worst["diff_flipped"],
            "mean_logit_diff": diff_sum / positions if positions
            else float("inf"), **limits}


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import INDEXER_SCOPE, SELECT_SCOPE
    mla = ctx.load("drivers", "serve_mla")
    scopes = {"indexer": INDEXER_SCOPE, "latent_select": SELECT_SCOPE}
    load, read_program_trace = ctx.load, mla.read_program_trace

    def with_scopes(read, at):
        """``read`` with this model's scopes beside those it is given."""
        def reading(*args):
            args = list(args)
            args[at] = {**args[at], **scopes}
            return read(*args)
        return reading

    def loading(kind, name):
        module = load(kind, name)
        if (kind, name) == ("drivers", "serve_lm"):
            module.read_decode_trace = with_scopes(
                module.read_decode_trace, 2)
        return module

    ctx.load = loading
    vars(mla).update(
        reference_settings=reference_settings, _closed_loop=_closed_loop,
        arithmetic_mla=arithmetic_dsa, _check=_check,
        read_program_trace=with_scopes(read_program_trace, 3))
    obs = mla.run(ctx)
    # a selecting layer's programs fall short of the floor on the selection
    # by the limits alone: the floor is part of ``correct``
    obs["correct"] = bool(
        obs["correct"] and obs["checks"]["selection_agreement"] >=
        ctx.config["min_selection_agreement"])
    return obs
