"""Driver of the serving cells whose model is a MiMo-V2-Flash decoder
(``model_type`` mimo_v2_flash): window layers of a short ring with a learned
sink beside full layers, each kind with key/value heads and a rotary base of
its own, keys wider than values, and routed experts of which this chip holds
one share.

It is ``drivers/serve_mla.py``'s run with the pieces that know the model
put in its place, and nothing else: the same window, clocks, warm-up,
traces and ``obs`` (and its ``model_config``: the router at its published
width, ``share_index``'s experts held), so that every reader of the serving
cells works on it.  ``run`` loads a copy of that module of its own
(``ctx.load`` makes a fresh one each time) and binds, in that copy:

* ``reference_settings``: what ``references/mimo_v2_flash_decoder.py`` needs
  of the configuration file's keys;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_mimo.py``: how many layers
  route and what one expert's matrices weigh;
* ``balance_routers`` -> ``balance``, below: ``serve_lm.balance_router_biases``
  (the published router has a bias that chooses and does not weigh, set by
  load and not by gradient, as Trinity's is and for the reason that file
  gives) and then ``spread_head``: the head made orthogonal to the mean of
  its input.  At these widths a random-weight model's final hidden states
  share one direction (the mean is 0.92 to 0.99 of their length), the head
  of that direction is one token, and every row's greedy continuation
  repeats it: the decode's rows then sit on 2.9 to 5.4 of this chip's 16
  experts a layer a tick, by the seed, where rows that say different things
  touch some 10, and a window's tokens spread by 3.4 % between the
  quartiles of six runs (PERF.md, PR 51).  Nothing that tells one token's
  logits from another's changes;
* ``_closed_loop`` -> ``serve_dsa._closed_loop``: every caller on a part of
  the stream of its own (prompts this long make a race between two callers
  worth several per cent of a window, ``serve_dsa.py`` says how much);
* ``read_program_trace`` and (through ``ctx.load``) ``serve_lm``'s
  ``read_decode_trace``: both also sum the device events under the
  program's scopes ``window_core`` and ``full_core``
  (``obs["decode_trace"]``, ``obs["chunk_trace"]``: ``window_core_s``,
  ``full_core_s``);
* ``_check``: ``serve_mla._check``'s comparison (the window's own compiled
  ``_chunk_prefill``, ``_scatter_row`` and ``_decode`` replayed at the
  window's shapes against the reference's full forward pass) with every
  checked request's reference run over its own context rounded up to
  ``reference_length_step`` positions, not over the mix's longest: at
  32,768 positions the reference is minutes a request.
"""
import numpy as np

from chipbench import arithmetic_mimo


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"heads": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "v_head_dim": config["v_head_dim"],
            "kv_heads": {"full": config["num_key_value_heads"],
                         "sliding": config["swa_num_key_value_heads"]},
            "theta": {"full": config["rope_theta"],
                      "sliding": config["swa_rope_theta"]},
            "rotary_dim": int(config["head_dim"] *
                              config["partial_rotary_factor"]),
            "window": config["sliding_window"],
            "value_scale": config["attention_value_scale"],
            "sink_kinds": [kind for kind, key in (
                ("full", "add_full_attention_sink_bias"),
                ("sliding", "add_swa_attention_sink_bias")) if config[key]],
            "pattern": arithmetic_mimo.pattern(config),
            "eps": config["layernorm_epsilon"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "route_scale": config["routed_scaling_factor"] or 1.0,
            "experts_first": config["share_index"] *
            config["n_routed_experts"],
            "query_block": config["reference_query_block"]}


# spread_head's batch of uniform token ids
HEAD_TOKENS = 2048


def spread_head(model, params, key, vocab):
    """The language-model head made orthogonal to the mean of its input
    (the final norm's output over ``HEAD_TOKENS`` uniform token ids): ``W -=
    u (u^T W)``, ``u`` the mean's direction, as ``serve_mla.balance_routers``
    does to a router and for its reason: random weights give every
    position's final hidden state a common direction, and ``W^T`` of it is
    a preference for a few tokens whatever the context, which a trained
    head does not have."""
    import jax
    import jax.numpy as jnp
    ids = jax.random.randint(key, (1, min(HEAD_TOKENS, model.config.seq_len)),
                             4, vocab)
    hidden = jax.jit(lambda p: model.apply(p, ids, return_hidden=True))(params)
    mean = hidden.astype(jnp.float32).mean((0, 1))
    u = mean / jnp.linalg.norm(mean)

    def moved(path, x):
        if path[-2].key != "lm_head":
            return x
        w = x.astype(jnp.float32)
        return (w - jnp.outer(u, u @ w)).astype(x.dtype)

    # the same tree, its big leaves shared
    return jax.tree_util.tree_map_with_path(moved, params)


def balance(biases):
    """``balance_routers`` of this driver: ``biases``
    (``serve_lm.balance_router_biases``), then ``spread_head``, each with a
    key of its own."""
    def balanced(model, params, key, vocab):
        import jax
        params = biases(model, params, key, vocab)
        return spread_head(model, params, jax.random.fold_in(key, 1), vocab)
    return balanced


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
        "logit_atol_flipped", "logit_mean_atol", "min_choice_agreement")}
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
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
            logits, ref_experts = reference.logits_and_experts(
                weights, ids, rows=(n_prompt - 1, length - (n_prompt - 1)))
            logits = logits[:n_out]
            served = jnp.asarray(rec["tokens"], jnp.int32)
            chosen = jnp.take_along_axis(logits, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(logits.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            wants.append(np.asarray(ref_experts)[:, :n_out])
        replayed = lm._replay(generator, scatter_row, engine_rows, group,
                              refs)
        del refs
        for deficit, want, (diff, got) in zip(deficits, wants, replayed):
            # which of the reference's experts the program chose too
            found = (want[..., :, None] == got[..., None, :]).any(-1)
            same = found.all(-1).all(0)
            known = (got >= 0).all(-1).all(0)
            common += int(found[:, known].sum())
            choices += want[:, known].size
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
            "worst_logit_deficit": worst["deficit_same"],
            "worst_logit_deficit_flipped": worst["deficit_flipped"],
            "worst_logit_diff": worst["diff_same"],
            "worst_logit_diff_flipped": worst["diff_flipped"],
            "mean_logit_diff": diff_sum / positions if positions
            else float("inf"), **limits}


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import FULL_CORE_SCOPE, WINDOW_CORE_SCOPE
    mla = ctx.load("drivers", "serve_mla")
    scopes = {"window_core": WINDOW_CORE_SCOPE, "full_core": FULL_CORE_SCOPE}
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
        reference_settings=reference_settings, arithmetic_mla=arithmetic_mimo,
        balance_routers=balance(
            load("drivers", "serve_lm").balance_router_biases),
        _closed_loop=load("drivers", "serve_dsa")._closed_loop,
        _check=_check,
        read_program_trace=with_scopes(read_program_trace, 3))
    return mla.run(ctx)
