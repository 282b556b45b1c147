"""Driver of the serving cells whose model is a Jamba decoder
(``model_type`` jamba): Mamba-1 mixers whose states (``mamba_d_state``
values a channel a row, and the convolution's last positions) ride in the
list of caches beside the caches of a few attention layers of ONE
key/value head, a gated MLP behind every mixer, nothing routed, the whole
model on this chip.

It is ``drivers/serve_mla.py``'s run with the pieces that know the model
put in its place, and nothing else: the same window, clocks, warm-up,
traces and ``obs``, so that every reader of the serving cells works on it.
``run`` loads a copy of that module of its own (``ctx.load`` makes a fresh
one each time) and binds, in that copy:

* ``model_config``: the configuration's own keys through
  ``config_from_hf``: nothing is held in shares;
* ``reference_settings``: what ``references/jamba_decoder.py`` needs of
  the configuration file's keys;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_jamba.py`` (no layer
  routes);
* ``balance_routers`` -> ``spread_tied_head``: ``serve_mimo.spread_head``
  for a head tied to the table: the table made orthogonal to the mean of
  the head's input, so that the rows' greedy continuations do not all
  repeat one token;
* ``_closed_loop`` -> ``serve_dsa._closed_loop``: every caller on a part of
  the stream of its own;
* ``_check``, below: ``serve_ssm._check``'s comparison of a model whose
  layers hold states (the window's own compiled ``_chunk_prefill``,
  ``_scatter_row`` and ``_decode`` over all the engine's rows replayed at
  the window's shapes against the reference's full forward pass; every
  served position held to ``logit_atol`` and ``logit_margin``, the mean to
  ``logit_mean_atol``, and the STATES themselves: every Mamba layer's
  ``h`` of every checked row after its prefill and after its last decoded
  position against the state the reference's loop over positions has
  there, over the state values that REMEMBER: those that, by their own
  ``A_log`` and their channel's ``dt_proj`` bias, hold more than
  ``state_memory_over`` positions; a layer's distance is the norm of the
  difference over the norm of the reference's, over those values, a
  reading is the worst layer's, and every reading is held to
  ``state_rtol_each``).  The requests are picked by ``_pick``: the longest
  prompt, whose context has to pass ``serve.check_context_over`` (32,768
  in the cell), the shortest prompt, which has to be under
  ``serve.check_context_under``, and others drawn from the seed.

  And one thing of its own, the ATTENTION.  The attention layers of a
  model made from a seed look at tens of thousands of positions almost
  evenly, every position's values share one large common part, and the
  layers' part of the stream is a hundredth of the mixers': a chunk whose
  attention reads a key block too few moved no logit and no state
  (``controls_jamba.py`` (g), my chip runs, PR 61).  Under the served
  weights nothing the timed programs give back says what their attention
  read.  So the longest checked request goes through the SAME compiled
  programs once more (``_replay``: the chunk step over its 60 chunks,
  ``_scatter_row``, ``_decode`` over all rows; the weights are arguments
  of those programs, not constants of them) under weights made to show
  the attention and nothing else, ``attention_probe``: the served weights
  with every mixer's ``out_proj`` and every MLP's ``down`` at zero (the
  stream is the token's embedding and what the two attention layers add;
  no common part, nothing forgets) and the attention layers' query
  projections ``PROBE_SHARPNESS`` times their own (a seeded score is then
  N(0, sharpness^2), and a query's weight lies on a few of its 61,440
  keys, anywhere in the context, as a trained layer's does, and not on
  all of them evenly).  The reference gets the same weights, less the
  layers that now add nothing, and the same ids.  Held: the logits at
  every served position (``probe_logit_rtol``: the mean over the
  vocabulary of |program's - reference's| over the mean of
  |reference's|, the worst position), which every tick's attention in
  both layers has to be right for; and the K and V that the replayed
  chunk steps and ticks WROTE, both layers, every position the request
  holds, against the reference's keys and values (``probe_kv_rtol``: a
  position's distance over the root mean square of the reference's rows'
  norms, the worst position of either array of either layer): the second
  layer's are the first layer's attention at every position of the
  prompt (the chunk's kernel, across 60 chunks) and of the answer (the
  tick's).

The four metrics of the Mamba-1 mixers read the program's own table of
device time by part, the registry and the configuration file
(``metrics/s6_*.py``), and need nothing of this driver.
"""
import numpy as np

from chipbench import arithmetic_jamba


def model_config(config: dict, **overrides):
    """The program's configuration of a configuration file: its keys as
    Hugging Face names them, whole."""
    from alpa_tpu.model.gpt_model import config_from_hf
    return config_from_hf(config, **overrides)


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"heads": config["num_attention_heads"],
            "eps": config["rms_norm_eps"],
            "query_block": config["reference_query_block"],
            "channel_blocks": config["reference_channel_blocks"]}


# uniform token ids the head's mean input is taken over
HEAD_TOKENS = 1024


def spread_tied_head(model, params, key, vocab):
    """``serve_mimo.spread_head`` where the head is the embedding table
    (``tie_word_embeddings``): every row of the table made orthogonal to
    the mean of the head's input (the final norm's output over
    ``HEAD_TOKENS`` uniform token ids), ``E -= (E u) u^T``.  Random weights
    give every position's final hidden state a common direction, and ``E``
    of it is a preference for a few tokens whatever the context, which a
    trained head does not have.  The embedding moves with the head; the
    mean is of the model before the move."""
    import jax
    import jax.numpy as jnp
    ids = jax.random.randint(key, (1, min(HEAD_TOKENS, model.config.seq_len)),
                             4, vocab)
    hidden = jax.jit(lambda p: model.apply(p, ids, return_hidden=True))(params)
    mean = hidden.astype(jnp.float32).mean((0, 1))
    u = mean / jnp.linalg.norm(mean)

    def moved(path, x):
        if [p.key for p in path[-2:]] != ["wte", "embedding"]:
            return x
        w = x.astype(jnp.float32)
        return (w - jnp.outer(w @ u, u)).astype(x.dtype)

    # the same tree, its big leaves shared
    return jax.tree_util.tree_map_with_path(moved, params)


def _pick(done, mix, seed, context_over, prompt_under):
    """The requests to check: the longest PROMPT (whose context must pass
    ``context_over``), the shortest prompt (which must be under
    ``prompt_under``), and others drawn from the seed, ``check_requests``
    in all."""
    if not done:
        return [], False, False
    prompts = [len(rec["prompt_ids"]) for rec in done]
    longest, shortest = int(np.argmax(prompts)), int(np.argmin(prompts))
    picks = [longest, shortest]
    for i in np.random.default_rng(seed).permutation(len(done)):
        if len(picks) >= mix["check_requests"]:
            break
        if int(i) not in picks:
            picks.append(int(i))
    picks = list(dict.fromkeys(picks))
    return ([done[i] for i in picks],
            prompts[longest] + len(done[longest]["tokens"]) > context_over,
            prompts[shortest] < prompt_under)


def keeps_states(load):
    """``serve_ssm._KeepsStates`` (a generator that ``serve_lm._replay``
    drives as it drives any, and that keeps, of the row of each checked
    request, every ``ssm_layers`` entry's ssm state after the row's
    prefill and after the last token the replay feeds it: ``states[r]``
    (ssm layers, 2, N, D)) with two things more: it keeps the resident
    caches as the last tick left them (``caches``), and ``_decode``'s
    routing says that no layer routes."""
    class KeepsStates(load("drivers", "serve_ssm")._KeepsStates):
        caches = None

        def _decode(self, *args):
            import jax.numpy as jnp
            logits, caches, _routing = super()._decode(*args)
            # (the tick before's were donated to this one)
            self.caches = caches
            return logits, caches, {
                "experts": jnp.zeros((0, logits.shape[0], 0), jnp.int32)}

    return KeepsStates


# how many times their own the probe's query projections are: a seeded
# score is N(0, 1), a softmax over n such scores lies on all n keys, and at
# N(0, s^2) about n exp(-s^2) keys share a query's weight: one or two of
# 61,440
PROBE_SHARPNESS = 3.3


def attention_probe(params):
    """The served parameters (the same tree, its big leaves shared) made
    to show the attention alone: every mixer's ``out_proj`` and every
    MLP's ``down`` one array of zeros, the attention layers' query
    projections ``PROBE_SHARPNESS`` times their own (module docstring)."""
    import jax.numpy as jnp
    tree = dict(params["params"])
    zeros = {}

    def silent(x):
        # (one array for every layer's)
        if x.shape not in zeros:
            zeros[x.shape] = jnp.zeros_like(x)
        return zeros[x.shape]

    i = 0
    while f"h{i}" in tree:
        block = dict(tree[f"h{i}"])
        block["mlp"] = {**block["mlp"], "down": {
            "kernel": silent(block["mlp"]["down"]["kernel"])}}
        if "ssm" in block:
            block["ssm"] = {**block["ssm"], "out_proj": {
                "kernel": silent(block["ssm"]["out_proj"]["kernel"])}}
        else:
            qkv = block["attn"]["qkv"]["kernel"]         # [q | k | v]
            n_q = block["attn"]["out"]["kernel"].shape[0]
            sharp = (qkv[:, :n_q] * PROBE_SHARPNESS).astype(qkv.dtype)
            block["attn"] = {**block["attn"], "qkv": {
                "kernel": jnp.concatenate([sharp, qkv[:, n_q:]], axis=1)}}
        tree[f"h{i}"] = block
        i += 1
    return {**params, "params": tree}


def kv_diff(caches, wanted, positions: int):
    """The K and V of a row's attention layers as the replayed programs
    wrote them (``caches``: a layer (keys, values), each (S, D), the ONE
    key/value head folded) against the reference's (``wanted``: a layer
    (k, v), each (S', 1, D)), over the row's first ``positions``: a
    position's distance over the root mean square of the reference's
    rows' norms, the worst position of any array."""
    import jax.numpy as jnp
    worst = 0.0
    for got_layer, want_layer in zip(caches, wanted):
        for got, want in zip(got_layer, want_layer):
            want = want[:positions].reshape(positions, -1)
            off = jnp.linalg.norm(
                got[:positions].astype(jnp.float32) - want, axis=-1)
            size = jnp.sqrt(jnp.square(want).sum(-1).mean())
            # (a NaN is over every limit)
            worst = max(worst, float(jnp.where(
                jnp.isnan(off).any(), jnp.inf, off.max() / size)))
    return worst


LIMITS = ("logit_margin", "logit_atol", "logit_mean_atol",
          "state_rtol_each", "probe_logit_rtol", "probe_kv_rtol")


def _wanted(reference, weights, rec, step: int, served_context: int):
    """What the reference says of a request: its logits at the positions
    that predict the served tokens (served, V), every Mamba layer's state
    after the prompt and after all but the last served token, and every
    attention layer's keys and values."""
    n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
    length = -(-(n_prompt + n_out) // step) * step
    if length > served_context:
        raise ValueError("a checked request's context does not fit the "
                         "served context")
    ids = np.zeros((length,), np.int32)
    ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
    # the row that predicts served token k: position n_prompt-1+k
    logits, states, caches = reference.logits_states_and_caches(
        weights, ids, rows=(n_prompt - 1, length - (n_prompt - 1)),
        at=(n_prompt, n_prompt + max(n_out - 1, 0)))
    return logits[:n_out], states, caches


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import copy
    import gc
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import kv_cache_kinds
    # the engine's resident caches go now and not at some later
    # collection: the reference's longest sequence needs their room
    gc.collect()
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = _pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    sizes = config["reference_length_step"], serve["served_context"]
    kinds = kv_cache_kinds(generator.config)
    ssm_layers = [i for i, kind in enumerate(kinds) if kind == "ssm"]
    full_layers = [i for i, kind in enumerate(kinds) if kind == "full"]
    KeepsStates = keeps_states(ctx.load)

    # how many positions a state value holds, by its own parameters: the
    # reciprocal of its decay a position at its channel's bias,
    # softplus(dt_bias[d]) exp(A_log[n, d])
    blocks = generator.params["params"]
    remembers = jnp.asarray(np.stack([1.0 / np.asarray(
        jax.nn.softplus(blocks[f"h{i}"]["ssm"]["dt_bias"])[None, :] *
        jnp.exp(blocks[f"h{i}"]["ssm"]["A_log"])) for i in ssm_layers]) >
        config["state_memory_over"])

    @jax.jit
    def state_diff(got, want):
        """Every layer's distance over the state values that remember
        (layers, N, D), relative to the reference's state, after the
        prefill and at the end: (ssm layers, 2)."""
        held = remembers[:, None]
        off = jnp.sqrt(jnp.where(held, jnp.square(got - want), 0.0).sum(
            (-2, -1)))
        size = jnp.sqrt(jnp.where(held, jnp.square(want), 0.0).sum((-2, -1)))
        return off / (size + 1e-30)

    limits = {name: config[name] for name in LIMITS}
    worst = {"deficit": 0.0, "diff": 0.0}
    positions = bad = 0
    diff_sum = 0.0
    state_diffs = []
    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        refs, deficits, states = [], [], []
        for rec in group:
            logits, ref_states, _caches = _wanted(reference, weights, rec,
                                                  *sizes)
            served = jnp.asarray(rec["tokens"], jnp.int32)
            chosen = jnp.take_along_axis(logits, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(logits.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            states.append(jnp.stack(ref_states))
        keeping = KeepsStates(generator, [rec["tokens"] for rec in group],
                              ssm_layers)
        replayed = lm._replay(keeping, scatter_row, engine_rows, group, refs)
        del refs
        for deficit, want_state, (diff, _), got_state in zip(
                deficits, states, replayed, keeping.states):
            state_diffs.append(np.asarray(
                state_diff(got_state, want_state), np.float64).T)
            diff_sum += float(diff.sum())
            for name, values, kind in (
                    ("deficit", deficit, "logit_margin"),
                    ("diff", diff, "logit_atol")):
                if float(values.max()) > worst[name]:
                    # for the record: where (the request's context, the
                    # served position) and what its neighbours read
                    k = int(values.argmax())
                    worst[name + "_at"] = [
                        positions, k, values[max(0, k - 2):k + 3].round(
                            5).tolist()]
                worst[name] = max(worst[name], float(values.max()))
                # (a NaN is over every limit)
                bad += int((~(values <= limits[kind])).sum())
            positions += len(deficit)
        del keeping
    # (requests, [after the prefill, at the end], ssm layers); a reading
    # is the worst layer's, and every reading is under the limit
    by_layer = np.asarray(state_diffs).reshape((-1, 2, len(ssm_layers)))
    held = by_layer.max(-1, initial=0.0)
    bad += int((~(held <= limits["state_rtol_each"])).sum())

    # the attention: the longest request once more through the same
    # programs, under the weights that show it (module docstring)
    probe = {"logit": float("inf"), "kv": float("inf")}
    if picked:
        rec = picked[0]
        shown = attention_probe(generator.params)
        shown_weights = ref_mod.weights_from_program(shown)
        # (a mixer whose out_proj is zero and the MLP behind it, whose
        # down is zero, leave the stream as it came)
        shown_weights["blocks"] = [b for b in shown_weights["blocks"]
                                   if b["kind"] == "attention"]
        logits, _states, wanted = _wanted(reference, shown_weights, rec,
                                          *sizes)
        sighted = copy.copy(generator)
        sighted.params = shown
        keeping = KeepsStates(sighted, [rec["tokens"]], ssm_layers)
        (diff, _), = lm._replay(keeping, scatter_row, engine_rows, [rec],
                                [logits])
        probe["logit"] = float(
            (diff / np.asarray(jnp.abs(logits).mean(-1), np.float64)).max())
        if keeping.caches is not None:
            probe["kv"] = kv_diff(
                [[array[0] for array in keeping.caches[i][:2]]
                 for i in full_layers], wanted,
                len(rec["prompt_ids"]) + len(rec["tokens"]) - 1)
    bad += int(not probe["logit"] <= limits["probe_logit_rtol"])
    bad += int(not probe["kv"] <= limits["probe_kv_rtol"])
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_prompts": [len(rec["prompt_ids"]) for rec in picked],
            "checked_positions": positions, "over_margin": bad,
            "long_context_checked": has_long,
            "short_context_checked": has_short,
            # nothing routes: the one choice a layer has is the reference's
            "choice_agreement": 1.0,
            "worst_logit_deficit": worst["deficit"],
            "worst_logit_diff": worst["diff"],
            # [checked positions before its request, served position,
            # the readings around it]
            "worst_logit_deficit_at": worst.get("deficit_at"),
            "worst_logit_diff_at": worst.get("diff_at"),
            # a request a row, [after the prefill, at the end], a layer
            "state_diffs_by_layer": by_layer.round(5).tolist(),
            "mean_logit_diff": diff_sum / positions if positions
            else float("inf"),
            # a request a row: [after the prefill, after its last token]
            "state_diffs": held.round(6).tolist(),
            "worst_state_diff": float(held.max()) if held.size
            else float("inf"),
            "probe_logit_diff": probe["logit"],
            "probe_kv_diff": probe["kv"],
            "probe_sharpness": PROBE_SHARPNESS,
            "values_that_remember": int(remembers.sum()),
            "state_memory_over": config["state_memory_over"], **limits}


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import Mamba1  # noqa: F401
    load = ctx.load
    mla = load("drivers", "serve_mla")
    vars(mla).update(
        model_config=model_config,
        reference_settings=reference_settings,
        arithmetic_mla=arithmetic_jamba,
        balance_routers=spread_tied_head,
        _closed_loop=load("drivers", "serve_dsa")._closed_loop,
        _check=_check)
    return mla.run(ctx)
