"""Driver of the serving cells whose model is an EvaByte decoder
(``model_type`` evabyte): attention whose ONE softmax runs over the exact
keys of the query's own aligned window and over a learned summary of every
chunk of the windows before it, a cache that holds both kinds of row in
one pair of arrays and is written twice, a float32 residual stream, norms
with a unit offset and several prediction heads of one matrix; one of four
pipeline stages of the published depth on this chip.

It is ``drivers/serve_mla.py``'s run with the pieces that know the model
put in its place, and nothing else: the same window, clocks, warm-up,
traces and ``obs``, so that every reader of the serving cells works on it.
``run`` loads a copy of that module of its own (``ctx.load`` makes a fresh
one each time) and binds, in that copy:

* ``model_config``: the configuration's own keys through
  ``config_from_hf``: nothing is held in shares;
* ``reference_settings``: what ``references/evabyte_decoder.py`` needs of
  the configuration file's keys;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_evabyte.py`` (no layer
  routes);
* ``balance_routers`` -> ``seeded_weights``: what of the seed's weights
  would hide a fault is drawn again (the norms' stored weights at
  ``NORM_WEIGHT_STD`` and not at 0; the pooling vectors at ``POOLING_STD``,
  so that the largest of a chunk's pooling weights is some 0.3 to 0.5 and
  a pooling is no plain mean), then ``serve_mimo.spread_head``;
* ``_closed_loop`` -> ``serve_dsa._closed_loop``: every caller on a part of
  the stream of its own;
* ``_check``, below.

``_check``: the window's own compiled ``_chunk_prefill``, ``_scatter_row``
and ``_decode`` over all the engine's rows replayed at the window's shapes
(``_replay``) against the reference's full forward pass.  The requests are
picked by ``_pick``: the longest context, which has to pass
``serve.check_context_over`` (twelve windows of summaries under one
query), one whose DECODE crosses a window's edge (a served position ``t``
with ``t % window == 0``: the tick after a rollover sees a window's worth
of new summaries and one exact key), and others drawn from the seed.
Held:

* every served position's logits, all the prediction heads' a tick gives
  (the chunk step hands the engine the first head's of a prompt's last
  position, so a request's first served position is held on that head
  alone): ``logit_atol`` on the mean absolute difference over the
  vocabulary, the worst head; ``logit_margin`` on the reference's first
  head's logit of the served byte under its largest; ``logit_mean_atol``
  on the mean over everything;
* what the replayed programs WROTE, every layer, when the row's last
  served byte but one has been fed: the summaries of every full chunk the
  row holds (those of its current window too, which nothing has read yet)
  and the rows of its current window, against the reference's pooled and
  turned keys and its values (``cache_rtol``: a slot's distance over the
  root mean square of the reference's slots' norms of that kind, the worst
  slot of any array of any layer).  A prompt ends inside a chunk 15 times
  of 16, so the summary the prefill began and the ticks finished is among
  them;
* and the ATTENTION by itself.  PR 61 found that a seeded model's attention
  at long contexts looks at thousands of keys almost evenly and that every
  value shares one large common part, so a tick that read a block of
  summaries too few moved no logit.  The longest checked request goes
  through the SAME compiled programs once more (the weights are arguments
  of those programs, not constants of them) under ``attention_probe``'s
  weights: every MLP's ``down`` at zero and the output projection of every
  layer but the first ``PROBE_LAYERS`` at zero (the stream is the byte's
  embedding and what those layers' attention adds), their query
  projections ``PROBE_SHARPNESS`` times their own, so that a query's
  weight lies on a few of its visible keys, summaries among them.  The
  reference gets the same weights, less the layers that now add nothing.
  Held: the logits at every served position (``probe_logit_rtol``: the
  mean over the vocabulary of |program's - reference's| over the mean of
  |reference's|, the worst head of the worst position) and what those
  layers wrote (``probe_cache_rtol``, measured as ``cache_rtol`` is: the
  second layer's rows and summaries are the first layer's attention at
  every position).

The four metrics of this configuration read the program's own table of
device time by part, the registry and the configuration file
(``metrics/eva_*.py``, ``metrics/summary_keys_pct.py``), and need nothing
of this driver.
"""
import zlib

import numpy as np

from chipbench import arithmetic_evabyte

# what the norms' stored weights are drawn at (a norm's gain is one more):
# at the published 0 a norm that forgot its offset gives nothing at all,
# and one that forgot its weight nothing wrong
NORM_WEIGHT_STD = 0.1
# what a head's two pooling vectors are drawn at, a channel: the pooling's
# logits ``s k . mu`` of unit-variance keys are then N(0, POOLING_STD^2),
# and the largest of a chunk's 16 weights is 0.36 on average (0.064 at the
# published init_std, a plain mean to three digits)
POOLING_STD = 1.5


def model_config(config: dict, **overrides):
    """The program's configuration of a configuration file: its keys as
    Hugging Face names them, whole."""
    from alpa_tpu.model.gpt_model import config_from_hf
    return config_from_hf(config, **overrides)


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"heads": config["num_attention_heads"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "window": config["window_size"], "chunk": config["chunk_size"],
            "pred_heads": config["num_pred_heads"],
            "query_block": config["reference_query_block"]}


def seeded_weights(load):
    """``balance_routers`` of this driver (module docstring)."""
    def seeded(model, params, key, vocab):
        import jax
        import jax.numpy as jnp

        def drawn(path, x):
            names = [p.key for p in path]
            at = jax.random.fold_in(
                key, zlib.crc32("/".join(names).encode()) % 2**31)
            if names[-1] == "scale":
                return (NORM_WEIGHT_STD * jax.random.normal(
                    at, x.shape, jnp.float32)).astype(x.dtype)
            if names[-1] in ("mu", "phi"):
                return (POOLING_STD * jax.random.normal(
                    at, x.shape, jnp.float32)).astype(x.dtype)
            return x

        params = jax.tree_util.tree_map_with_path(drawn, params)
        return load("drivers", "serve_mimo").spread_head(
            model, params, jax.random.fold_in(key, 1), vocab)
    return seeded


def crosses_an_edge(rec, window: int) -> bool:
    """Whether a tick of the request served a position ``t`` with ``t %
    window == 0``: its ticks' queries sit at ``prompt .. prompt + served -
    2``."""
    first = len(rec["prompt_ids"])
    last = first + len(rec["tokens"]) - 2
    return last // window > (first - 1) // window


def _pick(done, mix, seed, context_over, window):
    """The requests to check: the longest context (which must pass
    ``context_over``), one whose decode crosses a window's edge (the
    shortest such), and others drawn from the seed, ``check_requests`` in
    all."""
    def context(rec):
        return len(rec["prompt_ids"]) + len(rec["tokens"])
    if not done:
        return [], False, False
    by_length = sorted(range(len(done)), key=lambda i: context(done[i]))
    picks = [by_length[-1]]
    crossing = [i for i in by_length if crosses_an_edge(done[i], window)]
    picks += crossing[:1]
    for i in np.random.default_rng(seed).permutation(len(done)):
        if len(picks) >= mix["check_requests"]:
            break
        if int(i) not in picks:
            picks.append(int(i))
    picks = list(dict.fromkeys(picks))
    return ([done[i] for i in picks],
            context(done[by_length[-1]]) > context_over, bool(crossing))


# the layers whose attention the probe shows: the first's over keys that
# are projections of embeddings, the second's over keys that hold the
# first's attention at every position
PROBE_LAYERS = 2
# how many times their own the probe's query projections are: a seeded
# score is N(0, 1), a softmax over n such scores lies on all n keys, and at
# N(0, s^2) about n exp(-s^2) keys share a query's weight.  A summary is a
# mean of its chunk's keys under uneven weights and scores at about half
# an exact key's spread, so that the tick after a rollover (one exact key,
# every summary) lies on a few summaries
PROBE_SHARPNESS = 3.3


def attention_probe(params):
    """The served parameters (the same tree, its big leaves shared) made
    to show the attention alone (module docstring)."""
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
        attn = dict(block["attn"])
        if i < PROBE_LAYERS:
            qkv = attn["qkv"]["kernel"]                      # [q | k | v]
            n_q = attn["out"]["kernel"].shape[0]
            sharp = (qkv[:, :n_q] * PROBE_SHARPNESS).astype(qkv.dtype)
            attn["qkv"] = {"kernel": jnp.concatenate(
                [sharp, qkv[:, n_q:]], axis=1)}
        else:
            attn["out"] = {"kernel": silent(attn["out"]["kernel"])}
        block["attn"] = attn
        tree[f"h{i}"] = block
        i += 1
    return {**params, "params": tree}


def _wanted(reference, weights, rec, config):
    """What the reference says of a request: its logits at the positions
    that predict the served bytes (served, heads, V) and, of every layer
    (on the host), ``(k, v, k~, v~)``: the turned keys and the values of
    the row's current window when its last served byte but one has been
    fed, and the summaries of the full chunks it then holds."""
    import jax
    window, chunk = config["window_size"], config["chunk_size"]
    n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
    step = config["reference_length_step"]
    length = -(-(n_prompt + n_out) // step) * step
    if length > config["serve"]["served_context"]:
        raise ValueError("a checked request's context does not fit the "
                         "served context")
    ids = np.zeros((length,), np.int32)
    ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
    # the last position the programs were fed, and its window's start
    fed = n_prompt + n_out - 1
    start = (fed - 1) // window * window
    # the row that predicts served byte k: position n_prompt - 1 + k
    logits, caches = reference.logits_and_caches(
        weights, ids, rows=(n_prompt - 1, length - (n_prompt - 1)),
        keep=(start, min(window, length - start)))
    caches = [(k[:fed - start], v[:fed - start], k_sum[:fed // chunk],
               v_sum[:fed // chunk]) for k, v, k_sum, v_sum in caches]
    return logits[:n_out], jax.device_get(caches)


def _replay(generator, scatter_row, rows, group, refs):
    """``serve_lm._replay`` for a model of several prediction heads: the
    requests of ``group`` (at most ``rows``) once more through the
    programs the window ran, at the window's shapes: each prompt through
    the compiled chunk step, its caches and last logits into a row of
    resident caches of ``rows`` rows (the engine's ``scatter_row``), then
    ``_decode`` over all rows at once, every row fed the byte it served.
    ``refs``: a request's reference logits (served, heads, V) float32.

    A request: ``(diff, caches)``: at each served position and of each
    head the mean over the vocabulary of |program's logits - reference's|,
    (served, heads), NaN where the programs give no such logits (a
    request's first position, every head but the first); and the row's
    slice of every layer's two arrays (on the host) as they stood when
    its last served byte but one had been fed."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import init_kv_caches
    cfg = generator.config
    heads = cfg.num_pred_heads

    @jax.jit
    def first_diff(logits, ref, row):
        return jnp.abs(logits[row].astype(jnp.float32) - ref[0, 0]).mean()

    @jax.jit
    def heads_diff(pred, ref, row, at):
        return jnp.abs(pred[row].astype(jnp.float32) - ref[at]).mean(-1)

    @jax.jit
    def row_of(caches, row):
        return [(k[row], v[row]) for k, v, _i in caches]

    caches = [(k, v, jnp.zeros((rows,), jnp.int32))
              for k, v, _i in init_kv_caches(cfg, rows)]
    logits = jnp.zeros((rows, cfg.vocab_size), cfg.dtype)
    for r, rec in enumerate(group):
        prompt = np.asarray(rec["prompt_ids"], np.int32)
        last, row = generator._run_chunked_prefill(
            [prompt], jnp.asarray([len(prompt)], jnp.int32), 1)
        caches, logits = scatter_row(caches, row, logits, last, r)
    served = [rec["tokens"] for rec in group]
    nan = jnp.full((heads - 1,), jnp.nan, jnp.float32)
    diffs = [[jnp.concatenate([first_diff(logits, ref, r)[None], nan])]
             for r, ref in enumerate(refs)]
    kept = {r: jax.device_get(row_of(caches, r))
            for r, ids in enumerate(served) if len(ids) == 1}
    for k in range(max(map(len, served)) - 1):
        token = np.zeros((rows, 1), np.int32)
        for r, ids in enumerate(served):
            token[r, 0] = ids[min(k, len(ids) - 1)]
        logits, caches, said = generator._decode(
            generator.params, jnp.asarray(token), caches[0][2], caches)
        for r, ids in enumerate(served):
            if k + 1 < len(ids):
                diffs[r].append(heads_diff(said["pred_logits"], refs[r], r,
                                           k + 1))
            if k + 2 == len(ids):
                kept[r] = jax.device_get(row_of(caches, r))
    return [(np.asarray(jnp.stack(diffs[r]), np.float64), kept[r])
            for r in range(len(served))]


def cache_diff(got, wanted, config, layers=None):
    """What a row's arrays hold (``got``: a layer (keys, values), each
    (summaries + window, H D)) against the reference's (``wanted``: a layer
    ``(k, v, k~, v~)``): a slot's distance over the root mean square of
    the reference's slots' norms of its kind and array, ``{"summary",
    "window"}``: the worst slot of either array of any of ``layers`` (all
    of them)."""
    held = got[0][0].shape[0] - config["window_size"]
    worst = {"summary": 0.0, "window": 0.0}
    for layer, ((keys, values), (k, v, k_sum, v_sum)) in enumerate(
            zip(got, wanted)):
        if layers is not None and layer not in layers:
            continue
        for array, rows, pooled in ((keys, k, k_sum), (values, v, v_sum)):
            array = np.asarray(array, np.float32)
            for kind, have, want in (
                    ("summary", array[:len(pooled)], pooled),
                    ("window", array[held:held + len(rows)], rows)):
                if not len(want):
                    continue
                want = np.asarray(want, np.float32).reshape(len(want), -1)
                off = np.linalg.norm(have - want, axis=-1)
                size = np.sqrt(np.square(want).sum(-1).mean())
                # (a NaN is over every limit)
                reading = np.inf if np.isnan(off).any() else \
                    float(off.max() / size)
                worst[kind] = max(worst[kind], reading)
    return worst


LIMITS = ("logit_margin", "logit_atol", "logit_mean_atol", "cache_rtol",
          "probe_logit_rtol", "probe_cache_rtol")


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import copy
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
    picked, has_long, has_crossing = _pick(
        done, mix, ctx.seed, serve["check_context_over"],
        config["window_size"])
    limits = {name: config[name] for name in LIMITS}
    worst = {"deficit": 0.0, "diff": 0.0, "summary": 0.0, "window": 0.0}
    positions = bad = 0
    diff_sum, diff_count = 0.0, 0
    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        refs, deficits, wanted = [], [], []
        for rec in group:
            logits, caches = _wanted(reference, weights, rec, config)
            served = jnp.asarray(rec["tokens"], jnp.int32)
            first = logits[:, 0]
            chosen = jnp.take_along_axis(first, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(first.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            wanted.append(caches)
        replayed = _replay(generator, scatter_row, engine_rows, group, refs)
        del refs
        for deficit, want, (diff, got) in zip(deficits, wanted, replayed):
            given = ~np.isnan(diff)
            diff_sum += float(diff[given].sum())
            diff_count += int(given.sum())
            # a position's reading is its worst head's (a NaN among the
            # heads a tick gave is over every limit)
            by_position = np.where(given, diff, 0.0).max(-1)
            by_position[np.isnan(diff[:, 0]) | np.isnan(diff[1:]).any()] = \
                np.inf
            for name, values, kind in (
                    ("deficit", deficit, "logit_margin"),
                    ("diff", by_position, "logit_atol")):
                if float(values.max()) > worst[name]:
                    # for the record: where (checked positions before its
                    # request, the served position)
                    worst[name + "_at"] = [positions, int(values.argmax())]
                worst[name] = max(worst[name], float(values.max()))
                bad += int((~(values <= limits[kind])).sum())
            positions += len(deficit)
            for kind, reading in cache_diff(got, want, config).items():
                worst[kind] = max(worst[kind], reading)
                bad += int(not reading <= limits["cache_rtol"])

    # the attention: the longest request once more through the same
    # programs, under the weights that show it (module docstring)
    probe = {"logit": float("inf"), "summary": float("inf"),
             "window": float("inf")}
    if picked:
        rec = picked[0]
        shown = attention_probe(generator.params)
        shown_weights = ref_mod.weights_from_program(shown)
        # (a layer whose output projection and whose MLP's down are zero
        # leaves the stream as it came)
        shown_weights["blocks"] = shown_weights["blocks"][:PROBE_LAYERS]
        logits, want = _wanted(reference, shown_weights, rec, config)
        sighted = copy.copy(generator)
        sighted.params = shown
        (diff, got), = _replay(sighted, scatter_row, engine_rows, [rec],
                               [logits])
        size = np.asarray(jnp.abs(logits).mean(-1), np.float64)
        size[0, 1:] = np.nan
        probe["logit"] = float(np.nanmax(diff / size)) \
            if not np.isnan(diff[1:]).any() else float("inf")
        probe.update(cache_diff(got, want, config,
                                layers=range(PROBE_LAYERS)))
    bad += int(not probe["logit"] <= limits["probe_logit_rtol"])
    bad += sum(not probe[kind] <= limits["probe_cache_rtol"]
               for kind in ("summary", "window"))
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_prompts": [len(rec["prompt_ids"]) for rec in picked],
            "checked_positions": positions, "over_margin": bad,
            "long_context_checked": has_long,
            # (``serve_mla.run`` asks for it by this name: here, a checked
            # request whose decode crossed a window's edge)
            "short_context_checked": has_crossing,
            "edge_crossings_checked": sum(
                crosses_an_edge(rec, config["window_size"])
                for rec in picked),
            # nothing routes: the one choice a layer has is the reference's
            "choice_agreement": 1.0,
            "worst_logit_deficit": worst["deficit"],
            "worst_logit_diff": worst["diff"],
            "worst_logit_deficit_at": worst.get("deficit_at"),
            "worst_logit_diff_at": worst.get("diff_at"),
            "mean_logit_diff": diff_sum / diff_count if diff_count
            else float("inf"),
            "worst_summary_diff": worst["summary"],
            "worst_window_row_diff": worst["window"],
            "probe_logit_diff": probe["logit"],
            "probe_summary_diff": probe["summary"],
            "probe_window_row_diff": probe["window"],
            "probe_sharpness": PROBE_SHARPNESS,
            "probe_layers": PROBE_LAYERS, **limits}


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import eva_pool  # noqa: F401
    load = ctx.load
    mla = load("drivers", "serve_mla")
    vars(mla).update(
        model_config=model_config,
        reference_settings=reference_settings,
        arithmetic_mla=arithmetic_evabyte,
        balance_routers=seeded_weights(load),
        _closed_loop=load("drivers", "serve_dsa")._closed_loop,
        _check=_check)
    return mla.run(ctx)
