"""Driver of the serving cells whose model is a Nemotron-H decoder
(``model_type`` nemotron_h): layers that are a Mamba-2 mixer, an attention
or routed experts ALONE, the mixers' states (a matrix a head a row, and the
convolution's last positions) riding in the list of caches beside the
attention layers' caches, ungated experts of which this chip holds one
share.

It is ``drivers/serve_mla.py``'s run with the pieces that know the model
put in its place, and nothing else: the same window, clocks, warm-up,
traces and ``obs`` (and its ``model_config``: the router at its published
width, ``share_index``'s experts held), so that every reader of the serving
cells works on it.  ``run`` loads a copy of that module of its own
(``ctx.load`` makes a fresh one each time) and binds, in that copy:

* ``reference_settings``: what ``references/nemotron_h_decoder.py`` needs
  of the configuration file's keys;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_nemotron.py``: how many
  layers route and what one expert's TWO matrices weigh;
* ``balance_routers`` -> ``serve_mimo.balance`` of
  ``serve_lm.balance_router_biases``: the routers' biases set by load (the
  published router has a bias that chooses and does not weigh, set by load
  and not by gradient, as Trinity's is), then the head made orthogonal to
  the mean of its input, so that the rows' greedy continuations do not all
  repeat one token (``serve_mimo.spread_head`` says what that costs);
* ``_closed_loop`` -> ``serve_dsa._closed_loop``: every caller on a part of
  the stream of its own;
* ``_check``, below: ``serve_hybrid._check``'s comparison of a model whose
  layers hold states (the window's own compiled ``_chunk_prefill``,
  ``_scatter_row`` and ``_decode`` over all the engine's rows replayed at
  the window's shapes against the reference's full forward pass; every
  served position held to ``logit_atol`` and ``logit_margin``, the mean to
  ``logit_mean_atol``, the experts chosen to ``min_choice_agreement``)
  with two things of its own.  The requests are picked by ``_pick``: what
  has to be among the checked is a PROMPT of several chunks that ends
  inside a padded one, not a long context.  And the STATES are compared
  themselves (``state_rtol``): every Mamba-2 layer's ssm state of every
  checked row, as the replayed programs hold it after the prefill and
  after the row's last decoded position, against the state the
  reference's loop over positions has there, a head at a time (the norm
  of the difference over the norm of the reference's), the worst of the
  heads that REMEMBER: those whose state, by their own ``dt_bias`` and
  ``A_log``, holds more than ``state_heads_over`` positions.  A state has no mask to hide it, but a model made from
  a seed forgets: most heads' states turn over within some tens of
  positions (``dt`` some hundredths under ``A`` of -1 to -64), so that a
  state reset at a chunk's edge, or kept in bfloat16, is gone from the
  logits a few hundred positions on, under the rounding of the bfloat16
  activations (my chip runs, PR 58: both read ``correct`` by the logits
  alone), and a fast head's state is its last few positions, as far from
  the reference's as a flipped expert upstream made them (up to 0.39 in a
  sound run).  The heads that remember, ``A`` near -1 under a small
  ``dt``, average such positions away (0.002-0.009 sound) and carry a
  fault of the state for hundreds of positions: their states show it.

The five metrics of the Mamba-2 mixers read the program's own table of
device time by part, the registry and the configuration file
(``metrics/ssm_*.py``), and need nothing of this driver.
"""
import numpy as np

from chipbench import arithmetic_nemotron


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"mamba_heads": config["mamba_num_heads"],
            "mamba_groups": config["n_groups"],
            "head_dim": config["head_dim"],
            "eps": config["layer_norm_epsilon"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "routed_scaling_factor": float(config["routed_scaling_factor"]),
            "experts_first": config["share_index"] *
            config["n_routed_experts"],
            "query_block": config["reference_query_block"]}


def _pick(done, mix, seed, prompt_over, context_under):
    """The requests to check: the longest PROMPT (which must pass
    ``serve.check_context_over``, held here against the prompt: several
    chunks, the last of them padded), the shortest context (under
    ``serve.check_context_under``: a prompt that ends inside its first
    chunk), and others drawn from the seed, ``check_requests`` in all."""
    def context(rec):
        return len(rec["prompt_ids"]) + len(rec["tokens"])
    if not done:
        return [], False, False
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt_ids"]))
    shortest = min(range(len(done)), key=lambda i: context(done[i]))
    picks = [longest, shortest]
    for i in np.random.default_rng(seed).permutation(len(done)):
        if len(picks) >= mix["check_requests"]:
            break
        if int(i) not in picks:
            picks.append(int(i))
    picks = list(dict.fromkeys(picks))
    return ([done[i] for i in picks],
            len(done[longest]["prompt_ids"]) > prompt_over,
            context(done[shortest]) < context_under)


class _KeepsStates:
    """A generator that ``serve_lm._replay`` drives as it drives any (the
    checked requests once more through the window's compiled chunk step,
    the engine's ``scatter_row`` and ``_decode`` over all rows, every row
    fed the token it served), and that keeps, of the row of each of
    ``served`` (the requests' served tokens, in the replay's order), every
    ``ssm_layers`` entry's ssm state after the row's prefill and after the
    last token the replay feeds it as its own (its last served token but
    one): ``states[r]`` (ssm layers, 2, H, P, N)."""

    def __init__(self, generator, served, ssm_layers):
        self._generator, self._served = generator, served
        self._layers, self._ticks = ssm_layers, 0
        self._after, self._at_end = [], {}

    def __getattr__(self, name):
        return getattr(self._generator, name)

    def _of(self, caches, row):
        import jax.numpy as jnp
        return jnp.stack([caches[i][1][row] for i in self._layers])

    def _run_chunked_prefill(self, *args):
        last, row = self._generator._run_chunked_prefill(*args)
        self._after.append(self._of(row, 0))
        return last, row

    def _decode(self, *args):
        logits, caches, routing = self._generator._decode(*args)
        self._ticks += 1
        for r, ids in enumerate(self._served):
            if self._ticks + 1 == len(ids):
                self._at_end[r] = self._of(caches, r)
        return logits, caches, routing

    @property
    def states(self):
        import jax.numpy as jnp
        # (a request of one token was fed nothing after its prefill)
        return [jnp.stack([after, self._at_end.get(r, after)], axis=1)
                for r, after in enumerate(self._after)]


LIMITS = ("logit_margin", "logit_atol", "logit_mean_atol",
          "min_choice_agreement", "state_rtol")


def _check(ctx, lm, generator, scatter_row, engine_rows, records, config):
    """The comparison that decides ``correct`` (module docstring)."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import kv_cache_kinds
    mix, serve = ctx.mix, config["serve"]
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(reference_settings(config))
    weights = ref_mod.weights_from_program(generator.params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    picked, has_long, has_short = _pick(
        done, mix, ctx.seed, serve["check_context_over"],
        serve["check_context_under"])
    chunk = serve["prefill_chunk"]
    rows = mix["output_len"]["max"]
    # one shape for every checked request
    length = -(-(mix["prompt_len"]["max"] + rows) // chunk) * chunk
    if length > serve["served_context"]:
        raise ValueError("the mix's longest prompt and output do not fit "
                         "the served context")
    ssm_layers = [i for i, kind in enumerate(kv_cache_kinds(generator.config))
                  if kind == "ssm"]

    # how many positions a head's state holds, by its own parameters: the
    # reciprocal of the decay a position, softplus(dt_bias) exp(A_log)
    blocks = generator.params["params"]
    memory = np.stack([1.0 / np.asarray(
        jax.nn.softplus(blocks[f"h{i}"]["ssm"]["dt_bias"]) *
        jnp.exp(blocks[f"h{i}"]["ssm"]["A_log"])) for i in ssm_layers])
    remembers = memory > config["state_heads_over"]

    @jax.jit
    def state_diff(got, want):
        """Every head's distance, relative to the reference's state, after
        the prefill and at the end: (ssm layers, 2, heads)."""
        off = jnp.sqrt(jnp.square(got - want).sum((-2, -1)))
        size = jnp.sqrt(jnp.square(want).sum((-2, -1)))
        return off / (size + 1e-30)

    limits = {name: config[name] for name in LIMITS}
    worst = {"deficit_same": 0.0, "deficit_flipped": 0.0,
             "diff_same": 0.0, "diff_flipped": 0.0}
    common = choices = flipped = positions = bad = 0
    diff_sum = 0.0
    state_diffs = []
    for at in range(0, len(picked), engine_rows):
        group = picked[at:at + engine_rows]
        refs, wants, deficits, states = [], [], [], []
        for rec in group:
            n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
            ids = np.zeros((length,), np.int32)
            ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
            # the row that predicts served token k: position n_prompt-1+k;
            # the states after the prompt and after all but the last token
            logits, ref_experts, ref_states = \
                reference.logits_experts_and_states(
                    weights, ids, rows=(n_prompt - 1, rows),
                    at=(n_prompt, n_prompt + max(n_out - 1, 0)))
            logits = logits[:n_out]
            served = jnp.asarray(rec["tokens"], jnp.int32)
            chosen = jnp.take_along_axis(logits, served[:, None],
                                         axis=-1)[:, 0]
            deficits.append(np.asarray(logits.max(axis=-1) - chosen,
                                       np.float64))
            refs.append(logits)
            wants.append(np.asarray(ref_experts)[:, :n_out])
            states.append(jnp.stack(ref_states))
        keeping = _KeepsStates(generator, [rec["tokens"] for rec in group],
                               ssm_layers)
        replayed = lm._replay(keeping, scatter_row, engine_rows, group, refs)
        del refs
        for deficit, want, want_state, (diff, got), got_state in zip(
                deficits, wants, states, replayed, keeping.states):
            state_diffs.append(np.asarray(state_diff(got_state, want_state),
                                          np.float64).transpose(1, 0, 2))
            # which of the reference's experts the program chose too
            found = (want[..., :, None] == got[..., None, :]).any(-1)
            same = found.all(-1).all(0)
            known = (got >= 0).all(-1).all(0)
            common += int(found[:, known].sum())
            choices += want[:, known].size
            positions += len(deficit)
            flipped += int((~same & known).sum())
            diff_sum += float(diff.sum())
            for name, values, kind in (
                    ("deficit", deficit, "logit_margin"),
                    ("diff", diff, "logit_atol")):
                for which, where in (("_same", same), ("_flipped", ~same)):
                    if where.any():
                        worst[name + which] = max(
                            worst[name + which], float(values[where].max()))
                # (a NaN is over every limit)
                bad += int((~(values <= limits[kind])).sum())
    # (requests, [after the prefill, at the end], ssm layers, heads); the
    # limit is on the heads that remember, the worst of them a request
    state_diffs = np.asarray(state_diffs).reshape(
        (-1, 2) + remembers.shape)
    held = state_diffs[..., remembers].max(-1, initial=0.0)
    bad += int((~(held <= limits["state_rtol"])).sum())
    return {"checked_requests": len(picked),
            "checked_contexts": [len(rec["prompt_ids"]) + len(rec["tokens"])
                                 for rec in picked],
            "checked_prompts": [len(rec["prompt_ids"]) for rec in picked],
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
            else float("inf"),
            # a request a row: [after the prefill, after its last token]
            "state_diffs": held.round(6).tolist(),
            "worst_state_diff": float(held.max()) if held.size
            else float("inf"),
            "heads_that_remember": int(remembers.sum()),
            # for the record: the same over the heads that hold more than
            # so many positions (0: every head)
            "state_diff_by_memory": {
                str(over): state_diffs[..., memory > over].max(
                    (0, 2), initial=0.0).round(6).tolist()
                for over in (0, 30, 100, 200, 300)},
            "state_heads_over": config["state_heads_over"], **limits}


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import SSM_SCOPE  # noqa: F401
    load = ctx.load
    mla = load("drivers", "serve_mla")
    vars(mla).update(
        reference_settings=reference_settings,
        arithmetic_mla=arithmetic_nemotron,
        balance_routers=load("drivers", "serve_mimo").balance(
            load("drivers", "serve_lm").balance_router_biases),
        _closed_loop=load("drivers", "serve_dsa")._closed_loop,
        _check=_check)
    obs = mla.run(ctx)
    # the engine's resident state by kind, for the record
    ctx.info({"info": "kv_cache_bytes", **{
        kind: obs["counters"][1].get(
            f'alpa_serving_kv_cache_bytes{{kind="{kind}"}}')
        for kind in ("full", "ssm")}})
    return obs
