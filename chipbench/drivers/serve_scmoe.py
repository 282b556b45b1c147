"""Driver of the serving cells whose model is a LongCat-Flash decoder
(``model_type`` longcat_flash): a layer of two latent-attention sub-blocks
and two dense MLPs whose routed experts leave the stream after the first
and rejoin it after the second (shortcut-connected MoE), under a router
whose last outputs are identity experts, one chip's share of the experts
held here.

It is ``drivers/serve_mla.py``'s run with the four pieces that know the
model put in its place, and nothing else: the same window, clocks, warm-up,
closed loop, replay, traces and ``obs``, so that every reader of the
serving cells works on it.  ``run`` loads a copy of that module of its own
(``ctx.load`` makes a fresh one each time) and binds, in that copy:

* ``reference_settings``: what ``references/longcat_flash_decoder.py``
  needs of the configuration file's keys;
* ``balance_routers``: the same stand-in for a trained router's balance
  (every router made orthogonal to the mean of its input, layer after
  layer, from ``--seed``), for routers that live in a block's module
  ``moe`` beside its MLP and are as wide as the experts and the identity
  experts together; the stored bias stays zero;
* ``arithmetic_mla`` -> ``chipbench/arithmetic_longcat.py``: how many
  layers route and what one expert's matrices weigh;
* ``_check``: ``serve_mla._check`` itself (the window's own compiled
  ``_chunk_prefill``, ``_scatter_row`` and ``_decode`` replayed at the
  window's shapes against the reference's full forward pass; a position
  counts as flipped where any of its 4 x 12 picks differs, identity picks
  among them), told that a block of the kind "gated+shortcut" routes.

``serve_mla.model_config`` and ``share_of`` read this configuration's file
as they stand: ``n_routed_experts`` counts the experts held,
``published.n_routed_experts`` is the router's width before the identity
experts, ``share_index`` the share.
"""
from chipbench import arithmetic_longcat

# balance_routers' schedule: batches a layer and uniform token ids a batch
BALANCE_BATCHES, BALANCE_TOKENS = 4, 1024


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "mla_scale_q_lora",
            "mla_scale_kv_lora", "moe_topk", "routed_scaling_factor")
    return {**{k: config[k] for k in keys},
            "n_routed_experts": config["published"]["n_routed_experts"],
            "experts_first": config["share_index"] *
            config["n_routed_experts"],
            "query_block": config["reference_query_block"],
            "head_block": config["reference_head_block"]}


def balance_routers(model, params, key, vocab):
    """``serve_mla.balance_routers`` for routers in a block's ``moe``:
    every one made orthogonal to the mean of its input (the block's
    ``ln2``, which the dense MLP beside it reads too), layer after layer
    in the model's order, the mean over ``BALANCE_BATCHES`` batches of
    ``BALANCE_TOKENS`` uniform token ids with the earlier layers' routers
    already moved: ``W -= u (u^T W)``, ``u`` the mean's direction.  All
    768 columns lose that direction, the identity experts' too, so that
    no output is preferred by every token; what tells one token's scores
    from another's stays."""
    import jax
    import jax.numpy as jnp
    tokens = min(BALANCE_TOKENS, model.config.seq_len)
    layers = sorted((k for k, block in params["params"].items()
                     if "moe" in block), key=lambda k: int(k.lstrip("h")))

    @jax.jit
    def mean_inputs(params, ids):
        _, state = model.apply(
            params, ids, mutable=["intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name == "ln2")
        return {k: state["intermediates"][k]["ln2"]["__call__"][0].astype(
            jnp.float32).mean((0, 1)) for k in layers}

    for at, layer in enumerate(layers):
        mean = sum(mean_inputs(params, jax.random.randint(
            jax.random.fold_in(key, at * BALANCE_BATCHES + i),
            (1, tokens), 4, vocab))[layer] for i in range(BALANCE_BATCHES))
        u = mean / jnp.linalg.norm(mean)

        def moved(path, x, layer=layer, u=u):
            if path[1].key != layer or path[-2].key != "router":
                return x
            w = x.astype(jnp.float32)
            return (w - jnp.outer(u, u @ w)).astype(x.dtype)

        # the same tree, its big leaves shared
        params = jax.tree_util.tree_map_with_path(moved, params)
    return params


def routed_layers(cfg) -> int:
    """The blocks of the program's configuration that have a router."""
    from alpa_tpu.model.gpt_model import routed_mlp
    return sum(routed_mlp(cfg.mlp_kind(i)) for i in range(cfg.num_layers))


def run(ctx):
    # what the parent commit of this driver lacks fails here, at once
    from alpa_tpu.model.gpt_model import SHORTCUT_MLP  # noqa: F401
    mla = ctx.load("drivers", "serve_mla")
    check = mla._check

    def _check(ctx, lm, *args):
        # serve_lm._replay sizes its table of picks by the routed layers
        lm._expert_layers = routed_layers
        return check(ctx, lm, *args)

    vars(mla).update(reference_settings=reference_settings,
                     balance_routers=balance_routers,
                     arithmetic_mla=arithmetic_longcat, _check=_check)
    return mla.run(ctx)
