"""Mixture-of-Experts layers: dropless top-k routed experts (the MLP kind
"experts" of ``gpt_model.TransformerBlock``: OLMoE, Muennighoff et al.
2024) and, below it, the older GShard-style top-2 model with capacity
dropping.

Dropless: every one of the k x tokens pairs is computed.  The rows are
sorted by expert, each expert's consecutive rows meet its weights in one
grouped matmul (``ops/grouped_matmul.py``), and the results are put back
in token order and summed with the routing weights.  Nothing depends on an
expert's load, so there is no capacity and no padding to steal it.  A layer
that holds a share of its experts (expert parallelism's share of the
weights) multiplies the rows that land on those, a window of the sorted
rows at a time where nearly all rows leave (``windowed_expert_sum``).

GShard top-2 (the rest of this file):

Analog of ref ``alpa/model/moe.py`` (einsum-formulated top-2 gating,
ref :151-184): the expert dimension is a leading einsum dim, and expert
parallelism (``ep_axis``) dispatches tokens with EXPLICIT all-to-alls in a
``shard_map`` over the expert axis — the GShard exchange pattern the
reference obtains through its ILP ``allow_all_to_all`` strategies
(SURVEY.md §2.7 EP row).  Spelling the exchange manually (rather than a
``with_sharding_constraint`` on the expert dim) matters: GSPMD lowers the
constraint form with all-gathers, roughly n_experts/2 x the bytes of the
all-to-all.
"""
import dataclasses
from typing import Any, Optional

import functools
import logging
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import GPTConfig, SelfAttention, keep_positions

logger = logging.getLogger(__name__)


########################################
# dropless top-k routing
########################################

# the scope the expert path is traced under: the benchmark finds its
# device events by it (HLO metadata ``op_name``)
SCOPE = "moe"


def topk_routing(router_logits, k: int, norm_topk_prob: bool = False,
                 score: str = "softmax", bias=None, scale: float = 1.0,
                 n_group: int = 1, topk_group: int = 1):
    """The experts' scores in float32, then the k largest: (T, E) logits ->
    (weights (T, k) float32, experts (T, k) int32, scores (T, E)).  ``E`` is
    whatever width the router has: it may exceed the experts that have
    matrices (``GPTConfig.num_zero_experts``: the caller decides what a
    pick past them means), and the scores are over all of it.

    ``score`` "softmax" (OLMoE): the scores are the softmax over the
    experts; "sigmoid" (Trinity, DeepSeek-V3): each expert's own sigmoid.
    ``bias`` ((E,), None: none) is added to the scores for the CHOICE of
    the k experts only: the weights are the chosen experts' scores
    themselves, divided by their sum (+ 1e-20) where ``norm_topk_prob``
    (OLMoE's published configuration leaves it off), times ``scale``.

    ``n_group`` > 1 (DeepSeek-V2's ``group_limited_greedy``, its
    device-limited routing): the experts come in ``n_group`` groups of
    consecutive experts, a group scores as its best expert does, and the k
    experts are chosen among those of the ``topk_group`` best groups (the
    others' scores count as 0 in the choice)."""
    logits = router_logits.astype(jnp.float32)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {score!r}")
    if n_group > 1:
        if bias is not None:
            raise ValueError("group-limited routing takes no bias")
        grouped = scores.reshape(scores.shape[:-1] + (n_group, -1))
        _, groups = jax.lax.top_k(grouped.max(-1), topk_group)
        chosen = (groups[..., None] == jnp.arange(n_group)).any(-2)
        weights, experts = jax.lax.top_k(
            jnp.where(chosen[..., None], grouped, 0.0).reshape(scores.shape),
            k)
    elif bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        if score == "softmax":
            weights = weights / weights.sum(-1, keepdims=True)
        else:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), scores


def load_balancing_loss(prob_sums, counts, n_rows):
    """Hugging Face's ``load_balancing_loss_func`` from per-layer sums:
    ``prob_sums`` (L, E) the router probabilities summed over a layer's
    tokens, ``counts`` (L, E) how many of the token-slots chose each
    expert, ``n_rows`` = L x tokens.  All layers' tokens are pooled, as
    there: E * sum_e (slots that chose e / rows) * (mean probability of
    e).  k at a perfectly even routing.  No gradient flows through the
    counts."""
    share = jax.lax.stop_gradient(counts.sum(0).astype(jnp.float32)) / n_rows
    mean_prob = prob_sums.sum(0) / n_rows
    return prob_sums.shape[-1] * (share * mean_prob).sum()


def routing_summary(routings: list) -> dict:
    """What a model of routed-expert layers returns beside its logits,
    from each layer's ``DroplessExperts`` routing: ``load_balance_loss``
    (above), ``expert_counts`` (L, E) int32 and ``experts`` (L, T, k) int32,
    the experts of every token."""
    counts = jnp.stack([r["counts"] for r in routings])
    prob_sums = jnp.stack([r["prob_sums"] for r in routings])
    experts = jnp.stack([r["experts"] for r in routings])
    n_rows = experts.shape[0] * experts.shape[1]
    return {"load_balance_loss": load_balancing_loss(prob_sums, counts,
                                                     n_rows),
            "expert_counts": counts, "experts": experts}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_experts(x, order, inverse, k):
    """(T, H) tokens -> (T k, H) rows sorted by expert: row r is token
    ``order[r] // k``.  ``inverse`` is the inverse permutation of
    ``order``, so the gradient is a gather too (XLA would scatter-add)."""
    del inverse
    return x[order // k]


def _rows_to_experts_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _rows_to_experts_bwd(k, inverse, g):
    by_token = g[inverse].reshape(g.shape[0] // k, k, g.shape[1])
    return by_token.sum(1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation, with its inverse for the gradient."""
    del inverse
    return x[perm]


_permute_rows.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                     lambda inverse, g: (g[inverse], None, None))


# A call of a layer that holds a share of its experts (``experts_held``)
# gathers, multiplies and combines a WINDOW of its sorted rows at a time, of
# this many times the rows the held experts can expect of it (tokens x k x
# their share of the router's width), up to a whole number of the grouped
# matmul's smallest row tiles.  Set by ``scripts/time_expert_chunk.py`` on a
# v5e (PERF.md section 5, PR 60)
WINDOW_OVER_EXPECTED = 2
# ... where such a window is at most one in this many of the call's rows:
# a prefill chunk's 1,024 positions at a share of an eighth or less.  A
# decode tick's, a verify's and a block step's rows are so few that the
# smallest window is most of them: they go over all rows, as a layer that
# holds every expert does
WINDOW_WORTH_ROWS = 4


def expert_window(config, tokens: int) -> Optional[int]:
    """The rows of the window ``DroplessExperts`` walks a call of ``tokens``
    tokens in (above), from the shape and the held share alone; None where
    the call goes over all its rows at once."""
    from alpa_tpu.ops.grouped_matmul import MIN_ROW_TILE
    held = getattr(config, "experts_held", None)
    if held is None:
        return None
    rows = tokens * config.num_experts_per_tok
    width = config.num_experts + getattr(config, "num_zero_experts", 0)
    tile = MIN_ROW_TILE
    window = tile * math.ceil(
        WINDOW_OVER_EXPECTED * rows * held[1] / (width * tile))
    return window if window * WINDOW_WORTH_ROWS <= rows else None


def _expert_mlps(cfg, rows, w_in, w_down, group_sizes):
    """Rows sorted by expert through their experts, ``group_sizes`` rows
    each: ``down(act(gate(x)) * up(x))`` with ``w_in`` [gate | up] side by
    side, or, ungated, ``down(act(up(x)))`` with ``w_in`` (E, width, h)."""
    from alpa_tpu.model.gpt_model import activation_fn
    from alpa_tpu.ops.grouped_matmul import grouped_matmul
    act, width = activation_fn(cfg.activation), w_down.shape[1]
    if not getattr(cfg, "expert_gated", True):
        hidden = act(grouped_matmul(
            rows, w_in, group_sizes, transposed=True).astype(jnp.float32))
    else:
        # gate and up in one pass over the rows
        gate_up = grouped_matmul(rows, w_in, group_sizes)
        hidden = (act(gate_up[:, :width].astype(jnp.float32)) *
                  gate_up[:, width:].astype(jnp.float32))
    return grouped_matmul(hidden.astype(cfg.dtype), w_down, group_sizes)


def _sum_by_token(rows, token, tokens: int):
    """(W, h) float32 ``rows`` summed into (``tokens``, h): row r into
    token ``token[r]``.  A product with the one-hot (tokens, W) matrix at
    the precision that keeps float32 (a scatter-add walks its rows one
    after the other on a TPU)."""
    onto = (jnp.arange(tokens, dtype=jnp.int32)[:, None] ==
            token[None, :]).astype(jnp.float32)
    return jnp.dot(onto, rows, precision=jax.lax.Precision.HIGHEST)


def windowed_expert_sum(cfg, tokens, weights, order, group_sizes, w_in,
                        w_down, window: int):
    """The held experts' part of the routed sum of a call that sends most
    of its rows elsewhere, a window of ``window`` sorted rows at a time:
    ``(y (T, h) float32, passes)``.

    ``order`` sorts the call's T x k rows with the held experts' first, by
    expert (``group_sizes`` rows each, ``local`` in all).  Pass i gathers
    the tokens of the sorted rows ``i x window`` onward, runs
    ``_expert_mlps`` with each group's size clipped to the window, and adds
    ``weight x row`` of the local rows among them into ``y`` by token: no
    array of T x k rows is made, and the rows bound elsewhere are never
    gathered.  ``passes`` (int32) is ``ceil(local / window)``: one where
    the window sufficed, none where no row is local, more where the
    routing sent more here, so every local row is multiplied whatever the
    routing.  (A last window that would pass the end of the rows starts
    earlier and leaves out the rows the pass before it added.)  ``y`` is
    what the path over all rows sums, in another order of a float32 sum of
    at most k terms.

    The passes are a ``lax.fori_loop`` of a traced trip count, which JAX
    does not differentiate in reverse mode: a jitted gradient through a
    call that takes this path raises JAX's own error ("Reverse-mode
    differentiation does not work for lax.while_loop or lax.fori_loop with
    dynamic start/stop values").  Nothing differentiates a held share at
    such a shape; training holds every expert and goes over all rows."""
    (n_tokens, h), k = tokens.shape, weights.shape[1]
    n = n_tokens * k
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    local = ends[-1]
    flat_weights = weights.reshape(-1)

    def one_pass(i, y):
        done = i * window
        lo = jnp.minimum(done, n - window)
        taken = jax.lax.dynamic_slice(order, (lo,), (window,))
        token = taken // k
        sizes = jnp.clip(ends, lo, lo + window) - \
            jnp.clip(starts, lo, lo + window)
        out_rows = _expert_mlps(cfg, tokens[token], w_in, w_down, sizes)
        at = lo + jnp.arange(window, dtype=jnp.int32)
        # (what lies behind the local rows was not multiplied, and is
        # whatever the kernel's output buffer held)
        mine = (at >= done) & (at < local)
        weighted = jnp.where(
            mine[:, None],
            out_rows.astype(jnp.float32) * flat_weights[taken][:, None], 0)
        return y + _sum_by_token(weighted, token, n_tokens)

    passes = (local + window - 1) // window
    return jax.lax.fori_loop(
        0, passes, one_pass, jnp.zeros((n_tokens, h), jnp.float32)), passes


class DroplessExperts(nn.Module):
    """Top-k routed, gated experts without capacity (module docstring):
    ``down(act(gate(x)) * up(x))``, or with ``expert_gated`` False
    ``down(act(up(x)))``, two matrices and no gate (Nemotron-H, whose
    ``act`` is the squared ReLU).  ``config`` is a ``GPTConfig``:
    ``num_experts``, ``num_experts_per_tok``, ``expert_width`` (one
    expert's), ``activation``, the router's settings (``router_score``,
    ``router_bias``, ``norm_topk_prob``, ``route_scale``:
    ``topk_routing``), ``num_shared_experts``, ``dtype``.  The router and
    its scores are float32 at full matmul precision (a near-tie between
    two experts then flips only on the activations' own rounding); the
    experts multiply in ``dtype`` with float32 accumulation.  The shared
    experts are one MLP each of the routed experts' form, ``expert_width``
    wide or ``shared_expert_width``, applied to every token and added to
    the routed sum.

    ``experts_held`` (first, count): the layer's experts are divided over
    several chips and this program holds ``count`` of them from ``first``
    on (expert parallelism's share, without its exchange).  The router is
    still ``num_experts`` wide and chooses among all of them; the
    parameters are the held experts' alone; the rows routed to an absent
    expert are sorted behind the held groups, which the grouped matmul
    does not walk, and take no part: ``y`` is the held experts' part of
    the routed sum (and the shared experts, which every chip computes
    alike for its own tokens).  Where the call's shape says that at least
    three quarters of its rows are bound elsewhere (``expert_window``: a
    prefill chunk at a share of an eighth or less), those rows are not
    gathered, multiplied in tiles sized for or combined either: the layer
    walks the head of the sorted rows a window at a time
    (``windowed_expert_sum``).

    ``num_zero_experts`` Z (LongCat-Flash's zero-computation experts): the
    router (and its bias) is ``num_experts + Z`` wide, and a pick of an
    index from ``num_experts`` on is an IDENTITY expert, which has no
    matrix and adds its weight times the layer's input.  A pick is then
    one of three kinds: *held* (an expert whose matrices are here: its row
    goes through the grouped matmuls), *absent* (another chip's expert:
    its row lies behind the held groups and adds nothing here) and
    *identity* (its row lies behind the groups too, and ``y`` gains
    ``weight x input`` in float32, computed here for this program's own
    tokens whatever ``experts_held`` says, as the shared experts are).
    How many rows the experts multiply is so decided by the data; the
    grouped matmul's row count stays static: tokens x k, or the window.

    Returns ``(y, routing)``: ``routing`` holds ``counts`` (E,) int32 and
    ``prob_sums`` (E,) float32 over the ``num_experts`` with matrices,
    whichever are held, ``experts`` (T, k) int32, every pick as the router
    numbers it (0 .. E + Z - 1), where there are identity experts
    ``zero_picks`` (a scalar): how many of the picks were of one, and
    where the call walked windows ``window_passes`` (a scalar): how many
    (``record_window_passes``)."""
    config: Any

    @nn.compact
    def __call__(self, x):
        from alpa_tpu.model.gpt_model import MLPBlock
        cfg = self.config
        e, k, width = (cfg.num_experts, cfg.num_experts_per_tok,
                       cfg.expert_width)
        held = getattr(cfg, "experts_held", None)
        # router outputs past the experts: identity experts
        zeros = getattr(cfg, "num_zero_experts", 0)
        # the experts whose parameters are here
        first, mine = (0, e) if held is None else held
        h = x.shape[-1]
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        # False: ``down(act(up(x)))``, two matrices an expert and no gate
        gated = getattr(cfg, "expert_gated", True)
        if not gated:
            # stored (E, width, h), as ``w_down`` with the hidden size
            # minor-most: a width that is no whole number of lane tiles
            # (1,856) as a parameter's minor-most dimension makes the TPU
            # lay the array out in another order, and the kernel's operand
            # is then copied into the named one every call (3 ms a tick
            # of Nemotron-3-Nano's 23 layers, PERF.md, PR 58)
            w_up = self.param(
                "w_up", nn.initializers.lecun_normal(
                    in_axis=-1, out_axis=-2, batch_axis=(0,)),
                (mine, width, h), cfg.param_dtype)
        elif cfg.fused_gate_up:
            # [gate | up] of every expert, side by side as the grouped
            # matmul takes them
            w_gate_up = self.param("w_gate_up", init, (mine, h, 2 * width),
                                   cfg.param_dtype)
        else:
            w_gate = self.param("w_gate", init, (mine, h, width),
                                cfg.param_dtype)
            w_up = self.param("w_up", init, (mine, h, width),
                              cfg.param_dtype)
        w_down = self.param("w_down", init, (mine, width, h),
                            cfg.param_dtype)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (e + zeros,), jnp.float32) \
            if cfg.router_bias else None
        tokens = x.reshape(-1, h)
        with jax.named_scope(SCOPE):
            logits = nn.Dense(e + zeros, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              param_dtype=cfg.param_dtype,
                              name="router")(tokens.astype(jnp.float32))
            weights, experts, probs = topk_routing(
                logits, k, cfg.norm_topk_prob, cfg.router_score,
                None if bias is None else jax.lax.stop_gradient(bias),
                cfg.route_scale, getattr(cfg, "n_group", 1),
                getattr(cfg, "topk_group", 1))
            flat = experts.reshape(-1)
            slot = flat
            # whether some picks are of no expert held here
            partial = held is not None or zeros > 0
            if partial:
                # the held experts' rows first, by expert; the absent and
                # the identity experts' rows behind them, where no group
                # reaches
                here = (flat >= first) & (flat < first + mine)
                slot = jnp.where(here, flat - first, mine)
            # stable: an expert's rows stay in token order
            order = jnp.argsort(slot, stable=True).astype(jnp.int32)
            # rows of the window the call is walked in (``expert_window``)
            window = expert_window(cfg, tokens.shape[0])
            if window is None:
                inverse = jnp.argsort(order).astype(jnp.int32)
            counts = (flat[:, None] == jnp.arange(e, dtype=jnp.int32)).sum(
                0, dtype=jnp.int32)
            group_sizes = counts if held is None else \
                counts[first:first + mine]

            def w_in():
                """What ``_expert_mlps`` takes for the first product."""
                if not gated:
                    return w_up
                if cfg.fused_gate_up:
                    return w_gate_up
                return jnp.concatenate([w_gate, w_up], axis=-1)

            if window is not None:
                y, window_passes = windowed_expert_sum(
                    cfg, tokens.astype(cfg.dtype), weights, order,
                    group_sizes, w_in(), w_down, window)
            else:
                rows = _rows_to_experts(tokens.astype(cfg.dtype), order,
                                        inverse, k)
                out_rows = _expert_mlps(cfg, rows, w_in(), w_down,
                                        group_sizes)
                if partial:
                    # what lies behind the groups was not multiplied, and
                    # is whatever the kernel's output buffer held
                    walked = jnp.arange(out_rows.shape[0]) < \
                        group_sizes.sum()
                    out_rows = jnp.where(walked[:, None], out_rows, 0)
                by_token = _permute_rows(out_rows, inverse, order).reshape(
                    tokens.shape[0], k, h)
                y = (by_token.astype(jnp.float32) *
                     weights[..., None]).sum(1)
            if zeros:
                identity = experts >= e
                y = y + jnp.where(identity, weights, 0.0).sum(
                    -1, keepdims=True) * tokens.astype(jnp.float32)
            for i in range(cfg.num_shared_experts):
                y = y + MLPBlock(
                    cfg, gated=gated, name=f"shared{i}", width=getattr(
                        cfg, "shared_expert_width", None) or width)(
                            tokens).astype(jnp.float32)
        routing = {"counts": counts, "prob_sums": probs.sum(0),
                   "experts": experts}
        if zeros:
            routing.update(prob_sums=probs[:, :e].sum(0),
                           zero_picks=identity.sum(dtype=jnp.int32))
        if window is not None:
            routing["window_passes"] = window_passes
        return y.astype(cfg.dtype).reshape(x.shape), routing


def record_routing(expert_counts, dropped_rows: int = 0):
    """Feed the metrics registry from a step's routing, on the host: the
    caller has the step's ``expert_counts`` ((L, E), or (E,)) already read
    back and calls this where it wants the reading (a warm-up or a traced
    step: it is a device-to-host copy, so never in a timed loop).
    ``alpa_moe_routed_rows_total`` and ``alpa_moe_dropped_rows_total``
    count token-expert rows computed and rows a capacity limit dropped (0
    on the dropless path); the gauge ``alpa_moe_expert_load_max_over_mean``
    is the busiest expert's rows over the mean, the largest over the
    layers."""
    from alpa_tpu.telemetry import metrics as tmetrics
    counts = np.atleast_2d(np.asarray(expert_counts, dtype=np.float64))
    registry = tmetrics.get_registry()
    registry.counter("alpa_moe_routed_rows_total",
                     "token-expert rows the experts computed"
                     ).inc(float(counts.sum()))
    registry.counter("alpa_moe_dropped_rows_total",
                     "token-expert rows dropped by an expert's capacity"
                     ).inc(float(dropped_rows))
    load = (counts.max(-1) / np.maximum(counts.mean(-1), 1e-9)).max()
    registry.gauge("alpa_moe_expert_load_max_over_mean",
                   "rows of the busiest expert over the mean, largest over "
                   "the layers").set(float(load))


def record_window_passes(passes):
    """Feed the metrics registry from what a step said of the windows its
    expert layers walked their rows in (``return_routing``'s
    ``window_passes``, read back): ``alpa_moe_window_calls_total`` counts
    the layers' calls and ``alpa_moe_window_passes_total`` their passes.
    Passes over calls is 1 where a window always held the local rows, under
    1 where calls had none, over 1 where the routing sent a call more than
    its window (``WINDOW_OVER_EXPECTED``)."""
    from alpa_tpu.telemetry import metrics as tmetrics
    passes = np.asarray(passes)
    registry = tmetrics.get_registry()
    registry.counter("alpa_moe_window_calls_total",
                     "calls of an expert layer that walked their rows in "
                     "windows").inc(float(passes.size))
    registry.counter("alpa_moe_window_passes_total",
                     "windows those calls gathered, multiplied and combined "
                     "(over alpa_moe_window_calls_total: 1 where one window "
                     "always held the rows that landed on the held experts)"
                     ).inc(float(passes.sum()))


########################################
# GShard top-2 with capacity (the older model)
########################################


def legacy_routing(intermediates) -> tuple:
    """(rows each expert kept (E,), rows dropped) of one application of a
    model of ``MoEMLP`` layers, from its ``intermediates`` collection
    (``apply(..., mutable=["intermediates"])``), summed over the layers:
    what a caller hands to ``record_routing``."""
    flat = {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(intermediates)}
    kept = sum(np.asarray(v) for k, v in flat.items() if "kept_rows" in k)
    wanted = sum(int(v) for k, v in flat.items() if "wanted_rows" in k)
    return kept, int(wanted - kept.sum())


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 51200
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    seq_len: int = 1024
    num_experts: int = 8
    expert_group_size: int = 512   # tokens per routing group
    capacity_factor: float = 2.0
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    # every k-th layer uses an MoE MLP (ref benchmark suite uses 2)
    moe_every: int = 2
    # mesh axis to shard the expert dim over (None = let GSPMD decide)
    ep_axis: Optional[str] = None
    layer_norm_eps: float = 1e-5

    def gpt(self) -> GPTConfig:
        return GPTConfig(vocab_size=self.vocab_size,
                         hidden_size=self.hidden_size,
                         num_layers=self.num_layers,
                         num_heads=self.num_heads,
                         seq_len=self.seq_len,
                         mlp_ratio=self.mlp_ratio,
                         dtype=self.dtype,
                         layer_norm_eps=self.layer_norm_eps)


def top2_gating(logits: jnp.ndarray, capacity: int):
    """GShard top-2 gating over (G, S, E) router logits.

    Returns (combine_weights (G,S,E,C), dispatch_mask (G,S,E,C), aux_loss).
    Einsum-formulated so everything is one-hot matmuls (MXU-friendly, no
    scatters) — the same formulation family as ref moe.py:151-184.
    """
    g, s, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gate1 = jnp.argmax(probs, axis=-1)                       # (G,S)
    mask1 = jax.nn.one_hot(gate1, e, dtype=jnp.float32)
    probs_wo1 = probs * (1 - mask1)
    gate2 = jnp.argmax(probs_wo1, axis=-1)
    mask2 = jax.nn.one_hot(gate2, e, dtype=jnp.float32)

    # aux load-balancing loss (mean gate prob * mean assignment per expert)
    density = mask1.mean(axis=1)                             # (G,E)
    density_proxy = probs.mean(axis=1)
    aux_loss = (density * density_proxy).sum(-1).mean() * e * e

    # positions within expert capacity
    pos1 = (jnp.cumsum(mask1, axis=1) - 1) * mask1           # (G,S,E)
    mask1 = mask1 * (pos1 < capacity)
    pos1 = pos1 * mask1
    count1 = mask1.sum(axis=1, keepdims=True)                # (G,1,E)
    pos2 = (jnp.cumsum(mask2, axis=1) - 1) * mask2 + count1 * mask2
    mask2 = mask2 * (pos2 < capacity)
    pos2 = pos2 * mask2

    w1 = (probs * mask1).sum(-1)                             # (G,S)
    w2 = (probs * mask2).sum(-1)
    denom = jnp.maximum(w1 + w2, 1e-9)
    w1, w2 = w1 / denom, w2 / denom

    cap_range = jax.nn.one_hot(pos1.sum(-1).astype(jnp.int32), capacity)
    disp1 = mask1[..., None] * cap_range[:, :, None, :]      # (G,S,E,C)
    cap_range2 = jax.nn.one_hot(pos2.sum(-1).astype(jnp.int32), capacity)
    disp2 = mask2[..., None] * cap_range2[:, :, None, :]
    combine = w1[:, :, None, None] * disp1 + w2[:, :, None, None] * disp2
    dispatch = (combine > 0).astype(jnp.float32)
    return combine, dispatch, aux_loss


@functools.lru_cache(maxsize=64)
def _dispatch_fn(mesh, ep_axis: str):
    """Jitted GShard dispatch, cached per (mesh, axis) so repeated/eager
    calls (e.g. several MoE layers during flax init) share one
    compilation.  The jit wrapper also works around partial-manual
    shard_map rejecting eager execution over an abstract mesh."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def inner(tok, disp, comb, wi_l, wo_l):
        # tok: (G/n, S, H); disp/comb: (G/n, S, E, C);
        # wi_l/wo_l: (E/n, ...) local expert slices
        expert_in = jnp.einsum("gsec,gsh->egch", disp, tok)
        # exchange: every device keeps its E/n experts for ALL groups
        expert_in = lax.all_to_all(expert_in, ep_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        hmid = jnp.einsum("egch,ehm->egcm", expert_in, wi_l)
        hmid = nn.gelu(hmid, approximate=True)
        expert_out = jnp.einsum("egcm,emh->egch", hmid, wo_l)
        expert_out = lax.all_to_all(expert_out, ep_axis, split_axis=1,
                                    concat_axis=0, tiled=True)
        return jnp.einsum("egch,gsec->gsh", expert_out, comb)

    sm = jax.shard_map(inner,
                       mesh=mesh,
                       in_specs=(P(ep_axis), P(ep_axis), P(ep_axis),
                                 P(ep_axis), P(ep_axis)),
                       out_specs=P(ep_axis),
                       axis_names={ep_axis},
                       check_vma=False)
    return jax.jit(sm)


def _shard_map_expert_dispatch(tokens, dispatch, combine, wi, wo,
                               ep_axis: str):
    """The GShard dispatch as explicit all-to-alls over ``ep_axis``
    (ref §2.7 EP: 'expert dim sharded => all-to-all inserted by GSPMD' —
    GSPMD actually lowers the constraint form as all-gathers, so we spell
    the exchange ourselves, the same way ulysses_attention does):

      groups sharded over ep ->(local dispatch einsum)-> (E, G/n, C, H)
      -> all_to_all: split E, concat G -> (E/n, G, C, H)
      -> local expert MLP with the device's expert weight slices
      -> inverse all_to_all -> local combine.
    """
    mesh = jax.sharding.get_abstract_mesh()
    return _dispatch_fn(mesh, ep_axis)(tokens, dispatch, combine, wi, wo)


class MoEMLP(nn.Module):
    """Expert-parallel MLP block."""
    config: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, h = x.shape
        e = cfg.num_experts
        gs = min(cfg.expert_group_size, b * s)
        tokens = x.reshape(-1, h)
        n_tok = tokens.shape[0]
        g = max(1, n_tok // gs)
        if cfg.ep_axis is not None:
            # groups are sharded over the expert axis: G must be a
            # multiple of the axis size
            n_ep = dict(jax.sharding.get_abstract_mesh().shape)[cfg.ep_axis]
            if e % n_ep != 0:
                raise ValueError(
                    f"num_experts ({e}) must be divisible by the "
                    f"'{cfg.ep_axis}' mesh axis size ({n_ep}) for expert-"
                    "parallel dispatch; pick a divisible expert count or "
                    "set ep_axis=None")
            g_adj = max(n_ep, (g // n_ep) * n_ep)
            if g_adj != g:
                logger.warning(
                    "MoE group count adjusted %d -> %d to divide ep axis "
                    "(size %d); per-group capacity changes vs the "
                    "unsharded configuration", g, g_adj, n_ep)
            g = g_adj
            assert n_tok % g == 0, (
                f"tokens ({n_tok}) not divisible into {g} groups for "
                f"ep axis of size {n_ep}; adjust batch/expert_group_size")
        tokens = tokens.reshape(g, -1, h)                    # (G, S', H)
        sp = tokens.shape[1]
        capacity = max(1, int(cfg.capacity_factor * sp / e))

        router = nn.Dense(e, dtype=jnp.float32, use_bias=False,
                          name="router")(tokens)
        combine, dispatch, aux_loss = top2_gating(router, capacity)
        self.sow("intermediates", "aux_loss", aux_loss)
        # rows each expert kept; top-2 wants two a token, the rest were
        # dropped by the capacity (``legacy_routing`` reads both)
        self.sow("intermediates", "kept_rows", dispatch.sum((0, 1, 3)))
        self.sow("intermediates", "wanted_rows", 2 * g * sp)

        # per-expert MLP weights (leading expert dim)
        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (e, h, cfg.mlp_ratio * h), cfg.dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (e, cfg.mlp_ratio * h, h), cfg.dtype)

        if cfg.ep_axis is not None:
            out = _shard_map_expert_dispatch(
                tokens, dispatch.astype(x.dtype),
                combine.astype(x.dtype), wi, wo, cfg.ep_axis)
        else:
            # dispatch: (G,S,E,C) x (G,S,H) -> (E, G, C, H)
            expert_in = jnp.einsum("gsec,gsh->egch",
                                   dispatch.astype(x.dtype), tokens)
            hmid = jnp.einsum("egch,ehm->egcm", expert_in, wi)
            hmid = nn.gelu(hmid, approximate=True)
            expert_out = jnp.einsum("egcm,emh->egch", hmid, wo)
            # combine: (E,G,C,H) x (G,S,E,C) -> (G,S,H)
            out = jnp.einsum("egch,gsec->gsh", expert_out,
                             combine.astype(x.dtype))
        return out.reshape(b, s, h), aux_loss


class MoEBlock(nn.Module):
    config: MoEConfig
    use_moe: bool

    @nn.compact
    def __call__(self, x, kv_cache=None):
        cfg = self.config
        gcfg = cfg.gpt()
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                           name="ln1")(x)
        attn_out, new_cache = SelfAttention(gcfg, name="attn")(ln1,
                                                               kv_cache)
        x = x + attn_out.astype(x.dtype)
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                           name="ln2")(x)
        if self.use_moe:
            mlp_out, aux = MoEMLP(cfg, name="moe")(ln2)
        else:
            h = cfg.hidden_size
            y = nn.Dense(cfg.mlp_ratio * h, dtype=cfg.dtype,
                         name="fc_in")(ln2)
            y = nn.gelu(y, approximate=True)
            mlp_out = nn.Dense(h, dtype=cfg.dtype, name="fc_out")(y)
            aux = jnp.float32(0.0)
        return x + mlp_out.astype(x.dtype), aux, new_cache


class MoELMModel(nn.Module):
    """Decoder LM with alternating dense / MoE blocks
    (ref benchmark/alpa/suite_auto_moe.py model family).

    Training call: ``(logits, aux_loss) = apply(params, ids)``.
    Serving call (Mixtral-style MoE decoding): pass ``kv_caches`` and
    get ``(logits, new_caches)`` back — the gpt_model cache-as-invars
    contract, so the Generator / continuous-batching engine drive MoE
    models unchanged (routing happens per decoded token; the aux loss is
    an optimization-only term and is dropped in inference).

    SERVING CAPACITY CAVEAT: bucket-padded prefill feeds pad tokens into
    top-2 routing, and capacity slots go by token order — with
    ``capacity_factor < num_experts`` pads can steal expert capacity
    from real tokens and change their logits.  Serve with
    ``capacity_factor >= num_experts`` (no-drop regime; the Generator
    warns otherwise).  Training is unaffected (no padding there).
    The dropless path (``DroplessExperts``, ``GPTModel`` with ``mlp``
    "experts") has no capacity and so no such limit.
    """
    config: MoEConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, kv_caches=None,
                 logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
        emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       name="wte")
        x = emb(input_ids) + nn.Embed(cfg.seq_len, cfg.hidden_size,
                                      dtype=cfg.dtype,
                                      name="wpe")(position_ids)
        aux_total = jnp.float32(0.0)
        new_caches = [] if kv_caches is not None else None
        for i in range(cfg.num_layers):
            use_moe = (cfg.moe_every > 0 and
                       (i + 1) % cfg.moe_every == 0)
            cache_i = kv_caches[i] if kv_caches is not None else None
            x, aux, c = MoEBlock(cfg, use_moe, name=f"h{i}")(x, cache_i)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches.append(c)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_f")(keep_positions(x, logits_at))
        logits = emb.attend(x.astype(cfg.dtype))
        if new_caches is not None:
            return logits, new_caches
        return logits, aux_total


def init_moe_kv_caches(config: MoEConfig, batch_size: int,
                       dtype=None) -> list:
    """EXACTLY what the serving Generator builds for this config — one
    init path, so tests and serving cannot drift apart."""
    from alpa_tpu.model.gpt_model import init_kv_caches
    return init_kv_caches(config, batch_size, dtype)


# Benchmark ladder (ref benchmark/alpa/suite_auto_moe.py)
moe_specs = {
    "380M": (768, 8, 16, 8),
    "690M": (768, 8, 16, 16),
    "1.3B": (768, 16, 16, 16),
    "2.4B": (1024, 16, 16, 16),
    "10B": (1536, 16, 16, 32),
    "27B": (2048, 16, 16, 48),
}
