"""Autoregressive generation with resident KV caches.

Analog of ref ``examples/llm_serving/model/wrapper.py:501`` (``get_model``,
the HF-GenerationMixin-compatible wrapper): prefill + decode executables
compiled once, KV caches living on device between steps (ref
``init_cache_dis_array`` opt_model.py:1044 — here plain sharded jax.Arrays
threaded through the jitted step, ref cache-as-invars design).

Supports greedy / temperature / top-k sampling, batched requests, and a
pluggable parallel method (ShardParallel on one mesh today; the pipeshard
inference schedule slots in via the same executable interface).
"""
import collections
import dataclasses
import logging
import threading
from functools import partial
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import (GPTConfig, GPTModel, init_kv_caches,
                                      require_one_token_steps,
                                      require_uniform_kv_caches, routed_mlp,
                                      uniform_kv_caches)
from alpa_tpu.telemetry import device_time

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class PrefixHandle:
    """Precomputed KV for a shared prompt prefix (system prompt caching):
    B=1 caches holding ``length`` tokens at scalar write index
    ``length``.  Created by ``Generator.cache_prefix``; consumed by
    ``generate(..., prefix=handle)``, which broadcasts the K/V across
    the batch and prefills only each request's suffix.  ``last_logits``
    are the prefix's final-token logits, so empty suffixes generate
    straight from the cached prompt.  ``params`` is a strong reference
    used for identity guarding (a raw id() could collide after GC)."""
    caches: Any
    length: int
    last_logits: Any
    params: Any = dataclasses.field(repr=False, default=None)


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: int = 0           # 0 = no top-k filtering
    do_sample: bool = False
    eos_token_id: Optional[int] = None


# the two rules by which a denoising forward chooses what it unmasks
REMASKING = ("low_confidence_static", "low_confidence_dynamic")
# the scope the unmasking rule of a block step is traced under (a capture
# reads it: telemetry/device_time.py)
UNMASK_SCOPE = "unmask"


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """How a configuration with ``GPTConfig.block_length`` L generates
    (diffusion over blocks: ``Generator``'s class docstring): the id that
    stands for a position not yet decided, how many denoising forwards a
    block may take (None: L, one position a forward), and the rule by
    which a forward chooses what it unmasks of the ``m`` positions still
    masked with ``r`` forwards of the budget left.
    ``low_confidence_static``: the ``ceil(m / r)`` of highest confidence.
    ``low_confidence_dynamic``: every masked position whose confidence is
    over ``threshold``, and at least the static quota.  Ties go to the
    lower position."""
    mask_token_id: int
    denoising_steps: Optional[int] = None
    remasking: str = "low_confidence_dynamic"
    threshold: float = 0.9

    def __post_init__(self):
        if self.remasking not in REMASKING:
            raise ValueError(f"unknown remasking {self.remasking!r} "
                             f"(known: {REMASKING})")
        if self.denoising_steps is not None and self.denoising_steps < 1:
            raise ValueError("denoising_steps must be at least 1")


# The ONE top-k mask value, shared by the device sampler and the
# host-side prob warper.  It must be -inf: a finite sentinel like -1e9
# leaves masked tokens with tiny-but-nonzero device probability while
# the host assigns them exactly zero, and speculative sampling's
# acceptance ratio p/q is only exact when both agree on the support.
TOP_K_MASK = float("-inf")


def _sample_logits(logits, rng, cfg: GenerationConfig):
    logits = logits.astype(jnp.float32)
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1)
    if cfg.temperature != 1.0:
        logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k > 0:
        top = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < top, TOP_K_MASK, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def sample_rows(logits, key, do_sample, temperature, top_k):
    """One token a row, every row under its own settings, in one program:
    ``_sample_logits`` with the three settings as arrays of length ``B``
    (data, so no setting of any request compiles anything).  Rows with
    ``do_sample`` false take the argmax; the others divide by
    ``max(temperature, 1e-6)``, mask what lies below the row's k-th
    largest value (ties at it survive; ``top_k`` 0 masks nothing) and
    draw from what is left.  The draw and its sort sit under a ``cond``:
    while no row samples, the program is the argmax and ``key`` comes back
    as it went in.  Returns ``(tokens int32[B, 1], key)``, the shape the
    decode takes its tokens in."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)

    def draw(key):
        key, sub = jax.random.split(key)
        x = logits / jnp.maximum(temperature, 1e-6)[:, None]
        # k is data, so the k-th largest value is read off a full sort
        vocab = x.shape[-1]
        kth = jnp.take_along_axis(
            jnp.sort(x, axis=-1),
            (vocab - jnp.clip(top_k, 1, vocab))[:, None], axis=-1)
        x = jnp.where((top_k > 0)[:, None] & (x < kth), TOP_K_MASK, x)
        drawn = jax.random.categorical(sub, x, axis=-1)
        return jnp.where(do_sample, drawn, greedy), key

    tokens, key = jax.lax.cond(jnp.any(do_sample), draw,
                               lambda key: (greedy, key), key)
    return tokens.astype(jnp.int32)[:, None], key


def sample_positions(logits, key, do_sample, temperature, top_k):
    """``sample_rows`` for the ``L`` positions of a block a row: ``logits``
    (B, L, V), every position of a row drawn under the row's own settings
    ((B,) each).  Returns ``(x0 int32 (B, L), confidence float32 (B, L),
    key)``: the confidence of a position is ``softmax(logits / T)[x0]``,
    ``T`` the row's temperature where it samples and 1 where it takes the
    argmax (``top_k`` narrows the draw, not the confidence)."""
    b, l, v = logits.shape
    tokens, key = sample_rows(
        logits.reshape(b * l, v), key, jnp.repeat(do_sample, l),
        jnp.repeat(temperature, l), jnp.repeat(top_k, l))
    x0 = tokens.reshape(b, l)
    scaled = logits.astype(jnp.float32) / jnp.where(
        do_sample, jnp.maximum(temperature, 1e-6), 1.0)[:, None, None]
    chosen = jnp.take_along_axis(scaled, x0[..., None], axis=-1)[..., 0]
    confidence = jnp.exp(chosen - jax.nn.logsumexp(scaled, axis=-1))
    return x0, confidence, key


def unmask_quota(masked_count, forwards_left):
    """How many positions a denoising forward must unmask at least: the
    ``m`` masked spread evenly over the ``r`` forwards left,
    ``ceil(m / r)`` (a full block of 4 in 2 forwards: 2, 2; a block with 3
    masked: 2, 1).  Integers or integer arrays."""
    r = jnp.maximum(forwards_left, 1)
    return -(-masked_count // r)


def choose_unmasked(masked, confidence, forwards_left, remasking: str,
                    threshold: float):
    """Which positions a denoising forward unmasks: ``masked`` bool (B, L),
    ``confidence`` float32 (B, L), ``forwards_left`` int32 (B,) of the
    block's budget, this forward included.  Static rule: the
    ``unmask_quota`` masked positions of highest confidence, ties to the
    lower position; dynamic: those, and every masked position whose
    confidence is over ``threshold``.  A position that is not masked is
    never chosen.  bool (B, L)."""
    quota = unmask_quota(masked.sum(-1), forwards_left)
    conf = jnp.where(masked, confidence, -jnp.inf)
    at = jnp.arange(masked.shape[-1])
    # a position's rank: how many positions go before it
    before = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (at[None, :] < at[:, None]))
    take = masked & (before.sum(-1) < quota[:, None])
    if remasking == "low_confidence_dynamic":
        take |= masked & (confidence > threshold)
    elif remasking != "low_confidence_static":
        raise ValueError(f"unknown remasking {remasking!r}")
    return take


def read_block(was, now, mask: int):
    """What a forward made of one row's block, from the host's copies of
    the block as the forward met it (``was``) and as it left it (``now``),
    (L,) ids each: ``(denoised, unmasked, finished)``.  ``denoised``: the
    block still held a mask, so the forward was a denoising one (else it
    committed the block, and ``now`` is the row's next block);
    ``unmasked`` (L,) bool: the positions it decided; ``finished``: it
    decided the last of them, and the block's tokens can go out."""
    was_masked, now_masked = was == mask, now == mask
    denoised = bool(was_masked.any())
    return (denoised, was_masked & ~now_masked,
            denoised and not now_masked.any())


def _warp_probs_np(logits, cfg: GenerationConfig) -> np.ndarray:
    """Host-side probabilities under the cfg's warping (temperature +
    top-k), matching ``_sample_logits``'s semantics (ties at the k-th
    value survive).  float64 for exact rejection-sampling ratios."""
    x = np.asarray(logits, np.float64)
    if cfg.temperature != 1.0:
        x = x / max(cfg.temperature, 1e-6)
    if cfg.top_k > 0:
        kth = np.partition(x, -cfg.top_k, axis=-1)[..., -cfg.top_k, None]
        x = np.where(x < kth, TOP_K_MASK, x)
    x = x - x.max(axis=-1, keepdims=True)
    p = np.exp(x)
    return p / p.sum(axis=-1, keepdims=True)


def _sample_from_probs(p: np.ndarray, u: float) -> int:
    """Inverse-CDF draw from a probability vector with one uniform."""
    c = np.cumsum(p)
    return int(np.clip(np.searchsorted(c, u * c[-1], side="right"),
                       0, len(p) - 1))


def speculative_accept(props, q_probs, p_probs, us, u_extra):
    """Rejection-sampling acceptance (Leviathan et al. speculative
    sampling): token i drawn from q_i is accepted with probability
    min(1, p_i(x)/q_i(x)); the first rejection emits from the residual
    norm(max(p_i - q_i, 0)); a fully-accepted round emits a bonus token
    from p_k.  Returns (num_accepted, extra_token).  The marginal
    distribution of every emitted token is EXACTLY p — see
    tests/serve/test_speculative_sampling.py for the statistical proof
    harness.

    ``props``: k proposed tokens; ``q_probs``: (k, V) draft probs;
    ``p_probs``: (k+1, V) target probs; ``us``: k uniforms;
    ``u_extra``: one uniform for the residual/bonus draw.
    """
    k = len(props)
    for i in range(k):
        x = int(props[i])
        ratio = p_probs[i][x] / max(q_probs[i][x], 1e-300)
        if us[i] < min(1.0, ratio):
            continue
        residual = np.maximum(p_probs[i] - q_probs[i], 0.0)
        s = residual.sum()
        if s <= 0.0:
            # p == q exactly: the residual is empty and acceptance was
            # certain up to float rounding — fall back to p itself
            residual, s = p_probs[i], p_probs[i].sum()
        return i, _sample_from_probs(residual / s, u_extra)
    return k, _sample_from_probs(p_probs[k], u_extra)


def _jit_registered(fun, **jit_kwargs):
    """``jax.jit(fun, **jit_kwargs)`` whose compiled program a capture can
    read by part of the model (``telemetry/device_time.py``): whenever the
    function is traced, which is when a program of it compiles, it
    registers under the name the profiler gives that program's runs
    (``jit_<fun's name>``) a way to the program's optimised HLO text, by
    lowering the function again for the shapes it was traced for, one way
    a set of shapes.  jax keeps a function's lowering and its executable
    for shapes it has run (a serving run counts the same 71 compiles with
    and without a capture: PERF.md §6, PR 34); where it does not, the
    persistent compilation cache answers.  Nothing runs in a call that
    does not trace."""

    def traced(*args):
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        device_time.register_program(
            "jit_" + fun.__name__, jitted,
            lambda again: again.lower(*shapes).compile().as_text(),
            variant=str(shapes))
        return fun(*args)

    traced.__name__ = traced.__qualname__ = fun.__name__
    jitted = jax.jit(traced, **jit_kwargs)
    return jitted


def _jit_donating_kv(step):
    """``jax.jit`` of a cached step ``step(params, tokens, index, caches,
    *more)`` that donates the K and V arrays of ``caches`` (``[(k, v,
    index)]`` a layer), so that the step writes the resident cache in place, and not
    their indices: a caller may pass one index array as ``index`` and in
    every layer's triple, and may read it after the call.

    jax pairs donated arrays with outputs of the same shape in the order
    of the two flattened trees, not by data flow, so the jitted function
    (the result's ``jitted``) takes K and V as ``[(k, v)]`` a layer, the
    order its result has them in: handed over as all K and then all V,
    each layer's output would land in another layer's buffer at the price
    of a copy of every cache."""

    def split(params, tokens, index, kv, idxs, *more):
        return step(params, tokens, index,
                    [(k, v, i) for (k, v), i in zip(kv, idxs)], *more)

    # the compiled program keeps the step's name (``jit_decode``): traces
    # and the benchmark's readers find it by that
    split.__name__ = split.__qualname__ = step.__name__
    jitted = _jit_registered(split, donate_argnums=(3,))

    def call(params, tokens, index, caches, *more):
        return jitted(params, tokens, index,
                      [(k, v) for k, v, _ in caches],
                      [i for _, _, i in caches], *more)

    call.jitted = jitted
    return call


@partial(jax.jit, static_argnums=(0, 1))
def fresh_kv_caches(config, rows: int):
    """Fresh caches for ``rows`` rows, ``[(k, v, index)]`` a layer with
    every index the scalar 0: the one way serving code gets them.  From
    the host it is ONE dispatch whatever the model's depth (a program
    compiled once a configuration and number of rows, where
    ``init_kv_caches`` called eagerly is three small programs a layer);
    inside a traced program it is inlined and costs none."""
    return init_kv_caches(config, rows)


def row_length(n: int):
    """``int32[1]`` on the device, the one row's length a prefill takes:
    from a numpy array, which is a transfer, where a Python list would be
    a transfer and a program that converts it."""
    return jnp.asarray(np.array([n], np.int32))


# The lower step of an engine's admission ladder, as a share of its cap
# (``Generator.admission_ladder``).  One run of OPT-1.3B's ``jit_prefill``
# on a v5e by bucket is in PERF.md §6, PR 35.
ADMISSION_STEP = 0.25


# Bytes of cache arrays that chunk steps dispatched and not yet run may
# hold (``Generator._run_chunked_prefill``)
CHUNK_CACHES_AHEAD_BYTES = 2**31


def default_prompt_buckets(seq_len: int) -> List[int]:
    """Power-of-two prompt-length buckets up to seq_len."""
    buckets, b = [], 32
    while b < seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(seq_len)
    return buckets


class Generator:
    """Compiled prefill + decode loop over a GPT-family model.

    Shape bucketing (ref wrapper_1d.py intent): prompts are right-padded
    to a fixed bucket ladder, so serving traffic with arbitrary prompt
    lengths compiles exactly one prefill per (batch, bucket) pair and one
    decode per batch — not one pair per request shape.  Right padding is
    safe because the causal mask bounds attention to positions < the
    per-row write index, and each decode step overwrites the padded
    garbage at its position before that position ever becomes attendable.
    Mixed prompt lengths share one batch via per-row KV-cache indices.
    ``prefill_traces`` / ``decode_traces`` count actual retraces so tests
    can hold the bucketing to its promise.

    ``_decode(params, token, index, caches)`` takes the K and V arrays of
    ``caches`` away from its caller (``_jit_donating_kv``): the step writes
    them in place, they are deleted after the call, and the caller goes on
    with the caches the call returns.  The indices stay the caller's.
    ``_prefill``, ``_chunk_prefill`` and the verify step donate nothing (a
    ``PrefixHandle``'s caches are prefilled from again and again), nor
    does the ``parallel_method`` decode.

    Fresh caches come from ``fresh_kv_caches`` and from nowhere else: the
    dense ``_prefill`` handed ``None`` for its caches makes them inside
    its own program, the chunk step's first chunk is handed the one
    program's result, so what an admission dispatches does not grow with
    the model's depth.

    A configuration whose layers' caches differ (``GPTConfig.attention``:
    a ring of the window's positions in a "sliding" layer, the context in
    a "full" one, a state in a "conv" one) is prefilled with the rows'
    lengths handed to the model, so that no ring takes a chunk's padding.

    A layer of latent attention (``GPTConfig.attention`` "latent") caches
    ``(c, k_pe, index)``, two arrays of unlike shapes and no heads, where
    the others cache ``(k, v, index)``: everything here hands a layer's
    entry on as a triple and looks into none, so prefill in chunks, the
    donating decode and the engine's ``_scatter_row`` serve it as they are;
    what indexes per-head K and V (the block pool, the speculative verify
    step, beam search) refuses it
    (``require_uniform_kv_caches``).  So do a latent layer under the window
    (``"latent_sliding"``: a ring of the window's latents, prefilled with
    the rows' lengths as every ring is) and one that selects its positions
    (``GPTConfig.index_topk``: a row and an index key a position); of such
    a configuration ``_decode``'s ``routing`` also holds what each
    selecting layer selected (``selected``, ``selected_real``:
    ``GPTModel``).

    A short-convolution layer (``GPTConfig.attention`` "conv") holds no
    positions: its entry is ``(state, empty, index)``, the last
    ``conv_taps - 1`` positions of a product, and rides in the list of
    caches as the others do (donated by the decode, scattered by the
    engine's ``_scatter_row``, repeated over a batch under a
    ``PrefixHandle``, which so snapshots the state with the prefix).  Such
    a configuration is prefilled with the rows' lengths too: a padded
    chunk or bucket leaves each row the state of its last real position
    (``update_conv_state``).  What rolls a row back by its index or
    indexes positions refuses it, by the same call.

    ``_decode`` returns ``(logits, caches, routing)``: ``routing`` is
    ``{"experts": (expert layers, rows, k) int32}``, every row's experts in
    every routed-expert layer, and ``{}`` (no output of the compiled
    program) for a configuration without such layers.

    **Generation by diffusion over blocks** (a configuration with
    ``block_length`` L, and ``diffusion=BlockDiffusion(...)``).  The
    sequence is the prompt followed by masks up to a multiple of L; the
    prompt's first ``(len // L) * L`` positions are prefilled (by the same
    ``_prefill`` / ``_chunk_prefill``, under the block-causal mask) and its
    last ``len % L`` tokens are the fixed head of the first generated
    block.  A third compiled step, ``_block_step(params, ids, index,
    caches, left, settings, key)``, is ONE forward of a whole block a row,
    whatever the rows' phases, donating K and V as ``_decode`` does: ``ids``
    (rows, L) are the rows' blocks (the mask id where a position is not
    decided), ``index`` (rows,) the blocks' first positions, ``left``
    (rows,) what is left of each block's budget of ``denoising_steps``
    forwards, ``settings`` the rows' ``(do_sample, temperature, top_k)``.
    Inside the program: a row whose block holds no mask COMMITS (decided
    from the ids, on the device); the model runs once on (rows, L) at the
    positions ``index + 0..L-1`` and writes the block's keys and values
    there in every forward (a later forward of the same block overwrites
    them), but the index advances by L only in committing rows; under
    ``jax.named_scope("unmask")`` every position draws ``x0`` under its
    row's settings with the confidence ``softmax(logits / T)[x0]``
    (``sample_positions``), the rule chooses what a denoising row unmasks
    (``choose_unmasked``), and a committing row's next block comes out all
    masks with a fresh budget.  It returns ``(ids, left, unmasked, commits,
    logits, caches, routing, key)``: the new blocks and budgets, which
    positions were unmasked (rows, L) and which rows committed (rows,),
    the logits at every position (rows, L, V), ``routing`` for rows x L
    tokens.  ``decode_traces`` counts one trace of it whatever the rows do.
    ``generate`` runs it for its batch (``generate_blocks``), so that a
    request alone and the same request among an engine's rows run the same
    program.  What is built on one token a row a step refuses such a
    configuration by name (``require_one_token_steps``):
    ``generate_speculative``, ``generate_beam``, ``cache_prefix`` (a static
    prefix), the KV block pool, ``serve/disagg.py``.
    """

    def __init__(self, model: GPTModel, params, config: GPTConfig,
                 batch_size: int = 1,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 parallel_method: Optional[Any] = None,
                 prefill_chunk: Optional[int] = None,
                 diffusion: Optional[BlockDiffusion] = None):
        """``parallel_method``: optional alpa_tpu ParallelMethod for the
        prefill/decode executables — e.g. ``PipeshardParallel(
        pipeline_schedule="inference")`` with a layer-marked model config
        gives pipelined inference with per-stage-resident KV caches (ref
        get_pipeshard_executable, opt_model.py:770); cache outputs keep
        their stage placement so the next decode's device_put is a no-op.

        ``prefill_chunk``: CHUNKED prefill — prompts stream through the
        cached decode-style path in fixed-size chunks, so ONE compiled
        step serves every prompt length (no bucket ladder, no per-bucket
        compiles; the long-context serving mode).  Positions enter via
        the cache write index, so it applies to every decoder family.

        ``diffusion``: the settings of generation by diffusion over
        blocks, which a configuration with ``block_length`` needs and no
        other takes (``BlockDiffusion``).
        """
        block = getattr(config, "block_length", 0)
        if bool(block) != (diffusion is not None):
            raise ValueError(
                "a configuration with block_length generates by diffusion "
                "over blocks and needs Generator(diffusion="
                "BlockDiffusion(mask_token_id=...)); no other takes one")
        if block:
            if parallel_method is not None:
                raise ValueError("generation by diffusion over blocks has "
                                 "no parallel_method block step")
            if prefill_chunk and prefill_chunk % block:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} is no multiple of the "
                    f"block length {block}: a block would straddle two "
                    "chunks and see half of itself")
            if not 0 <= diffusion.mask_token_id < config.vocab_size:
                raise ValueError("mask_token_id lies outside the vocabulary")
        self.diffusion = diffusion
        self.model = model
        self.params = params
        self.config = config
        self.batch_size = batch_size
        self.prompt_buckets = sorted(prompt_buckets or
                                     default_prompt_buckets(config.seq_len))
        self.prefill_traces = 0
        self.decode_traces = 0
        # what the cached calls hand the model beyond ids, positions and
        # caches (other decoder families take neither)
        kinds = getattr(config, "mlp", "dense")
        # (or what its selecting layers selected)
        routed = any(routed_mlp(kind) for kind in
                     ([kinds] if isinstance(kinds, str) else kinds)) or \
            getattr(config, "index_topk", 0) > 0
        rings = not uniform_kv_caches(config)

        def lengths_kw(lengths):
            return {"cache_lengths": lengths} if rings else {}

        # MoE capacity hazard: bucket pads enter routing and can steal
        # expert capacity from real tokens below the no-drop regime
        # (see MoELMModel docstring)
        cap = getattr(config, "capacity_factor", None)
        n_exp = getattr(config, "num_experts", None)
        if cap is not None and n_exp is not None and cap < n_exp:
            logger.warning(
                "serving an MoE config with capacity_factor (%s) < "
                "num_experts (%s): padded prefill tokens can steal "
                "expert capacity and change real tokens' logits — use "
                "capacity_factor >= num_experts for exact serving", cap,
                n_exp)

        def prefill(params, input_ids, caches, lengths):
            self.prefill_traces += 1
            b, s = input_ids.shape
            if caches is None:
                # a prefill from nothing makes its zeros itself: nothing
                # is dispatched for them, and the compiler keeps only
                # those the prompt's positions do not overwrite
                caches = fresh_kv_caches(config, b)
            pos = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
            logits, caches = model.apply(params, input_ids, pos, caches,
                                         **lengths_kw(lengths))
            last = logits[jnp.arange(b), lengths - 1]
            # per-row cache indices: each row continues at its own length
            caches = [(kc, vc, lengths) for (kc, vc, _i) in caches]
            return last, caches

        def decode(params, token, index, caches):
            self.decode_traces += 1
            pos = index[:, None]
            if routed:
                logits, caches, routing = model.apply(
                    params, token, pos, caches, return_routing=True)
                return logits[:, 0, :], caches, routing
            logits, caches = model.apply(params, token, pos, caches)
            return logits[:, 0, :], caches, {}

        self.prefill_chunk = prefill_chunk
        # the logits of the last chunk step sent, where the steps in
        # flight are bounded (``_run_chunked_prefill``)
        self._last_chunk = None
        self._parallel_method = parallel_method

        def chunk_prefill(params, ids_chunk, lengths, caches, last):
            """One fixed-shape chunk through the cached path: ``(last,
            caches)``.  The chunk's absolute start position rides the
            caches' scalar write index.  The final norm and the head run
            over one position a row, the one at ``lengths - 1`` where the
            chunk holds it, and ``last`` (B, V) takes that row's logits
            there.  A row whose prompt goes on past the chunk, or ended
            before it, keeps what ``last`` held: for a chunk that no
            prompt ends in, ``last`` comes back as it was given (the one
            row the head ran over is thrown away)."""
            self.prefill_traces += 1
            b, c = ids_chunk.shape
            start = caches[0][2]                     # scalar chunk start
            pos = start + jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
            off = lengths - 1 - start                # (B,)
            logits, caches = model.apply(
                params, ids_chunk, pos, caches,
                logits_at=jnp.clip(off, 0, c - 1)[:, None],
                **lengths_kw(lengths))
            hit = (off >= 0) & (off < c)
            last = jnp.where(hit[:, None], logits[:, 0], last)
            return last, caches

        def block_step(params, ids, index, caches, left, settings, key):
            """One forward of a whole block a row (class docstring)."""
            self.decode_traces += 1
            b, l = ids.shape
            masked = ids == diffusion.mask_token_id
            # a row whose block holds no mask commits it
            commits = ~masked.any(-1)
            pos = index[:, None] + jax.lax.broadcasted_iota(
                jnp.int32, (b, l), 1)
            if routed:
                logits, caches, routing = model.apply(
                    params, ids, pos, caches, return_routing=True)
            else:
                logits, caches = model.apply(params, ids, pos, caches)
                routing = {}
            with jax.named_scope(UNMASK_SCOPE):
                x0, confidence, key = sample_positions(logits, key,
                                                       *settings)
                unmasked = choose_unmasked(
                    masked, confidence, left, diffusion.remasking,
                    diffusion.threshold)
                ids = jnp.where(unmasked, x0, ids)
                # a committing row goes on to its next block: all masks,
                # a fresh budget
                ids = jnp.where(commits[:, None],
                                jnp.int32(diffusion.mask_token_id), ids)
                left = jnp.where(commits, jnp.int32(self.denoising_steps),
                                 jnp.maximum(left - 1, 1))
            # the block's keys and values were written at its positions;
            # only a commit keeps them (the next forward of a block that
            # denoises on writes the same positions again)
            index = jnp.where(commits, index + l, index)
            caches = [(k, v, index) for (k, v, _i) in caches]
            return (ids, left, unmasked, commits, logits, caches, routing,
                    key)

        #: denoising forwards a block may take (its budget)
        self.denoising_steps = (diffusion.denoising_steps or block) \
            if block else 0
        self._block_step = _jit_donating_kv(block_step) if block else None

        if parallel_method is not None:
            import alpa_tpu
            self._prefill = alpa_tpu.parallelize(
                prefill, method=parallel_method, donate_argnums=())
            self._decode = alpa_tpu.parallelize(
                decode, method=parallel_method, donate_argnums=())
            self._chunk_prefill = alpa_tpu.parallelize(
                chunk_prefill, method=parallel_method, donate_argnums=())
        else:
            self._prefill = _jit_registered(prefill)
            self._decode = _jit_donating_kv(decode)
            self._chunk_prefill = _jit_registered(chunk_prefill)
        # beam-search KV-cache gather, compiled once (per cache shapes)
        self._reorder = jax.jit(
            lambda caches, idx: jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx, axis=0)
                if hasattr(x, "ndim") and x.ndim > 0 else x, caches))

    def _run_bucketed_prefill(self, prompts, lengths_j, b, bucket=None):
        """Classic bucketed prefill: right-pad to the bucket ladder (one
        compile per bucket), or to ``bucket`` where the caller chose it.
        The single shared implementation for generate, speculative
        decoding and an engine's dense admission."""
        bucket = bucket or \
            self._bucket_len(int(max(len(p) for p in prompts)))
        ids = np.zeros((b, bucket), np.int32)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = p
        return self._prefill(self.params, jnp.asarray(ids), None, lengths_j)

    def _run_chunked_prefill(self, prompts, lengths_j, b, caches=None,
                             start=0, init_last=None):
        """Stream the prompts through the fixed-shape chunk step: one
        compile covers every prompt length.

        ``caches``/``start``: continue from precomputed K/V (prefix
        caching) — ``prompts`` are then suffixes written from position
        ``start``, and ``lengths_j`` are TOTAL lengths (prefix+suffix).
        ``init_last`` seeds the final-logit accumulator (the prefix's
        last-token logits, so empty suffixes keep them).
        """
        c = self.prefill_chunk
        s_max = int(max(len(p) for p in prompts))
        if s_max == 0 and caches is not None:
            # all suffixes empty: nothing to prefill — the prefix's
            # last_logits (init_last) already seed decode
            caches = [(kc, vc, lengths_j) for (kc, vc, _i) in caches]
            return init_last, caches
        n_chunks = max(1, -(-s_max // c))
        if start + n_chunks * c > self.config.seq_len:
            # hard error (not assert): under -O a clamped cache write
            # would silently corrupt earlier tokens' K/V
            raise ValueError(
                f"chunked prefill of {s_max} tokens at offset {start} "
                f"pads to {start + n_chunks * c}, exceeding the KV "
                f"capacity (seq_len {self.config.seq_len}); use a chunk "
                f"size dividing seq_len or a shorter prompt")
        ids = np.zeros((b, n_chunks * c), np.int32)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = p
        if caches is None:
            caches = fresh_kv_caches(self.config, b)   # scalar index 0
        if init_last is None:
            init_last = jnp.zeros((b, self.config.vocab_size),
                                  self.config.dtype)
        last = init_last
        # the chunk step does not donate its caches (they may be a prefix
        # handle's), and a dispatch allocates its results at once: a host
        # that runs two prompts of 22 chunks ahead of the device holds 44
        # sets of a row's caches (7.5 GB where a set is 171 MB).  Where a
        # whole context's chunks of such sets would not fit
        # ``CHUNK_CACHES_AHEAD_BYTES``, the host lets the prompt before
        # this one finish first, and waits for the chunk it sent ``ahead``
        # chunks ago before it sends the next: the device is never without
        # a chunk but between two prompts.  A configuration whose sets are
        # small is dispatched as far ahead as the host gets, as ever
        ahead = CHUNK_CACHES_AHEAD_BYTES // max(1, sum(
            getattr(kc, "nbytes", 0) + getattr(vc, "nbytes", 0)
            for kc, vc, _i in caches))
        bounded = ahead < self.config.seq_len // c
        ahead = max(2, ahead)
        if bounded and self._last_chunk is not None:
            jax.block_until_ready(self._last_chunk)
        sent = collections.deque()
        for ci in range(n_chunks):
            chunk = jnp.asarray(ids[:, ci * c:(ci + 1) * c])
            last, caches = self._chunk_prefill(self.params, chunk,
                                               lengths_j, caches, last)
            sent.append(last)
            if bounded and len(sent) > ahead:
                jax.block_until_ready(sent.popleft())
        if bounded:
            self._last_chunk = last
        # per-row decode positions take over from the scalar chunk index
        caches = [(kc, vc, lengths_j) for (kc, vc, _i) in caches]
        return last, caches

    def cache_prefix(self, prefix_ids) -> "PrefixHandle":
        """Precompute KV for a shared prefix (system prompt caching).
        Chunked mode only — the chunk step is what lets suffixes resume
        at an arbitrary cache offset with one compile.  A short
        convolution's state after the prefix's last token is in the
        handle with the keys and values (it rides in the same list)."""
        require_one_token_steps(self.config, "a static prefix "
                                "(cache_prefix)")
        if not self.prefill_chunk:
            raise ValueError(
                "cache_prefix requires Generator(prefill_chunk=...)")
        p = np.asarray(prefix_ids, np.int32).reshape(-1)
        lengths = jnp.asarray([len(p)], jnp.int32)
        last, caches = self._run_chunked_prefill([p], lengths, 1)
        # restore the SCALAR index (suffix chunks continue from here)
        caches = [(kc, vc, jnp.int32(len(p))) for (kc, vc, _i) in caches]
        return PrefixHandle(caches=caches, length=len(p),
                            last_logits=last, params=self.params)

    def _bucket_len(self, n: int,
                    buckets: Optional[Sequence[int]] = None) -> int:
        """The smallest bucket that holds ``n`` positions, of ``buckets``
        (ascending; the engines hand their admission ladder) or of the
        generator's whole ladder."""
        buckets = buckets or self.prompt_buckets
        for b in buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds the largest bucket "
                         f"{buckets[-1]}")

    def admission_ladder(self, cap: int) -> List[int]:
        """The buckets an engine's dense admission pads a prompt to:
        ``cap`` (the longest prompt the engine takes) and, where the
        generator's ladder has a bucket below it, the one nearest to
        ``ADMISSION_STEP`` of it.  Two steps and not the whole ladder,
        because an engine compiles every program it can run before it
        admits (``compile_row_prefills``) and each costs its set-up."""
        below = [b for b in self.prompt_buckets if b < cap]
        if not below:
            return [cap]
        return [min(below, key=lambda b: abs(b - ADMISSION_STEP * cap)), cap]

    def prefill_row(self, prompt: np.ndarray, ladder: Sequence[int]):
        """One prompt prefilled from nothing for an engine's row: padded
        to the smallest bucket of ``ladder`` that holds it, through the
        dense ``_prefill`` for one row.  ``(last-token logits, caches,
        bucket)``; the caches are of the full length whatever the bucket,
        so a row scatters the result of any step alike.  The one way the
        decode engine and the disaggregated ``PrefillEngine`` pad, which
        keeps a handed-off row bit-identical to one computed in place."""
        bucket = self._bucket_len(len(prompt), ladder)
        logits1, caches1 = self._run_bucketed_prefill(
            [prompt], row_length(len(prompt)), 1, bucket)
        return logits1, caches1, bucket

    def compile_row_prefills(self, ladder: Sequence[int]):
        """Compile (or read back) every program ``prefill_row`` can run
        over ``ladder`` and run each once over a prompt of padding, so
        that no admission compiles: which program an admission runs
        follows from its prompt's length, and a window must not meet one
        for the first time.

        A program's set-up is a second of tracing and lowering, which
        holds the interpreter, and then the compile or the read from the
        compile cache, which does not (one to three seconds for OPT-1.3B
        on a v5e: PERF.md §6, PR 35).  So the calling thread traces the
        programs one after the other and leaves the compile of all but
        the last to a thread of its own, beside the next program's trace;
        two traces at once would only take turns."""
        padding = {b: np.zeros((b,), np.int32) for b in ladder}
        compiling = []
        try:
            # the pipelined (parallel_method) prefill has no lowering
            # apart from its call: it compiles in the runs below
            ahead = ladder[:-1] if self._parallel_method is None else []
            for bucket in ahead:
                lowered = self._prefill.lower(
                    self.params, jnp.asarray(padding[bucket][None]), None,
                    row_length(bucket))
                compiling.append(threading.Thread(
                    target=lowered.compile, name="ladder-compile",
                    daemon=True))
                compiling[-1].start()
            # the last bucket compiles here, in its run, beside the
            # others; then theirs, through the call an admission makes
            for bucket in reversed(ladder):
                jax.block_until_ready(
                    self.prefill_row(padding[bucket], ladder)[0])
                while compiling:
                    compiling.pop().join()
        except Exception:  # pylint: disable=broad-except
            # the caller is an engine's thread, which has to live: the
            # admission that meets the fault then fails alone, as it
            # did when it was the first to compile
            logger.exception("compiling the admission ladder failed")

    def generate(self,
                 input_ids,
                 generation_config: Optional[GenerationConfig] = None,
                 rng: Optional[jax.Array] = None,
                 prefix: Optional["PrefixHandle"] = None
                 ) -> List[np.ndarray]:
        """Generate for a batch of (possibly mixed-length) prompts.

        ``input_ids``: (B, S) array, or a list of 1-D prompts of varying
        lengths.  Uniform-length batches return a (B, S + T) array with
        finished rows eos-padded; mixed-length batches return a list of B
        1-D arrays (prompt + generation, truncated at eos).

        ``prefix``: a ``cache_prefix`` handle — the prefix's KV is
        broadcast across the batch and only each request's SUFFIX
        (``input_ids``) is prefilled; returned rows contain suffix +
        generation (the caller already has the prefix tokens).
        """
        cfg = generation_config or GenerationConfig()
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if isinstance(input_ids, (list, tuple)):
            prompts = [np.asarray(p, np.int32).reshape(-1)
                       for p in input_ids]
        else:
            arr = np.asarray(input_ids, np.int32)
            if arr.ndim == 1:
                arr = arr[None]
            prompts = list(arr)
        b = len(prompts)
        if self.diffusion is not None:
            if prefix is not None:
                require_one_token_steps(self.config, "a static prefix")
            rows = self.generate_blocks(prompts, cfg, rng)[0]
            outs = [np.concatenate([p, np.asarray(row, np.int32)])
                    for p, row in zip(prompts, rows)]
            if len({len(o) for o in outs}) == 1:
                return np.stack(outs)
            return outs
        plen = 0
        if prefix is not None:
            if not self.prefill_chunk:
                raise ValueError("prefix caching requires "
                                 "Generator(prefill_chunk=...)")
            if prefix.params is not self.params:
                raise ValueError("PrefixHandle was built for different "
                                 "params")
            plen = prefix.length
        lengths = np.array([plen + len(p) for p in prompts], np.int32)
        s_max = int(lengths.max())
        if s_max + cfg.max_new_tokens > self.config.seq_len:
            # hard error: under -O a stripped assert would let decode
            # write past the cache and silently corrupt the last entry
            raise ValueError(
                f"prompt {s_max} + max_new_tokens {cfg.max_new_tokens} "
                f"exceeds seq_len {self.config.seq_len}")
        lengths_j = jnp.asarray(lengths)
        if self.prefill_chunk:
            # no bucket ladder in chunked mode: any length up to the KV
            # capacity streams through the one compiled chunk step
            init = None
            init_last = None
            if prefix is not None:
                # broadcast the prefix K/V across the batch; the scalar
                # write index (== plen) rides along, and the prefix's
                # last logits seed rows whose suffix is empty
                init = [(jnp.repeat(kc, b, axis=0),
                         jnp.repeat(vc, b, axis=0), idx)
                        for (kc, vc, idx) in prefix.caches]
                init_last = jnp.repeat(prefix.last_logits, b, axis=0)
            logits, caches = self._run_chunked_prefill(
                prompts, lengths_j, b, caches=init, start=plen,
                init_last=init_last)
        else:
            logits, caches = self._run_bucketed_prefill(prompts, lengths_j,
                                                        b)
        generated = []
        finished = jnp.zeros((b,), bool)
        index = lengths_j
        for _ in range(cfg.max_new_tokens):
            rng, sub = jax.random.split(rng)
            nxt = _sample_logits(logits, sub, cfg).astype(jnp.int32)
            if cfg.eos_token_id is not None:
                nxt = jnp.where(finished, cfg.eos_token_id, nxt)
                finished = finished | (nxt == cfg.eos_token_id)
            generated.append(nxt)
            logits, caches, _ = self._decode(self.params, nxt[:, None],
                                             index, caches)
            index = index + 1
            if cfg.eos_token_id is not None and bool(finished.all()):
                break
        gen = np.stack([np.asarray(g) for g in generated], axis=1) \
            if generated else np.zeros((b, 0), np.int32)
        if len(set(lengths.tolist())) == 1:
            # uniform prompts: 2-D (B, S + T) result, finished rows padded
            # with eos (classic HF-style batch output)
            return np.concatenate([np.stack(prompts), gen], axis=1)
        # mixed lengths: one 1-D row per prompt, truncated at its eos
        outs = []
        for i, p in enumerate(prompts):
            row = gen[i]
            if cfg.eos_token_id is not None:
                hits = np.nonzero(row == cfg.eos_token_id)[0]
                if hits.size:
                    row = row[:hits[0] + 1]
            outs.append(np.concatenate([p, row]))
        return outs


    def first_block(self, prompt: np.ndarray) -> tuple:
        """``(prefilled, block)`` of a prompt under diffusion over blocks
        of L: the first ``(len // L) * L`` positions are prefilled, and the
        prompt's last ``len % L`` tokens are the fixed head of the first
        generated block, ``block`` (L,) int32 with masks behind them.  A
        prompt that holds the mask id is refused."""
        l, mask = self.config.block_length, self.diffusion.mask_token_id
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if (prompt == mask).any():
            raise ValueError(
                f"the prompt holds the mask token id {mask}, which stands "
                "for a position not yet decided: it cannot be told from "
                "one")
        prefilled = len(prompt) // l * l
        block = np.full((l,), mask, np.int32)
        block[:len(prompt) - prefilled] = prompt[prefilled:]
        return prefilled, block

    def blocks_end(self, prompt_len: int, max_new_tokens: int) -> int:
        """The position past the last block a request of ``max_new_tokens``
        writes: the cache must hold it."""
        l = self.config.block_length
        return -(-(prompt_len + max_new_tokens) // l) * l

    def sampling_settings(self, rows: int, cfg: GenerationConfig):
        """``cfg``'s sampling settings as the arrays a block step takes
        them in, one entry a row."""
        return (jnp.full((rows,), cfg.do_sample, bool),
                jnp.full((rows,), cfg.temperature, jnp.float32),
                jnp.full((rows,), cfg.top_k, jnp.int32))

    def generate_blocks(self, prompts, cfg: GenerationConfig, rng=None):
        """Generation by diffusion over blocks for a batch of prompts (1-D
        int arrays): the prompts' whole blocks prefilled, then the one
        compiled ``_block_step`` forward after forward, every row in its
        own phase, until every row has its ``max_new_tokens`` (or its EOS).
        Returns ``(tokens, forwards)``, a list a row each: the generated
        tokens in position order, and the number of the forward (from 1, a
        row's denoising and committing forwards alike) that unmasked each
        of them."""
        l, mask = self.config.block_length, self.diffusion.mask_token_id
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        b = len(prompts)
        firsts = [self.first_block(p) for p in prompts]
        for p in prompts:
            if self.blocks_end(len(p), cfg.max_new_tokens) > \
                    self.config.seq_len:
                raise ValueError(
                    f"prompt {len(p)} + max_new_tokens "
                    f"{cfg.max_new_tokens}, in blocks of {l}, exceeds "
                    f"seq_len {self.config.seq_len}")
        lengths = jnp.asarray([n for n, _ in firsts], jnp.int32)
        heads = [p[:n] for p, (n, _) in zip(prompts, firsts)]
        if not any(n for n, _ in firsts):
            caches = [(k, v, lengths) for k, v, _ in
                      fresh_kv_caches(self.config, b)]
        elif self.prefill_chunk:
            _, caches = self._run_chunked_prefill(heads, lengths, b)
        else:
            _, caches = self._run_bucketed_prefill(heads, lengths, b)
        ids = jnp.asarray(np.stack([block for _, block in firsts]))
        left = jnp.full((b,), self.denoising_steps, jnp.int32)
        settings = self.sampling_settings(b, cfg)
        # the host's copy of every row's block, the positions of the first
        # block that are the prompt's, and what came out so far
        held = [block.copy() for _, block in firsts]
        skip = [len(p) - n for p, (n, _) in zip(prompts, firsts)]
        since = [np.zeros((l,), np.int64) for _ in range(b)]
        tokens = [[] for _ in range(b)]
        forwards = [[] for _ in range(b)]
        done = [cfg.max_new_tokens <= 0] * b
        n = 0
        while not all(done):
            n += 1
            ids, left, _unmasked, _commits, _logits, caches, _routing, \
                rng = self._block_step(self.params, ids, caches[0][2],
                                       caches, left, settings, rng)
            now = np.asarray(ids)
            for r in range(b):
                if done[r]:
                    continue
                was, held[r] = held[r], now[r]
                _denoised, unmasked, finished = read_block(was, now[r], mask)
                since[r][unmasked] = n
                if not finished:
                    continue
                # the block holds no mask any more: its tokens, in order
                for t, at in zip(now[r][skip[r]:], since[r][skip[r]:]):
                    tokens[r].append(int(t))
                    forwards[r].append(int(at))
                    if len(tokens[r]) >= cfg.max_new_tokens or \
                            t == cfg.eos_token_id:
                        done[r] = True
                        break
                skip[r] = 0
        return tokens, forwards

    def generate_speculative(self,
                             draft: "Generator",
                             input_ids,
                             generation_config: Optional[
                                 GenerationConfig] = None,
                             num_draft: int = 4,
                             seed: int = 0):
        """Speculative decoding: ``draft`` (a small Generator over the
        same tokenizer) proposes ``num_draft`` tokens per round; this
        (target) model verifies them in ONE cached forward.

        Exactness: greedy mode accepts the agreeing argmax prefix and
        provably emits the same sequence as plain greedy decoding of the
        target.  With ``cfg.do_sample`` the proposals are sampled from
        the draft's (warped) distribution and accepted by rejection
        sampling (``speculative_accept``), which makes every emitted
        token EXACTLY target-distributed — speculation changes only how
        many target forwards it takes.  Cache rollback after a rejection
        is free under the cache-as-invars design: garbage K/V beyond the
        write index is masked, so rollback is just resetting the index.
        ``seed`` drives the sampled path's host-side randomness.
        Returns (output_row, stats) where stats has ``rounds`` /
        ``proposed`` / ``accepted``.
        """
        # a rejected round is rolled back by resetting ONE index, which a
        # ring that the rejected tokens were written into does not undo
        for gen in (self, draft):
            require_one_token_steps(gen.config, "speculative decoding "
                                    "(generate_speculative)")
            require_uniform_kv_caches(gen.config,
                                      "the speculative verify step")
        cfg = generation_config or GenerationConfig()
        np_rng = np.random.default_rng(seed)
        prompt = np.asarray(input_ids, np.int32).reshape(-1)
        k = int(num_draft)
        if k < 1:
            raise ValueError(f"num_draft must be >= 1, got {k}")
        if len(prompt) + cfg.max_new_tokens > self.config.seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens "
                f"{cfg.max_new_tokens} exceeds seq_len "
                f"{self.config.seq_len}")
        if len(prompt) + cfg.max_new_tokens > draft.config.seq_len:
            # a too-small draft cache would overrun silently: proposals
            # degrade to garbage and acceptance collapses with no error
            raise ValueError(
                f"draft seq_len {draft.config.seq_len} cannot hold "
                f"prompt {len(prompt)} + max_new_tokens "
                f"{cfg.max_new_tokens}")

        def pick_target(logits):
            """Next token from target logits: argmax, or a warped draw."""
            if not cfg.do_sample:
                return int(np.argmax(np.asarray(logits)[0]))
            p = _warp_probs_np(np.asarray(logits)[0], cfg)
            return _sample_from_probs(p, np_rng.uniform())

        t_logits, t_caches = self._spec_prefill(self, prompt)
        d_logits, d_caches = self._spec_prefill(draft, prompt)
        del d_logits

        pending = pick_target(t_logits)
        generated = [pending]
        stats = {"rounds": 0, "proposed": 0, "accepted": 0}
        eos = cfg.eos_token_id
        while len(generated) < cfg.max_new_tokens and \
                (eos is None or pending != eos):
            # shrink the round near the KV capacity so the verify write
            # (k_r + 1 tokens incl. a bonus slot) always fits — greedy
            # exactness must hold all the way to the cache edge
            idx = int(np.asarray(t_caches[0][2])[0])
            cap = min(self.config.seq_len, draft.config.seq_len)
            k_r = min(k, cap - idx - 1,
                      cfg.max_new_tokens - len(generated))
            if k_r < 1:
                # no room for a proposal round: plain single decode
                t_logits, t_caches, _ = self._decode(
                    self.params, jnp.asarray([[pending]], jnp.int32),
                    t_caches[0][2], t_caches)
                pending = pick_target(t_logits)
                generated.append(pending)
                continue
            # draft proposes k_r tokens (k_r+1 decodes: the last feed
            # keeps the draft cache in lockstep with the verify write)
            props, q_rows = [], []
            tok = pending
            for _ in range(k_r):
                d_logits, d_caches, _ = draft._decode(
                    draft.params, jnp.asarray([[tok]], jnp.int32),
                    d_caches[0][2], d_caches)
                if cfg.do_sample:
                    q = _warp_probs_np(np.asarray(d_logits)[0], cfg)
                    q_rows.append(q)
                    tok = _sample_from_probs(q, np_rng.uniform())
                else:
                    tok = int(np.argmax(np.asarray(d_logits)[0]))
                props.append(tok)
            _discard, d_caches, _ = draft._decode(
                draft.params, jnp.asarray([[props[-1]]], jnp.int32),
                d_caches[0][2], d_caches)

            # target verifies [pending, p1..p_{k_r}] in one forward
            verify = self._get_verify_step(k_r + 1)
            toks = jnp.asarray([[pending] + props], jnp.int32)
            v_logits, t_caches = verify(self.params, toks,
                                        t_caches[0][2], t_caches)
            if cfg.do_sample:
                p_rows = _warp_probs_np(np.asarray(v_logits)[0], cfg)
                a, extra = speculative_accept(
                    props, np.stack(q_rows), p_rows,
                    np_rng.uniform(size=k_r), np_rng.uniform())
                emitted = props[:a] + [extra]
            else:
                t_preds = np.argmax(np.asarray(v_logits)[0], axis=-1)
                a = 0
                while a < k_r and t_preds[a] == props[a]:
                    a += 1
                emitted = props[:a] + [int(t_preds[a] if a < k_r
                                           else t_preds[k_r])]
            stats["rounds"] += 1
            stats["proposed"] += k_r
            stats["accepted"] += a

            # rollback: confirmed this round = pending + a proposals
            conf = 1 + a
            t_caches = [(kc, vc, idx2 - (k_r + 1) + conf)
                        for (kc, vc, idx2) in t_caches]
            d_caches = [(kc, vc, idx2 - (k_r + 1) + conf)
                        for (kc, vc, idx2) in d_caches]
            for t in emitted:
                generated.append(t)
                if eos is not None and t == eos:
                    break
            pending = generated[-1]

        gen = np.asarray(generated[:cfg.max_new_tokens], np.int32)
        if eos is not None:
            hits = np.nonzero(gen == eos)[0]
            if hits.size:
                gen = gen[:hits[0] + 1]
        return np.concatenate([prompt, gen]), stats

    @staticmethod
    def _spec_prefill(gen: "Generator", prompt):
        lengths = jnp.asarray([len(prompt)], jnp.int32)
        if gen.prefill_chunk:
            return gen._run_chunked_prefill([prompt], lengths, 1)
        return gen._run_bucketed_prefill([prompt], lengths, 1)

    def _get_verify_step(self, s: int):
        """Compiled multi-token cached forward (the verify leg): writes
        ``s`` tokens at the per-row index and returns all logits.
        Compiled through the Generator's parallel method when one is set
        (same placement as prefill/decode — the caches stay sharded)."""
        cached = getattr(self, "_verify_steps", None)
        if cached is None:
            cached = self._verify_steps = {}
        if s not in cached:
            model = self.model

            def verify(params, toks, index, caches):
                b, sl = toks.shape
                pos = index[:, None] + jax.lax.broadcasted_iota(
                    jnp.int32, (b, sl), 1)
                return model.apply(params, toks, pos, caches)

            if self._parallel_method is not None:
                import alpa_tpu
                cached[s] = alpa_tpu.parallelize(
                    verify, method=self._parallel_method,
                    donate_argnums=())
            else:
                cached[s] = jax.jit(verify)
        return cached[s]

    def generate_beam(self,
                      input_ids: np.ndarray,
                      num_beams: int = 4,
                      max_new_tokens: int = 32,
                      length_penalty: float = 1.0,
                      eos_token_id: Optional[int] = None) -> np.ndarray:
        """Beam search for a single prompt (B=1).

        KV caches are replicated per beam and reordered after every step
        with a compiled gather — the analog of the reference's
        ``get_index_select_mesh_executable`` beam-cache reordering
        (ref mesh_executable.py:1168 / wrapper.py:20).
        """
        require_one_token_steps(self.config, "beam search (generate_beam)")
        require_uniform_kv_caches(self.config, "beam search")
        input_ids = jnp.asarray(input_ids, jnp.int32)
        if input_ids.ndim == 1:
            input_ids = input_ids[None]
        assert input_ids.shape[0] == 1, "beam search takes one prompt"
        s = input_ids.shape[1]
        if s + max_new_tokens > self.config.seq_len:
            raise ValueError(
                f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
                f"seq_len {self.config.seq_len}")

        # Prefill ONCE (B=1), then broadcast logits + caches across the
        # beam axis — K-times cheaper than prefilling identical copies.
        # Chunked mode keeps its one-compile contract here too.
        if self.prefill_chunk:
            logits1, caches1 = self._run_chunked_prefill(
                [np.asarray(input_ids[0])],
                jnp.full((1,), s, jnp.int32), 1)
        else:
            logits1, caches1 = self._prefill(self.params, input_ids, None,
                                             jnp.full((1,), s, jnp.int32))
        beams = jnp.repeat(input_ids, num_beams, axis=0)     # (K, S)
        logits = jnp.repeat(logits1, num_beams, axis=0)
        caches = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, num_beams, axis=0)
            if hasattr(x, "ndim") and x.ndim > 0 else x, caches1)
        scores = jnp.where(jnp.arange(num_beams) == 0, 0.0, -1e9)
        finished = jnp.zeros((num_beams,), bool)
        # generated length per beam, frozen at its eos
        gen_len = jnp.zeros((num_beams,), jnp.float32)

        index = s
        for t in range(max_new_tokens):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            v = logp.shape[-1]
            cand = scores[:, None] + jnp.where(
                finished[:, None], jnp.where(
                    jnp.arange(v)[None] == (eos_token_id or 0), 0.0, -1e9),
                logp)                                        # (K, V)
            flat = cand.reshape(-1)
            top_scores, top_idx = jax.lax.top_k(flat, num_beams)
            beam_idx = top_idx // v
            tok_idx = (top_idx % v).astype(jnp.int32)
            beams = jnp.take(beams, beam_idx, axis=0)
            beams = jnp.concatenate([beams, tok_idx[:, None]], axis=1)
            scores = top_scores
            finished = jnp.take(finished, beam_idx)
            gen_len = jnp.take(gen_len, beam_idx)
            # beams running at the START of this step count this token
            # (including an EOS, matching the standard length convention)
            gen_len = jnp.where(finished, gen_len, gen_len + 1.0)
            if eos_token_id is not None:
                finished = finished | (tok_idx == eos_token_id)
            last_step = (t == max_new_tokens - 1) or (
                eos_token_id is not None and bool(finished.all()))
            if last_step:
                break
            caches = self._reorder(caches, beam_idx)
            logits, caches, _ = self._decode(
                self.params, tok_idx[:, None],
                jnp.full((num_beams,), index, jnp.int32), caches)
            index += 1
        # best beam by length-normalized score (per-beam generated length)
        norm = scores / (jnp.maximum(gen_len, 1.0)**length_penalty)
        best = int(jnp.argmax(norm))
        return np.asarray(beams[best:best + 1])


def get_model(name_or_config,
              params=None,
              batch_size: int = 1,
              rngkey=None) -> Generator:
    """Build a servable Generator (ref wrapper.py:501 get_model).

    ``name_or_config``: a GPTConfig, or a ladder name like "gpt-125M" /
    "opt-2.7b" (random-initialized — weight loading plugs in via
    ``params``; HF checkpoints via ``serve.get_hf_model``).
    """
    from alpa_tpu.model.gpt_model import (config_from_opt_spec,
                                          config_from_spec, init_gpt_real)

    if isinstance(name_or_config, GPTConfig):
        config = name_or_config
    else:
        name = str(name_or_config)
        if name.lower().startswith("opt"):
            config = config_from_opt_spec(name)
        else:
            config = config_from_spec(name.split("-")[-1])
    model = GPTModel(config)
    if params is None:
        model, params = init_gpt_real(config, batch_size, rngkey)
    return Generator(model, params, config, batch_size)
