"""Packed 1-D prefill: many prompts in ONE model forward.

TPU-native analog of the reference's 1-D packed batching
(ref ``examples/llm_serving/model/opt_model_1d.py`` + ``wrapper_1d.py``):
the reference flattens all prompts into one token stream and relies on a
custom fused-MHA CUDA kernel with an external cache manager; here the
same packing rides a block-diagonal SEGMENT mask inside stock XLA
attention (static shapes, no custom kernel), and the packed KV is
re-gathered into per-row caches with one XLA gather — so the row-level
continuous-batching engine decodes from it unchanged.

Why packing: N single-prompt prefills waste (bucket - len) padding FLOPs
per prompt and N dispatches; one packed prefill pays one dispatch and
pads only to the shared total bucket.

Scope: models whose positions enter via ``position_ids`` (GPT/OPT
learned embeddings).  Rotary/ALiBi models bake positions into attention
at their GLOBAL offset, so relocating packed KV to row-local offsets
would corrupt them — they take the per-row prefill path instead.
"""
import logging
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import (GPTConfig, require_one_token_steps,
                                      require_uniform_kv_caches)
from alpa_tpu.serve.generation import fresh_kv_caches

logger = logging.getLogger(__name__)


def pack_prompts(prompts: Sequence[np.ndarray], total_bucket: int,
                 max_rows: int) -> Tuple[np.ndarray, ...]:
    """Pack prompts into one (1, total_bucket) row.

    Returns (ids, segment_ids, position_ids, starts, lens); all prompt
    slots beyond ``len(prompts)`` get a 1-token dummy segment sharing
    position 0 of the padding region (masked out by segment id -1 where
    unused).
    """
    assert len(prompts) <= max_rows
    ids = np.zeros((1, total_bucket), np.int32)
    seg = np.full((1, total_bucket), -1, np.int32)
    pos = np.zeros((1, total_bucket), np.int32)
    starts = np.zeros((max_rows,), np.int32)
    lens = np.ones((max_rows,), np.int32)
    off = 0
    for r, p in enumerate(prompts):
        p = np.asarray(p, np.int32).reshape(-1)
        n = len(p)
        assert off + n <= total_bucket, (
            f"packed length {off + n} exceeds bucket {total_bucket}")
        ids[0, off:off + n] = p
        seg[0, off:off + n] = r
        pos[0, off:off + n] = np.arange(n)
        starts[r] = off
        lens[r] = n
        off += n
    return ids, seg, pos, starts, lens


class PackedPrefill:
    """One compiled executable: packed forward + KV re-gather to rows.

    ``__call__`` takes up to ``max_rows`` prompts whose total length fits
    ``total_bucket`` and returns (last_logits (max_rows, V), row_caches)
    where row_caches are (max_rows, seq_len, H, D) caches with per-row
    write indices — exactly the continuous-batching engine's resident
    layout.  Rows beyond the submitted prompt count carry a 1-token dummy
    and must be ignored by the caller.
    """

    def __init__(self, model, params, config: GPTConfig,
                 total_bucket: int, max_rows: int, prefix=None):
        """``prefix``: an optional ``generation.PrefixHandle`` (shared
        system prompt).  The packed chunk is then written at cache
        offset ``prefix.length``: every segment attends to the prefix
        K/V plus its own span, positions continue from the prefix, and
        the per-row re-gather lays each row out as [prefix | suffix]."""
        require_one_token_steps(config, "the packed prefill "
                                "(packed_admission)")
        require_uniform_kv_caches(config, "the packed prefill")
        self.model = model
        self.params = params
        self.config = config
        self.prefix = prefix
        plen = int(prefix.length) if prefix is not None else 0
        self.prefix_len = plen
        self.total_bucket = int(total_bucket)
        self.max_rows = int(max_rows)
        assert plen + self.total_bucket <= config.seq_len, (
            f"prefix {plen} + packed bucket {total_bucket} exceeds "
            f"KV-cache capacity (seq_len {config.seq_len})")
        if prefix is not None and getattr(prefix, "params", None) \
                is not params:
            raise ValueError("PrefixHandle was built for different params")
        self.traces = 0
        row_cap = config.seq_len
        cap = plen + self.total_bucket

        def prefill(params, ids, seg, pos, starts, lens, caches):
            self.traces += 1
            if caches is None:
                # no prefix: the program makes its own zeros
                caches = fresh_kv_caches(config, 1)
            # packed caches sized to prefix + bucket, not full seq_len
            caches = [(k[:, :cap], v[:, :cap], i)
                      for (k, v, i) in caches]
            logits, caches = model.apply(params, ids, pos, caches,
                                         segment_ids=seg)
            # one gather per layer relocates each prompt's KV span to
            # its row-local origin, after the shared prefix region
            # (copied verbatim to every row); positions past len are
            # clamped repeats, masked at decode by the per-row index
            t = jnp.arange(row_cap)[None, :]                 # (1, cap)
            sfx = plen + starts[:, None] + jnp.minimum(
                jnp.maximum(t - plen, 0), lens[:, None] - 1)
            idx = jnp.minimum(jnp.where(t < plen, t, sfx), cap - 1)
            row_caches = []
            for (k, v, _i) in caches:
                rk = k[0][idx]                               # (R, cap, H, D)
                rv = v[0][idx]
                row_caches.append((rk, rv, plen + lens))
            last = logits[0, starts + lens - 1]              # (R, V)
            return last, row_caches

        self._prefill = jax.jit(prefill)

    def __call__(self, prompts: Sequence[np.ndarray]):
        ids, seg, pos, starts, lens = pack_prompts(
            prompts, self.total_bucket, self.max_rows)
        caches = None
        if self.prefix is not None:
            caches = self.prefix.caches
            pos = pos + self.prefix_len  # global positions after prefix
        return self._prefill(self.params, jnp.asarray(ids),
                             jnp.asarray(seg), jnp.asarray(pos),
                             jnp.asarray(starts), jnp.asarray(lens),
                             caches)
