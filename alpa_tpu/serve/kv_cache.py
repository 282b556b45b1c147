"""Paged KV cache with cross-request prefix reuse (ISSUE 11 tentpole).

vLLM-style paged attention memory, adapted to this repo's cache-as-invars
convention: the serving engine keeps decoding on its DENSE resident caches
(``(B, seq_len, heads, head_dim)`` per layer — the compute view the
compiled decode step was built for), while this pool is the STORAGE tier
behind it: KV lives in fixed-size token blocks, each sequence owns a
block table, blocks are refcounted with copy-on-write, and a hash-chain
index over full-block contents lets any request whose prompt shares a
token prefix with a live or recently finished request skip recomputing
those blocks entirely (they are gathered back into the dense row and
prefill resumes at the match offset via the chunked-prefill path).

Design points that keep everything fixed-shape (one jit compile per
engine lifetime, like the rest of the serving stack):

* Block id 0 is a reserved scratch block.  Gather/scatter calls take
  block-id vectors padded to the per-sequence maximum with id 0 plus a
  mask; masked-out lanes read as zeros and write into scratch, which is
  never read — so every pool op runs at one fixed shape regardless of
  how many blocks a sequence actually holds.
* Eviction only ever touches blocks whose sole reference is the prefix
  index itself (refcount == 1, leaf entries, not pinned), so a cached
  prefix being dropped under pressure can never corrupt a live
  sequence's KV.
* Gather and scatter move bits unchanged, and the no-hit admission path
  is operation-identical to the unpaged engine — paged decode is
  bit-exact vs unpaged (pinned in tests/serve/test_kv_cache.py).

The pool is NOT thread-safe by design intent (the engine loop is its
single writer), but all bookkeeping is taken under an internal lock so
stats/readers from other threads (``/healthz``, the router) stay
consistent.
"""
import hashlib
import logging
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.global_env import global_config
from alpa_tpu.model.gpt_model import (init_kv_caches,
                                      require_one_token_steps,
                                      require_uniform_kv_caches)
from alpa_tpu.telemetry import metrics as _tmetrics

logger = logging.getLogger(__name__)

_REG = _tmetrics.get_registry()
_BLOCKS_IN_USE = _REG.gauge(
    "alpa_kv_blocks_in_use",
    "KV pool blocks held by live sequences or the prefix index")
_PREFIX_HITS = _REG.counter(
    "alpa_kv_prefix_hits_total",
    "Admissions that reused at least one cached prefix block")
_BYTES_SAVED = _REG.counter(
    "alpa_kv_bytes_saved_total",
    "KV bytes served from the prefix index instead of recomputed")
_EVICTIONS = _REG.counter(
    "alpa_kv_evictions_total",
    "Prefix-index blocks evicted under pool pressure")

_ROOT = b"alpa-kv-root"


class KVPoolExhaustedError(RuntimeError):
    """A single request needs more blocks than the pool can ever free."""


class PagedSequence:
    """One sequence's block table: ``ids[i]`` backs token positions
    ``[i*block_size, (i+1)*block_size)``.  Capacity is reserved up front
    (prompt + max_new_tokens) so admission is the only backpressure
    point — a decoding sequence can never run out of blocks."""

    __slots__ = ("ids", "block_size", "prompt_len", "matched_tokens",
                 "capacity_tokens")

    def __init__(self, ids: List[int], block_size: int, prompt_len: int,
                 matched_tokens: int, capacity_tokens: int):
        self.ids = ids
        self.block_size = block_size
        self.prompt_len = prompt_len
        self.matched_tokens = matched_tokens
        self.capacity_tokens = capacity_tokens

    def block_of(self, pos: int) -> int:
        return self.ids[pos // self.block_size]


class _Entry:
    """One cached full block in the prefix index.  ``key`` is the chain
    hash H(parent_key, block_tokens): equal keys mean equal token
    PATHS from the sequence start, so a key match guarantees the cached
    KV is exactly what recomputation would produce."""

    __slots__ = ("key", "parent", "block", "pinned")

    def __init__(self, key: bytes, parent: bytes, block: int,
                 pinned: bool):
        self.key = key
        self.parent = parent
        self.block = block
        self.pinned = pinned


def _chain_key(parent: bytes, tokens: np.ndarray) -> bytes:
    h = hashlib.sha256(parent)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class KVBlockPool:
    """Refcounted block pool + prefix index for one engine/generator.

    A pool is bound to one set of params (cached KV is only valid for
    the weights that produced it); hot weight swaps therefore rebuild
    the engine AND its pool together (``controller._Replica``).
    """

    def __init__(self, config, num_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 prefix_reuse: Optional[bool] = None):
        bs = block_size or global_config.kv_block_size
        if bs <= 0:
            raise ValueError(f"kv_block_size must be positive, got {bs}")
        if config.seq_len % bs:
            raise ValueError(
                f"kv_block_size {bs} must divide seq_len "
                f"{config.seq_len} (block tables tile the cache exactly)")
        n = num_blocks if num_blocks is not None else \
            global_config.kv_cache_blocks
        self.blocks_per_seq = config.seq_len // bs
        if not n:
            # auto-size: room for a full engine batch worth of sequences
            # is the caller's job (for_generator); standalone default is
            # two sequences' worth
            n = 2 * self.blocks_per_seq
        self.block_size = bs
        self.num_blocks = int(n)
        self.seq_len = config.seq_len
        self.prefix_reuse = (global_config.kv_prefix_reuse
                             if prefix_reuse is None else prefix_reuse)
        self.config = config

        # per-layer pool arrays mirror the engine cache convention via
        # the same init used for the dense caches (works for any family
        # honoring the (k, v, index) contract)
        # write_tokens mirrors ONE position a row a tick
        require_one_token_steps(config, "the KV block pool (KVBlockPool)")
        require_uniform_kv_caches(config, "the KV block pool")
        template = init_kv_caches(config, 1)
        self._kp, self._vp = [], []
        self.token_bytes = 0
        for (k, v, _i) in template:
            blk_shape = (self.num_blocks + 1, bs) + k.shape[2:]
            self._kp.append(jnp.zeros(blk_shape, k.dtype))
            self._vp.append(jnp.zeros(blk_shape, v.dtype))
            per_tok = int(np.prod(k.shape[2:]))
            self.token_bytes += 2 * per_tok * k.dtype.itemsize
        self.block_bytes = self.token_bytes * bs

        self._lock = threading.RLock()
        self._rc = np.zeros(self.num_blocks + 1, np.int64)
        self._rc[0] = 1  # scratch: permanently reserved
        self._free = list(range(self.num_blocks, 0, -1))
        self._entries: "OrderedDict[bytes, _Entry]" = OrderedDict()
        self._children: Dict[bytes, set] = {}
        self.prefix_hits = 0
        self.bytes_saved = 0
        self.evictions = 0

        nmax, L = self.blocks_per_seq, config.seq_len

        def gather(kp, vp, ids, mask):
            outs = []
            m4 = mask[:, None, None, None]
            for k, v in zip(kp, vp):
                dk = jnp.where(m4, k[ids], 0).reshape((1, L) + k.shape[2:])
                dv = jnp.where(m4, v[ids], 0).reshape((1, L) + v.shape[2:])
                outs.append((dk, dv))
            return outs

        def scatter_blocks(kp, vp, dk, dv, ids, mask):
            # masked-out lanes are redirected into scratch block 0
            sel = jnp.where(mask, ids, 0)
            nk, nv = [], []
            for k, v, d_k, d_v in zip(kp, vp, dk, dv):
                bk = d_k.reshape((nmax, bs) + k.shape[2:])
                bv = d_v.reshape((nmax, bs) + v.shape[2:])
                nk.append(k.at[sel].set(bk))
                nv.append(v.at[sel].set(bv))
            return nk, nv

        def scatter_token(kp, vp, ck, cv, pos, blocks, offs):
            rows = jnp.arange(pos.shape[0])
            nk, nv = [], []
            for k, v, c_k, c_v in zip(kp, vp, ck, cv):
                nk.append(k.at[blocks, offs].set(c_k[rows, pos]))
                nv.append(v.at[blocks, offs].set(c_v[rows, pos]))
            return nk, nv

        def copy_block(kp, vp, src, dst):
            nk, nv = [], []
            for k, v in zip(kp, vp):
                nk.append(k.at[dst].set(k[src]))
                nv.append(v.at[dst].set(v[src]))
            return nk, nv

        self._gather_jit = jax.jit(gather)
        # the pool buffers are donated: every mutator returns the new
        # arrays and the (lock-held) caller immediately rebinds
        # self._kp/_vp, so XLA updates the pool in place instead of
        # copying the whole block store per scatter
        self._scatter_blocks_jit = jax.jit(scatter_blocks,
                                           donate_argnums=(0, 1))
        self._scatter_token_jit = jax.jit(scatter_token,
                                          donate_argnums=(0, 1))
        self._copy_block_jit = jax.jit(copy_block,
                                       donate_argnums=(0, 1))

    @classmethod
    def for_generator(cls, generator, max_batch: int = 4,
                      **kwargs) -> "KVBlockPool":
        """Pool sized for an engine: knob ``kv_cache_blocks`` when set,
        else one full batch of sequences plus one batch's worth of
        headroom for cached prefixes."""
        cfg = generator.config
        bs = kwargs.get("block_size") or global_config.kv_block_size
        n = global_config.kv_cache_blocks or \
            (2 * max_batch * (cfg.seq_len // max(1, bs)))
        kwargs.setdefault("num_blocks", n)
        return cls(cfg, **kwargs)

    # ---- capacity ---------------------------------------------------

    def _pinned_blocks(self) -> int:
        return sum(1 for e in self._entries.values() if e.pinned)

    def fits(self, total_tokens: int) -> bool:
        """Whether a request of ``total_tokens`` (prompt + max new) can
        EVER be admitted — pinned prefix blocks are unreclaimable."""
        need = -(-total_tokens // self.block_size)
        with self._lock:
            return need <= self.num_blocks - self._pinned_blocks()

    def blocks_in_use(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "blocks_in_use": self.num_blocks - len(self._free),
                "cached_entries": len(self._entries),
                "pinned_entries": self._pinned_blocks(),
                "prefix_hits": self.prefix_hits,
                "bytes_saved": self.bytes_saved,
                "evictions": self.evictions,
            }

    def _update_gauge(self):
        _BLOCKS_IN_USE.set(self.num_blocks - len(self._free))

    # ---- refcounting ------------------------------------------------

    def _decref(self, block: int):
        self._rc[block] -= 1
        if self._rc[block] < 0:
            raise AssertionError(f"block {block} refcount underflow")
        if self._rc[block] == 0:
            self._free.append(block)

    def _evict_one(self) -> bool:
        """Drop the least-recently-used evictable index entry (leaf, not
        pinned, no other holders).  Parents are always touched at least
        as recently as their children on a match walk, so LRU order
        visits children first — eviction peels chains from the tail."""
        for key in list(self._entries):
            e = self._entries[key]
            if e.pinned or self._children.get(key):
                continue
            if self._rc[e.block] != 1:
                continue  # a live sequence still shares this block
            del self._entries[key]
            sibs = self._children.get(e.parent)
            if sibs is not None:
                sibs.discard(key)
                if not sibs:
                    del self._children[e.parent]
            self._decref(e.block)
            self.evictions += 1
            _EVICTIONS.inc()
            return True
        return False

    def _allocate(self, n: int) -> Optional[List[int]]:
        while len(self._free) < n:
            if not self._evict_one():
                return None
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._rc[b] = 1
        return got

    # ---- prefix index -----------------------------------------------

    def _match_and_ref(self, tokens: np.ndarray) -> List[int]:
        """Walk the hash chain over full prompt blocks, taking a
        reference on every hit.  Capped so at least the final prompt
        token is always recomputed — its logits seed decode."""
        bs = self.block_size
        cap = (len(tokens) - 1) // bs
        matched, parent = [], _ROOT
        for i in range(cap):
            key = _chain_key(parent, tokens[i * bs:(i + 1) * bs])
            e = self._entries.get(key)
            if e is None:
                break
            self._rc[e.block] += 1
            self._entries.move_to_end(key)
            matched.append(e.block)
            parent = key
        return matched

    def _register_chain(self, tokens: np.ndarray, ids: List[int],
                        pinned: bool = False) -> int:
        """Insert every full block of ``tokens`` into the index (the
        index holds its own reference).  Existing entries win — content
        keys are path-unique, so a duplicate block is simply not
        indexed twice."""
        bs = self.block_size
        parent, added = _ROOT, 0
        for i in range(len(tokens) // bs):
            key = _chain_key(parent, tokens[i * bs:(i + 1) * bs])
            e = self._entries.get(key)
            if e is None:
                e = _Entry(key, parent, ids[i], pinned)
                self._entries[key] = e
                self._children.setdefault(parent, set()).add(key)
                self._rc[ids[i]] += 1
                added += 1
            elif pinned:
                e.pinned = True
            self._entries.move_to_end(key)
            parent = key
        return added

    # ---- sequence lifecycle -----------------------------------------

    def begin_sequence(self, tokens, max_new_tokens: int
                       ) -> Optional[PagedSequence]:
        """Reserve a block table for prompt + max_new_tokens, reusing
        cached prefix blocks when the index matches.  Returns ``None``
        when the pool cannot free enough blocks RIGHT NOW (live
        sequences hold them — the caller backpressures and retries
        after a decode tick); raises :class:`KVPoolExhaustedError` when
        the request can never fit."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        total = len(tokens) + int(max_new_tokens)
        need = -(-total // self.block_size)
        with self._lock:
            if need > self.num_blocks - self._pinned_blocks():
                raise KVPoolExhaustedError(
                    f"request needs {need} blocks; pool has "
                    f"{self.num_blocks} ({self._pinned_blocks()} pinned)")
            matched: List[int] = []
            if self.prefix_reuse:
                matched = self._match_and_ref(tokens)
            got = self._allocate(need - len(matched))
            if got is None:
                for b in matched:
                    self._decref(b)
                return None
            seq = PagedSequence(
                ids=matched + got, block_size=self.block_size,
                prompt_len=len(tokens),
                matched_tokens=len(matched) * self.block_size,
                capacity_tokens=need * self.block_size)
            if matched:
                self.prefix_hits += 1
                _PREFIX_HITS.inc()
                saved = len(matched) * self.block_bytes
                self.bytes_saved += saved
                _BYTES_SAVED.inc(saved)
            self._update_gauge()
            return seq

    def release(self, seq: PagedSequence, tokens=None,
                register: bool = True):
        """Return a sequence's blocks.  With ``register`` (and reuse
        on), every FULL block of ``tokens`` (prompt + generated) is
        first published to the prefix index so follow-up and multi-turn
        requests can hit it; the index reference keeps those blocks
        alive past the sequence."""
        with self._lock:
            if register and self.prefix_reuse and tokens is not None:
                tokens = np.asarray(tokens, np.int32).reshape(-1)
                self._register_chain(tokens, seq.ids)
            for b in seq.ids:
                self._decref(b)
            seq.ids = []
            self._update_gauge()

    def register_prompt(self, seq: PagedSequence, tokens):
        """Publish a live sequence's full PROMPT blocks immediately
        after admission, so concurrent requests sharing the prefix hit
        while this one is still decoding."""
        if not self.prefix_reuse:
            return
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        nfull = (len(tokens) // self.block_size) * self.block_size
        with self._lock:
            self._register_chain(tokens[:nfull], seq.ids)
            self._update_gauge()

    # ---- copy-on-write ----------------------------------------------

    def fork(self, seq: PagedSequence) -> PagedSequence:
        """Second table over the same blocks (shared until written)."""
        with self._lock:
            for b in seq.ids:
                self._rc[b] += 1
            self._update_gauge()
            return PagedSequence(
                ids=list(seq.ids), block_size=seq.block_size,
                prompt_len=seq.prompt_len,
                matched_tokens=seq.matched_tokens,
                capacity_tokens=seq.capacity_tokens)

    def ensure_writable(self, seq: PagedSequence, block_idx: int) -> int:
        """Copy-on-write: before writing into ``seq.ids[block_idx]``,
        give the sequence a private copy if the block is shared (other
        tables or the prefix index hold it)."""
        with self._lock:
            b = seq.ids[block_idx]
            if self._rc[b] <= 1:
                return b
            got = self._allocate(1)
            if got is None:
                raise KVPoolExhaustedError(
                    "no free block for copy-on-write")
            dst = got[0]
            self._kp, self._vp = self._copy_block_jit(
                self._kp, self._vp, b, dst)
            self._decref(b)
            seq.ids[block_idx] = dst
            self._update_gauge()
            return dst

    # ---- device data movement ---------------------------------------

    def _padded_ids(self, ids: List[int], lo: int, hi: int):
        arr = np.zeros((self.blocks_per_seq,), np.int32)
        mask = np.zeros((self.blocks_per_seq,), bool)
        arr[lo:hi] = ids[lo:hi]
        mask[lo:hi] = True
        return jnp.asarray(arr), jnp.asarray(mask)

    def gather_dense(self, seq: PagedSequence):
        """Materialize the matched prefix region of ``seq`` as dense
        per-layer caches ``[(k, v, index_vec)]`` positioned at the match
        offset — exactly the shape ``Generator._run_chunked_prefill``
        resumes from (the reuse-hit admission path)."""
        m = seq.matched_tokens // self.block_size
        ids, mask = self._padded_ids(seq.ids, 0, m)
        with self._lock:
            outs = self._gather_jit(self._kp, self._vp, ids, mask)
        idx = jnp.asarray([seq.matched_tokens], jnp.int32)
        return [(k, v, idx) for (k, v) in outs]

    def gather_blocks(self, seq: PagedSequence, num_blocks: int):
        """Materialize the first ``num_blocks`` blocks of ``seq`` as
        host block arrays ``[(k, v)]`` per layer, each shaped
        ``(num_blocks, block_size, ...)`` — the block-table slice a
        disaggregated prefill replica ships to a decode replica
        (serve.disagg).  Gather moves bits unchanged, so the handoff
        payload is exactly what the pool holds."""
        ids, mask = self._padded_ids(seq.ids, 0, num_blocks)
        with self._lock:
            outs = self._gather_jit(self._kp, self._vp, ids, mask)
        bs = self.block_size
        res = []
        for (k, v) in outs:
            kk = np.asarray(k)[0, :num_blocks * bs]
            vv = np.asarray(v)[0, :num_blocks * bs]
            res.append((kk.reshape((num_blocks, bs) + kk.shape[1:]),
                        vv.reshape((num_blocks, bs) + vv.shape[1:])))
        return res

    def scatter_prompt(self, seq: PagedSequence, dense_caches):
        """Store the freshly prefilled prompt region (dense single-row
        caches) into the sequence's NEW blocks — matched blocks already
        hold identical bits and are skipped."""
        m = seq.matched_tokens // self.block_size
        nprompt = -(-seq.prompt_len // self.block_size)
        if nprompt <= m:
            return
        ids, mask = self._padded_ids(seq.ids, m, nprompt)
        dk = [c[0] for c in dense_caches]
        dv = [c[1] for c in dense_caches]
        with self._lock:
            self._kp, self._vp = self._scatter_blocks_jit(
                self._kp, self._vp, dk, dv, ids, mask)

    def write_tokens(self, batch_caches,
                     tables: List[Optional[PagedSequence]],
                     positions: np.ndarray):
        """Per decode tick: copy each active row's just-written K/V
        position from the dense batch caches into its table's block.
        Rows without a table write into scratch (fixed shape — one
        compile for the engine's whole life)."""
        B = len(tables)
        blocks = np.zeros((B,), np.int32)
        offs = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        for r, t in enumerate(tables):
            if t is None:
                continue
            p = int(positions[r])
            if p >= t.capacity_tokens:
                raise AssertionError(
                    f"row {r} wrote past its reserved blocks "
                    f"({p} >= {t.capacity_tokens})")
            blocks[r] = t.block_of(p)
            offs[r] = p % self.block_size
            pos[r] = p
        ck = [c[0] for c in batch_caches]
        cv = [c[1] for c in batch_caches]
        with self._lock:
            self._kp, self._vp = self._scatter_token_jit(
                self._kp, self._vp, ck, cv, jnp.asarray(pos),
                jnp.asarray(blocks), jnp.asarray(offs))

    # ---- warmed (registered) prefixes -------------------------------

    def warm_prefix(self, generator, prefix_ids) -> int:
        """Precompute a system prompt's KV into PINNED index entries
        (supersedes the one-static-``PrefixHandle`` mode for paged
        serving: requests send FULL prompts and match against any number
        of warmed prefixes).  Returns the number of tokens warmed."""
        ids = np.asarray(prefix_ids, np.int32).reshape(-1)
        nfull = len(ids) // self.block_size
        if nfull == 0 or not self.prefix_reuse:
            return 0
        span = nfull * self.block_size
        lengths = jnp.asarray([span], jnp.int32)
        if generator.prefill_chunk:
            _, caches = generator._run_chunked_prefill(
                [ids[:span]], lengths, 1)
        else:
            _, caches = generator._run_bucketed_prefill(
                [ids[:span]], lengths, 1)
        with self._lock:
            got = self._allocate(nfull)
            if got is None:
                raise KVPoolExhaustedError(
                    f"cannot pin {nfull} blocks for a warmed prefix")
        seq = PagedSequence(ids=got, block_size=self.block_size,
                            prompt_len=span, matched_tokens=0,
                            capacity_tokens=span)
        self.scatter_prompt(seq, caches)
        with self._lock:
            self._register_chain(ids[:span], got, pinned=True)
            # drop the bootstrap references; the pinned entries keep
            # the blocks alive forever
            for b in got:
                self._decref(b)
            self._update_gauge()
        logger.info("warmed %d prefix tokens (%d pinned blocks)",
                    span, nfull)
        return span
