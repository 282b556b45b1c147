"""Row-level continuous batching engine.

TPU-native analog of ref ``examples/llm_serving/model/wrapper_1d.py``
(1-D continuous batching): a persistent decode loop over a fixed-size
batch of KV-cache rows.  Finished rows are refilled IMMEDIATELY from the
request queue via a single-row prefill scattered into the resident batch
cache — a long generation never blocks short requests behind it, and the
decode executable compiles exactly once for the engine's lifetime.

The per-row KV-cache indices introduced in ``model.gpt_model`` are what
make this possible: every row decodes at its own position.

A dense admission pads its prompt to the smaller of two buckets that holds
it (``Generator.admission_ladder``), and the engine compiles both programs
before it admits anything: ``ContinuousBatchingEngine.__init__``.
"""
import itertools
import logging
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu import fault
from alpa_tpu.model.gpt_model import (cached_key_block, eva_slots,
                                      init_kv_caches, kv_cache_kinds,
                                      require_one_token_steps,
                                      selected_per_row)
from alpa_tpu.serve.generation import (GenerationConfig, Generator,
                                       fresh_kv_caches, read_block,
                                       row_length, sample_rows)
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry import trace as _ttrace

logger = logging.getLogger(__name__)

_REG = _tmetrics.get_registry()
_ADMISSIONS = _REG.counter(
    "alpa_serving_admissions_total", "Requests admitted to a KV-cache row")
_DECODE_STEPS = _REG.counter(
    "alpa_serving_decode_steps_total", "Engine decode ticks executed")
_TOKENS = _REG.counter(
    "alpa_serving_tokens_total", "Tokens generated across all requests")
_STEP_FAILURES = _REG.counter(
    "alpa_serving_step_failures_total", "Engine decode ticks that raised")
_SAMPLE_TICKS = _REG.counter(
    "alpa_serving_sample_ticks_total",
    "Engine decode ticks by what their sampling had to do: greedy (no "
    "active row samples) or sampled (at least one does, so the tick pays "
    "for the sort and the draw)", labelnames=("mode",))
_ACTIVE_ROWS = _REG.gauge(
    "alpa_serving_active_rows", "KV-cache rows currently decoding")
_TTFT = _REG.histogram(
    "alpa_serving_ttft_seconds",
    "Time from submit to first generated token")
_QUEUE_WAIT = _REG.histogram(
    "alpa_serving_queue_wait_seconds",
    "Time from submit to the request taking a KV-cache row")
_PREFILL_PROMPT = _REG.counter(
    "alpa_serving_prefill_prompt_tokens_total",
    "Prompt tokens the engine's prefills were asked to compute")
_PREFILL_PADDED = _REG.counter(
    "alpa_serving_prefill_padded_tokens_total",
    "Token positions the engine's prefill programs ran over")
_DENSE_PREFILLS = _REG.counter(
    "alpa_serving_dense_prefills_total",
    "Admissions prefilled by the dense prefill program, by the bucket of "
    "the engine's admission ladder their prompt was padded to (which "
    "program ran)", labelnames=("bucket",))
_EXPERTS_TOUCHED = _REG.counter(
    "alpa_moe_experts_touched_total",
    "Distinct experts the decode ticks' routed layers touched, summed "
    "over the layers and the ticks (over alpa_serving_decode_steps_total "
    "and the expert layers: experts a layer a tick); where the program "
    "holds a share of a layer's experts, those among them")
_ROUTED_ROWS = _REG.counter(
    "alpa_moe_routed_rows_total", "token-expert rows the experts computed")
_ZERO_PICKS = _REG.counter(
    "alpa_moe_zero_picks_total",
    "token-expert picks of the decode ticks that fell on an identity "
    "expert, which has no matrix and adds its weight times the layer's "
    "input (GPTConfig.num_zero_experts; alpa_moe_routed_rows_total counts "
    "these picks too, so this over that is the share of the routed picks "
    "that multiply nothing)")
_LOCAL_ROWS = _REG.counter(
    "alpa_moe_local_rows_total",
    "token-expert rows the decode ticks routed to an expert this program "
    "holds (GPTConfig.experts_held; over alpa_moe_routed_rows_total of the "
    "same ticks: the share of the routed work that lands here)")
_DECODE_POSITIONS = _REG.counter(
    "alpa_serving_decode_positions_total",
    "Cache positions the decode ticks' active rows attended over (a row's "
    "prompt and the tokens it has so far, the new one included), summed "
    "over the rows and the ticks: what an attention that reads of a cache "
    "what its row holds has to read, in positions; of a tick that verifies "
    "a draft, summed over the row's two query positions, each with what it "
    "attends over")
_DECODE_POSITIONS_READ = _REG.counter(
    "alpa_serving_decode_positions_read_total",
    "Cache positions the decode ticks' attention cores fetched for the "
    "active rows: each row's positions of "
    "alpa_serving_decode_positions_total rounded up to the whole key "
    "blocks its core reads, or the whole served context a row where the "
    "program's core reads every position the cache can hold (held over "
    "read: how much of what the ticks fetched was wanted); where layers "
    "select their positions (GPTConfig.index_topk), what such a layer's "
    "core fetches a row: the selection, or all the row holds while that "
    "is less")
_EVA_KEYS = _REG.counter(
    "alpa_serving_eva_keys_total",
    "Keys a layer the decode ticks' active rows' queries saw, where the "
    "configuration's attention runs over keys of two kinds "
    "(GPTConfig.attention \"eva\"), summed over the rows and the ticks, "
    "from the rows' positions: exact (a query at position t sees t % "
    "eva_window + 1 rows of its own window) and summary (one pooled key "
    "for every eva_chunk positions of the windows before, eva_window / "
    "eva_chunk x (t // eva_window)); not fed by any other configuration",
    labelnames=("kind",))
_EVA_CHUNK_PAIRS = _REG.counter(
    "alpa_serving_eva_chunk_pairs_total",
    "Pairs of a query and a key it sees a layer, over the positions the "
    "chunk steps of chunked admissions ran over (padding included: the "
    "step computes them alike), from the chunks' offsets: exact (the "
    "window's rows at or before the query) and summary (the pooled keys "
    "of the windows before); the counterpart of "
    "alpa_serving_eva_keys_total for the prefill",
    labelnames=("kind",))
_SELECT_POSITIONS = _REG.counter(
    "alpa_serving_select_positions_total",
    "Cache positions on the decode ticks' layers that select their "
    "positions, summed over those layers, the active rows and the ticks: "
    "held (what the rows held there, which the indexer scored) and "
    "selected (what the core fetched and attended over: index_topk a row "
    "a layer, or all it holds while that is less)",
    labelnames=("what",))
_MTP_DRAFTS = _REG.counter(
    "alpa_serving_mtp_drafts_total",
    "Ticks of the active rows of an engine that drafts with a "
    "multi-token-prediction module, by what became of the row's draft: "
    "accepted (it was the model's own greedy choice: the tick yields two "
    "tokens), rejected, or not_offered (the row samples, has one token "
    "left to give, or was admitted since the last tick)",
    labelnames=("result",))
_VERIFY_FORWARDS = _REG.counter(
    "alpa_serving_verify_forwards_total",
    "Forwards of the model by the active rows of an engine that drafts "
    "(row-forwards: every tick is one a row, over the row's sure token and "
    "its draft); alpa_serving_tokens_total over this is tokens a forward, "
    "between 1 and 2")
_BLOCK_FORWARDS = _REG.counter(
    "alpa_serving_block_forwards_total",
    "Forwards of a block by the active rows of an engine that generates "
    "by diffusion over blocks (row-forwards: every tick is one a row), by "
    "what the forward did: denoise (the block still held a mask) or "
    "commit (it held none, and its keys and values stay)",
    labelnames=("phase",))
_BLOCK_UNMASKED = _REG.counter(
    "alpa_serving_block_tokens_unmasked_total",
    "Positions the active rows' denoising forwards decided (over "
    "alpa_serving_block_forwards_total of both phases: tokens a forward)")
_BLOCKS_COMMITTED = _REG.counter(
    "alpa_serving_blocks_committed_total",
    "Blocks the active rows committed to their caches")
_KV_CACHE_BYTES = _REG.gauge(
    "alpa_serving_kv_cache_bytes",
    "Bytes of the engine's resident K and V caches, by the kind of the "
    "layers' cache: window (a ring of the sliding window's positions), "
    "full (the served context), latent (the served context of a latent "
    "layer's normed latent and shared rotary key; with an index key a "
    "position where the layer selects its positions; a ring of the "
    "window's latents where the layer is under the window), conv (a "
    "short convolution's state: its last positions, whatever the context) "
    "or ssm (a Mamba-2 or Mamba-1 mixer's two states: its convolution's "
    "last positions and a matrix a head or a few values a channel, "
    "whatever the context); "
    "or, where a layer's keys are of two kinds (GPTConfig.attention "
    "\"eva\"), window (the rows of one aligned window) and summary (a "
    "pooled key and value for every chunk of the served context), the two "
    "parts of one pair of arrays by their slots; "
    "the arrays' sizes as the device lays them out (held_bytes)",
    labelnames=("kind",))
_KV_CACHE_ARRAY_BYTES = _REG.gauge(
    "alpa_serving_kv_cache_array_bytes",
    "The same bytes by the kind of the layers' cache, the array (keys, "
    "values: a latent layer's latents and shared keys, a short "
    "convolution's state and nothing, a Mamba-2 mixer's conv state and "
    "ssm state) and the shape one layer's array has, "
    "summed over the layers that hold it: a configuration whose kinds "
    "differ in heads and whose keys are wider than its values has four "
    "series",
    labelnames=("kind", "array", "shape"))

# every engine span: category "serving", on this track (the queue waits,
# which overlap each other, on their own)
_TRACK = "serve-engine"
_QUEUE_TRACK = "serve-queue"


def held_bytes(x) -> int:
    """The bytes the device holds for the array ``x``: its elements with
    the two minor-most dimensions of its layout rounded up to the layout's
    first tile, where the device says how it tiles (a TPU pads a
    minor-most dimension to whole lanes); ``x.nbytes`` where it does not."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    tiling = getattr(layout, "tiling", None)
    if not tiling or not tiling[0]:
        return x.nbytes
    # the dimensions from the minor-most up, each tile dimension padding one
    dims = [x.shape[d] for d in reversed(layout.major_to_minor)]
    for at, tile in enumerate(reversed(tiling[0])):
        if at < len(dims):
            dims[at] = -(-dims[at] // tile) * tile
    return int(np.prod(dims, dtype=np.int64)) * x.dtype.itemsize


def _lowered_for_tpu() -> bool:
    """Whether the generator's programs are lowered for a TPU (they run
    on the default backend)."""
    return jax.default_backend() == "tpu"


def _phase(rec, name, args=None):
    """A span of the engine loop — or the shared no-op when ``rec`` is
    None: the loop looks at ``trace.enabled()`` once a tick and hands
    the recorder (or None) down, so a tick with tracing off pays one
    flag check."""
    if rec is None:
        return _ttrace.NULL_SPAN
    return rec.span(name, "serving", args, _TRACK)


_STREAM_END = object()


class _TokenStream:
    """Token iterator for ``submit_stream`` with a close() that works at
    ANY point — including before the first token.  A plain generator
    cannot do this: ``close()`` on a never-started generator is a no-op
    (GeneratorExit only reaches a body suspended at a yield), so
    pre-admission cancellation through a generator is unreachable."""

    def __init__(self, item, q):
        self._item = item
        self._q = q
        #: the engine's identifier of this request (``rid`` of its spans)
        self.rid = item["rid"]

    def __iter__(self):
        return self

    def __next__(self):
        t = self._q.get()
        if t is _STREAM_END:
            if self._item["error"] is not None:
                raise self._item["error"]
            raise StopIteration
        return int(t)

    def close(self):
        """Flag the request cancelled: a queued request is retired at
        admission, an active row is freed next tick."""
        self._item["cancelled"] = True

    def __del__(self):
        self.close()


class _DoneEvent(threading.Event):
    """Event with a completion hook (streams push their end sentinel from
    whichever engine path finishes the item — success, EOS, or error)."""

    def __init__(self, hook=None):
        super().__init__()
        self._hook = hook

    def set(self):
        if self._hook is not None:
            try:
                self._hook()
            except Exception:  # pylint: disable=broad-except
                logger.exception("done hook failed")
        super().set()


class ContinuousBatchingEngine:
    """Persistent decode loop with immediate row refill.

    A tick (``_step``) is: sample one token a row from the resident logits
    in one device program (``generation.sample_rows``, every row under the
    settings its request was admitted with) -> enqueue the decode on that
    program's own output -> only then read the tokens back -> deliver them
    -> admit into the rows that ended.  The token does not leave the device
    between two decodes, so the chip works on the next decode while the
    host reads, delivers and admits.  The decode is enqueued before the
    host knows which rows ended with this token: such a row is decoded
    once more, for nobody, and the next admission overwrites it whole
    (its cache row, index and logits), so the extra step is harmless.

    A generator that generates by diffusion over blocks
    (``Generator.diffusion``, ``GPTConfig.block_length`` L) gets another
    tick in the same order (``_block_tick``): every tick is ONE forward of
    a whole block a row through the generator's one ``_block_step``,
    whatever the rows' phases, which the program decides from the ids (a
    block that still holds a mask is denoised, one that holds none is
    committed to the cache and the row goes on to its next block).  The
    rows' blocks (B, L) and their budgets stay on the device between two
    forwards: the tick enqueues the step on the previous step's own
    output, then reads back the ids it has just handed in, which are what
    the previous step made of them, and delivers a block's tokens in
    position order at the tick that first reads the block without a mask
    (the tick that enqueues its commit).  So a tick yields between none
    and L tokens a row.  An admission prefills the prompt's whole blocks
    in chunks and scatters the row as ever; the prompt's last ``len % L``
    tokens are the fixed head of the row's first block.  A request ends
    inside a block with exactly what it asked for.  The step donates the
    K and V of the resident caches as the decode does.  Such an engine
    needs ``chunked_admission`` and refuses, by name, what is built on one
    token a row a step: ``kv_pool``, a static ``prefix`` and a prefilled
    (disaggregated) admission.

    A generator whose model has a multi-token-prediction module
    (``GPTConfig.num_nextn_predict_layers``, ``Generator._verify_draft``)
    gets a third tick in the same order (``_draft_tick``): sample the row's
    SURE token from the resident logits as ``_step`` does, then ONE program
    that runs the model over two positions a row, the sure token and the
    row's draft of the one after, accepts the draft where it is the model's
    own greedy choice, and runs the module over the positions now confirmed
    for the next draft.  The draft, what is left of the row's
    ``max_new_tokens`` and the row's index stay on the device: the index
    advances by one or by two as the data decide, and a rejected position is
    overwritten by the next tick.  The host reads the sure token it has
    just handed in and, one tick late as the routing always came, whether
    the PREVIOUS tick's draft was kept: a tick delivers that draft (if
    kept) and then the sure token, one or two tokens a row.  A row that
    samples, a row with one token left and a row admitted since the last
    tick are offered no draft and yield one token, which is what ``_step``
    would have yielded.  Such an engine needs ``chunked_admission`` (the
    module's block is prefilled with the chunks) and refuses ``kv_pool``, a
    static ``prefix`` and a prefilled admission by name.
    """

    def __init__(self, generator: Generator, max_batch: int = 4,
                 prompt_bucket: Optional[int] = None,
                 prefix: Optional[Any] = None,
                 scheduler: Optional[Any] = None,
                 kv_pool: Optional[Any] = None,
                 chunked_admission: bool = False):
        """``prompt_bucket`` is the cap of the dense admission: the longest
        prompt ``submit`` takes (the generator's smallest bucket if not
        given), and the top of the ladder a dense admission pads to
        (``Generator.admission_ladder``: the cap and the generator's
        bucket nearest a quarter of it).  An admission runs the prefill of
        the smallest step that holds its prompt, and the engine's thread
        compiles every step's program before it admits anything, so that
        no admission compiles whatever its prompt's length.  An engine
        that cannot reach the dense prefill (``chunked_admission``, a
        static ``prefix``) has no ladder and compiles none of it.

        ``prefix``: a ``Generator.cache_prefix`` handle shared by EVERY
        request (system prompt): each admission prefills only its
        suffix over a copy of the prefix K/V.  Requires the generator's
        chunked-prefill mode (per-row admissions ride chunked suffix
        prefill).

        ``scheduler``: an admission policy speaking the queue protocol
        (``serve.scheduler``: FIFOQueue default, WeightedFairQueue,
        NestedScheduler).  ``submit(..., queue=name)`` routes requests
        to named queues; admission order follows the policy.

        ``kv_pool``: a :class:`serve.kv_cache.KVBlockPool` — every
        admission reserves its block table up front (backpressure
        instead of over-admission), prompts sharing a cached token
        prefix skip recomputing those blocks (gather + chunked suffix
        prefill), and each decode tick scatters the new K/V position
        into the row's current block.  Decode math still runs on the
        dense resident caches, so paged output is bit-exact vs unpaged.
        Mutually exclusive with ``prefix`` (warmed prefixes live in the
        pool's index instead).

        ``chunked_admission``: a prompt is prefilled in the generator's
        fixed chunks (``Generator(prefill_chunk=...)``, the step prefix
        reuse takes its suffixes through) and not by the one dense prefill
        padded to ``prompt_bucket``: one compiled chunk serves every
        prompt up to the cache's length, which a deployment whose longest
        prompt's dense attention would not fit the chip needs.  The
        chunks of one admission run back to back; the resident rows wait
        for them as they wait for a dense prefill."""
        if chunked_admission and not generator.prefill_chunk:
            raise ValueError("chunked admission requires "
                             "Generator(prefill_chunk=...)")
        # generation by diffusion over blocks: the other tick
        self._blocks = getattr(generator, "diffusion", None) is not None
        if self._blocks:
            for what, given in (("kv_pool (KVBlockPool)", kv_pool),
                                ("a static prefix", prefix)):
                if given:
                    require_one_token_steps(generator.config, what)
            if not chunked_admission:
                raise ValueError(
                    "an engine over a generator that generates by "
                    "diffusion over blocks admits in chunks: "
                    "chunked_admission=True")
        # a multi-token-prediction module drafting inside the tick
        self._drafts = getattr(generator, "_verify_draft", None) is not None
        if self._drafts:
            for what, given in (("kv_pool (KVBlockPool)", kv_pool),
                                ("a static prefix", prefix)):
                if given:
                    raise ValueError(
                        f"{what} is built on one position a row a step, "
                        "and this generator's engine verifies a "
                        "multi-token-prediction module's draft in its tick "
                        "(Generator._verify_draft)")
            if not chunked_admission:
                raise ValueError(
                    "an engine over a generator that drafts with a "
                    "multi-token-prediction module admits in chunks (the "
                    "module's block is prefilled with them): "
                    "chunked_admission=True")
        self._chunked = chunked_admission
        self.gen = generator
        self.B = max_batch
        self.bucket = prompt_bucket or generator.prompt_buckets[0]
        cfgm = generator.config
        # the positions in a key block of the tick's attention core, 0
        # where it reads every position the cache can hold
        # (``_positions_read``): a program lowered for a TPU takes the
        # cores over key blocks by its shapes alone
        self._key_block = cached_key_block(
            cfgm, cfgm.block_length if self._blocks else 1) \
            if _lowered_for_tpu() else 0
        # what a selecting layer's core fetches a row at most (0: no layer
        # selects), and how many layers select
        self._selected = selected_per_row(cfgm)
        self._select_layers = kv_cache_kinds(cfgm).count("latent_index")
        # (window, summaries a window) where layers' keys are of two
        # kinds, which the ticks and the chunked admissions then count
        # (``_EVA_KEYS``); None: nothing is counted
        self._eva = (cfgm.eva_window, cfgm.eva_window // cfgm.eva_chunk) \
            if "eva" in kv_cache_kinds(cfgm) else None
        if self._eva is not None:
            for what, given in (("kv_pool (KVBlockPool)", kv_pool),
                                ("a static prefix", prefix)):
                if given:
                    require_one_token_steps(cfgm, what)
            if not chunked_admission:
                raise ValueError(
                    "an engine over a configuration whose layers' caches "
                    "hold ONE window's rows beside summaries "
                    "(GPTConfig.attention \"eva\") admits in chunks that "
                    "divide the window: chunked_admission=True")
        self._prefix = prefix
        # what a dense admission pads to; empty where none can happen
        self._ladder = [] if chunked_admission or prefix is not None \
            else generator.admission_ladder(self.bucket)
        self._pool = kv_pool
        self._tables: List[Optional[Any]] = [None] * max_batch
        self._pool_reuse = False
        if kv_pool is not None:
            if prefix is not None:
                raise ValueError(
                    "kv_pool supersedes the static PrefixHandle: warm "
                    "system prompts via pool.warm_prefix instead")
            if kv_pool.seq_len != cfgm.seq_len:
                raise ValueError(
                    f"kv_pool seq_len {kv_pool.seq_len} != generator "
                    f"seq_len {cfgm.seq_len}")
            self._pool_reuse = (kv_pool.prefix_reuse and
                                bool(generator.prefill_chunk))
            if kv_pool.prefix_reuse and not generator.prefill_chunk:
                logger.warning(
                    "kv prefix reuse needs Generator(prefill_chunk=...) "
                    "to prefill suffixes from the match offset; paging "
                    "stays on but every admission recomputes its prompt")
        if prefix is not None:
            if not generator.prefill_chunk:
                raise ValueError(
                    "engine prefix caching requires "
                    "Generator(prefill_chunk=...)")
            if getattr(prefix, "params", None) is not generator.params:
                # same guard Generator.generate enforces: a stale handle
                # would serve plausible-but-wrong tokens silently
                raise ValueError(
                    "PrefixHandle was built for different params")

        # resident state: batch KV caches + per-row bookkeeping
        self._init_resident()
        self._active = np.zeros((self.B,), bool)
        self._rows: List[Optional[dict]] = [None] * self.B
        # each row's sampling settings, written when the row is given to a
        # request (a free row keeps its last ones, and nobody reads its
        # token), and their device copies, None until the next tick sends
        # them
        self._do_sample = np.zeros((self.B,), bool)
        self._temperature = np.ones((self.B,), np.float32)
        self._top_k = np.zeros((self.B,), np.int32)
        self._settings = None
        if scheduler is None:
            from alpa_tpu.serve.scheduler import FIFOQueue
            scheduler = FIFOQueue()
        self._queue = scheduler
        self._cv = threading.Condition()
        self._key = jax.random.PRNGKey(0)   # carried through sample_rows
        self._sample_rows = jax.jit(sample_rows)
        self._rids = itertools.count()
        self.admissions = 0
        self.decode_steps = 0
        self.step_failures = 0
        self._stop = False

        def scatter_row(caches, caches1, logits, logits1, row):
            new = []
            for (k, v, idx), (k1, v1, idx1) in zip(caches, caches1):
                new.append((k.at[row].set(k1[0]),
                            v.at[row].set(v1[0]),
                            idx.at[row].set(idx1[0])))
            return new, logits.at[row].set(logits1[0].astype(logits.dtype))

        # the scatter donates the engine's own caches and logits (every
        # caller replaces them by the result) and writes the admitted row
        # in place; caches1 may still belong to a prefix handle or a
        # disaggregated prefill and is only read
        self._scatter_row = jax.jit(scatter_row, donate_argnums=(0, 2))

        def set_block(blocks, left, row, block):
            return (blocks.at[row].set(block),
                    left.at[row].set(generator.denoising_steps))

        # an admitted row's first block and a fresh budget
        self._set_block = jax.jit(set_block, donate_argnums=(0, 1))
        # an admitted row has no draft yet, and its whole request to give
        self._set_draft = jax.jit(lambda drafts, left, row, asked: (
            drafts.at[row].set(-1), left.at[row].set(asked)))
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # ---- public API ----

    def submit(self, prompt: np.ndarray,
               cfg: Optional[GenerationConfig] = None,
               on_token=None, queue: Optional[str] = None) -> np.ndarray:
        """Blocking generate for one prompt; rides the shared batch.
        ``on_token(int)`` is invoked from the engine loop as each token
        lands (streaming hook; must not block).  ``queue`` names the
        scheduler queue this request rides (policy-dependent)."""
        item = self._make_item(prompt, cfg, on_token, queue=queue)
        with self._cv:
            self._queue.append(item)
            self._cv.notify()
        item["done"].wait()
        if item["error"] is not None:
            raise item["error"]
        row = np.asarray(item["tokens"], np.int32)
        return np.concatenate([item["prompt"], row])

    def submit_stream(self, prompt: np.ndarray,
                      cfg: Optional[GenerationConfig] = None,
                      queue: Optional[str] = None):
        """Iterator over generated tokens as they land (SSE-friendly).
        Validates and enqueues EAGERLY (so callers can still fail a
        request before committing to a streamed response); raises at the
        point of failure if the engine errors mid-stream."""
        import queue as _queue

        q: "_queue.Queue" = _queue.Queue()
        item = self._make_item(prompt, cfg, q.put,
                               on_done=lambda: q.put(_STREAM_END),
                               queue=queue)
        with self._cv:
            self._queue.append(item)
            self._cv.notify()
        # consumer abandoning the stream (client disconnect) calls
        # close(), which cancels BEFORE admission too — a queued
        # abandoned request is retired instead of burning a KV row
        return _TokenStream(item, q)

    def submit_prefilled(self, prompt: np.ndarray,
                         cfg: Optional[GenerationConfig],
                         caches1, logits1, on_token=None,
                         queue: Optional[str] = None) -> np.ndarray:
        """Blocking decode for a request whose prefill ALREADY ran
        elsewhere (disaggregated serving, serve.disagg): ``caches1`` is
        the dense single-row per-layer ``[(k, v, index)]`` state
        positioned at the prompt length and ``logits1`` the last-token
        logits — exactly what the in-engine prefill would have produced,
        so decode stays bit-exact vs the monolithic path.  The row joins
        the continuous decode batch at the next admission point
        (mid-tick: between decode steps, never waiting out other
        generations)."""
        item = self._make_item(prompt, cfg, on_token, queue=queue,
                               prefilled=(logits1, caches1))
        with self._cv:
            self._queue.append(item)
            self._cv.notify()
        item["done"].wait()
        if item["error"] is not None:
            raise item["error"]
        row = np.asarray(item["tokens"], np.int32)
        return np.concatenate([item["prompt"], row])

    def submit_prefilled_stream(self, prompt: np.ndarray,
                                cfg: Optional[GenerationConfig],
                                caches1, logits1,
                                queue: Optional[str] = None):
        """Streaming variant of :meth:`submit_prefilled` (the decode
        half of a disaggregated handoff)."""
        import queue as _queue

        q: "_queue.Queue" = _queue.Queue()
        item = self._make_item(prompt, cfg, q.put,
                               on_done=lambda: q.put(_STREAM_END),
                               queue=queue, prefilled=(logits1, caches1))
        with self._cv:
            self._queue.append(item)
            self._cv.notify()
        return _TokenStream(item, q)

    def _init_resident(self):
        """Fresh batch caches (per-row index vectors) and logits."""
        cfgm = self.gen.config
        self._caches = [(k, v, jnp.zeros((self.B,), jnp.int32))
                        for (k, v, _i) in init_kv_caches(cfgm, self.B)]
        # by the kind of the layer's entry; a short convolution's is its
        # state, as large a row whatever the context
        by_kind = {"window": 0, "full": 0, "latent": 0, "conv": 0, "ssm": 0}
        if self._eva is not None:
            by_kind["summary"] = 0
        by_array = {}
        for kind, (k, v, _i) in zip(kv_cache_kinds(cfgm), self._caches):
            if kind == "none":
                # a layer that is its MLP alone holds nothing
                continue
            if kind == "eva":
                # one pair of arrays holds rows of two kinds: the
                # summaries' slots first, one window's rows behind them
                summaries, rows = eva_slots(cfgm)
                for array, x in (("keys", k), ("values", v)):
                    held = held_bytes(x)
                    by_kind["summary"] += held * summaries // (
                        summaries + rows)
                    by_kind["window"] += held * rows // (summaries + rows)
                    series = (kind, array, "x".join(map(str, x.shape)))
                    by_array[series] = by_array.get(series, 0) + held
                continue
            # every latent layer's entry is "latent", whatever it holds
            kind = kind.partition("_")[0]
            for array, x in (("keys", k), ("values", v)):
                by_kind[kind] += held_bytes(x)
                series = (kind, array, "x".join(map(str, x.shape)))
                by_array[series] = by_array.get(series, 0) + held_bytes(x)
        for kind, nbytes in by_kind.items():
            _KV_CACHE_BYTES.labels(kind).set(nbytes)
        for series, nbytes in by_array.items():
            _KV_CACHE_ARRAY_BYTES.labels(*series).set(nbytes)
        # what the last decode said of its routed layers ({}: no decode
        # yet, or no such layers): read back with the next tick's tokens
        self._routing = {}
        # the decode's logits as it returns them (every family computes
        # them in the configuration's dtype); sample_rows casts to float32
        self._logits = jnp.zeros((self.B, cfgm.vocab_size), cfgm.dtype)
        if self._blocks:
            # every row's block and what is left of its budget: all
            # masks, as a row that has just committed
            self._block_ids = jnp.full(
                (self.B, cfgm.block_length),
                self.gen.diffusion.mask_token_id, jnp.int32)
            self._left = jnp.full((self.B,), self.gen.denoising_steps,
                                  jnp.int32)
        if self._drafts:
            # every row's draft of the token after its next (-1: none) and
            # how many tokens its request may still be given
            self._draft_ids = jnp.full((self.B,), -1, jnp.int32)
            self._tokens_left = jnp.zeros((self.B,), jnp.int32)

    def _fail_active_locked(self, err):
        """Fail every resident request with ``err`` and free its row."""
        for r in range(self.B):
            if self._active[r]:
                self._rows[r]["error"] = err
                self._release_table(r, self._rows[r])
                self._rows[r]["done"].set()
                self._active[r] = False
                self._rows[r] = None

    def _recover_resident_locked(self, err):
        """The decode and the scatters donate the resident arrays.  A call
        that fails while it traces or compiles has taken nothing; one that
        fails later leaves them deleted, and with them every resident
        row: those requests fail with ``err`` and the engine goes on with
        fresh caches."""
        gone = [self._logits] + [a for k, v, _ in self._caches
                                 for a in (k, v)]
        if self._blocks:
            gone += [self._block_ids, self._left]
        if any(a.is_deleted() for a in gone):
            self._fail_active_locked(err)
            self._init_resident()

    def _make_item(self, prompt, cfg, on_token, on_done=None, queue=None,
                   prefilled=None):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cfg = cfg or GenerationConfig()
        seq_len = self.gen.config.seq_len
        plen = self._prefix.length if self._prefix is not None else 0
        if self._blocks:
            if prefilled is not None:
                require_one_token_steps(self.gen.config,
                                        "a prefilled (disaggregated) "
                                        "admission")
            # refuses a prompt that holds the mask id
            self.gen.first_block(prompt)
            if self.gen.blocks_end(len(prompt), cfg.max_new_tokens) > \
                    seq_len:
                raise ValueError(
                    f"prompt {len(prompt)} + max_new_tokens "
                    f"{cfg.max_new_tokens}, in blocks of "
                    f"{self.gen.config.block_length}, exceeds seq_len "
                    f"{seq_len}")
        if prefilled is not None and self._drafts:
            raise ValueError(
                "a prefilled (disaggregated) admission brings no entry for "
                "the multi-token-prediction module's block, which this "
                "engine's tick drafts with")
        if prefilled is not None and self._prefix is not None:
            raise ValueError(
                "prefilled admission is incompatible with a static "
                "PrefixHandle engine (ingested caches carry the full "
                "prompt)")
        if prefilled is None and self._chunked:
            padded = self._chunk_padded(len(prompt))
            if padded > seq_len:
                raise ValueError(
                    f"prompt {len(prompt)} pads to {padded} in chunks of "
                    f"{self.gen.prefill_chunk}, exceeding seq_len {seq_len}")
        elif prefilled is None and len(prompt) > self.bucket:
            # prefilled rows never run this engine's prefill, so the
            # prefill bucket does not constrain them (seq_len does)
            raise ValueError(
                f"prompt {len(prompt)} exceeds engine bucket "
                f"{self.bucket}")
        # hard errors (not asserts): -O must not admit a request whose
        # decode would write past the cache
        if plen + len(prompt) + cfg.max_new_tokens > seq_len:
            raise ValueError(
                f"prefix {plen} + prompt {len(prompt)} + max_new_tokens "
                f"{cfg.max_new_tokens} exceeds seq_len {seq_len}")
        if self._pool is not None and not self._pool.fits(
                len(prompt) + cfg.max_new_tokens):
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens "
                f"{cfg.max_new_tokens} needs more KV blocks than the "
                f"pool holds ({self._pool.num_blocks} x "
                f"{self._pool.block_size} tokens)")
        if self._prefix is not None:
            # admission prefills in fixed chunks FROM the prefix offset:
            # reject synchronously what chunk padding cannot fit
            padded = self._chunk_padded(len(prompt))
            if plen + padded > seq_len:
                raise ValueError(
                    f"prompt {len(prompt)} pads to {padded} chunks past "
                    f"prefix {plen}, exceeding seq_len {seq_len}; use a "
                    "smaller chunk size or shorter prompt")
        return {"prompt": prompt, "cfg": cfg, "tokens": [],
                "done": _DoneEvent(on_done), "error": None,
                "on_token": on_token, "cancelled": False,
                "queue": queue or "default", "prefilled": prefilled,
                "rid": next(self._rids),
                "t_submit": time.monotonic(),
                # the queue-wait span's start, stamped only while tracing
                "t_submit_us": _ttrace.now_us() if _ttrace.enabled()
                else None}

    def shutdown(self):
        with self._cv:
            self._stop = True
            self._cv.notify()

    # ---- engine loop ----

    def _row_taken(self, rec, item):
        """A queued request gets its row: its wait is over."""
        _QUEUE_WAIT.observe(time.monotonic() - item["t_submit"])
        if rec is not None and item["t_submit_us"] is not None:
            ts = item["t_submit_us"]
            rec.complete("engine.queue-wait", "serving", ts,
                         _ttrace.now_us() - ts,
                         {"rid": item["rid"],
                          "prompt_len": len(item["prompt"])},
                         _QUEUE_TRACK)

    def _give_row(self, r: int, item: dict):
        """Row ``r`` is ``item``'s from here on, sampled under its settings
        (``max_new_tokens`` and ``eos_token_id`` are no sampling settings:
        the deliver phase reads them off the request)."""
        cfg = item["cfg"]
        self._rows[r] = item
        self._active[r] = True
        self._do_sample[r] = cfg.do_sample
        self._temperature[r] = cfg.temperature
        self._top_k[r] = cfg.top_k
        self._settings = None
        self.admissions += 1
        _ADMISSIONS.inc()

    def _chunk_padded(self, n: int) -> int:
        """Positions the chunked prefill runs over for ``n`` tokens."""
        c = self.gen.prefill_chunk
        return -(-n // c) * c

    def _admit_locked(self, rec=None):
        """Fill free rows from the queue, a prefill a row.

        Admission failures (trace/compile/device errors) fail ONLY the
        requests being admitted — the engine loop and resident rows
        survive (a dead loop thread would deadlock every submitter) —
        unless the failing call had already taken the donated resident
        arrays (``_recover_resident_locked``).

        ``rec`` is the trace recorder while tracing is on (see
        ``_phase``): a call that admits is then one ``engine.admit`` span
        with an ``engine.prefill`` child for each prefill it ran.
        """
        t_admit = _ttrace.now_us() if rec is not None else 0.0
        admitted_before = self.admissions

        def next_live():
            """Policy-head item, retiring requests cancelled while still
            queued (client disconnected before admission: prefilling and
            decoding them would burn a row for nobody).  Returns the
            head WITHOUT popping it."""
            while True:
                nxt = self._queue.peek()
                if nxt is None or not nxt.get("cancelled"):
                    return nxt
                self._queue.popleft()["done"].set()

        for r in range(self.B):
            if self._active[r]:
                continue
            nxt = next_live()
            if nxt is None:
                continue
            seq = None
            if self._pool is not None:
                try:
                    seq = self._pool.begin_sequence(
                        nxt["prompt"], nxt["cfg"].max_new_tokens)
                except Exception as e:  # pylint: disable=broad-except
                    self._queue.popleft()
                    nxt["error"] = e
                    nxt["done"].set()
                    continue
                if seq is None:
                    # pool backpressure: live sequences hold the blocks.
                    # Leave the request queued; a retirement frees blocks
                    # and the next tick re-admits.  With NO live rows the
                    # pool can only be out of evictable blocks — fits()
                    # was checked at submit, so fail loudly instead of
                    # spinning.
                    if not self._active.any():
                        from alpa_tpu.serve.kv_cache import \
                            KVPoolExhaustedError
                        item = self._queue.popleft()
                        item["error"] = KVPoolExhaustedError(
                            "KV pool exhausted with no live sequences "
                            "to wait on")
                        item["done"].set()
                    break
            item = self._queue.popleft()
            self._row_taken(rec, item)
            try:
                p = item["prompt"]
                # asked: the tokens this prefill has to compute; padded:
                # the positions its program runs over
                with _phase(rec, "engine.prefill") as prefill_span:
                    if item.get("prefilled") is not None:
                        # disaggregated handoff: the prefill ran on
                        # another replica; its dense row state lands here
                        # unchanged (bit-identical to what this engine's
                        # own prefill would produce — serve.disagg pins
                        # this)
                        path, asked, padded = "handoff", 0, 0
                        logits1, caches1 = item["prefilled"]
                        item["prefilled"] = None  # drop the reference
                    elif seq is not None and seq.matched_tokens:
                        # prefix-reuse hit: gather the cached blocks into
                        # a dense row and prefill ONLY the suffix from the
                        # match offset (gather moves bits unchanged; the
                        # chunk step masks exactly, so this stays
                        # bit-exact)
                        m = seq.matched_tokens
                        path, asked = "prefix", len(p) - m
                        padded = self._chunk_padded(asked)
                        total = row_length(len(p))
                        gathered = self._pool.gather_dense(seq)
                        logits1, caches1 = self.gen._run_chunked_prefill(
                            [p[m:]], total, 1, caches=gathered, start=m)
                    elif self._prefix is not None:
                        # suffix-only prefill OVER the shared prefix K/V.
                        # The handle's arrays are shared read-only: the
                        # chunk step is functional and non-donating, so
                        # the handle survives every admission unchanged.
                        h = self._prefix
                        path, asked = "prefix", len(p)
                        padded = self._chunk_padded(asked)
                        total = row_length(h.length + len(p))
                        logits1, caches1 = self.gen._run_chunked_prefill(
                            [p], total, 1, caches=h.caches,
                            start=h.length, init_last=h.last_logits)
                    elif self._blocks:
                        # the prompt's whole blocks; what is left of it
                        # heads the row's first block
                        asked, block = self.gen.first_block(p)
                        path = "chunked"
                        padded = self._chunk_padded(asked)
                        if asked:
                            logits1, caches1 = \
                                self.gen._run_chunked_prefill(
                                    [p[:asked]], row_length(asked), 1)
                        else:
                            logits1 = self._logits[:1]
                            caches1 = [(k, v, row_length(0)) for k, v, _
                                       in fresh_kv_caches(self.gen.config,
                                                          1)]
                        self._block_ids, self._left = self._set_block(
                            self._block_ids, self._left, r,
                            jnp.asarray(block))
                        item.update(block=None, skip=len(p) - asked,
                                    held=asked)
                    elif self._chunked:
                        path, asked = "chunked", len(p)
                        padded = self._chunk_padded(asked)
                        logits1, caches1 = self.gen._run_chunked_prefill(
                            [p], row_length(len(p)), 1)
                        if self._eva is not None:
                            self._count_eva_chunks(padded)
                    else:
                        path, asked = "dense", len(p)
                        logits1, caches1, padded = self.gen.prefill_row(
                            p, self._ladder)
                    self._caches, self._logits = self._scatter_row(
                        self._caches, caches1, self._logits, logits1, r)
                    if self._drafts:
                        self._draft_ids, self._tokens_left = \
                            self._set_draft(
                                self._draft_ids, self._tokens_left, r,
                                item["cfg"].max_new_tokens)
                        # its first tick reads nothing of the tick before
                        item["fresh"] = True
                    if rec is not None:
                        # programs the prefill ran: the chunks of the
                        # chunk step, or the one dense prefill
                        chunks = padded // self.gen.prefill_chunk \
                            if path in ("chunked", "prefix") else \
                            int(path == "dense")
                        prefill_span.args = {
                            "rid": item["rid"], "prompt_len": len(p),
                            "padded_len": padded, "path": path,
                            "chunks": chunks,
                            # positions the head ran over: the one a
                            # chunk keeps, every one of a dense prefill
                            "head_rows": padded if path == "dense"
                            else chunks}
                _PREFILL_PROMPT.inc(asked)
                _PREFILL_PADDED.inc(padded)
                if path == "dense":
                    _DENSE_PREFILLS.labels(str(padded)).inc()
                if seq is not None:
                    # publish the prompt's full blocks while the row is
                    # still live, so concurrent shared-prefix requests
                    # hit immediately
                    self._pool.scatter_prompt(seq, caches1)
                    if self._pool_reuse:
                        self._pool.register_prompt(seq, p)
                    self._tables[r] = seq
                self._give_row(r, item)
            except Exception as e:  # pylint: disable=broad-except
                logger.exception("row admission failed")
                if seq is not None:
                    self._pool.release(seq, register=False)
                item["error"] = e
                item["done"].set()
                self._recover_resident_locked(e)
        n = self.admissions - admitted_before
        if rec is not None and n:
            rec.complete("engine.admit", "serving", t_admit,
                         _ttrace.now_us() - t_admit, {"n": n}, _TRACK)

    def _release_table(self, r: int, item: Optional[dict]):
        """Return row ``r``'s blocks to the pool.  A cleanly finished
        request first publishes its full prompt+output blocks to the
        prefix index ("recently finished" reuse, incl. multi-turn);
        cancelled/errored rows just free."""
        if self._pool is None or self._tables[r] is None:
            return
        seq = self._tables[r]
        self._tables[r] = None
        try:
            clean = (item is not None and item["error"] is None and
                     not item.get("cancelled"))
            toks = None
            if clean and self._pool_reuse:
                toks = np.concatenate(
                    [item["prompt"],
                     np.asarray(item["tokens"], np.int32)])
            self._pool.release(seq, tokens=toks, register=toks is not None)
        except Exception:  # pylint: disable=broad-except
            logger.exception("KV pool release failed for row %d", r)

    def _run(self):
        # the engine is not ready before its ladder is
        self.gen.compile_row_prefills(self._ladder)
        while True:
            with self._cv:
                if not self._stop and len(self._queue) == 0 and \
                        not self._active.any():
                    # nothing to do: named, so that a device idle for
                    # want of requests is not read as the engine's fault
                    idle = _ttrace.begin("engine.idle", "serving", None,
                                         _TRACK)
                    while not self._stop and (len(self._queue) == 0 and
                                              not self._active.any()):
                        self._cv.wait()
                    _ttrace.end(idle)
                if self._stop:
                    # fail pending work so no submitter deadlocks
                    err = RuntimeError("engine shut down")
                    for item in self._queue.drain():
                        item["error"] = err
                        item["done"].set()
                    self._fail_active_locked(err)
                    return
                # the one look at the flag for this turn of the loop
                rec = _ttrace.get_recorder() if _ttrace.enabled() \
                    else None
                self._admit_locked(rec)
            try:
                with _phase(rec, "engine.decode-tick",
                            {"active": int(self._active.sum())}
                            if rec is not None else None) as tick_span:
                    self._step(rec, tick_span)
            except Exception as e:  # pylint: disable=broad-except
                logger.exception("engine step failed")
                self.step_failures += 1
                _STEP_FAILURES.inc()
                with self._cv:
                    self._fail_active_locked(e)
                    self._recover_resident_locked(e)

    def _count_routing(self, routing):
        """What a step said of its routed layers, into the counters, and
        with it what the admissions' chunks before it said of theirs: the
        host has just read tokens sampled behind them."""
        self.gen.count_window_passes()
        held = getattr(self.gen.config, "experts_held", None)
        zeros = getattr(self.gen.config, "num_zero_experts", 0)
        for layer in routing.get("experts", ()):
            _ROUTED_ROWS.inc(layer.size)
            if zeros:
                # picks past the experts with matrices: identity experts
                real = layer < self.gen.config.num_experts
                _ZERO_PICKS.inc(layer.size - int(real.sum()))
                layer = layer[real]
            if held is not None:
                # this program's share of the layer's experts
                layer = layer[(layer >= held[0]) &
                              (layer < held[0] + held[1])]
                _LOCAL_ROWS.inc(layer.size)
            _EXPERTS_TOUCHED.inc(len(np.unique(layer)))

    def _finish_row(self, r: int, item: dict):
        """Row ``r``'s request is over: the row is free."""
        self._release_table(r, item)
        item["done"].set()
        self._active[r] = False
        self._rows[r] = None

    def _deliver_token(self, item: dict, t: int) -> bool:
        """One token to its request; whether it was the request's last."""
        cfg = item["cfg"]
        item["tokens"].append(t)
        _TOKENS.inc()
        if len(item["tokens"]) == 1 and "t_submit" in item:
            _TTFT.observe(time.monotonic() - item["t_submit"])
        if item.get("on_token") is not None:
            try:
                item["on_token"](t)
            except Exception:  # pylint: disable=broad-except
                logger.exception("on_token callback failed")
        return (cfg.eos_token_id is not None and t == cfg.eos_token_id) or \
            len(item["tokens"]) >= cfg.max_new_tokens

    def _block_tick(self, rec, tick_span):
        """One forward of a whole block for every row (class docstring):
        dispatch, wait, deliver, as ``_step`` has them.  What the host
        reads back are the ids it has just handed to the step, which is
        what the previous step made of every row's block (and what an
        admission since wrote): a row's phase in the previous step, what
        that unmasked and whether the block is finished all follow from
        the row's block as the host read it a tick ago."""
        gen = self.gen
        mask, length = gen.diffusion.mask_token_id, gen.config.block_length
        with _phase(rec, "engine.dispatch"):
            ids = self._block_ids
            routing = self._routing
            (self._block_ids, self._left, _unmasked, _commits, _logits,
             self._caches, self._routing, self._key) = gen._block_step(
                 gen.params, ids, self._caches[0][2], self._caches,
                 self._left, self._settings, self._key)
        self.decode_steps += 1
        _DECODE_STEPS.inc()
        with _phase(rec, "engine.wait"):
            # behind the enqueue: waits for the previous step and for any
            # prefill and scatter behind it, not for this one
            now, routing = jax.device_get((ids, routing))
            self._count_routing(routing)
        with self._cv:
            with _phase(rec, "engine.deliver") as deliver_span:
                delivered = positions = read = 0
                denoising = committing = unmasked = 0
                for r in range(self.B):
                    if not self._active[r]:
                        continue
                    item = self._rows[r]
                    was, item["block"] = item["block"], now[r]
                    over = bool(item.get("cancelled"))
                    # was None: admitted since the last tick, and the
                    # step just enqueued is its first
                    denoised, decided, finished = (False, (), False) \
                        if was is None else read_block(was, now[r], mask)
                    if denoised:
                        denoising += 1
                        unmasked += int(decided.sum())
                    elif was is not None:
                        committing += 1
                        item["held"] += length
                    if finished:
                        # the block's tokens in order, but for the
                        # prompt's in the first block
                        for t in now[r][item["skip"]:]:
                            delivered += 1
                            if self._deliver_token(item, int(t)):
                                over = True
                                break
                        item["skip"] = 0
                    # what the step just enqueued attends over for row r
                    positions += item["held"] + length
                    read += self._positions_read(item["held"] + length)
                    if over:
                        self._finish_row(r, item)
                _BLOCK_FORWARDS.labels("denoise").inc(denoising)
                _BLOCK_FORWARDS.labels("commit").inc(committing)
                _BLOCKS_COMMITTED.inc(committing)
                _BLOCK_UNMASKED.inc(unmasked)
                _DECODE_POSITIONS.inc(positions)
                _DECODE_POSITIONS_READ.inc(read)
                if rec is not None:
                    deliver_span.args = {"tokens": delivered}
                    tick_span.args.update(denoising=denoising,
                                          committing=committing,
                                          unmasked=unmasked)
            # refill freed rows before the next tick
            self._admit_locked(rec)
            _ACTIVE_ROWS.set(int(self._active.sum()))

    def _draft_tick(self, rec, tick_span):
        """One tick of an engine that drafts (class docstring): sample,
        dispatch, wait, deliver, as ``_step`` has them."""
        gen = self.gen
        sampling = int((self._do_sample & self._active).sum())
        with _phase(rec, "engine.sample",
                    {"rows": sampling} if rec is not None else None):
            tokens, self._key = self._sample_rows(
                self._logits, self._key, *self._settings)
        with _phase(rec, "engine.dispatch"):
            said = self._routing
            self._logits, self._caches, self._routing = gen._verify_draft(
                gen.params, tokens, self._caches[0][2], self._caches,
                self._draft_ids, self._tokens_left, self._settings[0])
            self._draft_ids = self._routing.pop("draft")
            self._tokens_left = self._routing.pop("left")
        self.decode_steps += 1
        _DECODE_STEPS.inc()
        with _phase(rec, "engine.wait"):
            # behind the enqueue, as in ``_step``: the sure tokens just
            # handed in, and of the tick before what it did with its drafts
            # and its experts (its logits and selections stay on the device)
            nxt, said = jax.device_get(
                (tokens, {k: v for k, v in said.items() if k in (
                    "experts", "drafted", "offered", "accepted")}))
            nxt = nxt[:, 0]
            self._count_routing(said)
        with self._cv:
            with _phase(rec, "engine.deliver") as deliver_span:
                delivered = positions = read = forwards = 0
                results = {"accepted": 0, "rejected": 0, "not_offered": 0}
                for r in range(self.B):
                    if not self._active[r]:
                        continue
                    item = self._rows[r]
                    forwards += 1
                    over = False
                    if item.pop("fresh", False) or not said:
                        pass        # the tick before was not this request's
                    elif said["accepted"][r]:
                        results["accepted"] += 1
                        delivered += 1
                        over = self._deliver_token(item,
                                                   int(said["drafted"][r]))
                    else:
                        results["rejected" if said["offered"][r]
                                else "not_offered"] += 1
                    if not over:
                        delivered += 1
                        over = self._deliver_token(item, int(nxt[r]))
                    # what the two query positions of the tick just
                    # enqueued attend over for row r
                    held = len(item["prompt"]) + len(item["tokens"])
                    positions += 2 * held + 1
                    read += self._positions_read(held) + \
                        self._positions_read(held + 1)
                    if over or item.get("cancelled"):
                        self._finish_row(r, item)
                for result, n in results.items():
                    _MTP_DRAFTS.labels(result).inc(n)
                _VERIFY_FORWARDS.inc(forwards)
                self._count_positions(positions, read)
                if rec is not None:
                    deliver_span.args = {"tokens": delivered}
                    tick_span.args.update(
                        drafts_accepted=results["accepted"])
            # refill freed rows before the next tick
            self._admit_locked(rec)
            _ACTIVE_ROWS.set(int(self._active.sum()))

    def _count_positions(self, positions: int, read: int):
        """What a tick's active rows held and what its cores fetched of
        it, into the counters (the selecting layers' beside)."""
        _DECODE_POSITIONS.inc(positions)
        _DECODE_POSITIONS_READ.inc(read)
        if self._selected:
            _SELECT_POSITIONS.labels("held").inc(
                positions * self._select_layers)
            _SELECT_POSITIONS.labels("selected").inc(
                read * self._select_layers)

    def _count_eva_keys(self, position: int):
        """What the query a tick enqueued for a row at ``position`` sees a
        layer, into the counters: the rows of its own window up to itself,
        and a summary for every chunk of the windows before."""
        window, summaries = self._eva
        _EVA_KEYS.labels("exact").inc(position % window + 1)
        _EVA_KEYS.labels("summary").inc(summaries * (position // window))

    def _count_eva_chunks(self, padded: int):
        """What the queries of a chunked admission's ``padded`` positions
        (whole chunks from position 0) see a layer, into the counters:
        query ``t`` sees ``t % window + 1`` exact keys and the summaries of
        the windows before."""
        window, summaries = self._eva
        whole, rest = divmod(padded, window)
        _EVA_CHUNK_PAIRS.labels("exact").inc(
            whole * window * (window + 1) // 2 + rest * (rest + 1) // 2)
        _EVA_CHUNK_PAIRS.labels("summary").inc(summaries * (
            window * whole * (whole - 1) // 2 + rest * whole))

    def _positions_read(self, held: int) -> int:
        """Of a row that holds ``held`` positions, those the tick's
        attention core fetches: whole key blocks up to the row's newest
        position, or the whole served context; where layers select their
        positions, what such a layer's core fetches: the selection."""
        seq_len = self.gen.config.seq_len
        if self._selected:
            return min(held, self._selected)
        if not self._key_block:
            return seq_len
        return min(-(-held // self._key_block) * self._key_block, seq_len)

    def _step(self, rec=None, tick_span=None):
        """One decode tick for every active row.  ``rec``: see ``_phase``;
        the tick's phases are child spans of ``engine.decode-tick``, in
        the order sample, dispatch, wait, deliver: the sampled tokens go
        from one device program into the next, and the host reads them
        only once the decode that consumes them is enqueued (why that is
        safe for a row that ends with this token: the class docstring).
        ``tick_span``: the tick's own span, for what only the read-back
        tells of it."""
        fault.fire("scheduler_tick", step=self.decode_steps,
                   active=int(self._active.sum()))
        if self._settings is None:
            # the first tick, and every tick that follows an admission;
            # copies, because the host arrays are written in place
            self._settings = jax.device_put(tuple(
                a.copy() for a in
                (self._do_sample, self._temperature, self._top_k)))
        sampling = int((self._do_sample & self._active).sum())
        _SAMPLE_TICKS.labels("sampled" if sampling else "greedy").inc()
        if self._blocks:
            # the sampling is a part of the block step's own program
            return self._block_tick(rec, tick_span)
        if self._drafts:
            return self._draft_tick(rec, tick_span)
        with _phase(rec, "engine.sample",
                    {"rows": sampling} if rec is not None else None):
            tokens, self._key = self._sample_rows(
                self._logits, self._key, *self._settings)
        with _phase(rec, "engine.dispatch"):
            index = self._caches[0][2]          # per-row positions
            routing = self._routing
            self._logits, self._caches, self._routing = self.gen._decode(
                self.gen.params, tokens, index, self._caches)
        self.decode_steps += 1
        _DECODE_STEPS.inc()
        with _phase(rec, "engine.wait"):
            # the read-back behind the enqueue: the host waits here for
            # the previous tick's decode, any prefill behind it, and this
            # tick's sampling; a decode that failed on the device raises
            # here, one tick late.  What the previous decode said of its
            # routed layers comes along: it was done before this tick's
            # sampling began, so nothing more is waited for
            # (of what it said, the experts alone: what its selecting
            # layers selected stays on the device)
            nxt, routing = jax.device_get(
                (tokens, {k: v for k, v in routing.items()
                          if k == "experts"}))
            nxt = nxt[:, 0]
            self._count_routing(routing)
        if self._pool is not None:
            # the tick wrote each row's new K/V at its pre-decode index;
            # mirror those positions into the block pool (rows without a
            # table land in the scratch block)
            self._pool.write_tokens(self._caches, list(self._tables),
                                    np.asarray(index))

        with self._cv:
            with _phase(rec, "engine.deliver") as deliver_span:
                delivered = positions = read = 0
                for r in range(self.B):
                    if not self._active[r]:
                        continue
                    item = self._rows[r]
                    over = self._deliver_token(item, int(nxt[r]))
                    delivered += 1
                    # what the decode just enqueued attends over for row r
                    held = len(item["prompt"]) + len(item["tokens"])
                    positions += held
                    read += self._positions_read(held)
                    if self._eva is not None:
                        self._count_eva_keys(held - 1)
                    if over or item.get("cancelled"):
                        self._finish_row(r, item)
                self._count_positions(positions, read)
                if rec is not None:
                    deliver_span.args = {"tokens": delivered}
            # refill freed rows before the next tick
            self._admit_locked(rec)
            _ACTIVE_ROWS.set(int(self._active.sum()))
