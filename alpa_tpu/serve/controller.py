"""HTTP serving controller.

Analog of ref ``alpa/serve/controller.py:96`` (Controller Ray actor with
uvicorn/starlette ingress + model registry + replica dispatch) — rebuilt on
the standard library: a ``ThreadingHTTPServer`` front end, a registry of
named models, round-robin replica dispatch, and a per-replica
``RequestBatcher`` that coalesces concurrent requests into one
mixed-length batched generate call (device execution is serialized per
replica by the batcher's single worker thread).

Endpoints:
  GET  /models                          -> registered model names
  POST /completions                     -> {"model", "prompt_ids",
        "max_new_tokens"?, "temperature"?, "top_k"?, "do_sample"?}
        => {"output_ids": [[...]]}
  POST /disagg/prefill                  -> same body as /completions;
        => KV handoff artifact wire JSON (serve.disagg)
  POST /disagg/ingest                   -> artifact wire JSON; => SSE
        token stream joining the decode batch
  POST /disagg/fetch | /disagg/ack      -> {"request_id"}: retained-
        artifact re-ingest source / release
  GET  /health                        -> {"status": "ok" | "degraded"
        | "shedding"} (503 when shedding; see docs/fault_tolerance.md)
  GET  /healthz                         -> recovery-state liveness probe
        (200 healthy/suspect/recovering, 503 degraded;
        docs/observability.md)
  GET  /metrics                         -> Prometheus text exposition of
        the process metrics registry (docs/observability.md)
"""
import dataclasses
import json
import logging
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

import numpy as np

from alpa_tpu import fault
from alpa_tpu.global_env import global_config
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry import trace as _ttrace

logger = logging.getLogger(__name__)

_REG = _tmetrics.get_registry()
_QUEUE_DEPTH = _REG.gauge(
    "alpa_serving_queue_depth", "Requests waiting in the batcher queue")
_BATCH_SIZE = _REG.histogram(
    "alpa_serving_batch_size", "Prompts per batched generate call",
    buckets=(1, 2, 4, 8, 16, 32, 64))
_BATCHES = _REG.counter(
    "alpa_serving_batches_total", "Batched generate calls executed")
_REQUESTS = _REG.counter(
    "alpa_serving_requests_total", "Completion requests by outcome",
    labelnames=("outcome",))
_REQ_LATENCY = _REG.histogram(
    "alpa_serving_request_seconds",
    "End-to-end /completions latency (batched path)")


class RequestBatcher:
    """Groups concurrent completion requests into ONE mixed-length
    batched ``Generator.generate`` call (iteration-level batching; the
    analog of ref ``wrapper_1d.py``'s 1-D batching).  Requests arriving
    while the device is busy queue up and ride the next batch instead of
    serializing one generate per request.  Only requests with identical
    sampling settings share a batch; ``max_new_tokens`` may differ (the
    batch runs to the max, each request is truncated to its own)."""

    def __init__(self, generator: Generator, max_batch: int = 8,
                 max_wait_ms: float = 2.0, prefix=None, scheduler=None):
        self.generator = generator
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        # shared system-prompt handle: prompts are suffixes over it, in
        # BOTH the batched and streaming paths (same request semantics)
        self.prefix = prefix
        if scheduler is None:
            from alpa_tpu.serve.scheduler import FIFOQueue
            scheduler = FIFOQueue()
        for method in ("append", "take", "drain", "__len__"):
            if not hasattr(scheduler, method):
                # fail at REGISTRATION, loudly: a protocol gap surfacing
                # inside the worker thread would kill it and hang every
                # submit() forever
                raise TypeError(
                    f"scheduler {type(scheduler).__name__} lacks "
                    f"{method}(); see serve.scheduler's queue protocol")
        self._queue = scheduler
        self._cv = threading.Condition()
        # drain barrier for hot weight swaps: the worker holds this for
        # the whole device-execution section of each batch, so whoever
        # else acquires it (checkpoint.hot_swap via _Replica.swap_
        # weights) is guaranteed no batch is mid-flight — queued
        # requests simply wait and ride the next batch on new weights
        self._gen_lock = threading.Lock()
        self.batches_run = 0          # introspection for tests
        # degraded mode: a broken custom scheduler demotes this batcher
        # to a fresh FIFO queue instead of failing queued requests
        self.degraded = False
        self.degraded_reason: Optional[str] = None
        self.on_degraded = None       # callback(exc), set by _Replica
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def submit(self, prompts: List[np.ndarray],
               cfg: GenerationConfig,
               queue: Optional[str] = None) -> List[np.ndarray]:
        item = {"prompts": prompts, "cfg": cfg,
                "done": threading.Event(), "result": None, "error": None,
                "queue": queue or "default"}
        with self._cv:
            self._queue.append(item)
            self._cv.notify()
        item["done"].wait()
        if item["error"] is not None:
            raise item["error"]
        return item["result"]

    @staticmethod
    def _group_key(cfg: GenerationConfig):
        return (cfg.do_sample, cfg.temperature, cfg.top_k,
                cfg.eos_token_id)

    def _run(self):
        import time
        while True:
            with self._cv:
                while len(self._queue) == 0:
                    self._cv.wait()
                # small window lets concurrent arrivals coalesce
                deadline = time.monotonic() + self.max_wait_s
            while time.monotonic() < deadline:
                time.sleep(self.max_wait_s / 4)
            with self._cv:
                if len(self._queue) == 0:
                    continue
                # selective take in POLICY order (FIFO default): the
                # head item picks the sampling-settings group,
                # compatible items join; skipped items stay in the
                # scheduler with their original priority (fairness
                # neither freezes nor re-tags — scheduler.take's
                # contract)
                state = {"key": None, "n": 0}

                def selector(item, state=state):
                    if state["key"] is None:
                        state["key"] = self._group_key(item["cfg"])
                    fits = state["n"] + len(item["prompts"]) <= \
                        self.max_batch
                    # an oversized request runs alone rather than
                    # starving (its batch is just bigger)
                    if (self._group_key(item["cfg"]) == state["key"]
                            and (fits or state["n"] == 0)):
                        state["n"] += len(item["prompts"])
                        return "take"
                    if state["n"] >= self.max_batch:
                        # batch full: nothing later can join — stop
                        # scanning the backlog
                        return "stop"
                    return "skip"

                try:
                    fault.fire("scheduler_take",
                               backlog=len(self._queue))
                    batch = self._queue.take(selector)
                except Exception as e:  # pylint: disable=broad-except
                    # a faulty custom scheduler must not take queued
                    # requests down with it: demote to a fresh FIFO,
                    # carry every drained item over, and keep serving
                    # (degraded — policy lost, liveness kept).  Failing
                    # the whole backlog here would turn one policy bug
                    # into N client-visible errors.
                    logger.exception(
                        "scheduler.take failed; degrading to FIFO")
                    from alpa_tpu.serve.scheduler import FIFOQueue
                    fresh = FIFOQueue()
                    try:
                        for item in self._queue.drain():
                            fresh.append(item)
                    except Exception:  # pylint: disable=broad-except
                        # drain is the last resort; if even that raises,
                        # whatever it yielded so far is preserved
                        logger.exception("scheduler.drain also failed")
                    self._queue = fresh
                    if not self.degraded:
                        self.degraded = True
                        self.degraded_reason = \
                            f"{type(e).__name__}: {e}"
                        if self.on_degraded is not None:
                            try:
                                self.on_degraded(e)
                            except Exception:  # pylint: disable=broad-except
                                logger.exception(
                                    "on_degraded callback failed")
                    continue
                if not batch:
                    continue
                _QUEUE_DEPTH.set(len(self._queue))
            try:
                with self._gen_lock:
                    prompts = [p for it in batch for p in it["prompts"]]
                    run_cfg = dataclasses.replace(
                        batch[0]["cfg"],
                        max_new_tokens=max(it["cfg"].max_new_tokens
                                           for it in batch))
                    _BATCH_SIZE.observe(len(prompts))
                    with _ttrace.span(
                            "batcher.generate", "serving",
                            {"prompts": len(prompts),
                             "max_new_tokens": run_cfg.max_new_tokens}
                            if _ttrace.enabled() else None,
                            "serve-batcher"):
                        outs = self.generator.generate(prompts, run_cfg,
                                                       prefix=self.prefix)
                self.batches_run += 1
                _BATCHES.inc()
                i = 0
                for it in batch:
                    k = len(it["prompts"])
                    rows = []
                    for j, p in enumerate(it["prompts"]):
                        row = outs[i + j]
                        limit = len(p) + it["cfg"].max_new_tokens
                        rows.append(row[:limit])
                    it["result"] = rows
                    it["done"].set()
                    i += k
            except Exception as e:  # pylint: disable=broad-except
                for it in batch:
                    it["error"] = e
                    it["done"].set()


class _Replica:

    def __init__(self, generator: Generator, prefix=None,
                 scheduler_factory=None, on_degraded=None,
                 warm_prefix_ids=None, engine_rows=None,
                 chunked_admission=False):
        self.generator = generator
        #: sizes of the deployment the streaming engine is built to
        self.engine_rows = engine_rows
        self.chunked_admission = chunked_admission
        self.batcher = RequestBatcher(
            generator, prefix=prefix,
            scheduler=scheduler_factory() if scheduler_factory else None)
        self.batcher.on_degraded = on_degraded
        self.prefix = prefix
        self.scheduler_factory = scheduler_factory
        #: system prompt to pre-warm into the paged prefix index when
        #: the streaming engine is built (kv_paged + kv_prefix_reuse)
        self.warm_prefix_ids = warm_prefix_ids
        self._engine = None
        self._prefill_engine = None
        self._lock = threading.Lock()

    @property
    def degraded(self) -> bool:
        return self.batcher.degraded

    def swap_weights(self, new_params, prefix_ids=None,
                     drain_timeout: float = 30.0) -> None:
        """Swap this replica onto already-staged weights under a drain
        barrier (the swap phase of checkpoint.hot_swap; the staged
        params must share the current params' shapes/dtypes, so every
        compiled executable is reused — the swap is a pointer flip).

        Guarantees: the in-flight batch finishes on the OLD weights;
        queued requests are never dropped and run on the NEW weights;
        a shared-prefix model gets its prefix KV recomputed under the
        barrier so no request ever mixes old prefix with new params.
        The streaming engine is drained (bounded by ``drain_timeout``)
        and lazily rebuilt; an undrained straggler stream finishes its
        remaining tokens on the new weights rather than erroring.
        """
        from alpa_tpu.checkpoint.hot_swap import drain_engine

        # Hold the replica lock first: new streaming requests acquire it
        # in the `engine` property, so none can board the old engine
        # while we retire it.
        with self._lock:
            old_engine = self._engine
            drained = (old_engine is None or
                       drain_engine(old_engine, timeout=drain_timeout))
            # the drain barrier proper: wait out the in-flight batch
            with self.batcher._gen_lock:
                self.generator.params = new_params
                new_prefix = None
                if prefix_ids is not None:
                    new_prefix = self.generator.cache_prefix(prefix_ids)
                self.prefix = new_prefix
                self.batcher.prefix = new_prefix
            if old_engine is not None:
                if drained:
                    old_engine.shutdown()
                else:
                    logger.warning(
                        "engine streams outlived the %.0fs drain window;"
                        " leaving the old engine to finish them on the "
                        "new weights", drain_timeout)
                self._engine = None  # next stream builds a fresh engine
            if self._prefill_engine is not None:
                # prefill-pool KV (and retained handoff artifacts) are
                # only valid for the params that produced them
                self._prefill_engine.shutdown()
                self._prefill_engine = None

    @property
    def engine(self):
        """Lazy continuous-batching engine for streaming requests (so
        non-streaming deployments never spin its decode thread).  When
        the model was registered with a prefix, every streamed request's
        prompt_ids are a SUFFIX over that shared system prompt — unless
        ``kv_paged`` + ``kv_prefix_reuse`` are on, in which case the
        prefix is pre-warmed into the engine's paged block pool and
        requests send FULL prompts (any shared token prefix hits).  A
        hot weight swap rebuilds engine AND pool together: cached KV is
        only valid for the params that produced it."""
        with self._lock:
            if self._engine is None:
                from alpa_tpu.serve.engine import ContinuousBatchingEngine
                sched = (self.scheduler_factory()
                         if self.scheduler_factory else None)
                pool = None
                if global_config.kv_paged:
                    if self.prefix is not None:
                        # kv_prefix_reuse=off kept the PrefixHandle
                        # suffix semantics; those are incompatible with
                        # block tables, so this replica stays unpaged
                        logger.warning(
                            "kv_paged with a static PrefixHandle "
                            "(kv_prefix_reuse=off): replica keeps the "
                            "unpaged suffix engine")
                    else:
                        from alpa_tpu.serve.kv_cache import KVBlockPool
                        pool = KVBlockPool.for_generator(self.generator)
                rows = {} if self.engine_rows is None else \
                    {"max_batch": self.engine_rows}
                self._engine = ContinuousBatchingEngine(
                    self.generator,
                    prompt_bucket=self.generator.prompt_buckets[-1],
                    prefix=None if pool is not None else self.prefix,
                    scheduler=sched, kv_pool=pool,
                    chunked_admission=self.chunked_admission, **rows)
                if pool is not None and self.warm_prefix_ids is not None:
                    pool.warm_prefix(self.generator, self.warm_prefix_ids)
            return self._engine

    @property
    def prefill_engine(self):
        """Lazy prefill-only engine for the disaggregated prefill pool
        (serve.disagg).  Mirrors the decode engine's admission exactly
        (same cap and so the same admission ladder, same prefix-hit
        path), so the handoff
        artifact carries bit-identical KV to what the monolithic engine
        would have computed in place.  A static-PrefixHandle replica
        cannot serve the prefill pool (block tables need kv_paged +
        kv_prefix_reuse semantics)."""
        with self._lock:
            if self._prefill_engine is None:
                if self.prefix is not None:
                    raise fault.ServiceDegradedError(
                        "replica runs a static PrefixHandle; the "
                        "disaggregated prefill pool needs paged KV "
                        "(kv_paged + kv_prefix_reuse)")
                from alpa_tpu.serve.disagg import PrefillEngine
                sched = (self.scheduler_factory()
                         if self.scheduler_factory else None)
                self._prefill_engine = PrefillEngine(
                    self.generator,
                    prompt_bucket=self.generator.prompt_buckets[-1],
                    scheduler=sched)
                if self.warm_prefix_ids is not None:
                    self._prefill_engine.pool.warm_prefix(
                        self.generator, self.warm_prefix_ids)
            return self._prefill_engine


class Controller:
    """Model registry + dispatch (ref controller.py:96)."""

    def __init__(self):
        self._models: Dict[str, List[_Replica]] = {}
        self._rr: Dict[str, int] = {}
        self._prefix_ids: Dict[str, Any] = {}
        self._lock = threading.Lock()
        # health: "ok" -> full service; "degraded" -> serving, but some
        # replica lost its admission policy (FIFO fallback); "shedding"
        # -> recovery declared the backend dead, new work is rejected
        # with ServiceDegradedError (HTTP 503) until recovery clears it
        self._health = "ok"
        self._health_reason: Optional[str] = None
        #: bound RecoveryManager (attach_recovery) — drives /healthz
        self._recovery = None
        #: completed hot swaps, newest last (introspection + /admin)
        self.reloads: List[Dict[str, Any]] = []
        #: recent request latencies (seconds) feeding load_report's p99
        #: — the router's load-aware placement signal (serve.router)
        self._latencies = deque(maxlen=512)

    # -- health / graceful degradation --------------------------------

    def set_health(self, state: str, reason: Optional[str] = None):
        if state not in ("ok", "degraded", "shedding"):
            raise ValueError(f"unknown health state {state!r}")
        with self._lock:
            self._health = state
            self._health_reason = reason
        logger.warning("controller health -> %s (%s)", state, reason)

    def health_report(self) -> Dict[str, Any]:
        with self._lock:
            state, reason = self._health, self._health_reason
            degraded = sorted(name for name, reps in self._models.items()
                              if any(r.degraded for r in reps))
        if state == "ok" and degraded:
            state = "degraded"
            reason = f"replica scheduler fallback: {degraded}"
        report = {"status": state}
        if reason:
            report["reason"] = reason
        if degraded:
            report["degraded_models"] = degraded
        return report

    def load_report(self) -> Dict[str, Any]:
        """Load signals for the multi-replica router (serve.router) and
        ``/healthz``: total queued requests (batcher + engine queues),
        tokens held by in-flight streams, and a request-latency p99 over
        the recent window (ms; ``None`` before any traffic)."""
        depth = 0
        tokens_in_flight = 0
        with self._lock:
            replicas = [r for reps in self._models.values() for r in reps]
        for rep in replicas:
            depth += len(rep.batcher._queue)
            pe = rep._prefill_engine
            if pe is not None:
                depth += pe.queue_depth()
            eng = rep._engine
            if eng is None:
                continue
            with eng._cv:
                depth += len(eng._queue)
                for it in eng._rows:
                    if it is not None:
                        tokens_in_flight += (len(it["prompt"]) +
                                             len(it["tokens"]))
        lat = sorted(self._latencies)
        p99 = lat[int(0.99 * (len(lat) - 1))] * 1e3 if lat else None
        return {"queue_depth": depth,
                "tokens_in_flight": tokens_in_flight,
                "ttft_p99_ms": p99}

    def attach_recovery(self, recovery) -> None:
        """Bind a :class:`alpa_tpu.fault.RecoveryManager`: entering
        DEGRADED sheds load here (503s), recovering restores service."""
        self._recovery = recovery
        recovery.on_degrade = (
            lambda reason=None: self.set_health(
                "shedding", reason or "mesh recovery failed"))
        recovery.on_recover = (
            lambda: self.set_health("ok", "recovered"))

    def _check_shedding(self):
        with self._lock:
            state, reason = self._health, self._health_reason
        if state == "shedding":
            raise fault.ServiceDegradedError(
                f"service unavailable: {reason or 'backend recovering'}")

    def register_model(self, name: str, generator: Generator,
                       prefix_ids=None, scheduler_factory=None,
                       engine_rows: Optional[int] = None,
                       chunked_admission: bool = False):
        """``prefix_ids``: optional shared system prompt.

        ``engine_rows``, ``chunked_admission``: sizes of the deployment,
        for this replica's streaming engine: how many KV-cache rows it
        decodes at once (None: the engine's default), and whether it
        admits a prompt in the generator's prefill chunks
        (``ContinuousBatchingEngine``; the served context is the
        generator's ``config.seq_len``).

        Default (``kv_paged`` off, or ``kv_prefix_reuse`` off): its KV
        is precomputed once (Generator.cache_prefix; requires the
        generator's chunked-prefill mode) and every request to this
        model (batched or streamed) sends only its suffix.  All
        replicas of one model must register the SAME prefix: round-robin
        dispatch must not change what prompt_ids mean.

        With ``kv_paged`` + ``kv_prefix_reuse`` (ISSUE 11) that
        limitation is SUPERSEDED on the streaming path: the prefix is
        pre-warmed into the replica's paged prefix index instead
        (``serve.kv_cache.KVBlockPool.warm_prefix``), requests send
        FULL prompts, any shared token prefix — warmed or organic —
        hits the block cache, and different replicas may warm different
        prefixes (no consistency error).

        ``scheduler_factory``: builds this replica's admission policy
        (``serve.scheduler``, e.g.
        ``lambda: WeightedFairQueue({"paid": 4})``) — one instance for
        the batcher and one for the streaming engine; requests carry a
        ``"queue"`` field to pick their named queue on either path."""
        prefix_ids = (None if prefix_ids is None
                      else np.asarray(prefix_ids, np.int32).reshape(-1))
        if global_config.kv_paged and global_config.kv_prefix_reuse:
            # paged prefix reuse: no shared PrefixHandle, no
            # one-prefix-per-model constraint — prompt_ids are always
            # full prompts, so dispatch cannot change their meaning
            with self._lock:
                self._models.setdefault(name, []).append(
                    _Replica(generator,
                             scheduler_factory=scheduler_factory,
                             warm_prefix_ids=prefix_ids,
                             engine_rows=engine_rows,
                             chunked_admission=chunked_admission,
                             on_degraded=lambda e, n=name: logger.warning(
                                 "model %s replica degraded to FIFO: %s",
                                 n, e)))
                self._rr.setdefault(name, 0)
            logger.info(
                "registered model %s (%d replicas, paged KV%s)", name,
                len(self._models[name]),
                f", warm prefix {len(prefix_ids)} tokens"
                if prefix_ids is not None else "")
            return

        def check_consistent():
            prev = self._prefix_ids[name]
            same = ((prev is None and prefix_ids is None) or
                    (prev is not None and prefix_ids is not None and
                     np.array_equal(prev, prefix_ids)))
            if not same:
                raise ValueError(
                    f"model {name!r} replicas must share one "
                    "prefix: an inconsistent replica would make "
                    "identical requests mean different prompts")

        # Validate first (no commit), run the possibly-slow/failing
        # cache_prefix OUTSIDE the lock, then commit _prefix_ids and the
        # replica append together — a cache_prefix failure must not pin
        # the name to a prefix with zero replicas, and two concurrent
        # registrations of the same name must both be checked against
        # whatever actually got committed.
        with self._lock:
            if name in self._prefix_ids:
                check_consistent()
        prefix = None
        if prefix_ids is not None:
            prefix = generator.cache_prefix(prefix_ids)
        with self._lock:
            if name in self._prefix_ids:
                check_consistent()
            else:
                self._prefix_ids[name] = prefix_ids
            self._models.setdefault(name, []).append(
                _Replica(generator, prefix=prefix,
                         scheduler_factory=scheduler_factory,
                         engine_rows=engine_rows,
                         chunked_admission=chunked_admission,
                         on_degraded=lambda e, n=name: logger.warning(
                             "model %s replica degraded to FIFO: %s",
                             n, e)))
            self._rr.setdefault(name, 0)
        logger.info("registered model %s (%d replicas%s)", name,
                    len(self._models[name]),
                    f", prefix {prefix.length} tokens" if prefix else "")

    def list_models(self) -> List[str]:
        return sorted(self._models)

    def reload_model(self, name: str, checkpoint_source,
                     step: Optional[int] = None) -> Dict[str, Any]:
        """Zero-downtime weight reload (``POST /admin/reload``).

        Phase 1 (background, per replica): stage the checkpoint step
        onto the replica's exact device placement, hash-verifying every
        chunk — requests keep flowing on the old weights the whole time,
        and a corrupt checkpoint fails here without touching serving.
        Phase 2: swap each replica under its drain barrier
        (:meth:`_Replica.swap_weights`) — in-flight requests finish on
        the old weights, queued ones ride the new; nothing is dropped.
        """
        from alpa_tpu.checkpoint.hot_swap import (
            stage_weights_from_checkpoint)
        with self._lock:
            if name not in self._models:
                raise KeyError(f"unknown model {name!r}; "
                               f"registered: {sorted(self._models)}")
            replicas = list(self._models[name])
            prefix_ids = self._prefix_ids.get(name)
        loaded_step = None
        for replica in replicas:
            new_params, loaded_step = stage_weights_from_checkpoint(
                checkpoint_source, replica.generator.params, step=step)
            replica.swap_weights(new_params, prefix_ids=prefix_ids)
        result = {"model": name, "step": loaded_step,
                  "replicas_swapped": len(replicas)}
        with self._lock:
            self.reloads.append(result)
        logger.info("hot-swapped model %s to checkpoint step %s "
                    "(%d replicas)", name, loaded_step, len(replicas))
        return result

    def _pick_replica(self, name: str) -> _Replica:
        with self._lock:
            replicas = self._models[name]
            i = self._rr[name] % len(replicas)
            self._rr[name] += 1
        return replicas[i]

    def _parse_request(self, request: Dict[str, Any]):
        """Shared request validation: (replica, prompt_ids, cfg) — one
        parser so streaming and non-streaming cannot diverge.  Checks
        load shedding FIRST: in shedding mode every new request is
        rejected up front (503) — cheap refusal beats queueing work the
        backend cannot run."""
        self._check_shedding()
        name = request["model"]
        if name not in self._models:
            raise KeyError(f"unknown model {name!r}; "
                           f"registered: {self.list_models()}")
        prompt_ids = np.asarray(request["prompt_ids"], np.int32)
        queue = request.get("queue")
        if queue is not None and (not isinstance(queue, str) or
                                  len(queue) > 64):
            # untrusted input headed for scheduler dict keys: reject
            # non-strings (unhashable lists would 500) and cap length
            raise ValueError("queue must be a string of <= 64 chars")
        cfg = GenerationConfig(
            max_new_tokens=int(request.get("max_new_tokens", 32)),
            temperature=float(request.get("temperature", 1.0)),
            top_k=int(request.get("top_k", 0)),
            do_sample=bool(request.get("do_sample", False)),
            eos_token_id=request.get("eos_token_id"))
        return self._pick_replica(name), prompt_ids, cfg, queue

    def completions(self, request: Dict[str, Any]) -> Dict[str, Any]:
        tic = time.monotonic()
        try:
            with _ttrace.span("serve.request", "serving",
                              {"model": str(request.get("model"))}
                              if _ttrace.enabled() else None,
                              "serve-driver"):
                replica, prompt_ids, cfg, queue = \
                    self._parse_request(request)
                if prompt_ids.ndim == 1:
                    prompt_ids = prompt_ids[None]
                outs = replica.batcher.submit(list(prompt_ids), cfg,
                                              queue=queue)
        except fault.ServiceDegradedError:
            _REQUESTS.labels("shed").inc()
            raise
        except Exception:
            _REQUESTS.labels("error").inc()
            raise
        _REQUESTS.labels("ok").inc()
        elapsed = time.monotonic() - tic
        _REQ_LATENCY.observe(elapsed)
        self._latencies.append(elapsed)
        return {"output_ids": [o.tolist() for o in outs]}

    def completions_stream(self, request: Dict[str, Any]):
        """Token iterator for a single-prompt streaming request (rides
        the replica's continuous-batching engine, so concurrent streams
        share decode ticks).  Yields ints; the full row is
        prompt + yielded tokens."""
        replica, prompt_ids, cfg, queue = self._parse_request(request)
        if prompt_ids.ndim > 1 and prompt_ids.shape[0] != 1:
            raise ValueError(
                "streaming accepts exactly one prompt per request; got "
                f"{prompt_ids.shape[0]} rows")
        return replica.engine.submit_stream(prompt_ids.reshape(-1), cfg,
                                            queue=queue)

    # -- disaggregated prefill/decode (serve.disagg) -------------------

    def disagg_prefill(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Prefill-phase half of a disaggregated request: admit + run
        the prompt's prefill on this replica's prefill pool and return
        the (retained) handoff artifact's wire form."""
        tic = time.monotonic()
        replica, prompt_ids, cfg, queue = self._parse_request(request)
        if prompt_ids.ndim > 1 and prompt_ids.shape[0] != 1:
            raise ValueError(
                "disaggregated prefill takes exactly one prompt per "
                f"request; got {prompt_ids.shape[0]} rows")
        pe = replica.prefill_engine
        pe.model = request["model"]
        art = pe.prefill(prompt_ids.reshape(-1), cfg, queue=queue)
        self._latencies.append(time.monotonic() - tic)
        return art.to_wire()

    def disagg_ingest(self, wire: Dict[str, Any]):
        """Decode-phase half: verify the artifact (any hash mismatch
        raises ArtifactCorruptError — corrupt KV is never decoded),
        land it on a replica's decode engine, and return the token
        stream that joins the continuous decode batch mid-tick."""
        from alpa_tpu.serve import disagg
        self._check_shedding()
        art = disagg.KVHandoffArtifact.from_wire(wire)  # verifies
        name = art.model
        if name not in self._models:
            raise KeyError(f"unknown model {name!r}; "
                           f"registered: {self.list_models()}")
        replica = self._pick_replica(name)
        return disagg.ingest_stream(replica.engine, art)

    def disagg_fetch(self, request_id: str) -> Dict[str, Any]:
        """The retained artifact for ``request_id`` — the router's
        re-ingest source after a decode-side failure."""
        with self._lock:
            replicas = [r for reps in self._models.values()
                        for r in reps]
        for rep in replicas:
            pe = rep._prefill_engine
            if pe is not None:
                art = pe.fetch(request_id)
                if art is not None:
                    return art.to_wire()
        raise KeyError(f"no retained artifact {request_id!r}")

    def disagg_ack(self, request_id: str) -> bool:
        """Drop the retained artifact: its decode stream finished."""
        with self._lock:
            replicas = [r for reps in self._models.values()
                        for r in reps]
        return any(rep._prefill_engine is not None and
                   rep._prefill_engine.ack(request_id)
                   for rep in replicas)


class _Handler(BaseHTTPRequestHandler):
    controller: Controller = None  # set by run_controller

    def log_message(self, fmt, *args):  # quiet
        logger.debug(fmt, *args)

    def _send(self, code: int, payload: Dict):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, code: int, text: str,
                   content_type: str = "text/plain; version=0.0.4"):
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _metrics(self):
        """Prometheus text exposition of the whole process registry.
        Importing monitoring first guarantees every module-level family
        (watchdog gauges, compile-cache collector, ...) is registered
        even when the controller is the only thing this process ran."""
        import alpa_tpu.monitoring  # noqa: F401  pylint: disable=unused-import
        # the serving-fleet families (alpa_kv_*, alpa_router_*) register
        # at module import; pull them in so a controller that never built
        # a pool or router still exposes the series
        import alpa_tpu.serve.kv_cache  # noqa: F401  pylint: disable=unused-import
        import alpa_tpu.serve.router  # noqa: F401  pylint: disable=unused-import
        # ... and the overlap runtime's (alpa_overlap_*), which a process
        # that has run no pipeshard step has not imported yet
        import alpa_tpu.pipeline_parallel.runtime_emitter  # noqa: F401  pylint: disable=unused-import
        self._send_text(200, _tmetrics.get_registry().to_prometheus_text())

    def _healthz(self):
        """Liveness wired to the recovery state machine: 200 while
        HEALTHY/SUSPECT/RECOVERING (body carries the state), 503 once
        DEGRADED.  Falls back to the controller health report when no
        RecoveryManager is attached.  The body also carries
        ``last_flight_dump`` — the path of the most recent flight
        recorder post-mortem (ISSUE 6), so an operator seeing a SUSPECT
        or DEGRADED state knows where the instruction timeline landed
        (null when nothing has been dumped), and ``elastic`` — the
        ElasticSupervisor's episode report when this process runs one
        (docs/fault_tolerance.md#elastic-training; null otherwise)."""
        from alpa_tpu import elastic as _elastic
        from alpa_tpu.telemetry import flight as _flight
        recovery = self.controller._recovery
        if recovery is not None:
            state = recovery.state.value
            code = 503 if state == "degraded" else 200
            self._send(code, {"status": state,
                              "last_flight_dump": _flight.last_dump_path(),
                              "elastic": _elastic.status_report(),
                              "load": self.controller.load_report()})
            return
        report = self.controller.health_report()
        report["last_flight_dump"] = _flight.last_dump_path()
        report["elastic"] = _elastic.status_report()
        report["load"] = self.controller.load_report()
        code = 503 if report["status"] == "shedding" else 200
        self._send(code, report)

    def do_GET(self):
        if self.path == "/health":
            report = self.controller.health_report()
            code = 503 if report["status"] == "shedding" else 200
            self._send(code, report)
        elif self.path == "/healthz":
            self._healthz()
        elif self.path == "/metrics":
            self._metrics()
        elif self.path == "/models":
            self._send(200, {"models": self.controller.list_models()})
        else:
            self._send(404, {"error": f"unknown path {self.path}"})

    def do_POST(self):
        if self.path == "/admin/reload":
            self._admin_reload()
            return
        if self.path.startswith("/disagg/"):
            self._disagg()
            return
        if self.path != "/completions":
            self._send(404, {"error": f"unknown path {self.path}"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            request = json.loads(self.rfile.read(length) or b"{}")
            if request.get("stream"):
                self._stream(request)
                return
            result = self.controller.completions(request)
            self._send(200, result)
        except fault.ServiceDegradedError as e:
            self._send(503, {"error": str(e)})
        except KeyError as e:
            self._send(404, {"error": str(e)})
        except (json.JSONDecodeError, ValueError, AssertionError,
                TypeError) as e:
            self._send(400, {"error": f"bad request: {e}"})
        except Exception as e:  # pylint: disable=broad-except
            logger.exception("completions failed")
            self._send(500, {"error": f"{type(e).__name__}: {e}"})

    def _admin_reload(self):
        """``POST /admin/reload`` {"model", "ckpt_dir", "step"?}: stage
        + hash-verify the checkpoint in the background, then swap every
        replica of the model under a drain barrier.  Requests in flight
        during the call are served without interruption."""
        try:
            length = int(self.headers.get("Content-Length", 0))
            request = json.loads(self.rfile.read(length) or b"{}")
            name = request.get("model")
            ckpt_dir = request.get("ckpt_dir")
            if not name or not ckpt_dir:
                raise ValueError(
                    "reload needs 'model' and 'ckpt_dir' fields")
            step = request.get("step")
            result = self.controller.reload_model(
                name, ckpt_dir, step=None if step is None else int(step))
            self._send(200, result)
        except KeyError as e:
            self._send(404, {"error": str(e)})
        except (json.JSONDecodeError, ValueError, TypeError,
                FileNotFoundError) as e:
            self._send(400, {"error": f"bad reload request: {e}"})
        except Exception as e:  # pylint: disable=broad-except
            logger.exception("hot reload failed")
            self._send(500, {"error": f"{type(e).__name__}: {e}"})

    def _stream(self, request):
        """Server-sent events: one ``data: {"token": t}`` per generated
        token, then ``data: {"done": true}``.  Close-delimited (no
        Content-Length; Connection: close) so stdlib clients can read
        incrementally.

        Validation happens BEFORE headers go out (bad requests still get
        a JSON error status via do_POST); once streaming has started, any
        failure is reported as a final ``data: {"error": ...}`` event —
        never a second status line into the open SSE body.
        """
        # ``serve.request`` for a streamed completion: from the request's
        # validation to its last event, with the engine's ``rid`` so the
        # span joins the engine's ``engine.queue-wait`` / ``engine.prefill``
        args = {"model": str(request.get("model")), "stream": True} \
            if _ttrace.enabled() else None
        token = _ttrace.begin("serve.request", "serving", args,
                              "serve-driver")
        try:
            it = self.controller.completions_stream(request)  # validates
            if args is not None:
                args["rid"] = it.rid
            self._stream_body(it)
        finally:
            _ttrace.end(token)

    def _disagg(self):
        """Disaggregation endpoints (serve.disagg / serve.router):
        ``/disagg/prefill`` -> handoff artifact wire JSON;
        ``/disagg/ingest`` -> SSE token stream joining the decode
        batch; ``/disagg/fetch`` + ``/disagg/ack`` manage the prefill
        side's retained artifacts.  A corrupt artifact maps to 422 so
        the router re-fetches the retained copy instead of failing the
        request."""
        from alpa_tpu.serve.disagg import ArtifactCorruptError
        try:
            length = int(self.headers.get("Content-Length", 0))
            request = json.loads(self.rfile.read(length) or b"{}")
            if self.path == "/disagg/prefill":
                self._send(200,
                           self.controller.disagg_prefill(request))
            elif self.path == "/disagg/ingest":
                it = self.controller.disagg_ingest(request)  # validates
                self._stream_body(it)
            elif self.path == "/disagg/fetch":
                self._send(200, self.controller.disagg_fetch(
                    str(request.get("request_id"))))
            elif self.path == "/disagg/ack":
                self._send(200, {"acked": self.controller.disagg_ack(
                    str(request.get("request_id")))})
            else:
                self._send(404, {"error": f"unknown path {self.path}"})
        except fault.ServiceDegradedError as e:
            self._send(503, {"error": str(e)})
        except ArtifactCorruptError as e:
            self._send(422, {"error": str(e)})
        except KeyError as e:
            self._send(404, {"error": str(e)})
        except (json.JSONDecodeError, ValueError, AssertionError,
                TypeError) as e:
            self._send(400, {"error": f"bad request: {e}"})
        except Exception as e:  # pylint: disable=broad-except
            logger.exception("disagg request failed")
            self._send(500, {"error": f"{type(e).__name__}: {e}"})

    def _stream_body(self, it):
        """Write an already-validated token iterator as SSE."""
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        try:
            try:
                for t in it:
                    self.wfile.write(
                        f"data: {json.dumps({'token': t})}\n\n".encode())
                    self.wfile.flush()
                final = {"done": True}
            except (BrokenPipeError, ConnectionResetError):
                logger.info("stream client disconnected")
                it.close()  # flags the engine row cancelled
                return
            except Exception as e:  # pylint: disable=broad-except
                logger.exception("stream failed mid-generation")
                final = {"error": f"{type(e).__name__}: {e}"}
            self.wfile.write(f"data: {json.dumps(final)}\n\n".encode())
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            logger.info("stream client disconnected at finish")
            it.close()
        finally:
            self.close_connection = True


class _HTTPServer(ThreadingHTTPServer):
    # the listen queue: the standard library's 5 resets the connections of
    # a burst that opens more at once than the accepting thread has taken
    # (seen once as ConnectionResetError in a warm-up of 33 connections;
    # an engine of 64 rows meets 65 at once, and 128 callers after)
    request_queue_size = 1024


class ControllerServer:
    """The running HTTP server (ref run_controller:280)."""

    def __init__(self, controller: Controller, host: str, port: int):
        handler = type("BoundHandler", (_Handler,),
                       {"controller": controller})
        self.httpd = _HTTPServer((host, port), handler)
        self.controller = controller
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self):
        self.thread.start()

    def shutdown(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def run_controller(host: str = "127.0.0.1",
                   port: int = 8000,
                   start: bool = True) -> ControllerServer:
    """Create (and start) a controller server (ref run_controller:280)."""
    server = ControllerServer(Controller(), host, port)
    if start:
        server.start()
    return server
