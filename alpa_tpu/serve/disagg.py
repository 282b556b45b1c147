"""Disaggregated prefill/decode serving (ISSUE 18 tentpole).

DistServe-style phase splitting over the PR 11 serving stack: long
prefills convoy a monolithic :class:`~alpa_tpu.serve.engine.
ContinuousBatchingEngine` — every chunked prefill runs between decode
ticks, so one 2k-token prompt inflates inter-token latency for every
decoding request behind it.  This module splits the two phases onto
separate replica pools:

* A **prefill replica** runs admission + prefill ONLY
  (:class:`PrefillEngine`): it reserves a block table in its own
  :class:`~alpa_tpu.serve.kv_cache.KVBlockPool` (cross-request prefix
  reuse applies — a cached prefix skips recomputation exactly like the
  monolithic engine's hit path), prefills the prompt, and packages the
  request's block-table slice as a :class:`KVHandoffArtifact`:
  per-block K/V payload, content-hashed per block (sha256 over the wire
  bytes, so corruption anywhere between the pools is detected before a
  single token is decoded), plus the last-token logits that seed decode.
* The artifact crosses replicas over the cross-mesh transfer layer:
  payload arrays land on the decode replica's cache sharding through
  :func:`~alpa_tpu.pipeline_parallel.cross_mesh_resharding.
  make_ingest_transfer` (the arrival half of a DirectTransfer whose
  source lives in another process), and the PR 7 activation codec can
  quantize the payload blockwise (``disagg_codec=int8|fp8`` — lossy
  within ``reshard_codec.ERROR_BOUND``, OFF by default so the handoff
  ships verbatim bits).
* A **decode replica** ingests (:func:`ingest_stream`): hashes are
  verified, the dense row state is reconstructed and the request joins
  the continuous decode batch mid-tick via
  ``ContinuousBatchingEngine.submit_prefilled_stream`` — the engine
  scatters the blocks into ITS pool and registers the prefix chain, so
  cross-request reuse keeps working on the decode side too.

Bit-exactness: the prefill replica computes the SAME prefill function
(same code path: ``Generator.prefill_row`` over the same admission
ladder on a miss, gather + chunked suffix prefill on a prefix hit) the
monolithic engine would run, the verbatim payload moves bits unchanged,
and the decode engine's admission/tick path is shared — so the
disaggregated decode stream is
``np.array_equal`` with the monolithic engine on miss, full-hit, and
shared-prefix paths (pinned in tests/serve/test_disagg.py).

Failure handling (no handoff is ever dropped): every produced artifact
is RETAINED by the prefill engine until the router acks the finished
stream.  A decode replica dying mid-handoff (or mid-stream, greedy
decode) makes the router re-fetch the retained artifact and re-ingest
on a survivor; a corrupt artifact (any flipped block hash) is rejected
with :class:`ArtifactCorruptError` and re-fetched — never silently
decoded.  Phase-aware routing, SLOs, and backpressure live in
``serve.router``; knobs in ``global_env`` (``disagg_*``);
docs/serving.md#disaggregated-prefilldecode.
"""
import base64
import dataclasses
import logging
import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import metrics as _tmetrics

logger = logging.getLogger(__name__)

_REG = _tmetrics.get_registry()
_HANDOFF_BYTES = _REG.counter(
    "alpa_disagg_handoff_bytes_total",
    "KV handoff payload bytes shipped prefill -> decode")
_HANDOFF_SECONDS = _REG.histogram(
    "alpa_disagg_handoff_seconds",
    "Handoff latency: artifact produced -> decode replica admitted it")
_HANDOFFS_IN_FLIGHT = _REG.gauge(
    "alpa_disagg_handoffs_in_flight",
    "Handoff artifacts produced and not yet acked by the router")
_TTFT_H = _REG.histogram(
    "alpa_disagg_ttft_seconds",
    "Time to first token through the disaggregated path, by pool",
    labelnames=("pool",))
_ITL_H = _REG.histogram(
    "alpa_disagg_itl_seconds",
    "Inter-token gap through the disaggregated path, by pool",
    labelnames=("pool",))
_REINGESTS = _REG.counter(
    "alpa_disagg_reingests_total",
    "Handoffs re-ingested from the retained artifact, by reason",
    labelnames=("reason",))
_BACKPRESSURE_SHEDS = _REG.counter(
    "alpa_disagg_backpressure_sheds_total",
    "Prefill admissions shed by decode-pool backpressure")
_PREFILLS = _REG.counter(
    "alpa_disagg_prefills_total",
    "Prefill-phase requests completed into handoff artifacts")


class ArtifactCorruptError(RuntimeError):
    """A handoff artifact failed per-block content verification.  The
    router re-fetches the retained pristine copy from the prefill side
    instead of ever decoding corrupt KV."""


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # registered by jax; covers fp8/bfloat16 names
        return np.dtype(getattr(ml_dtypes, name))


def _arr_to_wire(a: np.ndarray) -> Dict[str, Any]:
    a = np.ascontiguousarray(a)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def _arr_from_wire(d: Dict[str, Any]) -> np.ndarray:
    raw = base64.b64decode(d["data"])
    return np.frombuffer(raw, dtype=_np_dtype(d["dtype"])).reshape(
        tuple(d["shape"])).copy()


def _codec_ok(mode: str, dtype: np.dtype) -> bool:
    """Whether the reshard codec can carry this KV dtype under ``mode``
    (mirrors ``reshard_codec.eligible`` minus the size floor — handoff
    payloads opt in explicitly)."""
    if mode == "off":
        return True
    from alpa_tpu.pipeline_parallel import reshard_codec
    if mode not in reshard_codec.ERROR_BOUND:
        return False
    if str(dtype) not in reshard_codec._ELIGIBLE_DTYPES:
        return False
    if mode == "fp8" and not reshard_codec.have_fp8():
        return False
    return True


def _encode_blocks(blocks: np.ndarray, mode: str
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Quantize one layer's ``(num_blocks, block_size, ...)`` K or V
    payload per KV block through the reshard codec (per-block so the
    per-block content hashes stay meaningful over the wire payload)."""
    import jax.numpy as jnp

    from alpa_tpu.pipeline_parallel import reshard_codec
    qs, ss = [], []
    for i in range(blocks.shape[0]):
        q, s = reshard_codec.encode(jnp.asarray(blocks[i]), mode)
        qs.append(np.asarray(q))
        ss.append(np.asarray(s))
    return np.stack(qs), np.stack(ss)


def _decode_blocks(q: np.ndarray, s: np.ndarray, block_shape, dtype,
                   mode: str) -> np.ndarray:
    import jax.numpy as jnp

    from alpa_tpu.pipeline_parallel import reshard_codec
    outs = [np.asarray(reshard_codec.decode(
        jnp.asarray(q[i]), jnp.asarray(s[i]), block_shape, dtype, mode))
        for i in range(q.shape[0])]
    return np.stack(outs)


@dataclasses.dataclass
class KVHandoffArtifact:
    """One request's prefilled KV state, packaged for the wire.

    ``layers[l]`` is ``{"k": arr, "v": arr}`` (codec off, arrays shaped
    ``(num_blocks, block_size, ...)`` in the model's KV dtype) or
    ``{"k_q", "k_s", "v_q", "v_s"}`` (codec on: per-block quantized
    payload + scales).  ``block_hashes[i]`` is sha256 over block ``i``'s
    wire bytes across every layer; ``logits_hash`` covers the seed
    logits + prompt.  Hashes are computed over what actually crosses
    the wire, so verification catches transport corruption exactly and
    a re-fetched artifact re-ingests bitwise identically (quantized or
    not)."""

    request_id: str
    model: str
    prompt: np.ndarray
    cfg: Dict[str, Any]
    queue: Optional[str]
    weights_tag: str
    block_size: int
    num_blocks: int
    codec: str
    kv_dtype: str
    layers: List[Dict[str, np.ndarray]]
    last_logits: np.ndarray
    block_hashes: List[str]
    logits_hash: str

    # ---- construction -----------------------------------------------

    @classmethod
    def build(cls, request_id: str, model: str, prompt: np.ndarray,
              cfg: Dict[str, Any], queue: Optional[str],
              weights_tag: str, block_size: int,
              layer_blocks: List[Tuple[np.ndarray, np.ndarray]],
              last_logits: np.ndarray,
              codec: str = "off") -> "KVHandoffArtifact":
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        kv_dtype = str(layer_blocks[0][0].dtype)
        num_blocks = int(layer_blocks[0][0].shape[0])
        layers: List[Dict[str, np.ndarray]] = []
        for (kb, vb) in layer_blocks:
            if codec == "off":
                layers.append({"k": np.ascontiguousarray(kb),
                               "v": np.ascontiguousarray(vb)})
            else:
                kq, ks = _encode_blocks(kb, codec)
                vq, vs = _encode_blocks(vb, codec)
                layers.append({"k_q": kq, "k_s": ks,
                               "v_q": vq, "v_s": vs})
        art = cls(request_id=request_id, model=model, prompt=prompt,
                  cfg=dict(cfg), queue=queue, weights_tag=weights_tag,
                  block_size=int(block_size), num_blocks=num_blocks,
                  codec=codec, kv_dtype=kv_dtype, layers=layers,
                  last_logits=np.ascontiguousarray(
                      np.asarray(last_logits)),
                  block_hashes=[], logits_hash="")
        art.block_hashes = art._hash_blocks()
        art.logits_hash = art._hash_logits()
        return art

    # ---- hashing ----------------------------------------------------

    def _block_bytes(self, i: int):
        import hashlib
        h = hashlib.sha256()
        for lay in self.layers:
            for key in sorted(lay):
                h.update(np.ascontiguousarray(lay[key][i]).tobytes())
        return h.hexdigest()

    def _hash_blocks(self) -> List[str]:
        return [self._block_bytes(i) for i in range(self.num_blocks)]

    def _hash_logits(self) -> str:
        import hashlib
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(self.prompt).tobytes())
        h.update(np.ascontiguousarray(self.last_logits).tobytes())
        return h.hexdigest()

    def verify(self) -> None:
        """Recompute every per-block content hash against the carried
        ones; any mismatch rejects the whole artifact (the decode side
        must never scatter corrupt KV into its pool)."""
        if len(self.block_hashes) != self.num_blocks:
            raise ArtifactCorruptError(
                f"artifact {self.request_id}: {len(self.block_hashes)} "
                f"hashes for {self.num_blocks} blocks")
        for i in range(self.num_blocks):
            if self._block_bytes(i) != self.block_hashes[i]:
                raise ArtifactCorruptError(
                    f"artifact {self.request_id}: block {i} content "
                    f"hash mismatch (corrupt handoff)")
        if self._hash_logits() != self.logits_hash:
            raise ArtifactCorruptError(
                f"artifact {self.request_id}: seed logits/prompt hash "
                f"mismatch (corrupt handoff)")

    # ---- payload accounting -----------------------------------------

    @property
    def payload_nbytes(self) -> int:
        return sum(int(a.nbytes) for lay in self.layers
                   for a in lay.values())

    # ---- wire form --------------------------------------------------

    def to_wire(self) -> Dict[str, Any]:
        return {
            "request_id": self.request_id, "model": self.model,
            "prompt": self.prompt.tolist(), "cfg": dict(self.cfg),
            "queue": self.queue, "weights_tag": self.weights_tag,
            "block_size": self.block_size,
            "num_blocks": self.num_blocks, "codec": self.codec,
            "kv_dtype": self.kv_dtype,
            "layers": [{k: _arr_to_wire(v) for k, v in lay.items()}
                       for lay in self.layers],
            "last_logits": _arr_to_wire(self.last_logits),
            "block_hashes": list(self.block_hashes),
            "logits_hash": self.logits_hash,
            "payload_nbytes": self.payload_nbytes,
        }

    @classmethod
    def from_wire(cls, wire: Dict[str, Any],
                  verify: bool = True) -> "KVHandoffArtifact":
        try:
            art = cls(
                request_id=str(wire["request_id"]),
                model=str(wire["model"]),
                prompt=np.asarray(wire["prompt"], np.int32).reshape(-1),
                cfg=dict(wire["cfg"]), queue=wire.get("queue"),
                weights_tag=str(wire.get("weights_tag", "")),
                block_size=int(wire["block_size"]),
                num_blocks=int(wire["num_blocks"]),
                codec=str(wire["codec"]),
                kv_dtype=str(wire["kv_dtype"]),
                layers=[{k: _arr_from_wire(v) for k, v in lay.items()}
                        for lay in wire["layers"]],
                last_logits=_arr_from_wire(wire["last_logits"]),
                block_hashes=[str(h) for h in wire["block_hashes"]],
                logits_hash=str(wire.get("logits_hash", "")))
        except (KeyError, TypeError, ValueError) as e:
            raise ArtifactCorruptError(
                f"malformed handoff artifact: {e}") from e
        if verify:
            art.verify()
        return art

    # ---- decode-side reconstruction ---------------------------------

    def dense_rows(self, layer: int, tail: Tuple[int, ...]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Like :meth:`materialize` but given the destination cache's
        per-token tail shape (needed to invert the codec's flattening)."""
        lay = self.layers[layer]
        dtype = _np_dtype(self.kv_dtype)
        block_shape = (self.block_size,) + tuple(tail)
        if self.codec == "off":
            kb, vb = lay["k"], lay["v"]
        else:
            kb = _decode_blocks(lay["k_q"], lay["k_s"], block_shape,
                                dtype, self.codec)
            vb = _decode_blocks(lay["v_q"], lay["v_s"], block_shape,
                                dtype, self.codec)
        n = self.num_blocks * self.block_size
        return (np.ascontiguousarray(kb).reshape((n,) + tuple(tail))
                .astype(dtype, copy=False),
                np.ascontiguousarray(vb).reshape((n,) + tuple(tail))
                .astype(dtype, copy=False))


class PrefillEngine:
    """Admission + prefill ONLY: the prefill-pool half of a
    disaggregated deployment.  One worker thread drains a scheduler
    queue (the same ``serve.scheduler`` protocol the batcher and the
    decode engine speak, so per-tenant weighted fairness holds on this
    pool too), runs each prompt's prefill against this replica's
    :class:`KVBlockPool` (prefix reuse included), and packages the
    block-table slice into a :class:`KVHandoffArtifact`.

    Every artifact is retained (LRU, ``disagg_retain_artifacts`` deep)
    until :meth:`ack` — the router's re-ingest path
    (:meth:`fetch`) rides this, so a decode-replica death or a corrupt
    wire copy never loses a handoff."""

    def __init__(self, generator, kv_pool=None, scheduler=None,
                 prompt_bucket: Optional[int] = None, model: str = "",
                 weights_tag: str = "", codec: Optional[str] = None,
                 max_retained: Optional[int] = None):
        from alpa_tpu.model.gpt_model import (require_one_token_steps,
                                              require_uniform_kv_caches)
        from alpa_tpu.serve.kv_cache import KVBlockPool
        # a handed-off row is a prompt's cache and its last logits, which
        # the decode half samples its first token from; what is handed
        # off is a slice of one block table of K and V
        require_one_token_steps(generator.config, "disaggregated serving "
                                "(serve/disagg.py PrefillEngine)")
        require_uniform_kv_caches(generator.config, "disaggregated serving "
                                  "(serve/disagg.py PrefillEngine)")
        self.gen = generator
        self.model = model
        self.weights_tag = weights_tag
        self.bucket = prompt_bucket or generator.prompt_buckets[-1]
        # a miss pads as the decode engine's dense admission does
        self._ladder = generator.admission_ladder(self.bucket)
        self.pool = kv_pool or KVBlockPool.for_generator(generator)
        if self.pool.seq_len != generator.config.seq_len:
            raise ValueError(
                f"kv_pool seq_len {self.pool.seq_len} != generator "
                f"seq_len {generator.config.seq_len}")
        self._reuse = (self.pool.prefix_reuse and
                       bool(generator.prefill_chunk))
        codec = (global_config.disagg_codec if codec is None else codec)
        if codec != "off" and not _codec_ok(
                codec, self.pool._kp[0].dtype):
            logger.warning(
                "disagg_codec=%s unsupported for KV dtype %s; handoff "
                "ships verbatim", codec, self.pool._kp[0].dtype)
            codec = "off"
        self.codec = codec
        if scheduler is None:
            from alpa_tpu.serve.scheduler import FIFOQueue
            scheduler = FIFOQueue()
        self._queue = scheduler
        self._cv = threading.Condition()
        self._retained: "OrderedDict[str, KVHandoffArtifact]" = \
            OrderedDict()
        self._retain_cap = (global_config.disagg_retain_artifacts
                            if max_retained is None else max_retained)
        self.prefills = 0
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # ---- public API -------------------------------------------------

    def prefill(self, prompt: np.ndarray, cfg=None,
                queue: Optional[str] = None,
                request_id: Optional[str] = None) -> KVHandoffArtifact:
        """Blocking: admit ``prompt``, prefill it, return (and retain)
        the handoff artifact."""
        from alpa_tpu.serve.generation import GenerationConfig
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cfg = cfg or GenerationConfig()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.bucket:
            raise ValueError(
                f"prompt {len(prompt)} exceeds prefill bucket "
                f"{self.bucket}")
        seq_len = self.gen.config.seq_len
        if len(prompt) + cfg.max_new_tokens > seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens "
                f"{cfg.max_new_tokens} exceeds seq_len {seq_len}")
        if not self.pool.fits(len(prompt)):
            raise ValueError(
                f"prompt {len(prompt)} needs more KV blocks than the "
                f"prefill pool holds")
        item = {"prompt": prompt, "cfg": cfg,
                "queue": queue or "default",
                "request_id": request_id or uuid.uuid4().hex,
                "done": threading.Event(), "artifact": None,
                "error": None}
        with self._cv:
            if self._stop:
                raise RuntimeError("prefill engine shut down")
            self._queue.append(item)
            self._cv.notify()
        item["done"].wait()
        if item["error"] is not None:
            raise item["error"]
        return item["artifact"]

    def fetch(self, request_id: str) -> Optional[KVHandoffArtifact]:
        """The retained artifact for ``request_id`` (None when already
        acked or evicted) — the router's re-ingest source."""
        with self._cv:
            return self._retained.get(request_id)

    def ack(self, request_id: str) -> bool:
        """Drop the retained artifact: its stream finished cleanly."""
        with self._cv:
            art = self._retained.pop(request_id, None)
        if art is not None:
            _HANDOFFS_IN_FLIGHT.dec()
        return art is not None

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def shutdown(self):
        with self._cv:
            self._stop = True
            self._cv.notify()

    # ---- worker -----------------------------------------------------

    def _run(self):
        # as the decode engine: no prefill of a window compiles
        self.gen.compile_row_prefills(self._ladder)
        while True:
            with self._cv:
                while not self._stop and len(self._queue) == 0:
                    self._cv.wait()
                if self._stop:
                    err = RuntimeError("prefill engine shut down")
                    for item in self._queue.drain():
                        item["error"] = err
                        item["done"].set()
                    return
                item = self._queue.popleft()
            try:
                item["artifact"] = self._prefill_one(item)
            except Exception as e:  # pylint: disable=broad-except
                logger.exception("prefill failed")
                item["error"] = e
            item["done"].set()

    def _prefill_one(self, item) -> KVHandoffArtifact:
        import dataclasses as _dc

        from alpa_tpu.serve.generation import row_length

        p = item["prompt"]
        # max_new_tokens=0: this pool never decodes — it only needs the
        # prompt's blocks, and releases them (into the prefix index)
        # right after the artifact is gathered
        seq = self.pool.begin_sequence(p, 0)
        if seq is None:
            raise RuntimeError(
                "prefill pool cannot free enough blocks (all held by "
                "the prefix index under concurrent prefills)")
        clean = False
        try:
            m = seq.matched_tokens
            if m:
                # prefix hit: identical to the monolithic engine's hit
                # path (gather + chunked suffix prefill from the match
                # offset) — bit-exactness rides the same ops
                gathered = self.pool.gather_dense(seq)
                logits1, caches1 = self.gen._run_chunked_prefill(
                    [p[m:]], row_length(len(p)), 1, caches=gathered,
                    start=m)
            else:
                logits1, caches1, _ = self.gen.prefill_row(p, self._ladder)
            self.pool.scatter_prompt(seq, caches1)
            if self._reuse:
                self.pool.register_prompt(seq, p)
            nb = -(-len(p) // self.pool.block_size)
            layer_blocks = self.pool.gather_blocks(seq, nb)
            art = KVHandoffArtifact.build(
                request_id=item["request_id"], model=self.model,
                prompt=p, cfg=_dc.asdict(item["cfg"]),
                queue=item["queue"], weights_tag=self.weights_tag,
                block_size=self.pool.block_size,
                layer_blocks=layer_blocks,
                last_logits=np.asarray(logits1), codec=self.codec)
            clean = True
        finally:
            self.pool.release(seq, tokens=p if clean else None,
                              register=clean)
        self.prefills += 1
        _PREFILLS.inc()
        _HANDOFF_BYTES.inc(art.payload_nbytes)
        with self._cv:
            self._retained[art.request_id] = art
            _HANDOFFS_IN_FLIGHT.inc()
            while len(self._retained) > max(1, self._retain_cap):
                evicted, _ = self._retained.popitem(last=False)
                _HANDOFFS_IN_FLIGHT.dec()
                logger.warning(
                    "retained-artifact cap reached; dropped %s (raise "
                    "disagg_retain_artifacts if re-ingest matters "
                    "more than memory)", evicted)
        return art


# ---- decode-side ingest ---------------------------------------------


def land_artifact(engine, artifact: KVHandoffArtifact):
    """Verify + reconstruct: the artifact's payload becomes the dense
    single-row caches + seed logits the decode engine's prefilled
    admission expects, landed on the engine's resident-cache sharding
    through the cross-mesh transfer layer."""
    import jax
    import jax.numpy as jnp

    from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
        make_ingest_transfer)
    artifact.verify()
    cfgm = engine.gen.config
    L = cfgm.seq_len
    if len(artifact.layers) != len(engine._caches):
        raise ValueError(
            f"artifact has {len(artifact.layers)} layers; decode "
            f"engine has {len(engine._caches)}")
    if artifact.num_blocks * artifact.block_size > L:
        raise ValueError(
            f"artifact carries {artifact.num_blocks * artifact.block_size} "
            f"token positions; decode seq_len is {L}")
    span = artifact.num_blocks * artifact.block_size
    idx = jnp.asarray([len(artifact.prompt)], jnp.int32)
    dense = []
    for l, (k_res, v_res, _i) in enumerate(engine._caches):
        tail = tuple(k_res.shape[2:])
        kb, vb = artifact.dense_rows(l, tail)
        if kb.shape[1:] != tail or str(kb.dtype) != str(k_res.dtype):
            raise ValueError(
                f"layer {l}: artifact KV {kb.shape[1:]}/{kb.dtype} "
                f"does not match decode caches {tail}/{k_res.dtype}")
        dk = np.zeros((1, L) + tail, kb.dtype)
        dv = np.zeros((1, L) + tail, vb.dtype)
        dk[0, :span] = kb
        dv[0, :span] = vb
        tr = make_ingest_transfer(
            jax.ShapeDtypeStruct(dk.shape, dk.dtype), k_res.sharding)
        dense.append((tr(dk), tr(dv), idx))
    logits1 = jnp.asarray(artifact.last_logits)
    return dense, logits1


def _ingest_cfg(artifact: KVHandoffArtifact):
    from alpa_tpu.serve.generation import GenerationConfig
    known = {f.name for f in dataclasses.fields(GenerationConfig)}
    return GenerationConfig(**{k: v for k, v in artifact.cfg.items()
                               if k in known})


def ingest_stream(engine, artifact: KVHandoffArtifact,
                  queue: Optional[str] = None):
    """Decode-side half of the handoff: verify, land, and join the
    request into ``engine``'s continuous decode batch mid-tick.
    Returns the engine token stream.  The engine scatters the prompt
    blocks into its OWN pool and registers the prefix chain, so
    cross-request reuse keeps working on the decode pool."""
    caches1, logits1 = land_artifact(engine, artifact)
    cfg = _ingest_cfg(artifact)
    return engine.submit_prefilled_stream(
        artifact.prompt, cfg, caches1, logits1,
        queue=queue or artifact.queue)


def ingest(engine, artifact: KVHandoffArtifact,
           queue: Optional[str] = None) -> np.ndarray:
    """Blocking variant of :func:`ingest_stream` (tests + batch path)."""
    caches1, logits1 = land_artifact(engine, artifact)
    cfg = _ingest_cfg(artifact)
    return engine.submit_prefilled(
        artifact.prompt, cfg, caches1, logits1,
        queue=queue or artifact.queue)


# ---- telemetry hooks shared with the router --------------------------


def observe_handoff(seconds: float) -> None:
    _HANDOFF_SECONDS.observe(seconds)


def observe_ttft(pool: str, seconds: float) -> None:
    _TTFT_H.labels(pool).observe(seconds)


def observe_itl(pool: str, seconds: float) -> None:
    _ITL_H.labels(pool).observe(seconds)


def count_reingest(reason: str) -> None:
    _REINGESTS.labels(reason).inc()


def count_backpressure_shed() -> None:
    _BACKPRESSURE_SHEDS.inc()
