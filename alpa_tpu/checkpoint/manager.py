"""Async distributed checkpoint manager.

``CheckpointManager`` is the training-loop-facing API over
:class:`~alpa_tpu.checkpoint.store.ShardStore`:

* **Async double-buffered saves** — ``save(step, state)`` blocks only
  for (a) the previous step's disk write to finish (at most one write in
  flight: the double buffer) and (b) device→host staging of the new
  state.  Hashing + chunk writes + manifest commit + retention GC all
  run on a background thread, so train step N+1 overlaps the disk write
  of step N.  ``last_blocking_seconds`` records exactly how long the
  training loop was stalled.
* **Save-failure surfacing** — a background write that fails is never
  silent: the first exception re-raises (wrapped in
  :class:`CheckpointSaveError`) from the next ``save()`` or ``wait()``.
  Store atomicity guarantees the failed step has no manifest, so
  ``latest_step()`` still points at the last good one.
* **Resume safety** — ``restore`` validates the manifest's recorded
  ``plan_fingerprint`` against the caller's (e.g.
  ``executable.get_plan_fingerprint()``), raising
  :class:`PlanFingerprintMismatch` instead of silently loading weights
  into a differently-parallelized program.
* **Resharding-on-read** — pass ``shardings`` (a pytree of shardings
  matching ``target``) and each device reads only the chunks
  overlapping its slice; the saving mesh shape is irrelevant.

``RecoveryCheckpointer`` plugs a manager into
:class:`alpa_tpu.fault.RecoveryManager`: quiesce → durable snapshot on
entry to RECOVERING, automatic restore of the last *verified* step when
recovery brings the mesh back.
"""
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from alpa_tpu.checkpoint import metrics
from alpa_tpu.checkpoint.policy import RetentionPolicy
from alpa_tpu.checkpoint.store import (CheckpointNotFoundError, ShardStore)
from alpa_tpu.telemetry import trace as _ttrace

logger = logging.getLogger(__name__)


class CheckpointSaveError(RuntimeError):
    """A background checkpoint write failed.  ``step`` is the step that
    was lost; the store holds no manifest for it."""

    def __init__(self, step: int, cause: BaseException):
        super().__init__(
            f"checkpoint save of step {step} failed: "
            f"{type(cause).__name__}: {cause}")
        self.step = step
        self.cause = cause


class PlanFingerprintMismatch(RuntimeError):
    """The checkpoint was saved under a different parallel plan than the
    one resuming — loading it would scatter weights into executables
    compiled for other shardings.  Re-solve or pass the saved plan."""


def _flatten_state_dict(target):
    from alpa_tpu.serialization import (_flatten_state_dict as _flat,
                                        _leaf_dirname)
    from flax.serialization import to_state_dict
    flat = _flat(to_state_dict(target))
    return {_leaf_dirname(path): (path, leaf)
            for path, leaf in flat.items()}


def _stage_leaf(leaf):
    """Device→host staging of one leaf: list of (global-index, ndarray)
    pieces.  The host copy is the only device-blocking part of a save."""
    import jax
    if isinstance(leaf, jax.Array):
        pieces = []
        for shard in leaf.addressable_shards:
            if shard.replica_id != 0:
                continue
            index = tuple(
                (s.start or 0, s.stop if s.stop is not None else dim)
                for s, dim in zip(shard.index, leaf.shape)) \
                if leaf.ndim else ()
            pieces.append((index, np.asarray(shard.data)))
        return list(leaf.shape), str(leaf.dtype), pieces
    arr = np.asarray(leaf)
    index = tuple((0, d) for d in arr.shape) if arr.ndim else ()
    return list(arr.shape), str(arr.dtype), [(index, arr)]


class CheckpointManager:
    """See module docstring.  Single-controller only: every shard must
    be addressable from this process (the tests' virtual CPU meshes and
    single-host TPU meshes qualify); multi-host runs keep using
    ``serialization.save_checkpoint``'s per-process index files until
    the manifest learns to merge per-process piece sets."""

    def __init__(self, root: str,
                 policy: Optional[RetentionPolicy] = None,
                 async_save: bool = True,
                 chunk_bytes: int = 64 * 1024 * 1024):
        self.store = ShardStore(root)
        self.policy = policy
        self.async_save = async_save
        self.chunk_bytes = chunk_bytes
        self._pending: Optional[threading.Thread] = None
        self._pending_step: Optional[int] = None
        self._errors: List[CheckpointSaveError] = []
        self._err_lock = threading.Lock()
        # stall accounting: what save() kept its caller for
        self.last_staging_seconds = 0.0
        self.last_write_seconds = 0.0
        self.last_blocking_seconds = 0.0

    # ---- save --------------------------------------------------------

    def save(self, step: int, state: Any,
             plan_fingerprint: Optional[str] = None,
             executable: Any = None,
             meta: Optional[Dict[str, Any]] = None,
             sync: Optional[bool] = None) -> None:
        """Checkpoint ``state`` (any flax-state-dict-able pytree) as
        ``step``.  ``executable`` (anything with
        ``get_plan_fingerprint()``) or ``plan_fingerprint`` stamps the
        manifest for resume validation.  ``sync=True`` forces the write
        inline (the benchmark baseline); default follows ``async_save``.
        """
        import jax
        if jax.process_count() > 1:
            raise NotImplementedError(
                "CheckpointManager is single-controller; multi-host "
                "saves go through serialization.save_checkpoint")
        self._raise_pending_error()
        if plan_fingerprint is None and executable is not None:
            plan_fingerprint = executable.get_plan_fingerprint()

        t0 = time.monotonic()
        save_span = _ttrace.begin(
            "checkpoint.save", "checkpoint",
            {"step": step} if _ttrace.enabled() else None)
        # double buffer: at most ONE write in flight — step N's write
        # must land (or fail) before step N+1's chunks hit the store,
        # which also keeps retention GC from racing fresh chunk files
        self._join_pending()
        t_joined = time.monotonic()

        flat = _flatten_state_dict(state)
        leaves: Dict[str, Dict[str, Any]] = {}
        staged_bytes = 0
        for name, (_path, leaf) in flat.items():
            shape, dtype, pieces = _stage_leaf(leaf)
            staged_bytes += sum(p.nbytes for _i, p in pieces)
            leaves[name] = {"shape": shape, "dtype": dtype,
                            "pieces": pieces}
        t_staged = time.monotonic()
        self.last_staging_seconds = t_staged - t_joined
        metrics.incr("staging_seconds", self.last_staging_seconds)
        metrics.incr("staged_bytes", staged_bytes)

        def write():
            w0 = time.monotonic()
            wtok = (_ttrace.begin("checkpoint.write", "checkpoint",
                                  {"step": step}, "ckpt-writer")
                    if _ttrace.enabled() else None)
            try:
                self.store.write_step(
                    step, leaves, plan_fingerprint=plan_fingerprint,
                    meta=meta, chunk_bytes=self.chunk_bytes)
                self._apply_retention()
            except BaseException as e:  # pylint: disable=broad-except
                logger.exception("async checkpoint write of step %d "
                                 "failed", step)
                with self._err_lock:
                    self._errors.append(CheckpointSaveError(step, e))
                metrics.incr("save_failures")
                return
            finally:
                self.last_write_seconds = time.monotonic() - w0
                metrics.incr("write_seconds", self.last_write_seconds)
                _ttrace.end(wtok)
            metrics.incr("saves")

        if sync if sync is not None else not self.async_save:
            write()
            self.last_blocking_seconds = time.monotonic() - t0
            self._raise_pending_error()
        else:
            t = threading.Thread(target=write, daemon=True,
                                 name=f"ckpt-write-{step}")
            self._pending = t
            self._pending_step = step
            t.start()
            self.last_blocking_seconds = time.monotonic() - t0
        metrics.incr("blocking_seconds", self.last_blocking_seconds)
        _ttrace.end(save_span)

    def _apply_retention(self):
        if self.policy is None:
            return
        doomed = self.policy.to_delete(self.store.all_steps())
        for s in doomed:
            self.store.delete_step(s)
        if doomed:
            self.store.gc()
            logger.info("retention dropped steps %s", doomed)

    def _join_pending(self):
        t = self._pending
        if t is not None:
            t.join()
            self._pending = None
            self._pending_step = None

    def _raise_pending_error(self):
        with self._err_lock:
            if self._errors:
                err = self._errors.pop(0)
                raise err

    def wait(self) -> None:
        """Block until the in-flight write lands; re-raise the first
        background failure (``CheckpointSaveError``)."""
        self._join_pending()
        self._raise_pending_error()

    # ---- introspection ----------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self.store.latest_step()

    def all_steps(self) -> List[int]:
        return self.store.all_steps()

    def last_verified_step(self) -> Optional[int]:
        return self.store.last_verified_step()

    # ---- restore -----------------------------------------------------

    def restore(self, target: Any, step: Optional[int] = None,
                shardings: Any = None,
                expected_plan_fingerprint: Optional[str] = None,
                executable: Any = None,
                verify: bool = True) -> Any:
        """Restore ``target``'s structure from ``step`` (default:
        latest committed).  ``shardings``: optional pytree matching
        ``target`` — each leaf is materialized directly into that
        sharding, reading only the covering chunks (resharding-on-read:
        the saving mesh shape does not matter).  ``verify`` re-hashes
        every chunk read (detects bit-rot/truncation up front)."""
        import jax
        from flax.serialization import from_state_dict, to_state_dict
        t0 = time.monotonic()
        restore_span = _ttrace.begin(
            "checkpoint.restore", "checkpoint",
            {"step": step} if _ttrace.enabled() else None)
        if expected_plan_fingerprint is None and executable is not None:
            expected_plan_fingerprint = executable.get_plan_fingerprint()
        manifest = self.store.read_manifest(step)
        saved_fp = manifest.get("plan_fingerprint")
        if expected_plan_fingerprint is not None:
            if saved_fp is None:
                logger.warning(
                    "checkpoint step %s carries no plan fingerprint; "
                    "cannot validate resume plan", manifest["step"])
            elif saved_fp != expected_plan_fingerprint:
                raise PlanFingerprintMismatch(
                    f"checkpoint step {manifest['step']} was saved under "
                    f"plan {saved_fp[:12]}… but this run compiled plan "
                    f"{expected_plan_fingerprint[:12]}…; restore with "
                    "the saved parallel plan (parallel_plan.plan_to_"
                    "method) or re-checkpoint under the new plan")

        flat = _flatten_state_dict(target)
        shard_flat = {}
        if shardings is not None:
            shard_flat = _flatten_state_dict(shardings)

        new_flat = {}
        for name, (path, _leaf) in flat.items():
            info = manifest["leaves"].get(name)
            if info is None:
                raise KeyError(
                    f"checkpoint step {manifest['step']} has no leaf "
                    f"{name!r}; saved leaves: "
                    f"{sorted(manifest['leaves'])[:8]}…")
            shape = tuple(info["shape"])
            dtype = np.dtype(info["dtype"])
            sharding = shard_flat.get(name, (None, None))[1]
            if sharding is not None:
                def cb(idx, _info=info, _shape=shape, _dtype=dtype):
                    index = tuple(
                        (s.start or 0,
                         s.stop if s.stop is not None else d)
                        for s, d in zip(idx, _shape)) if _shape else ()
                    return jax.numpy.asarray(
                        self.store.read_leaf_slice(_info, index,
                                                   verify=verify),
                        dtype=_dtype)
                new_flat[path] = jax.make_array_from_callback(
                    shape, sharding, cb)
            else:
                full = tuple((0, d) for d in shape) if shape else ()
                new_flat[path] = self.store.read_leaf_slice(
                    info, full, verify=verify)

        sd = to_state_dict(target)

        def rebuild(tree_path, node):
            if isinstance(node, dict):
                return {k: rebuild(tree_path + (k,), v)
                        for k, v in node.items()}
            return new_flat[tree_path]

        restored = from_state_dict(target, rebuild((), sd))
        metrics.incr("restores")
        metrics.incr("restore_seconds", time.monotonic() - t0)
        _ttrace.end(restore_span)
        return restored


class RecoveryCheckpointer:
    """Durable backend for :class:`alpa_tpu.fault.RecoveryManager`.

    * ``snapshot_hook`` — on entry to RECOVERING the recovery manager
      quiesces in-flight work, then this hook writes a SYNCHRONOUS
      (``wait()``-ed) snapshot: durability before the re-probe gamble.
    * restore-on-recover — when the state machine transitions
      RECOVERING/DEGRADED → HEALTHY, the last *verified* step is
      restored and handed to ``state_setter`` before the pre-existing
      resume hook runs: the quiesced in-flight state is gone, so the
      training/serving loop must restart from the snapshot.

    ``state_provider()`` returns the live state pytree to snapshot (and
    the restore target); ``step_provider()`` the step to save under
    (default: one past the newest committed step).  Pass
    ``plan_fingerprint``/``executable`` so resume refuses checkpoints
    from a differently-parallelized program.
    """

    def __init__(self, manager: CheckpointManager, recovery,
                 state_provider: Callable[[], Any],
                 state_setter: Optional[Callable[[Any], Any]] = None,
                 step_provider: Optional[Callable[[], int]] = None,
                 shardings_provider: Optional[Callable[[], Any]] = None,
                 plan_fingerprint: Optional[str] = None,
                 executable: Any = None):
        from alpa_tpu.fault import MeshHealth
        self.manager = manager
        self.recovery = recovery
        self.state_provider = state_provider
        self.state_setter = state_setter
        self.step_provider = step_provider or (
            lambda: (manager.latest_step() or 0) + 1)
        self.shardings_provider = shardings_provider
        if plan_fingerprint is None and executable is not None:
            plan_fingerprint = executable.get_plan_fingerprint()
        self.plan_fingerprint = plan_fingerprint
        self.snapshots_saved = 0
        self.restores_done = 0
        self._needs_restore = False
        self._mesh_health = MeshHealth

        recovery.snapshot_hook = self.snapshot
        self._chain_state_change()
        self._chain_resume()

    # -- wiring --------------------------------------------------------

    def _chain_state_change(self):
        prev = self.recovery.on_state_change
        health = self._mesh_health

        def on_state_change(old, new):
            if new is health.HEALTHY and old in (health.RECOVERING,
                                                 health.DEGRADED):
                self._needs_restore = True
            if prev is not None:
                prev(old, new)

        self.recovery.on_state_change = on_state_change

    def _chain_resume(self):
        prev = self.recovery.resume_hook

        def resume():
            if self._needs_restore:
                self._needs_restore = False
                self.restore_latest_verified()
            if prev is not None:
                prev()

        self.recovery.resume_hook = resume

    # -- hooks ---------------------------------------------------------

    def snapshot(self) -> Optional[int]:
        """Durable snapshot of the live state (RecoveryManager's
        ``snapshot_hook``): synchronous — recovery must not gamble on a
        write that has not landed."""
        step = self.step_provider()
        self.manager.save(step, self.state_provider(),
                          plan_fingerprint=self.plan_fingerprint,
                          meta={"reason": "recovery_snapshot"},
                          sync=True)
        self.manager.wait()
        self.snapshots_saved += 1
        logger.info("recovery snapshot committed as step %d", step)
        return step

    def restore_latest_verified(self) -> Optional[Any]:
        """Restore the newest step whose chunks all pass hash
        verification (a half-written or bit-rotted newest step falls
        back to the one before it)."""
        step = self.manager.last_verified_step()
        if step is None:
            logger.warning("recovery restore requested but the store "
                           "has no verified steps")
            return None
        shardings = (self.shardings_provider()
                     if self.shardings_provider else None)
        restored = self.manager.restore(
            self.state_provider(), step=step, shardings=shardings,
            expected_plan_fingerprint=self.plan_fingerprint)
        if self.state_setter is not None:
            self.state_setter(restored)
        self.restores_done += 1
        logger.info("recovery restored verified step %d", step)
        return restored


def get_checkpoint_stats() -> Dict[str, float]:
    """Process-global checkpoint counters (bytes, timings, failures) —
    surfaced by ``alpa_tpu.monitoring.get_checkpoint_stats``."""
    return metrics.snapshot()


# re-exported for callers that only import the manager module
__all__ = [
    "CheckpointManager", "CheckpointSaveError", "CheckpointNotFoundError",
    "PlanFingerprintMismatch", "RecoveryCheckpointer",
    "get_checkpoint_stats",
]
