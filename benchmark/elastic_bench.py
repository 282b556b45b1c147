"""Elastic-training benchmark (ISSUE 16): injected-kill sweep over the
committed 2-stage pipeshard fixture, one scenario per failure class:

* ``kill``    — half the 8-device mesh dies at a step boundary
  (``worker_lost``); the supervisor re-solves a 2-stage plan over the
  4 survivors, restores the last verified step, resumes.  Scored on
  replay distance, recovery wall clock, and bitwise loss continuity
  against an uninterrupted run restored from the same step on the
  same surviving plan.
* ``preempt`` — an eviction notice (``preemption_notice``) with a
  grace window; scored on whether the synchronous snapshot landed
  inside the window (hit rate must be 1.0) plus recovery wall clock.
* ``wedge``   — a mid-step instruction failure whose WedgeDetector
  probe hangs: torn state is never
  snapshotted; the supervisor resets and replays from the last
  verified checkpoint, bitwise.

Deterministic up to wall-clock timings: the loss-continuity and
hit-rate metrics are exact (gated at 1.0), the seconds metrics are
gated with generous absolute bounds (CPU episode recovery is
sub-second; the bound only catches order-of-magnitude regressions
like a quiesce that starts blocking on a dead mesh).

Usage:  python benchmark/elastic_bench.py [--out F] [--gate]

``--gate`` checks the ``elastic.*`` metrics against
``benchmark/results/perf_gate_baseline.json`` and exits nonzero on
regression.  Writes benchmark/results/elastic.json.
"""
import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from alpa_tpu.platform import pin_cpu_platform  # noqa: E402

DEFAULT_OUT = os.path.join(REPO, "benchmark", "results", "elastic.json")

N_STEPS = 4


def _make_solve():
    import numpy as np

    import alpa_tpu
    from alpa_tpu.device_mesh import VirtualPhysicalMesh
    from alpa_tpu.pipeline_parallel.layer_construction import \
        ManualLayerOption
    from alpa_tpu.pipeline_parallel.stage_construction import \
        UniformStageOption
    from alpa_tpu.testing import get_mlp_train_step

    cache = {}

    def solve(devices):
        key = tuple(id(d) for d in devices)
        if key not in cache:
            n = len(devices)
            vm = VirtualPhysicalMesh(
                1, n, np.array(list(devices), dtype=object).reshape(1, n))
            method = alpa_tpu.PipeshardParallel(
                devices=vm, num_micro_batches=2,
                layer_option=ManualLayerOption(),
                stage_option=UniformStageOption(num_stages=2))
            cache[key] = get_mlp_train_step(method,
                                            use_value_and_grad=True)
        return cache[key]

    return solve


def _fresh_state_and_batch():
    from alpa_tpu.testing import create_mlp_train_state_and_batch
    return create_mlp_train_state_and_batch(
        batch_size=64, num_layers=4, manual_pipeline_layer=True)


def _drive(sup, batch, until):
    import numpy as np
    losses = {}
    for _ in range(50):
        if sup.step_index >= until:
            return losses
        loss = sup.step(batch)
        losses[sup.step_index] = np.asarray(loss)
    raise RuntimeError(f"supervisor stuck at step {sup.step_index}")


def _bitwise_vs_comparator(losses, root, restored_step, step_fn, batch,
                           until):
    """1.0 iff every post-episode committed loss equals an
    uninterrupted run restored from the same step on the same plan."""
    import numpy as np

    from alpa_tpu.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(root, async_save=False)
    c_state, _ = _fresh_state_and_batch()
    c_state = mgr.restore(c_state, step=restored_step)
    for i in range(restored_step + 1, until + 1):
        c_state, c_loss = step_fn(c_state, batch)
        if not np.array_equal(losses[i], np.asarray(c_loss)):
            return 0.0
    return 1.0


def run() -> dict:
    import jax

    from alpa_tpu import fault
    from alpa_tpu.elastic import (ElasticSupervisor, PreemptionNotice,
                                  WedgeDetector, WorkerLost)
    import alpa_tpu

    alpa_tpu.init(cluster="local")
    solve = _make_solve()
    scratch = tempfile.mkdtemp(prefix="elastic_bench_")
    scenarios = {}

    # ---- kill: 8 -> 4 survivors at a step boundary -------------------
    state, batch = _fresh_state_and_batch()
    root = os.path.join(scratch, "kill")
    sup = ElasticSupervisor(solve, state, checkpoint_root=root,
                            register_globally=False)
    survivors = list(jax.devices())[:4]
    with fault.FaultPlan(fault.FaultSpec(
            "worker_lost", times=1, after=2,
            exc=lambda: WorkerLost(survivors=survivors))):
        losses = _drive(sup, batch, N_STEPS)
    ep = dict(sup.episodes[0])
    kill_bitwise = _bitwise_vs_comparator(
        losses, root, ep["restored_step"], solve(survivors), batch,
        N_STEPS)
    scenarios["kill"] = {"episode": ep, "bitwise": kill_bitwise}

    # ---- preempt: eviction notice with a grace window ----------------
    state, _ = _fresh_state_and_batch()
    root = os.path.join(scratch, "preempt")
    sup = ElasticSupervisor(solve, state, checkpoint_root=root,
                            register_globally=False)
    with fault.FaultPlan(fault.FaultSpec(
            "preemption_notice", times=1, after=2,
            exc=lambda: PreemptionNotice(grace_s=30.0))):
        _drive(sup, batch, N_STEPS)
    ep = dict(sup.episodes[0])
    scenarios["preempt"] = {
        "episode": ep,
        "snapshot_hit": float(bool(ep.get("snapshot_before_kill"))),
    }

    # ---- wedge: mid-step failure + hung probe ------------------------
    state, _ = _fresh_state_and_batch()
    root = os.path.join(scratch, "wedge")
    det = WedgeDetector(mesh_group=[object()],
                        probe=lambda m: time.sleep(5.0),
                        probe_timeout_s=0.1)
    sup = ElasticSupervisor(solve, state, checkpoint_root=root,
                            wedge_detector=det, register_globally=False)
    with fault.FaultPlan(fault.FaultSpec("stage_launch", times=1,
                                         after=12)):
        losses = _drive(sup, batch, N_STEPS)
    ep = dict(sup.episodes[0])
    wedge_bitwise = _bitwise_vs_comparator(
        losses, root, ep["restored_step"], solve(list(jax.devices())),
        batch, N_STEPS)
    scenarios["wedge"] = {"episode": ep, "bitwise": wedge_bitwise}

    all_eps = [s["episode"] for s in scenarios.values()]
    gate_metrics = {
        "elastic.kill_replay_steps":
            float(scenarios["kill"]["episode"]["replay_steps"]),
        "elastic.kill_recovery_seconds":
            round(scenarios["kill"]["episode"]["seconds"], 4),
        "elastic.kill_bitwise": kill_bitwise,
        "elastic.preempt_snapshot_hit_rate":
            scenarios["preempt"]["snapshot_hit"],
        "elastic.preempt_recovery_seconds":
            round(scenarios["preempt"]["episode"]["seconds"], 4),
        "elastic.wedge_recovery_seconds":
            round(scenarios["wedge"]["episode"]["seconds"], 4),
        "elastic.wedge_bitwise": wedge_bitwise,
        "elastic.episodes_within_budget": float(all(
            e["within_step_budget"] and e["within_time_budget"]
            for e in all_eps)),
    }
    return {
        "fixture": {"steps": N_STEPS, "devices": 8,
                    "pipeline": "2-stage 1f1b, 2 microbatches"},
        "scenarios": scenarios,
        "gate_metrics": gate_metrics,
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default=DEFAULT_OUT)
    parser.add_argument("--gate", action="store_true",
                        help="check elastic.* metrics against the "
                             "committed perf-gate baseline")
    args = parser.parse_args()

    pin_cpu_platform(8)
    result = run()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(result, indent=2, sort_keys=True))
    print(f"\nwrote {args.out}")

    if args.gate:
        from benchmark.perf_gate import gate
        verdict = gate(result["gate_metrics"])
        print(json.dumps(verdict, indent=1))
        if not verdict["pass"]:
            sys.exit("ELASTIC BENCH PERF GATE FAILED")


if __name__ == "__main__":
    main()
