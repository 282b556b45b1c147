"""Record an auto-search solution artifact at multi-billion-param scale.

Runs the full auto path (layer clustering -> cost model [checked-in DB or
analytic TPU calibration] -> OSDI'22 stage DP) COMPILE-ONLY on a virtual
8-device mesh for a GPT-6.7B-class model, and commits the chosen plan
(stages x submeshes x microbatches) under benchmark/results/ — the analog
of the reference's recorded GPT-39B solution (ref benchmark/alpa/
suite_auto_gpt.py:71-84).  No TPU or model weights needed: parameters are
abstract (jax.eval_shape), the search runs on jaxprs.

Usage:  python benchmark/auto_search_artifact.py [--model 6.7B] [--out F]
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_OUT = os.path.join(REPO, "benchmark", "results",
                           "auto_plan_gpt{model}_8dev.json")

# the rungs of the reference's GPT ladder (seq 1024, vocab 51200, ref
# suite_manual_gpt.py:18-26) that have a recorded plan under results/
GPT_SPECS = {
    "6.7B": dict(hidden_size=4096, num_layers=32, num_heads=32),
    "15B": dict(hidden_size=5120, num_layers=48, num_heads=40),
    "39B": dict(hidden_size=8192, num_layers=48, num_heads=64),
}


def search_gpt_plan(model_name="6.7B", n_devices=8, batch_size=32,
                    num_micro_batches=8, layer_num=8,
                    profiling_database=None, seq_len=1024, num_hosts=1,
                    memory_budget=16e9, force_ilp=False):
    """Run the plan-only auto search for one GPT rung; returns the plan."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.device_mesh import VirtualPhysicalMesh
    from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss
    from alpa_tpu.pipeline_parallel.compile_executable import (
        search_pipeshard_plan)
    from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
    from alpa_tpu.pipeline_parallel.stage_construction import AutoStageOption
    from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption

    spec = GPT_SPECS[model_name]
    cfg = GPTConfig(seq_len=seq_len, vocab_size=51200, dtype=jnp.bfloat16,
                    **spec)
    model = GPTModel(cfg)
    rng = jax.random.PRNGKey(0)
    ids_aval = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)

    # abstract parameters: no 6.7B materialization anywhere
    params_aval = jax.eval_shape(model.init, rng, ids_aval)
    state_aval = jax.eval_shape(
        lambda p: train_state.TrainState.create(
            apply_fn=model.apply, params=p, tx=optax.adam(1e-4)),
        params_aval)
    batch_aval = {"input_ids": ids_aval, "labels": ids_aval}

    flat_avals, tree = jax.tree_util.tree_flatten((state_aval, batch_aval))
    batch_invars = [tuple(a.shape[:1]) == (batch_size,)
                    for a in flat_avals]

    def flat_fun(*leaves):
        state, batch = jax.tree_util.tree_unflatten(tree, leaves)

        def loss_fn(p):
            # the loss formulation the benchmark's driver measures (shared
            # helper: the searched jaxpr cannot drift from the benchmarked)
            return gpt_lm_loss(state.apply_fn, p, batch)

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    mesh = VirtualPhysicalMesh(num_hosts, n_devices // num_hosts)
    plan = search_pipeshard_plan(
        flat_fun, mesh, flat_avals, batch_invars, num_micro_batches,
        AutoShardingOption(),
        # per-layer remat, as any real multi-billion-param training run:
        # the activation stash shrinks to layer boundaries, which is what
        # makes the 16 GB/device budget satisfiable at all
        layer_option=AutoLayerOption(layer_num=layer_num, remat_layer=True),
        stage_option=AutoStageOption(
            profiling_database_filename=profiling_database,
            memory_budget_per_device=memory_budget,
            use_hlo_cost_model=not force_ilp))
    plan["model"] = f"gpt-{model_name}"
    plan["model_spec"] = dict(spec, seq_len=seq_len, vocab_size=51200)
    plan["batch_size"] = batch_size
    plan["n_devices"] = n_devices
    plan["num_hosts"] = num_hosts
    plan["memory_budget_per_device"] = memory_budget
    plan["cost_basis"] = (os.path.basename(profiling_database)
                          if profiling_database else "analytic")
    return plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="6.7B", choices=sorted(GPT_SPECS))
    ap.add_argument("--out", default=None)
    ap.add_argument("--pod", action="store_true",
                    help="pod-scale search: 8 hosts x 8 devices, bigger "
                    "global batch (the reference's recorded GPT-39B "
                    "solution ran at 64 GPUs, suite_auto_gpt.py:80-84)")
    ap.add_argument("--pod4", action="store_true",
                    help="4 hosts x 8 devices (the reference's recorded "
                    "GPT-15B solution ran at 32 GPUs: 4 stages x (1,8), "
                    "suite_auto_gpt.py:75-79)")
    args = ap.parse_args()

    from alpa_tpu.platform import pin_cpu_platform
    pin_cpu_platform(8)

    from alpa_tpu.mesh_profiling import (analytic_calibration,
                                         set_global_calibration)

    def pod_case(suffix, key, num_hosts, num_micro_batches):
        out = args.out or DEFAULT_OUT.format(model=args.model).replace(
            "_8dev", suffix)
        set_global_calibration(analytic_calibration("v5e"))
        plan = search_gpt_plan(args.model, n_devices=8 * num_hosts,
                               num_hosts=num_hosts, batch_size=128,
                               num_micro_batches=num_micro_batches,
                               layer_num=16)
        plan["cost_basis"] = "analytic-v5e"
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w", encoding="utf-8") as f:
            json.dump({key: plan}, f, indent=1)
        print(json.dumps({"out": out,
                          "plan": plan["forward_stage_layer_ids"],
                          "submeshes": plan["submesh_shapes"]}))

    if args.pod4:
        # the reference's recorded GPT-15B solution ran at 32 GPUs
        pod_case("_4x8dev", "analytic_v5e_4x8", 4, 16)
        return
    if args.pod:
        # the reference's recorded GPT-39B solution ran at 64 GPUs
        pod_case("_8x8dev", "analytic_v5e_8x8", 8, 32)
        return
    out = args.out or DEFAULT_OUT.format(model=args.model)

    # plan 1: under the checked-in CPU-mesh measured DB (deterministic,
    # test-asserted); plan 2: under the analytic v5e TPU calibration
    cpu_db = os.path.join(REPO, "prof_database_cpu8.json")
    plan_db = search_gpt_plan(args.model, profiling_database=cpu_db)
    set_global_calibration(analytic_calibration("v5e"))
    plan_v5e = search_gpt_plan(args.model)
    plan_v5e["cost_basis"] = "analytic-v5e"
    # 2 hosts x 8: the slow cross-host axis should trade TP width for
    # pipeline stages (additive per-layer ILP keeps comm in the costs)
    plan_2host = search_gpt_plan(args.model, n_devices=16, num_hosts=2)
    plan_2host["cost_basis"] = "analytic-v5e"

    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w", encoding="utf-8") as f:
        json.dump({"checked_in_db": plan_db, "analytic_v5e": plan_v5e,
                   "analytic_v5e_2x8": plan_2host}, f, indent=1)
    print(json.dumps({"out": out,
                      "db_plan": plan_db["forward_stage_layer_ids"],
                      "db_submeshes": plan_db["submesh_shapes"],
                      "v5e_plan": plan_v5e["forward_stage_layer_ids"],
                      "v5e_submeshes": plan_v5e["submesh_shapes"],
                      "v5e_2x8_submeshes": plan_2host["submesh_shapes"]}))


if __name__ == "__main__":
    main()
