"""Inspect the collective resharding planner (ISSUE 7).

Usage::

    python scripts/reshard_tool.py plan --shape 1024,1024 \
        --src-devices 4 --dst-devices 4 \
        --src-spec x,None --dst-spec None,None \
        [--dtype float32]

    python scripts/reshard_tool.py grad --shapes 1024x1024,4096x256,64 \
        --devices 8 --mode int8 [--min-bytes 65536] \
        [--num-micro-batches 4] [--no-error-feedback]

``plan`` plans one cross-mesh edge with :func:`plan_resharding` and
prints the chosen strategy, every candidate's estimated cost and
busiest-link load, and the planned wire bytes — the same per-edge
decision `dump_debug_info` records as ``resharding_plan.txt``.

``grad`` prices a list of gradient tensors through the quantized
collective cost model (ISSUE 19): per tensor it prints the full
fp32 wire bytes, the quantized wire bytes (payload + one fp32 scale
per 256-element block), the full all-reduce vs quantized
reduce-scatter cost from the live :class:`LogicalDeviceMesh` cost
model, the mode the ILP would choose under the given knobs
(``grad_eligible``), and the composed certified error bound
(``grad_error_bound``, two-hop reduce-scatter composition with the
error-feedback amortization rule applied).

Spec syntax: comma-separated PartitionSpec entries over the 1-D device
axis ``x`` (``x`` = sharded on that dim, ``None`` = replicated), e.g.
``x,None`` is a row shard.  Runs on the CPU backend with emulated
devices; the planner's tiling math is device-count-driven, so the
decisions match what the real meshes would get.
"""
import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _parse_spec(text, mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    entries = [None if e in ("None", "none", "") else e
               for e in text.split(",")]
    return NamedSharding(mesh, PartitionSpec(*entries))


def cmd_plan(args):
    n_dev = args.src_devices + args.dst_devices
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={n_dev}")
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from alpa_tpu.pipeline_parallel import cross_mesh_resharding as cmr

    devices = jax.devices()
    if len(devices) < n_dev:
        sys.exit(f"need {n_dev} devices, have {len(devices)}")
    src_mesh = Mesh(np.array(devices[:args.src_devices]), ("x",))
    dst_mesh = Mesh(np.array(devices[args.src_devices:n_dev]), ("x",))
    shape = tuple(int(s) for s in args.shape.split(","))
    itemsize = np.dtype(args.dtype).itemsize
    src = _parse_spec(args.src_spec, src_mesh)
    dst = _parse_spec(args.dst_spec, dst_mesh)

    spec = cmr.plan_resharding(shape, itemsize, src, dst)
    print(f"edge: {shape} {args.dtype} "
          f"{cmr._sharding_key(src)} -> {cmr._sharding_key(dst)}")
    print(f"chosen strategy: {spec.strategy}"
          f"{' (from compile cache)' if spec.strategy_cached else ''}")
    print(f"planned cross-mesh bytes: {spec.transfer_bytes:.0f} "
          f"(broadcast {spec.broadcast_bytes:.0f}); "
          f"max-link {spec.max_link_bytes:.0f} B "
          f"(naive {spec.max_link_bytes_naive:.0f} B)")
    print("candidates:")
    for name, stats in spec.strategy_stats.items():
        cost = spec.strategy_costs.get(name)
        cost_s = f"{cost * 1e3:.3f}ms" if cost is not None else "n/a"
        mark = " <-- chosen" if name == spec.strategy else ""
        print(f"  {name:<22} est={cost_s:>10}  "
              f"link_msgs={stats['max_link_messages']:>3}  "
              f"link_bytes={stats['max_link_bytes']:>10.0f}  "
              f"wire_total={stats['total_bytes']:>10.0f}{mark}")
    if args.verify:
        from alpa_tpu.analysis import plan_verifier
        print("static edge verdict:")
        for line in plan_verifier.verify_edge(shape, args.dtype, src, dst,
                                              weight=args.weight):
            print(f"  {line}")
    print()
    print(cmr.format_resharding_plan())


def cmd_grad(args):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from alpa_tpu.device_mesh import LogicalDeviceMesh
    from alpa_tpu.pipeline_parallel import reshard_codec as codec

    dtype = np.dtype(args.dtype)
    mesh = LogicalDeviceMesh(None, np.arange(args.devices))
    min_bytes = args.min_bytes
    ef = not args.no_error_feedback
    hops = args.num_micro_batches

    shapes = []
    for tok in args.shapes.split(","):
        shapes.append(tuple(int(s) for s in tok.split("x")))

    print(f"devices={args.devices}  mode={args.mode}  "
          f"min_bytes={min_bytes}  error_feedback={'on' if ef else 'off'}  "
          f"micro_batches={hops}")
    hdr = (f"{'shape':<16} {'bytes':>12} {'wire_bytes':>12} "
           f"{'all_reduce':>12} {'rs_quant':>12} {'chosen':>10} "
           f"{'bound':>10}")
    print(hdr)
    print("-" * len(hdr))
    total_full = total_wire = 0.0
    for shape in shapes:
        nbytes = float(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        full_cost = mesh.all_reduce_cost(nbytes, 0)
        q_cost = mesh.reduce_scatter_cost_quantized(nbytes, 0,
                                                    dtype.itemsize)
        eligible = codec.grad_eligible(shape, dtype, args.mode,
                                       min_bytes=min_bytes)
        chosen = args.mode if eligible else "full"
        wire = (codec.grad_wire_bytes(shape, dtype.itemsize, args.mode)
                if eligible else nbytes)
        bound = (codec.grad_error_bound(args.mode, reduce_scatter=True,
                                        error_feedback=ef, hops=hops)
                 if eligible else 0.0)
        total_full += nbytes
        total_wire += wire
        shape_s = "x".join(str(s) for s in shape)
        print(f"{shape_s:<16} {nbytes:>12.0f} {wire:>12.0f} "
              f"{full_cost:>12.4f} {q_cost:>12.4f} {chosen:>10} "
              f"{bound:>10.5f}")
    ratio = total_full / total_wire if total_wire else 1.0
    print("-" * len(hdr))
    print(f"total wire bytes: {total_full:.0f} -> {total_wire:.0f} "
          f"({ratio:.2f}x reduction)")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    pp = sub.add_parser("plan", help="plan one cross-mesh edge and "
                        "print the strategy decision")
    pp.add_argument("--shape", default="1024,1024",
                    help="global array shape, comma-separated")
    pp.add_argument("--dtype", default="float32")
    pp.add_argument("--src-devices", type=int, default=4)
    pp.add_argument("--dst-devices", type=int, default=4)
    pp.add_argument("--src-spec", default="x,None",
                    help="source PartitionSpec entries, e.g. x,None")
    pp.add_argument("--dst-spec", default="None,None")
    pp.add_argument("--verify", action="store_true",
                    help="append the static per-edge typing verdict "
                         "(plan_verifier.verify_edge)")
    pp.add_argument("--weight", action="store_true",
                    help="treat the edge as microbatch-invariant "
                         "(weight) payload for --verify")
    pp.set_defaults(fn=cmd_plan)
    pg = sub.add_parser("grad", help="price gradient tensors through the "
                        "quantized collective cost model")
    pg.add_argument("--shapes", default="1024x1024",
                    help="comma-separated tensor shapes, dims joined "
                         "with 'x', e.g. 1024x1024,4096x256,64")
    pg.add_argument("--dtype", default="float32")
    pg.add_argument("--devices", type=int, default=8,
                    help="data-parallel group size")
    pg.add_argument("--mode", default="int8", choices=("int8", "fp8"),
                    help="gradient codec (grad_quantize knob)")
    pg.add_argument("--min-bytes", type=int, default=65536,
                    help="grad_quantize_min_bytes eligibility floor")
    pg.add_argument("--num-micro-batches", type=int, default=4,
                    help="accumulation hops for the composed bound")
    pg.add_argument("--no-error-feedback", action="store_true",
                    help="price without the error-feedback "
                         "amortization rule (bound scales with hops)")
    pg.set_defaults(fn=cmd_grad)
    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
