"""Multi-process serving fleet recipe (ISSUE 18 satellite).

Launches N ControllerServer worker PROCESSES (each its own Python
process with its own model replica — the multi-host serving shape,
minus the hosts) plus ONE phase-aware RouterServer in this process,
wired over HTTP.  Workers share identical params (same PRNG seed), so
the fleet serves one logical model and the disaggregated handoff is
bit-exact across processes.

Each worker process claims the device jax finds for it, and a chip
belongs to one process: a fleet of more than one worker runs with
``JAX_PLATFORMS=cpu`` set, and is refused otherwise.

Usage::

    # monolithic 2-replica fleet
    python scripts/serve_fleet.py --replicas 2

    # disaggregated: 1 prefill + 2 decode workers
    python scripts/serve_fleet.py --prefill 1 --decode 2 \
        --disagg-mode auto

    # one-shot smoke: boot, run one streamed request, exit 0/1
    python scripts/serve_fleet.py --prefill 1 --decode 1 \
        --disagg-mode auto --smoke

The parent prints ``FLEET_READY router=http://127.0.0.1:PORT`` once
every worker passed ``/healthz`` and the router is serving; send it a
``POST /completions`` (``stream`` supported — SSE passes through the
router for HTTP replicas) or ``GET /healthz`` for the per-replica,
per-phase view.  Ctrl-C tears the whole fleet down.

Worker mode (internal): ``--worker --phase X`` boots one
ControllerServer on a free port, registers the tiny bench model as
``m``, and prints ``WORKER_READY port=N`` on stdout.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MODEL = "m"


def _build_generator(seq_len: int, prefill_chunk: int):
    from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
    from alpa_tpu.serve.generation import Generator
    cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                    seq_len=seq_len, vocab_size=64)
    # default PRNGKey(0): every worker process holds identical params
    model, params = init_gpt_real(cfg, 1)
    return Generator(model, params, cfg, prefill_chunk=prefill_chunk)


def run_worker(args) -> None:
    from alpa_tpu.global_env import global_config
    from alpa_tpu.serve.controller import Controller, ControllerServer
    global_config.kv_paged = True
    global_config.kv_prefix_reuse = True
    controller = Controller()
    controller.register_model(
        MODEL, _build_generator(args.seq_len, args.prefill_chunk))
    server = ControllerServer(controller, args.host, 0)
    server.start()
    print(f"WORKER_READY port={server.port} phase={args.phase}",
          flush=True)
    signal.sigwait({signal.SIGINT, signal.SIGTERM})
    server.shutdown()


def _spawn_worker(args, phase: str):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--phase", phase, "--host", args.host,
           "--seq-len", str(args.seq_len),
           "--prefill-chunk", str(args.prefill_chunk)]
    # the worker runs on whatever platform the environment names (or jax
    # finds): the platform is never chosen here
    return subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)


def _await_worker(proc, timeout: float):
    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line and proc.poll() is not None:
            raise RuntimeError("worker exited before WORKER_READY")
        if line.startswith("WORKER_READY"):
            return int(dict(kv.split("=") for kv in
                            line.split()[1:])["port"])
    raise RuntimeError(f"worker not ready within {timeout:.0f}s "
                       f"(last: {line!r})")


def _await_healthz(base: str, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=2) as resp:
                if resp.status == 200:
                    return
        except Exception:  # pylint: disable=broad-except
            pass
        time.sleep(0.1)
    raise RuntimeError(f"{base} never became healthy")


def _smoke(router_base: str) -> int:
    """One streamed request through the router; 0 on success."""
    body = json.dumps({
        "model": MODEL, "prompt_ids": [5, 9, 3, 7, 1, 2, 8, 4],
        "max_new_tokens": 4, "temperature": 0.0,
        "stream": True}).encode()
    req = urllib.request.Request(
        router_base + "/completions", data=body,
        headers={"Content-Type": "application/json"})
    tokens = []
    with urllib.request.urlopen(req, timeout=60) as resp:
        for raw in resp:
            raw = raw.strip()
            if not raw.startswith(b"data:"):
                continue
            evt = json.loads(raw[len(b"data:"):])
            if evt.get("done"):
                break
            if "error" in evt:
                print(f"SMOKE_FAIL error={evt['error']}", flush=True)
                return 1
            tokens.append(evt["token"])
    ok = len(tokens) == 4
    print(f"SMOKE_{'OK' if ok else 'FAIL'} tokens={tokens}",
          flush=True)
    return 0 if ok else 1


def run_fleet(args) -> int:
    from alpa_tpu.serve.router import (HTTPReplicaHandle, Router,
                                       RouterServer)
    plan = ([("prefill", i) for i in range(args.prefill)] +
            [("decode", i) for i in range(args.decode)] +
            [("any", i) for i in range(args.replicas)])
    if not plan:
        plan = [("any", 0), ("any", 1)]
    if len(plan) > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        # a chip belongs to one process: every worker would claim the
        # accelerator jax finds, and all but the first would fail or hang
        print(f"serve_fleet: refusing to start {len(plan)} worker "
              f"processes on the accelerator (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}): a chip belongs to "
              f"one process.  Set JAX_PLATFORMS=cpu for a CPU fleet, or "
              f"start one worker.", file=sys.stderr, flush=True)
        return 2
    procs = []
    try:
        procs = [(phase, i, _spawn_worker(args, phase))
                 for (phase, i) in plan]
        router = Router(disagg_mode=args.disagg_mode,
                        disagg_backpressure_depth=args.backpressure)
        for phase, i, proc in procs:
            port = _await_worker(proc, args.boot_timeout)
            base = f"http://{args.host}:{port}"
            _await_healthz(base, args.boot_timeout)
            router.add_replica(f"{phase}{i}", HTTPReplicaHandle(base),
                               phase=phase)
            print(f"worker {phase}{i} up at {base}", flush=True)
        server = RouterServer(router, host=args.host, port=args.port)
        server.start()
        base = f"http://{args.host}:{server.port}"
        print(f"FLEET_READY router={base} workers="
              f"{','.join(f'{ph}{i}' for ph, i, _ in procs)}",
              flush=True)
        if args.smoke:
            rc = _smoke(base)
            server.shutdown()
            return rc
        try:
            signal.sigwait({signal.SIGINT, signal.SIGTERM})
        except KeyboardInterrupt:
            pass
        server.shutdown()
        return 0
    finally:
        for _, _, proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for _, _, proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one controller worker process")
    ap.add_argument("--phase", default="any",
                    choices=("any", "prefill", "decode"))
    ap.add_argument("--replicas", type=int, default=0,
                    help="phase-agnostic worker count")
    ap.add_argument("--prefill", type=int, default=0,
                    help="prefill-pool worker count")
    ap.add_argument("--decode", type=int, default=0,
                    help="decode-pool worker count")
    ap.add_argument("--disagg-mode", default="auto",
                    choices=("off", "auto", "forced"))
    ap.add_argument("--backpressure", type=int, default=0,
                    help="disagg decode-pool backpressure depth")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="router port (0 = ephemeral)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--boot-timeout", type=float, default=120.0)
    ap.add_argument("--smoke", action="store_true",
                    help="boot, run one streamed request, exit")
    args = ap.parse_args(argv)
    if args.worker:
        run_worker(args)
        return 0
    return run_fleet(args)


if __name__ == "__main__":
    sys.exit(main())
