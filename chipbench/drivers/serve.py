"""Driver of the serving cells: the model behind ``run_controller`` +
``register_model``, loaded over HTTP (``POST /completions`` with
``"stream": true``) by clients in this process, closed loop or open loop as
the traffic mix says.

Every time is taken at the client: a token's time is when its event was
read from the socket.  An open-loop request is timed from when it was DUE,
not from when it was sent, so a stall counts for the requests behind it.

``--trace 2`` goes the way of ``--trace 0`` to the end of the window, where
every number of the window is decided.  Then the same traffic goes on for
the traced seconds inside a capture of the program: the closed loop's
callers simply carry on, and an open loop gets arrivals of its own at the
mix's rate and sizes.  A request sent after the window is of kind
``after`` and enters no metric of the window.
"""
import http.client
import json
import threading
import time

import numpy as np

from chipbench import observe, program, stats, traffic


class _Client:
    """Sends requests and records, for each, when it was due, when it was
    sent and when each token came."""

    def __init__(self, ctx, port: int, model: str):
        self.ctx, self.port, self.model = ctx, port, model
        self.records = []
        self.open = {}              # connection -> record, while in flight
        self.closed = False         # set when the callers are to stop
        self.kind = "measured"      # of the requests sent from now on
        self.lock = threading.Lock()

    def request(self, req: dict, due: float, kind: str = None):
        rec = {"due": due, "sent": None, "token_times": [], "tokens": [],
               "prompt_ids": req["prompt_ids"],
               "asked": req["max_new_tokens"], "error": None,
               "cut": False, "kind": kind}
        with self.lock:
            if kind is None:
                rec["kind"] = self.kind
            self.records.append(rec)
            if self.closed:
                rec["cut"] = True
                return rec
        body = json.dumps({"model": self.model, "stream": True,
                           "prompt_ids": req["prompt_ids"],
                           "max_new_tokens": req["max_new_tokens"]})
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=300)
        with self.lock:
            self.open[conn] = rec
        try:
            with self.ctx.spans.span("request_send"):
                rec["sent"] = time.perf_counter()
                conn.request("POST", "/completions", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
            with self.ctx.spans.span("await_tokens"):
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    if not line.startswith(b"data: "):
                        continue
                    now = time.perf_counter()
                    event = json.loads(line[6:])
                    if "token" in event:
                        rec["token_times"].append(now)
                        rec["tokens"].append(event["token"])
                    elif "error" in event:
                        raise RuntimeError(event["error"])
                    elif event.get("done"):
                        break
            if len(rec["tokens"]) != rec["asked"]:
                raise RuntimeError(
                    f"{len(rec['tokens'])} tokens came, "
                    f"{rec['asked']} were asked for")
        except Exception as e:  # pylint: disable=broad-except
            # the boundary of one request: it fails alone, and is counted
            if not rec["cut"]:
                rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            with self.lock:
                self.open.pop(conn, None)
            conn.close()
        return rec

    def end_window(self, cut: bool):
        """The measured window ends: requests sent from now on are of kind
        ``after``.  ``cut`` (a closed loop): the requests in flight are cut
        off, and neither counted as attempted nor failed.  The tokens they
        streamed inside the window stay in the record: they are work the
        window did."""
        with self.lock:
            self.kind = "after"
            for rec in self.records if cut else ():
                if rec["error"] is None and \
                        len(rec["tokens"]) < rec["asked"]:
                    rec["cut"] = True

    def close_cut_requests(self, stop: bool):
        """Shut the sockets of the requests that are cut off (and of those
        sent after the window, which nobody waits for); ``stop``: and let
        no caller send another."""
        with self.lock:
            self.closed = self.closed or stop
            conns = []
            for conn, rec in self.open.items():
                if rec["kind"] == "after":
                    rec["cut"] = True
                if rec["cut"]:
                    conns.append(conn)
        for conn in conns:
            try:
                if conn.sock is not None:
                    conn.sock.shutdown(2)
            except OSError:
                pass


def _warm_up(client, vocab, seed):
    """Compile what the window will use, and nothing else: the engine's
    prefill, its decode over all rows, the scatter of an admission, and the
    per-row sampling it falls into when the rows' settings differ.  Five
    requests at once, with different lengths, fill every row and admit one
    more when the first is done."""
    rng = np.random.default_rng(seed)
    threads = []
    for i, n_out in enumerate((12, 13, 14, 15, 6)):
        req = {"prompt_ids": rng.integers(4, vocab, size=24 + i).tolist(),
               "max_new_tokens": n_out}
        t = threading.Thread(target=client.request,
                             args=(req, time.perf_counter(), "warmup"))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    bad = [r["error"] for r in client.records if r["error"]]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")


def _closed_loop(ctx, client, mix, vocab):
    source = traffic.closed_loop(mix, ctx.seed, vocab)
    source_lock = threading.Lock()
    stop = threading.Event()

    def caller():
        while not stop.is_set():
            with source_lock:
                req = next(source)
            client.request(req, time.perf_counter())

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(mix["clients"])]
    for t in threads:
        t.start()
    return stop, threads, threads


def _open_loop(client, schedule, t0):
    """Send ``schedule`` (``traffic.open_loop``) with its times from
    ``t0``."""
    stop = threading.Event()
    threads = []

    def sender():
        for req in schedule:
            due = t0 + req["due_s"]
            wait = due - time.perf_counter()
            if wait > 0 and stop.wait(wait):
                return
            t = threading.Thread(target=client.request, args=(req, due),
                                 daemon=True)
            t.start()
            threads.append(t)

    main = threading.Thread(target=sender, daemon=True)
    main.start()
    return stop, [main], threads


def _sleep_until(t):
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def _check(ctx, params, records, config):
    """The reference's full forward pass over prompt plus served tokens
    (teacher-forced, no cache) must give every served token a logit within
    ``logit_margin`` of the largest logit at its position."""
    import jax.numpy as jnp
    ref_mod = ctx.load("references", config["reference"])
    reference = ref_mod.Reference(program.reference_settings(config))
    weights = ref_mod.weights_from_program(params)
    done = [r for r in records if r["kind"] == "measured" and
            not r["cut"] and r["error"] is None]
    rng = np.random.default_rng(ctx.seed)
    picks = rng.permutation(len(done))[:ctx.mix["check_requests"]]
    seq_len = config["max_position_embeddings"]
    # one shape for every checked request: the whole context, and as many
    # rows of logits as the longest output the mix asks for
    rows = ctx.mix["output_len"]["max"]
    if ctx.mix["prompt_len"]["max"] + rows > seq_len:
        raise ValueError("the mix's longest prompt and output do not fit "
                         "the configuration's context")
    worst, bad = 0.0, 0
    for i in picks:
        rec = done[i]
        n_prompt, n_out = len(rec["prompt_ids"]), len(rec["tokens"])
        ids = np.zeros((seq_len,), np.int32)
        ids[:n_prompt + n_out] = rec["prompt_ids"] + rec["tokens"]
        # the row that predicts served token k is position n_prompt-1+k
        logits = reference.logits(weights, ids, rows=(n_prompt - 1, rows))
        logits = logits[:n_out]
        served = jnp.asarray(rec["tokens"], jnp.int32)
        chosen = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        deficit = float((logits.max(axis=-1) - chosen).max())
        worst = max(worst, deficit)
        bad += deficit > config["logit_margin"]
    return {"checked_requests": len(picks), "over_margin": int(bad),
            "worst_logit_deficit": worst,
            "logit_margin": config["logit_margin"]}


def run(ctx):
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import GPTModel
    from alpa_tpu.serve import get_model, run_controller
    from alpa_tpu.telemetry import metrics as tmetrics
    from alpa_tpu.telemetry import trace as ttrace

    config, mix = ctx.config, ctx.mix
    gcfg = program.gpt_config(config)
    vocab = gcfg.vocab_size
    # the program's spans: all through a traced run of its own, and only
    # inside the capture of --trace 2
    ttrace.set_enabled(ctx.trace == 1)
    registry = tmetrics.get_registry()
    timers = {}

    # the weights: on the device, from the seed, in one jitted call
    tic = time.perf_counter()
    model = GPTModel(gcfg)
    params = jax.jit(
        lambda key: model.init(key, jnp.ones((1, 8), jnp.int32)))(
            program.key_from_seed(ctx.seed))
    generator = get_model(gcfg, params=params)
    jax.block_until_ready(generator.params)
    timers["state_init_s"] = time.perf_counter() - tic
    weight_bytes = sum(
        x.nbytes for path, x in
        jax.tree_util.tree_flatten_with_path(generator.params)[0]
        if "wpe" not in jax.tree_util.keystr(path))

    name = config["name"]
    closed = mix["kind"] == "closed_loop"
    server = run_controller(port=0)
    engine = None
    try:
        server.controller.register_model(name, generator)
        client = _Client(ctx, server.port, name)
        tic = time.perf_counter()
        _warm_up(client, vocab, ctx.seed)
        timers["warmup_s"] = time.perf_counter() - tic
        # the controller's own engine, as its streaming path built it
        engine = server.controller._pick_replica(name).engine
        ctx.info({"info": "engine", "rows": engine.B,
                  "prompt_bucket": engine.bucket,
                  "memory": observe.device_memory(jax.local_devices())})

        compiles_before = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0)
        trace = program.DeviceTrace(ctx) if ctx.trace else None
        setup_s = observe.seconds_since_process_start()
        counters_t0 = registry.snapshot()
        window_t0_us = ttrace.now_us()
        t0 = time.perf_counter()
        # (stop, the threads that send, the threads that wait for answers)
        if closed:
            stop, senders, workers = _closed_loop(ctx, client, mix, vocab)
        else:
            stop, senders, workers = _open_loop(
                client, traffic.open_loop(mix, ctx.seed, vocab, ctx.seconds),
                t0)
        if ctx.trace == 1:
            _sleep_until(t0 + min(mix["trace_after_s"], ctx.seconds / 2))
            trace.start()
            _sleep_until(time.perf_counter() + mix["trace_seconds"])
            trace.stop()
        _sleep_until(t0 + ctx.seconds)
        t1 = time.perf_counter()
        window_t1_us = ttrace.now_us()
        client.end_window(cut=closed)
        counters = (counters_t0, registry.snapshot())
        compiles_in_window = ctx.compile_events.counts.get(
            observe.CompileEvents.COMPILE, 0) - compiles_before
        memory = observe.device_memory(jax.local_devices())

        if ctx.trace == 2:
            # the same traffic for the traced seconds, inside a capture
            trace.warm_up()
            trace.start()
            if not closed:
                more = traffic.open_loop(mix, ctx.seed + 1, vocab,
                                         mix["trace_seconds"])
                stop_more, send_more, _ = _open_loop(
                    client, more, time.perf_counter())
                senders = senders + send_more
            _sleep_until(time.perf_counter() + mix["trace_seconds"])
            trace.stop()
            if not closed:
                stop_more.set()
        memory_run = observe.device_memory(jax.local_devices())

        stop.set()
        client.close_cut_requests(stop=closed)
        for t in senders:
            t.join(timeout=30)
        # the drain: requests that were due get a stated time to finish
        deadline = t1 + mix["drain_s"]
        for t in list(workers):
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        drain_end = time.perf_counter()
    finally:
        if engine is not None:
            engine.shutdown()
            engine._thread.join(timeout=30)
        server.shutdown()

    # every request of the window, and those of them that count: a request
    # cut off at the end of a closed-loop window is neither attempted nor
    # failed, and its tokens inside the window are still the window's work
    requests = [r for r in client.records if r["kind"] == "measured"]
    records = [r for r in requests if not r["cut"]]
    for rec in records:
        if rec["error"] is None and len(rec["tokens"]) < rec["asked"]:
            rec["error"] = "due and not answered by the end of the drain"
    failed = sum(r["error"] is not None for r in records)
    # for the record: any other statistic of the waits can be had from it
    ctx.info({"info": "ttft_ms", "sorted": sorted(
        round(w * 1e3, 3) for w in stats.ttft_waits(requests, drain_end))})
    # where in the window the process stood still, if it did: the latest
    # sends and the longest silences between any two tokens,
    # [seconds, at which second of the window]
    times = sorted(t for r in requests for t in r["token_times"]
                   if t0 <= t <= t1)
    ctx.info({"info": "stalls",
              "latest_sends": sorted(
                  ([r["sent"] - r["due"], r["due"] - t0] for r in requests
                   if r["sent"] is not None), reverse=True)[:3],
              "longest_silences": sorted(
                  ([b - a, a - t0] for a, b in zip(times, times[1:])),
                  reverse=True)[:3]})
    checks = _check(ctx, generator.params, client.records, config)
    checks["compiles_in_window"] = compiles_in_window
    checks["errors"] = sorted({r["error"] for r in records
                               if r["error"]})[:5]
    return {
        "correct": bool(failed == 0 and checks["over_margin"] == 0 and
                        checks["checked_requests"] > 0 and
                        compiles_in_window == 0),
        "attempted": len(records),
        "failed": failed,
        "checks": checks,
        "setup_s": setup_s,
        "timers": timers,
        "window": (t0, t1),
        "drain_end": drain_end,
        "requests": requests,
        "engine_rows": engine.B,
        "weight_bytes": weight_bytes,
        "cache_itemsize": jnp.dtype(gcfg.dtype).itemsize,
        "program_spans": trace.program_spans() if trace else [],
        "program_window_us": (window_t0_us, window_t1_us),
        "counters": counters,
        "memory": memory,
        "memory_run": memory_run,
        "device_trace": trace.summary() if trace else None,
        # what the readers of spans see in place of the window's: the
        # traced interval, and every request that streamed in it
        "traced": {"window": trace.interval,
                   "program_window_us": trace.interval_us,
                   "requests": [r for r in client.records
                                if r["kind"] != "warmup"]}
        if ctx.trace == 2 else {},
    }
