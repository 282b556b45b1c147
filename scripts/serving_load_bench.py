"""Concurrent serving load test (VERDICT r4 next #5).

Drives N concurrent HTTP clients — mixed SSE streaming and
non-streaming — against the stdlib controller + continuous-batching
engine on a tiny CPU model, and records time-to-first-token
percentiles and aggregate decoded tokens/s.  The point is behavior
UNDER CONCURRENCY: ThreadingHTTPServer thread-per-connection fan-in,
engine decode-tick sharing, batcher coalescing.

The harness of tests/serve/test_serving_load.py, where the assertions
live; what the engine carries on the chip is the benchmark's to say
(chipbench/, the ``opt-1.3b`` cells).
"""
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_server(seq_len=128, max_new_tokens=8):
    from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
    from alpa_tpu.serve.controller import Controller, ControllerServer
    from alpa_tpu.serve.generation import Generator

    cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                    seq_len=seq_len, vocab_size=64)
    model, params = init_gpt_real(cfg, 1)
    gen = Generator(model, params, cfg, prompt_buckets=[16])
    controller = Controller()
    controller.register_model("tiny", gen)
    server = ControllerServer(controller, "127.0.0.1", 0)
    server.start()
    return server, max_new_tokens


def _one_client(port, i, max_new_tokens, results, n_requests):
    rng = np.random.RandomState(i)
    recs = []
    for _ in range(n_requests):
        prompt = rng.randint(0, 64, (int(rng.randint(3, 12)),)).tolist()
        body = {"model": "tiny", "prompt_ids": prompt,
                "max_new_tokens": max_new_tokens}
        stream = i % 2 == 0
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        t0 = time.perf_counter()
        try:
            if stream:
                body["stream"] = True
                conn.request("POST", "/completions", json.dumps(body),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200, resp.status
                ttft, ntok = None, 0
                while True:
                    line = resp.fp.readline()
                    if not line:
                        break
                    line = line.strip()
                    if not line.startswith(b"data: "):
                        continue
                    evt = json.loads(line[len(b"data: "):])
                    if "token" in evt:
                        ntok += 1
                        if ttft is None:
                            ttft = time.perf_counter() - t0
                    elif "error" in evt:
                        raise RuntimeError(evt["error"])
                    else:
                        break  # done
                recs.append({"mode": "sse", "ttft_s": ttft,
                             "tokens": ntok,
                             "total_s": time.perf_counter() - t0})
            else:
                conn.request("POST", "/completions", json.dumps(body),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200, resp.status
                out = json.loads(resp.read())
                ntok = len(out["output_ids"][0]) - len(prompt)
                dt = time.perf_counter() - t0
                # non-streaming TTFT == full latency (tokens arrive at
                # once); recorded separately so the SSE percentile is
                # not polluted
                recs.append({"mode": "batch", "ttft_s": dt,
                             "tokens": ntok, "total_s": dt})
        except Exception as e:  # pylint: disable=broad-except
            recs.append({"mode": "sse" if stream else "batch",
                         "error": f"{type(e).__name__}: {e}"})
        finally:
            conn.close()
    results[i] = recs


def run_load(n_clients=16, n_requests=3, max_new_tokens=8):
    server, mnt = build_server(max_new_tokens=max_new_tokens)
    port = server.port
    try:
        # warmup: compile the engine decode/prefill + batcher paths once
        # so the percentiles measure steady-state serving, not XLA
        warm = [None, None]
        wt = [threading.Thread(target=_one_client,
                               args=(port, i, mnt, warm, 1))
              for i in range(2)]
        for t in wt:
            t.start()
        for t in wt:
            t.join()
        assert all("error" not in r for recs in warm for r in recs), warm

        results = [None] * n_clients
        tic = time.perf_counter()
        threads = [threading.Thread(target=_one_client,
                                    args=(port, i, mnt, results,
                                          n_requests))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - tic
    finally:
        server.shutdown()

    flat = [r for recs in results for r in recs]
    errors = [r for r in flat if "error" in r]
    ok = [r for r in flat if "error" not in r]
    sse_ttft = sorted(r["ttft_s"] for r in ok
                      if r["mode"] == "sse" and r["ttft_s"] is not None)
    batch_lat = sorted(r["total_s"] for r in ok if r["mode"] == "batch")

    def pct(xs, p):
        if not xs:
            return None
        return round(xs[min(len(xs) - 1, int(p / 100 * len(xs)))], 4)

    total_tokens = sum(r["tokens"] for r in ok)
    return {
        "n_clients": n_clients,
        "n_requests_per_client": n_requests,
        "max_new_tokens": max_new_tokens,
        "wall_s": round(wall, 3),
        "ok": len(ok),
        "errors": [r["error"] for r in errors],
        "sse_ttft_p50_s": pct(sse_ttft, 50),
        "sse_ttft_p99_s": pct(sse_ttft, 99),
        "batch_latency_p50_s": pct(batch_lat, 50),
        "batch_latency_p99_s": pct(batch_lat, 99),
        "aggregate_tokens_per_s": round(total_tokens / wall, 1),
        "sum_of_individual_s": round(sum(r["total_s"] for r in ok), 3),
    }
