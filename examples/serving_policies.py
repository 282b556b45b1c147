"""Serving with admission policies, prefix caching, and speculative
decoding — the round-trip of the serving stack's scheduling features
(ref examples/llm_serving/service/scheduler.py; docs/serving.md).

  python examples/serving_policies.py --platform cpu

Registers a tiny LM with a weighted-fair scheduler (paid queue 4x the
free queue) and a cached system prompt, drives mixed streamed traffic
on both queues, then shows sampled speculative decoding with a draft
model.
"""
import argparse
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None, choices=[None, "cpu"],
                    nargs="?")
    args = ap.parse_args()
    if args.platform == "cpu":
        from alpa_tpu.platform import pin_cpu_platform
        pin_cpu_platform(8)

    from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
    from alpa_tpu.serve import (Controller, ControllerServer, Generator,
                                WeightedFairQueue)
    from alpa_tpu.serve.generation import GenerationConfig

    cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=4,
                    seq_len=128, vocab_size=256)
    model, params = init_gpt_real(cfg, 1)
    gen = Generator(model, params, cfg, prompt_buckets=[16],
                    prefill_chunk=16)

    system_prompt = np.arange(1, 9, dtype=np.int32)  # shared prefix
    controller = Controller()
    controller.register_model(
        "lm", gen, prefix_ids=system_prompt,
        scheduler_factory=lambda: WeightedFairQueue({"paid": 4.0,
                                                     "free": 1.0}))
    server = ControllerServer(controller, "127.0.0.1", 0)
    server.start()
    print(f"serving on :{server.port} (prefix {len(system_prompt)} "
          "tokens cached; paid queue weighted 4x)")

    def stream_one(queue, prompt, out):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=120)
        body = {"model": "lm", "prompt_ids": prompt, "stream": True,
                "max_new_tokens": 6, "queue": queue}
        t0 = time.perf_counter()
        conn.request("POST", "/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        toks, ttft = [], None
        while True:
            line = resp.fp.readline()
            if not line:
                break
            if line.startswith(b"data: "):
                evt = json.loads(line[6:])
                if "token" in evt:
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    toks.append(evt["token"])
                else:
                    break
        conn.close()
        out.append((queue, round(ttft or 0.0, 3), toks))

    results = []
    threads = [threading.Thread(
        target=stream_one,
        args=("paid" if i % 2 == 0 else "free", [10 + i, 20 + i],
              results)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for queue, ttft, toks in results:
        print(f"  [{queue:4s}] ttft {ttft:6.3f}s tokens {toks}")

    # sampled speculative decoding: draft proposes, target verifies by
    # rejection sampling — output exactly target-distributed
    dcfg = GPTConfig(hidden_size=32, num_layers=1, num_heads=2,
                     seq_len=128, vocab_size=256)
    dmodel, dparams = init_gpt_real(dcfg, 1)
    draft = Generator(dmodel, dparams, dcfg, prompt_buckets=[16])
    out, stats = gen.generate_speculative(
        draft, np.array([5, 6, 7], np.int32),
        GenerationConfig(max_new_tokens=12, do_sample=True,
                         temperature=1.1, top_k=8),
        num_draft=4, seed=0)
    print(f"speculative (sampled): {out.tolist()}  "
          f"accepted {stats['accepted']}/{stats['proposed']} "
          f"in {stats['rounds']} rounds")
    server.shutdown()


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
