"""Serving a decoder LM over HTTP.

  python examples/serve_gpt.py --port 8000 [--family gpt|bloom|codegen]

  # batched completion
  curl -X POST localhost:8000/completions \
      -d '{"model": "lm", "prompt_ids": [1,2,3], "max_new_tokens": 16}'
  # token streaming (server-sent events, rides continuous batching)
  curl -N -X POST localhost:8000/completions \
      -d '{"model": "lm", "prompt_ids": [1,2,3], "max_new_tokens": 16,
           "stream": true}'
"""
import argparse
import time

import jax


def build_generator(family, hidden, layers):
    from alpa_tpu.serve import get_model
    from alpa_tpu.serve.generation import Generator
    if family == "bloom":
        from alpa_tpu.model.bloom_model import BloomConfig, BloomModel
        cfg = BloomConfig(hidden_size=hidden, num_layers=layers,
                          num_heads=8, seq_len=512, vocab_size=32000)
        model = BloomModel(cfg)
    elif family == "codegen":
        from alpa_tpu.model.codegen_model import (CodeGenConfig,
                                                  CodeGenModel)
        cfg = CodeGenConfig(hidden_size=hidden, num_layers=layers,
                            num_heads=8,
                            rotary_dim=min(16, hidden // 8) // 2 * 2,
                            seq_len=512, vocab_size=32000)
        model = CodeGenModel(cfg)
    else:
        from alpa_tpu.model.gpt_model import GPTConfig
        return get_model(GPTConfig(hidden_size=hidden, num_layers=layers,
                                   num_heads=8, seq_len=512,
                                   vocab_size=32000))
    params = model.init(jax.random.PRNGKey(0),
                        jax.numpy.ones((1, 8), jax.numpy.int32))
    return Generator(model, params, cfg, batch_size=1)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default=None)
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--hidden", type=int, default=256)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--family", default="gpt",
                        choices=["gpt", "bloom", "codegen"])
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from alpa_tpu.serve import run_controller

    server = run_controller(port=args.port)
    server.controller.register_model(
        "lm", build_generator(args.family, args.hidden, args.layers))
    print(f"serving on http://127.0.0.1:{server.port}  "
          f"(models: {server.controller.list_models()})")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.shutdown()


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
