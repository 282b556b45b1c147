"""Hashes of the lowered programs of cells a change should leave as they
are: ``python3 scripts/lowered_program_hashes.py <root of a checkout>``
prints one JSON line, a name -> the first 16 hex digits of the SHA-256 of
the program's StableHLO text, lowered from abstract arguments on the CPU
(nothing is compiled or run): OPT-1.3B's decode over 4 rows and its two
dense prefills, the gradient of two GPT-1.3B blocks under remat, of one
OLMoE layer with its routed experts, and the served decode and chunk step
of LFM2 and Trinity at a depth of four and five layers, and the decode tick
(the verify of a tick that drafts, GLM-5's) of the six served cells that hold
a share of their experts, at a depth of two layers, or as many as hold one
of every kind; and of those six the chunk step and the tick once more,
lowered FOR A TPU (``<name>.chunk.tpu``, ``<name>.decode.tpu``: the Pallas
kernels that ``lax.platform_dependent`` chooses there are in the text, body
and all, where a lowering for the CPU holds their ``jax.numpy`` twins).  Two
checkouts whose lines agree build the same programs for those cells (PR 58
compared its tree with its parent so; PR 60 added the six ticks, which its
windows over a chunk's expert rows leave as they were; PR 62 the lowerings
for a TPU, which its kernel under a selection changes in the chunk steps of
dots3-note and GLM-5 alone; PR 63 Jamba's tick and chunk step for a TPU, a
Mamba-1 and an attention layer: twenty-nine in all)."""
import hashlib, json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax, jax.numpy as jnp
from alpa_tpu.model.gpt_model import (GPTModel, config_from_hf, config_from_opt_spec, config_from_spec, init_kv_caches)
from alpa_tpu.serve.generation import Generator

def digest(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]

def for_tpu(jitted, *args):
    """The program lowered for a TPU; a kernel's body, which the text holds as bytecode with the checkout's paths and line numbers in it, as its text without them."""
    import base64, re
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    def body(match):
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            return ir.Module.parse(base64.b64decode(match.group(1))).operation.get_asm(enable_debug_info=False)
    text = jitted.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    return hashlib.sha256(re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text).encode()).hexdigest()[:16]

def abstract(tree):
    return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

out = {}
# OPT-1.3B served: decode over 4 rows, dense prefill at 512 and 2048, the chunk step
cfg = config_from_opt_spec("1.3b", dtype=jnp.bfloat16)
model = GPTModel(cfg)
params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
gen = Generator(model, params, cfg, prefill_chunk=None)
caches = jax.eval_shape(lambda: init_kv_caches(cfg, 4))
S = jax.ShapeDtypeStruct
out["opt.decode"] = digest(gen._decode.jitted.lower(params, S((4, 1), jnp.int32), S((4,), jnp.int32), [(k, v) for k, v, _ in caches], [S((4,), jnp.int32) for _ in caches]))
for bucket in (512, 2048):
    out[f"opt.prefill{bucket}"] = digest(gen._prefill.lower(params, S((1, bucket), jnp.int32), None, S((1,), jnp.int32)))
# GPT 1.3B training: loss and gradients of the block stack
import dataclasses
cfg = dataclasses.replace(config_from_spec("1.3B", dtype=jnp.bfloat16, remat_blocks=True), num_layers=2)
model = GPTModel(cfg)
params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
def loss(p, ids):
    return model.apply(p, ids).astype(jnp.float32).mean()
out["gpt.grad"] = digest(jax.jit(jax.grad(loss)).lower(params, S((8, 1024), jnp.int32)))
# OLMoE: routed experts forward and backward
hf = dict(json.load(open(os.path.join(root, "chipbench/configs/olmoe-1b-7b-1chip.json"))), num_hidden_layers=1)
cfg = config_from_hf(hf, dtype=jnp.bfloat16)
model = GPTModel(cfg)
params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
def loss(p, ids):
    logits, routing = model.apply(p, ids)
    return logits.astype(jnp.float32).mean() + routing["load_balance_loss"]
out["olmoe.grad"] = digest(jax.jit(jax.grad(loss)).lower(params, S((2, 4096), jnp.int32)))
def served(cfg, serve):
    """(generator, abstract parameters, the cell's rows, the decode tick's abstract arguments) of a served configuration."""
    model = GPTModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    gen = Generator(model, params, cfg, prefill_chunk=serve["prefill_chunk"])
    rows = serve["engine_rows"]
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, rows))
    return gen, params, rows, (params, S((rows, 1), jnp.int32), S((rows,), jnp.int32), [(k, v) for k, v, _ in caches], [S((rows,), jnp.int32) for _ in caches])

# LFM2 and Trinity served: the decode over the cell's rows and the chunk step
for name, layers in (("lfm2-8b-a1b-1chip", 5), ("trinity-mini-1chip", 4)):
    hf = json.load(open(os.path.join(root, f"chipbench/configs/{name}.json")))
    hf = dict(hf, num_hidden_layers=layers, layer_types=hf["layer_types"][:layers])
    serve = hf["serve"]
    cfg = config_from_hf(hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=serve["served_context"])
    gen, params, rows, tick = served(cfg, serve)
    out[name + ".decode"] = digest(gen._decode.jitted.lower(*tick))
    out[name + ".chunk"] = digest(gen._chunk_prefill.lower(params, S((1, serve["prefill_chunk"]), jnp.int32), S((1,), jnp.int32), jax.eval_shape(lambda: init_kv_caches(cfg, 1)), S((1, cfg.vocab_size), jnp.bfloat16)))
# the six cells that hold a share of their experts: the decode tick over the cell's rows
from chipbench import run
serve_mla = run.load_module("drivers", "serve_mla")
for name, layers in (("deepseek-v2-1chip", 2), ("longcat-flash-1chip", 2), ("dots3-note-prev-1chip", 2), ("mimo-v2-flash-1chip", 2), ("glm-5-1chip", 2), ("nemotron-3-nano-30b-a3b-1chip", 9)):
    hf = dict(json.load(open(os.path.join(root, f"chipbench/configs/{name}.json"))), num_hidden_layers=layers)
    serve = hf["serve"]
    cfg = serve_mla.model_config(hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=serve["served_context"])
    gen, params, rows, tick = served(cfg, serve)
    if gen._verify_draft is None:
        decode = gen._decode.jitted
    else:
        decode, tick = gen._verify_draft.jitted, tick + (S((rows,), jnp.int32), S((rows,), jnp.int32), S((rows,), jnp.bool_))
    out[name + ".decode"] = digest(decode.lower(*tick))
    out[name + ".decode.tpu"] = for_tpu(decode, *tick)
    out[name + ".chunk.tpu"] = for_tpu(gen._chunk_prefill, params, S((1, serve["prefill_chunk"]), jnp.int32), S((1,), jnp.int32), jax.eval_shape(lambda: init_kv_caches(cfg, 1)), S((1, cfg.vocab_size), jnp.bfloat16))
# Jamba (PR 63 added it: its two attention layers of one key/value head lower through the folded kernels that a cache in two parts shares): a Mamba-1 and an attention layer, the tick and the chunk step for a TPU
hf = dict(json.load(open(os.path.join(root, "chipbench/configs/jamba2-3b-1chip.json"))), num_hidden_layers=2, attn_layer_period=2, attn_layer_offset=1)
serve = hf["serve"]
cfg = config_from_hf(hf, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, seq_len=serve["served_context"])
gen, params, rows, tick = served(cfg, serve)
out["jamba2-3b-1chip.decode.tpu"] = for_tpu(gen._decode.jitted, *tick)
out["jamba2-3b-1chip.chunk.tpu"] = for_tpu(gen._chunk_prefill, params, S((1, serve["prefill_chunk"]), jnp.int32), S((1,), jnp.int32), jax.eval_shape(lambda: init_kv_caches(cfg, 1)), S((1, cfg.vocab_size), jnp.bfloat16))
print(json.dumps(out))
