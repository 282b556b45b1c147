"""Device time by part of the model (``telemetry/device_time.py``, ISSUE
34): parts read off ``op_name`` paths and HLO text, the reduction of device
events by hand, one trace recorded on the chip with its HLO text
(``tests/runtime/data``, made by ``record_device_trace.py``), the registry
that programs write when they compile, and the name stacks that have to
survive the planner for any of it to read a program on a mesh."""
import collections
import contextlib
import gzip
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import optax
import pytest
from flax.training import train_state

import alpa_tpu
from alpa_tpu.model.gpt_model import (ATTENTION_SCOPE, CACHE_WRITE_SCOPE,
                                      GPTConfig, GPTModel, init_gpt_real)
from alpa_tpu.model.model_util import LOSS_SCOPE, gpt_lm_loss
from alpa_tpu.model.moe import SCOPE as MOE_SCOPE
from alpa_tpu.ops.grouped_matmul import SCOPE as MATMUL_SCOPE
from alpa_tpu.serve.generation import Generator
from alpa_tpu.shard_parallel import strategy
from alpa_tpu.telemetry import device_time as dt
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.testing import init_params

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ---- parts from op_name paths ------------------------------------------

@pytest.mark.parametrize("op_name, part", [
    # forward
    ("jit(decode)/GPTModel/wte/jit(_take)/gather", "embed"),
    ("jit(decode)/GPTModel/wpe/convert_element_type", "embed"),
    ("jit(decode)/GPTModel/h0/ln1/reduce_sum", "norm"),
    ("jit(decode)/GPTModel/ln_f/div", "norm"),
    ("jit(decode)/GPTModel/h3/ln2_post/mul", "norm"),
    ("jit(decode)/GPTModel/h3/attn/q_norm/rsqrt", "norm"),
    ("jit(decode)/GPTModel/h3/attn/kv_a_norm/rsqrt", "norm"),
    ("jit(decode)/GPTModel/h0/attn/qkv/dot_general", "projection"),
    ("jit(decode)/GPTModel/h0/attn/out/add", "projection"),
    ("jit(decode)/GPTModel/h0/attn/gate/dot_general", "projection"),
    ("jit(decode)/GPTModel/h0/attn/q_b/dot_general", "projection"),
    ("jit(decode)/GPTModel/h0/attn/split", "projection"),
    ("jit(decode)/GPTModel/h0/attn/attention/bqhd,bkhd->bhqk/dot_general",
     "attention"),
    ("jit(decode)/GPTModel/h0/attn/attention/cache_write/"
     "dynamic_update_slice", "attention.cache_write"),
    # a latent layer that selects its positions (PR 47)
    ("jit(decode)/GPTModel/h1/attn/attention/indexer/index_q/dot_general",
     "attention.indexer"),
    ("jit(decode)/GPTModel/h1/attn/attention/indexer/index_k_ln/rsqrt",
     "attention.indexer"),
    ("jit(decode)/GPTModel/h1/attn/attention/indexer/top_k",
     "attention.indexer"),
    ("jit(decode)/GPTModel/h1/attn/attention/latent_select/gather",
     "attention.latent_select"),
    ("jit(chunk_prefill)/GPTModel/h5/attn/attention/latent_select/"
     "cond/branch_1_fun/pallas_call", "attention.latent_select"),
    # window and full layers that differ in more than the mask (PR 51)
    ("jit(decode)/GPTModel/h1/attn/attention/window_core/exp",
     "attention.window_core"),
    ("jit(decode)/GPTModel/h6/attn/attention/full_core/"
     "jit(_folded_key_blocks)/cond/branch_1_fun/pallas_call",
     "attention.full_core"),
    ("jit(decode)/GPTModel/h1/attn/attention/window_core/cache_write/"
     "dynamic_update_slice", "attention.cache_write"),
    ("jit(decode)/GPTModel/h0/mlp/fc_in/dot_general", "mlp"),
    ("jit(decode)/GPTModel/h2/mlp/moe/router/dot_general", "moe"),
    ("jit(decode)/GPTModel/h2/mlp/moe/grouped_matmul/cond/branch_0_fun/"
     "jit(gmm)/pallas_call", "moe.grouped_matmul"),
    ("jit(decode)/GPTModel/h2/mlp/shared/up/dot_general", "mlp"),
    ("jit(decode)/GPTModel/wte.attend/dot_general", "head"),
    ("jit(prefill)/GPTModel/lm_head/dot_general", "head"),
    ("jit(decode)/GPTModel/h0/add", "block"),
    ("jit(train_step)/jit(main)/jvp(loss)/reduce_sum", "loss"),
    # the head's product inside the chunked loss is the head's
    ("jit(f)/jvp(loss)/while/body/closed_call/checkpoint/lm_head/"
     "dot_general", "head"),
    # the backward pass wraps every component
    ("jit(flat_fun)/transpose(jvp(GPTModel))/transpose(jvp(h0))/"
     "transpose(jvp(attn))/transpose(jvp(attention))/dot_general",
     "attention"),
    ("jit(flat_fun)/transpose(jvp(GPTModel))/wte/scatter-add", "embed"),
    # a rematerialised block
    ("jit(constrained)/transpose(jvp(GPTModel))/jvp(GPTModel)/checkpoint/"
     "rematted_computation/h0/attn/attention/exp", "attention"),
    ("jit(constrained)/transpose(jvp(GPTModel))/jvp(GPTModel)/checkpoint/"
     "h0/mlp/fc_out/dot_general", "mlp"),
    # the fused attention kernels of a training step, as a lowering for
    # the v5e names them (``tests/ops/test_tpu_compile.py`` reads the live
    # ones): the forward pass's, the rematerialised block's, and the
    # backward pass's under ``checkpoint`` and without
    ("jit(loss)/jvp(TransformerBlock)/attn/attention/jit(_fused_attention)/"
     "cond/branch_0_fun/flash_attention_forward/pallas_call", "attention"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "TransformerBlock/attn/attention/jit(_fused_attention)/cond/"
     "branch_0_fun/flash_attention_forward/pallas_call", "attention"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/TransformerBlock/attn/"
     "attention/jit(_fused_attention)/cond/jit(loss)/"
     "transpose(jvp(jvp()))/checkpoint/TransformerBlock/attn/attention/"
     "jit(_fused_attention)/cond/branch_0_fun/flash_attention_backward/"
     "pallas_call", "attention"),
    ("jit(loss)/transpose(jvp(TransformerBlock))/attn/attention/"
     "jit(_fused_attention)/cond/jit(loss)/"
     "transpose(jvp(TransformerBlock))/attn/attention/"
     "jit(_fused_attention)/cond/branch_0_fun/flash_attention_backward/"
     "pallas_call", "attention"),
    # a weight the compiler re-lays out keeps the argument's name, as the
    # HLO text escapes it and as jax writes it
    ("params[\\'params\\'][\\'h0\\'][\\'attn\\'][\\'q_b\\'][\\'kernel\\']",
     "projection"),
    ("params['params']['wte']['embedding']", "embed"),
    ("kv[0][1]", dt.OUTSIDE_MODEL),
    # under none of the parts; no name at all
    ("jit(flat_fun)/mul", dt.OUTSIDE_MODEL),
    ("jit(flat_fun)/jit(scale_by_adam)/sqrt", dt.OUTSIDE_MODEL),
    ("broadcast.8", dt.OUTSIDE_MODEL),
    ("", dt.UNSCOPED),
    (None, dt.UNSCOPED),
])
def test_part_of_an_op_name(op_name, part):
    assert dt.part_of(op_name) == part
    assert part in dt.PARTS


def test_the_table_knows_the_programs_scopes():
    """``telemetry`` imports no model: the scope constants are spelled out
    in its table, and keep their values (the benchmark imports them)."""
    assert (ATTENTION_SCOPE, MOE_SCOPE, MATMUL_SCOPE) == \
        ("attention", "moe", "grouped_matmul")
    assert dt.part_of(f"jit(f)/{ATTENTION_SCOPE}/exp") == "attention"
    assert dt.part_of(f"jit(f)/{ATTENTION_SCOPE}/{CACHE_WRITE_SCOPE}/x") == \
        "attention.cache_write"
    assert dt.part_of(f"jit(f)/{MOE_SCOPE}/top_k") == "moe"
    assert dt.part_of(f"jit(f)/{MOE_SCOPE}/{MATMUL_SCOPE}/pallas_call") == \
        "moe.grouped_matmul"
    assert dt.part_of(f"jit(f)/jvp({LOSS_SCOPE})/log") == "loss"


# ---- parts from HLO text ------------------------------------------------

def _meta(path):
    return f'metadata={{op_name="jit(decode)/GPTModel/{path}" stack_frame_id=3}}'


HLO = f"""HloModule jit_decode, is_scheduled=true, entry_computation_layout={{(f32[4,64]{{1,0}})->f32[4,64]{{1,0}}}}

%region_1 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[]{{:T(128)}} parameter(0)
  %b = f32[]{{:T(128)}} parameter(1)
  ROOT %add.9 = f32[]{{:T(128)}} add(%a, %b)
}}

%fused_inner (p0: bf16[4,256], p1: bf16[256,64]) -> bf16[4,64] {{
  %p0 = bf16[4,256]{{1,0:T(4,128)(2,1)}} parameter(0)
  %p1 = bf16[256,64]{{1,0:T(8,128)(2,1)}} parameter(1)
  ROOT %convolution.7 = bf16[4,64]{{1,0:T(4,128)(2,1)}} convolution(%p0, %p1), dim_labels=bf_io->bf, {_meta("h0/mlp/fc_out/dot_general")}
}}

%fused_mlp (param_0: bf16[4,256], param_1: bf16[256,64], param_2: f32[4,64]) -> (f32[4], bf16[4,64]) {{
  %param_0 = bf16[4,256]{{1,0:T(4,128)(2,1)}} parameter(0)
  %param_1 = bf16[256,64]{{1,0:T(8,128)(2,1)}} parameter(1)
  %param_2 = f32[4,64]{{1,0:T(4,128)}} parameter(2)
  %fusion.inner = bf16[4,64]{{1,0:T(4,128)(2,1)}} fusion(%param_0, %param_1), kind=kOutput, calls=%fused_inner, {_meta("h0/mlp/fc_out/dot_general")}
  %convert.1 = f32[4,64]{{1,0:T(4,128)}} convert(%fusion.inner)
  %add.1 = f32[4,64]{{1,0:T(4,128)}} add(%convert.1, %param_2), {_meta("h0/add")}
  %constant.1 = f32[]{{:T(128)}} constant(0)
  %reduce.1 = f32[4]{{0:T(128)}} reduce(%add.1, %constant.1), dimensions={{1}}, to_apply=%region_1, {_meta("h1/ln1/reduce_sum")}
  %convert.2 = bf16[4,64]{{1,0:T(4,128)(2,1)}} convert(%add.1)
  ROOT %tuple.1 = (f32[4]{{0:T(128)}}, bf16[4,64]{{1,0:T(4,128)(2,1)}}) tuple(%reduce.1, %convert.2)
}}

%fused_norm (param_0.1: f32[4,64], param_1.1: f32[4]) -> f32[4,64] {{
  %param_0.1 = f32[4,64]{{1,0:T(4,128)}} parameter(0)
  %param_1.1 = f32[4]{{0:T(128)}} parameter(1)
  %broadcast.2 = f32[4,64]{{1,0:T(4,128)}} broadcast(%param_1.1), dimensions={{0}}, {_meta("h0/ln2/sub")}
  ROOT %subtract.2 = f32[4,64]{{1,0:T(4,128)}} subtract(%param_0.1, %broadcast.2), {_meta("h0/ln2/sub")}
}}

%fused_two_dots (param_0.2: bf16[4,64], param_1.2: bf16[64,64], param_2.2: bf16[64,4096]) -> bf16[4,4096] {{
  %param_0.2 = bf16[4,64]{{1,0:T(4,128)(2,1)}} parameter(0)
  %param_1.2 = bf16[64,64]{{1,0:T(8,128)(2,1)}} parameter(1)
  %param_2.2 = bf16[64,4096]{{1,0:T(8,128)(2,1)}} parameter(2)
  %dot.1 = bf16[4,64]{{1,0:T(4,128)(2,1)}} dot(%param_0.2, %param_1.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, {_meta("ln_f/dot_general")}
  ROOT %dot.2 = bf16[4,4096]{{1,0:T(4,128)(2,1)}} dot(%dot.1, %param_2.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, {_meta("wte.attend/dot_general")}
}}

%fused_gathered (param_0.3: bf16[4,64], param_1.3: bf16[64,64]) -> bf16[8,64] {{
  %param_0.3 = bf16[4,64]{{1,0:T(4,128)(2,1)}} parameter(0)
  %param_1.3 = bf16[64,64]{{1,0:T(8,128)(2,1)}} parameter(1)
  %convolution.3 = bf16[4,64]{{1,0:T(4,128)(2,1)}} convolution(%param_0.3, %param_1.3), dim_labels=bf_io->bf, {_meta("h0/attn/out/dot_general")}
  ROOT %all-gather.3 = bf16[8,64]{{1,0:T(8,128)(2,1)}} all-gather(%convolution.3), dimensions={{0}}
}}

%fused_scattered (param_0.4: f32[4,64]) -> f32[2,64] {{
  %param_0.4 = f32[4,64]{{1,0:T(4,128)}} parameter(0)
  %all-reduce.4 = f32[4,64]{{1,0:T(4,128)}} all-reduce(%param_0.4), channel_id=2, replica_groups={{{{0,1}}}}, to_apply=%region_1, {_meta("h0/attn/qkv/dot_general")}
  %partition-id.4 = u32[] partition-id()
  ROOT %dynamic-slice.4 = f32[2,64]{{1,0:T(2,128)}} dynamic-slice(%all-reduce.4, %partition-id.4, %partition-id.4), dynamic_slice_sizes={{2,64}}, {_meta("h0/attn/qkv/dot_general")}
}}

%body (p: (s32[], f32[4,64])) -> (s32[], f32[4,64]) {{
  %p = (s32[]{{:T(128)}}, f32[4,64]{{1,0:T(4,128)}}) parameter(0)
  %gte.0 = s32[]{{:T(128)}} get-tuple-element(%p), index=0
  %gte.1 = f32[4,64]{{1,0:T(4,128)}} get-tuple-element(%p), index=1
  %exp.5 = f32[4,64]{{1,0:T(4,128)}} exponential(%gte.1), {_meta("h0/attn/attention/while/body/exp")}
  ROOT %tuple.5 = (s32[]{{:T(128)}}, f32[4,64]{{1,0:T(4,128)}}) tuple(%gte.0, %exp.5)
}}

%cond (p.1: (s32[], f32[4,64])) -> pred[] {{
  %p.1 = (s32[]{{:T(128)}}, f32[4,64]{{1,0:T(4,128)}}) parameter(0)
  ROOT %lt.1 = pred[]{{:T(512)}} constant(true)
}}

ENTRY %main.25 (x: f32[4,64], w: bf16[256,64], big: bf16[64,4096]) -> bf16[4,4096] {{
  %x = f32[4,64]{{1,0:T(4,128)}} parameter(0), metadata={{op_name="x"}}
  %w = bf16[256,64]{{1,0:T(8,128)(2,1)}} parameter(1), metadata={{op_name="params[\\'w\\']"}}
  %big = bf16[64,4096]{{1,0:T(8,128)(2,1)}} parameter(2), metadata={{op_name="big"}}
  %copy-start.3 = (bf16[256,64]{{1,0:T(8,128)(2,1)S(1)}}, bf16[256,64]{{1,0:T(8,128)(2,1)}}, u32[]{{:S(2)}}) copy-start(%w)
  %copy-done.3 = bf16[256,64]{{1,0:T(8,128)(2,1)S(1)}} copy-done(%copy-start.3)
  %act = bf16[4,256]{{1,0:T(4,128)(2,1)}} constant({{...}})
  %convert_reduce_fusion.2 = (f32[4]{{0:T(128)}}, bf16[4,64]{{1,0:T(4,128)(2,1)}}) fusion(%act, %copy-done.3, %x), kind=kOutput, calls=%fused_mlp, {_meta("h1/ln1/reduce_sum")}
  %gte.s = f32[4]{{0:T(128)}} get-tuple-element(%convert_reduce_fusion.2), index=0
  %sub_fusion = f32[4,64]{{1,0:T(4,128)}} fusion(%x, %gte.s), kind=kLoop, calls=%fused_norm, {_meta("h0/ln2/sub")}
  %init = (s32[]{{:T(128)}}, f32[4,64]{{1,0:T(4,128)}}) tuple(%gte.s, %sub_fusion)
  %while.4 = (s32[]{{:T(128)}}, f32[4,64]{{1,0:T(4,128)}}) while(%init), condition=%cond, body=%body, {_meta("h0/attn/attention/while")}
  %all-reduce.1 = f32[4,64]{{1,0:T(4,128)}} all-reduce(%sub_fusion), channel_id=1, replica_groups={{{{0,1}}}}, to_apply=%region_1, {_meta("h0/mlp/fc_out/dot_general")}
  %all-gather-start.2 = (f32[4,64]{{1,0}}, f32[8,64]{{1,0}}) all-gather-start(%sub_fusion), dimensions={{0}}
  %h = bf16[4,64]{{1,0:T(4,128)(2,1)}} convert(%sub_fusion), {_meta("h0/mlp/fc_in/convert_element_type")}
  %fusion.gathered = bf16[8,64]{{1,0:T(8,128)(2,1)}} fusion(%h, %w), kind=kOutput, calls=%fused_gathered, {_meta("h0/attn/out/dot_general")}
  %fusion.scattered = f32[2,64]{{1,0:T(2,128)}} fusion(%sub_fusion), kind=kLoop, calls=%fused_scattered, {_meta("h0/attn/qkv/dot_general")}
  %copy.9 = f32[2,64]{{0,1:T(2,128)}} copy(%fusion.scattered)
  ROOT %fusion.83 = bf16[4,4096]{{1,0:T(4,128)(2,1)}} fusion(%h, %w, %big), kind=kOutput, calls=%fused_two_dots, {_meta("ln_f/dot_general")}
}}
"""


@pytest.fixture(scope="module")
def parts():
    return dt.instruction_parts(HLO)


@pytest.mark.parametrize("instruction, expected", [
    # a fusion whose root is the next norm's and whose product is the
    # MLP's (inside a fusion of its own, as the TPU compiler nests them)
    ("convert_reduce_fusion.2", ("mlp", dt.MIXED)),
    # no product inside: the root's, and nothing else in it
    ("sub_fusion", ("norm", None)),
    # two products: the one with the largest operand decides
    ("fusion.83", ("head", dt.MIXED)),
    # a while keeps its own name, and its body's instructions theirs
    ("while.4", ("attention", None)),
    ("exp.5", ("attention", None)),
    # by opcode, whatever the op_name says
    ("all-reduce.1", ("collective", None)),
    ("all-gather-start.2", ("collective", None)),
    # a product fused with a collective is the product's; with no product
    # in it, the collective's
    ("fusion.gathered", ("projection", dt.MIXED)),
    ("fusion.scattered", ("collective", dt.MIXED)),
    # the compiler's own instructions carry no op_name: a prefetch works
    # for the fusion that uses it, through the instructions between
    ("copy-start.3", ("mlp", dt.INHERITED)),
    ("copy-done.3", ("mlp", dt.INHERITED)),
    ("gte.s", ("norm", dt.INHERITED)),
    # ... and what nobody named uses stays unscoped
    ("copy.9", (dt.UNSCOPED, None)),
    ("h", ("mlp", None)),
])
def test_part_of_an_instruction(parts, instruction, expected):
    assert parts[instruction] == expected


def test_the_instructions_inside_a_fusion_are_no_events(parts):
    assert "convolution.7" not in parts and "reduce.1" not in parts
    assert "fusion.inner" not in parts and "dot.2" not in parts
    # the reducer of a reduce is a computation of its own: harmless
    assert parts["add.9"] == (dt.UNSCOPED, None)


# ---- the reduction, by hand ----------------------------------------------

KNOWN = {"a": ("mlp", None), "w": ("attention", None),
         "b": ("attention.cache_write", dt.INHERITED),
         "m": ("projection", dt.MIXED)}


def _reduce(chips, window=(0, 10_000), known=(KNOWN,)):
    return dt.reduce_events(chips, window, lambda name: list(known))


def test_nested_events_keep_their_own_time_only():
    ops = [("a", 0, 100), ("w", 100, 400), ("b", 120, 200),
           ("x", 200, 300), ("m", 400, 450)]
    table = _reduce({0: (ops, [("jit_p", 0, 450)])})
    entry = table["programs"][0]["jit_p"]
    assert entry["runs"] == 1 and entry["run_s"] == [450e-9]
    assert entry["parts"] == pytest.approx({
        "mlp": 100e-9, "attention": 120e-9,       # 300 less 80 and 100
        "attention.cache_write": 80e-9, dt.UNSCOPED: 100e-9,
        "projection": 50e-9})
    assert sum(entry["parts"].values()) == pytest.approx(450e-9)
    assert entry["mixed_s"] == pytest.approx(50e-9)
    assert entry["inherited_s"] == pytest.approx(80e-9)
    assert entry["unscoped_s"] == pytest.approx(100e-9)
    assert dt.part_seconds(entry, "attention") == pytest.approx(200e-9)
    assert dt.part_seconds(entry, "attention.cache_write") == \
        pytest.approx(80e-9)
    assert table["busy_s"] == {0: pytest.approx(450e-9)}
    assert table["window_us"] == (0.0, 10.0)


def test_every_chip_and_every_program_has_its_own_table():
    chips = {
        0: ([("a", 0, 100), ("b", 200, 260)],
            [("jit_p", 0, 100), ("jit_q", 200, 260)]),
        3: ([("a", 50, 80), ("a", 500, 530)],
            [("jit_p", 50, 80), ("jit_p", 500, 530)])}
    table = _reduce(chips)
    assert sorted(table["programs"]) == [0, 3]
    assert sorted(table["programs"][0]) == ["jit_p", "jit_q"]
    assert table["programs"][3]["jit_p"]["runs"] == 2
    assert table["programs"][3]["jit_p"]["run_s"] == [30e-9, 30e-9]
    assert table["programs"][3]["jit_p"]["parts"] == \
        pytest.approx({"mlp": 60e-9})
    assert table["busy_s"] == pytest.approx({0: 160e-9, 3: 60e-9})


def test_a_run_the_window_cuts_counts_as_busy_time_alone():
    ops = [("a", 900, 1100), ("a", 1200, 1300), ("a", 1900, 2100)]
    runs = [("jit_p", 900, 1100), ("jit_p", 1200, 1300),
            ("jit_p", 1900, 2100)]
    table = _reduce({0: (ops, runs)}, window=(1000, 2000))
    entry = table["programs"][0]["jit_p"]
    assert entry["runs"] == 1 and entry["run_s"] == [100e-9]
    assert entry["parts"] == pytest.approx({"mlp": 100e-9})
    assert table["busy_s"][0] == pytest.approx(300e-9)   # 100 + 100 + 100


def test_a_program_that_registered_nothing_is_all_unscoped():
    table = _reduce({0: ([("a", 0, 10), ("z", 10, 30)],
                         [("jit_sample", 0, 30)])}, known=())
    entry = table["programs"][0]["jit_sample"]
    assert entry["parts"] == pytest.approx({dt.UNSCOPED: 30e-9})
    assert entry["unscoped_s"] == pytest.approx(30e-9)
    assert entry["mixed_s"] == entry["inherited_s"] == 0.0


def test_of_programs_that_share_a_name_the_one_that_ran_is_taken():
    other = {"init.1": ("outside_model", None), "a": ("head", None)}
    ops = [("a", 0, 10), ("w", 10, 100), ("m", 100, 130)]
    table = _reduce({0: (ops, [("jit_flat_fun", 0, 130)])},
                    known=(other, KNOWN))
    assert table["programs"][0]["jit_flat_fun"]["parts"] == pytest.approx(
        {"mlp": 10e-9, "attention": 90e-9, "projection": 30e-9})


def test_each_run_is_read_by_the_program_that_knows_its_instructions():
    """One jitted function that ran as two programs, an engine's dense
    prefill at two buckets: both are ``jit_prefill``, their instructions
    share names (``a`` is the small one's head and the large one's MLP),
    and the entry holds the runs of both, each read by its own."""
    small = {"a": ("head", None), "s": ("attention", None)}
    large = dict(KNOWN, big=("embed", None))
    ops = [("a", 0, 10), ("s", 10, 30),                 # the small program
           ("a", 100, 150), ("w", 150, 300), ("big", 300, 310),
           ("a", 400, 410), ("s", 410, 430)]
    runs = [("jit_prefill", 0, 30), ("jit_prefill", 100, 310),
            ("jit_prefill", 400, 430)]
    for known in ((small, large), (large, small)):
        entry = _reduce({0: (ops, runs)}, known=known)[
            "programs"][0]["jit_prefill"]
        assert entry["runs"] == 3
        assert entry["run_s"] == pytest.approx([30e-9, 210e-9, 30e-9])
        assert entry["parts"] == pytest.approx({
            "head": 20e-9, "attention": 40e-9 + 150e-9, "mlp": 50e-9,
            "embed": 10e-9})
        assert entry["unscoped_s"] == 0.0
    # a registered program that also knows every instruction of a run and
    # more besides does not take it from the one that ran
    superset = dict(small, a=("norm", None), extra=("loss", None))
    entry = _reduce({0: (ops[:2], runs[:1])}, known=(superset, small))[
        "programs"][0]["jit_prefill"]
    assert entry["parts"] == pytest.approx({"head": 10e-9,
                                            "attention": 20e-9})


def test_without_a_marker_the_window_is_the_events_and_none_is_empty():
    table = dt.reduce_events(
        {0: ([("a", 100, 200)], [("jit_p", 100, 200)])}, None,
        lambda name: [KNOWN])
    assert table["window_us"] == (0.1, 0.2)
    assert table["programs"][0]["jit_p"]["runs"] == 1
    assert dt.reduce_events({}, None) == dt.empty_table() == \
        {"programs": {}, "busy_s": {}, "collectives": {},
         "window_us": None}


# ---- one trace recorded on the chip, with its HLO text ---------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "toy_decode.hlo.json.gz"), "rt") as f:
        texts = json.load(f)
    marker, chips = dt.read_profile(
        os.path.join(DATA, "toy_decode.xplane.pb"), ttrace.CAPTURE_MARKER)
    table = dt.reduce_events(
        chips, marker,
        lambda name: [dt.instruction_parts(t) for t in texts.get(name, ())])
    return texts, marker, chips, table


def test_the_recorded_trace_holds_one_prefill_and_six_decodes(recorded):
    _texts, marker, chips, table = recorded
    assert list(chips) == [0] and marker[1] > marker[0]
    programs = table["programs"][0]
    assert programs["jit_prefill"]["runs"] == 1
    assert programs["jit_decode"]["runs"] == 6
    # the argmax and the index's increment of the script, jitted by jax
    assert {"jit__argmax", "jit_add"} <= set(programs)
    for name in ("jit__argmax", "jit_add"):
        assert programs[name]["unscoped_s"] == \
            pytest.approx(sum(programs[name]["parts"].values()))
    lo, hi = table["window_us"]
    assert 0 < table["busy_s"][0] < (hi - lo) / 1e6


@pytest.mark.parametrize("program", ["jit_decode", "jit_prefill"])
def test_the_parts_of_a_recorded_program_sum_to_its_device_seconds(
        recorded, program):
    """By hand: these programs hold no loop, so their seconds are the
    plain sum of the events inside their runs."""
    _texts, (lo, hi), chips, table = recorded
    ops, runs = chips[0]
    inside = [(s, e) for name, s, e in runs
              if name == program and lo <= s and e <= hi]
    total = sum(e - s for _n, s, e in ops
                if any(rs <= s and e <= re_ for rs, re_ in inside))
    entry = table["programs"][0][program]
    assert sum(entry["parts"].values()) == pytest.approx(total / 1e9)
    assert entry["run_s"] == pytest.approx([(e - s) / 1e9
                                            for s, e in sorted(inside)])
    # the compiler's own copies, which carry no name: a fixed 0.25 ms a
    # decode at these widths (record_device_trace.py)
    assert entry["unscoped_s"] < 0.1 * total / 1e9
    assert 0 < entry["mixed_s"] < total / 1e9
    assert {"attention", "attention.cache_write", "projection", "mlp",
            "embed", "norm", "head"} <= set(entry["parts"])


def test_the_recorded_cache_writes_are_the_events_under_their_scope(
        recorded):
    """A second reader of the same trace: instruction names under the
    scope by a plain search of the text, their events summed."""
    texts, (lo, hi), chips, table = recorded
    (text,) = texts["jit_decode"]
    named = set()
    for line in text.splitlines():
        m = re.match(r'^\s+(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"',
                     line)
        if m and "/cache_write/" in m.group(2) and not \
                re.match(r"^\s+\S+ = \S+ parameter\(", line):
            named.add(m.group(1))
    ops, runs = chips[0]
    inside = [(s, e) for name, s, e in runs
              if name == "jit_decode" and lo <= s and e <= hi]
    fused = set(re.findall(r"^%?(fused_computation[\w.\-]*) ", text, re.M))
    assert named and fused
    total = sum(e - s for name, s, e in ops if name in named and
                any(rs <= s and e <= re_ for rs, re_ in inside))
    assert total > 0
    # the plain reader counts named instructions only: the table agrees
    # with it once what it lent the unnamed ones is taken back
    named_only = {op: (part, how) if how != dt.INHERITED
                  else (dt.UNSCOPED, None)
                  for op, (part, how) in dt.instruction_parts(text).items()}
    plain = dt.reduce_events(chips, (lo, hi), lambda name: [named_only])
    assert plain["programs"][0]["jit_decode"]["parts"][
        "attention.cache_write"] == pytest.approx(total / 1e9, rel=1e-6)
    entry = table["programs"][0]["jit_decode"]
    lent = entry["parts"]["attention.cache_write"] - total / 1e9
    assert 0 <= lent <= entry["inherited_s"]
    assert plain["programs"][0]["jit_decode"]["unscoped_s"] == \
        pytest.approx(entry["unscoped_s"] + entry["inherited_s"])


def test_offset_and_device_time_come_from_one_read_of_the_file(
        tmp_path, monkeypatch):
    """A capture made by hand around the recorded file: whichever is asked
    first reads it, and the other asks nothing more."""
    where = tmp_path / "plugins" / "profile" / "2026_09_28"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "toy_decode.xplane.pb"),
                where / "host.xplane.pb")
    reads = []
    read_profile = dt.read_profile
    monkeypatch.setattr(dt, "read_profile", lambda *a: (
        reads.append(a), read_profile(*a))[1])
    capture = ttrace.Capture(str(tmp_path), [], marker_ts_us=1000.0)
    marker, _ = read_profile(str(where / "host.xplane.pb"),
                             ttrace.CAPTURE_MARKER)
    assert capture.offset_us() == pytest.approx(marker[0] / 1e3 - 1000.0)
    table = capture.device_time()
    assert len(reads) == 1
    # nothing registered these programs here: by program only
    entry = table["programs"][0]["jit_decode"]
    assert entry["runs"] == 6
    assert entry["unscoped_s"] == pytest.approx(sum(entry["parts"].values()))
    shutil.rmtree(tmp_path / "plugins")     # the benchmark deletes it
    assert capture.device_time() is table and capture.offset_us() > 0
    assert len(reads) == 1


# ---- the capture on the CPU ----------------------------------------------

def test_stop_capture_reads_once_and_is_the_last_capture(tmp_path,
                                                         monkeypatch):
    reads = []
    read_profile = dt.read_profile
    monkeypatch.setattr(dt, "read_profile", lambda *a: (
        reads.append(a), read_profile(*a))[1])
    prev, was = ttrace.set_recorder(ttrace.TraceRecorder()), ttrace.enabled()
    try:
        ttrace.start_capture(str(tmp_path))
        jnp.ones((8, 8)).sum().block_until_ready()
        capture = ttrace.stop_capture()
    finally:
        ttrace.set_recorder(prev)
        ttrace.set_enabled(was)
    assert ttrace.last_capture() is capture
    assert len(reads) == 1
    shutil.rmtree(tmp_path / "plugins")
    # a CPU trace has no TPU plane: the table holds no chip
    table = capture.device_time()
    assert table["programs"] == {} and table["busy_s"] == {}
    assert table["window_us"][1] > table["window_us"][0]
    assert isinstance(capture.offset_us(), float)
    assert len(reads) == 1


# ---- the registry ---------------------------------------------------------

class _Owner:
    def __init__(self, text):
        self.text = text


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(dt, "_PROGRAMS", {})
    return dt._PROGRAMS


def test_the_registry_holds_owners_weakly_and_reads_a_text_once(registry):
    asked = []
    text_of = lambda owner: (asked.append(id(owner)), owner.text)[1]
    first, second = _Owner(HLO), _Owner(HLO.replace("while.4", "while.9"))
    dt.register_program("jit_decode", first, text_of)
    dt.register_program("jit_decode", second, text_of)
    dt.register_program("jit_decode", second, text_of, variant="(8, 1)")
    assert len(registry["jit_decode"]) == 3
    found = dt.registered_parts("jit_decode")
    assert len(found) == 3 and len(asked) == 3
    assert sum("while.9" in parts for parts in found) == 2
    assert len(dt.registered_parts("jit_decode")) == 3 and len(asked) == 3
    del second
    (only,) = dt.registered_parts("jit_decode")
    assert "while.4" in only
    assert dt.registered_parts("jit_never") == []


def test_a_text_that_cannot_be_got_fails_no_reduction(registry):
    owner = _Owner(None)

    def broken(_owner):
        raise RuntimeError("the executable was deleted")

    dt.register_program("jit_p", owner, broken)
    table = dt.reduce_events({0: ([("a", 0, 10)], [("jit_p", 0, 10)])},
                             (0, 100))
    assert table["programs"][0]["jit_p"]["unscoped_s"] == pytest.approx(1e-8)


CFG = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=64,
                vocab_size=64)


def test_a_generator_registers_its_programs_when_they_compile(registry):
    model, params = init_gpt_real(CFG, 1)
    gen = Generator(model, params, CFG, batch_size=2, prompt_buckets=[16])
    assert registry == {}                   # nothing has compiled yet
    ids = jnp.ones((2, 16), jnp.int32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    logits, caches = gen._prefill(gen.params, ids, None, lengths)
    token = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        logits, caches, _ = gen._decode(gen.params, token, lengths, caches)
    assert sorted(registry) == ["jit_decode", "jit_prefill"]
    assert [len(v) for v in registry.values()] == [1, 1]
    traced = (gen.decode_traces, gen.prefill_traces)
    (decode,) = dt.registered_parts("jit_decode")
    (prefill,) = dt.registered_parts("jit_prefill")
    # the text is that of the program that runs: no second trace of it
    assert (gen.decode_traces, gen.prefill_traces) == traced
    for parts in (decode, prefill):
        found = collections.Counter(part for part, _ in parts.values())
        assert {"attention", "attention.cache_write", "projection", "mlp",
                "norm", "head"} <= set(found)
    # another shape is another program of the same name
    gen._prefill(gen.params, jnp.ones((1, 16), jnp.int32), None,
                 jnp.asarray([3], jnp.int32))
    assert len(registry["jit_prefill"]) == 2
    del gen, model
    import gc
    gc.collect()
    assert dt.registered_parts("jit_decode") == []


# ---- the name stacks through the planner ------------------------------------

def _instructions(hlo_text):
    """The text's instructions and computation headers without metadata:
    what has to stay as it was."""
    kept = []
    for line in hlo_text.splitlines():
        if re.match(r"^(\s+(ROOT )?%?[\w.\-]+ = |ENTRY |%[\w.\-]+ \(|\})",
                    line):
            kept.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return kept


def _toy_train_step(method, layers=2, boundary_every=0):
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=layers,
                    num_heads=4, seq_len=64, dtype=jnp.bfloat16,
                    remat_blocks=True, attention_impl="reference",
                    pipeline_boundary_every=boundary_every)
    model = GPTModel(cfg)
    tx = optax.adam(1e-4)

    def create_state():
        params = init_params(model, jax.random.PRNGKey(0),
                             jnp.ones((8, 64), jnp.int32))
        return train_state.TrainState.create(apply_fn=model.apply,
                                             params=params, tx=tx)

    @alpa_tpu.parallelize(method=method, static_argnums=(),
                          donate_argnums=(0,))
    def train_step(state, batch):
        loss, grads = alpa_tpu.value_and_grad(
            lambda p: gpt_lm_loss(state.apply_fn, p, batch))(state.params)
        return state.apply_gradients(grads=grads), loss

    batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32)
             for k in ("input_ids", "labels")}
    executable, _ = train_step.get_executable(jax.eval_shape(create_state),
                                              batch)
    return executable


@contextlib.contextmanager
def _as_the_parent_bound_equations(monkeypatch):
    """``make_constrained_fun`` as it was: every equation bound with no
    name stack and no traceback of its own."""
    with monkeypatch.context() as patch:
        patch.setattr(strategy.source_info_util, "user_context",
                      lambda *a, **k: contextlib.nullcontext())
        yield


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def test_names_survive_make_constrained_fun_on_two_devices(monkeypatch,
                                                           registry):
    method = lambda: alpa_tpu.ShardParallel(devices=jax.devices()[:2])
    executable = _toy_train_step(method())
    text = executable.get_hlo_text()
    names = _op_names(text)
    assert any("/h0/attn/attention/" in n for n in names)
    assert any("/h1/mlp/fc_out/" in n for n in names)
    assert any("jvp(loss)" in n for n in names)
    found = collections.Counter(
        part for part, _ in dt.instruction_parts(text).values())
    assert {"attention", "projection", "mlp", "norm", "embed", "head",
            "loss", "outside_model"} <= set(found)
    # the step registered itself under the name the profiler will use
    # (the planner's re-evaluation of it is what was jitted)
    assert dt.compiled_name(executable.compiled) == "jit_constrained"
    (registered,) = dt.registered_parts("jit_constrained")
    assert registered == dt.instruction_parts(text)
    with _as_the_parent_bound_equations(monkeypatch):
        before = _toy_train_step(method()).get_hlo_text()
    assert not any("/h0/attn/attention/" in n for n in _op_names(before))
    assert _instructions(before) == _instructions(text)


def test_names_survive_the_pipeshard_slicing_on_four_devices(monkeypatch,
                                                             registry):
    from alpa_tpu.pipeline_parallel.layer_construction import \
        ManualLayerOption
    from alpa_tpu.pipeline_parallel.stage_construction import \
        UniformStageOption

    def compile_stages():
        alpa_tpu.init(cluster="local", devices=jax.devices()[:4])
        method = alpa_tpu.PipeshardParallel(
            num_micro_batches=2, pipeline_schedule="1f1b",
            layer_option=ManualLayerOption(),
            stage_option=UniformStageOption(num_stages=2))
        executable = _toy_train_step(method, boundary_every=1)
        stages = executable.stage_execs + [
            e for e in executable.apply_execs if e is not None]
        texts = {s.name: s.compiled.as_text() for s in stages}
        alpa_tpu.shutdown()
        return texts

    texts = compile_stages()
    assert sorted(texts) == ["apply_grad_0", "apply_grad_1", "stage_0_bwd",
                             "stage_0_fwd", "stage_1_bwd", "stage_1_fwd"]
    # every stage program runs, and registered, under its own name
    for name, text in texts.items():
        assert text.startswith(f"HloModule jit_{name},")
        assert f"jit_{name}" in registry
    assert any("/h0/attn/attention/" in n
               for n in _op_names(texts["stage_0_fwd"]))
    assert any("rematted_computation/h1/attn/attention/" in n
               for n in _op_names(texts["stage_1_bwd"]))
    assert any("jvp(loss)" in n for n in _op_names(texts["stage_1_fwd"]))
    parts = collections.Counter(
        part for part, _ in dt.instruction_parts(
            texts["stage_0_bwd"]).values())
    assert {"attention", "projection", "mlp", "norm", "embed"} <= set(parts)
    with _as_the_parent_bound_equations(monkeypatch):
        before = compile_stages()
    assert not any("/attention/" in n
                   for n in _op_names(before["stage_0_fwd"]))
    for name, text in texts.items():
        # but for the program's name (the parent's were all
        # ``jit_constrained``), metadata alone differs
        strip = lambda t: [re.sub(r"^HloModule \S+", "", line)
                           for line in _instructions(t)]
        assert strip(before[name]) == strip(text), name
