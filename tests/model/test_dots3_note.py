"""The dots3-note (``model_type`` dots3_note) kinds of the one decoder
definition (latent attention over the positions a learned indexer selects
in the full layers; latent attention with widths of its own under a
window, its cache a ring of latents, in the sliding layers; head-wise
gates; a sigmoid router with a choice bias over a share of the experts)
against the plain reference ``chipbench/references/dots3_note_decoder.py``
at a toy size on the CPU where every mechanism is live: hidden 64, six
layers F F S S S F, window 5, 8 index heads of 16 selecting 8 positions,
full layers of 4 heads of 16 + 8 and 16 (ranks 24 and 16), sliding layers
of 2 heads of 24 + 8 and 16 (ranks 24 and 32), 16 experts of which 4 are
held, 3 picks a token, seeded weights.  Float32 at full matmul precision,
so that what is compared is the mathematics: the training call, prefill in
chunks and then decoding through the caches against the reference's full
forward pass, logits and not tokens, and the selected sets themselves.  The
benchmark's cell compares the bfloat16 program with the same reference on
the chip."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model import gpt_model
from alpa_tpu.model.gpt_model import (LATENT_SLIDING, GPTModel,
                                      config_from_hf, init_kv_caches,
                                      kv_cache_kinds, kv_cache_shapes,
                                      latent_kv_caches,
                                      require_uniform_kv_caches,
                                      selected_per_row, uniform_kv_caches)
from alpa_tpu.ops import latent_attention as kernels
from alpa_tpu.serve.generation import GenerationConfig, Generator
from alpa_tpu.testing import highest, init_params, shake
from chipbench import arithmetic_dsa, run

TOY = run.load_json(run.HERE, "configs", "toy-dots3.json")
CELL = run.load_json(run.HERE, "configs", "dots3-note-prev-1chip.json")
DRIVER = run.load_module("drivers", "serve_dsa")
MLA = run.load_module("drivers", "serve_mla")
REF = run.load_module("references", "dots3_note_decoder")
CONTEXT, S = 96, 48
TOL = 5e-5


def toy_config(**kwargs):
    return MLA.model_config(
        TOY, **{"dtype": jnp.float32, "seq_len": CONTEXT, **kwargs})


# norm weights, the index keys' LayerNorm and the routers' biases
SHAKEN = ("scale", "bias", "router_bias")


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    model = GPTModel(cfg)
    params = shake(init_params(model, jax.random.PRNGKey(0),
                               jnp.ones((1, 8), jnp.int32)), SHAKEN)
    return cfg, model, params


@pytest.fixture(scope="module")
def reference():
    return REF.Reference(DRIVER.reference_settings(TOY))


@pytest.fixture(scope="module")
def wanted(toy, reference):
    """The reference's logits, picks and selections of one sequence."""
    _, _, params = toy
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (S,), 4,
                                        TOY["vocab_size"]))
    logits, picks, (chosen, real) = reference.logits_experts_selections(
        REF.weights_from_program(params), ids, (0, S))
    return ids, np.asarray(logits), np.asarray(picks), \
        np.asarray(chosen), np.asarray(real)


def as_sets(positions, real):
    return [set(p[:n].tolist()) for p, n in zip(positions, real)]


# ---- the configuration ------------------------------------------------

def parameters(cfg):
    shapes = jax.eval_shape(GPTModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_config_from_hf_reads_the_catalog_rows_config(catalog_row):
    """The catalog row's ``config``, unedited, is the published 46-layer
    model: 279.55 B language-model parameters."""
    published = catalog_row("dots3-note-prev")["config"]
    cfg = config_from_hf(published)
    assert cfg.num_layers == 46 and cfg.hidden_size == 5120
    assert cfg.attention.count("latent") == 13
    assert cfg.attention.count(LATENT_SLIDING) == 33
    assert cfg.attention[:6] == ("latent", "latent") + \
        (LATENT_SLIDING,) * 3 + ("latent",)
    assert cfg.mlp == ("gated",) + ("experts",) * 45
    assert (cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rope_theta) == (128, 1024, 512, 128, 64, 128, 8e7)
    assert cfg.sliding_latent == gpt_model.LatentWidths(
        64, 1024, 1024, 192, 64, 128, 5e4, None, np.sqrt(5), np.sqrt(5))
    assert cfg.q_lora_scale == np.sqrt(5) and \
        cfg.kv_lora_scale == np.sqrt(10)
    assert (cfg.sliding_window, cfg.index_topk, cfg.index_n_heads,
            cfg.index_head_dim) == (513, 2048, 64, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.num_shared_experts, cfg.mlp_width) == (256, 8, 1536, 1,
                                                        13824)
    assert cfg.router_score == "sigmoid" and cfg.router_bias and \
        cfg.norm_topk_prob and cfg.route_scale == 1.0
    assert cfg.attn_gate == "head" and not cfg.tie_embeddings
    assert parameters(cfg) == 279_551_726_592
    assert arithmetic_dsa.model_parameters(dict(
        published, published={"n_routed_experts": 256})) == \
        279_551_148_032      # beside the norms and the routers' biases


def test_the_cells_file_is_the_share_the_issue_counts():
    cfg = MLA.model_config(CELL, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16,
                           seq_len=CELL["serve"]["served_context"])
    assert cfg.num_layers == 6 and cfg.experts_held == (0, 32) and \
        cfg.num_experts == 256 and cfg.vocab_size == 19008
    assert parameters(cfg) == 5_011_092_992
    assert arithmetic_dsa.model_parameters(CELL) == 5_011_013_632
    assert CELL["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    caches = jax.eval_shape(
        lambda: init_kv_caches(cfg, CELL["serve"]["engine_rows"]))
    assert sum(c.size * 2 + k.size * 2 for c, k, _ in caches) == \
        2_469_500_928
    assert arithmetic_dsa.full_layer_bytes_per_position(CELL, 2) == 1408
    assert arithmetic_dsa.full_layer_bytes_held_per_position(CELL, 2) == \
        1536
    assert arithmetic_dsa.ring_bytes_per_row(CELL, 2) == 513 * 1088 * 2


def test_the_cells_json_keeps_the_catalog_rows_numbers(catalog_row):
    published = catalog_row("dots3-note-prev")["config"]
    for key, value in published.items():
        if key in CELL["reduced"]:
            assert CELL[key] != value and CELL["published"][key] == value
        else:
            assert CELL[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("topk_method", "group_limited_greedy"), ("scoring_func", "softmax"),
    ("attention_gate_type", "elementwise"),
    ("swa_attention_gate_type", "none"),
    ("swa_num_key_value_heads", 1),
    ("layer_types", ["full_attention"] * 5 + ["linear_attention"]),
    ("layer_types", ["full_attention"] * 3),
    ("rope_scaling", {"type": "yarn", "factor": 4})])
def test_what_the_file_says_and_the_program_cannot_is_refused(key, value):
    with pytest.raises(ValueError):
        config_from_hf(dict(TOY, **{key: value}))


# ---- the caches -------------------------------------------------------

def test_two_new_cache_kinds(toy):
    cfg, _, _ = toy
    assert kv_cache_kinds(cfg) == ["latent_index"] * 2 + \
        ["latent_window"] * 3 + ["latent_index"]
    full = ((2, CONTEXT, 128), (2, CONTEXT, 16))   # 16 + 8 in whole lanes
    ring = ((2, 5, 32), (2, 8, 5))
    assert kv_cache_shapes(cfg, 2) == [full, full, ring, ring, ring, full]
    assert latent_kv_caches(cfg) and not uniform_kv_caches(cfg)
    assert selected_per_row(cfg) == 8
    assert selected_per_row(toy_config(index_topk=0)) == 0
    assert gpt_model.cached_key_block(cfg, 1) == 0


@pytest.mark.parametrize("what", ["kv_pool (KVBlockPool)",
                                  "generate_speculative", "generate_beam"])
def test_the_new_caches_are_refused_by_name(toy, what):
    cfg, _, _ = toy
    with pytest.raises(ValueError, match="index_topk") as e:
        require_uniform_kv_caches(cfg, what)
    assert what in str(e.value) and "latent_sliding" in str(e.value)
    # a window alone
    with pytest.raises(ValueError, match="ring of the window's latents"):
        require_uniform_kv_caches(toy_config(index_topk=0), what)


def test_the_ring_holds_exactly_the_window():
    """A row's ring after a chunk of 7 and three decode steps holds the
    window's last five positions, each in its slot, and the attention
    over it sees those and no others."""
    w, r, dr = 5, 4, 2
    cache = (jnp.zeros((1, w, r)), jnp.zeros((1, dr, w)), jnp.int32(0))

    def at(p, n):
        pos = jnp.arange(p, p + n, dtype=jnp.float32)
        return (jnp.broadcast_to(pos[None, :, None], (1, n, r)),
                jnp.broadcast_to(pos[None, :, None] + 100, (1, n, dr)))

    c_use, pe_use, held, cache = gpt_model.update_latent_ring(
        cache, *at(0, 7), jnp.asarray([7]))
    assert c_use.shape == (1, w + 7, r) and pe_use.shape == (1, dr, w + 7)
    assert held[0].tolist() == [-5, -4, -3, -2, -1] + list(range(7))
    assert cache[0][0, :, 0].tolist() == [5, 6, 2, 3, 4]
    cache = (cache[0], cache[1], jnp.asarray([7]))
    for p in (7, 8, 9):
        c_use, pe_use, held, cache = gpt_model.update_latent_ring(
            cache, *at(p, 1))
        assert sorted(held[0].tolist()) == list(range(p - 4, p + 1))
        assert c_use[0, :, 0].tolist() == held[0].tolist()
        assert (pe_use[0, 0] - 100).tolist() == held[0].tolist()
    # a padded chunk writes its real positions alone
    fresh = (jnp.zeros((1, w, r)), jnp.zeros((1, dr, w)), jnp.int32(0))
    *_, cache = gpt_model.update_latent_ring(fresh, *at(0, 7),
                                             jnp.asarray([3]))
    assert cache[0][0, :, 0].tolist() == [0, 1, 2, 0, 0]


# ---- the selection ----------------------------------------------------

# (Sk, k): 96 is three times a power of two, as GLM-5's 24,576 is; 768
# holds six of the compaction's blocks of 128 positions
SELECTIONS = [(64, 10), (96, 8), (768, 100)]
@partial(jax.jit, static_argnums=1)
def _selected_every_way(x, k):
    """One program a shape: the mask, the table and what ``top_k`` names."""
    return (gpt_model.selected_mask(x, k),
            *gpt_model.selected_positions(x, k), *jax.lax.top_k(x, k))


def _held_lengths(sk, k):
    """How much of ``sk`` positions the longest row holds: the static
    leading lengths a chunk's selection counts over
    (``selected_mask_upto``) and the lengths either side of each."""
    return sorted({n + d for n in (k, 2 * k, 4 * k, 8 * k, sk)
                   for d in (-1, 0, 1) if n + d <= sk})


def _scores_to_select(kind, queries, sk):
    """(6, Sk), or (6, queries, Sk) where ``queries``: scores in halves, so
    that ties abound, a query seeing one position more than the query
    before it."""
    shape = (6,) + queries + (sk,)
    x = np.round(np.random.RandomState(3).randn(*shape) * 2).astype(
        np.float32) / 2
    back = np.arange(*queries, 0, -1)[:, None] - 1 if queries else 0

    def holding(held):
        return np.where(np.arange(sk) < held - back, x, -np.inf)

    if kind == "a-tie-straddles-the-kth":
        return holding(sk * 5 // 8)
    if kind == "all-equal":
        return np.ones(shape, np.float32)
    if kind == "all-equal-of-those-held":
        return np.where(holding(sk // 2) > -np.inf, np.float32(0.25),
                        -np.inf)
    if kind == "fewer-finite-than-k":
        return holding(5)
    if kind == "a-row-with-none":
        x = holding(sk)
        x[2] = -np.inf
        return x
    return holding(int(kind.split("-")[1]))


@pytest.mark.parametrize("queries", [(), (2,)],
                         ids=["one-query-a-row", "two-queries-a-row"])
@pytest.mark.parametrize("sk,k,kind", [
    (sk, k, kind) for sk, k in SELECTIONS for kind in [
        "a-tie-straddles-the-kth", "all-equal", "all-equal-of-those-held",
        "fewer-finite-than-k", "a-row-with-none"] + [
            "held-%d" % n for n in _held_lengths(sk, k)]])
def test_the_selection_is_the_top_k_ties_to_the_lower_position(sk, k, kind,
                                                               queries):
    """``selected_positions`` (no sort: ``selected_mask``'s counts, and a
    compaction) and ``selected_mask`` name the set ``jax.lax.top_k`` names,
    the table in ascending position with the real ones first."""
    x = _scores_to_select(kind, queries, sk)
    mask, positions, real, best, want = map(
        np.asarray, _selected_every_way(x, k))
    assert positions.shape == x.shape[:-1] + (k,) and \
        positions.dtype == np.int32 and real.shape == x.shape[:-1]
    flat = x.reshape(-1, sk)
    mask, positions, real, best, want = (
        a.reshape((len(flat),) + a.shape[x.ndim - 1:])
        for a in (mask, positions, real, best, want))
    assert (real == np.minimum((flat > -np.inf).sum(-1), k)).all()
    assert (mask.sum(-1) == real).all()
    assert positions.min() >= 0 and positions.max() < sk
    for row, table, n, named in zip(mask, positions, real, want):
        assert (np.diff(table[:n]) > 0).all()
        assert set(np.flatnonzero(row).tolist()) == \
            set(table[:n].tolist()) == set(named[:n].tolist())
    if kind == "a-tie-straddles-the-kth":
        # a tie that straddles the k-th place was broken somewhere
        assert ((flat == best[:, -1:]).sum(-1) >
                (best == best[:, -1:]).sum(-1)).any()
    if kind == "a-row-with-none":
        assert (real.reshape((6,) + queries)[2] == 0).all()


@pytest.mark.parametrize("upto", [3, 8, 9, 16, 17, 40, 64, 65, 96])
def test_the_selection_over_the_live_positions_is_the_selection(upto):
    """A chunk's selection counts over the shortest leading part of the
    cache that holds the chunk's last query; it is the selection over the
    whole."""
    scores = jax.random.normal(jax.random.PRNGKey(upto), (2, 6, 96))
    scores = jnp.where(jnp.arange(96) < upto - jnp.arange(6)[:, None],
                       scores, -jnp.inf)
    got = jax.jit(gpt_model.selected_mask_upto, static_argnums=1)(
        scores, 8, jnp.int32(upto))
    assert (np.asarray(got) ==
            np.asarray(gpt_model.selected_mask(scores, 8))).all()
    assert (np.asarray(got).sum(-1) == np.minimum(
        8, np.maximum(upto - np.arange(6), 0))).all()


def rnd(i, *shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)


@pytest.mark.parametrize("rows,queries,starts", [
    (1, 256, (0,)), (1, 256, (700,)), (1, 256, (1792,)),
    (3, 1, (5, 1023, 2047)),
    # a few queries a row: a verify of a tick that drafts (PR 53)
    (3, 2, (0, 1023, 2046)), (1, 8, (40,))])
def test_the_index_kernels_are_their_twin(rows, queries, starts):
    heads, dim, keys = 8, 128, 2048
    q, w = rnd(0, rows, queries, heads, dim), rnd(1, rows, queries, heads)
    k = rnd(2, rows, keys, dim)
    q_pos = jnp.asarray(starts)[:, None] + jnp.arange(queries)[None]
    assert kernels.index_scores_fits(q, k)
    got = kernels.index_scores(q, w, k, q_pos, interpret=True)
    want = gpt_model._index_scores_blocks(q, w, k, q_pos)
    seen = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == seen).all()
    assert (seen == (np.arange(keys) <= np.asarray(q_pos)[..., None])).all()
    np.testing.assert_allclose(np.asarray(got)[seen],
                               np.asarray(want)[seen], atol=1e-4)


# name: (rows' offsets, heads, a key's channels before its padding to a
# whole lane, a value's channels); 64 queries a row, which a step takes in
# four parts of 16, over a cache of two key blocks of 1,024
MASKED_CASES = {
    "one-row": ((600,), 2, 128, 128),
    # row 0's queries end in the first of the cache's two key blocks
    "two-rows-one-ends-early": ((100, 1500), 2, 128, 128),
    "three-heads": ((1500,), 3, 128, 128),
    # the second key block begins among the queries of a step's first part
    # (row 0) and of its second (row 1)
    "a-key-block-begins-inside-a-part": ((1010, 1000), 2, 128, 128),
    # GLM-5's: keys padded to a whole lane, values of two lanes
    "keys-padded-values-two-lanes": ((1500,), 4, 96, 256),
    # a whole key block in which some queries selected nothing before the
    # block that holds what they did, and one query that selected nothing
    # at all
    "first-key-block-holds-nothing": ((1100, 1500), 4, 128, 128),
}


@pytest.mark.parametrize("case", MASKED_CASES)
def test_the_masked_kernel_is_the_masked_twin(case):
    offsets, heads, dn, dv = MASKED_CASES[case]
    b, sq, dr, rank, keys = len(offsets), 64, 64, 128, 2048
    qn, qp = rnd(6, b, sq, heads, dn), rnd(7, b, sq, heads, dr)
    rows, w = rnd(8, b, keys, rank + dr), rnd(9, rank, heads, dn + dv) * 0.1
    offset = jnp.asarray(offsets)
    q_pos = offset[:, None] + jnp.arange(sq)[None]
    scores = jnp.where(jnp.arange(keys)[None, None] <= q_pos[:, :, None],
                       rnd(10, b, sq, keys), -jnp.inf)
    chosen = gpt_model.selected_mask(scores, 100)
    answered = np.ones((b, sq), bool)
    if case == "first-key-block-holds-nothing":
        chosen = chosen.at[0, :8, :1024].set(False)
        chosen = chosen.at[1, 4:12, :1024].set(False).at[1, 20].set(False)
        answered[1, 20] = False
        assert chosen[0, :8].any(-1).all() and chosen[1, 4:12].any(-1).all()
    k_pe = rows[..., rank:].swapaxes(1, 2)
    # as ``latent_attention_selected`` hands a key of 96 channels in
    spare = -dn % 128
    wide_q = jnp.pad(qn, ((0, 0),) * 3 + ((0, spare),))
    wide_w = jnp.concatenate(
        [w[..., :dn], jnp.zeros(w.shape[:2] + (spare,)), w[..., dn:]], -1)
    assert kernels.fits(wide_q, rows, wide_w, masked=True)
    got = kernels.expanded(wide_q, qp, rows, k_pe, wide_w, offset,
                           scale=0.07, selected=chosen.astype(jnp.int8),
                           interpret=True)
    want = gpt_model._latent_attention_masked(
        qn, qp, rows[..., :rank], k_pe, w, chosen, scale=0.07)
    np.testing.assert_allclose(np.asarray(got)[answered],
                               np.asarray(want)[answered], atol=2e-5)
    # a query that selected nothing answers 0
    assert (np.asarray(got)[~answered] == 0).all()
    if case == "one-row":
        everything = kernels.expanded(wide_q, qp, rows, k_pe, wide_w, offset,
                                      scale=0.07, interpret=True)
        assert float(jnp.abs(everything - want).max()) > 1e-2


@pytest.mark.parametrize("queries,keys,unmasked,masked", [
    (1024, 32768, True, True),      # dots3-note's chunk over its cache
    (1024, 24576, True, True),      # GLM-5's
    (64, 2048, True, True),
    (64, 1536, True, False),        # whole key blocks of 512, not of 1,024
    (32, 2048, True, False),        # a part of the queries half a sublane tile
    (24, 2048, False, False)])
def test_the_masked_kernel_takes_whole_blocks_of_its_own(queries, keys,
                                                         unmasked, masked):
    """Under a selection the kernel walks key blocks of ``SELECTED_BLOCK_K``
    and takes a step's queries in parts: a cache or a chunk that is not
    whole ones goes to the ``jax.numpy`` twin (``gpt_model.
    latent_attention_selected`` asks ``fits``)."""
    shapes = (jnp.zeros((1, queries, 2, 128)), jnp.zeros((1, keys, 192)),
              jnp.zeros((128, 2, 256)))
    assert kernels.fits(*shapes) == unmasked
    assert kernels.fits(*shapes, masked=True) == masked


# ---- against the reference --------------------------------------------

def test_full_forward_equals_the_reference(toy, wanted):
    _, model, params = toy
    ids, logits, picks, _, _ = wanted
    got, routing = highest(model.apply, params, ids[None])
    np.testing.assert_allclose(got[0], logits, atol=TOL)
    assert (np.sort(routing["experts"], -1) == np.sort(picks, -1)).all()


@pytest.mark.parametrize("variant", ["gates_left_out", "recent_positions",
                                     "window_of_six", "one_theta",
                                     "index_keys_unnormed"])
def test_a_wrong_wiring_fails(toy, wanted, variant, monkeypatch):
    from chipbench import controls_dots3
    cfg, model, params = toy
    ids, logits, *_ = wanted
    if variant in controls_dots3.CONTROLS:
        controls_dots3.CONTROLS[variant](TOY, monkeypatch.setattr)
    elif variant == "window_of_six":
        model = GPTModel(toy_config(sliding_window=6))
    elif variant == "one_theta":
        import dataclasses
        model = GPTModel(toy_config(sliding_latent=dataclasses.replace(
            cfg.sliding_latent, rope_theta=cfg.rope_theta)))
    else:
        monkeypatch.setattr(gpt_model.nn, "LayerNorm",
                            lambda **kw: lambda x: x)
    got, _ = highest(model.apply, params, ids[None])
    assert float(np.abs(np.asarray(got[0]) - logits).max()) > 100 * TOL


@pytest.mark.parametrize("chunk,prompt", [(8, 29), (8, 8), (16, 37)])
def test_chunked_prefill_then_decode_equals_the_reference(toy, wanted,
                                                          chunk, prompt):
    """Chunks through the two kinds of cache, then decode steps: logits,
    picks and the selected sets against the reference's full forward."""
    cfg, model, params = toy
    ids, logits, picks, chosen, real = wanted
    gen = Generator(model, params, cfg, prefill_chunk=chunk)
    last, caches = highest(
        gen._run_chunked_prefill, [ids[:prompt]],
        jnp.asarray([prompt], jnp.int32), 1)
    np.testing.assert_allclose(last[0], logits[prompt - 1], atol=TOL)
    for t in range(prompt, S):
        out, caches, routing = highest(
            gen._decode, params, jnp.asarray(ids[None, t:t + 1]),
            caches[0][2], caches)
        np.testing.assert_allclose(out[0], logits[t], atol=TOL)
        assert (np.sort(routing["experts"][:, 0], -1) ==
                np.sort(picks[:, t], -1)).all()
        assert routing["selected"].shape == (3, 1, 8)
        assert as_sets(np.asarray(routing["selected"][:, 0]),
                       np.asarray(routing["selected_real"][:, 0])) == \
            as_sets(chosen[:, t], real[:, t])
    # every ring holds the window's last positions of the sequence
    for kind, (c, _, index) in zip(kv_cache_kinds(cfg), caches):
        assert int(index[0]) == S
        assert c.shape[1] == (5 if kind == "latent_window" else CONTEXT)


def test_the_reference_selects_what_its_indexer_scores_highest(toy, wanted):
    """By hand, one query of one full layer: the weighted relu scores, the
    eight best."""
    _, _, params = toy
    ids, _, _, chosen, real = wanted
    assert (real[:, :8] == np.arange(1, 9)).all() and (real[:, 8:] == 8).all()
    for t in (3, 20, S - 1):
        assert all(max(s) <= t for s in as_sets(chosen[:, t], real[:, t]))
    # layer 0's input is the embedding: its scores can be made here
    w = REF.weights_from_program(params)
    a = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64),
                               w["layers"][0]["attn"])
    x = np.asarray(w["wte"], np.float64)[ids]
    h = a["n_attn"] * x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)
    c_q = h @ a["w_q_a"]
    c_q = a["n_q"] * c_q / np.sqrt((c_q * c_q).mean(-1, keepdims=True) +
                                   1e-5) * np.sqrt(64 / 24)
    k = h @ a["w_ik"]
    k = (k - k.mean(-1, keepdims=True)) / np.sqrt(
        k.var(-1, keepdims=True) + 1e-6) * a["n_ik"] + a["b_ik"]
    q = (c_q @ a["w_iq"]).reshape(S, 8, 16)

    def turned(v, t):
        angle = t * 8e7 ** (-np.arange(4) / 4)
        first, second = v[..., :4], v[..., 4:8]
        return np.concatenate([
            first * np.cos(angle) - second * np.sin(angle),
            second * np.cos(angle) + first * np.sin(angle), v[..., 8:]], -1)

    t = 30
    keys = np.stack([turned(k[s], s) for s in range(t + 1)])
    scores = (np.maximum(turned(q[t], t) @ keys.T, 0) *
              ((h[t] @ a["w_iw"]) * 8 ** -0.5 * 16 ** -0.5)[:, None]).sum(0)
    assert set(np.argsort(-scores)[:8].tolist()) == \
        set(chosen[0, t, :8].tolist())


def test_rows_of_mixed_lengths_in_one_engine(toy):
    """The engine's rows hold unlike lengths in one tick, through the new
    caches; the counters say what the selecting layers fetched."""
    from alpa_tpu.serve.engine import ContinuousBatchingEngine
    from alpa_tpu.telemetry import metrics as tmetrics
    cfg, model, params = toy
    gen = Generator(model, params, cfg, prefill_chunk=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, 256, size=n) for n in (5, 19, 33, 12)]
    ask = GenerationConfig(max_new_tokens=9)
    series = 'alpa_serving_select_positions_total{what="%s"}'
    before = tmetrics.get_registry().snapshot()
    engine = ContinuousBatchingEngine(gen, max_batch=3,
                                      chunked_admission=True)
    try:
        got = [np.asarray(engine.submit(p, ask)) for p in prompts]
    finally:
        engine.shutdown()
    after = tmetrics.get_registry().snapshot()
    for p, out in zip(prompts, got):
        want = np.asarray(gen.generate([p], ask)[0])
        assert out.tolist() == want.tolist()

    def rose(name):
        return after[name] - before.get(name, 0.0)

    # a row of n positions at each of its nine ticks: three layers fetch
    # min(n, 8) of them
    held = sum(n + k for n in (5, 19, 33, 12) for k in range(1, 10))
    taken = sum(min(n + k, 8) for n in (5, 19, 33, 12) for k in range(1, 10))
    assert rose(series % "held") == 3 * held
    assert rose(series % "selected") == 3 * taken
    assert rose("alpa_serving_decode_positions_read_total") == taken
    assert after['alpa_serving_kv_cache_bytes{kind="latent"}'] == sum(
        np.prod(c) * 4 + np.prod(k) * 4 for c, k in kv_cache_shapes(cfg, 3))


def test_the_shares_of_a_layer_add_up_to_the_whole(reference):
    """The routed parts that the four shares give, and the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer; the program's share is the reference's."""
    whole_cfg = MLA.model_config(
        dict(TOY, n_routed_experts=16, share_index=0,
             published={"n_routed_experts": 16}),
        dtype=jnp.float32, seq_len=CONTEXT, experts_held=None)
    model = GPTModel(whole_cfg)
    params = shake(init_params(model, jax.random.PRNGKey(5),
                               jnp.ones((1, 8), jnp.int32)), SHAKEN)
    layer = REF.weights_from_program(params)["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (S, 64), jnp.float32)
    args = (1e-5, 3, True, 1.0)
    whole, picks = REF.routed_mlp(x, layer, *args, 0)

    def cut(first):
        return dict(layer, w_gate_up=layer["w_gate_up"][first:first + 4],
                    w_down=layer["w_down"][first:first + 4])

    no_shared = dict(s_gate=jnp.zeros_like(layer["s_gate"]),
                     s_up=layer["s_up"], s_down=layer["s_down"])
    shared = REF.routed_mlp(x, dict(cut(0), **no_shared,
                                    w_gate_up=layer["w_gate_up"][:0],
                                    w_down=layer["w_down"][:0]),
                            *args, 0)[0] - x
    assert float(jnp.abs(shared).max()) == 0.0
    shared = REF.routed_mlp(x, dict(layer, w_gate_up=layer["w_gate_up"][:0],
                                    w_down=layer["w_down"][:0]),
                            *args, 0)[0] - x
    parts = [REF.routed_mlp(x, cut(first), *args, first) for first in
             (0, 4, 8, 12)]
    for part, chosen in parts:
        assert (chosen == picks).all()
    total = sum(part - x - shared for part, _ in parts) + shared + x
    np.testing.assert_allclose(total, whole, atol=TOL)
    # the program's share 1 of the same layer
    from alpa_tpu.model.moe import DroplessExperts
    import dataclasses
    held = dataclasses.replace(whole_cfg, experts_held=(4, 4))
    mine = dict(params["params"]["h1"]["mlp"])
    mine.update(w_gate_up=mine["w_gate_up"][4:8], w_down=mine["w_down"][4:8])
    u = REF.rms(x, layer["n_mlp"], 1e-5)
    got, _ = highest(DroplessExperts(held).apply, {"params": mine}, u[None])
    np.testing.assert_allclose(got[0] + x, parts[1][0], atol=TOL)


# ---- the driver -------------------------------------------------------

def test_driver_runs_the_toy_cell(toy_context, checks_the_same_requests):
    """``chipbench/drivers/serve_dsa.py`` end to end on the CPU
    (``chipbench/rehearsal.json`` is not this PR's to edit): weights, the
    routers' balance, controller, warm-up, a closed-loop window over HTTP,
    the traced seconds, the check against the reference (logits, picks and
    selected sets); and what the cell's readers make of it."""
    obs = DRIVER.run(toy_context("toy-dots3.longctx", "toy-longctx", 3.0, 2,
                                 checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and obs["attempted"] >= 16, checks
    assert checks["checked_requests"] == 4 and checks["over_margin"] == 0
    assert checks["long_context_checked"] and \
        checks["short_context_checked"], checks
    assert checks["choice_agreement"] >= TOY["min_choice_agreement"]
    assert checks["selection_agreement"] >= TOY["min_selection_agreement"]
    assert checks["compiles_in_window"] == 0
    assert obs["correct"], checks
    assert obs["engine_rows"] == 3 and obs["expert_layers"] == 5
    assert obs["expert_bytes"] == 3 * 64 * 32 * 4
    # a CPU trace has no TPU plane
    assert obs["decode_trace"] == {} and obs["chunk_trace"] == {}
    obs.update(peaks=None, config=TOY)
    # three full layers by position, three rings spread over the context
    # (float32 in the toy)
    assert run.metric_reader("kv_cache_bytes_per_position")(obs) == \
        arithmetic_dsa.kv_cache_bytes_per_position(TOY, 4, 96) == \
        3 * (128 + 16) * 4 + 3 * 5 * (32 + 8) * 4 / 96
    selected = run.metric_reader("selected_positions_pct")(obs)
    assert 10 < selected < 50         # 8 of some 30 positions a row
    local = run.metric_reader("moe_local_rows_pct")(obs)
    assert 10 < local < 45            # 4 of 16 experts
    assert run.metric_reader("experts_touched_per_tick")(obs) <= 4
    for traced in ("indexer_decode_share_pct", "indexer_chunk_share_pct",
                   "latent_select_decode_roofline_pct"):
        assert run.metric_reader(traced)(obs) is None
    spans = [s for s in obs["program_spans"]
             if s["name"] == "engine.prefill"]
    assert spans and all(
        s["args"]["path"] == "chunked" and
        s["args"]["chunks"] == -(-s["args"]["prompt_len"] // 8)
        for s in spans)


@pytest.mark.parametrize("control", ["cache_in_float8", "recent_positions",
                                     "gates_left_out"])
def test_driver_fails_a_control(toy_context, monkeypatch, control,
                                checks_the_same_requests):
    """The controls the cell's limits are set against
    (``chipbench/controls_dots3.py``), planted at the toy size: each
    serves plausible tokens and is not correct."""
    from chipbench import controls_dots3
    controls_dots3.CONTROLS[control](TOY, monkeypatch.setattr)
    obs = DRIVER.run(toy_context("toy-dots3.longctx", "toy-longctx", 3.0, 0,
                                 checks_the_same_requests))
    checks = obs["checks"]
    assert obs["failed"] == 0 and checks["checked_requests"] == 4
    assert not obs["correct"], checks


# ---- the decode under its selection's mask (ISSUE 59) ----------------------

def test_the_decode_under_the_mask_is_the_gathered_decode(
        selecting_decode, selecting_cores_traced):
    """What the ``selects`` branch calls for a decode
    (``latent_attention_over_selection``) at widths the kernel takes
    (dots3-note's: one query a row, keys of 128 + 64 and values of 128),
    the kernel interpreted: the cache of four key blocks goes
    under the mask whatever its rows hold, and gives what
    ``latent_attention_gathered`` gives of the same cache and table."""
    args, want = selecting_decode(1, 32, 128, 128, [37, 1023, 2500])
    before = selecting_cores_traced(1).get("under_mask", 0)
    got = gpt_model.latent_attention_over_selection(*args, interpret=True)
    assert selecting_cores_traced(1)["under_mask"] == before + 1
    assert got.shape == want.shape == (3, 1, 32, 128)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("starts,under_mask", [([100, 900], True),
                                               ([100, 3500], False)],
                         ids=["rows-that-hold-little", "rows-that-hold-much"])
def test_the_rule_by_what_the_rows_hold_has_one_answer(
        selecting_decode, selecting_cores_traced, monkeypatch, starts,
        under_mask):
    """A cache longer than a query's gather is worth (here one key block
    of 1,024 positions a (row, query): four blocks are too many to take
    statically) is read ``by_held``: under the mask while the call's rows
    hold no more key blocks than their gathers are worth, gathered beyond;
    one result on both sides."""
    from alpa_tpu.ops import latent_attention as kernels
    monkeypatch.setattr(gpt_model, "GATHER_WORTH_KEY_BLOCKS", 1)
    args, want = selecting_decode(1, 32, 128, 128, starts, seed=1)
    held = int(kernels.decode_blocks(args[5], 1, 4096).sum())
    assert (held <= len(starts) * 1) == under_mask
    before = selecting_cores_traced(1).get("by_held", 0)
    got = jax.jit(lambda *a: gpt_model.latent_attention_over_selection(
        *a[:4], args[4], *a[4:], interpret=True))(*args[:4], *args[5:])
    assert selecting_cores_traced(1)["by_held"] == before + 1
    np.testing.assert_allclose(got, want, atol=1e-5)
