"""Plain reference of the dots3-note decoder (``dots-studio/dots3-note-prev``
``config.json``; ``model_type`` dots3_note), the language model alone: the
forward pass in straightforward ``jax.numpy``, float32, full matmul
precision.  No cache, no ring, no absorption, no gather, no grouped matmul,
no kernel, no batching: one sequence at a time, latent attention in its
expanded (published) form with every position's per-head keys and values
made from its latent, every query attending over the whole sequence under
a mask (the window's, or the indexer's selection), and every held expert
applied to every token with a routing weight that is zero for the experts
the token did not choose.  Queries go in blocks, heads in groups, the index
heads and the experts one after another, each matrix upcast where it is
used, and a full layer's (S, S) selection is kept eight positions a byte,
so that 32,768 positions fit beside the served model.

Written from the catalog's row of the published ``config.json`` (the sizes)
and, where the configuration does not fix a thing, from what the families
its keys name do (marked (*); the configuration file lists each under
``assumed``), not from the program's model file.  ``h`` is the stream,
``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``, every norm has a weight of
its own, nothing has a bias but the index keys' LayerNorm and the router's
choice:

* ``x = E[ids]``; 46 (here: as many as the weights have) pre-norm layers
  ``a = h + Attn(rms(h)); h' = a + MLP(rms(a))``; a final RMSNorm and an
  untied head.  ``layer_types`` says which layers are full and which
  sliding; layer ``i < first_k_dense_replace`` has a dense gated MLP, every
  other a routed one.
* ``MLP(u) = (silu(u Wg) * (u Wu)) Wd``.
* latent attention, H heads, with the widths of the layer's kind (a
  sliding layer's are the ``swa_`` keys'): ``c_q = s_q rms(x Wq_a)``, ``q =
  c_q Wq_b`` a head ``[q_nope (dn) | q_pe (dr)]``; ``[c | k_pe] = x Wkv_a``,
  ``c = s_kv rms(c)``, ``k_pe`` ONE key of dr channels a position for all
  heads, not scaled; ``s_q = sqrt(hidden / q_lora_rank)``, ``s_kv =
  sqrt(hidden / kv_lora_rank)`` where ``apply_mla_qkv_lora_rescale`` (*:
  read as LongCat-Flash's factors, after the norm); ``[k_nope (dn) | v
  (dv)] = c Wkv_b`` a head.  Rotary positions on ``q_pe`` and ``k_pe`` only,
  on the interleaved pairs (2i, 2i + 1) (*), at ``theta^(-2i/dr)``, theta
  the kind's own.  ``scores = (q_nope . k_nope + q_pe . k_pe) * (dn +
  dr)^-0.5``; softmax over the keys the layer's mask shows; ``out = ((probs
  v) * g) Wo`` with ``g = sigmoid(x Wg)``, one gate a head (*: head-wise
  gated attention, from the layer's normed input, after the values).
* a SLIDING layer's mask: query t sees s with ``0 <= t - s < window``.
* a FULL layer's mask is its indexer's selection (*: DeepSeek-V3.2-Exp's
  ``Indexer``): ``qI = c_q W_Iq`` (J heads of D), ``kI = LayerNorm(x W_Ik)``
  (D, eps 1e-6, weight and bias), rotary positions on the first dr
  channels of each, rotate-half (channel i with i + dr/2), ``w = x W_Iw *
  J^-0.5 * D^-0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  for ``s <= t``; query t sees the ``index_topk`` positions of largest
  ``I[t, .]``, ties to the lower position (all of them while ``t <
  index_topk``).
* routed MLP: scores ``sigmoid(u Wr)`` over ALL the layer's experts; the k
  largest of ``score + b`` are chosen, ``b`` a stored bias that enters the
  choice only, ties to the lower index; the weights are the chosen scores,
  divided by their sum where ``norm_topk_prob``, times
  ``routed_scaling_factor``; every expert a gated SiLU MLP; one shared
  expert of an expert's width is added for every token.  No token is
  dropped.

**The share.**  Where the layer's experts are divided over several chips
the reference is given what one chip holds: the experts from
``experts_first`` on, as many as the weights have, of a router that is
still as wide as the layer; what the absent experts would have added is
left out, and that partial result goes on.  The shared expert is in every
share.  Given all the experts (``experts_first`` 0) it is the whole layer.
The vocabulary's slice is simply a smaller vocabulary.

Departures from the published model, none in the mathematics of what is
built: weights are random, from the benchmark's seed; dropout 0; the
release's FP8 index keys and Hadamard rotation are its kernel's and are
not built; its vision and audio towers and its multi-token-prediction
layer are not in the catalog's ``config`` and are not built.

The program keeps gate and up of the routed experts in one matrix laid out
[gate | up]; the split is made where the matrix is used.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    dots3_note kinds) as the plain names used here: ``layers`` a list of
    ``{"attn": ..., "mlp": ...}``.  Arrays are shared, not copied."""
    p = params["params"]
    layers = []
    while f"h{len(layers)}" in p:
        block = p[f"h{len(layers)}"]
        a, m = block["attn"], block["mlp"]
        attn = {"n_attn": block["ln1"]["scale"],
                "w_q_a": a["q_a"]["kernel"], "n_q": a["q_a_norm"]["scale"],
                "w_q_b": a["q_b"]["kernel"], "w_kv_a": a["kv_a"]["kernel"],
                "n_kv": a["kv_a_norm"]["scale"], "w_kv_b": a["kv_b"],
                "w_g": a["gate"]["kernel"], "w_o": a["out"]["kernel"]}
        if "index_q" in a:
            attn.update(w_iq=a["index_q"]["kernel"],
                        w_ik=a["index_k"]["kernel"],
                        n_ik=a["index_k_ln"]["scale"],
                        b_ik=a["index_k_ln"]["bias"],
                        w_iw=a["index_w"]["kernel"])
        if "router" in m:
            shared = m["shared0"]
            mlp = {"w_r": m["router"]["kernel"], "b_r": m["router_bias"],
                   "w_gate_up": m["w_gate_up"], "w_down": m["w_down"],
                   "s_gate": shared["gate"]["kernel"],
                   "s_up": shared["up"]["kernel"],
                   "s_down": shared["down"]["kernel"]}
        else:
            mlp = {"d_gate": m["gate"]["kernel"], "d_up": m["up"]["kernel"],
                   "d_down": m["down"]["kernel"]}
        mlp["n_mlp"] = block["ln2"]["scale"]
        layers.append({"attn": attn, "mlp": mlp})
    return {"wte": p["wte"]["embedding"], "layers": layers,
            "wf": p["ln_f"]["scale"], "w_head": p["lm_head"]["kernel"]}


def _f32(tree):
    """Every weight the reference applies passes through here, where it is
    used."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def layer_norm(x, w, b, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _angles(positions, dr, theta):
    inv_freq = jnp.asarray([theta ** (-2.0 * i / dr) for i in range(dr // 2)],
                           jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv_freq


def rotate(x, theta, positions=None):
    """x (S, heads, dr) at ``positions`` (S,) (None: 0..S-1): the pairs
    (2i, 2i + 1) turned in place by ``position * theta^(-2i/dr)``."""
    s, _, dr = x.shape
    angles = _angles(jnp.arange(s) if positions is None else positions, dr,
                     theta)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def rotate_half(x, theta, positions=None):
    """x (S, heads, dr): the pairs (i, i + dr/2) turned by the same
    angles."""
    s, _, dr = x.shape
    if dr == 0:
        return x
    angles = _angles(jnp.arange(s) if positions is None else positions, dr,
                     theta)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def select(h, c_q, b, heads, dim, dr, topk, theta, block):
    """A full layer's selection of one sequence: ``h`` (S, hidden) the
    layer's normed input, ``c_q`` (S, rank) its scaled query latent.
    Returns ``(packed (S, S / 8) uint8, chosen (S, topk) int32, real (S,)
    int32)``: bit s of row t says whether query t sees s; ``chosen[t]``
    are the positions, the best first, of which the first ``real[t]`` are
    real.  Queries in blocks of ``block`` (a block's index queries are
    made where it is scored), the index heads one after another."""
    s = h.shape[0]
    k = layer_norm(h @ b["w_ik"], b["n_ik"], b["b_ik"])
    k = jnp.concatenate([rotate_half(k[:, None, :dr], theta)[:, 0],
                         k[:, dr:]], -1)
    w = (h @ b["w_iw"]) * (heads ** -0.5 * dim ** -0.5)
    k_pos = jnp.arange(s)[None, :]
    take = min(topk, s)

    def one_block(args):
        c_qb, wb, q_pos = args            # (T, rank), (T, J), (T,)
        qb = (c_qb @ b["w_iq"]).reshape(-1, heads, dim)
        qb = jnp.concatenate(
            [rotate_half(qb[..., :dr], theta, q_pos), qb[..., dr:]], -1)

        def one_head(total, args):
            qj, wj = args                 # (T, D), (T,)
            return total + wj[:, None] * jax.nn.relu(qj @ k.T), None

        scores, _ = jax.lax.scan(
            one_head, jnp.zeros((qb.shape[0], s), jnp.float32),
            (qb.swapaxes(0, 1), wb.T))
        scores = jnp.where(k_pos <= q_pos[:, None], scores, -jnp.inf)
        best, chosen = jax.lax.top_k(scores, take)
        # everything above the last one taken, and of what ties with it
        # the lowest positions
        kth = best[:, -1:]
        above, tied = scores > kth, scores == kth
        room = take - above.sum(-1, keepdims=True)
        seen = (above | (tied & (jnp.cumsum(tied, -1) <= room))) & \
            (scores > -jnp.inf)
        return (jnp.packbits(seen, axis=-1), chosen.astype(jnp.int32),
                (best > -jnp.inf).sum(-1).astype(jnp.int32))

    n = s // block
    packed, chosen, real = jax.lax.map(one_block, (
        c_q.reshape(n, block, -1), w.reshape(n, block, heads),
        jnp.arange(s).reshape(n, block)))
    return (packed.reshape(s, -1), chosen.reshape(s, take),
            real.reshape(s))


def attention(x, b, heads, dn, dr, dv, eps, theta, rescale, window, index,
              block, head_block):
    """``x + Attn(rms(x))`` of one sequence ``x`` (S, hidden), and a full
    layer's selection (``select``'s ``chosen`` and ``real``; two empty
    arrays of a sliding layer): the heads in groups of ``head_block`` one
    after another (each group's queries, keys and values made from the
    latents, its gates and its part of ``Wo`` applied and added), the
    queries in blocks of ``block`` against all keys under the layer's
    mask.  ``window`` > 0: a sliding layer.  ``index`` (J, D, topk): a full
    layer's indexer."""
    with jax.default_matmul_precision(_PRECISION):
        # (the three matrices that go a group of heads at a time are
        # upcast there)
        by_group = {k: b[k] for k in ("w_q_b", "w_kv_b", "w_o")}
        b = _f32({k: v for k, v in b.items() if k not in by_group})
        s, hidden = x.shape
        h = rms(x, b["n_attn"], eps)
        c_q = rms(h @ b["w_q_a"], b["n_q"], eps)
        kv_a = h @ b["w_kv_a"]
        rank = kv_a.shape[1] - dr
        c = rms(kv_a[:, :rank], b["n_kv"], eps)
        if rescale:
            c_q = c_q * math.sqrt(hidden / c_q.shape[1])
            c = c * math.sqrt(hidden / rank)
        k_pe = rotate(kv_a[:, None, rank:], theta)            # one key
        gates = jax.nn.sigmoid(h @ b["w_g"])                  # (S, heads)
        scale = (dn + dr) ** -0.5
        k_pos = jnp.arange(s)[None, :]
        n = s // block
        q_positions = jnp.arange(s).reshape(n, block)
        if window:
            packed = jnp.zeros((n, block, 0), jnp.uint8)
            chosen = real = jnp.zeros((0,), jnp.int32)
        else:
            packed, chosen, real = select(h, c_q, b, *index[:2], dr,
                                          index[2], theta, block)
            packed = packed.reshape(n, block, -1)
        hb = head_block

        def one_group(y, args):
            w_q, w_kv, w_o, g = _f32(args)
            q = (c_q @ w_q).reshape(s, hb, dn + dr)
            kv = (c @ w_kv).reshape(s, hb, dn + dv)
            q = jnp.concatenate(
                [q[..., :dn], rotate(q[..., dn:], theta)], -1)
            # every head's key: its own k_nope beside the shared k_pe
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (s, hb, dr))], -1)
            v = kv[..., dn:]

            def one_block(args):
                qb, q_pos, bits = args           # (T, hb, d), (T,), (T, .)
                scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
                if window:
                    seen = (k_pos <= q_pos[:, None]) & \
                        (q_pos[:, None] - k_pos < window)
                else:
                    seen = jnp.unpackbits(bits, axis=-1, count=s) != 0
                probs = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), -1)
                return jnp.einsum("hqk,khd->qhd", probs, v)

            out = jax.lax.map(one_block, (
                q.reshape(n, block, hb, dn + dr), q_positions, packed))
            out = out.reshape(s, hb, dv) * g[:, :, None]
            return y + out.reshape(s, hb * dv) @ w_o, None

        groups = heads // hb
        attn, _ = jax.lax.scan(one_group, jnp.zeros_like(x), (
            by_group["w_q_b"].reshape(
                -1, groups, hb * (dn + dr)).swapaxes(0, 1),
            by_group["w_kv_b"].reshape(
                -1, groups, hb * (dn + dv)).swapaxes(0, 1),
            by_group["w_o"].reshape(groups, hb * dv, -1),
            gates.reshape(s, groups, hb).swapaxes(0, 1)))
        return x + attn, chosen, real


def dense_mlp(x, b, eps, block):
    """``x + MLP(rms(x))`` of a layer with a dense MLP, ``block`` rows at
    a time (the 13,824 hidden values of 32,768 rows are 1.8 GB in
    float32, and there are three such arrays)."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)

        def one_block(xb):
            u = rms(xb, b["n_mlp"], eps)
            hidden = jax.nn.silu(u @ b["d_gate"]) * (u @ b["d_up"])
            return xb + hidden @ b["d_down"]

        return jax.lax.map(one_block, x.reshape(
            -1, block, x.shape[1])).reshape(x.shape)


def route(u, w_r, b_r, k, norm, scale):
    """(S, E) routing weights, zero but for each token's ``k`` picks, and
    the (S, k) picks, largest ``score + b`` first: k times the largest of
    what is left (no sort)."""
    scores = jax.nn.sigmoid(u @ w_r)
    rows = jnp.arange(scores.shape[0])
    left = scores + b_r
    chosen = []
    for _ in range(k):
        pick = jnp.argmax(left, axis=-1)
        chosen.append(pick)
        left = left.at[rows, pick].set(-jnp.inf)
    weights = jnp.where(jnp.isinf(left), scores, 0.0)
    if norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * scale, jnp.stack(chosen, -1)


def routed_mlp(x, b, eps, k, norm, scale, first, block=None):
    """``x + Routed(rms(x)) + Shared(rms(x))`` of one sequence with the
    routed experts the weights hold (the layer's experts ``first`` ..),
    and the (S, k) picks of its router among ALL the layer's experts.
    ``block`` rows at a time (None: all at once), expert after expert:
    each is applied to all the block's tokens and its result added with
    the tokens' routing weights for it."""
    with jax.default_matmul_precision(_PRECISION):
        held = b["w_down"].shape[0]
        rest = _f32({name: b[name] for name in (
            "n_mlp", "w_r", "b_r", "s_gate", "s_up", "s_down")})

        def one_block(xb):
            u = rms(xb, rest["n_mlp"], eps)
            weights, chosen = route(u, rest["w_r"], rest["b_r"], k, norm,
                                    scale)

            def one_expert(y, args):
                w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (T,)
                width = w_down.shape[0]
                gate_up = u @ w_gate_up
                out = (jax.nn.silu(gate_up[:, :width]) *
                       gate_up[:, width:]) @ w_down
                return y + out * w_e[:, None], None

            routed, _ = jax.lax.scan(
                one_expert, jnp.zeros_like(u),
                (b["w_gate_up"], b["w_down"],
                 weights[:, first:first + held].T))
            shared = (jax.nn.silu(u @ rest["s_gate"]) *
                      (u @ rest["s_up"])) @ rest["s_down"]
            return xb + routed + shared, chosen

        if block is None:
            return one_block(x)
        out, chosen = jax.lax.map(one_block,
                                  x.reshape(-1, block, x.shape[1]))
        return out.reshape(x.shape), chosen.reshape(x.shape[0], k)


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, _f32(wf), eps) @ _f32(w_head)


class Reference:
    """The reference bound to one configuration.  ``settings``: ``full``
    and ``sliding``, each ``{"heads", "dn", "dr", "dv", "theta"}``;
    ``layer_types`` (one a layer the weights have), ``window``,
    ``index_n_heads``, ``index_head_dim``, ``index_topk``, ``rescale``
    (``apply_mla_qkv_lora_rescale``), ``rms_norm_eps``,
    ``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``experts_first`` (the layer's expert that the weights' first is),
    ``query_block`` (how many queries meet all keys at once) and
    ``head_block`` (how many heads are expanded at once).  Each piece is
    jitted by itself and called layer after layer; weights are arguments,
    never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention,
                                  static_argnums=tuple(range(2, 13)))
        self._dense = jax.jit(dense_mlp, static_argnums=(2, 3))
        self._routed = jax.jit(routed_mlp,
                               static_argnums=(2, 3, 4, 5, 6, 7))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def _attend(self, x, b, kind: str):
        s, n = self.s, x.shape[0]
        w = s["sliding" if kind == "sliding_attention" else "full"]
        sliding = kind == "sliding_attention"
        return self._attention(
            x, b, w["heads"], w["dn"], w["dr"], w["dv"], s["rms_norm_eps"],
            float(w["theta"]), bool(s["rescale"]),
            s["window"] if sliding else 0,
            None if sliding else (s["index_n_heads"], s["index_head_dim"],
                                  s["index_topk"]),
            self._block_of(n, s["query_block"]),
            self._block_of(w["heads"], s["head_block"]))

    def layer(self, x, layer: dict, kind: str):
        """One layer of one sequence: (S, hidden) -> the same, every
        token's (S, k) picks (None: a dense MLP) and a full layer's
        selection ``(chosen (S, topk), real (S,))`` (None: sliding)."""
        s = self.s
        x, chosen, real = self._attend(x, layer["attn"], kind)
        selection = None if kind == "sliding_attention" else (chosen, real)
        if "w_r" not in layer["mlp"]:
            return self._dense(
                x, layer["mlp"], s["rms_norm_eps"],
                self._block_of(x.shape[0], 8 * s["query_block"])), None, \
                selection
        x, picks = self._routed(
            x, layer["mlp"], s["rms_norm_eps"], s["num_experts_per_tok"],
            bool(s["norm_topk_prob"]), float(s["routed_scaling_factor"]),
            s["experts_first"],
            self._block_of(x.shape[0], 8 * s["query_block"]))
        return x, picks, selection

    def hidden(self, w: dict, ids, rows=None):
        """(S,) ids -> the last hidden states (S, H), per routed layer
        every token's picks (S, k) and per full layer its selection; with
        ``rows`` = (first, count) the picks and selections of those
        positions alone (what is kept beside the stream)."""
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]

        def kept(a):
            return a if rows is None else \
                jax.lax.dynamic_slice_in_dim(a, rows[0], rows[1], axis=0)

        picks, selections = [], []
        for layer, kind in zip(w["layers"], self.s["layer_types"]):
            x, what, selection = self.layer(x, layer, kind)
            if what is not None:
                picks.append(kept(what))
            if selection is not None:
                selections.append(tuple(kept(a) for a in selection))
        return x, picks, selections

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _, _ = self.hidden(w, ids, rows)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])

    def logits_experts_selections(self, w: dict, ids, rows):
        """``logits(rows=...)``, the picks of those positions in every
        routed layer, (layers, count, k), and their selections in every
        full layer: ``(chosen (layers, count, topk), real (layers,
        count))``."""
        x, picks, selections = self.hidden(w, ids, rows)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        none = jnp.zeros((0, rows[1], 0), jnp.int32)
        return (self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"]),
                jnp.stack(picks) if picks else none,
                (jnp.stack([c for c, _ in selections]),
                 jnp.stack([n for _, n in selections]))
                if selections else (none, none[..., 0]))

    def lm_loss(self, w: dict, input_ids, labels):
        """Mean next-token cross-entropy of a batch (B, S), a sequence at
        a time."""
        total = 0.0
        for ids, want in zip(input_ids, labels):
            logp = jax.nn.log_softmax(self.logits(w, ids), axis=-1)
            total += -jnp.take_along_axis(
                logp, jnp.asarray(want)[:, None], axis=-1).mean()
        return total / len(input_ids)
