"""Train state and optimizer utilities.

Analog of ref ``alpa/model/model_util.py`` (TrainState, optimizers incl.
dynamic loss scale).  Built on flax/optax; the dynamic-scale logic follows
the standard flax DynamicScale pattern re-expressed so the scale update is
part of the train step (jit-compatible, no host sync).
"""
from typing import Any, Callable, Optional

import flax
import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from flax.training import train_state


class TrainState(train_state.TrainState):
    """TrainState with optional dynamic loss scaling state and master-copy
    support (ref model_util.py TrainState)."""
    dynamic_scale: Optional[Any] = None

    @classmethod
    def create_with_scale(cls, *, apply_fn, params, tx, use_dynamic_scale=False,
                          **kwargs):
        ds = DynamicScaleState.create() if use_dynamic_scale else None
        return cls.create(apply_fn=apply_fn, params=params, tx=tx,
                          dynamic_scale=ds, **kwargs)


class DynamicScaleState(struct.PyTreeNode):
    """Loss-scale state for mixed-precision training."""
    scale: jnp.ndarray
    growth_interval: int = struct.field(pytree_node=False, default=2000)
    growth_factor: float = struct.field(pytree_node=False, default=2.0)
    backoff_factor: float = struct.field(pytree_node=False, default=0.5)
    fine_count: jnp.ndarray = None

    @classmethod
    def create(cls, init_scale: float = 2.0**15):
        return cls(scale=jnp.float32(init_scale),
                   fine_count=jnp.zeros((), jnp.int32))

    def update(self, grads_finite: jnp.ndarray) -> "DynamicScaleState":
        grow = (self.fine_count + 1) >= self.growth_interval
        new_scale = jnp.where(
            grads_finite,
            jnp.where(grow, self.scale * self.growth_factor, self.scale),
            jnp.maximum(self.scale * self.backoff_factor, 1.0))
        new_count = jnp.where(grads_finite & ~grow, self.fine_count + 1,
                              jnp.zeros((), jnp.int32))
        return self.replace(scale=new_scale, fine_count=new_count)


def all_finite(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.bool_(True)
    return jnp.all(
        jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]))


def create_adamw(learning_rate=1e-3, weight_decay=0.01, b1=0.9, b2=0.999,
                 grad_clip: Optional[float] = 1.0):
    chain = []
    if grad_clip:
        chain.append(optax.clip_by_global_norm(grad_clip))
    chain.append(optax.adamw(learning_rate, b1=b1, b2=b2,
                             weight_decay=weight_decay))
    return optax.chain(*chain)


# the scope the loss is traced under, from the logits (or, chunked, the
# hidden states) to the scalar: a capture reads device time by it
# (telemetry/device_time.py)
LOSS_SCOPE = "loss"


def gpt_lm_loss(apply_fn, params, batch, chunked=False):
    """LM loss for a GPT-family model with tied embeddings: dense fp32
    CE, or the fused/chunked lm-head + CE that never materializes the
    full logits tensor (shared by the benchmark's drivers, the smoke
    and the tools so the loss formulation cannot drift between them)."""
    if chunked:
        hidden = apply_fn(params, batch["input_ids"], return_hidden=True)
        emb = params["params"]["wte"]["embedding"]
        return chunked_cross_entropy_loss(hidden, emb, batch["labels"])
    logits = apply_fn(params, batch["input_ids"])
    with jax.named_scope(LOSS_SCOPE):
        return cross_entropy_loss(logits.astype(jnp.float32),
                                  batch["labels"])


def routed_lm_loss(apply_fn, params, batch, aux_loss_coef: float):
    """LM loss of a model with routed-expert layers (``GPTModel`` whose
    ``mlp`` names "experts"): mean token cross-entropy plus
    ``aux_loss_coef`` times the routers' load-balancing term.  Returns
    ``(loss, routing)`` for ``value_and_grad(..., has_aux=True)``."""
    logits, routing = apply_fn(params, batch["input_ids"])
    with jax.named_scope(LOSS_SCOPE):
        loss = cross_entropy_loss(logits.astype(jnp.float32),
                                  batch["labels"])
        return loss + aux_loss_coef * routing["load_balance_loss"], routing


def cross_entropy_loss(logits, labels, label_mask=None, vocab_size=None):
    """Mean token cross-entropy with optional mask."""
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    if label_mask is not None:
        return (loss * label_mask).sum() / jnp.maximum(label_mask.sum(), 1)
    return loss.mean()


@jax.named_scope(LOSS_SCOPE)
def chunked_cross_entropy_loss(hidden, embedding, labels, chunk_size=512):
    """Fused lm-head + mean cross-entropy without materializing the full
    logits tensor.

    ``hidden``: (B, S, H) final hidden states; ``embedding``: (V, H) tied
    lm-head weights; ``labels``: (B, S) int.  Token rows are processed in
    ``chunk_size`` chunks under ``jax.checkpoint``: the lm-head matmul
    runs in the embedding's dtype (bf16 on the MXU path, matching the
    unchunked ``tok_emb.attend``), only lse/loss math is fp32.  Peak
    logits memory is O(chunk * V) instead of O(B * S * V) — for GPT's
    51200 vocab at bs8/seq1024, ~50 MB bf16 per chunk vs a 1.6 GB fp32
    buffer (+ its saved backward residuals).
    """
    b, s, h = hidden.shape
    x = hidden.reshape(-1, h).astype(embedding.dtype)
    y = labels.reshape(-1)
    n = x.shape[0]
    n_chunks = max(1, -(-n // chunk_size))
    pad = n_chunks * chunk_size - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, h), x.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    x = x.reshape(n_chunks, chunk_size, h)
    y = y.reshape(n_chunks, chunk_size)

    @jax.checkpoint
    def one_chunk(args):
        xc, yc = args
        with jax.named_scope("lm_head"):    # the head's product, not loss
            logits = xc @ embedding.T
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return lse - gold

    losses = jax.lax.map(one_chunk, (x, y)).reshape(-1)
    if pad:
        mask = jnp.arange(losses.shape[0]) < n
        return (losses * mask).sum() / n
    return losses.mean()
