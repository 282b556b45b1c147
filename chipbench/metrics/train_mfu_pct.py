"""Model FLOP/s utilisation: the operations one token's forward and
backward pass need (``arithmetic.decoder_train_flops_per_token``,
recomputed operations not counted) times tokens per second, over the chips'
bf16 peak.  An end-to-end utilisation, not a kernel's roofline share."""
from chipbench import stats


def read(obs):
    if obs["peaks"] is None:
        return None
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * obs["train_flops_per_token"] * \
        stats.train_tokens_per_s(obs) / peak
