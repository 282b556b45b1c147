"""Share of the positions the engine's prefill programs ran over that were
prompt tokens: the rise of ``alpa_serving_prefill_prompt_tokens_total`` over
the rise of ``alpa_serving_prefill_padded_tokens_total`` in the window.  The
rest is padding up to the prompt bucket."""
from chipbench import counters


def read(obs):
    padded = counters.delta(obs, "alpa_serving_prefill_padded_tokens_total")
    asked = counters.delta(obs, "alpa_serving_prefill_prompt_tokens_total")
    if not padded or asked is None:
        return None
    return 100.0 * asked / padded
