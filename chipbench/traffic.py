"""The one traffic generator.  A traffic mix is a data file
``chipbench/traffic/<mix>.json`` naming a ``kind`` below and its parameters;
a new mix is a new file, never new code.

Every seed gets the SAME sizes at the SAME times (drawn once from
``sizes_seed``, which lives in the file) with other token ids (and the
driver makes other weights): the seed must not change the work.  A window
holds some tens of requests, so a tail over them depends on the order in
which long and short requests happen to arrive far more than on the program;
with one order for every seed two runs differ by what the program and the
host did, which is what a check is after.  Another schedule is another mix:
a copy of the file with another ``sizes_seed``.

Kinds:

``lm_batches``   training batches: ``batch`` sequences of ``seq_len`` + 1
                 uniform token ids; the inputs are all but the last id and
                 the labels all but the first (next-token prediction).
``closed_loop``  ``clients`` callers, each sending its next request when the
                 last one finished.  Requests come from a pool of
                 ``pool_size`` (prompt length, output length) pairs.
``open_loop``    requests sent on a schedule whether or not earlier ones have
                 finished: ``rate_per_s`` x seconds arrivals of a Poisson
                 process conditioned on that count (exponential gaps from
                 ``sizes_seed``, scaled to fill the window).

A serving mix also says how the driver treats it: ``check_requests`` (how
many completed requests the reference checks), ``drain_s`` (how long after
the window a request in flight may still finish), ``trace_after_s`` and
``trace_seconds`` (where in the window the profiler runs in a traced run);
a training mix says ``trace_steps``.  None has a default in code.
"""
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix["kind"] not in KINDS:
        raise ValueError(f"traffic mix {name!r}: unknown kind "
                         f"{mix['kind']!r} (known: {sorted(KINDS)})")
    return mix


def _lognormal_lengths(rng, n, spec):
    """``n`` lengths from a lognormal with the given median and sigma,
    clipped to [min, max]."""
    raw = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], size=n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def request_pool(mix: dict, n: int) -> list:
    """The first ``n`` (prompt length, output length) pairs of the mix: the
    same for every seed."""
    rng = np.random.default_rng(mix["sizes_seed"])
    prompts = _lognormal_lengths(rng, n, mix["prompt_len"])
    outputs = _lognormal_lengths(rng, n, mix["output_len"])
    return list(zip(prompts.tolist(), outputs.tolist()))


def _prompt_ids(rng, length, vocab):
    # ids 0..3 are kept clear of: the OPT vocabulary puts its special
    # tokens there
    return rng.integers(4, vocab, size=length).tolist()


def lm_batches(mix: dict, seed: int, seq_len: int, vocab: int):
    """Endless iterator of {"input_ids", "labels"} numpy batches."""
    rng = np.random.default_rng(seed)
    while True:
        ids = rng.integers(0, vocab, size=(mix["batch"], seq_len + 1),
                           dtype=np.int32)
        yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def closed_loop(mix: dict, seed: int, vocab: int):
    """Endless iterator of requests {"prompt_ids", "max_new_tokens"}; the
    driver's clients draw from it under a lock.  The pool is walked in its
    own order, again and again."""
    rng = np.random.default_rng(seed)
    pool = request_pool(mix, mix["pool_size"])
    while True:
        for n_prompt, n_out in pool:
            yield {"prompt_ids": _prompt_ids(rng, n_prompt, vocab),
                   "max_new_tokens": n_out}


def open_loop(mix: dict, seed: int, vocab: int, seconds: float) -> list:
    """The whole schedule of one window: a list of requests
    {"due_s", "prompt_ids", "max_new_tokens"} sorted by ``due_s``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = np.random.default_rng(mix["sizes_seed"]).exponential(
        1.0, size=n + 1)
    rng = np.random.default_rng(seed)
    # conditioned on the count, the arrivals of a Poisson process are the
    # partial sums of exponential gaps scaled to the window
    starts = np.cumsum(gaps)[:n] / gaps.sum() * seconds
    requests = []
    for due, (n_prompt, n_out) in zip(starts, request_pool(mix, n)):
        requests.append({"due_s": float(due),
                         "prompt_ids": _prompt_ids(rng, n_prompt, vocab),
                         "max_new_tokens": n_out})
    return requests


KINDS = {"lm_batches": lm_batches, "closed_loop": closed_loop,
         "open_loop": open_loop}
