"""Time, on the chip, one expert layer (``model/moe.py`` ``DroplessExperts``)
that holds a share of its experts over a prefill chunk of 1,024 positions,
at the published widths of the six served cells that hold a share, over all
its rows at once and in windows (``moe.windowed_expert_sum``), and hold the
windows to the path over all rows there:

    chiprun -- python3 scripts/time_expert_chunk.py
    chiprun -- python3 scripts/time_expert_chunk.py --over 1 1.5 3 --sums

One JSON line a shape and a path: ``ms`` a call (the median of ``--repeat``
runs of ``--inner`` calls that end in one ``block_until_ready``), ``rows``
the call routes (tokens x k), ``local`` those that land on the held experts,
``window`` and ``passes`` of the call, ``max_diff`` from the path over all
rows (both bfloat16, so the order of the float32 sum shows in the last bit
of some outputs), and ``by_op_ms``: the device time of a call by operation,
from a profiler trace of ``--inner`` calls: an event's own time (a ``while``
less its body's) summed under the last name of its instruction's
``op_name``, ``kernel:`` before it inside the scope ``grouped_matmul`` and
``router:`` inside the router's; the largest ten.  The paths: ``all_rows``
(what every call ran before PR 60 and a decode tick still runs) and
``windows`` at ``moe.WINDOW_OVER_EXPECTED`` times the rows the held experts
can expect; ``--over``: windows at other multiples (1: half the calls take
a second pass).

``--sums``: the windows' combine alone, (window, hidden) float32 rows summed
into 1,024 tokens, two ways: ``one_hot_highest`` (``moe._sum_by_token``) and
``scatter_add``, each against a float64 sum on the host.  ``--tiny``
rehearses the control flow on a CPU at toy widths, the kernels interpreted,
no trace.
"""
import argparse
import collections
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from alpa_tpu.model import moe  # noqa: E402
from alpa_tpu.model.gpt_model import GPTConfig  # noqa: E402
from alpa_tpu.ops.grouped_matmul import MIN_ROW_TILE, SCOPE  # noqa: E402
from alpa_tpu.telemetry import device_time  # noqa: E402

CHUNK = 1024
# name: the configuration's own fields (chipbench/configs/<cell>.json and
# its ``published`` router width); every router a plain softmax top-k but
# DeepSeek-V2's, whose choice among 3 of 8 groups bunches the local rows
SHAPES = {
    "longcat-flash": dict(
        hidden_size=6144, moe_intermediate_size=2048, num_experts=512,
        num_zero_experts=256, experts_held=(0, 16), num_experts_per_tok=12),
    "glm-5": dict(
        hidden_size=6144, moe_intermediate_size=2048, num_experts=256,
        experts_held=(0, 16), num_experts_per_tok=8),
    "deepseek-v2": dict(
        hidden_size=5120, moe_intermediate_size=1536, num_experts=160,
        experts_held=(0, 20), num_experts_per_tok=6, n_group=8,
        topk_group=3),
    "dots3-note-prev": dict(
        hidden_size=5120, moe_intermediate_size=1536, num_experts=256,
        experts_held=(0, 32), num_experts_per_tok=8),
    "mimo-v2-flash": dict(
        hidden_size=4096, moe_intermediate_size=2048, num_experts=256,
        experts_held=(0, 16), num_experts_per_tok=8),
    "nemotron-3-nano": dict(
        hidden_size=2688, moe_intermediate_size=1856, num_experts=128,
        experts_held=(0, 8), num_experts_per_tok=6, expert_gated=False,
        activation="relu2"),
}
TINY = dict(hidden_size=128, moe_intermediate_size=128)


def config(name, tiny):
    fields = {"activation": "silu", **SHAPES[name]}
    if tiny:
        fields.update(TINY)
    return GPTConfig(mlp="experts", fused_gate_up=True, num_shared_experts=0,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **fields)


def window_at(cfg, over):
    """``moe.expert_window``'s window at ``over`` times the expected rows."""
    with mock.patch.object(moe, "WINDOW_OVER_EXPECTED", over), \
            mock.patch.object(moe, "WINDOW_WORTH_ROWS", 1):
        return moe.expert_window(cfg, CHUNK)


def timed(fn, *args, repeat, inner):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        tic = time.perf_counter()
        for _ in range(inner):
            last = fn(*args)
        jax.block_until_ready(last)
        times.append((time.perf_counter() - tic) / inner)
    return out, 1e3 * statistics.median(times)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*op_name=\"([^\"]*)\"")


def operation_names(hlo_text):
    """instruction -> the name its device time is summed under."""
    names = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        path = m.group(2)
        last = path.rsplit("/", 1)[-1]
        if SCOPE in path:
            last = "kernel:" + last
        elif "router" in path:
            last = "router:" + last
        names[m.group(1)] = last
    return names


def by_operation(fn, *args, inner):
    """Device milliseconds a call by operation (module docstring)."""
    names = operation_names(fn.lower(*args).compile().as_text())
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir):
            for _ in range(inner):
                last = fn(*args)
            jax.block_until_ready(last)
        path = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        _, chips = device_time.read_profile(path, "")
    if not chips:
        return {}
    ops, _runs = chips[min(chips)]
    ops = sorted((s, -e, name) for name, s, e in ops)
    own, open_ops = [], []
    for at, (s, minus_e, _name) in enumerate(ops):
        while open_ops and -ops[open_ops[-1]][1] <= s:
            open_ops.pop()
        if open_ops:
            own[open_ops[-1]] -= -minus_e - s
        own.append(-minus_e - s)
        open_ops.append(at)
    total = collections.Counter()
    for (_s, _e, name), ns in zip(ops, own):
        total[names.get(name, name)] += ns / 1e6 / inner
    return {name: round(ms, 4) for name, ms in total.most_common(10)}


def layer_paths(name, args):
    cfg = config(name, args.tiny)
    layer = moe.DroplessExperts(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, CHUNK, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    rule = moe.expert_window(cfg, CHUNK)
    paths = [("all_rows", None), ("windows", rule)] + [
        (f"windows_over_{over:g}", window_at(cfg, over)) for over in args.over]
    want = None
    for path, window in paths:
        with mock.patch.object(moe, "expert_window", lambda *_: window):
            fn = jax.jit(lambda p, x: layer.apply(p, x))
            (y, routing), ms = timed(fn, params, x, repeat=args.repeat,
                                     inner=args.inner)
            held = cfg.experts_held
            line = {"shape": name, "path": path, "ms": round(ms, 4),
                    "rows": CHUNK * cfg.num_experts_per_tok,
                    "local": int(routing["counts"][
                        held[0]:held[0] + held[1]].sum()),
                    "window": window,
                    "passes": int(routing.get("window_passes", 0))}
            if want is None:
                want = y.astype(jnp.float32)
            else:
                line["max_diff"] = float(
                    jnp.abs(y.astype(jnp.float32) - want).max())
                line["max_abs"] = float(jnp.abs(want).max())
            if not args.tiny:
                line["by_op_ms"] = by_operation(fn, params, x,
                                                inner=args.inner)
        print(json.dumps(line), flush=True)


def scatter_add(rows, token, tokens):
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(rows)


def sums(name, args):
    cfg = config(name, args.tiny)
    window, h = moe.expert_window(cfg, CHUNK), cfg.hidden_size
    rows = jax.random.normal(jax.random.PRNGKey(2), (window, h), jnp.float32)
    token = jax.random.randint(jax.random.PRNGKey(3), (window,), 0, CHUNK)
    want = np.zeros((CHUNK, h), np.float64)
    np.add.at(want, np.asarray(token), np.asarray(rows, np.float64))
    for way, fn in (("one_hot_highest", moe._sum_by_token),
                    ("scatter_add", scatter_add)):
        got, ms = timed(jax.jit(fn, static_argnums=2), rows, token, CHUNK,
                        repeat=args.repeat, inner=args.inner)
        print(json.dumps({
            "shape": name, "sum": way, "window": window, "hidden": h,
            "ms": round(ms, 4),
            "max_diff": float(np.abs(np.asarray(got, np.float64) -
                                     want).max())}), flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", nargs="+", default=list(SHAPES),
                        choices=list(SHAPES))
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--inner", type=int, default=8)
    parser.add_argument("--over", nargs="*", type=float, default=[],
                        help="windows at these multiples of the expected "
                        "rows besides the module's")
    parser.add_argument("--sums", action="store_true")
    parser.add_argument("--tiny", action="store_true",
                        help="a rehearsal of the control flow off the "
                        "chip: toy widths, the kernels interpreted")
    args = parser.parse_args()
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform,
                      "kind": device.device_kind,
                      "min_row_tile": MIN_ROW_TILE}), flush=True)
    for name in args.shapes:
        layer_paths(name, args)
        if args.sums:
            sums(name, args)


if __name__ == "__main__":
    main()
